"""`prefill_mfu` under the end-to-end metric a prefill pass moves where
a pass prefills one row and every running stream waits for it
(`itl_p98_ms`): the operations of the prompts prefilled while the trace
ran over the prefill programs' device time x peak. The reading is the
accepted reader's own, from the file beside this one."""
import os

from benchmark import manifest

_read = manifest.module_at(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "prefill_mfu.py")).read


def read(ctx):
    return _read(ctx)
