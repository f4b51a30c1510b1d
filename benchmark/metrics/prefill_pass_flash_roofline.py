"""`prefill_flash_fwd_roofline` under the end-to-end metric a prefill
pass moves where every running stream waits for it (`itl_p98_ms`): the
flash forward kernel's least time for the prompts prefilled while the
trace ran (grouped K/V heads read once, window layers counting only the
pairs a window leaves visible: the family's `flash_fwd_work`) over its
time in the trace. The reading is the accepted reader's own, from the
file beside this one."""
import os

from benchmark import manifest

_read = manifest.module_at(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "prefill_flash_fwd_roofline.py")).read


def read(ctx):
    return _read(ctx)
