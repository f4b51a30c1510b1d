"""`gdn_update_roofline` for a cell outside that metric's pinned list:
the one-token state update's least time (each live slot's state read
once and written once, at this family's state of 96 x 192 a head) over
its device time in the traced span. The reading is the accepted
reader's own, from the file beside this one."""
import os

from benchmark import manifest

_read = manifest.module_at(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "gdn_update_roofline.py")).read


def read(ctx):
    return _read(ctx)
