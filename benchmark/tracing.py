"""Taking one short profiler trace inside a window.

The harness holds a host span named `trace_reduce.WINDOW` open while it
traces; the reduction clips the device's events to it. Python's tracer
is off (it slows every host thread); the host's TraceMe spans and the
benchmark's own `TraceAnnotation`s around its calls into the program are
what idle gaps are put down to. The trace is written under `TMPDIR`,
read once the window has closed, and removed.
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Callable, Optional

from benchmark import trace_reduce


class Tracer:
    def __init__(self, keep_copy_in: Optional[str] = None):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        #: set by a builder who wants to look at the raw trace
        self.keep_copy_in = keep_copy_in

    def record(self, seconds: float,
               snapshot: Optional[Callable[[], dict]] = None) -> dict:
        """Trace `seconds` from now. `snapshot` is read at both ends,
        inside the traced span."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                t0 = time.perf_counter()
                snap0 = snapshot() if snapshot else None
                time.sleep(seconds)
                snap1 = snapshot() if snapshot else None
                t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        return {"host": (t0, t1), "snap0": snap0, "snap1": snap1}

    def reduce(self) -> Optional[dict]:
        """Read the trace that `record` wrote; None where there is
        none."""
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            return None
        if self.keep_copy_in:
            os.makedirs(self.keep_copy_in, exist_ok=True)
            shutil.copy(found[-1], self.keep_copy_in)
        return trace_reduce.reduce(trace_reduce.load_xplane(found[-1]))

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
