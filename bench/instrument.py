"""The one hook the benchmark sets on the program (traced runs only): a
wrapper around each ``ServeEngine``'s public ``step()``.

It times every step that did work on the host's monotonic clock and
writes a ``bench.step`` annotation into the profiler's trace, so that an
idle gap on the device can be laid against the step loop.  A step did
work where slots were occupied after it, or after the step before it
(the step that finished the last request).
"""
from __future__ import annotations

import threading
import time
from typing import List


class StepTimer:
    def __init__(self):
        self.steps: List[tuple] = []      # (t0, t1) of steps that did work
        self._lock = threading.Lock()

    def install(self, serve) -> None:
        from jax.profiler import TraceAnnotation

        step = serve.step
        last = [0]

        def timed_step(*args, **kwargs):
            t0 = time.monotonic()
            with TraceAnnotation("bench.step"):
                n = step(*args, **kwargs)
            t1 = time.monotonic()
            if n or last[0]:
                with self._lock:
                    self.steps.append((t0, t1))
            last[0] = n
            return n

        serve.step = timed_step

    def steps_in(self, t0: float, t1: float) -> List[float]:
        """Durations (s) of the working steps that began in [t0, t1)."""
        with self._lock:
            return [b - a for a, b in self.steps if t0 <= a < t1]
