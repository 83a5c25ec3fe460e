"""One run of one cell: what the drivers get, and the clocks they report
through (set-up, the measured window, peak memory)."""
from __future__ import annotations

import contextlib
import os
import shutil
import time

from bench.lib import trace

# process start, as close to interpreter start as the harness can see
T0 = time.perf_counter()


class Window:
    def __init__(self):
        self.t0 = time.perf_counter()
        self.seconds = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class Cell:
    def __init__(self, *, name, conf, traffic, seed, seconds, tracing,
                 trace_dir, chips):
        self.name, self.conf, self.traffic = name, conf, traffic
        self.seed, self.seconds, self.chips = seed, seconds, chips
        self.tracing, self.trace_dir = tracing, trace_dir
        self.setup_s = None
        self.memory_peak_bytes = None
        self.compile_events = 0        # counted by bench.run's listener
        self.compiles_in_window = None

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T0

    @contextlib.contextmanager
    def window(self):
        """The measured window; with tracing, a profiler trace of it."""
        ctx = contextlib.nullcontext()
        if self.tracing:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            ctx = trace.capture(self.trace_dir)
        with ctx:
            before = self.compile_events
            w = Window()
            with trace.span(trace.WINDOW):
                yield w
            w.seconds = w.elapsed()
            self.compiles_in_window = self.compile_events - before

    def read_memory(self) -> None:
        """Peak bytes in use on the fullest chip, read once the window has
        closed and before the reference runs."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        self.memory_peak_bytes = int(max(peaks))
