"""Profiler trace: capture, and reduction to device busy time, op time,
kernel time and idle gaps.

A trace is reduced from plain event lists, ``{plane: {line: [(name,
start_ns, duration_ns), ...]}}``, so the reduction can be checked on a
recorded trace without a chip.  Device planes are ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per operation that ran.  Host
spans of the benchmark itself are named ``bench.*``; the window is the
span ``bench.window``.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"

_tracing = False


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace while a trace is captured; no
    cost otherwise."""
    if not _tracing:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace everything inside the block into ``log_dir``."""
    global _tracing
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    _tracing = True
    try:
        yield
    finally:
        _tracing = False
        jax.profiler.stop_trace()


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as plain event lists
    (device op lines and the benchmark's host spans only)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    out: dict = {}
    for plane in data.planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            if dev and line.name == OPS_LINE:
                lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
            elif not dev:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events if e.name.startswith("bench.")]
                if evs:
                    lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


def save(events: dict, path: str) -> None:
    """Write event lists as JSON (gzip-compressed for a ``.gz`` path)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(events, f)


def read(path: str) -> dict:
    """Event lists written by ``save``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def op_key(name: str) -> str:
    """``%fusion.12 = ...`` -> ``%fusion.12`` (plus ``[target]`` for a
    custom call)."""
    key = name.split(" = ", 1)[0][:80]
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{key} [{m.group(1)}]" if m else key


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Reduced:
    """Busy time, op time and idle gaps of each device inside the window."""

    def __init__(self, events: dict):
        spans = [(n, s, s + d) for plane, lines in events.items()
                 if not DEVICE_PLANE.match(plane)
                 for evs in lines.values() for n, s, d in evs]
        wins = [(s, e) for n, s, e in spans if n == WINDOW]
        if not wins:
            raise ValueError(f"the trace holds no {WINDOW!r} span")
        self.w0, self.w1 = wins[0]
        self.spans = [x for x in spans if x[0] != WINDOW]
        self.window_s = (self.w1 - self.w0) * 1e-9
        self.devices = {}
        for plane, lines in sorted(events.items()):
            if not DEVICE_PLANE.match(plane):
                continue
            ops = []
            for n, s, d in lines.get(OPS_LINE, []):
                a, b = max(s, self.w0), min(s + d, self.w1)
                if b > a:
                    ops.append((n, a, b))
            if ops:
                self.devices[plane] = ops

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    def busy_s(self, plane: str) -> float:
        return sum(e - s for s, e in _union(
            [(a, b) for _, a, b in self.devices[plane]])) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(p) for p in self.devices) / len(self.devices)

    def op_seconds(self, pattern=None) -> float:
        """Summed device time of the ops whose name matches ``pattern``,
        averaged over devices."""
        if not self.devices:
            return 0.0
        rx = re.compile(pattern) if pattern else None
        tot = sum(b - a for ops in self.devices.values()
                  for n, a, b in ops if rx is None or rx.search(n))
        return tot * 1e-9 / len(self.devices)

    def op_count(self, pattern: str) -> int:
        """Ops whose name matches ``pattern`` on the first device."""
        rx = re.compile(pattern)
        ops = next(iter(self.devices.values()), [])
        return sum(1 for n, _, _ in ops if rx.search(n))

    def top_ops(self, k: int = 10):
        """[[name, seconds]] of the k ops that took the most device time
        (device 0).  The trace names an op by its whole HLO text; the key
        is the instruction name, with the custom-call target if any."""
        ops = next(iter(self.devices.values()), [])
        tot: dict = {}
        for n, a, b in ops:
            key = op_key(n)
            tot[key] = tot.get(key, 0) + (b - a)
        best = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return [[n, t * 1e-9] for n, t in best]

    def idle_gaps(self, k: int = 10):
        """[[what the host was doing, seconds]] of the k longest idle gaps
        of device 0: the innermost benchmark span over the gap's middle."""
        ops = next(iter(self.devices.values()), [])
        busy = _union([(a, b) for _, a, b in ops])
        gaps, t = [], self.w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.w1:
            gaps.append((t, self.w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            inner = [x for x in self.spans if x[1] <= mid <= x[2]]
            name = min(inner, key=lambda x: x[2] - x[1])[0] if inner \
                else "outside any span"
            out.append([name, (e - s) * 1e-9])
        return out
