"""The program's own spans in a traced window.

The program marks its layer boundaries with ``jax.profiler``
annotations named ``repro.*`` (the serving tick and its prefill, decode
and readback; the training phase, step, data wait and stage; ...), with
its counters as keyword arguments, which the profiler keeps as the
event's stats.  They share the profiler's clock with the device planes,
so the device busy time inside a span can be read off the trace.

A program without these spans leaves the list empty, and every reader
built on it then reports nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import NamedTuple

from bench.lib.trace import DEVICE_PLANE, _union
from bench.run import TRACE_DIR

PREFIX = "repro."

_loaded: dict = {}


class Span(NamedTuple):
    name: str
    t0: float          # ns, the profiler's clock
    t1: float
    stats: dict        # the span's counters


def load(log_dir: str = TRACE_DIR) -> list:
    """Every host event named ``repro.*`` in the newest ``.xplane.pb``
    under ``log_dir``, with its stats, in order of start; empty where there
    is no trace or no such event.  A file is read once."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    key = (files[-1], os.path.getmtime(files[-1]))
    if key not in _loaded:
        from jax.profiler import ProfileData
        out = []
        for plane in ProfileData.from_file(files[-1]).planes:
            if DEVICE_PLANE.match(plane.name):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        out.append(Span(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        dict(e.stats)))
        out.sort(key=lambda s: s.t0)
        _loaded.clear()
        _loaded[key] = out
    return _loaded[key]


def named(run, name: str) -> list:
    """The spans called ``name`` that lie wholly inside ``run``'s traced
    window, in order of start; empty without a trace."""
    if run.trace is None:
        return []
    w0, w1 = run.trace.w0, run.trace.w1
    return [s for s in load() if s.name == name and w0 <= s.t0
            and s.t1 <= w1]


def busy_s(devices: dict, intervals) -> list:
    """Device busy time (the union of op intervals, averaged over the
    devices) inside each ``(t0, t1)`` of ``intervals``, in seconds.
    ``devices`` is ``{plane: [(op, start, end), ...]}``, as
    ``trace.Reduced.devices`` holds it."""
    intervals = list(intervals)
    out = [0.0] * len(intervals)
    for ops in devices.values():
        merged = _union([(a, b) for _, a, b in ops])
        starts = [s for s, _ in merged]
        ends = [e for _, e in merged]
        cum = [0.0]
        for s, e in merged:
            cum.append(cum[-1] + (e - s))
        for k, (t0, t1) in enumerate(intervals):
            i = bisect.bisect_right(ends, t0)    # first op run ending after t0
            j = bisect.bisect_left(starts, t1)   # op runs starting before t1
            if i >= j:
                continue
            out[k] += (cum[j] - cum[i] - max(0.0, t0 - starts[i])
                       - max(0.0, ends[j - 1] - t1))
    n = max(1, len(devices))
    return [x * 1e-9 / n for x in out]
