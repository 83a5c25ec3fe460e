"""Stall at a CPL phase boundary: the wall time from the end of the last
``repro.train.step`` of one phase to the start of the first step of the
next (the flat store unraveled and raveled again, the next executable
acquired, its first chunk staged; at a cycle's end also the return from
one ``repro.api.run`` and the start of the next), averaged over the
consecutive phase pairs inside the traced window, in ms.  A step starts
another phase where its ``phase`` counter changes or its step number
does not follow on from the step before."""
from bench.lib import spans


def read(run):
    steps = spans.named(run, "repro.train.step")
    gaps = [b.t0 - a.t1 for a, b in zip(steps, steps[1:])
            if b.stats.get("phase") != a.stats.get("phase")
            or b.stats.get("step_num") != a.stats.get("step_num", 0)
            + a.stats.get("steps", 1)]
    if not gaps:
        return None
    return sum(gaps) * 1e-6 / len(gaps)
