"""Data wait a training step: the summed time the engine blocked on the
data plane (``repro.data.wait`` spans, the wait for a staged chunk or the
synchronous stage) inside the traced window, over the steps of the
window's ``repro.train.step`` spans (their ``steps`` counters), in ms."""
from bench.lib import spans


def read(run):
    steps = sum(s.stats.get("steps", 1)
                for s in spans.named(run, "repro.train.step"))
    if not steps:
        return None
    wait = sum(s.t1 - s.t0 for s in spans.named(run, "repro.data.wait"))
    return wait * 1e-6 / steps
