"""Device time of one prefill chunk: the device busy time inside each of
the traced window's ``repro.serve.prefill`` spans (the scheduler's call
of the prefill hook, one ``(1, chunk)`` step from dispatch to the read of
its token), averaged over those spans, in ms."""
from bench.lib import spans


def read(run):
    pre = spans.named(run, "repro.serve.prefill")
    if not pre:
        return None
    busy = spans.busy_s(run.trace.devices, [(s.t0, s.t1) for s in pre])
    return 1e3 * sum(busy) / len(busy)
