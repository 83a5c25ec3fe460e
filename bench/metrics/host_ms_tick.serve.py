"""Host time of a serving tick: for each ``repro.serve.tick`` span wholly
inside the traced window, its wall time less the device busy time inside
it (what the scheduler and engine do on the host between and around the
device steps), averaged over those ticks, in ms."""
from bench.lib import spans


def read(run):
    ticks = spans.named(run, "repro.serve.tick")
    if not ticks:
        return None
    busy = spans.busy_s(run.trace.devices, [(s.t0, s.t1) for s in ticks])
    host = [(s.t1 - s.t0) * 1e-9 - b for s, b in zip(ticks, busy)]
    return 1e3 * sum(host) / len(host)
