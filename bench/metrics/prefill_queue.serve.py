"""Prefill queue: slots in state ``prefill`` once a tick's admission is
done (the ``prefilling`` counter of ``repro.serve.tick``), averaged over
the ticks wholly inside the traced window.  One chunk a tick goes to the
lowest of them; the rest wait."""
from bench.lib import spans


def read(run):
    counts = [s.stats["prefilling"]
              for s in spans.named(run, "repro.serve.tick")
              if "prefilling" in s.stats]
    if not counts:
        return None
    return sum(counts) / len(counts)
