"""Whole training step's share of the chips' bf16 peak: valid samples of
the traced window times the forward and backward operations of one
sample at its size (the configuration's ``bench/cost`` module), over
window x chips x peak."""
from bench.lib import program


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    cost = program.cost(run.conf)
    flops = sum(n * cost.train_flops_sequence(run.conf, size)
                for size, n in run.out["samples_by_size"].items())
    return 100.0 * flops / (run.out["window_s"] * run.chips
                            * run.peak["bf16_flops"])
