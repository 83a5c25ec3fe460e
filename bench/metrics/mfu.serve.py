"""Whole serving step's share of the chips' bf16 peak: forward operations
of every prefilled prompt token and every decoded token at its position
(read from the scheduler's log, ``bench/cost``), over window x chips x
peak."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.out["work"].forward_flops / (
        run.out["window_s"] * run.chips * run.peak["bf16_flops"])
