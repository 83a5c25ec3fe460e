"""The one generator of every traffic mix: reads a mix's parameters (a
file under ``bench/traffic``) and the seed, and makes the inputs.

Sizes are drawn at stratified quantiles, in an order fixed by the mix
alone, so every seed serves the same sizes in the same order and the
seed changes only the tokens: on the chip, a different order of one set
of sizes moved a closed loop's throughput by 5% from seed to seed.
"""
from __future__ import annotations

import math

import numpy as np


def _quantile(dist: dict, u):
    if dist["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(x) for x in np.atleast_1d(u)])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(np.int64)


def stratified_sizes(dist: dict, n: int, rng) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 0.5) / n, in an order from rng."""
    sizes = _quantile(dist, (np.arange(n) + 0.5) / n)
    return sizes[rng.permutation(n)]


class ZipfTokens:
    """Token ids with Zipf-distributed frequencies over the vocabulary
    (rank r has weight r^-s; ranks map to ids by a permutation drawn from
    the seed).  Row ``i`` of cycle ``c`` is drawn from ``(seed, c, i)``
    alone, so rows are reproducible in any order.  Speaks the program's
    data-source contract: ``len``, ``batch_at(indices, seq)``,
    ``struct(batch, seq)``.  While ``record`` is set, the first batch it
    returns at each sequence length is kept for the output check."""

    def __init__(self, seed: int, vocab: int, exponent: float,
                 examples: int):
        self.seed, self.vocab, self.examples = int(seed), vocab, examples
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
        self.cdf = np.cumsum(w) / w.sum()
        self.ids = np.random.default_rng(
            [self.seed, 0x7A1F]).permutation(vocab).astype(np.int32)
        self.cycle = 0
        self.record = False
        self.recorded: dict = {}

    def rows(self, indices, length: int, stream: int = 1) -> np.ndarray:
        out = np.empty((len(indices), length), np.int32)
        for j, i in enumerate(np.asarray(indices)):
            rng = np.random.default_rng([self.seed, stream, self.cycle,
                                         int(i)])
            ranks = np.searchsorted(self.cdf, rng.random(length))
            out[j] = self.ids[np.minimum(ranks, self.vocab - 1)]
        return out

    def __len__(self):
        return self.examples

    def batch_at(self, indices, input_size: int) -> dict:
        toks = self.rows(indices, input_size + 1)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if self.record and input_size not in self.recorded:
            self.recorded[input_size] = {k: v.copy()
                                         for k, v in batch.items()}
        return batch

    def struct(self, batch: int, input_size: int) -> dict:
        return {"tokens": ((batch, input_size), np.int32),
                "labels": ((batch, input_size), np.int32)}


def backlog(traffic: dict, seed: int, index: int, vocab: int):
    """Backlog ``index`` of a serving mix: ``[(prompt ids, max_new)]``,
    prompt and output lengths stratified in an order of the mix's own,
    tokens Zipf over the vocab from the seed."""
    rng = np.random.default_rng([0xBAC, index])
    n = traffic["backlog"]
    plens = stratified_sizes(traffic["prompt"], n, rng)
    olens = stratified_sizes(traffic["output"], n, rng)
    src = ZipfTokens(seed, vocab, traffic.get("zipf", 1.1), 1 << 30)
    src.cycle = index
    out = []
    for i, (p, o) in enumerate(zip(plens, olens)):
        out.append((src.rows([i], int(p), stream=2)[0], int(o)))
    return out


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(np.percentile(v, q))
