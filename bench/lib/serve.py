"""Driver of serving mixes (``"kind": "serve"``): the program's
continuous-batching ``ServeEngine`` on its contiguous KV cache
(``backend="contig"``: one slot row of ``slot_tokens`` per client).

The mix is a closed loop of ``slots`` clients: each sends its next request
the moment its answer completes.  The requests are one long backlog
(``backlog`` requests, sizes in an order of the mix's own) that the
engine admits as slots free, so the window sees the loop as it runs, with
no drain.

Set-up makes the weights, builds the engine, serves one short request
that calls both step shapes (prefill of one chunk on one slot row, decode
over every slot), then starts the backlog and fills every slot: the
window opens once each of the first ``slots`` requests has its first
token.  It closes at the first tick that ends ``--seconds`` later; the
engine's loop is left there (the requests still in flight are not
measured).
"""
from __future__ import annotations

import gc

import numpy as np

from bench.lib import program, trace, weights
from bench.lib.traffic import backlog


class WindowClosed(Exception):
    """Raised from the engine's hooks to leave its loop when the window
    closes."""


def page_spec(cfg, traffic: dict):
    """The KV cache of a mix: ``slots`` contiguous rows of ``slot_tokens``
    tokens (the program sizes rows in pages of ``page_len``)."""
    from repro.models.layers import dtype_of
    from repro.serve import PageSpec
    return PageSpec(page_len=traffic["page_len"],
                    pages_per_slot=traffic["slot_tokens"]
                    // traffic["page_len"],
                    n_slots=traffic["slots"],
                    store_dtype=dtype_of(cfg.compute_dtype))


def expected_shapes(traffic: dict) -> set:
    """The step shapes the mix calls: decode over every slot, prefill of
    one chunk on one gathered slot row."""
    return {("step", traffic["slots"], 1), ("rows", 1, traffic["prefill_chunk"])}


class Work:
    """What the window computed, counted at the engine's hooks: tokens
    prefilled and decoded, and their forward operations at their
    positions."""

    def __init__(self, conf: dict):
        self.cost = program.cost(conf)
        self.conf = conf
        self.two_mm = 2 * self.cost.matmul_params(conf)
        self.attn1 = self.cost.attn_flops_per_token(conf, 1)
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.forward_flops = 0

    def prefill(self, pos: int, n: int) -> None:
        self.prefill_tokens += n
        keys = n * pos + n * (n + 1) // 2
        self.forward_flops += n * self.two_mm + self.attn1 * keys

    def decode(self, lengths, counts) -> None:
        for length, c in zip(lengths, counts):
            self.decode_tokens += c
            self.forward_flops += c * self.two_mm \
                + self.attn1 * (c * length + c * (c + 1) // 2)


class Loop:
    """The engine's prefill and decode hooks, wrapped: benchmark spans,
    the work of the window, when the window opens (every slot has had its
    first token) and when it closes."""

    def __init__(self, cell, engine):
        self.cell, self.engine = cell, engine
        self.work = Work(cell.conf)
        self.firsts = 0
        self.ctx = self.w = None
        self.t_open = self.t_close = None
        prefill, decode = engine.prefill, engine.decode

        def on_prefill(slot, req, chunk, pos, last):
            with trace.span("bench.prefill"):
                prefill(slot, req, chunk, pos, last)
            if self.w is not None:
                self.work.prefill(pos, len(chunk))
            self.firsts += bool(last)
            self._tick()

        def on_decode(slots):
            lengths = [int(engine._lengths[s]) for s in slots]
            with trace.span("bench.decode"):
                counts = decode(slots)
            if self.w is not None:
                self.work.decode(lengths, [counts.get(s, 1) for s in slots])
            self._tick()
            return counts

        engine.prefill, engine.decode = on_prefill, on_decode

    def _tick(self) -> None:
        if self.w is None:
            if self.firsts >= self.cell.traffic["slots"]:
                self.cell.setup_done()
                self.ctx = self.cell.window()
                self.w = self.ctx.__enter__()
                self.t_open = self.w.t0
        elif self.t_close is None and self.w.elapsed() >= self.cell.seconds:
            self.close()
            raise WindowClosed

    def close(self) -> None:
        if self.ctx is not None and self.t_close is None:
            self.ctx.__exit__(None, None, None)
            self.t_close = self.t_open + self.w.seconds


def run(cell) -> dict:
    import jax
    from repro.serve import Request, ServeEngine

    conf, traffic, seed = cell.conf, cell.traffic, cell.seed
    seed32 = seed % (1 << 31)
    fam = program.adapter(conf)
    cfg = fam.model_config(conf)
    params = fam.params_from(weights.make(conf, seed, conf["torch_dtype"]))
    engine = ServeEngine(cfg, params, spec=page_spec(cfg, traffic),
                         backend="contig",
                         prefill_chunk=traffic["prefill_chunk"],
                         sample_seed=seed32)
    vocab = conf["vocab_size"]
    reqs = [Request(rid=i, tokens=p, max_new=o)
            for i, (p, o) in enumerate(backlog(traffic, seed, 0, vocab))]

    # -- warm-up: one short request calls both step shapes ---------------
    engine.serve([Request(rid=len(reqs), tokens=reqs[0].tokens[:16],
                          max_new=2)])
    missing = expected_shapes(traffic) - set(engine.compile_log)
    shapes_before = len(engine.compile_log)

    # -- the backlog: set-up fills the slots, then the window ------------
    loop = Loop(cell, engine)
    try:
        engine.serve(reqs)
    except WindowClosed:
        pass
    loop.close()                      # a backlog that ran dry closes it
    if loop.w is None:
        raise RuntimeError("the backlog ended before every slot was filled")
    cell.read_memory()
    shapes_in_window = len(engine.compile_log) - shapes_before

    t0, t1 = loop.t_open, loop.t_close
    ttft, itl, out_tokens, done = [], [], 0, []
    for req in reqs:
        rec = engine.records.get(req.rid)
        if rec is None:
            continue
        times = np.asarray(rec.token_times)
        inside = (times >= t0) & (times <= t1)
        out_tokens += int(inside.sum())
        if rec.t_first is not None and t0 <= rec.t_first <= t1:
            ttft.append(rec.t_first - rec.t_admit)
        both = inside[1:] & inside[:-1]
        itl.extend(np.diff(times)[both].tolist())
        if rec.t_done is not None and t0 <= rec.t_done <= t1:
            done.append((req, rec))
    failed = sum(1 for req, rec in done
                 if len(rec.tokens) != req.max_new
                 or not all(0 <= t < vocab for t in rec.tokens))

    # sample for the output check: the longest request finished in the
    # window, then others drawn from the seed, up to min_tokens served
    rng = np.random.default_rng([seed, 0xC4EC])
    order = sorted(range(len(done)), key=lambda i: -(
        len(done[i][0].tokens) + len(done[i][1].tokens)))
    pick = order[:1] + [int(i) for i in rng.permutation(order[1:])]
    sample, served = [], 0
    for i in pick:
        if served >= traffic["check"]["min_tokens"] \
                or len(sample) >= traffic["check"]["max_requests"]:
            break
        req, rec = done[i]
        sample.append((np.asarray(req.tokens, np.int32),
                       np.asarray(rec.tokens, np.int32)))
        served += len(rec.tokens)
    del engine, params, loop.engine
    gc.collect()                      # the hooks close a cycle on the engine
    jax.clear_caches()
    return {"window_s": t1 - t0, "attempted": len(done), "failed": failed,
            "output_tokens": out_tokens, "ttft_s": ttft, "itl_s": itl,
            "work": loop.work, "missing_shapes": sorted(missing),
            "compiles_in_window": shapes_in_window, "sample": sample}


def gaps(conf, seed, sample, mm_name="f32", length=None):
    """Per served token, how far the reference's logit of that token lies
    below the reference's best at its position.  With ``mm_name`` "fp8"
    (the control: weights held and matmuls run in float8) the token read
    is the one the float8 reference puts first at the same position of
    the same prompt and served tokens."""
    import jax
    import jax.numpy as jnp

    ref = program.reference(conf)
    out = []
    with jax.default_matmul_precision("highest"):
        w = weights.as_f32(weights.make(conf, seed, conf["torch_dtype"]))
        f32 = jax.jit(lambda w, t: ref.logits(w, t, conf))
        low = jax.jit(lambda w, t: ref.logits(
            ref.lower(w), t, conf, ref.MATMULS[mm_name]))
        for prompt, served in sample:
            seq = np.concatenate([prompt, served[:-1]])
            n, p = len(seq), len(prompt)
            # causal: padding after the last position changes nothing
            # before it, and one length keeps one compiled program
            seq = jnp.asarray(np.pad(seq, (0, (length or n) - n))[None])
            logits = f32(w, seq)[0, p - 1:n]
            if mm_name != "f32":
                toks = jnp.argmax(low(w, seq)[0, p - 1:n], -1)
            else:
                toks = jnp.asarray(served)
            best = jnp.max(logits, -1)
            got = jnp.take_along_axis(logits, toks[:, None], -1)[:, 0]
            out.append(np.asarray(best - got, np.float64))
    return np.concatenate(out) if out else np.asarray([np.inf])


def check(cell, out) -> tuple:
    g = gaps(cell.conf, cell.seed, out["sample"],
             length=cell.traffic["slot_tokens"])
    nums = {"logit_gap": float(g.max()),
            "missing_shapes": float(len(out["missing_shapes"]))}
    notes = {"served_tokens_compared": int(g.size),
             "requests_compared": len(out["sample"]),
             "longest": int(max((len(p) + len(s) for p, s in out["sample"]),
                                default=0))}
    return nums, notes


def end_to_end(out) -> dict:
    from bench.lib.traffic import percentile
    return {
        "serve_tokens_per_s": (out["output_tokens"] / out["window_s"],
                               "tokens/s"),
        "itl_p95_ms": (percentile(out["itl_s"], 95) * 1e3, "ms"),
    }
