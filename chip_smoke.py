#!/usr/bin/env python3
"""Bring-up smoke: the trainer and the paged server on a TPU at published
phi3-mini widths.

    python chip_smoke.py             one chip: kernels, train, serve
    python chip_smoke.py --chips 4   the dual-batch schedule on a 4-chip
                                     data mesh, against the same schedule
                                     on one chip of that host

Model: ``phi3-mini-3.8b`` at its published widths (d_model 3072, 32 heads
of 96, kv 32, d_ff 8192, vocab 32064, bf16), depth cut from 32 layers to 4
(the stack is dense, so one layer is a whole period), random weights from
``--seed``.

One chip runs three phases in one process:

  kernels  one ``dbl_apply_flat2d`` call against ``kernels.ref`` and one
           ``flash_decode_paged`` call against ``paged_decode_ref``;
  train    ``launch.train`` with ``--scheme hybrid --optimizer sgd``: two
           CPL phases (seq 256 then 512, B_L 16), fused ``dbl_merge`` scan
           path, next phase compiled in the background;
  serve    ``launch.serve``'s continuous ``ServeEngine`` (paged bf16 KV)
           answers 8 requests of 128-512 prompt tokens, 32 new tokens each.

Every check that fails is printed and the script exits 1.  Without a TPU
it exits 2 before any work.  The last line of stdout is one JSON object;
it is printed only when every check passed.  Times printed here are a
smoke's wall clock, not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "phi3-mini-3.8b"
LAYERS = 4
# the CLI flags the train phase runs with (launch.train's own parser)
TRAIN_FLAGS = ["--scheme", "hybrid", "--optimizer", "sgd", "--seq", "512",
               "--global-batch", "16", "--steps", "16", "--lr", "0.05"]
# serve: 4 slots of 36 16-token pages hold a 512-token prompt padded to
# whole 128-token prefill chunks plus 32 new tokens
SERVE_FLAGS = ["--engine", "continuous", "--slots", "4", "--page-len", "16",
               "--pages-per-slot", "36", "--prefill-chunk", "128"]
N_REQUESTS, PROMPT_LENS, NEW_TOKENS = 8, (128, 512), 32

# tolerances of the on-chip comparisons
TOL_APPLY = 2e-6       # |kernel - ref|: f32 w - lr*g, four f32 ulps at |w| < 8
TOL_DECODE = 1.6e-2    # |kernel - ref|: bf16 outputs, two bf16 ulps at 1.0
TOL_MESH_FIRST = 1e-3  # relative loss gap, first step (same params, data)
TOL_MESH = 1e-2        # relative loss gap, any step (bf16 grads reduced in
                       # another order drift the params apart)


class Checks:
    """Collects named pass/fail results; every failure is printed."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"[{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def model_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), n_layers=LAYERS)


# ------------------------------ kernels ----------------------------------
def kernel_phase(check: Checks, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.flat import BLOCK_ROWS, LANE
    from repro.kernels import flash_decode as fd
    from repro.kernels.dbl_merge import dbl_apply_flat2d
    from repro.kernels.ref import dbl_merge_ref

    key = jax.random.PRNGKey(seed)
    kp, kg, kq, kk, kv, kt = jax.random.split(key, 6)
    rows, lr = 8 * BLOCK_ROWS, 0.05            # gridded: 8 row blocks
    p2 = jax.random.normal(kp, (rows, LANE), jnp.float32)
    g2 = jax.random.normal(kg, (rows, LANE), jnp.float32)
    ref = dbl_merge_ref(p2, g2, jnp.zeros_like(g2), factor=0.0, lr=lr)
    out = jax.jit(lambda p, g: dbl_apply_flat2d(p, g, lr=lr))(p2, g2)
    d = float(jnp.max(jnp.abs(out - ref)))
    print(f"dbl_apply_flat2d f32 ({rows}, {LANE}): max|kernel - ref| = {d!r}")
    check(d <= TOL_APPLY, f"dbl_apply_flat2d within {TOL_APPLY} of ref")

    cfg = model_config()
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ns, page_len, pp, n_pages = 4, 16, 32, 160
    bf = jnp.bfloat16
    q = jax.random.normal(kq, (ns, h, 1, hd), jnp.float32).astype(bf)
    kpg = jax.random.normal(kk, (n_pages, page_len, kvh, hd)).astype(bf)
    vpg = jax.random.normal(kv, (n_pages, page_len, kvh, hd)).astype(bf)
    table = jax.random.permutation(kt, n_pages)[:ns * pp].reshape(ns, pp)
    lengths = jnp.asarray([0, 37, 300, pp * page_len - 1], jnp.int32)
    for window in (0, 64):
        kern = jax.jit(lambda *a, w=window: fd.flash_decode_paged(
            *a, window=w, impl="pallas"))
        text = kern.lower(q, kpg, vpg, table, lengths).as_text()
        out = kern(q, kpg, vpg, table, lengths)
        with jax.default_matmul_precision("highest"):
            ref = fd.paged_decode_ref(q, kpg, vpg, table, lengths,
                                      window=window)
        d = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                  - ref.astype(jnp.float32))))
        print(f"flash_decode_paged bf16 H={h} KV={kvh} hd={hd} "
              f"window={window}: max|kernel - ref| = {d!r}")
        check("tpu_custom_call" in text,
              f"flash_decode_paged window={window} lowers to a Pallas kernel")
        check(bool(np.isfinite(np.asarray(out, np.float32)).all())
              and d <= TOL_DECODE,
              f"flash_decode_paged window={window} within {TOL_DECODE} of ref")


# ------------------------------ train ------------------------------------
def _losses(res) -> list:
    return [float(r["loss"]) for r in res.history]


def train_phase(check: Checks, seed: int):
    import numpy as np

    from repro.launch import train

    cfg = model_config()
    args = train.parse_args(TRAIN_FLAGS + ["--seed", str(seed)])
    print(f"train: {cfg.name} x{cfg.n_layers} layers, "
          f"{cfg.param_count() / 1e6:.0f}M params, flags {TRAIN_FLAGS}",
          flush=True)
    t0 = time.perf_counter()
    res, engine = train.train(cfg, args, log_every=1)
    print(f"train wall {time.perf_counter() - t0!r} s (compiles included)")
    for rec in res.phases:
        print("phase", json.dumps(rec))
    for rec in engine.stall_log:
        print("boundary", json.dumps(rec))

    losses = _losses(res)
    check(len(losses) == args.steps and bool(np.isfinite(losses).all()),
          f"{args.steps} finite losses")
    check(len(losses) > 1 and losses[-1] < losses[0],
          f"loss falls ({losses[0]} -> {losses[-1] if losses else None})")
    for e in engine.warm_exceptions:
        print(f"warm compile failed: {e!r}")
    check(engine.warm_errors == 0, "no warm compile failed")
    kinds = {r["kind"] for r in engine.stall_log}
    check(kinds == {"scan"}, f"every phase ran the fused scan ({kinds})")
    check(any(r["warm"] for r in engine.stall_log[1:]),
          "the second phase got its executable from the background compile")
    exes = engine.phase_executables
    check(len(exes) >= 2
          and all("tpu_custom_call" in c.as_text() for c in exes),
          f"all {len(exes)} phase executables hold the Pallas update kernel")
    return cfg, res.params


# ------------------------------ serve ------------------------------------
def serve_phase(check: Checks, cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.serve import Request
    from repro.serve import paged as pg

    args = serve.parse_args(SERVE_FLAGS + ["--seed", str(seed)])
    engine = serve.make_engine(cfg, params, args)
    spec = engine.spec
    check(jnp.dtype(spec.store_dtype) == jnp.bfloat16, "KV pages are bf16")

    # the decode step this platform builds must stream pages via the kernel
    m = spec.n_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    struct = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    text = jax.jit(pg.make_token_fn(cfg, spec, "paged")).lower(
        struct(params), struct(pg.init_paged_cache(cfg, spec)),
        i32(m, spec.pages_per_slot), i32(m), i32(m), i32(m, 1), i32(m),
        i32(m)).as_text()
    check("tpu_custom_call" in text, "the decode step calls flash_decode_paged")

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, tokens=rng.integers(
                0, 256, int(rng.integers(PROMPT_LENS[0],
                                         PROMPT_LENS[1] + 1))),
                    max_new=NEW_TOKENS)
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    recs = engine.serve(reqs)
    wall = time.perf_counter() - t0
    print(f"serve: {len(recs)} requests, prompts "
          f"{[r.prompt_len for r in recs]}, wall {wall!r} s (compiles "
          f"included), calls {engine.stats['prefill_calls']} prefill / "
          f"{engine.stats['decode_calls']} decode, compiled "
          f"{engine.compile_log}")
    for r in recs:
        print(f"  request {r.rid}: {len(r.tokens)} tokens {r.tokens[:8]}...")
    check(len(recs) == N_REQUESTS
          and all(len(r.tokens) == NEW_TOKENS for r in recs),
          f"all {N_REQUESTS} requests got {NEW_TOKENS} tokens")
    check(all(0 <= t < cfg.vocab_size for r in recs for t in r.tokens),
          "every token is in the vocabulary")


# ------------------------------ 4 chips ----------------------------------
def mesh_phase(check: Checks, seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.launch import train
    from repro.launch.mesh import make_host_mesh

    cfg = model_config()
    args = train.parse_args(TRAIN_FLAGS + ["--seed", str(seed)])
    runs = {}
    for n in (4, 1):
        mesh = make_host_mesh(n, model=1)
        print(f"mesh run on {n} chip(s): {dict(mesh.shape)}", flush=True)
        t0 = time.perf_counter()
        res, engine = train.train(cfg, args, log_every=1, mesh=mesh)
        print(f"mesh run on {n} chip(s): wall {time.perf_counter() - t0!r} s "
              "(compiles included)")
        runs[n] = (_losses(res), res.params, engine)

    l4, params4, engine4 = runs[4]
    l1 = runs[1][0]
    gaps = [abs(a - b) / max(abs(b), 1e-9) for a, b in zip(l4, l1)]
    print(f"losses 4 chips {l4}")
    print(f"losses 1 chip  {l1}")
    print(f"relative loss gap per step {gaps}")
    check(len(l4) == len(l1) == args.steps
          and bool(np.isfinite(l4 + l1).all()), "finite losses on both")
    check(bool(gaps) and gaps[0] <= TOL_MESH_FIRST,
          f"first-step losses within {TOL_MESH_FIRST} (relative)")
    check(bool(gaps) and max(gaps) <= TOL_MESH,
          f"all losses within {TOL_MESH} (relative)")

    leaves = jax.tree_util.tree_leaves(params4)
    per_dev: dict = {}
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    total = sum(leaf.nbytes for leaf in leaves)
    print(f"param bytes {total} in all; per device {per_dev}")
    check(len(per_dev) == 4 and max(per_dev.values()) < total,
          "params are split over 4 devices")
    bsh = engine4.placement["batch"]["tokens"]
    print(f"batch tokens sharding {bsh}")
    check(len(bsh.device_set) == 4 and bsh.spec[:1] == P("data"),
          "batches are split over 4 devices on the data axis")


# ------------------------------ main -------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {dev.device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    check = Checks()
    if args.chips == 4:
        mesh_phase(check, args.seed)
    else:
        kernel_phase(check, args.seed)
        cfg, params = train_phase(check, args.seed)
        serve_phase(check, cfg, params, args.seed)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
