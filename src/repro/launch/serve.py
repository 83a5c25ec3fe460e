"""Serving driver: batched prefill + decode, static or continuous batching.

Static batching (``generate``) runs one fixed batch to completion.  Its
prefill is ONE chunked decode call for attention archs — the whole prompt
enters the KV cache in a single compiled dispatch — and falls back to
token-by-token stepping only for recurrent state (mamba2 / rwkv6), which
has no cache to chunk into.

Continuous batching (``--engine continuous``) hands the request stream to
``repro.serve.ServeEngine``: paged KV cache, admission the moment pages
free up, chunked prefill interleaved with in-flight decode.  Attention
archs only.  ``--spec-k`` turns on draft-free speculative decode (n-gram
prompt lookup, greedy only), ``--temperature``/``--top-k`` switch to
in-jit sampled decode, and ``--prefix-share`` enables copy-on-write
prefix sharing across admitted prompts.

Usage (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --reduced \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b \
      --engine continuous --requests 16
  PYTHONPATH=src python -m repro.launch.serve --engine continuous \
      --workload repetitive --spec-k 3
  PYTHONPATH=src python -m repro.launch.serve --engine continuous \
      --workload shared-prefix --prefix-share
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import ARCH_IDS, get_config, reduced
from repro.configs.base import MAMBA2, RWKV6
from repro.launch.steps import make_decode_step


def chunkable(cfg) -> bool:
    """Whole-prompt (T=P) prefill works iff every layer carries a KV cache
    — recurrent segments must consume tokens one step at a time."""
    if cfg.encoder_layers:
        return False
    from repro.models.transformer import layout
    return all(s.kind not in (MAMBA2, RWKV6) for s in layout(cfg))


def generate(cfg, params, prompts, *, gen: int, max_seq: int, greedy=True,
             rng=None, stepped_prefill: bool = False):
    """prompts: (B, P) int32. Returns (B, P+gen) tokens.

    Attention archs prefill in ONE chunked decode call (O(1) compiled
    dispatches); recurrent archs — or ``stepped_prefill=True`` — step
    token-by-token as before.
    """
    b, p = prompts.shape
    cache = models.init_cache(cfg, b, max_seq)
    decode = jax.jit(make_decode_step(cfg),
                     donate_argnums=(1,))

    toks = prompts
    if stepped_prefill or not chunkable(cfg):
        # prefill by stepping (the only correct path for recurrent state)
        logits = None
        for t in range(p):
            logits, cache = decode(params, cache, toks[:, t:t + 1],
                                   jnp.int32(t))
    else:
        logits, cache = decode(params, cache, toks, jnp.int32(0))
    out = [toks]
    for t in range(p, p + gen):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None] \
            if greedy else jax.random.categorical(
                jax.random.fold_in(rng, t), logits)[:, None].astype(jnp.int32)
        out.append(nxt)
        if t < p + gen - 1:
            logits, cache = decode(params, cache, nxt, jnp.int32(t))
    return jnp.concatenate(out, axis=1)


def make_engine(cfg, params, args):
    """The ``--engine continuous`` ServeEngine for a ``parse_args``
    namespace: paged KV pool whose pages are stored in the model's compute
    dtype (bf16 pages for a bf16 model)."""
    from repro.models.layers import dtype_of
    from repro.serve import PageSpec, ServeEngine
    spec = PageSpec(page_len=args.page_len, pages_per_slot=args.pages_per_slot,
                    n_slots=args.slots,
                    store_dtype=dtype_of(cfg.compute_dtype))
    return ServeEngine(cfg, params, spec=spec,
                       prefill_chunk=args.prefill_chunk, spec_k=args.spec_k,
                       temperature=args.temperature, top_k=args.top_k,
                       sample_seed=args.seed, prefix_share=args.prefix_share)


def _serve_continuous(cfg, params, args):
    from repro.serve import (repetitive_workload, shared_prefix_workload,
                             synthetic_workload)
    engine = make_engine(cfg, params, args)
    spec = engine.spec
    if args.workload == "repetitive":
        reqs = repetitive_workload(args.seed, args.requests,
                                   vocab=cfg.vocab_size,
                                   prompt_len=args.prompt_len,
                                   gen=(args.gen, args.gen + 8))
    elif args.workload == "shared-prefix":
        reqs = shared_prefix_workload(args.seed, args.requests,
                                      vocab=cfg.vocab_size,
                                      gen=(args.gen, args.gen + 8))
    else:
        reqs = synthetic_workload(args.seed, args.requests,
                                  vocab=cfg.vocab_size,
                                  prompt_lens=(4, args.prompt_len),
                                  gen_long=(args.gen, args.gen + 8))
    t0 = time.time()
    recs = engine.serve(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in recs)
    ttft = np.mean([r.ttft_s for r in recs])
    print(f"arch={cfg.name} continuous requests={len(recs)} "
          f"slots={spec.n_slots} pages={spec.n_pages}x{spec.page_len}: "
          f"{n_tok / dt:.1f} tok/s  mean TTFT {ttft * 1e3:.1f}ms "
          f"({engine.stats['decode_calls']} decode / "
          f"{engine.stats['prefill_calls']} prefill calls)")
    if args.spec_k:
        print(f"  speculative k={args.spec_k}: accept rate "
              f"{engine.accept_rate:.3f} "
              f"({engine.stats['draft_accepted']}/"
              f"{engine.stats['draft_proposed']} drafts, "
              f"{engine.stats['spec_dispatches']} verify dispatches)")
    if args.prefix_share:
        print(f"  prefix sharing: skipped "
              f"{engine.prefill_skip_frac:.1%} of prompt tokens "
              f"({engine.stats['prefill_skipped_tokens']}/"
              f"{engine.stats['prompt_tokens']}, "
              f"{engine.stats['cow_copies']} COW page copies)")
    return recs


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="gemma3-4b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stepped-prefill", action="store_true",
                    help="force token-by-token prefill on attention archs")
    # continuous-engine knobs
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-len", type=int, default=16)
    ap.add_argument("--pages-per-slot", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--workload",
                    choices=("synthetic", "repetitive", "shared-prefix"),
                    default="synthetic")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: n-gram draft length "
                         "(0 = one-token decode; greedy only)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy; "
                         "incompatible with --spec-k)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampled decode (0 = full)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="COW prefix sharing across admitted prompts")
    return ap.parse_args(argv)


def run(argv=None):
    args = parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if cfg.encoder_layers:
        raise SystemExit("use examples/serve_encdec.py for enc-dec archs")
    rng = jax.random.PRNGKey(args.seed)
    params = models.init_params(cfg, rng)

    if args.engine == "continuous":
        return _serve_continuous(cfg, params, args)

    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    toks = generate(cfg, params, prompts, gen=args.gen,
                    max_seq=args.prompt_len + args.gen, rng=rng,
                    stepped_prefill=args.stepped_prefill)
    dt = time.time() - t0
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}: {args.batch * args.gen / dt:.1f} tok/s "
          f"({dt:.1f}s)")
    print("sample:", np.asarray(toks[0])[:24])
    return toks


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
