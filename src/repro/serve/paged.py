"""Block-paged KV cache + batched serve-step builders.

The training side got its flat store in PR 3/7: compute the buffer layout
once, keep the hot loop on a single padded buffer.  This module is the
serving analogue for KV state.  Instead of one contiguous
``(slot, max_seq, ...)`` cache per sequence — which pins worst-case memory
per slot and forces whole-cache reallocation to admit a new request —
each layer owns a fixed pool of ``(n_pages, page_len, kv_heads, head_dim)``
blocks, and a per-slot *page table* maps logical token positions to pool
pages.  Admission is then a page-budget check, eviction returns pages, and
the pool's token axis rides the same sublane-tile rule as the flat store
(``flat.sublane_for`` / ``flat.padded_len``): a ``page_len`` that is a
legal f32/bf16 store tile keeps every page a clean lane/sublane block for
either ``store_dtype``.

Two step builders share ONE attention-math path (`_slot_attention`):

  * ``backend="paged"``   — gather KV through the page table (XLA), or
    stream pages with ``kernels.flash_decode.flash_decode_paged`` on TPU
    (the page table rides scalar prefetch, so no gather materializes).
  * ``backend="contig"``  — classic per-slot contiguous cache, stored
    lane-dense (``init_contig_cache``) and read directly.

Because the two backends differ only in how bytes are addressed — the
values entering the attention math are identical, and masked positions
contribute an exact ``0.0`` (scores hit ``NEG_INF``, the shifted ``exp``
underflows to zero, and ``0 x finite == 0``) — paged and contiguous
logits are *bit-identical* in f32 when the logical extents match
(``contig`` token axis == ``pages_per_slot * page_len``).  That parity is
a HARD CI gate (`benchmarks/serve_throughput.py`).

Steps are batched over ``m`` slot rows and ``T`` chunk tokens; one
builder serves decode ``(m, 1)``, chunked prefill ``(1, C)`` AND the
speculative verify chunk ``(m, k+1)`` — the engine's compile cache is
keyed on ``(m, T)`` only.  ``active`` carries a per-row *valid token
count* (0 = dead row): rows whose chunks are shorter than ``T`` write
exactly their first ``active[i]`` positions to the KV cache, so a
padded verify batch never scribbles junk past a slot's real draft
length, and a rejected draft needs no cleanup — the junk positions the
verify step *did* write (the accepted-prefix overshoot) are always
rewritten by a later chunk before any query can attend to them
(queries only see positions their own chunk or an earlier one wrote).

``make_token_fn`` closes the host-sync gap: greedy argmax — or
temperature/top-k sampling with counter-based per-request RNG streams
keyed ``(seed, request, step)``, the DataPlane idiom — runs INSIDE the
jitted step, so only an int32 token row crosses to host each tick
instead of an ``(m, V)`` f32 logits block plus a separate argmax
dispatch per call.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, ModelConfig
from repro.core import flat
from repro.kernels import flash_decode as fd
from repro.models.attention import NEG_INF, attn_project_qkv, gqa_expand
from repro.models.layers import dtype_of, rms_norm, swiglu
from repro.models.moe import moe_ffn
from repro.models.transformer import Segment, layout


def attention_segments(cfg: ModelConfig) -> Tuple[Segment, ...]:
    """The layer layout, validated to be servable by the paged engine.

    Paged KV needs KV-cache semantics per layer; recurrent segments
    (mamba2 / rwkv6) carry dense states and the weight-tied shared block
    would need its own one-layer pool — both stay on the static
    ``launch.serve.generate`` path.
    """
    segs = layout(cfg)
    bad = sorted({s.kind for s in segs if s.kind != ATTN})
    if bad:
        raise ValueError(
            f"paged serving supports attention-only stacks; found segments "
            f"{bad} — use launch.serve.generate (static batch) for this arch")
    return segs


@dataclass(frozen=True)
class PageSpec:
    """Geometry of the paged KV pool (shared by every attention layer).

    ``page_len`` must be a multiple of the store dtype's sublane tile
    (``flat.sublane_for``): 8 tokens for f32, 16 for bf16 — the same rule
    that pads the flat parameter store's rows.  ``n_pages`` defaults to
    ``n_slots * pages_per_slot`` (enough for every slot to be full); an
    oversubscribed pool (smaller ``n_pages``) makes admission genuinely
    contend for pages.
    """
    page_len: int = 16
    pages_per_slot: int = 8
    n_slots: int = 4
    n_pages: int = 0                      # 0 -> n_slots * pages_per_slot
    store_dtype: Any = jnp.float32

    def __post_init__(self):
        sub = flat.sublane_for(self.store_dtype)
        if self.page_len % sub or self.page_len <= 0:
            raise ValueError(
                f"page_len={self.page_len} is not a {jnp.dtype(self.store_dtype).name} "
                f"store tile; use a multiple of {sub} "
                f"(flat.padded_len({self.page_len}) = "
                f"{flat.padded_len(self.page_len, self.store_dtype)})")
        if self.n_pages == 0:
            object.__setattr__(self, "n_pages",
                               self.n_slots * self.pages_per_slot)

    @property
    def slot_tokens(self) -> int:
        """Max logical tokens one slot can address (its table's reach)."""
        return self.pages_per_slot * self.page_len

    def pages_needed(self, prompt_len: int, max_new: int,
                     prefill_chunk: int) -> int:
        """Pages a request must hold before admission.

        Prefill runs in fixed ``prefill_chunk`` ticks with the final chunk
        padded (junk KV beyond the real length is masked, then overwritten
        by decode), so the budget covers the padded prefill extent plus
        the decode tokens — over-allocating at most one page rather than
        ever scattering into a page the slot doesn't own.
        """
        c = max(1, int(prefill_chunk))
        padded = -(-max(1, int(prompt_len)) // c) * c
        return -(-(padded + max(0, int(max_new))) // self.page_len)

    def pool_bytes(self, cfg: ModelConfig) -> int:
        """Total KV pool bytes across layers (what bf16 pages halve)."""
        n_layers = sum(s.count for s in attention_segments(cfg))
        per = (self.n_pages * self.page_len * cfg.n_kv_heads * cfg.head_dim
               * jnp.dtype(self.store_dtype).itemsize)
        return 2 * n_layers * per


def init_paged_cache(cfg: ModelConfig, spec: PageSpec) -> List[dict]:
    """Per-segment page pools: ``(count, n_pages, page_len, kv, hd)``."""
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (spec.n_pages, spec.page_len, kv, hd)
    return [{"k": jnp.zeros((s.count,) + shape, spec.store_dtype),
             "v": jnp.zeros((s.count,) + shape, spec.store_dtype)}
            for s in attention_segments(cfg)]


def init_contig_cache(cfg: ModelConfig, spec: PageSpec) -> List[dict]:
    """Contiguous baseline caches with the SAME logical extent as the
    paged pool (``slot_tokens`` per slot) — the bit-parity contract.

    Stored lane-dense, ``(count, n_slots, slot_tokens, kv * hd)``: a head
    dim that is no lane multiple (phi3's 96) would otherwise lead the TPU
    compiler to store the donated cache transposed and convert all of it
    in and out of every step.  The step views a row as ``(kv, hd)`` only
    where attention reads it."""
    shape = (spec.n_slots, spec.slot_tokens, cfg.n_kv_heads * cfg.head_dim)
    return [{"k": jnp.zeros((s.count,) + shape, spec.store_dtype),
             "v": jnp.zeros((s.count,) + shape, spec.store_dtype)}
            for s in attention_segments(cfg)]


# ------------------------- shared attention math ---------------------------
def _slot_attention(q, k, v, positions, window):
    """Masked attention over per-row KV state — the ONE math path both
    backends feed.  q: (m, T, H, hd); k/v: (m, S, KV, hd); positions:
    (m, T) per-row absolute positions of the chunk tokens; window: traced
    per-layer scalar (0 = global)."""
    m, t, h, hd = q.shape
    s, kvh = k.shape[1], k.shape[2]
    n_rep = h // kvh
    k = gqa_expand(k, n_rep).astype(jnp.float32)
    v = gqa_expand(v, n_rep).astype(jnp.float32)
    qf = q.astype(jnp.float32) * hd ** -0.5
    scores = jnp.einsum("bqhd,bshd->bhqs", qf, k)
    idx = jnp.arange(s)
    valid = idx[None, None, :] <= positions[:, :, None]          # (m, T, S)
    valid = jnp.logical_and(
        valid, jnp.where(window > 0,
                         idx[None, None, :] > positions[:, :, None] - window,
                         True))
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, v).astype(q.dtype)


# ------------------------- step builder ------------------------------------
def make_serve_step(cfg: ModelConfig, spec: PageSpec,
                    backend: str = "paged", *, gather_rows: bool = False):
    """Build the batched serve step for one backend.

    Returns ``step(params, caches, rows, lengths, active, tokens) ->
    (logits (m, T, V), new caches)`` where

      rows     paged:  (m, pages_per_slot) int32 page-table rows
               contig: (m,) int32 slot ids owning each batch row
      lengths  (m,) int32 — tokens already in each row's cache; the chunk
               occupies positions lengths[i] .. lengths[i] + T - 1
      active   (m,) int32 — per-row count of VALID chunk tokens: row i
               writes KV only for chunk positions j < active[i] (0 rows
               compute junk but never write).  Full-chunk rows pass T.
      tokens   (m, T) int32

    ``gather_rows`` (contig only): gather cache rows by slot id — needed
    when m < n_slots (single-row prefill).  With ``gather_rows=False``
    the cache is read whole and ``rows`` MUST be ``arange(n_slots)``;
    that keeps the contiguous decode baseline gather-free (honest perf
    for the paged-vs-contig CI gate).

    One jit-specialization serves any (m, T): decode is (n_slots, 1),
    chunked prefill is (1, C), and paged slot-bucketing just changes m.
    """
    segs = attention_segments(cfg)
    if backend not in ("paged", "contig"):
        raise ValueError(f"backend must be 'paged' or 'contig': {backend!r}")
    paged = backend == "paged"
    page_len, pp = spec.page_len, spec.pages_per_slot
    n_pages, slot_tokens = spec.n_pages, spec.slot_tokens
    # TPU decode streams pages via the Pallas kernel (page table in scalar
    # prefetch); everywhere else the XLA gather path runs — same
    # auto-selection contract as dbl_merge's update="auto".
    use_flash = paged and fd.resolve_impl("auto") == "pallas"

    kv, hd = cfg.n_kv_heads, cfg.head_dim

    # ``ck`` is a segment's whole stacked cache, carried through the layer
    # scan and written in place at layer ``li``: only the chunk's tokens
    # move, never a layer or the whole cache.
    def write_kv(ck, li, k, rows, positions, active):
        valid = jnp.arange(positions.shape[1])[None, :] < active[:, None]
        ok = jnp.logical_and(valid, positions < slot_tokens)
        k = k.astype(ck.dtype)
        if paged:
            pi = jnp.take_along_axis(
                rows, jnp.clip(positions // page_len, 0, pp - 1), axis=1)
            pi = jnp.where(ok, pi, n_pages)     # OOB page index -> dropped
            return ck.at[li, pi, positions % page_len].set(k, mode="drop")
        pos_w = jnp.where(ok, positions, slot_tokens)
        return ck.at[li, rows[:, None], pos_w].set(
            k.reshape(k.shape[:2] + (kv * hd,)), mode="drop")

    def layer_kv(ck, li):
        return jax.lax.dynamic_index_in_dim(ck, li, keepdims=False)

    def read_kv(ck, li, rows, m):
        if paged:
            return ck[li, rows].reshape(m, slot_tokens, kv, hd)
        c = ck[li, rows] if gather_rows else layer_kv(ck, li)
        return c.reshape(c.shape[:2] + (kv, hd))

    def step(params, caches, rows, lengths, active, tokens):
        m, t = tokens.shape
        cdt = dtype_of(cfg.compute_dtype)
        x = params["embed"][tokens].astype(cdt)
        positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)

        new_caches = []
        for seg, sp, cache in zip(segs, params["segments"], caches):
            uniform_w = seg.windows[0] if len(set(seg.windows)) == 1 else None
            flash = use_flash and t == 1 and uniform_w is not None
            windows = jnp.asarray(seg.windows, jnp.int32)

            def body(carry, xs, flash=flash, uniform_w=uniform_w):
                x, ck, cv = carry
                p, w, li = xs
                xin = rms_norm(x, p["ln1"], cfg.norm_eps)
                q, k, v = attn_project_qkv(p["attn"], xin, positions, cfg)
                with jax.named_scope("write_kv"):
                    ck = write_kv(ck, li, k, rows, positions, active)
                    cv = write_kv(cv, li, v, rows, positions, active)
                with jax.named_scope("attention"):
                    if flash:
                        o = fd.flash_decode_paged(
                            q.transpose(0, 2, 1, 3), layer_kv(ck, li),
                            layer_kv(cv, li), rows, lengths,
                            window=uniform_w).transpose(0, 2, 1, 3)
                    else:
                        o = _slot_attention(q, read_kv(ck, li, rows, m),
                                            read_kv(cv, li, rows, m),
                                            positions, w)
                h = x + o.reshape(m, t, cfg.n_heads * cfg.head_dim) \
                    @ p["attn"]["wo"]
                hin = rms_norm(h, p["ln2"], cfg.norm_eps)
                with jax.named_scope("mlp"):
                    if cfg.moe:
                        y, _ = moe_ffn(p["moe"], hin, cfg.moe, dropless=True)
                    else:
                        y = swiglu(hin, p["mlp"]["wi"], p["mlp"]["wg"],
                                   p["mlp"]["wo"])
                return (h + y, ck, cv), None

            (x, ck, cv), _ = jax.lax.scan(
                body, (x, cache["k"], cache["v"]),
                (sp, windows, jnp.arange(seg.count, dtype=jnp.int32)))
            new_caches.append({"k": ck, "v": cv})

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params.get("lm_head", params["embed"])
        logits = jnp.einsum("bsd,vd->bsv", x, head)
        return logits, new_caches

    return step


# ------------------------- in-jit token selection --------------------------
def make_token_fn(cfg: ModelConfig, spec: PageSpec, backend: str = "paged",
                  *, gather_rows: bool = False, temperature: float = 0.0,
                  top_k: int = 0, seed: int = 0):
    """Serve step + in-jit token selection (the one-sync-per-tick contract).

    Returns ``fn(params, caches, rows, lengths, active, tokens, rids,
    steps0) -> (next_tokens (m, T) int32, logits (m, T, V), new caches)``.
    The host pulls only ``next_tokens`` — an int32 row — per tick; logits
    stay on device unless a caller explicitly materializes them
    (``record_logits`` debugging / parity runs).

    ``temperature == 0`` is greedy argmax — bit-identical to the host
    argmax it replaces.  ``temperature > 0`` samples every chunk position
    ``j`` of row ``i`` with the counter-based key ``fold_in(fold_in(
    PRNGKey(seed), rids[i]), steps0[i] + j)``: keyed on *(seed, request,
    generation step)* exactly like the DataPlane's ``(seed, phase,
    worker, step)`` streams, so sampled runs replay bit-identically
    regardless of batch composition, slot bucketing or admission policy.
    ``top_k > 0`` keeps only the k highest logits (ties at the k-th value
    survive) before the temperature-scaled categorical draw.
    """
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0: {temperature}")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0: {top_k}")
    step = make_serve_step(cfg, spec, backend, gather_rows=gather_rows)
    base_key = jax.random.PRNGKey(seed)

    def fn(params, caches, rows, lengths, active, tokens, rids, steps0):
        logits, caches = step(params, caches, rows, lengths, active, tokens)
        if temperature == 0.0:
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return toks, logits, caches

        lo = logits.astype(jnp.float32)
        if top_k > 0:
            kth = jax.lax.top_k(lo, top_k)[0][..., -1:]
            lo = jnp.where(lo >= kth, lo, NEG_INF)
        lo = lo / temperature

        def sample_row(lrow, rid, s0):      # lrow: (T, V)
            kr = jax.random.fold_in(base_key, rid)
            steps = s0 + jnp.arange(lrow.shape[0], dtype=jnp.int32)
            keys = jax.vmap(lambda s: jax.random.fold_in(kr, s))(steps)
            return jax.vmap(jax.random.categorical)(keys, lrow)

        toks = jax.vmap(sample_row)(lo, rids, steps0).astype(jnp.int32)
        return toks, logits, caches

    return fn


# ------------------------- copy-on-write page duplication ------------------
def make_cow_copy(cfg: ModelConfig):
    """One-dispatch page duplication for copy-on-write prefix sharing.

    ``cow(caches, src, dst)`` copies page ``src`` onto page ``dst`` in
    every attention layer's K and V pools (``src``/``dst`` are traced
    scalars — one compile covers every COW event).  The shared reader
    duplicates the boundary page *before* its first write into it; the
    writer's original page is untouched, so both sequences keep exact
    KV prefixes with no other data movement.
    """
    def cow(caches, src, dst):
        return [{"k": c["k"].at[:, dst].set(c["k"][:, src]),
                 "v": c["v"].at[:, dst].set(c["v"][:, src])}
                for c in caches]
    return cow
