"""Pallas TPU flash-DECODE kernel: single-token attention over a long KV
cache (the decode_32k / long_500k hot spot).

Unlike the prefill kernel (q tiles x kv tiles), decode has one query row per
(batch, head) and a huge KV axis, so the kernel streams KV blocks with an
online-softmax accumulator in VMEM scratch — the flash-decoding pattern
restricted to one grid pass (the cross-device seq split is handled by the
sharding layer; each shard runs this kernel over its local cache slice and
XLA merges partials via the m/l outputs... here we emit the final merged
output per device since the q row is replicated per shard group).

Masking: positions > pos are invalid (cache tail), and an optional static
sliding window restricts to the last `window` positions.

Block shapes: (block_k, hd) KV tiles, hd lane-aligned (pad head_dim to a
multiple of 128 at the wrapper level for odd dims).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_k: int, nk: int, window: int, scale: float):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[0]
    k_start = ki * block_k

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (1, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        valid = kpos <= pos
        if window > 0:
            valid = jnp.logical_and(valid, kpos > pos - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))

    # skip blocks entirely beyond the needed range: start > pos
    pl.when(k_start <= pos)(compute)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def resolve_impl(impl: str) -> str:
    """``"auto"`` -> the Pallas kernel on TPU, an XLA reference off-TPU —
    the same policy as ``cluster.trace.resolve_update``: interpret-mode
    Pallas is a semantics fallback, not a fast path, so CPU serving
    benches / CI must measure the real XLA work, not emulation overhead."""
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _xla_decode(q, k_cache, v_cache, pos, *, window: int = 0):
    """XLA form of the decode attention (same math/mask as the kernel)."""
    b, h, _, hd = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // kvh
    k = jnp.repeat(k_cache, n_rep, axis=1).astype(jnp.float32)
    v = jnp.repeat(v_cache, n_rep, axis=1).astype(jnp.float32)
    sc = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32), k) * hd ** -0.5
    idx = jnp.arange(s)
    valid = idx <= pos
    if window > 0:
        valid = jnp.logical_and(valid, idx > pos - window)
    sc = jnp.where(valid[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhqs,bhsd->bqhd", p, v).transpose(0, 2, 1, 3) \
        .astype(q.dtype)


def flash_decode(q, k_cache, v_cache, pos, *, window: int = 0,
                 block_k: int = 512, interpret: bool | None = None,
                 impl: str = "auto"):
    """q: (B, H, 1, hd); k_cache/v_cache: (B, KV, S, hd); pos: scalar int32
    index of the newest token.  Returns (B, H, 1, hd).

    ``impl``: "pallas" (the kernel), "xla" (reference implementation), or
    "auto" — kernel on TPU, XLA elsewhere (CPU-honest: emulating the
    kernel with ``interpret=True`` measures the interpreter, not the
    attention).  Passing ``interpret`` explicitly forces the Pallas path
    with that interpret setting (kernel-semantics tests)."""
    if interpret is None:
        if resolve_impl(impl) == "xla":
            return _xla_decode(q, k_cache, v_cache, pos, window=window)
        interpret = False
    b, h, _, hd = q.shape
    kvh, s = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // kvh
    block_k = min(block_k, s)
    assert s % block_k == 0, "pad cache length to block_k"
    nk = s // block_k
    scale = hd ** -0.5
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    grid = (b, h, nk)
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, nk=nk, window=window,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, hd), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, ki: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, ki: (bi, hi // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd),
                               lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode",
    )(pos_arr, q, k_cache, v_cache)


# ------------------------- paged decode --------------------------------
def _paged_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_len: int, n_pages_slot: int,
                  n_rep: int, window: int, scale: float):
    """One grid step = one slot x one page, every head at once.

    The K/V block is the whole ``(page_len, KV, hd)`` page, so its last two
    dims equal the pool's and the TPU tiling rule holds for any KV.  Query
    head ``g * n_rep + r`` reads KV head ``g``; the wrapper hands the
    queries in as ``(n_rep, KV, hd)`` so each of the ``n_rep`` passes below
    lines its heads up with the page's KV axis.  Scores keep a trailing
    unit lane dim, ``(page_len, KV, 1)``, so every softmax statistic
    broadcasts against ``(KV, hd)`` rows without a relayout.
    """
    pi = pl.program_id(1)
    si = pl.program_id(0)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = len_ref[si]
    k_start = pi * page_len       # LOGICAL position of this page's 1st token

    def compute():
        k = k_ref[0].astype(jnp.float32)                 # (page_len, KV, hd)
        v = v_ref[0].astype(jnp.float32)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (page_len, 1, 1), 0)
        valid = kpos <= pos
        if window > 0:
            valid = jnp.logical_and(valid, kpos > pos - window)
        for r in range(n_rep):
            q = q_ref[0, r].astype(jnp.float32) * scale  # (KV, hd)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # (pl, KV, 1)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[r]                            # (KV, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[None])
            l_ref[r] = l_ref[r] * alpha + jnp.sum(p, axis=0)
            m_ref[r] = m_cur
            acc_ref[r] = acc_ref[r] * alpha + jnp.sum(p * v, axis=0)

    # pages wholly beyond the slot's live range contribute nothing; skip
    pl.when(k_start <= pos)(compute)

    @pl.when(pi == n_pages_slot - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_paged(q, k_pages, v_pages, page_table, lengths, *,
                       window: int = 0, interpret: bool | None = None,
                       impl: str = "auto"):
    """Gather-free paged decode attention: one query token per slot over a
    block-paged KV pool, the page table fed to the kernel as a
    scalar-prefetch operand so each KV page streams straight from its pool
    row (``BlockSpec`` index maps read the table — no materialized gather).

    q:          (S, H, 1, hd)         one new token per serving slot
    k/v_pages:  (P, page_len, KV, hd) the page pool (one layer's pages)
    page_table: (S, PP) int32         pool page id of each logical page
    lengths:    (S,) int32            per-slot position of the newest token
                                      (mask: logical index <= lengths[s])

    The grid is ``(S, PP)``: each step DMAs one whole page — all KV heads —
    and updates every query head's online softmax, so a GQA page is read
    once per slot, not once per query head.

    Off-TPU (``impl="auto"``) this dispatches to the XLA reference
    (``paged_decode_ref``) — gather + masked softmax, honest CPU work —
    mirroring ``flash_decode``; ``interpret=True`` forces the kernel under
    the Pallas interpreter (semantics tests).
    """
    ns, h, _, hd = q.shape
    n_pages, page_len, kvh, _ = k_pages.shape
    pp = page_table.shape[1]
    n_rep = h // kvh
    if interpret is None:
        if resolve_impl(impl) == "xla":
            return paged_decode_ref(q, k_pages, v_pages, page_table, lengths,
                                    window=window)
        interpret = False
    scale = hd ** -0.5
    table = jnp.asarray(page_table, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    # head g * n_rep + r -> [r, g]: each r-slice lines up with the KV axis
    qg = q.reshape(ns, kvh, n_rep, hd).transpose(0, 2, 1, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ns, pp),
        in_specs=[
            pl.BlockSpec((1, n_rep, kvh, hd),
                         lambda si, pi, tbl, ln: (si, 0, 0, 0)),
            pl.BlockSpec((1, page_len, kvh, hd),
                         lambda si, pi, tbl, ln: (tbl[si, pi], 0, 0, 0)),
            pl.BlockSpec((1, page_len, kvh, hd),
                         lambda si, pi, tbl, ln: (tbl[si, pi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_rep, kvh, hd),
                               lambda si, pi, tbl, ln: (si, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_rep, kvh, 1), jnp.float32),
            pltpu.VMEM((n_rep, kvh, 1), jnp.float32),
            pltpu.VMEM((n_rep, kvh, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_len=page_len, n_pages_slot=pp,
                          n_rep=n_rep, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ns, n_rep, kvh, hd), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(table, lens, qg, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3).reshape(ns, h, 1, hd)


def paged_decode_ref(q, k_pages, v_pages, page_table, lengths, *,
                     window: int = 0):
    """XLA reference for ``flash_decode_paged``: gather the slot's pages
    into logical order, then the exact contiguous decode-attention math —
    the off-TPU serving path (``repro.serve.paged`` builds its batched
    step on the same gather-then-attend form)."""
    ns, h, _, hd = q.shape
    page_len, kvh = k_pages.shape[1], k_pages.shape[2]
    pp = page_table.shape[1]
    s = pp * page_len
    k = k_pages[page_table].reshape(ns, s, kvh, hd)     # (S, pp*pl, KV, hd)
    v = v_pages[page_table].reshape(ns, s, kvh, hd)
    n_rep = h // kvh
    k = jnp.repeat(k.transpose(0, 2, 1, 3), n_rep, axis=1)  # (S, H, s, hd)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), n_rep, axis=1)
    sc = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * hd ** -0.5
    idx = jnp.arange(s)
    valid = idx[None, :] <= lengths[:, None]                # (S_slots, s)
    if window > 0:
        valid = jnp.logical_and(valid,
                                idx[None, :] > lengths[:, None] - window)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhqs,bhsd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
