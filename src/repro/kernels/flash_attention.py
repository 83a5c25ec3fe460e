"""Pallas TPU flash-attention forward kernel (causal + sliding window, GQA).

VMEM tiling: per grid step one (block_q, hd) query tile and one
(block_k, hd) KV tile live in VMEM; the online-softmax accumulators
(m, l, acc) persist in VMEM scratch across the KV-block axis (innermost grid
dim — TPU grids iterate sequentially, so scratch carries state).  GQA is
handled by the KV BlockSpec index map (kv head = q head // n_rep): no
expanded KV copies in HBM.  Fully-masked KV blocks above the causal diagonal
(or outside the sliding window) are skipped with @pl.when, so causal compute
is ~half of dense — the static-skip optimization the XLA path lacks.

Block sizes default to (128, 128): MXU-aligned on the contraction and
lane dims for f32/bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q, block_k, nk, causal, window, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # static-shape positions for this tile pair
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        if causal:
            mask = q_pos >= k_pos
            if window > 0:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_cur
        acc_ref[...] = acc_ref[...] * alpha \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))

    if causal:
        # skip blocks entirely above the diagonal / outside the window
        needed = k_start <= q_start + block_q - 1
        if window > 0:
            needed = jnp.logical_and(
                needed, k_start + block_k - 1 > q_start - window)
        pl.when(needed)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd).  Returns (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    n_rep = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, "pad seq to block size"
    nq, nk = sq // block_q, sk // block_k
    scale = hd ** -0.5

    grid = (b, h, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0))
    out_spec = pl.BlockSpec((1, 1, block_q, hd),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    kern = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                             nk=nk, causal=causal, window=window, scale=scale)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
