"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid (B, H, n_chunks) with the chunk axis innermost; the (P, N) carry state
lives in VMEM scratch and persists across chunk iterations (TPU grids run
sequentially).  Within a chunk the recurrence is the SSD masked-matmul
decomposition, so the MXU does the heavy lifting:

    y_intra = ((C·Bᵀ) ⊙ decay_mask) @ (dt·x)
    y_inter = (C @ hᵀ) ⊙ exp(cum)
    h'      = exp(cum_Q)·h + (dt·x ⊙ exp(cum_Q − cum))ᵀ @ B

Chunk length Q and head dim P default to 128 — MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, o_ref, h_ref, *,
            q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                 # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)               # (Q, 1)
    a = -jnp.exp(alog_ref[0, 0].astype(jnp.float32))    # (1, 1)
    B = b_ref[0].astype(jnp.float32)                    # (Q, N)
    C = c_ref[0].astype(jnp.float32)                    # (Q, N)
    dskip = d_ref[0, 0].astype(jnp.float32)             # (1, 1)

    la = dt * a                                         # (Q, 1) log decay
    cum = jnp.cumsum(la, axis=0)                        # (Q, 1)
    xdt = x * dt                                        # (Q, P)

    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    dmat = jnp.where(tri, jnp.exp(cum - cum.T), 0.0)    # (Q, Q)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (Q, Q)
    y_intra = jax.lax.dot_general(cb * dmat, xdt,
                                  (((1,), (0,)), ((), ())))   # (Q, P)

    h = h_ref[...]                                      # (P, N)
    y_inter = jax.lax.dot_general(C, h, (((1,), (1,)), ((), ()))) \
        * jnp.exp(cum)                                  # (Q, P)

    tot = cum[-1:]                                      # (1, 1)
    dec_out = jnp.exp(tot - cum)                        # (Q, 1)
    contrib = jax.lax.dot_general(xdt * dec_out, B,
                                  (((0,), (0,)), ((), ())))   # (P, N)
    h_ref[...] = h * jnp.exp(tot) + contrib

    o_ref[0, 0] = (y_intra + y_inter + x * dskip).astype(o_ref.dtype)


def mamba_ssd_scan(x, dt, A_log, B, C, D_skip, *, chunk: int = 128,
                   interpret: bool = False):
    """x: (Bt,H,S,P); dt: (Bt,H,S); A_log: (H,); B,C: (Bt,S,N); D: (H,).

    Returns y (Bt,H,S,P).
    """
    bt, h, s, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, "pad seq to chunk size"
    nc = s // q

    dt2 = dt[..., None]                                 # (Bt,H,S,1)
    alog2 = A_log.reshape(h, 1, 1)
    d2 = D_skip.reshape(h, 1, 1)

    grid = (bt, h, nc)
    return pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, ci: (hi, 0, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, hi, ci: (hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bt, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="mamba_ssd_scan",
    )(x, dt2, alog2, B, C, d2)
