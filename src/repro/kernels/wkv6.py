"""Pallas TPU kernel for the RWKV-6 WKV recurrence (data-dependent decay).

Grid (B, H, n_chunks), chunk axis innermost; the (K, V) state persists in
VMEM scratch.  Within a chunk a fori_loop applies the exact per-step
recurrence (rank-1 VPU updates on a 64x64 state — small enough that the
sequential inner loop stays VMEM-resident; the chunk framing exists so HBM
traffic is blocked and the state never round-trips).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)       # (Q, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)       # (Q, V)
    w = w_ref[0, 0].astype(jnp.float32)       # (Q, K)
    u = u_ref[0].astype(jnp.float32)          # (1, K)

    def step(t, S):
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)       # (1, K)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)       # (1, V)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)       # (1, K)
        kv = kt.T @ vt                                      # (K, V)
        y = rt @ (S + u.T * kv)                             # (1, V)
        o_ref[0, 0, pl.ds(t, 1), :] = y.astype(o_ref.dtype)
        return S * wt.T + kv

    S = jax.lax.fori_loop(0, q, step, s_ref[...])
    s_ref[...] = S


def wkv6_chunked(r, k, v, w, u, *, chunk: int = 128,
                 interpret: bool = False):
    """r,k,w: (B,H,S,K); v: (B,H,S,V); u: (H,K).  Returns y (B,H,S,V)."""
    b, h, s, kd = r.shape
    vd = v.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, "pad seq to chunk size"
    nc = s // q
    u2 = u.reshape(h, 1, kd)

    grid = (b, h, nc)
    spec = lambda d: pl.BlockSpec((1, 1, q, d),
                                  lambda bi, hi, ci: (bi, hi, ci, 0))
    return pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=grid,
        in_specs=[spec(kd), spec(kd), spec(vd), spec(kd),
                  pl.BlockSpec((1, 1, kd), lambda bi, hi, ci: (hi, 0, 0))],
        out_specs=spec(vd),
        out_shape=jax.ShapeDtypeStruct((b, h, s, vd), r.dtype),
        scratch_shapes=[pltpu.VMEM((kd, vd), jnp.float32)],
        interpret=interpret,
        name="wkv6_chunked",
    )(r, k, v, w, u2)
