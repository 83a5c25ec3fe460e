"""Shared primitive layers (pure JAX, functional, pytree params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def normal_init(rng, shape, scale, dtype):
    return (scale * jax.random.normal(rng, shape, jnp.float32)).astype(dtype)


def rms_norm(x, gamma, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def swiglu(x, wi, wg, wo):
    """SwiGLU MLP: silu(x@wg) * (x@wi) @ wo."""
    h = jax.nn.silu(x @ wg) * (x @ wi)
    return h @ wo


def dropout(x, rng, rate):
    if rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


# ----------------------------- RoPE ---------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))


def _tile2_last(t, hd: int):
    """[t, t] along the last dim via broadcast+reshape, NOT concatenate."""
    return jnp.broadcast_to(t[..., None, :], (*t.shape[:-1], 2, hd // 2)) \
              .reshape(*t.shape[:-1], hd)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32.

    Roll-based rotate-half: out = x·[cos,cos] + roll(x, hd/2)·[−sin,sin].
    Algebraically identical to the split/concat form, but never splits or
    concatenates along the head dim: the CPU SPMD partitioner of jax 0.4.37
    produced wrong values when a tensor model-sharded on that dim was
    split/concatenated and combined elementwise with an in-graph concat.
    Under the installed jax 0.9.0 the roll form passes tests/test_spmd.py's
    sharded-vs-single parity; the split form has not been re-tested there.
    """
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta))               # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                          # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    sign = jnp.asarray(np.repeat(np.float32([-1.0, 1.0]), hd // 2))
    cos_full = _tile2_last(cos, hd)                           # (..., S, 1, hd)
    sin_signed = _tile2_last(sin, hd) * sign
    xf = x.astype(jnp.float32)
    rot = jnp.roll(xf, hd // 2, axis=-1)                      # [x2, x1]
    return (xf * cos_full + rot * sin_signed).astype(x.dtype)


def chunked_cross_entropy(hidden, head, labels, *, chunk: int = 8192,
                          label_mask=None):
    """Streaming CE over vocab-projected logits without materializing the
    full (B, S, V) f32 tensor — the memory lever for 256k-vocab heads
    (gemma3, seamless): logits are computed per S-chunk and reduced.

    hidden: (B, S, D); head: (V, D); labels: (B, S).
    Returns per-example losses (B,), like cross_entropy.
    """
    b, s, d = hidden.shape
    q = min(chunk, s)
    while s % q:
        q -= 1
    nc = s // q
    hc = hidden.reshape(b, nc, q, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, q).transpose(1, 0, 2)
    if label_mask is None:
        label_mask = jnp.ones((b, s), jnp.float32)
    mc = label_mask.reshape(b, nc, q).transpose(1, 0, 2)

    def body(carry, xs):
        tok_sum, cnt = carry
        h, l, m = xs
        logits = jnp.einsum("bqd,vd->bqv", h, head).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * m
        return (tok_sum + jnp.sum(nll, axis=-1),
                cnt + jnp.sum(m, axis=-1)), None

    (tok, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.float32)),
        (hc, lc, mc))
    return tok / jnp.maximum(cnt, 1.0)


def cross_entropy(logits, labels, label_mask=None):
    """Per-example mean token cross-entropy.

    logits: (B, S, V) f32-castable; labels: (B, S) int32;
    label_mask: (B, S) {0,1} — returns (B,) per-example losses and (B,) weights.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold                                    # (B, S)
    if label_mask is None:
        label_mask = jnp.ones_like(nll)
    tok = jnp.sum(nll * label_mask, axis=-1)
    cnt = jnp.maximum(jnp.sum(label_mask, axis=-1), 1.0)
    return tok / cnt
