"""Plain float32 reference of a dense decoder (Phi-3 family layout).

Straight ``jax.numpy``: no kernels, no cache, no batching tricks, matmul
precision "highest" (set by the caller).  It imports nothing of the
program and takes weights only from ``bench.lib.weights``.

Layer equations (Phi-3 modelling code): pre-RMSNorm, multi-head causal
attention with rotate-half RoPE, SiLU-gated MLP, final RMSNorm, untied
LM head.  The sliding window masks keys more than ``sliding_window - 1``
positions back.

``mm`` is the matmul the whole model runs through: ``mm_f32`` for the
reference, ``mm_fp8`` for the control (both operands rounded to float8
e4m3 with one scale per tensor, forward and backward), with the
weights held by ``lower``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# leaves the configuration keeps in float32 (RMSNorm weights)
NORMS = ("final_norm", "ln1", "ln2")


def shapes(conf: dict) -> dict:
    """The weight tree of a configuration: leaf name -> shape."""
    d, v = conf["hidden_size"], conf["vocab_size"]
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, f, n = d // h, conf["intermediate_size"], conf["num_hidden_layers"]
    return {"embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
            "layers": {"ln1": (n, d), "ln2": (n, d),
                       "wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                       "wv": (n, d, kv * hd), "wo": (n, h * hd, d),
                       "w_gate": (n, d, f), "w_up": (n, d, f),
                       "w_down": (n, f, d)}}


def mm_f32(a, b):
    return jnp.matmul(a, b)


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale (max |x| -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def lower(weights):
    """The control's weights: every weight held in float8 e4m3
    (per-tensor scale) but the RMSNorm weights, which stay float32 as the
    program keeps them."""
    def q(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        return leaf if name in NORMS else _q8(leaf)
    return jax.tree_util.tree_map_with_path(q, weights)


@jax.custom_vjp
def mm_fp8(a, b):
    return jnp.matmul(_q8(a), _q8(b))


def _mm_fp8_fwd(a, b):
    return mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    qa, qb, qg = _q8(a), _q8(b), _q8(g)
    da = jnp.matmul(qg, jnp.swapaxes(qb, -1, -2))
    db = jnp.matmul(jnp.swapaxes(qa, -1, -2), qg)
    # reduce broadcast batch dims of b (weights are 2D)
    while db.ndim > b.ndim:
        db = db.sum(0)
    return da, db


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)

# the reference's matmul, and the control's (the precision below bf16)
MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, positions, theta):
    """x: (B, S, H, hd); rotate-half form of the published code."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(
        inv, jnp.float32)                                  # (B, S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


def attention(q, k, v, window, mm):
    """Causal attention, q/k/v: (B, S, H, hd)."""
    b, s, h, hd = q.shape
    qh = jnp.transpose(q, (0, 2, 1, 3)) * hd ** -0.5      # (B, H, S, hd)
    kh = jnp.transpose(k, (0, 2, 3, 1))                   # (B, H, hd, S)
    vh = jnp.transpose(v, (0, 2, 1, 3))
    scores = mm(qh, kh)                                   # (B, H, S, S)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = (j <= i) & (j > i - window)
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = mm(p, vh)                                         # (B, H, S, hd)
    return jnp.transpose(o, (0, 2, 1, 3)).reshape(b, s, h * hd)


def layer(x, w, conf, positions, mm):
    b, s, d = x.shape
    h = conf["num_attention_heads"]
    kvh = conf["num_key_value_heads"]
    hd = d // h
    eps = conf["rms_norm_eps"]
    y = rms_norm(x, w["ln1"], eps)
    q = mm(y, w["wq"]).reshape(b, s, h, hd)
    k = mm(y, w["wk"]).reshape(b, s, kvh, hd)
    v = mm(y, w["wv"]).reshape(b, s, kvh, hd)
    q = rope(q, positions, conf["rope_theta"])
    k = rope(k, positions, conf["rope_theta"])
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    x = x + mm(attention(q, k, v, conf["sliding_window"], mm), w["wo"])
    y = rms_norm(x, w["ln2"], eps)
    g = mm(y, w["w_gate"])
    u = mm(y, w["w_up"])
    return x + mm(jax.nn.silu(g) * u, w["w_down"])


def logits(weights, tokens, conf, mm=mm_f32):
    """tokens (B, S) -> f32 logits (B, S, V)."""
    b, s = tokens.shape
    x = weights["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    layers = weights["layers"]
    for i in range(conf["num_hidden_layers"]):
        w = jax.tree_util.tree_map(lambda a: a[i], layers)
        x = layer(x, w, conf, positions, mm)
    x = rms_norm(x, weights["final_norm"], conf["rms_norm_eps"])
    return mm(x, weights["lm_head"].T)


def row_losses(weights, tokens, labels, conf, mm=mm_f32):
    """(B,) mean next-token cross-entropy of each row."""
    lg = logits(weights, tokens, conf, mm)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold, axis=-1)
