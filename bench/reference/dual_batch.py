"""Plain reference of the dual-batch layout and its SGD server update.

The layout follows the paper's Eq. 4, 6 and 8 (dual-batch plan) and the
mapping of a plan onto one global batch of ``n_workers`` equal worker
blocks: the last ``n_small`` blocks are the small workers, of whose rows
only the first ``small_valid`` are live.  The update merges the two
groups' mean losses with the model-update factor f = d_S / d_L:

    L = (L_large + f * L_small) / (1 + f),   w' = w - lr * dL/dw
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def layout(traffic: dict, size: int) -> dict:
    """The dual-batch layout of the CPL sub-stage at ``size``."""
    sizes = traffic["sub_sizes"]
    ref = max(sizes)
    n, ns = traffic["n_workers"], traffic["n_small"]
    ratio = ref / size                           # sequence axis: cost ~ s
    gb = int(round(traffic["global_batch"] * ratio))
    gb = max(n, n * round(gb / n))
    b_l = int(round(traffic["global_batch"] * ratio))
    d = traffic["global_batch"] * traffic["dataset_rows_per_batch"]
    a = traffic["time_model"]["a"] * size / ref
    b = traffic["time_model"]["b"]
    k = traffic["k"]
    d_l = k * d / n
    d_s = (d - (n - ns) * d_l) / ns
    b_s = max(1, int(round(b / ((a + b / b_l) * (d_l / d_s) - a))))
    pw = gb // n
    small_valid = max(1, int(round(pw * b_s / b_l)))
    n_large_rows = (n - ns) * pw
    small_rows = np.concatenate([n_large_rows + w * pw + np.arange(small_valid)
                                 for w in range(ns)])
    return {"global_batch": gb, "per_worker": pw, "small_valid": small_valid,
            "large_rows": np.arange(n_large_rows), "small_rows": small_rows,
            "factor": d_s / d_l,
            "valid_rows": n_large_rows + ns * small_valid}


def merged_loss_and_grad(weights, tokens, labels, lay, row_losses, *,
                         block: int = 8, keep: float = 1.0):
    """(merged loss, gradient) over the live rows, taken in blocks of rows
    so that the activations of one block fit beside the weights.  Each
    row carries its share of the merged loss; the last block is padded
    with rows of share 0, so every block has one shape.  ``keep`` < 1
    plants a fault for the control: only the first ``keep`` of each
    group's rows count, and the mean is taken over them."""
    f = lay["factor"]
    large = lay["large_rows"][:max(1, int(len(lay["large_rows"]) * keep))]
    small = lay["small_rows"][:max(1, int(len(lay["small_rows"]) * keep))]
    rows = np.concatenate([large, small])
    coef = np.concatenate([np.full(len(large), 1.0 / ((1.0 + f) * len(large))),
                           np.full(len(small), f / ((1.0 + f) * len(small)))])
    pad = (-len(rows)) % block
    rows = np.concatenate([rows, np.repeat(rows[-1:], pad)])
    coef = np.concatenate([coef, np.zeros(pad)]).astype(np.float32)

    def block_loss(w, t, l, c):
        return jnp.sum(c * row_losses(w, t, l))

    vg = jax.jit(jax.value_and_grad(block_loss))
    loss = 0.0
    grad = jax.tree_util.tree_map(jnp.zeros_like, weights)
    for i in range(0, len(rows), block):
        r = rows[i:i + block]
        val, g = vg(weights, jnp.asarray(tokens[r]), jnp.asarray(labels[r]),
                    jnp.asarray(coef[i:i + block]))
        loss += float(val)
        grad = jax.tree_util.tree_map(jnp.add, grad, g)
    return loss, grad


def sgd(weights, grad, lr: float):
    return jax.tree_util.tree_map(lambda w, g: w - lr * g, weights, grad)


def leaf_norms(tree) -> dict:
    """{leaf path: float64 L2 norm}."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[name] = math.sqrt(float(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))))
    return out
