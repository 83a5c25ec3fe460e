"""Canonical train-step builders — the ONE implementation behind
``launch/steps.make_train_step``, ``core/spmd_dual_batch.make_train_step`` /
``make_micro_train_step`` and the engine's compiled-step cache.

Three step kinds:

  weighted   — single weighted-loss pass; the dual-batch contribution-scaled
               merge realized as one weighted mean of per-example gradients
               (works with ANY optimizer).
  micro      — beyond-weighted variant: the small group takes ``micro_steps``
               sequential local SGD steps inside one global step (lax.scan)
               before the factor-weighted merge.
  fused_dbl  — the paper §3.4 server update for the SGD dual-batch case,
               applied by the Pallas ``dbl_merge`` kernel in ONE launch over
               the whole flat parameter store (``repro.core.flat`` codec):
               w' = w − lr·(g_L + f·g_S)/(1 + f), with g_L/g_S the large and
               small group mean gradients.  ``interpret=True`` on non-TPU
               backends; ``fused=False`` falls back to the XLA-fused
               reference update (``kernels.ref.dbl_merge_ref``).

All steps share one signature:

    step(params, opt_state, batch, lr, rng) -> (params, opt_state, metrics)

``rng`` is only consumed when ``drop_rate > 0`` (pass None otherwise);
``metrics`` always contains "loss".

``make_fused_phase_scan`` is the fused path's WHOLE-PHASE form: the carry
is the flat ``(params, velocity)`` buffer pair, gradients are taken w.r.t.
the flat buffer (autodiff transposes the codec's unravel into the ravel —
no per-step pad/reshape), and a ``lax.scan`` over pre-stacked batches
compiles the entire inner loop into one executable with exactly one
``dbl_merge`` launch per server update.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models


def _weighted_loss(params, cfg, batch, rng, drop_rate):
    return models.loss_fn(params, cfg, batch, drop_rng=rng,
                          drop_rate=drop_rate)


def make_weighted_step(cfg, optimizer, *, layout=None, drop_rate: float = 0.0):
    """Weighted-loss step: batch["weight"] (or ``layout.weights()``) carries
    the dual-batch per-example contributions; any optimizer."""
    def step(params, opt_state, batch, lr, rng=None):
        if layout is not None and "weight" not in batch:
            batch = dict(batch, weight=layout.weights().astype(jnp.float32))
        (loss, _), grads = jax.value_and_grad(
            _weighted_loss, has_aux=True)(params, cfg, batch, rng, drop_rate)
        params, opt_state = optimizer.update(grads, opt_state, params, lr)
        return params, opt_state, {"loss": loss}

    return step


def _small_valid_index(layout) -> np.ndarray:
    """Static row indices of the small group's VALID examples in the global
    padded batch (first ``small_valid`` rows of each small worker block)."""
    pw = layout.per_worker
    nl_rows = (layout.n_workers - layout.n_small) * pw
    return np.concatenate([
        nl_rows + w * pw + np.arange(layout.small_valid)
        for w in range(layout.n_small)]).astype(np.int32)


def make_fused_dbl_step(cfg, layout, *, drop_rate: float = 0.0,
                        fused: bool = True, interpret: Optional[bool] = None,
                        mesh=None):
    """SGD dual-batch step with the fused ``dbl_merge`` parameter update on
    the hot path (paper §3.4).  ``opt_state`` passes through untouched — the
    server update IS the optimizer.  ``fused=False`` selects the unfused
    reference update (flag for perf comparison / debugging).  With a
    ``mesh`` the params are sharded by ``launch.sharding.param_specs``: the
    kernel then runs per leaf on each device's own shards under
    ``shard_map`` — the compiler cannot partition a Mosaic kernel, and the
    flat-store concat would break the shardings."""
    from repro.kernels.dbl_merge import dbl_merge_tree
    from repro.kernels.ref import dbl_merge_ref

    if layout.n_small == 0 or layout.small_valid == 0:
        raise ValueError("fused dbl step needs a non-empty small group; "
                         "use make_weighted_step for the baseline")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pw = layout.per_worker
    nl_rows = (layout.n_workers - layout.n_small) * pw
    small_idx = jnp.asarray(_small_valid_index(layout))
    f = float(layout.factor_small)

    def group_grad(params, batch, rows, rng):
        sub = {k: v[rows] for k, v in batch.items() if k in _GROUP_KEYS}
        return jax.value_and_grad(_weighted_loss, has_aux=True)(
            params, cfg, sub, rng, drop_rate)

    def step(params, opt_state, batch, lr, rng=None):
        # lr is STATIC here (baked into the fused kernel) — the engine jits
        # fused steps with static_argnums=(3,); phases carry a constant lr.
        lr_f = float(lr)
        (loss_l, _), g_large = group_grad(params, batch,
                                          jnp.arange(nl_rows), rng)
        (loss_s, _), g_small = group_grad(params, batch, small_idx, rng)
        if fused and mesh is not None:
            from repro.launch.sharding import param_specs
            specs = param_specs(params, mesh)
            merge = functools.partial(dbl_merge_tree, factor=f, lr=lr_f,
                                      interpret=interpret, leafwise=True)
            # check_vma off: the kernel's outputs carry no varying-axes
            # annotation; each shard's update is local by construction
            params = jax.shard_map(merge, mesh=mesh, in_specs=(specs,) * 3,
                                   out_specs=specs, check_vma=False)(
                params, g_large, g_small)
        elif fused:
            params = dbl_merge_tree(params, g_large, g_small, factor=f,
                                    lr=lr_f, interpret=interpret)
        else:
            params = jax.tree_util.tree_map(
                lambda p, gl, gs: dbl_merge_ref(p, gl, gs, factor=f,
                                                lr=lr_f),
                params, g_large, g_small)
        loss = (loss_l + f * loss_s) / (1.0 + f)
        return params, opt_state, {"loss": loss, "loss_large": loss_l,
                                   "loss_small": loss_s}

    return step


_GROUP_KEYS = ("tokens", "labels", "images", "embeddings")


def make_fused_phase_scan(cfg, layout, spec, *, lr: float,
                          drop_rate: float = 0.0, momentum: float = 0.0,
                          interpret: Optional[bool] = None):
    """The fused dual-batch hot path for a WHOLE phase, scan-compiled.

    Returns ``phase_fn(p2, v2, batches, rngs) -> (p2, v2, losses)``:

      * ``p2`` / ``v2`` — flat ``(rows, LANE)`` f32 param / velocity
        buffers from ``spec.ravel`` (``v2 = None`` when ``momentum == 0``;
        the engine jits with both donated, so the server update runs in
        place across the phase);
      * ``batches`` — the phase's batches stacked on a leading steps axis;
      * ``rngs`` — per-step dropout keys stacked likewise (None when
        ``drop_rate == 0``);
      * ``losses`` — the per-step merged loss, stacked.

    Per step this does ONE backward pass and ONE kernel launch.  The loss
    differentiated is the already-merged scalar ``(L_L + f·L_S)/(1+f)``:
    gradients are linear, so its gradient IS the paper's merged gradient
    ``(g_L + f·g_S)/(1+f)`` — the scale/add/normalize of §3.4 rides the
    backward accumulation instead of materializing two parameter-sized
    gradients and merging them after.  The loss is taken w.r.t. the flat
    buffer through ``spec.unravel``, so the gradient arrives flat (autodiff
    transposes the unravel into the ravel — no per-step pad/reshape), and
    ``dbl_apply_flat2d`` finishes with the single apply(+momentum) sweep.
    ``lr`` is baked in (phases carry a constant lr on this path).

    Mixed precision: when ``spec`` has a non-f32 ``store_dtype`` the
    ``p2`` carry is the ``(shadow, master)`` buffer pair — the
    low-precision shadow drives forward/backward (``spec.unravel`` upcasts
    leaves to their f32 dtypes, so only the stored weights are rounded),
    the gradient is taken w.r.t. the EXACT f32 view of the shadow (the
    cast is linear, so it is the same merged gradient — but it reaches the
    kernel unrounded and the backward never touches emulated-bf16 ops),
    and ``dbl_apply_flat2d``'s master form writes the f32 master and the
    re-rounded shadow in the same single launch.
    """
    from repro.kernels.dbl_merge import dbl_apply_flat2d

    if layout.n_small == 0 or layout.small_valid == 0:
        raise ValueError("fused dbl phase needs a non-empty small group; "
                         "use make_weighted_step for the baseline")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pw = layout.per_worker
    nl_rows = (layout.n_workers - layout.n_small) * pw
    small_idx = jnp.asarray(_small_valid_index(layout))
    f = float(layout.factor_small)
    lr_f = float(lr)
    mom = float(momentum)
    mixed = spec.store_dtype != jnp.dtype(jnp.float32)

    def merged_loss(p2, batch, rng):
        params = spec.unravel(p2)
        sub = lambda rows: {k: v[rows] for k, v in batch.items()
                            if k in _GROUP_KEYS}
        loss_l, _ = _weighted_loss(params, cfg, sub(jnp.arange(nl_rows)),
                                   rng, drop_rate)
        loss_s, _ = _weighted_loss(params, cfg, sub(small_idx), rng,
                                   drop_rate)
        return (loss_l + f * loss_s) / (1.0 + f), ()

    def phase_fn(p2, v2, batches, rngs):
        # keep the scan carry/xs as lean as the configuration allows —
        # extra pytree structure in the carry costs real per-step time
        def step_update(p2, v2, xs):
            batch, rng = xs if rngs is not None else (xs, None)
            shadow = p2[0] if mixed else p2
            # mixed: differentiate w.r.t. the f32 VIEW of the shadow — the
            # upcast is exact (forward still sees the bf16-rounded values)
            # and the cast is linear, so the gradient is the same merged
            # gradient, but it arrives f32: the backward stays off the
            # emulated-bf16 path (2.4x slower on CPU) and the kernel's
            # master update consumes it unrounded
            (loss, _), g2 = jax.value_and_grad(merged_loss, has_aux=True)(
                shadow.astype(jnp.float32) if mixed else shadow, batch, rng)
            if mixed:
                master = p2[1]
                if mom > 0:
                    shadow, master, v2 = dbl_apply_flat2d(
                        shadow, g2, vel2=v2, lr=lr_f, momentum=mom,
                        master2=master, interpret=interpret)
                else:
                    shadow, master = dbl_apply_flat2d(
                        shadow, g2, lr=lr_f, master2=master,
                        interpret=interpret)
                return (shadow, master), v2, loss
            if mom > 0:
                p2, v2 = dbl_apply_flat2d(p2, g2, vel2=v2, lr=lr_f,
                                          momentum=mom, interpret=interpret)
            else:
                p2 = dbl_apply_flat2d(p2, g2, lr=lr_f, interpret=interpret)
            return p2, v2, loss

        xs = (batches, rngs) if rngs is not None else batches
        if mom > 0:
            def body(carry, x):
                p2, v2, loss = step_update(*carry, x)
                return (p2, v2), loss
            (p2, v2), losses = jax.lax.scan(body, (p2, v2), xs)
        else:
            def body(p2, x):
                p2, _, loss = step_update(p2, None, x)
                return p2, loss
            p2, losses = jax.lax.scan(body, p2, xs)
        return p2, v2, losses

    return phase_fn


def make_micro_step(cfg, optimizer, *, layout, micro_steps: int = 2,
                    drop_rate: float = 0.0):
    """Micro-update mode (beyond-weighted variant, DESIGN.md §3.2): the small
    group's rows split into ``micro_steps`` sequential micro-batches; a
    lax.scan applies local SGD steps over them from the pulled params, and
    the delta merges into the global update with the model-update factor —
    recovering ASP's higher small-batch update frequency synchronously."""
    pw = layout.per_worker
    n_small_rows = layout.n_small * pw

    def step(params, opt_state, batch, lr, rng=None):
        tokens, labels = batch["tokens"], batch["labels"]
        nl_rows = layout.global_batch - n_small_rows
        big = {"tokens": tokens[:nl_rows], "labels": labels[:nl_rows]}
        small = {"tokens": tokens[nl_rows:], "labels": labels[nl_rows:]}

        # large-group gradient (one big batch)
        (loss_b, _), g_big = jax.value_and_grad(
            _weighted_loss, has_aux=True)(params, cfg, big, rng, drop_rate)

        # small-group local SGD over micro-batches
        msz = n_small_rows // micro_steps
        mt = small["tokens"][: msz * micro_steps].reshape(
            micro_steps, msz, *tokens.shape[1:])
        ml = small["labels"][: msz * micro_steps].reshape(
            micro_steps, msz, *labels.shape[1:])

        def micro(p, xs):
            t, l = xs
            (ls, _), g = jax.value_and_grad(_weighted_loss, has_aux=True)(
                p, cfg, {"tokens": t, "labels": l}, rng, drop_rate)
            p = jax.tree_util.tree_map(
                lambda w, gg: w - (lr * gg).astype(w.dtype), p, g)
            return p, ls
        p_small, losses = jax.lax.scan(micro, params, (mt, ml))

        # merge: factor-scaled small-group delta + large-group SGD step
        f = layout.factor_small
        delta_small = jax.tree_util.tree_map(lambda a, b: a - b, p_small,
                                             params)
        params2, opt_state = optimizer.update(g_big, opt_state, params, lr)
        params2 = jax.tree_util.tree_map(
            lambda p, d: p + (f * d.astype(jnp.float32)).astype(p.dtype),
            params2, delta_small)
        return params2, opt_state, {"loss": loss_b,
                                    "loss_small": jnp.mean(losses)}

    return step
