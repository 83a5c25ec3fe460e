"""Pallas TPU kernel for the fused dual-batch server update (paper §3.4).

The paper's global update applies the large-group gradient at factor 1 and
the small-group gradient at the model-update factor f:

    w' = w − lr · (g_L + f·g_S) / (1 + f)

Fusing the scale/add/normalize/apply into one VMEM pass removes three HBM
round-trips of the parameter-sized temporaries the naive HLO sequence makes
(see ``kernels.ref.dbl_merge_unfused`` for that sequence, materialized).

The hot-path entry point is ``dbl_merge_flat2d``: ONE launch over the whole
flat parameter store (``repro.core.flat``) — a lane/sublane-padded
``(rows, LANE)`` f32 buffer — updated in place via ``input_output_aliases``.
Buffers up to ``MAX_WHOLE_ROWS`` rows run as a single whole-buffer block;
larger ones grid over ``BLOCK_ROWS``-row tiles (the codec pads rows to the
matching multiple).  An optional velocity buffer folds the PS server
momentum into the same VMEM sweep:

    v' = m·v + (g_L + f·g_S)/(1 + f);   w' = w − lr·v'

``launch_count()`` counts Python-level kernel launches as traced — each
call here is exactly one ``pallas_call`` in the compiled step, which the
flat-store tests assert stays at ONE per server update.

``dbl_merge_tree`` / ``dbl_merge_flat`` are the pytree / 1D front ends
(both route through the same single-launch core).

``dbl_apply_worker_flat2d`` is the trace-compiled PS simulator's per-event
update: the velocity of every simulated worker lives in ONE stacked
``(n_workers, rows, LANE)`` buffer, and the kernel gathers worker ``wid``'s
velocity row block, applies momentum + the factor-scaled server push, and
scatters the row back — local update and server push in a single launch,
with ``lr`` / ``factor`` / ``momentum`` / ``wid`` as tiny traced operands
so one executable serves every event of a ``lax.scan`` over the trace.

Mixed precision (bf16 store): every entry point takes ``master2=`` — the
float32 master-weight buffer in the store's exact ``(rows, LANE)``
geometry (``FlatSpec.ravel_master``).  The kernel then updates the MASTER
in f32 (gradient upcast, f32 velocity) and writes BOTH the updated master
and its rounded ``p2.dtype`` shadow in the SAME single launch, each output
aliased onto its input buffer — no extra sweep, no extra HBM round trip
for keeping a low-precision store trainable.  With ``master2=None`` the
f32-only kernels are byte-for-byte what they were before the option
existed.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.flat import (BLOCK_ROWS, LANE, MAX_WHOLE_ROWS, SUBLANE,
                             padded_rows)

_LAUNCHES = 0


def launch_count() -> int:
    """Python-level kernel launches so far (increments once per traced
    ``pallas_call`` — the flat-store launch-count test reads this)."""
    return _LAUNCHES


def _kernel(p_ref, gl_ref, gs_ref, o_ref, *, factor: float, lr: float):
    p = p_ref[...].astype(jnp.float32)
    gl = gl_ref[...].astype(jnp.float32)
    gs = gs_ref[...].astype(jnp.float32)
    step = (gl + factor * gs) * (1.0 / (1.0 + factor))
    o_ref[...] = (p - lr * step).astype(o_ref.dtype)


def _kernel_vel(p_ref, gl_ref, gs_ref, v_ref, op_ref, ov_ref, *,
                factor: float, lr: float, momentum: float):
    p = p_ref[...].astype(jnp.float32)
    gl = gl_ref[...].astype(jnp.float32)
    gs = gs_ref[...].astype(jnp.float32)
    g = (gl + factor * gs) * (1.0 / (1.0 + factor))
    v = momentum * v_ref[...].astype(jnp.float32) + g
    ov_ref[...] = v.astype(ov_ref.dtype)
    op_ref[...] = (p - lr * v).astype(op_ref.dtype)


def _kernel_apply(p_ref, g_ref, o_ref, *, lr: float):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = (p - lr * g).astype(o_ref.dtype)


def _kernel_apply_vel(p_ref, g_ref, v_ref, op_ref, ov_ref, *,
                      lr: float, momentum: float):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v = momentum * v_ref[...].astype(jnp.float32) + g
    ov_ref[...] = v.astype(ov_ref.dtype)
    op_ref[...] = (p - lr * v).astype(op_ref.dtype)


# -- mixed-dtype master forms: the math runs on the f32 MASTER (gradient
# upcast from the low-precision store), and the same pass writes the
# updated master AND its rounded store-dtype shadow.  The shadow input ref
# is never read — it exists so the shadow output can alias its buffer.
def _kernel_master(p_ref, m_ref, gl_ref, gs_ref, op_ref, om_ref, *,
                   factor: float, lr: float):
    del p_ref
    m = m_ref[...].astype(jnp.float32)
    gl = gl_ref[...].astype(jnp.float32)
    gs = gs_ref[...].astype(jnp.float32)
    step = (gl + factor * gs) * (1.0 / (1.0 + factor))
    m = m - lr * step
    om_ref[...] = m.astype(om_ref.dtype)
    op_ref[...] = m.astype(op_ref.dtype)


def _kernel_master_vel(p_ref, m_ref, gl_ref, gs_ref, v_ref, op_ref, om_ref,
                       ov_ref, *, factor: float, lr: float, momentum: float):
    del p_ref
    m = m_ref[...].astype(jnp.float32)
    gl = gl_ref[...].astype(jnp.float32)
    gs = gs_ref[...].astype(jnp.float32)
    g = (gl + factor * gs) * (1.0 / (1.0 + factor))
    v = momentum * v_ref[...].astype(jnp.float32) + g
    m = m - lr * v
    ov_ref[...] = v.astype(ov_ref.dtype)
    om_ref[...] = m.astype(om_ref.dtype)
    op_ref[...] = m.astype(op_ref.dtype)


def _kernel_apply_master(p_ref, m_ref, g_ref, op_ref, om_ref, *, lr: float):
    del p_ref
    m = m_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m - lr * g
    om_ref[...] = m.astype(om_ref.dtype)
    op_ref[...] = m.astype(op_ref.dtype)


def _kernel_apply_master_vel(p_ref, m_ref, g_ref, v_ref, op_ref, om_ref,
                             ov_ref, *, lr: float, momentum: float):
    del p_ref
    m = m_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v = momentum * v_ref[...].astype(jnp.float32) + g
    m = m - lr * v
    ov_ref[...] = v.astype(ov_ref.dtype)
    om_ref[...] = m.astype(om_ref.dtype)
    op_ref[...] = m.astype(op_ref.dtype)


def _launch(kernel, ins, out_shape, aliases, *, interpret, block_rows):
    """One ``pallas_call`` over same-shaped flat buffers: a single
    whole-buffer block up to ``MAX_WHOLE_ROWS`` rows, a 1-D grid of
    ``block_rows``-row tiles beyond (the codec pads rows to the matching
    multiple).  Counts as exactly one launch."""
    global _LAUNCHES
    _LAUNCHES += 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows = ins[0].shape[0]
    if rows <= MAX_WHOLE_ROWS:
        # whole-buffer block: no grid machinery, no index maps
        return pl.pallas_call(kernel, out_shape=out_shape,
                              interpret=interpret,
                              input_output_aliases=aliases)(*ins)
    if rows % block_rows:
        raise ValueError(
            f"flat buffer of {rows} rows cannot grid over "
            f"block_rows={block_rows}; pad rows to a multiple (the codec's "
            f"padded_rows() does this for the default BLOCK_ROWS)")
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out_specs = (spec if not isinstance(out_shape, tuple)
                 else tuple(spec for _ in out_shape))
    return pl.pallas_call(kernel, grid=(rows // block_rows,),
                          in_specs=[spec] * len(ins), out_specs=out_specs,
                          out_shape=out_shape, interpret=interpret,
                          input_output_aliases=aliases)(*ins)


def _sds(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def dbl_merge_flat2d(p2, gl2, gs2, *, factor: float, lr: float,
                     vel2=None, momentum: float = 0.0, master2=None,
                     interpret: Optional[bool] = None,
                     block_rows: int = BLOCK_ROWS):
    """ONE fused server update over the whole flat store.

    p2 / gl2 / gs2 (and vel2, if given): ``(rows, LANE)`` buffers from
    ``FlatSpec.ravel``.  Returns the updated params buffer, or the
    ``(params, velocity)`` pair when ``vel2`` is given (momentum folded
    into the same pass).  Updates alias their inputs, so jit callers that
    donate the carry run the sweep in place.

    ``master2`` (mixed precision): the f32 master buffer backing a
    low-precision ``p2``.  The update then runs on the master and the same
    launch writes both it and the rounded ``p2``-dtype shadow — returns
    ``(params, master)`` or ``(params, master, velocity)``, every output
    aliased onto its input.
    """
    if master2 is not None:
        if vel2 is None:
            return _launch(
                functools.partial(_kernel_master, factor=factor, lr=lr),
                (p2, master2, gl2, gs2), (_sds(p2), _sds(master2)),
                {0: 0, 1: 1}, interpret=interpret, block_rows=block_rows)
        return _launch(
            functools.partial(_kernel_master_vel, factor=factor, lr=lr,
                              momentum=momentum),
            (p2, master2, gl2, gs2, vel2),
            (_sds(p2), _sds(master2), _sds(vel2)),
            {0: 0, 1: 1, 4: 2}, interpret=interpret, block_rows=block_rows)
    if vel2 is None:
        return _launch(functools.partial(_kernel, factor=factor, lr=lr),
                       (p2, gl2, gs2),
                       jax.ShapeDtypeStruct(p2.shape, p2.dtype), {0: 0},
                       interpret=interpret, block_rows=block_rows)
    return _launch(functools.partial(_kernel_vel, factor=factor, lr=lr,
                                     momentum=momentum),
                   (p2, gl2, gs2, vel2),
                   (jax.ShapeDtypeStruct(p2.shape, p2.dtype),
                    jax.ShapeDtypeStruct(vel2.shape, vel2.dtype)),
                   {0: 0, 3: 1}, interpret=interpret, block_rows=block_rows)


def dbl_apply_flat2d(p2, g2, *, lr: float, vel2=None, momentum: float = 0.0,
                     master2=None, interpret: Optional[bool] = None,
                     block_rows: int = BLOCK_ROWS):
    """ONE server apply over the whole flat store, for a gradient that
    already carries the dual-batch merge.

    Gradients are linear, so ``grad((L_L + f·L_S)/(1+f))`` IS the paper's
    merged gradient ``(g_L + f·g_S)/(1+f)`` — the engine's scan path folds
    the scale/add/normalize into the backward accumulation and hands this
    kernel the merged ``g2``, leaving one apply (+momentum) VMEM sweep:

        v' = m·v + g;   w' = w − lr·v'      (v ≡ g when m == 0)

    Same aliasing/blocking contract as ``dbl_merge_flat2d``, including the
    mixed-precision ``master2`` form (returns ``(params, master)`` or
    ``(params, master, velocity)``, one launch either way).
    """
    if master2 is not None:
        if vel2 is None:
            return _launch(
                functools.partial(_kernel_apply_master, lr=lr),
                (p2, master2, g2), (_sds(p2), _sds(master2)), {0: 0, 1: 1},
                interpret=interpret, block_rows=block_rows)
        return _launch(
            functools.partial(_kernel_apply_master_vel, lr=lr,
                              momentum=momentum),
            (p2, master2, g2, vel2),
            (_sds(p2), _sds(master2), _sds(vel2)),
            {0: 0, 1: 1, 3: 2}, interpret=interpret, block_rows=block_rows)
    if vel2 is None:
        return _launch(functools.partial(_kernel_apply, lr=lr), (p2, g2),
                       jax.ShapeDtypeStruct(p2.shape, p2.dtype), {0: 0},
                       interpret=interpret, block_rows=block_rows)
    return _launch(functools.partial(_kernel_apply_vel, lr=lr,
                                     momentum=momentum),
                   (p2, g2, vel2),
                   (jax.ShapeDtypeStruct(p2.shape, p2.dtype),
                    jax.ShapeDtypeStruct(vel2.shape, vel2.dtype)),
                   {0: 0, 2: 1}, interpret=interpret, block_rows=block_rows)


def _kernel_apply_worker(wid_ref, lr_ref, fac_ref, mom_ref, p_ref, g_ref,
                         v_ref, op_ref, ov_ref):
    # one simulated-PS event: gather worker wid's velocity row block from
    # the stacked buffer, fold the momentum update in, apply the
    # factor-scaled server push, scatter the row back.  The float op order
    # mirrors the legacy event path exactly (m·v + g, then −lr·v, then
    # w + f·d) so the trace-compiled executor stays bit-identical to it.
    w = wid_ref[0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[pl.ds(w, 1)][0].astype(jnp.float32)
    v = mom_ref[0] * v + g
    d = -lr_ref[0] * v
    op_ref[...] = (p + fac_ref[0] * d).astype(op_ref.dtype)
    ov_ref[pl.ds(w, 1)] = v[None].astype(ov_ref.dtype)


def _kernel_apply_worker_master(wid_ref, lr_ref, fac_ref, mom_ref, p_ref,
                                m_ref, g_ref, v_ref, op_ref, om_ref, ov_ref):
    # mixed-precision twin of _kernel_apply_worker: the update runs on the
    # f32 master (same float op order), the same launch writes master +
    # rounded store-dtype shadow.  The shadow input is only an alias donor.
    del p_ref
    w = wid_ref[0]
    m = m_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    v = v_ref[pl.ds(w, 1)][0].astype(jnp.float32)
    v = mom_ref[0] * v + g
    d = -lr_ref[0] * v
    m = m + fac_ref[0] * d
    om_ref[...] = m.astype(om_ref.dtype)
    op_ref[...] = m.astype(op_ref.dtype)
    ov_ref[pl.ds(w, 1)] = v[None].astype(ov_ref.dtype)


def _worker_block_rows(rows: int, n_workers: int, block_rows: int) -> int:
    """Row-tile height for the gridded worker kernel: the velocity block
    carries ALL workers' rows for the tile, so halve the tile until the
    stacked block fits the same VMEM budget a (BLOCK_ROWS, LANE) pair does
    AND divides the buffer's row count (power-of-two heights divide any
    sublane-padded row count once small enough)."""
    budget = 2 * BLOCK_ROWS          # in+out param-block rows equivalent
    b = block_rows
    while b > 1 and (b * n_workers > budget or rows % b):
        b //= 2
    return b


def dbl_apply_worker_flat2d(p2, g2, vel3, wid, lr, factor,
                            momentum, *, master2=None,
                            interpret: Optional[bool] = None,
                            block_rows: int = BLOCK_ROWS):
    """ONE fused per-event PS update over the whole flat store.

    p2 / g2: ``(rows, LANE)`` param / merged-gradient buffers; vel3: the
    stacked ``(n_workers, rows, LANE)`` per-worker velocity buffer.  wid /
    lr / factor / momentum are traced scalars (or ``(1,)`` arrays) — the
    trace executor feeds them per event from the ``SimTrace`` arrays, so a
    single compiled ``lax.scan`` serves every event regardless of which
    worker fired or what the epoch schedule set lr to:

        v'[wid] = m·v[wid] + g;   d = −lr·v'[wid];   w' = w + f·d

    Returns ``(params, velocity)``; both alias their inputs, and only
    worker ``wid``'s velocity row block is rewritten.  With ``master2``
    (mixed precision) the update runs on the f32 master and the same
    launch also writes the rounded ``p2``-dtype shadow — returns
    ``(params, master, velocity)``, all aliased.
    """
    global _LAUNCHES
    _LAUNCHES += 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    as1 = lambda x, dt: jnp.reshape(jnp.asarray(x), (1,)).astype(dt)
    scalars = (as1(wid, jnp.int32), as1(lr, jnp.float32),
               as1(factor, jnp.float32), as1(momentum, jnp.float32))
    if master2 is None:
        kernel = _kernel_apply_worker
        bufs = (p2, g2, vel3)
        out_shape = (_sds(p2), _sds(vel3))
        aliases = {4: 0, 6: 1}
        vel_pos = 2                    # vel3's index within bufs
    else:
        kernel = _kernel_apply_worker_master
        bufs = (p2, master2, g2, vel3)
        out_shape = (_sds(p2), _sds(master2), _sds(vel3))
        aliases = {4: 0, 5: 1, 7: 2}
        vel_pos = 3
    rows = p2.shape[0]
    n_workers = vel3.shape[0]
    # whole-buffer only while the STACKED velocity block also fits the
    # budget — rows alone says nothing once n_workers grows, and the
    # worker-sweep regime is exactly where it does
    if rows <= MAX_WHOLE_ROWS and n_workers * rows <= 2 * MAX_WHOLE_ROWS:
        return pl.pallas_call(kernel, out_shape=out_shape,
                              interpret=interpret,
                              input_output_aliases=aliases)(
            *scalars, *bufs)
    block = _worker_block_rows(rows, n_workers, block_rows)
    if rows % block:
        raise ValueError(
            f"flat buffer of {rows} rows cannot grid over worker block "
            f"rows {block}; pad rows to a sublane multiple (the codec's "
            "padded_rows() does this)")
    sspec = pl.BlockSpec((1,), lambda i: (0,))
    pspec = pl.BlockSpec((block, LANE), lambda i: (i, 0))
    vspec = pl.BlockSpec((n_workers, block, LANE), lambda i: (0, i, 0))
    bspecs = [pspec] * len(bufs)
    bspecs[vel_pos] = vspec
    ospecs = tuple(pspec for _ in out_shape[:-1]) + (vspec,)
    return pl.pallas_call(
        kernel, grid=(rows // block,),
        in_specs=[sspec] * 4 + bspecs,
        out_specs=ospecs, out_shape=out_shape,
        interpret=interpret, input_output_aliases=aliases)(
        *scalars, *bufs)


def dbl_apply_worker_xla(p2, g2, vel3, wid, lr, factor, momentum,
                         master2=None):
    """XLA-elementwise form of ``dbl_apply_worker_flat2d`` — the same
    per-event PS update as a handful of fused elementwise ops instead of a
    ``pallas_call``:

        v'[wid] = m·v[wid] + g;   d = −lr·v'[wid];   w' = w + f·d

    The float op order is identical to the kernel's and to the event
    path's jitted ``local_update``, so all three forms are bit-equal on
    f32 buffers; the barrier pins the gradient the way the opaque kernel
    call does, keeping XLA from folding the update math into the backward
    epilogue (the bit-moving fusion the parity contract forbids).

    This form is also what the **batched candidate replay** vmaps: every
    op here maps cleanly over a leading candidate axis (params
    ``(C, rows, LANE)``, velocity ``(C, n_workers, rows, LANE)``), whereas
    vmapping an interpret-mode ``pallas_call`` would just multiply
    emulation overhead.  Returns ``(params, velocity)`` like the kernel.

    ``optimization_barrier`` has no vmap batching rule, so under the
    candidate-batched replay the barrier drops out — harmless there: the
    batched executable IS one fusion scope per event for every candidate,
    so all candidates see the same (reassociation-free elementwise)
    schedule and the batched-vs-sequential f32 parity contract is upheld
    by the op order alone.
    """
    try:
        g2 = jax.lax.optimization_barrier(g2)
    except NotImplementedError:      # vmapped (batched candidate replay)
        pass
    vrow = jax.lax.dynamic_slice_in_dim(vel3, wid, 1, 0)[0]
    if master2 is not None:
        # mixed precision: update the f32 master, re-round the shadow —
        # same op order as _kernel_apply_worker_master
        g32 = g2.astype(jnp.float32)
        v = momentum * vrow + g32
        d = -lr * v
        master2 = master2 + factor * d
        p2 = master2.astype(p2.dtype)
        vel3 = jax.lax.dynamic_update_slice_in_dim(vel3, v[None], wid, 0)
        return p2, master2, vel3
    v = momentum * vrow + g2
    d = -lr * v
    p2 = p2 + factor * d
    vel3 = jax.lax.dynamic_update_slice_in_dim(vel3, v[None], wid, 0)
    return p2, vel3


def dbl_merge_flat(p, g_large, g_small, *, factor: float, lr: float,
                   block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """p, g_large, g_small: flat (N,) arrays -> updated flat params.
    Pads to the store layout (respecting a custom ``block_rows`` so large
    buffers always grid), runs the single-launch core, slices back."""
    n = p.shape[0]
    rows = padded_rows(n)
    if rows > MAX_WHOLE_ROWS and rows % block_rows:
        rows += block_rows - rows % block_rows
    pad = rows * LANE - n

    def to2(x):
        return jnp.pad(x, (0, pad)).reshape(rows, LANE)

    out = dbl_merge_flat2d(to2(p), to2(g_large), to2(g_small),
                           factor=factor, lr=lr, interpret=interpret,
                           block_rows=block_rows)
    return out.reshape(-1)[:n]


def dbl_merge_tree(params, g_large, g_small, *, factor: float, lr: float,
                   interpret: bool = False, leafwise: bool = False):
    """Fused merge over parameter pytrees — ONE kernel launch for the whole
    tree via the flat-store codec (offsets cached on treedef identity),
    not one per leaf.

    ``leafwise=True`` applies the same kernel per leaf instead: the flat
    concat would destroy per-leaf shardings (XLA falls back to a full
    rematerialization), so mesh-sharded trees keep the leaf-at-a-time form.
    On TPU the compiler cannot partition the kernel itself, so a sharded
    tree is updated under ``shard_map``, each device on its own shards
    (``engine.steps.make_fused_dbl_step``).
    """
    if leafwise:
        return jax.tree_util.tree_map(
            lambda p, gl, gs: dbl_merge_flat(
                p.reshape(-1), gl.reshape(-1), gs.reshape(-1),
                factor=factor, lr=lr, interpret=interpret).reshape(p.shape),
            params, g_large, g_small)
    from repro.core.flat import flat_spec
    spec = flat_spec(params)
    out = dbl_merge_flat2d(spec.ravel(params), spec.ravel(g_large),
                           spec.ravel(g_small), factor=factor, lr=lr,
                           interpret=interpret)
    return spec.unravel(out)
