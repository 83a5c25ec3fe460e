"""Opt-in activation sharding constraints (mesh-agnostic model code).

The model zoo never names mesh axes; the launcher opts in via
``activation_sharding(...)`` and model code calls ``constrain(x, dims)``
with logical dim tags:

    "b"  batch        -> data axes
    "h"  heads/experts-> model axis (if the dim divides it)
    "m"  model-dim    -> model axis (column-sharded activations)
    "."  unsharded

Without an active context constrain() is a no-op, so single-device smoke
tests and the PS simulator never see mesh machinery.  §Perf iteration 1
measures the effect (attention einsums otherwise replicate over the model
axis — XLA's propagation does not re-shard the reshaped head dim).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import AxisType, PartitionSpec as P

_state = threading.local()


def _ctx():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def activation_sharding(mesh, *, data_axes=("data",), model_axis="model"):
    sizes = {a: mesh.shape[a] for a in mesh.axis_names}
    dsize = 1
    for a in data_axes:
        dsize *= sizes[a]
    prev = _ctx()
    _state.ctx = {"mesh": mesh, "data": tuple(data_axes),
                  "model": model_axis, "dsize": dsize,
                  "msize": sizes[model_axis]}
    try:
        yield
    finally:
        _state.ctx = prev


def constrain_first(x, options):
    """Apply the first dims-string whose 'h'/'m' tags all divide the model
    axis (e.g. MoE: shard experts if E % tp == 0, else the ff dim)."""
    ctx = _ctx()
    if ctx is None:
        return x
    for dims in options:
        ok = all(size % ctx["msize"] == 0
                 for tag, size in zip(dims, x.shape) if tag in ("h", "m"))
        if ok:
            return constrain(x, dims)
    return x


def constrain(x, dims: str):
    """dims: one tag per array dim ('b', 'h', 'm', '.')."""
    ctx = _ctx()
    if ctx is None:
        return x
    if len(dims) != x.ndim:
        raise ValueError(f"dims {dims!r} vs rank {x.ndim}")
    spec = []
    for tag, size in zip(dims, x.shape):
        if tag == "b" and size % ctx["dsize"] == 0:
            spec.append(ctx["data"])
        elif tag in ("h", "m") and size % ctx["msize"] == 0:
            spec.append(ctx["model"])
        else:
            spec.append(None)
    mesh = ctx["mesh"]
    sharding = jax.sharding.NamedSharding(mesh, P(*spec))
    if AxisType.Explicit in mesh.axis_types:
        # explicit axes (``jax.make_mesh``'s default) carry the sharding in
        # the array's type; a constraint may only name Auto axes
        return jax.sharding.reshard(x, sharding)
    return jax.lax.with_sharding_constraint(x, sharding)
