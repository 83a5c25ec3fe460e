"""Production meshes (TPU v5e target).

Defined as functions, not module constants, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link


def _auto(n_axes: int) -> tuple:
    return (AxisType.Auto,) * n_axes


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis Auto.

    ``jax.make_mesh`` builds Explicit axes, which carry the sharding in
    each array's type, so every gather along a sharded dim must name its
    output sharding.  The program's rules (``launch.sharding``) place
    arrays with ``NamedSharding`` and let the compiler propagate — the
    Auto contract.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=_auto(len(mesh.axis_names)))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod ("data","model"); 2 pods stack a leading
    "pod" axis (data-parallel across DCN/ICI-superpod)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(n: int | None = None, axes=("data", "model"),
                   model: int | None = None):
    """Mesh over the first ``n`` devices of this host (all by default):
    ``model`` devices on the model axis (default: the largest of 4, 2, 1
    that divides ``n``), the rest on data.  ``model=1`` is a pure data
    mesh — the dual-batch workers spread over the chips."""
    dev = len(jax.devices()) if n is None else n
    if model is None:
        model = next(m for m in (4, 2, 1) if dev % m == 0)
    return jax.make_mesh((dev // model, model), axes,
                         axis_types=_auto(len(axes)),
                         devices=jax.devices()[:dev])


def data_axes(mesh) -> tuple:
    """Axes that shard the batch dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
