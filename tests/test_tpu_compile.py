"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described rather than attached.  These cases catch what
interpret mode cannot — block shapes the tiling rule refuses, kernels that
ask for too much fast memory — at the published widths the program runs
(phi3-mini: 32 heads of 96, kv 32; its 4-layer flat store of 5,079,040
rows).  Every case asserts that the compiled program holds the Pallas
kernel (``tpu_custom_call``), not an XLA fallback.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.flat import LANE

FLAT_ROWS_PHI3_4L = 5_079_040     # FlatSpec(phi3-mini, 4 layers).rows
N_WORKERS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [2048, FLAT_ROWS_PHI3_4L],
                         ids=["whole_buffer", "gridded_phi3_4l"])
def test_dbl_apply_flat2d_f32(one_chip, rows):
    from repro.kernels.dbl_merge import dbl_apply_flat2d
    buf = _sds((rows, LANE), jnp.float32, one_chip)
    _assert_kernel(lambda p, g: dbl_apply_flat2d(p, g, lr=0.05,
                                                 interpret=False), buf, buf)


def test_dbl_apply_flat2d_bf16_master(one_chip):
    from repro.kernels.dbl_merge import dbl_apply_flat2d
    shape = (FLAT_ROWS_PHI3_4L, LANE)
    p2 = _sds(shape, jnp.bfloat16, one_chip)
    f32 = _sds(shape, jnp.float32, one_chip)
    _assert_kernel(lambda p, m, g: dbl_apply_flat2d(
        p, g, lr=0.05, master2=m, interpret=False), p2, f32, f32)


@pytest.mark.parametrize("rows", [2048, 16 * 1024],
                         ids=["whole_buffer", "gridded"])
def test_dbl_apply_worker_flat2d(one_chip, rows):
    from repro.kernels.dbl_merge import dbl_apply_worker_flat2d
    buf = _sds((rows, LANE), jnp.float32, one_chip)
    vel = _sds((N_WORKERS, rows, LANE), jnp.float32, one_chip)
    _assert_kernel(lambda p, g, v: dbl_apply_worker_flat2d(
        p, g, v, 1, 0.05, 0.8, 0.9, interpret=False), buf, buf, vel)


@pytest.mark.parametrize("heads,kv_heads,window",
                         [(32, 32, 0), (32, 32, 64), (32, 8, 0)],
                         ids=["phi3_mha", "phi3_mha_window", "gqa4"])
def test_flash_decode_paged_bf16(one_chip, heads, kv_heads, window):
    """phi3-mini widths (hd 96): the K/V block is a whole page, so its last
    two dims equal the pool's ``(KV, hd)`` whatever KV is."""
    from repro.kernels.flash_decode import flash_decode_paged
    slots, hd, page_len, pages_per_slot, n_pages = 8, 96, 16, 32, 256
    q = _sds((slots, heads, 1, hd), jnp.bfloat16, one_chip)
    pool = _sds((n_pages, page_len, kv_heads, hd), jnp.bfloat16, one_chip)
    table = _sds((slots, pages_per_slot), jnp.int32, one_chip)
    lengths = _sds((slots,), jnp.int32, one_chip)
    _assert_kernel(lambda *a: flash_decode_paged(*a, window=window,
                                                 interpret=False),
                   q, pool, pool, table, lengths)
