"""Native TPU compiles of the main-path Pallas kernels, without a chip.

Each test compiles one kernel for one chip of a described v5e
(``jax.experimental.topologies``) with ``interpret=False`` at real
widths — n = 2^20 users, K = 10 knapsacks, Q = 1, the default 49 bucket
edges and 511 profit edges, tile 512 — and asserts that the compiled HLO
holds the Mosaic custom call. The TPU compiler refuses here what it would
refuse on the chip (slices not aligned to the tiling, more VMEM than a
kernel may use), at no chip time. Everything built from the topology is
built in fixtures, so collecting this file never loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import SolverConfig
from repro.kernels.scd_fused import scd_finalize_hist, scd_fused_hist
from repro.kernels.screen_bound import screen_bound

N, K, Q, TILE = 2**20, 10, 1, 512
E = 2 * SolverConfig().bucket_half + 1          # 49 bucket edges
PROFIT_EDGES = SolverConfig().profit_buckets - 1  # 511 profit edges


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — any refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described device's executable cannot be read back: keep the
    persistent cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_native(lowered):
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo


def test_scd_fused_hist_compiles_natively(one_chip, no_compile_cache):
    _assert_native(scd_fused_hist.lower(
        _sds((N, K), one_chip), _sds((N, K), one_chip),
        _sds((K,), one_chip), _sds((K, E), one_chip), Q,
        tile_n=TILE, interpret=False))


@pytest.mark.parametrize("with_hist", [True, False])
def test_scd_finalize_hist_compiles_natively(one_chip, no_compile_cache,
                                             with_hist):
    _assert_native(scd_finalize_hist.lower(
        _sds((N, K), one_chip), _sds((N, K), one_chip),
        _sds((K,), one_chip), _sds((PROFIT_EDGES,), one_chip), Q,
        tile_n=TILE, interpret=False, with_hist=with_hist))


def test_screen_bound_compiles_natively(one_chip, no_compile_cache):
    _assert_native(screen_bound.lower(
        _sds((N, K), one_chip), _sds((N, K), one_chip),
        tile_n=TILE, interpret=False))
