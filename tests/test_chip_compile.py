"""Native TPU compiles of the main-path Pallas kernels, without a chip.

Each test compiles one kernel for one chip of a described v5e
(``jax.experimental.topologies``) with ``interpret=False`` at real
widths — n = 2^20 users, K = 10 knapsacks, Q = 1, the default 49 bucket
edges and 511 profit edges, tile 512; ``scd_fused_hist`` at the
benchmark cells' shapes with its default lane tile — and asserts that
the compiled HLO holds the Mosaic custom call. The TPU compiler refuses
here what it would refuse on the chip (slices not aligned to the tiling,
more VMEM than a kernel may use), at no chip time. One test compiles the
whole resident kernel solve and reads its HLO for relayouts of p and b.
Everything built from the topology is built in fixtures, so collecting
this file never loads the TPU library.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import SolverConfig
from repro.core.solver import solve_fn
from repro.core.types import SparseKP
from repro.kernels import scd_fused
from repro.kernels.scd_fused import scd_finalize_hist, scd_fused_hist
from repro.kernels.screen_bound import screen_bound

N, K, Q, TILE = 2**20, 10, 1, 512
E = 2 * SolverConfig().bucket_half + 1          # 49 bucket edges
PROFIT_EDGES = SolverConfig().profit_buckets - 1  # 511 profit edges
# The benchmark cells' user axes: the resident 1e7-user share and the
# host-fed chunk.
CELL_N = (10_000_000, 65536)


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


_MOVE = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?) (?:copy|copy-start|transpose)\(")


def _relayouts(comps, n, where):
    """Copies and transposes that yield an (n, K) array, or its (K, n)
    view, in the computations ``where`` names."""
    shapes = (f"f32[{n},{K}]", f"f32[{K},{n}]")
    return [line for comp in where for line in comps[comp]
            if (m := _MOVE.match(line))
            and any(s in m.group(1) for s in shapes)]


def _computations(hlo):
    """HLO text -> {computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            head = line.split()
            name = head[1] if head[0] == "ENTRY" else head[0]
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    return comps


def _called(comps, roots):
    """The computations ``roots`` run, with everything they call."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(
                r"(?:calls|body|condition|to_apply)=(%[\w.\-]+)", line)
    return seen


@pytest.mark.parametrize("n", CELL_N)
def test_scd_fused_hist_compiles_natively(one_chip, no_compile_cache, n):
    """Default lane tile at the cells' shapes: p and b reach the kernel
    as bitcasts of their users-minor layout, never copied."""
    hlo = scd_fused_hist.lower(
        _sds((n, K), one_chip), _sds((n, K), one_chip),
        _sds((K,), one_chip), _sds((K, E), one_chip), Q,
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo
    comps = _computations(hlo)
    assert not _relayouts(comps, n, comps)


def test_resident_solve_moves_no_p_or_b(one_chip, no_compile_cache,
                                        monkeypatch):
    """The resident kernel solve at the share cell's n: no copy or
    transpose of p or b in the iteration loop, and no K-padded relayout
    temporary of them (which alone would take n * 128 * 4 bytes)."""
    n = CELL_N[0]
    # The solve asks the default backend (here the CPU) whether to
    # interpret; the described chip compiles the kernel natively.
    monkeypatch.setattr(scd_fused, "resolve_interpret", lambda i: False)
    kp = SparseKP(p=_sds((n, K), one_chip), b=_sds((n, K), one_chip),
                  budgets=_sds((K,), one_chip))
    compiled = solve_fn(SolverConfig(max_iters=20, use_kernels=True),
                        Q).lower(kp, _sds((K,), one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    comps = _computations(hlo)
    bodies = _called(comps, [b for lines in comps.values() for line in lines
                             if " while(" in line
                             for b in re.findall(r"body=(%[\w.\-]+)", line)])
    assert bodies
    assert not _relayouts(comps, n, bodies)
    assert compiled.memory_analysis().temp_size_in_bytes < n * 128 * 4


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
