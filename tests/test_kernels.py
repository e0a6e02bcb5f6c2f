"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
plus cross-checks of the oracles against the core solver modules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")

SHAPES = [(128, 8), (256, 16), (512, 64), (384, 10)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _inst(n, k, dtype, seed=0):
    kp, kb, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = jax.random.uniform(kp, (n, k), jnp.float32)
    b = jax.random.uniform(kb, (n, k), jnp.float32, 0.05, 1.0)
    lam = jax.random.uniform(kl, (k,), jnp.float32, 0.0, 1.5)
    return p.astype(dtype), b.astype(dtype), lam.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("q", [1, 2, 4])
def test_adjusted_topc_matches_ref(shape, dtype, q):
    n, k = shape
    p, b, lam = _inst(n, k, dtype)
    x_k, v_k = ops.adjusted_topc(p, b, lam, q, tile_n=128, interpret=True)
    x_r, v_r = ref.adjusted_topc_ref(p, b, lam, q)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_array_equal(np.asarray(x_k), np.asarray(x_r))
    np.testing.assert_allclose(
        np.asarray(v_k, np.float32), np.asarray(v_r, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("q", [1, 2, 4])
def test_scd_candidates_matches_ref(shape, dtype, q):
    n, k = shape
    p, b, lam = _inst(n, k, dtype, seed=1)
    v1_k, v2_k = ops.scd_candidates(p, b, lam, q, tile_n=128, interpret=True)
    v1_r, v2_r = ref.scd_candidates_ref(p, b, lam, q)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(v1_k, np.float32), np.asarray(v1_r, np.float32),
        rtol=tol, atol=tol)
    np.testing.assert_allclose(
        np.asarray(v2_k, np.float32), np.asarray(v2_r, np.float32),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(256, 8), (512, 16)])
@pytest.mark.parametrize("n_edges", [17, 49])
def test_bucket_hist_matches_ref(shape, n_edges):
    n, k = shape
    p, b, lam = _inst(n, k, jnp.float32, seed=2)
    v1 = p / b
    v2 = b
    edges = jnp.sort(
        jax.random.uniform(jax.random.PRNGKey(5), (k, n_edges), jnp.float32,
                           0.0, 3.0), axis=-1)
    h_k = ops.bucket_hist(v1, v2, edges, tile_n=128, interpret=True)
    h_r = ref.bucket_hist_ref(v1, v2, edges)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=1e-5, atol=1e-5)
    # total mass preserved
    np.testing.assert_allclose(float(h_k.sum()), float(v2.sum()), rtol=1e-5)


@given(seed=st.integers(0, 2**31 - 1), q=st.integers(1, 5))
@settings(max_examples=25, deadline=None)
def test_kernel_refs_match_core_modules(seed, q):
    """The kernel oracles and the core solver must agree (same tie-breaks)."""
    from repro.core.sparse_scd import candidates_sparse, select_sparse

    kp_, kb, kl = jax.random.split(jax.random.PRNGKey(seed), 3)
    n, k = 64, 8
    p = jax.random.uniform(kp_, (n, k))
    b = jax.random.uniform(kb, (n, k), minval=0.05)
    lam = jax.random.uniform(kl, (k,), maxval=1.5)

    x_ref, _ = ref.adjusted_topc_ref(p, b, lam, q)
    x_core = select_sparse(p, b, lam, q)
    np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_core))

    v1_ref, v2_ref = ref.scd_candidates_ref(p, b, lam, q)
    v1_core, v2_core = candidates_sparse(p, b, lam, q)
    np.testing.assert_allclose(np.asarray(v1_ref), np.asarray(v1_core), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v2_ref), np.asarray(v2_core), rtol=1e-6)


def test_bucket_hist_accumulates_across_grid():
    """Multi-tile grid: the VMEM accumulator pattern must sum all tiles."""
    n, k, e = 1024, 4, 9
    v1 = jnp.tile(jnp.linspace(0.0, 2.0, n)[:, None], (1, k))
    v2 = jnp.ones((n, k))
    edges = jnp.tile(jnp.linspace(0.25, 1.75, e)[None, :], (k, 1))
    h = ops.bucket_hist(v1, v2, edges, tile_n=128, interpret=True)
    assert float(h.sum()) == pytest.approx(n * k)
    h1 = ops.bucket_hist(v1, v2, edges, tile_n=1024, interpret=True)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h1), rtol=1e-6)


def test_solver_kernel_path_matches_jnp_path():
    """End-to-end: the solver with use_kernels=True (Pallas interpret mode)
    reproduces the jnp path's multipliers and primal."""
    from repro.core import SolverConfig, solve
    from repro.core.instances import shard_key, sparse_instance

    kp, q = sparse_instance(shard_key(33), n=512, k=8, q=2, tightness=0.4)
    cfg = SolverConfig(reduce="bucketed", max_iters=6)
    a = solve(kp, cfg, q=q)
    b = solve(kp, cfg.replace(use_kernels=True), q=q)
    np.testing.assert_allclose(np.asarray(a.lam), np.asarray(b.lam),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(a.primal), float(b.primal), rtol=1e-5)


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False),
                                          ("gpu", None)])
def test_interpret_only_on_cpu(monkeypatch, backend, want):
    """The kernels compile on a TPU and interpret only on the CPU; any
    other backend refuses rather than silently interpreting."""
    from repro.kernels._util import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    if want is None:
        with pytest.raises(RuntimeError, match="gpu"):
            resolve_interpret(None)
    else:
        assert resolve_interpret(None) is want


_HIST_CONTRACTIONS = {
    "finalize_kernel": lambda p, b, lam, pe, ed: jax.jit(
        lambda *a: ops.scd_finalize_hist(*a, 1, tile_n=128)).lower(
            p, b, lam, pe),
    "finalize_ref": lambda p, b, lam, pe, ed: jax.jit(
        lambda *a: ref.scd_finalize_ref(*a, 1)).lower(p, b, lam, pe),
    "bucket_hist_ref": lambda p, b, lam, pe, ed: jax.jit(
        ref.bucket_hist_ref).lower(p, b, ed),
}


@pytest.mark.parametrize("which", sorted(_HIST_CONTRACTIONS))
def test_histogram_contractions_pin_highest_precision(which):
    """One-hot histogram matmuls carry precision=HIGHEST in their lowered
    HLO: at DEFAULT a TPU feeds their f32 consumption / profit operands to
    the MXU as bf16, which the CPU cannot show numerically."""
    p, b, lam = _inst(256, 10, jnp.float32)
    pedges = jnp.linspace(-1.0, 1.0, 7, dtype=jnp.float32)
    edges = jnp.tile(pedges, (10, 1))
    text = _HIST_CONTRACTIONS[which](p, b, lam, pedges, edges).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots, "expected the histogram contraction in the lowered HLO"
    assert all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots), dots


def test_chip_smoke_precision_phase_runs_interpreted(capsys):
    """chip_smoke.py phase (f), the on-chip bf16 check of the histogram
    contractions, runs end to end under the interpreter: its float64
    references agree with the kernels wherever operands stay f32."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    smoke.phase_precision(jax.devices()[0], n=1024)
    out = capsys.readouterr().out
    assert out.count("PASS") == 4, out
    assert "control: the same contraction at DEFAULT precision reads rel " \
        "0.000e+00" in out, out
