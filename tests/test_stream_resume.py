"""Preemption-safe sharded host streaming: checkpoint/resume equivalence.

The contract under test (core/prefetch.py, DESIGN.md §7): a host-fed
solve with ``cfg.checkpoint_every`` writes a constant-size resume state
atomically; killing the process at ANY point — mid iterate epoch, mid
save (torn ``.tmp``), between finalize chunks — and relaunching with
``resume_from=`` yields bitwise the uninterrupted ``lam/iters/r/primal/
dual/tau`` and the same fused-finalize histograms, on the same mesh or
any mesh whose device count divides the checkpoint's virtual-slot
count. The subprocess test at the bottom actually SIGKILLs the first
process on 8 virtual devices and resumes on 8 and on 4.
"""
import os
import pathlib
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.core import SolverConfig
from repro.core.chunked import ordered_fold
from repro.core.instances import shard_key, sparse_instance
from repro.core.prefetch import (
    host_array_source,
    sharded_source,
    solve_streaming_host,
)

jax.config.update("jax_platform_name", "cpu")

REPO = pathlib.Path(__file__).resolve().parent.parent

RESULT_FIELDS = ["lam", "iters", "r", "primal", "dual", "tau"]


def _instance(n=2048, k=8, chunk=128, seed=4):
    kp, q = sparse_instance(shard_key(seed), n=n, k=k, q=2, tightness=0.4)
    p, b = np.asarray(kp.p), np.asarray(kp.b)
    bud = np.asarray(kp.budgets)
    return (lambda: host_array_source(p, b, bud, chunk)), q


class _Kill(Exception):
    """In-process stand-in for preemption: raised from the source fn."""


def _killing(make_source, after):
    """Source whose fn raises _Kill after ``after`` chunk productions."""
    src = make_source()
    calls = {"n": 0}
    inner = src.fn

    def fn(i):
        calls["n"] += 1
        if calls["n"] > after:
            raise _Kill()
        return inner(i)

    return src._replace(fn=fn), calls


def _counting(make_source):
    src = make_source()
    calls = {"n": 0}
    inner = src.fn

    def fn(i):
        calls["n"] += 1
        return inner(i)

    return src._replace(fn=fn), calls


def _assert_bitwise(a, b, hists=True):
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    if hists:
        assert (a.fin_hist is None) == (b.fin_hist is None)
        if a.fin_hist is not None:
            for x, y in zip(a.fin_hist, b.fin_hist):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# sharded_source: the per-slot chunk-range splitter.
# ---------------------------------------------------------------------------

def test_sharded_source_splits_chunk_ranges():
    make, _ = _instance(n=1000, chunk=128)      # c = 8 ragged chunks
    src = make()
    subs = sharded_source(src, 4)               # cps = 2
    assert len(subs) == 4
    for s, sub in enumerate(subs):
        assert sub.chunk == 128 and sub.k == src.k
        np.testing.assert_array_equal(sub.budgets, src.budgets)
        for j in range(2):
            p, b = sub.fn(j)
            pg, bg = src.fn(2 * s + j)
            np.testing.assert_array_equal(p, pg)
            np.testing.assert_array_equal(b, bg)
    # Row ownership covers n exactly, in order.
    assert sum(sub.n for sub in subs) == 1000
    # Past the last real chunk: inert zeros (the traced padded-index
    # contract — those chunks still run, so they must exist).
    over = sharded_source(src, 8)               # cps = 1, slot 7 empty... c=8
    p, b = over[7].fn(1)                        # global chunk 8 >= c
    assert not p.any() and not b.any() and p.shape == (128, src.k)
    with pytest.raises(ValueError, match="slots"):
        sharded_source(src, 0)


# ---------------------------------------------------------------------------
# Validation: config/topology errors are actionable.
# ---------------------------------------------------------------------------

def test_checkpoint_and_slot_validation(tmp_path):
    make, q = _instance()
    with pytest.raises(ValueError, match="record_history"):
        solve_streaming_host(
            make(), SolverConfig(checkpoint_every=2, record_history=True,
                                 metrics_every=2),
            q=q, checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="multiple"):
        solve_streaming_host(
            make(), SolverConfig(),
            q=q, mesh=jax.make_mesh((1,), ("d",)), slots=0)
    with pytest.raises(ValueError, match="fused"):
        solve_streaming_host(make(), SolverConfig(stream_finalize="legacy"),
                             q=q, slots=4)


def test_resume_empty_dir_is_fresh_start(tmp_path):
    make, q = _instance()
    cfg = SolverConfig(reduce="bucketed", max_iters=15, checkpoint_every=2)
    base = solve_streaming_host(make(), cfg.replace(checkpoint_every=0),
                                q=q, slots=4)
    res = solve_streaming_host(make(), cfg, q=q, slots=4,
                               resume_from=str(tmp_path))
    _assert_bitwise(res, base)
    assert ckpt.latest_step(tmp_path) is not None   # and it checkpoints there


def test_resume_fingerprint_mismatch_refused(tmp_path):
    make, q = _instance(seed=4)
    other, _ = _instance(seed=5)
    cfg = SolverConfig(reduce="bucketed", max_iters=15, checkpoint_every=2)
    solve_streaming_host(make(), cfg, q=q, slots=4,
                         checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="fingerprint"):
        solve_streaming_host(other(), cfg, q=q, resume_from=str(tmp_path))
    with pytest.raises(ValueError, match="slots"):
        solve_streaming_host(make(), cfg, q=q, slots=8,
                             resume_from=str(tmp_path))


def test_checkpoint_keep_is_configurable(tmp_path):
    """Satellite: cfg.checkpoint_keep reaches ckpt.prune — the retention
    is a knob, not the hardcoded 3 — and a pruned-to-one directory still
    resumes (the newest state is always complete before pruning)."""
    make, q = _instance()
    base = solve_streaming_host(make(), SolverConfig(reduce="bucketed",
                                                     max_iters=20),
                                q=q, slots=4)

    def steps(d):
        return sorted(p.name for p in pathlib.Path(d).iterdir()
                      if p.name.startswith("step_")
                      and not p.name.endswith(".tmp"))

    for keep in (1, 2):
        d = tmp_path / f"keep{keep}"
        cfg = SolverConfig(reduce="bucketed", max_iters=20,
                           checkpoint_every=1, checkpoint_keep=keep)
        res = solve_streaming_host(make(), cfg, q=q, slots=4,
                                   checkpoint_dir=str(d))
        _assert_bitwise(res, base)
        assert len(steps(d)) == keep, steps(d)
    # Default retention unchanged: 3 states on disk.
    d3 = tmp_path / "default"
    solve_streaming_host(
        make(), SolverConfig(reduce="bucketed", max_iters=20,
                             checkpoint_every=1),
        q=q, slots=4, checkpoint_dir=str(d3))
    assert len(steps(d3)) == 3, steps(d3)
    with pytest.raises(ValueError, match="checkpoint_keep"):
        solve_streaming_host(
            make(), SolverConfig(checkpoint_every=1, checkpoint_keep=0),
            q=q, checkpoint_dir=str(tmp_path / "zero"))
    # Killed mid-solve with keep=1: the single retained state resumes
    # bitwise (pruning never races the newest complete step away).
    dk = tmp_path / "keep1_kill"
    cfgk = SolverConfig(reduce="bucketed", max_iters=20,
                        checkpoint_every=2, checkpoint_keep=1)
    src, _ = _killing(make, 70)
    with pytest.raises(_Kill):
        solve_streaming_host(src, cfgk, q=q, slots=4,
                             checkpoint_dir=str(dk))
    assert len(steps(dk)) == 1
    res = solve_streaming_host(make(), cfgk, q=q, resume_from=str(dk))
    _assert_bitwise(res, base)


# ---------------------------------------------------------------------------
# Corrupted checkpoint directories: loud, actionable, never a silent
# fresh start when a manifest exists.
# ---------------------------------------------------------------------------

def _checkpointed_dir(make, q, d):
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=2)
    solve_streaming_host(make(), cfg, q=q, slots=4, checkpoint_dir=str(d))
    return cfg


def test_truncated_manifest_raises_actionable(tmp_path):
    """A present-but-unparseable manifest is corruption, not 'no
    checkpoint': latest_step still reports the step and the restore
    raises an error naming the file — resuming must never silently
    discard the run."""
    make, q = _instance()
    cfg = _checkpointed_dir(make, q, tmp_path)
    latest = ckpt.latest_step(tmp_path)
    mpath = tmp_path / f"step_{latest:08d}" / "manifest.json"
    mpath.write_text(mpath.read_text()[: len(mpath.read_text()) // 2])
    assert ckpt.latest_step(tmp_path) == latest      # still visible
    with pytest.raises(ValueError, match="manifest.*corrupt|truncated"):
        ckpt.restore_auto(tmp_path, latest)
    with pytest.raises(ValueError, match="could not restore"):
        solve_streaming_host(make(), cfg, q=q, resume_from=str(tmp_path))


def test_missing_leaf_file_raises_actionable(tmp_path):
    make, q = _instance()
    cfg = _checkpointed_dir(make, q, tmp_path)
    latest = ckpt.latest_step(tmp_path)
    step_dir = tmp_path / f"step_{latest:08d}"
    victim = sorted(step_dir.glob("arr_*.npy"))[2]
    victim.unlink()
    with pytest.raises(ValueError, match=victim.name):
        ckpt.restore_auto(tmp_path, latest)
    with pytest.raises(ValueError, match="could not restore"):
        solve_streaming_host(make(), cfg, q=q, resume_from=str(tmp_path))


def test_corrupt_leaf_bytes_raise_actionable(tmp_path):
    make, q = _instance()
    _checkpointed_dir(make, q, tmp_path)
    latest = ckpt.latest_step(tmp_path)
    step_dir = tmp_path / f"step_{latest:08d}"
    victim = sorted(step_dir.glob("arr_*.npy"))[0]
    victim.write_bytes(victim.read_bytes()[:16])     # truncated .npy
    with pytest.raises(ValueError, match="unreadable"):
        ckpt.restore_auto(tmp_path, latest)


def test_stale_tmp_only_is_fresh_start(tmp_path):
    """A directory holding nothing but .tmp debris (killed first save)
    genuinely has no checkpoint: latest_step is None and the solve
    starts fresh — and the stale .tmp is pruned by the next save."""
    make, q = _instance()
    stale = tmp_path / "step_00000004.tmp"
    stale.mkdir(parents=True)
    (stale / "manifest.json").write_text('{"truncat')
    assert ckpt.latest_step(tmp_path) is None
    cfg = SolverConfig(reduce="bucketed", max_iters=15, checkpoint_every=2)
    base = solve_streaming_host(make(), cfg.replace(checkpoint_every=0),
                                q=q, slots=4)
    res = solve_streaming_host(make(), cfg, q=q, slots=4,
                               resume_from=str(tmp_path))
    _assert_bitwise(res, base)
    assert not stale.exists(), "prune should sweep stale .tmp debris"


def test_missing_manifest_dir_is_not_a_step(tmp_path):
    """A step-named directory without any manifest was not written by
    this layer (the atomic rename publishes the manifest with the step):
    it is ignored by latest_step, and restoring it by explicit step
    number says why."""
    bogus = tmp_path / "step_00000007"
    bogus.mkdir(parents=True)
    assert ckpt.latest_step(tmp_path) is None
    with pytest.raises(ValueError, match="no manifest.json"):
        ckpt.restore_auto(tmp_path, 7)


def test_pointer_document_corruption_raises(tmp_path):
    assert ckpt.read_json(tmp_path, "LIVE.json") is None
    ckpt.write_json(tmp_path, "LIVE.json", {"gen": 3})
    assert ckpt.read_json(tmp_path, "LIVE.json") == {"gen": 3}
    (tmp_path / "LIVE.json").write_text('{"gen"')
    with pytest.raises(ValueError, match="corrupt"):
        ckpt.read_json(tmp_path, "LIVE.json")


# ---------------------------------------------------------------------------
# Kill + resume: bitwise equivalence at every interruption point.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slots", [1, 8])
def test_kill_mid_iterate_resume_bitwise(tmp_path, slots):
    """Interrupt inside an iterate epoch (accumulators half-built) and
    resume: the replayed iteration re-runs from the last iteration
    boundary, so the final result is bitwise the uninterrupted one."""
    make, q = _instance()
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=2)
    base = solve_streaming_host(make(), cfg, q=q, slots=slots)
    src, _ = _killing(make, 70)                  # mid epoch ~3 of 16-chunk passes
    with pytest.raises(_Kill):
        solve_streaming_host(src, cfg, q=q, slots=slots,
                             checkpoint_dir=str(tmp_path))
    assert ckpt.latest_step(tmp_path) is not None
    res = solve_streaming_host(make(), cfg, q=q, resume_from=str(tmp_path))
    _assert_bitwise(res, base)


def test_kill_between_finalize_chunks_no_double_count(tmp_path):
    """Satellite: kill between chunks of the fused finalize pass, resume
    from the mid-pass cursor, and verify no chunk's contribution is
    double-counted — the resumed run consumes exactly the not-yet-folded
    columns and reproduces the histograms bit for bit."""
    make, q = _instance(n=2048, chunk=64)        # c = 32, cps = 4 at slots=8
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=1)
    base = solve_streaming_host(make(), cfg, q=q, slots=8)
    iters = int(base.iters)
    cols = 4                                     # cps
    # land between finalize columns: after 2.5 columns of the last pass
    kill_at = 1 + iters * 32 + 2 * 8 + 4         # fp probe + epochs + 2.5 cols
    src, _ = _killing(make, kill_at)
    with pytest.raises(_Kill):
        solve_streaming_host(src, cfg, q=q, slots=8,
                             checkpoint_dir=str(tmp_path))
    latest = ckpt.latest_step(tmp_path)
    assert latest > cfg.max_iters + 1            # a MID-finalize state
    state = ckpt.restore_auto(tmp_path, latest)
    cursor = int(np.asarray(state["cursor"]))
    assert 0 < cursor < cols
    src2, calls = _counting(make)
    res = solve_streaming_host(src2, cfg, q=q, resume_from=str(tmp_path))
    _assert_bitwise(res, base)
    # fingerprint probe + exactly the remaining columns, nothing replayed
    assert calls["n"] == 1 + (cols - cursor) * 8


def test_torn_save_ignored_and_resume_from_previous(tmp_path):
    """Satellite: crash mid-save. os.replace raises after the .tmp write,
    leaving a torn directory; restore ignores it and resumes from the
    previous step to a bitwise-identical result."""
    make, q = _instance()
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=2)
    base = solve_streaming_host(make(), cfg, q=q, slots=8)

    real_replace = os.replace
    n_ok = {"n": 0}

    def torn_replace(a, b):
        if n_ok["n"] >= 2:                      # third save dies mid-rename
            raise OSError("simulated crash during atomic rename")
        n_ok["n"] += 1
        return real_replace(a, b)

    ckpt.os.replace = torn_replace
    try:
        with pytest.raises(OSError, match="simulated crash"):
            solve_streaming_host(make(), cfg, q=q, slots=8,
                                 checkpoint_dir=str(tmp_path))
    finally:
        ckpt.os.replace = real_replace
    # The torn step exists only as .tmp; latest_step skips it.
    torn = [p.name for p in tmp_path.iterdir() if p.name.endswith(".tmp")]
    assert torn, "the interrupted save should have left a .tmp directory"
    latest = ckpt.latest_step(tmp_path)
    assert f"step_{latest:08d}.tmp" not in torn  # torn step > restored step
    res = solve_streaming_host(make(), cfg, q=q, resume_from=str(tmp_path))
    _assert_bitwise(res, base)


def test_resume_on_one_device_mesh_from_slots8(tmp_path):
    """Degraded-to-one-device resume in process: the slot partials are
    mesh-independent, so even D=1 reproduces the slots=8 run bitwise."""
    make, q = _instance()
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=3)
    base = solve_streaming_host(make(), cfg, q=q, slots=8)
    src, _ = _killing(make, 100)
    with pytest.raises(_Kill):
        solve_streaming_host(src, cfg, q=q, slots=8,
                             checkpoint_dir=str(tmp_path))
    res = solve_streaming_host(
        make(), cfg, q=q, resume_from=str(tmp_path),
        mesh=jax.make_mesh((1,), ("d",)))
    _assert_bitwise(res, base)


def test_checkpointed_run_matches_uncheckpointed_bitwise(tmp_path):
    """Checkpointing itself (the save synchronisation points) must not
    perturb the solve."""
    make, q = _instance()
    for slots in (1, 8):
        cfg = SolverConfig(reduce="bucketed", max_iters=20)
        base = solve_streaming_host(make(), cfg, q=q, slots=slots)
        res = solve_streaming_host(
            make(), cfg.replace(checkpoint_every=1), q=q, slots=slots,
            checkpoint_dir=str(tmp_path / f"s{slots}"))
        _assert_bitwise(res, base)


def test_ordered_fold_pins_addition_order():
    rng = np.random.default_rng(0)
    x = np.asarray(rng.uniform(0.1, 1.0, (8, 10, 50)), np.float32) * 1.000123
    acc = x[0].copy()
    for i in range(1, 8):
        acc = (acc + x[i]).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(ordered_fold)(x)), acc)


# ---------------------------------------------------------------------------
# The acceptance bar: SIGKILL a real 8-virtual-device solve, resume on the
# same mesh and on a degraded 4-device mesh (subprocess).
# ---------------------------------------------------------------------------

_KILL_RESUME_SCRIPT = textwrap.dedent("""
    import os, signal, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core import SolverConfig
    from repro.core.instances import shard_key, sparse_instance
    from repro.core.prefetch import host_array_source, solve_streaming_host

    mode, ndev, kill_after, ckpt_dir, out = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
        sys.argv[5])
    kp, q = sparse_instance(shard_key(4), n=2048, k=8, q=2, tightness=0.4)
    p, b = np.asarray(kp.p), np.asarray(kp.b)
    bud = np.asarray(kp.budgets)
    src = host_array_source(p, b, bud, 64)          # c = 32, cps = 4
    if mode == "kill":
        calls = {"n": 0}
        inner = src.fn
        def fn(i):
            calls["n"] += 1
            if calls["n"] > kill_after:
                os.kill(os.getpid(), signal.SIGKILL)
            return inner(i)
        src = src._replace(fn=fn)
    mesh = jax.make_mesh((ndev,), ("users",), devices=jax.devices()[:ndev])
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=1)
    res = solve_streaming_host(
        src, cfg, q=q, mesh=mesh, slots=8,
        checkpoint_dir=ckpt_dir if mode != "resume" else None,
        resume_from=ckpt_dir if mode == "resume" else None)
    np.savez(out, lam=np.asarray(res.lam), iters=np.asarray(res.iters),
             dual=np.asarray(res.dual), r=np.asarray(res.r),
             primal=np.asarray(res.primal), tau=np.asarray(res.tau),
             ch=np.asarray(res.fin_hist[0]), gh=np.asarray(res.fin_hist[1]))
    print("RESULT-OK", int(res.iters))
""")


def _run_script(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run([sys.executable, "-c", _KILL_RESUME_SCRIPT] + args,
                          env=env, capture_output=True, text=True,
                          timeout=timeout, cwd=str(REPO))


@pytest.mark.slow
@pytest.mark.parametrize("kill_after", [50, 300])   # mid-iterate / late
def test_sigkill_and_resume_subprocess(tmp_path, kill_after):
    """An 8-virtual-device host-fed solve SIGKILLed at an arbitrary point
    and resumed — on the same mesh AND on a 4-device degraded mesh —
    returns bitwise-identical lam/iters/dual (and every other field, and
    the fused-finalize histograms) to the uninterrupted run."""
    ref = tmp_path / "ref.npz"
    out = _run_script(["ref", "8", "0", str(tmp_path / "unused"), str(ref)])
    assert out.returncode == 0, out.stdout + out.stderr
    assert "RESULT-OK" in out.stdout

    ck = tmp_path / "ck"
    killed = _run_script(["kill", "8", str(kill_after), str(ck), "x"])
    assert killed.returncode == -signal.SIGKILL, (
        killed.returncode, killed.stdout, killed.stderr)
    assert ckpt.latest_step(ck) is not None

    want = np.load(ref)
    for ndev in (8, 4):
        got_path = tmp_path / f"resumed_{ndev}.npz"
        res = _run_script(["resume", str(ndev), "0", str(ck), str(got_path)])
        assert res.returncode == 0, res.stdout + res.stderr
        got = np.load(got_path)
        for key in ["lam", "iters", "dual", "r", "primal", "tau", "ch", "gh"]:
            np.testing.assert_array_equal(got[key], want[key],
                                          err_msg=f"ndev={ndev} {key}")


_TRACED_PARITY_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core import SolverConfig
    from repro.core.chunked import array_source, solve_streaming
    from repro.core.instances import shard_key, sparse_instance
    from repro.core.prefetch import host_array_source, solve_streaming_host

    kp, q = sparse_instance(shard_key(4), n=2048, k=8, q=2, tightness=0.4)
    p, b = np.asarray(kp.p), np.asarray(kp.b)
    bud = np.asarray(kp.budgets)
    mesh = jax.make_mesh((8,), ("users",))
    FIELDS = ["lam", "iters", "r", "primal", "dual", "tau"]

    for cfg in [SolverConfig(reduce="bucketed", max_iters=20),
                SolverConfig(algo="dd", max_iters=10, dd_lr=2e-3),
                SolverConfig(reduce="bucketed", max_iters=12,
                             partial_fraction=0.5),
                SolverConfig(reduce="bucketed", max_iters=20,
                             record_history=True, metrics_every=3)]:
        traced = solve_streaming(array_source(kp, 128), cfg, q=q, mesh=mesh)
        host = solve_streaming_host(host_array_source(p, b, bud, 128), cfg,
                                    q=q, mesh=mesh)
        for f in FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(host, f)), np.asarray(getattr(traced, f)),
                err_msg=f"{cfg.algo}/{cfg.partial_fraction} {f}")
        if cfg.record_history:
            for key in traced.history:
                np.testing.assert_array_equal(
                    np.asarray(host.history[key]),
                    np.asarray(traced.history[key]), err_msg=key)
    print("PARITY-OK")
""")


@pytest.mark.slow
def test_host_sharded_matches_traced_sharded_subprocess(tmp_path):
    """Tentpole contract: the host-fed sharded driver is bit-identical
    field-for-field to the traced shard_map driver on 8 virtual devices —
    SCD, DD, straggler scaling and sampled history alike."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", _TRACED_PARITY_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=900, cwd=str(REPO))
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "PARITY-OK" in out.stdout


# ---------------------------------------------------------------------------
# Screening-inertness digest + the screening/resume interplay.
# ---------------------------------------------------------------------------

# sha256 over RESULT_FIELDS of the 8-virtual-device sharded solve on the
# seeded fixture below. The digest is compared across runs of one
# process, never against a recorded value: its NumPy instance bytes are
# fixed, but the solve's f32 arithmetic moves with the XLA build, so a
# recorded pin breaks on every JAX upgrade. Screening on must reproduce
# the unscreened bytes on this uniform workload (its chunk ratio maxima
# never clear the bucket ladder, so the active set never shrinks), and
# the 8 slots on one device must reproduce them on eight (mesh-size
# invariance of the host-fed sharded driver).
_GOLDEN_SHARDED_SCRIPT = textwrap.dedent("""
    import hashlib, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core import SolverConfig
    from repro.core.prefetch import solve_streaming_host
    from repro.data.synth import sparse_host_chunk_source

    def digest(res):
        h = hashlib.sha256()
        for f in ("lam", "iters", "r", "primal", "dual", "tau"):
            h.update(np.asarray(getattr(res, f)).tobytes())
        return h.hexdigest()

    src = sparse_host_chunk_source(4, 2048, 8, 128, q=2, tightness=0.5)
    cfg = SolverConfig(reduce="bucketed", max_iters=40)
    mesh = jax.make_mesh((8,), ("users",))
    res = solve_streaming_host(src, cfg, q=2, mesh=mesh, slots=8)
    print("PLAIN", digest(res))
    scr = solve_streaming_host(src, cfg.replace(screening=True), q=2,
                               mesh=mesh, slots=8)
    assert bool(scr.screen["active"].all())
    print("SCREENED", digest(scr))
    one = solve_streaming_host(
        src, cfg, q=2, mesh=jax.make_mesh((1,), ("users",),
                                          devices=jax.devices()[:1]),
        slots=8)
    print("ONE-DEVICE", digest(one))
""")


@pytest.mark.slow
def test_sharded_golden_digest_unchanged():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", _GOLDEN_SHARDED_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=900, cwd=str(REPO))
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    got = dict(line.split() for line in out.stdout.splitlines()
               if line.split()[:1] in (["PLAIN"], ["SCREENED"],
                                       ["ONE-DEVICE"]))
    assert set(got) == {"PLAIN", "SCREENED", "ONE-DEVICE"}, out.stdout
    assert got["SCREENED"] == got["PLAIN"], out.stdout
    assert got["ONE-DEVICE"] == got["PLAIN"], out.stdout


def test_resume_across_screening_toggle_bitwise(tmp_path):
    """cfg.screening is resume-fingerprint-EXEMPT (it never steers the
    trajectory): a checkpoint written unscreened resumes under
    screening=True — and vice versa — bitwise. The end-to-end twin of
    test_fingerprint_fields.py's field-coverage guard."""
    make, q = _instance()
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=2)
    base = solve_streaming_host(make(), cfg, q=q, slots=4)

    d1 = tmp_path / "off_to_on"
    src, _ = _killing(make, 70)
    with pytest.raises(_Kill):
        solve_streaming_host(src, cfg, q=q, slots=4,
                             checkpoint_dir=str(d1))
    res = solve_streaming_host(make(), cfg.replace(screening=True), q=q,
                               resume_from=str(d1))
    _assert_bitwise(res, base)
    assert res.screen is not None

    d2 = tmp_path / "on_to_off"
    src, _ = _killing(make, 70)
    with pytest.raises(_Kill):
        solve_streaming_host(src, cfg.replace(screening=True), q=q,
                             slots=4, checkpoint_dir=str(d2))
    res = solve_streaming_host(make(), cfg, q=q, resume_from=str(d2))
    _assert_bitwise(res, base)
    assert res.screen is None
