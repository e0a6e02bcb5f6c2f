"""Tests for repro/launch/supervisor.py — the elastic supervision layer.

The coordinator state machine is exercised with *scripted fake workers*
(``worker_cmd`` override): tiny ``python -c`` subprocesses that speak
the lease file format directly without importing jax, so crash
restarts, lease-expiry hang takeovers, chaos injection, device
degradation and crash-loop containment all run in well under a second
of worker time each. One slow end-to-end test runs a real supervised
solve worker and pins the published record bitwise against an
in-process reference; the full soak (kills + stops + bitwise refresh
parity) is the ``--chaos-soak`` CI gate.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.launch.supervisor import (
    ChaosSchedule,
    Supervisor,
    SupervisorConfig,
    run_solve_task,
)
from repro.serve.engine import WorkloadSpec

# A scripted worker that renews leases without importing repro (or jax):
# argv = [python, -c, _FAKE, root, term, mode]. Modes:
#   ok            beat a few times, exit 0
#   crash-once    exit 5 in term 1, behave like "ok" afterwards
#   hang          beat once, then stop beating (SIGSTOP-shaped) forever
#   crash-always  exit 7 immediately
#   work          bump progress forever (chaos-injection target) in term
#                 1, behave like "ok" afterwards
_FAKE = r"""
import hashlib, json, os, sys, time
root, term, mode = sys.argv[1], int(sys.argv[2]), sys.argv[3]
path = os.path.join(root, "heartbeat.json")
seq = 0
def beat(progress):
    global seq
    seq += 1
    rec = dict(worker="fake", pid=os.getpid(), term=term, seq=seq,
               progress=progress, ttl=0.5, mono=time.monotonic(),
               wall=time.time())
    payload = json.dumps(rec, sort_keys=True).encode()
    data = payload + b"\n" + hashlib.sha256(payload).hexdigest().encode() \
        + b"\n"
    tmp = path + ".wtmp"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
if mode == "crash-always":
    sys.exit(7)
if mode == "crash-once" and term == 1:
    beat(0)
    sys.exit(5)
if mode == "hang" and term == 1:
    beat(0)
    time.sleep(3600)
if mode == "work" and term == 1:
    p = 0
    while True:
        p += 1
        beat(p)
        time.sleep(0.02)
for i in range(3):
    beat(i)
    time.sleep(0.05)
sys.exit(0)
"""


def _fake_cmd(mode):
    def cmd(root, term, devices):
        return [sys.executable, "-c", _FAKE, str(root), str(term), mode]
    return cmd


def _cfg(**kw):
    base = dict(ttl=0.4, poll=0.02, grace=5.0, max_restarts=4)
    base.update(kw)
    return SupervisorConfig(**base)


def test_clean_completion_publishes_done_status(tmp_path):
    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=4,
                     worker_cmd=_fake_cmd("ok"))
    out = sup.run()
    assert out["ok"] and out["spawns"] == 1 and out["restarts"] == 0
    status = ckpt.read_json(tmp_path, "SUPERVISOR.json")
    assert status["state"] == "done" and status["ok"]
    # The durable task intent was written before the first spawn.
    assert ckpt.read_json(tmp_path, "task.json") == {"kind": "noop",
                                                     "ttl": 0.4}


def test_crash_restart_resumes_on_degraded_devices(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    seen = []

    def cmd(root, term, devices):
        seen.append((term, devices))
        return [sys.executable, "-c", _FAKE, str(root), str(term),
                "crash-once"]

    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=4,
                     worker_cmd=cmd)
    out = sup.run()
    assert out["ok"] and out["crash_restarts"] == 1
    assert out["last_rc"] == 5
    assert out["degraded_spawns"] == 1
    assert seen == [(1, 4), (2, 2)], "respawn must halve the devices"
    # The respawn env forces the degraded device count on the CPU child.
    env2 = sup._env(2)
    assert env2["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=2" in env2["XLA_FLAGS"]


def test_accelerator_respawn_keeps_the_devices_it_has(tmp_path, monkeypatch):
    """A non-CPU worker cannot be given fewer chips from outside: no
    degraded respawn is counted, and ``devices`` is the count the worker
    reported, not the slot count asked for."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    seen = []

    def cmd(root, term, devices):
        seen.append((term, devices))
        # What the worker's JAX would announce once its backend is up.
        ckpt.write_json(root, "worker.json", {
            "term": term, "platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1})
        return [sys.executable, "-c", _FAKE, str(root), str(term),
                "crash-once"]

    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=4,
                     worker_cmd=cmd)
    out = sup.run()
    assert out["ok"] and out["crash_restarts"] == 1
    assert seen == [(1, 4), (2, 4)], "an accelerator respawn never degrades"
    assert out["degraded_spawns"] == 0
    assert out["devices"] == 1 == out["worker"]["device_count"]
    assert "--xla_force_host_platform_device_count" not in \
        sup._env(2).get("XLA_FLAGS", "")
    status = ckpt.read_json(tmp_path, "SUPERVISOR.json")
    assert status["devices"] == 1 and status["degraded_spawns"] == 0


def test_hang_detected_by_lease_expiry_and_taken_over(tmp_path):
    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=2,
                     worker_cmd=_fake_cmd("hang"))
    t0 = time.monotonic()
    out = sup.run()
    took = time.monotonic() - t0
    assert out["ok"] and out["hang_takeovers"] == 1
    assert out["crash_restarts"] == 0, "a hang is not an exit-code crash"
    # Detected by lease expiry within the deadline, not by luck: the
    # takeover must land shortly after ttl, far under the fake's sleep.
    assert took < 30.0
    # The adoption was exclusively claimed at term 2.
    assert (tmp_path / "heartbeat.json.claim_00000002").exists()


def test_chaos_kill_fires_at_progress_threshold(tmp_path):
    sched = ChaosSchedule(seed=0, events=(("kill", 5),))
    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=2,
                     worker_cmd=_fake_cmd("work"), chaos=sched)
    out = sup.run()
    assert out["ok"]
    assert out["kills_injected"] == 1 and out["crash_restarts"] == 1


def test_chaos_stop_detected_as_hang(tmp_path):
    sched = ChaosSchedule(seed=0, events=(("stop", 5),))
    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=2,
                     worker_cmd=_fake_cmd("work"), chaos=sched)
    out = sup.run()
    assert out["ok"]
    assert out["stops_injected"] == 1
    assert out["hang_takeovers"] == 1, \
        "a SIGSTOPped worker must surface via lease expiry"


def test_crash_loop_budget_stamps_failed_and_stops(tmp_path):
    sup = Supervisor(tmp_path, {"kind": "noop"},
                     cfg=_cfg(max_restarts=2), devices=4,
                     worker_cmd=_fake_cmd("crash-always"))
    out = sup.run()
    assert not out["ok"]
    assert out["crash_restarts"] == 3          # initial + 2 budgeted
    failed = ckpt.read_json(tmp_path, "FAILED.json")
    assert failed is not None
    assert "budget" in failed["reason"]
    status = ckpt.read_json(tmp_path, "SUPERVISOR.json")
    assert status["state"] == "failed"


def test_schedule_plan_is_deterministic_and_interleaved():
    a = ChaosSchedule.plan(7, kills=2, stops=1, lo=10, hi=50)
    b = ChaosSchedule.plan(7, kills=2, stops=1, lo=10, hi=50)
    assert a.events == b.events
    kinds = [k for k, _ in a.events]
    assert kinds == ["kill", "stop", "kill"]
    assert all(10 <= at < 50 for _, at in a.events)
    assert a.events != ChaosSchedule.plan(8, 2, 1, 10, 50).events


def test_poisoned_worker_exits_before_heavy_imports(tmp_path):
    # The real --worker entry point, poisoned: must exit with the poison
    # code fast (it runs before any jax import) and never read task.json.
    env = dict(os.environ)
    env["REPRO_WORKER_POISON"] = "3"
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    rc = subprocess.run(
        [sys.executable, "-m", "repro.launch.supervisor",
         "--worker", str(tmp_path), "--term", "1"],
        env=env, timeout=60).returncode
    assert rc == 3


def test_next_term_skips_debris_from_previous_coordinators(tmp_path):
    sup = Supervisor(tmp_path, {"kind": "noop"}, cfg=_cfg(), devices=1,
                     worker_cmd=_fake_cmd("ok"))
    assert sup._next_term() == 1
    # Claim debris from a dead coordinator advances the term.
    (tmp_path / "heartbeat.json.claim_00000004").write_text("1\n")
    assert sup._next_term() == 5
    out = sup.run()                        # must claim term 5, not term 1
    assert out["ok"] and out["term"] == 5


@pytest.mark.slow
def test_supervised_solve_matches_inprocess_reference(tmp_path):
    """End to end with a real worker subprocess: the supervised result
    record is bitwise the in-process one."""
    spec = WorkloadSpec(seed=3, n=1024, k=4, chunk=256, q=1,
                        tightness=0.5)
    cfg = dict(reduce="bucketed", max_iters=12, checkpoint_every=4,
               bucket_half=16)
    task = {"kind": "solve", "spec": spec.to_json(), "cfg": cfg,
            "slots": 2}
    ref = run_solve_task(tmp_path / "ref", task)
    sup = Supervisor(tmp_path / "sup", task,
                     cfg=SupervisorConfig(ttl=5.0, poll=0.1, grace=300.0,
                                          max_restarts=2),
                     devices=1)
    out = sup.run()
    assert out["ok"], out
    # The worker reported the platform its own JAX came up on.
    import jax
    assert out["worker"]["term"] == 1
    assert out["worker"]["platform"] == jax.devices()[0].platform
    assert ckpt.read_json(tmp_path / "sup", "SUPERVISOR.json")["worker"] \
        == out["worker"]
    got = ckpt.restore_auto(tmp_path / "sup" / "result", 0)
    for f in ["lam", "tau", "iters", "r", "primal", "dual"]:
        assert np.asarray(ref[f]).tobytes() \
            == np.asarray(got[f]).tobytes(), f


def test_supervise_parent_never_initialises_jax(tmp_path):
    """A chip belongs to one process: the ``--supervise`` coordinator
    leaves the JAX backend to its worker and never initialises one."""
    script = (
        "import sys\n"
        "from repro.launch import supervisor\n"
        "sys.argv = ['supervisor', '--supervise', 'solve', '--users', "
        "'2048', '--chunk', '512', '--max-iters', '5', '--slots', '1', "
        f"'--root', {str(tmp_path)!r}]\n"
        "try:\n"
        "    supervisor.main()\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0, e.code\n"
        "from jax._src import xla_bridge\n"
        "print('PARENT-INITIALISED', xla_bridge.backends_are_initialized())\n")
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PARENT-INITIALISED False" in out.stdout, out.stdout
    status = ckpt.read_json(tmp_path, "SUPERVISOR.json")
    assert status["ok"] and status["worker"]["term"] == 1
