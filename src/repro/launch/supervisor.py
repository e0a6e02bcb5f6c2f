"""Elastic supervision: heartbeat-leased workers, hang detection, self-healing.

PR 4 proved the *mechanism* — a SIGKILLed sharded solve resumes bitwise,
even on a degraded mesh — but an operator still had to notice the death
and relaunch. This launcher closes that loop. A solve or a
multi-generation refresh runs as a supervised **worker subprocess**
(``--worker``) that renews an fsync'd heartbeat lease
(:mod:`repro.core.heartbeat`) alongside its normal checkpoint cadence,
while the coordinator (:class:`Supervisor`) watches two signals:

* **exit codes** — a crashed worker (SIGKILL, OOM, a bug) is respawned
  with the same task file; the solver's resume protocol re-drives it
  from the last durable checkpoint, so the eventual result is bitwise
  the undisturbed one;
* **lease expiry** — a *hung* worker (SIGSTOP-shaped: every thread
  frozen, so the renewer stops; or stuck-fetch-shaped via the optional
  progress deadline) is detected when its lease stops advancing for
  ``ttl`` seconds of the coordinator's own clock, exclusively adopted
  (:func:`repro.core.heartbeat.claim_takeover`), killed, and respawned.

Each respawn may run on a **degraded device count** (devices halve per
restart, floor ``min_devices``): the checkpoint's virtual slot count is
fixed, PR 4's ``restore_auto`` elastic re-sharding does the rest, and
the published record stays bitwise. A bounded crash-loop budget
(``max_restarts``) escalates to a root-level ``FAILED.json`` stamp —
PR 6's containment shape: loud, durable, and the serving LIVE pointer
untouched. Every transition publishes supervision counters (restarts,
takeovers, injected chaos, lease ages) to ``SUPERVISOR.json``, which
:meth:`repro.serve.decisions.DecisionService.health` surfaces.

``--chaos-soak`` is the end-to-end proof, in the style of the
``--chaos`` fault gate: a seeded kill/stop/corrupt schedule
(:class:`ChaosSchedule`, FaultPlan-flavoured deterministic thresholds)
is injected into a supervised solve AND a supervised 3-generation
refresh; both must publish records **bitwise identical** to undisturbed
in-process reference runs — including takeovers that resumed on fewer
devices — and a poisoned crash-looping task must exhaust its budget
into ``FAILED.json`` while LIVE still points at the last good
generation. The gate asserts the exercised counters (kills, stops,
hang-takeovers, degraded spawns) so a schedule that silently failed to
fire cannot pass — the skip-proof convention of REQUIRE_HYPOTHESIS.

    PYTHONPATH=src python -m repro.launch.supervisor --chaos-soak --smoke
    PYTHONPATH=src python -m repro.launch.supervisor --supervise refresh \
        --root /tmp/sup --users 65536 --generations 3 --slots 4

Worker environments are assembled by :mod:`repro.launch.env`. A worker
inherits the coordinator's ``JAX_PLATFORMS`` unless the supervisor pins
one, so on a TPU host the worker gets the chip; the coordinator itself
never initialises a JAX backend, because a chip belongs to one process.
The degraded respawn is literally a smaller
``--xla_force_host_platform_device_count`` in the child's ``XLA_FLAGS``,
so it happens only when the workers' platform is ``cpu`` (the soak's CLI
pins it for exactly that); an accelerator worker is respawned on the
chips it has. Each worker writes ``worker.json`` (platform, device kind
and count) once its backend is up; ``SUPERVISOR.json`` carries it under
``"worker"`` and publishes its device count as ``devices``.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import pathlib
import re
import signal
import subprocess
import sys
import time
from typing import Callable, Optional

from . import env as envmod
from ..obs import MetricsRegistry

__all__ = ["SupervisorConfig", "ChaosSchedule", "Supervisor",
           "run_solve_task", "run_refresh_task", "run_chaos_soak"]

_STATUS = "SUPERVISOR.json"
_FAILED = "FAILED.json"
_TASK = "task.json"
_HEARTBEAT = "heartbeat.json"
_WORKER = "worker.json"
_CLAIM_RE = re.compile(r"\.claim_(\d+)$")


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Coordinator policy: deadlines, cadence, and the crash-loop budget.

    ``ttl`` is the lease deadline — a worker whose lease has not
    advanced for this many seconds (of the coordinator's clock) is
    declared hung and taken over. ``grace`` bounds process startup (the
    first beat lands before any heavy import, so this covers exec + a
    died-before-first-beat worker, not JIT warmup). ``max_restarts``
    bounds crash restarts plus hang takeovers together; exceeding it
    stamps ``FAILED.json`` and stops — the containment path, never a
    spin. ``degrade`` halves the worker device count on every respawn
    (floor ``min_devices``) of a CPU-platform worker, exercising elastic
    resume under real loss of capacity. ``progress_ttl`` optionally adds stuck-fetch detection
    (beats alive, progress frozen).
    """

    ttl: float = 3.0
    poll: float = 0.05
    grace: float = 120.0
    max_restarts: int = 8
    degrade: bool = True
    min_devices: int = 1
    progress_ttl: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class ChaosSchedule:
    """A seeded worker-level fault schedule (the FaultPlan of processes).

    ``events`` is an ordered tuple of ``(kind, at_progress)`` pairs,
    ``kind`` in {"kill", "stop"}: when the *current* worker's lease
    progress counter (chunk fetches) reaches ``at_progress``, the
    coordinator delivers SIGKILL or SIGSTOP and the event is consumed —
    so each event lands in a different worker life. Thresholds are pure
    hashes of ``(seed, index)`` in ``[lo, hi)``, so a soak replays the
    same schedule every run; the *exact* fetch the signal lands on may
    drift with OS scheduling, which is fine — the checkpoint protocol
    guarantees bitwise resume from any kill point, and the gate asserts
    the events fired, not where.
    """

    seed: int = 0
    events: tuple = ()

    @classmethod
    def plan(cls, seed: int, kills: int, stops: int,
             lo: int, hi: int) -> "ChaosSchedule":
        """Interleaved kill/stop events with hashed thresholds."""
        if hi <= lo:
            raise ValueError(f"need hi > lo, got [{lo}, {hi})")
        kinds = []
        k, s = kills, stops
        while k > 0 or s > 0:
            if k > 0:
                kinds.append("kill")
                k -= 1
            if s > 0:
                kinds.append("stop")
                s -= 1
        events = []
        for i, kind in enumerate(kinds):
            h = hashlib.sha256(f"chaos:{seed}:{i}".encode()).digest()
            at = lo + int.from_bytes(h[:8], "big") % (hi - lo)
            events.append((kind, at))
        return cls(seed=seed, events=tuple(events))


class Supervisor:
    """One supervised task: spawn, watch, re-drive, contain.

    ``root`` is the task's working directory — the worker's checkpoint
    and result/generation tree live here, next to the heartbeat lease,
    the durable ``task.json`` intent, the ``SUPERVISOR.json`` status
    document, and (on budget exhaustion) the ``FAILED.json`` stamp.
    ``task`` is the JSON-serialisable task description ``--worker``
    executes (see :func:`run_solve_task` / :func:`run_refresh_task`).
    ``worker_cmd(root, term, devices) -> argv`` overrides the spawned
    command (tests drive the coordinator with scripted fake workers);
    ``env_extra`` is merged into every worker environment.
    """

    def __init__(self, root, task: dict, cfg: SupervisorConfig = None,
                 devices: Optional[int] = None,
                 chaos: Optional[ChaosSchedule] = None,
                 worker_cmd: Optional[Callable] = None,
                 env_extra: Optional[dict] = None):
        self.root = pathlib.Path(root)
        self.cfg = cfg if cfg is not None else SupervisorConfig()
        self.task = dict(task)
        self.task.setdefault("ttl", self.cfg.ttl)
        self.devices0 = int(devices if devices is not None
                            else self.task.get("slots") or 1)
        self.chaos = chaos
        self.worker_cmd = worker_cmd
        self.env_extra = dict(env_extra or {})
        self.hb_path = self.root / _HEARTBEAT
        # Supervision counters live on a typed registry (monotone
        # counters for event tallies, gauges for the point-in-time
        # term/devices/lease-age readings); the :attr:`counters` dict
        # the rest of the stack consumes is assembled on read, with the
        # 13 counter keys SUPERVISOR.json has always published, plus
        # "worker": what the current worker's JAX reported (worker.json).
        self.registry = MetricsRegistry()
        self._ctrs = {
            k: self.registry.counter(f"supervisor_{k}")
            for k in ("spawns", "crash_restarts", "hang_takeovers",
                      "kills_injected", "stops_injected",
                      "degraded_spawns")}
        self._g_ok = self.registry.gauge("supervisor_ok")
        self._g_term = self.registry.gauge("supervisor_term")
        self._g_devices = self.registry.gauge("supervisor_devices")
        self._g_devices.set(self.devices0)
        self._g_lease_age = self.registry.gauge("supervisor_max_lease_age")
        self._info = {"state": "init", "last_rc": None, "worker": None}

    @property
    def counters(self) -> dict:
        """The status-document dict, assembled from the registry."""
        c = {k: int(v.value) for k, v in self._ctrs.items()}
        return {
            "ok": bool(self._g_ok.value),
            "state": self._info["state"],
            "spawns": c["spawns"],
            "crash_restarts": c["crash_restarts"],
            "hang_takeovers": c["hang_takeovers"],
            "restarts": c["crash_restarts"] + c["hang_takeovers"],
            "kills_injected": c["kills_injected"],
            "stops_injected": c["stops_injected"],
            "degraded_spawns": c["degraded_spawns"],
            "max_lease_age": round(float(self._g_lease_age.value), 3),
            "term": int(self._g_term.value),
            "devices": int(self._g_devices.value),
            "last_rc": self._info["last_rc"],
            "worker": self._info["worker"],
        }

    # -- spawn plumbing -----------------------------------------------------

    def _argv(self, term: int, devices: int) -> list:
        if self.worker_cmd is not None:
            return list(self.worker_cmd(self.root, term, devices))
        return [sys.executable, "-m", "repro.launch.supervisor",
                "--worker", str(self.root), "--term", str(term)]

    def _env(self, devices: int) -> dict:
        wenv = envmod.worker_env(devices)
        # The child must be able to import the running repro package even
        # when the parent was launched from an installed path.
        src = str(pathlib.Path(__file__).resolve().parents[2])
        pp = wenv.get("PYTHONPATH", "")
        if src not in pp.split(os.pathsep):
            wenv["PYTHONPATH"] = src + (os.pathsep + pp if pp else "")
        wenv.update(self.env_extra)
        return wenv

    def _spawn(self, term: int, devices: int) -> subprocess.Popen:
        return subprocess.Popen(self._argv(term, devices),
                                env=self._env(devices))

    def _next_term(self) -> int:
        """First unused term on this root (lease + claim debris aware).

        A supervisor relaunched over an existing root (its predecessor
        died) must not reuse a term: the lease records the last writer's
        term and the claim files record every adoption, so the next term
        is one past the max of both — keeping claim exclusivity
        meaningful across coordinator generations.
        """
        from ..core.heartbeat import TornLease, read_lease

        last = 0
        try:
            lease = read_lease(self.hb_path)
            if lease is not None:
                last = lease.term
        except TornLease:
            pass
        for p in self.hb_path.parent.glob(self.hb_path.name + ".claim_*"):
            m = _CLAIM_RE.search(p.name)
            if m:
                last = max(last, int(m.group(1)))
        return last + 1

    # -- status publication -------------------------------------------------

    def _read_worker(self, term: int) -> Optional[dict]:
        """The platform document ``term``'s worker wrote, once it has."""
        from ..checkpoint import ckpt

        try:
            doc = ckpt.read_json(self.root, _WORKER)
        except ValueError:
            return None
        return doc if doc is not None and doc.get("term") == term else None

    def _publish(self, state: str):
        from ..checkpoint import ckpt

        self._info["state"] = state
        doc = self.counters
        doc["updated_wall"] = time.time()
        ckpt.write_json(self.root, _STATUS, doc)

    # -- the watch loop -----------------------------------------------------

    def _kill(self, proc: subprocess.Popen):
        try:
            os.kill(proc.pid, signal.SIGKILL)   # kills STOPped workers too
        except ProcessLookupError:
            pass
        proc.wait()

    def _watch(self, proc: subprocess.Popen, term: int, events: list):
        """Watch one worker life; returns ('done'|'crash'|'hang', rc)."""
        from ..core.heartbeat import LeaseMonitor

        mon = LeaseMonitor(self.hb_path, ttl=self.cfg.ttl,
                           grace=self.cfg.grace, expect_term=term,
                           progress_ttl=self.cfg.progress_ttl)
        while True:
            if self._info["worker"] is None:
                self._info["worker"] = self._read_worker(term)
                if self._info["worker"] is not None:
                    self._g_devices.set(self._info["worker"]["device_count"])
                    self._publish("running")
            rc = proc.poll()
            st = mon.poll()
            if st["age"] is not None:
                self._g_lease_age.set_max(float(st["age"]))
            if rc is not None:
                return ("done", rc) if rc == 0 else ("crash", rc)
            if st["expired"]:
                # The hang path: no liveness evidence for ttl (or the
                # progress deadline). The worker may be SIGSTOPped,
                # wedged in a fetch, or a zombie-to-be — all get the
                # same treatment: kill, then re-drive from checkpoint.
                self._kill(proc)
                return ("hang", None)
            if events and st["state"] == "fresh" \
                    and st["progress"] is not None \
                    and st["progress"] >= events[0][1]:
                kind, _ = events.pop(0)
                try:
                    if kind == "kill":
                        os.kill(proc.pid, signal.SIGKILL)
                        self._ctrs["kills_injected"].inc()
                    else:
                        os.kill(proc.pid, signal.SIGSTOP)
                        self._ctrs["stops_injected"].inc()
                except ProcessLookupError:
                    pass
            time.sleep(self.cfg.poll)

    # -- the coordinator loop -----------------------------------------------

    def run(self) -> dict:
        """Drive the task to completion, a FAILED stamp, or bust.

        Returns the final counter dict (``ok`` True only when a worker
        exited 0). The task intent is written durably before the first
        spawn, so a relaunched supervisor re-drives the identical task.
        """
        from ..checkpoint import ckpt
        from ..core.heartbeat import claim_takeover

        ckpt.write_json(self.root, _TASK, self.task)
        self._info.update(state="starting", last_rc=None)
        self._g_ok.set(0)
        self._g_term.set(0)
        self._g_devices.set(self.devices0)
        events = list(self.chaos.events) if self.chaos is not None else []
        devices = self.devices0
        # Only the host platform's device count can be set from outside
        # the worker; an accelerator worker sees the chips it has.
        degrade = (self.cfg.degrade
                   and self._env(devices).get("JAX_PLATFORMS") == "cpu")
        term = self._next_term()
        while True:
            if term > 1 and not claim_takeover(self.hb_path, term):
                raise RuntimeError(
                    f"takeover claim for term {term} on {self.hb_path} "
                    "was already held — another coordinator owns this "
                    "root; standing down instead of double-driving it")
            proc = self._spawn(term, devices)
            self._info["worker"] = None
            self._ctrs["spawns"].inc()
            self._g_term.set(term)
            self._g_devices.set(devices)
            if devices < self.devices0:
                self._ctrs["degraded_spawns"].inc()
            self._publish("running")
            outcome, rc = self._watch(proc, term, events)
            if outcome == "done":
                self._g_ok.set(1)
                self._publish("done")
                return self.counters
            if outcome == "crash":
                self._ctrs["crash_restarts"].inc()
                self._info["last_rc"] = rc
            else:
                self._ctrs["hang_takeovers"].inc()
            if (self._ctrs["crash_restarts"].value
                    + self._ctrs["hang_takeovers"].value) \
                    > self.cfg.max_restarts:
                # Containment, not a spin: budget exhausted. The stamp is
                # root-level (the per-generation FAILED.json remains the
                # solver-level fetch-exhaustion stamp); LIVE — if this
                # root serves generations — is untouched, so readers
                # keep answering from the last good publication.
                ckpt.write_json(self.root, _FAILED, {
                    "reason": "crash-loop budget exhausted",
                    "max_restarts": self.cfg.max_restarts,
                    "counters": self.counters,
                    "task_kind": self.task.get("kind"),
                })
                self._publish("failed")
                return self.counters
            term += 1
            if degrade:
                devices = max(self.cfg.min_devices, devices // 2)


# ---------------------------------------------------------------------------
# The worker side: task execution (shared with in-process reference runs).
# ---------------------------------------------------------------------------

def _heartbeat_source(source, hb):
    """Wrap a chunk source so every fetch bumps the lease's progress."""
    inner = source.fn

    def fn(i):
        hb.bump()
        return inner(i)

    return source._replace(fn=fn)


def _task_mesh(slots: Optional[int]):
    """The widest local mesh the task's slot count divides over."""
    import jax

    nd = jax.device_count()
    if nd > 1 and slots and slots % nd == 0:
        return jax.make_mesh((nd,), ("slots",))
    return None


def _task_source(task: dict, spec, hb=None):
    """spec -> HostChunkSource per the task: synthetic workload, optional
    FaultPlan injection underneath, heartbeat progress on top."""
    from ..core.faults import FaultPlan, faulty_source
    from ..serve.engine import synthetic_source

    src = synthetic_source(spec)
    if task.get("fault_plan"):
        src = faulty_source(src, FaultPlan(**task["fault_plan"]))
    if hb is not None:
        src = _heartbeat_source(src, hb)
    return src


def _task_cfg(task: dict):
    from ..core.types import SolverConfig

    return SolverConfig(**task.get("cfg", {}))


def run_solve_task(root, task: dict, hb=None) -> dict:
    """Execute (or resume) a ``kind == "solve"`` task under ``root``.

    Solves the task's workload with checkpointing into ``root/ckpt`` and
    resume from the same directory — a respawned worker picks up where
    its predecessor died — and publishes the result record durably at
    ``root/result`` (ckpt protocol, step 0). Idempotent: a worker killed
    between the record save and its exit is a no-op on the next life.
    Returns the record as numpy arrays.
    """
    import numpy as np

    from ..checkpoint import ckpt
    from ..core.prefetch import solve_streaming_host
    from ..serve.engine import WorkloadSpec

    root = pathlib.Path(root)
    result_dir = root / "result"
    if ckpt.latest_step(result_dir) is not None:
        return ckpt.restore_auto(result_dir, 0)
    spec = WorkloadSpec.from_json(task["spec"])
    slots = task.get("slots")
    ckdir = str(root / "ckpt")
    res = solve_streaming_host(
        _task_source(task, spec, hb), _task_cfg(task), q=spec.q,
        mesh=_task_mesh(slots), slots=slots,
        checkpoint_dir=ckdir, resume_from=ckdir)
    record = {
        "lam": np.asarray(res.lam), "tau": np.asarray(res.tau),
        "iters": np.int32(res.iters), "r": np.asarray(res.r),
        "primal": np.asarray(res.primal), "dual": np.asarray(res.dual),
    }
    if res.fin_hist is not None:
        record["fin_ch"] = np.asarray(res.fin_hist[0])
        record["fin_gh"] = np.asarray(res.fin_hist[1])
    ckpt.save(result_dir, 0, record)
    return record


def run_refresh_task(root, task: dict, hb=None) -> dict:
    """Execute (or resume) a ``kind == "refresh"`` task under ``root``.

    Drives a :class:`~repro.serve.engine.RefreshEngine` over ``root``
    through the task's budget-scale schedule until ``generations``
    generations are live. Re-entrant by construction: ``recover()``
    finishes a preempted generation first, then the loop continues from
    the live pointer — the engine's two-step publication makes every
    completed generation bitwise the undisturbed one.
    """
    from ..serve.engine import RefreshEngine, WorkloadSpec

    spec = WorkloadSpec.from_json(task["spec"])
    slots = task.get("slots")
    engine = RefreshEngine(
        pathlib.Path(root), spec,
        make_source=lambda s: _task_source(task, s, hb),
        cfg=_task_cfg(task), mesh=_task_mesh(slots), slots=slots)
    engine.recover()
    generations = int(task["generations"])
    scales = task.get("budget_scales") or [1.0] * generations
    start = (engine.live_gen_id() + 1
             if engine.live_gen_id() is not None else 0)
    for g in range(start, generations):
        engine.refresh(budget_scale=scales[g])
    return {"live": engine.live_gen_id()}


def _announce_platform(root, term: int) -> None:
    """Bring this worker's JAX backend up and record what it got."""
    import jax

    from ..checkpoint import ckpt

    devs = jax.devices()
    ckpt.write_json(root, _WORKER, {
        "term": term, "pid": os.getpid(), "platform": devs[0].platform,
        "device_kind": devs[0].device_kind, "device_count": len(devs)})


def _worker_main(args) -> int:
    """``--worker`` entry: heartbeat up, then run the durable task.

    The poison hook (``REPRO_WORKER_POISON`` = exit code) sits before
    every heavy import: it is the deterministic crash-loop fixture the
    containment gate and tests drive budget exhaustion with, and its
    earliness keeps those loops cheap.
    """
    if os.environ.get("REPRO_WORKER_POISON"):
        return int(os.environ["REPRO_WORKER_POISON"])
    root = pathlib.Path(args.worker)
    task = json.loads((root / _TASK).read_text())

    from ..core.heartbeat import HeartbeatWriter

    envmod.enable_compile_cache()
    hb = HeartbeatWriter(root / _HEARTBEAT, worker=task.get("kind", "task"),
                         term=args.term, ttl=float(task.get("ttl", 3.0)))
    with hb:
        _announce_platform(root, args.term)
        if task["kind"] == "solve":
            run_solve_task(root, task, hb)
        elif task["kind"] == "refresh":
            run_refresh_task(root, task, hb)
        else:
            raise ValueError(f"unknown task kind {task['kind']!r} in "
                             f"{root / _TASK}")
    return 0


# ---------------------------------------------------------------------------
# The chaos soak: supervised self-healing, proven bitwise.
# ---------------------------------------------------------------------------

# Fetch-level injection riding under the process-level chaos (the
# "corrupt" leg of the soak schedule). Rates are deliberately milder
# than the --chaos gate's: with verify_refetch doubling reads, an
# attempt succeeds with (1 - drop - corrupt)^2 and the soak's workers
# re-fetch across several lives.
_SOAK_PLAN_KW = dict(drop=0.04, slow=0.02, slow_s=0.002, corrupt=0.02)
_SOAK_CFG_KW = dict(fetch_retries=8, fetch_backoff=1e-4,
                    fetch_backoff_cap=1e-3, verify_refetch=True)

_RESULT_FIELDS = ["lam", "tau", "iters", "r", "primal", "dual"]


def _diff_records(tag: str, want: dict, got: dict, fields) -> list:
    import numpy as np

    diffs = []
    for f in fields:
        a, b = want.get(f), got.get(f)
        if a is None or b is None:
            if (a is None) != (b is None):
                diffs.append(f"{tag}: field {f} present in only one run")
            continue
        if np.asarray(a).tobytes() != np.asarray(b).tobytes():
            diffs.append(f"{tag}: field {f} differs bitwise")
    return diffs


def _sample_decisions(spec, record: dict, users):
    """Decision rows for sampled users straight from a published record
    (lam + tau are the whole decision rule; the source bytes are the
    spec's)."""
    import numpy as np

    from ..core.chunked import decisions_rows
    from ..serve.engine import synthetic_source

    src = synthetic_source(spec)
    out = []
    for u in users:
        ci, off = divmod(int(u), src.chunk)
        p, b = src.fn(ci)
        rows = ci * src.chunk + np.arange(src.chunk)
        x = np.asarray(decisions_rows(p, b, record["lam"], spec.q,
                                      rows < src.n, record["tau"]))
        out.append(x[off])
    return np.asarray(out)


def run_chaos_soak(root, smoke: bool = False, seed: int = 0) -> tuple:
    """The supervision gate; returns ``(ok, report)``.

    Proves, end to end: a supervised solve and a supervised
    multi-generation refresh each survive a seeded schedule of worker
    SIGKILLs and SIGSTOP hangs (plus fetch-level drop/corrupt injection
    under retries) and publish records **bitwise identical** to
    undisturbed in-process reference runs — with at least one takeover
    resuming on a degraded device count — and a poisoned crash-looping
    task exhausts its restart budget into a root-level ``FAILED.json``
    while the serving LIVE pointer still names the last good generation.
    Every exercised path is counter-asserted: a soak in which the
    schedule silently failed to fire fails the gate.
    """
    import numpy as np

    from ..checkpoint import ckpt
    from ..serve.engine import RefreshEngine, WorkloadSpec
    from .refresh import _budget_schedule

    root = pathlib.Path(root)
    if smoke:
        n, chunk, generations, max_iters = 4096, 512, 3, 40
        lo, hi = 12, 40
    else:
        n, chunk, generations, max_iters = 65536, 2048, 3, 60
        lo, hi = 30, 150
    slots = 4
    spec = WorkloadSpec(seed=seed, n=n, k=8, chunk=chunk, q=2,
                        tightness=0.4)
    base_cfg = dict(reduce="bucketed", max_iters=max_iters,
                    checkpoint_every=2, bucket_half=16)
    chaos_cfg = {**base_cfg, **_SOAK_CFG_KW}
    plan = dict(seed=seed, **_SOAK_PLAN_KW)
    scales = _budget_schedule(generations, seed)
    sup_cfg = SupervisorConfig(ttl=2.5, poll=0.05, grace=120.0,
                               max_restarts=10)

    report: dict = {"smoke": smoke, "seed": seed}
    diffs: list = []

    # ---- undisturbed references, in-process, fault-free ------------------
    print(f"[soak] reference solve -> {root / 'solve_ref'}")
    ref_solve = run_solve_task(root / "solve_ref", {
        "kind": "solve", "spec": spec.to_json(), "cfg": base_cfg,
        "slots": slots})
    print(f"[soak] reference refresh ({generations} generations) -> "
          f"{root / 'refresh_ref'}")
    run_refresh_task(root / "refresh_ref", {
        "kind": "refresh", "spec": spec.to_json(), "cfg": base_cfg,
        "slots": slots, "generations": generations,
        "budget_scales": scales})

    # ---- supervised chaos solve ------------------------------------------
    solve_sched = ChaosSchedule.plan(seed, kills=1, stops=1, lo=lo, hi=hi)
    print(f"[soak] supervised chaos solve ({solve_sched.events}) -> "
          f"{root / 'solve_chaos'}")
    s_solve = Supervisor(
        root / "solve_chaos",
        {"kind": "solve", "spec": spec.to_json(), "cfg": chaos_cfg,
         "slots": slots, "fault_plan": plan},
        cfg=sup_cfg, devices=slots, chaos=solve_sched).run()
    report["solve"] = s_solve
    got_solve = ckpt.restore_auto(root / "solve_chaos" / "result", 0) \
        if s_solve["ok"] else {}
    if not s_solve["ok"]:
        diffs.append("solve: supervised run did not complete")
    else:
        got_solve = {k: np.asarray(v) for k, v in got_solve.items()}
        diffs += _diff_records("solve", ref_solve, got_solve,
                               _RESULT_FIELDS + ["fin_ch", "fin_gh"])
        rng = np.random.default_rng(seed)
        users = rng.integers(0, spec.n, 32)
        if not np.array_equal(_sample_decisions(spec, ref_solve, users),
                              _sample_decisions(spec, got_solve, users)):
            diffs.append("solve: sampled decisions differ")

    # ---- supervised chaos refresh ----------------------------------------
    refresh_sched = ChaosSchedule.plan(seed + 1, kills=1, stops=1,
                                       lo=lo, hi=hi)
    print(f"[soak] supervised chaos refresh ({refresh_sched.events}) -> "
          f"{root / 'refresh_chaos'}")
    s_refresh = Supervisor(
        root / "refresh_chaos",
        {"kind": "refresh", "spec": spec.to_json(), "cfg": chaos_cfg,
         "slots": slots, "generations": generations,
         "budget_scales": scales, "fault_plan": plan},
        cfg=sup_cfg, devices=slots, chaos=refresh_sched).run()
    report["refresh"] = s_refresh
    if not s_refresh["ok"]:
        diffs.append("refresh: supervised run did not complete")
    else:
        ref_eng = RefreshEngine(root / "refresh_ref", spec)
        got_eng = RefreshEngine(root / "refresh_chaos", spec)
        rng = np.random.default_rng(seed + 1)
        users = rng.integers(0, spec.n, 32)
        for g in range(generations):
            want, got = ref_eng.generation(g), got_eng.generation(g)
            fields = ["lam", "tau", "iters", "r", "primal", "dual",
                      "fingerprint"]
            diffs += _diff_records(
                f"refresh gen {g}",
                {f: getattr(want, f) for f in fields},
                {f: getattr(got, f) for f in fields}, fields)
            for i, (x, y) in enumerate(zip(want.fin_hist or (),
                                           got.fin_hist or ())):
                if np.asarray(x).tobytes() != np.asarray(y).tobytes():
                    diffs.append(f"refresh gen {g}: fin_hist[{i}] differs")
        live_want, live_got = ref_eng.live(), got_eng.live()
        rec_w = {"lam": live_want.lam, "tau": live_want.tau}
        rec_g = {"lam": live_got.lam, "tau": live_got.tau}
        if not np.array_equal(
                _sample_decisions(live_want.spec, rec_w, users),
                _sample_decisions(live_got.spec, rec_g, users)):
            diffs.append("refresh: sampled live decisions differ")

    # ---- containment: crash-loop budget -> FAILED.json, LIVE untouched ---
    live_before = RefreshEngine(root / "refresh_chaos", spec).live_gen_id()
    print("[soak] containment: poisoned crash-looping task "
          f"(budget 2) on {root / 'refresh_chaos'}")
    s_poison = Supervisor(
        root / "refresh_chaos",
        {"kind": "refresh", "spec": spec.to_json(), "cfg": chaos_cfg,
         "slots": slots, "generations": generations + 1,
         "budget_scales": scales + [1.0]},
        cfg=dataclasses.replace(sup_cfg, max_restarts=2),
        devices=slots, env_extra={"REPRO_WORKER_POISON": "3"}).run()
    report["poison"] = s_poison
    live_after = RefreshEngine(root / "refresh_chaos", spec).live_gen_id()
    failed = ckpt.read_json(root / "refresh_chaos", _FAILED)
    if s_poison["ok"]:
        diffs.append("containment: poisoned task reported success")
    if failed is None:
        diffs.append("containment: no FAILED.json stamped")
    if live_after != live_before:
        diffs.append(f"containment: LIVE moved {live_before} -> "
                     f"{live_after} under a failing task")

    # ---- skip-proof counter assertions -----------------------------------
    kills = s_solve["kills_injected"] + s_refresh["kills_injected"]
    stops = s_solve["stops_injected"] + s_refresh["stops_injected"]
    hangs = s_solve["hang_takeovers"] + s_refresh["hang_takeovers"]
    crashes = s_solve["crash_restarts"] + s_refresh["crash_restarts"]
    degraded = s_solve["degraded_spawns"] + s_refresh["degraded_spawns"]
    exercised = {"kills_injected": kills, "stops_injected": stops,
                 "hang_takeovers": hangs, "crash_restarts": crashes,
                 "degraded_spawns": degraded}
    report["exercised"] = exercised
    for name, got_n, need in [("kills_injected", kills, 2),
                              ("stops_injected", stops, 1),
                              ("hang_takeovers", hangs, 1),
                              ("crash_restarts", crashes, 2),
                              ("degraded_spawns", degraded, 1)]:
        if got_n < need:
            diffs.append(f"soak under-exercised: {name} = {got_n} < {need} "
                         "— the schedule did not fire; the gate proves "
                         "nothing")
    if hangs < stops:
        diffs.append(f"soak: {stops} SIGSTOPs injected but only {hangs} "
                     "lease-expiry takeovers — a hang went undetected")

    report["diffs"] = diffs
    report["ok"] = not diffs
    ckpt.write_json(root, "SOAK.json", report)
    for d in diffs:
        print(f"[soak] FAIL: {d}")
    if not diffs:
        print(f"[soak] OK: solve + {generations}-generation refresh "
              f"bitwise identical to undisturbed runs under {kills} kills, "
              f"{stops} stops ({hangs} lease-expiry takeovers, {degraded} "
              f"degraded respawns); crash-loop contained to FAILED.json "
              "with LIVE untouched")
    return not diffs, report


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------

def main():
    """CLI dispatch: --worker / --chaos-soak / --supervise."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", default=None, metavar="ROOT",
                    help="internal: run the durable task under ROOT as a "
                         "supervised worker")
    ap.add_argument("--term", type=int, default=1)
    ap.add_argument("--chaos-soak", action="store_true",
                    help="supervised self-healing gate: seeded kills/"
                         "stops/corruption against a solve and a refresh; "
                         "exit 1 unless results are bitwise identical to "
                         "the undisturbed runs and every chaos path "
                         "actually fired")
    ap.add_argument("--smoke", action="store_true",
                    help="small scenario (the CI gate size)")
    ap.add_argument("--supervise", choices=["solve", "refresh"],
                    default=None,
                    help="run one supervised task to completion")
    ap.add_argument("--root", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--users", type=int, default=65536)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--q", type=int, default=2)
    ap.add_argument("--tightness", type=float, default=0.4)
    ap.add_argument("--generations", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-iters", type=int, default=60)
    ap.add_argument("--checkpoint-every", type=int, default=4)
    ap.add_argument("--ttl", type=float, default=3.0)
    ap.add_argument("--max-restarts", type=int, default=8)
    args = ap.parse_args()

    if args.worker is not None:
        sys.exit(_worker_main(args))

    import tempfile

    envmod.enable_compile_cache()
    root = args.root or tempfile.mkdtemp(prefix="supervisor_")
    if args.chaos_soak:
        # The soak drills degraded respawns, which only the host
        # platform's virtual device count can fake, and compares its
        # CPU workers bitwise against references run in this process.
        envmod.set_platform("cpu")
        ok, _ = run_chaos_soak(root, smoke=args.smoke, seed=args.seed)
        sys.exit(0 if ok else 1)
    if args.supervise is not None:
        from ..serve.engine import WorkloadSpec
        from .refresh import _budget_schedule

        spec = WorkloadSpec(seed=args.seed, n=args.users, k=args.k,
                            chunk=args.chunk, q=args.q,
                            tightness=args.tightness)
        cfg = dict(reduce="bucketed", max_iters=args.max_iters,
                   checkpoint_every=args.checkpoint_every)
        task = {"kind": args.supervise, "spec": spec.to_json(),
                "cfg": cfg, "slots": args.slots}
        if args.supervise == "refresh":
            task["generations"] = args.generations
            task["budget_scales"] = _budget_schedule(args.generations,
                                                     args.seed)
        sup = Supervisor(root, task,
                         cfg=SupervisorConfig(ttl=args.ttl,
                                              max_restarts=args.max_restarts),
                         devices=args.slots)
        out = sup.run()
        print(f"[supervisor] {out}")
        sys.exit(0 if out["ok"] else 1)
    ap.error("pick a mode: --worker, --chaos-soak, or --supervise")


if __name__ == "__main__":
    main()
