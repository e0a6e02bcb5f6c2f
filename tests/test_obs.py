"""The observability layer: registry, tracing, parity, /metrics.

The contracts under test (DESIGN.md §14, repro/obs/):

* the typed registry — monotone counters, set/computed gauges,
  fixed-ladder histograms — is get-or-create keyed by (name, labels)
  and refuses kind collisions; the null registry is inert;
* Prometheus text round-trips through ``render_prometheus`` /
  ``parse_prometheus``; snapshot merging sums counters/gauges and adds
  histogram counts elementwise; ``label_snapshot`` stamps labels;
* the phase-span tracer journals host-side spans to fsync-batched
  JSONL; request ids ride a contextvar into every span emitted inside
  ``request(rid)``; ``read_trace`` tolerates a torn tail (a SIGKILLed
  writer loses at most the buffered spans, never a reader) but flags
  mid-file corruption;
* **the host-side-only rule**: a chunked solve, a sharded (virtual
  slot) solve and an engine refresh with observability ON publish
  results **bitwise identical** to the same runs with it OFF;
* ``/metrics`` on the replica RPC and the front aggregates the same
  numbers ``/health`` reports, the fleet aggregate is the sum of the
  per-replica labeled series, and one request id correlates the
  ``front.decide`` span with the replica-side ``serve.fill`` spans;
* the degraded bit is the *current* binding's state — a rebind onto a
  healed generation clears it while ``stale_serves`` stays monotone;
* SUPERVISOR.json goes through ``ckpt.write_json`` (fsync'd tmp +
  atomic rename), never a bare ``open().write``.
"""
import json
import os
import signal
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from repro.checkpoint import ckpt
from repro.core import SolverConfig
from repro.core.prefetch import solve_streaming_host
from repro.data.synth import sparse_host_chunk_source
from repro.launch.supervisor import Supervisor, SupervisorConfig
from repro.obs import (
    LATENCY_BUCKETS,
    MetricsRegistry,
    NULL_REGISTRY,
    NULL_TRACER,
    Tracer,
    current_rid,
    label_snapshot,
    make_obs,
    merge_snapshots,
    null_obs,
    parse_prometheus,
    read_trace,
    render_prometheus,
    request,
    trace_path,
)
from repro.serve import (
    Front,
    RefreshEngine,
    ReplicaClient,
    ReplicaServer,
    WorkloadSpec,
    synthetic_source,
)

jax.config.update("jax_platform_name", "cpu")

SPEC = WorkloadSpec(seed=5, n=1024, k=4, chunk=128, q=1, tightness=0.4)
CFG = SolverConfig(reduce="bucketed", max_iters=25, checkpoint_every=0)
SCALES = [1.0, 0.9]
CHUNKS = SPEC.n // SPEC.chunk
RESULT_FIELDS = ("lam", "iters", "r", "primal", "dual", "tau")
GEN_FIELDS = ("lam", "tau", "r", "primal", "dual")


# ---------------------------------------------------------------------------
# Metrics registry: typed instruments, get-or-create, null inertness.
# ---------------------------------------------------------------------------

def test_counter_is_monotone_and_snapshots():
    reg = MetricsRegistry()
    c = reg.counter("hits", route="a")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert not hasattr(c, "set")        # counters cannot go down
    (s,) = reg.snapshot()
    assert s == {"kind": "counter", "name": "hits",
                 "labels": {"route": "a"}, "value": 5}


def test_gauge_set_max_and_computed():
    reg = MetricsRegistry()
    g = reg.gauge("lease_age")
    g.set(2.0)
    g.set_max(1.0)                      # lower: ignored
    g.set_max(7.5)
    assert g.value == 7.5
    backing = [1, 2, 3]
    live = reg.gauge("cache_size", fn=lambda: len(backing))
    assert live.value == 3
    backing.append(4)
    assert live.value == 4              # computed at read time


def test_histogram_buckets_and_ladder():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    assert h.buckets == LATENCY_BUCKETS
    for v in (2e-5, 2e-5, 0.3, 99.0):   # two in one bucket, one +Inf
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(99.30004)
    (s,) = reg.snapshot()
    assert sum(s["counts"]) == 4
    assert s["counts"][-1] == 1         # 99.0 lands past the last edge
    assert s["counts"][1] == 2          # both 2e-5 in the 2.5e-5 bucket


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.counter("x", a="1") is not reg.counter("x", a="2")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_null_registry_is_inert():
    inst = NULL_REGISTRY.counter("anything")
    inst.inc()
    inst.set(9)
    inst.observe(1.0)
    assert inst.value == 0
    assert NULL_REGISTRY.snapshot() == []
    assert NULL_REGISTRY.gauge("g") is inst       # one shared instrument
    assert null_obs() is null_obs()               # and one shared bundle


# ---------------------------------------------------------------------------
# Prometheus text: render/parse round-trip, merge, labeling.
# ---------------------------------------------------------------------------

def test_render_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter("req", route="decide").inc(3)
    reg.gauge("up").set(1)
    reg.histogram("lat").observe(3e-5)
    series = parse_prometheus(render_prometheus(reg.snapshot()))
    assert series[("req", (("route", "decide"),))] == 3
    assert series[("up", ())] == 1
    assert series[("lat_count", ())] == 1
    assert series[("lat_sum", ())] == pytest.approx(3e-5)
    # Cumulative buckets: the +Inf bucket equals the count.
    assert series[("lat_bucket", (("le", "+Inf"),))] == 1


def test_merge_snapshots_sums_and_adds_histograms():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("q").inc(2)
    b.counter("q").inc(5)
    a.histogram("lat").observe(1e-4)
    b.histogram("lat").observe(2.0)
    m = merge_snapshots([a.snapshot(), b.snapshot()])
    by_name = {s["name"]: s for s in m}
    assert by_name["q"]["value"] == 7
    assert by_name["lat"]["count"] == 2
    assert sum(by_name["lat"]["counts"]) == 2
    assert by_name["lat"]["sum"] == pytest.approx(2.0001)


def test_label_snapshot_stamps_and_merge_keeps_labels_apart():
    reg = MetricsRegistry()
    reg.counter("q").inc(3)
    s0 = label_snapshot(reg.snapshot(), replica="0")
    s1 = label_snapshot(reg.snapshot(), replica="1")
    m = merge_snapshots([s0, s1, reg.snapshot()])
    vals = {tuple(sorted(s["labels"].items())): s["value"] for s in m}
    # Distinct label sets never merge; the unlabeled entry is separate.
    assert vals == {(("replica", "0"),): 3, (("replica", "1"),): 3, (): 3}


# ---------------------------------------------------------------------------
# Tracing: spans to JSONL, rid propagation, torn-tail-proof reader.
# ---------------------------------------------------------------------------

def test_tracer_spans_events_records_and_rid(tmp_path):
    path = trace_path(tmp_path, "t")
    with Tracer(path) as tr:
        with tr.span("solve.iterate", iter=3):
            pass
        tr.event("screen.skip", chunk=7)
        tr.record("ingest.fetch", 123.0, 0.25, chunks=8)
        with request("abc-1"):
            assert current_rid() == "abc-1"
            tr.event("serve.fill", chunk=0)
        assert current_rid() is None
    spans = read_trace(path)
    by_phase = {s["phase"]: s for s in spans}
    assert by_phase["solve.iterate"]["iter"] == 3
    assert by_phase["solve.iterate"]["dur_s"] >= 0
    assert by_phase["screen.skip"]["dur_s"] == 0.0
    assert by_phase["ingest.fetch"]["t"] == 123.0
    assert by_phase["ingest.fetch"]["dur_s"] == 0.25
    assert by_phase["serve.fill"]["rid"] == "abc-1"
    assert "rid" not in by_phase["screen.skip"]
    assert all(s["pid"] == os.getpid() for s in spans)


def test_tracer_batches_fsyncs(tmp_path):
    path = trace_path(tmp_path, "b")
    tr = Tracer(path, fsync_every=4)
    for i in range(3):
        tr.event("e", i=i)
    assert read_trace(path) == []       # still buffered, nothing on disk
    tr.event("e", i=3)                  # 4th: batch-flushed + fsync'd
    assert len(read_trace(path)) == 4
    tr.close()


def test_read_trace_torn_tail_and_corruption(tmp_path):
    p = tmp_path / "j.jsonl"
    rec = json.dumps({"phase": "x", "t": 0, "dur_s": 0, "pid": 1})
    p.write_text(rec + "\n" + rec + "\n" + rec[: len(rec) // 2])
    assert len(read_trace(p)) == 2          # torn tail dropped, no raise
    p.write_text(rec + "\n{bad}\n" + rec + "\n")
    with pytest.raises(ValueError, match="corrupt trace line 2"):
        read_trace(p)                       # mid-file damage is loud
    assert read_trace(tmp_path / "missing.jsonl") == []


def test_trace_journal_survives_sigkill(tmp_path):
    """A writer SIGKILLed mid-journal leaves a readable trace: every
    fsync'd span survives and the reader never crashes on the tail."""
    prog = (
        "import sys; sys.path.insert(0, {src!r})\n"
        "from repro.obs import Tracer, trace_path\n"
        "tr = Tracer(trace_path({root!r}, 'victim'), fsync_every=1)\n"
        "tr.event('warmup')\n"
        "tr.flush()\n"
        "print('ready', flush=True)\n"
        "import time\n"
        "i = 0\n"
        "while True:\n"
        "    tr.event('tick', i=i); i += 1; time.sleep(0.001)\n"
    ).format(src=str((os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))) + "/src"), root=str(tmp_path))
    proc = subprocess.Popen([sys.executable, "-c", prog],
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        # The journal is pid-stamped by the *writer* process.
        path = os.path.join(tmp_path, "obs", f"victim-{proc.pid}.jsonl")
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if os.path.exists(path) and \
                    len(open(path, "rb").read().splitlines()) > 20:
                break
            time.sleep(0.01)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    spans = read_trace(path)                # must not raise, ever
    ticks = [s for s in spans if s["phase"] == "tick"]
    assert len(ticks) >= 10
    # What survived is a prefix: fsync order == emission order.
    assert [s["i"] for s in ticks] == list(range(len(ticks)))


# ---------------------------------------------------------------------------
# The host-side-only rule: obs on == obs off, bitwise.
# ---------------------------------------------------------------------------

def _bitwise_result(a, b):
    for f in RESULT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


def _source():
    return sparse_host_chunk_source(3, SPEC.n, 6, SPEC.chunk,
                                    q=2, tightness=0.3)


def test_chunked_solve_bitwise_identical_obs_on_off(tmp_path):
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=0)
    base = solve_streaming_host(_source(), cfg, q=2)
    with Tracer(trace_path(tmp_path, "solve")) as tr:
        traced = solve_streaming_host(_source(), cfg, q=2, tracer=tr)
    _bitwise_result(base, traced)
    phases = {s["phase"] for s in read_trace(tr.path)}
    assert {"solve.iterate", "solve.finalize",
            "ingest.fetch", "ingest.h2d"} <= phases


def test_sharded_solve_bitwise_identical_obs_on_off(tmp_path):
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=0)
    base = solve_streaming_host(_source(), cfg, q=2, slots=4)
    with Tracer(trace_path(tmp_path, "shard")) as tr:
        traced = solve_streaming_host(_source(), cfg, q=2, slots=4,
                                      tracer=tr)
    _bitwise_result(base, traced)
    phases = {s["phase"] for s in read_trace(tr.path)}
    assert {"solve.iterate", "solve.finalize", "ingest.fetch"} <= phases


# The sharded runtime's own host phases, on a mesh of four host devices.
# The devices are forced the way benchmarks/chip/tests/conftest.py forces
# them, in a subprocess, so they never leak into this process's JAX.
_SHARDED_SPANS_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
import jax
import numpy as np
from repro.core import SolverConfig
from repro.core.prefetch import solve_streaming_host
from repro.data.synth import sparse_host_chunk_source
from repro.obs import Tracer, trace_path

root = sys.argv[1]
assert len(jax.devices()) == 4, jax.devices()
mesh = jax.make_mesh((4,), ("slots",))
cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=0)
# 1000 users in chunks of 128: 8 chunks, the last one ragged, so the
# last of the 4 slots ends on the ragged chunk.
src = lambda: sparse_host_chunk_source(3, 1000, 6, 128, q=2, tightness=0.3)
base = solve_streaming_host(src(), cfg, q=2, mesh=mesh, slots=4)
with Tracer(trace_path(root, "shard4")) as tr:
    traced = solve_streaming_host(src(), cfg, q=2, mesh=mesh, slots=4,
                                  tracer=tr)
for f in ("lam", "iters", "r", "primal", "dual", "tau"):
    np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(traced, f)), err_msg=f)
print(tr.path)
print(int(traced.iters))
"""


def test_sharded_runtime_spans_stack_and_combine(tmp_path):
    """On a 4-device mesh with 4 slots, every column's ``ingest.h2d``
    holds one ``ingest.stack``, and every iteration epoch and the
    finalize each close one ``slots.combine`` inside their top-level
    span; the traced solve is bitwise the untraced one."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_SPANS_SCRIPT, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    path, iters = out.stdout.split()[-2:]
    iters = int(iters)
    spans = read_trace(path)
    cols = 2                                # ceil(8 chunks / 4 slots)

    h2d = [s for s in spans if s["phase"] == "ingest.h2d"]
    stack = [s for s in spans if s["phase"] == "ingest.stack"]
    assert len(h2d) == len(stack) == (iters + 1) * cols
    # A span is journaled when it closes: each stack just before its h2d.
    for inner, outer in zip(stack, h2d):
        assert inner["col"] == outer["col"]
        assert inner["t"] >= outer["t"] - 1e-3
        assert inner["t"] + inner["dur_s"] <= \
            outer["t"] + outer["dur_s"] + 1e-3

    top = [s["phase"] for s in spans if s["phase"] in
           ("slots.combine", "solve.iterate", "solve.finalize")]
    assert top == (["slots.combine", "solve.iterate"] * iters
                   + ["slots.combine", "solve.finalize"])


def test_single_runtime_opens_no_sharded_spans(tmp_path):
    """The one-device runtime neither stacks slot columns nor combines
    slot partials, so it opens neither span."""
    cfg = SolverConfig(reduce="bucketed", max_iters=20, checkpoint_every=0)
    with Tracer(trace_path(tmp_path, "single")) as tr:
        solve_streaming_host(_source(), cfg, q=2, tracer=tr)
    phases = {s["phase"] for s in read_trace(tr.path)}
    assert {"solve.iterate", "ingest.h2d"} <= phases
    assert not phases & {"ingest.stack", "slots.combine"}


def test_refresh_bitwise_identical_obs_on_off(tmp_path):
    plain = RefreshEngine(tmp_path / "off", SPEC, cfg=CFG)
    obs = make_obs(tmp_path / "on", role="engine")
    traced = RefreshEngine(tmp_path / "on", SPEC, cfg=CFG, obs=obs)
    for scale in SCALES:
        a = plain.refresh(budget_scale=scale)
        b = traced.refresh(budget_scale=scale)
        for f in GEN_FIELDS:
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)),
                                          err_msg=f)
        # Same solver identity hash: the traced solve IS the same solve.
        assert a.fingerprint.tobytes() == b.fingerprint.tobytes()
        assert a.iters == b.iters
    obs.close()
    phases = [s["phase"] for s in read_trace(obs.tracer.path)]
    # The refresh journal holds the solve spans AND the publish steps.
    assert "solve.iterate" in phases and "solve.finalize" in phases
    assert phases.count("refresh.publish") == 2 * len(SCALES)


# The spans that tile one refresh, in order (solve.iterate once per
# iteration, refresh.publish for the record, then for the pointer).
TOP_PHASES = ("refresh.prepare", "solve.fingerprint", "solve.iterate",
              "solve.finalize", "refresh.stamp", "refresh.publish",
              "refresh.readback")
CKPT_EVERY = 2


def _journaled_refreshes(tmp_path, cfg):
    """Refresh SCALES with a journal and without; returns (generations
    with it, generations without it, the journal's spans)."""
    plain = RefreshEngine(tmp_path / "off", SPEC, cfg=cfg)
    obs = make_obs(tmp_path / "on", role="engine")
    traced = RefreshEngine(tmp_path / "on", SPEC, cfg=cfg, obs=obs)
    on = [traced.refresh(budget_scale=s) for s in SCALES]
    off = [plain.refresh(budget_scale=s) for s in SCALES]
    obs.close()
    return on, off, read_trace(obs.tracer.path)


def test_refresh_top_level_phases_in_order(tmp_path):
    gens, _, spans = _journaled_refreshes(tmp_path, CFG)
    # A span is journaled when it closes, so the top-level spans (which
    # never nest in one another) appear in the order they ran.
    top = [s["phase"] for s in spans if s["phase"] in TOP_PHASES]
    want = []
    for g in gens:
        want += (["refresh.prepare", "solve.fingerprint"]
                 + ["solve.iterate"] * g.iters
                 + ["solve.finalize", "refresh.stamp", "refresh.publish",
                    "refresh.publish", "refresh.readback"])
    assert top == want
    assert [s["step"] for s in spans if s["phase"] == "refresh.publish"] \
        == ["record", "pointer"] * len(SCALES)


def test_refresh_ckpt_save_spans_match_the_cadence(tmp_path):
    cfg = CFG.replace(checkpoint_every=CKPT_EVERY)
    on, off, spans = _journaled_refreshes(tmp_path, cfg)
    for a, b in zip(on, off):   # saves under a journal change nothing
        for f in GEN_FIELDS:
            np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                          np.asarray(getattr(b, f)),
                                          err_msg=f)
        assert a.iters == b.iters
    # Per refresh: one save per CKPT_EVERY iterations but the last, the
    # finalize-entry save, one per CKPT_EVERY finalize columns but the
    # last column.
    want = sum((g.iters - 1) // CKPT_EVERY + 1 + (CHUNKS - 1) // CKPT_EVERY
               for g in on)
    ckpt_spans = [s["phase"] for s in spans if s["phase"].startswith("ckpt.")]
    # Each save holds one gather and one write, closed before it closes.
    assert ckpt_spans == ["ckpt.gather", "ckpt.write", "ckpt.save"] * want
    saves = [s for s in spans if s["phase"] == "ckpt.save"]
    inner = [s for s in spans if s["phase"] in ("ckpt.gather", "ckpt.write")]
    for i, save in enumerate(saves):
        for child in inner[2 * i:2 * i + 2]:
            assert child["t"] >= save["t"] - 1e-3
            assert child["dur_s"] <= save["dur_s"]


def test_tracer_span_reaches_the_profiler_host_plane(tmp_path):
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    log_dir = str(tmp_path / "profile")
    tr = Tracer(trace_path(tmp_path, "prof"))
    jax.profiler.start_trace(log_dir)
    try:
        with tr.span("test.profiled", step=1):
            jnp.arange(8).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tr.close()
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert "test.profiled" in names
    # The journal keeps its format: one record with the span's attrs.
    (rec,) = read_trace(tr.path)
    assert rec["phase"] == "test.profiled" and rec["step"] == 1
    assert set(rec) == {"phase", "t", "dur_s", "pid", "step"}


# ---------------------------------------------------------------------------
# /metrics over the wire: replica RPC, front aggregation, rid correlation.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two obs-enabled replicas behind a traced front, ready to query."""
    path = tmp_path_factory.mktemp("obs_front")
    eng = RefreshEngine(path, SPEC, cfg=CFG)
    refs = []
    for s in SCALES:
        g = eng.refresh(budget_scale=s)
        refs.append(eng.decision_service(
            generation=g, fallback=False).decide_batch(np.arange(SPEC.n)))

    reps, clients = [], []
    for i in range(2):
        e = RefreshEngine.attach(path, cfg=CFG,
                                 obs=make_obs(path, role=f"replica{i}"))
        rep = ReplicaServer(e, index=i, cache_chunks=CHUNKS, poll_s=0.02)
        port = rep.start()
        reps.append(rep)
        clients.append(ReplicaClient("127.0.0.1", port))
    front_obs = make_obs(path, role="front")
    front = Front(clients, tracer=front_obs.tracer)
    yield SimpleNamespace(path=path, reps=reps, clients=clients,
                          front=front, front_obs=front_obs, refs=refs)
    for c in clients:
        c.close()
    front.shutdown()
    for r in reps:
        r.stop()
    front_obs.close()
    for r in reps:
        r.engine.obs.close()


def test_replica_metrics_op_matches_health(served):
    rc = served.clients[0]
    for u in (3, 700, 3):
        rc.call({"op": "lookup", "user": u})
    h = rc.call({"op": "health"})
    m = rc.call({"op": "metrics"})
    assert m["replica"] == 0
    series = parse_prometheus(m["text"])
    assert series[("serve_queries", ())] == h["queries"]
    assert series[("serve_fills", ())] == h["fills"]
    assert series[("serve_hits", ())] == h["hits"]
    assert series[("serve_stale_serves", ())] == h["stale_serves"] == 0
    assert series[("replica_rebinds", ())] == served.reps[0].rebinds
    # The fill latencies landed in the shared-ladder histogram.
    assert series[("serve_fill_seconds_count", ())] == h["fills"]
    # The snapshot in the payload renders to the same text.
    assert render_prometheus(m["snapshot"]) == m["text"]


def test_front_metrics_aggregate_is_sum_of_replicas(served):
    front = served.front
    for u in (1, 2, 3, 4, 5):
        r = front.decide(u)
        assert not r["stale"]
        assert (np.asarray(r["x"]) == served.refs[-1][u]).all()
    front.decide_batch([7, 8, 9])
    series = parse_prometheus(front.metrics_text())
    assert series[("front_requests", ())] == front.stats["requests"]
    for name in ("serve_queries", "serve_fills", "replica_rebinds"):
        per = [series.get((name, (("replica", str(i)),)), 0.0)
               for i in range(2)]
        assert series[(name, ())] == sum(per), name
    # Both replicas actually answered traffic (round-robin works).
    per_q = [series.get(("serve_queries", (("replica", str(i)),)), 0.0)
             for i in range(2)]
    assert all(q > 0 for q in per_q)


def test_request_id_correlates_front_and_replica_spans(served):
    # User 513 lives in chunk 4 — untouched by the earlier tests, so
    # this decide provably misses the cache and fills under its rid.
    served.front.decide(513)
    served.front_obs.tracer.flush()
    for rep in served.reps:
        rep.engine.obs.tracer.flush()
    fronts = [s for s in read_trace(trace_path(served.path, "front"))
              if s["phase"] == "front.decide"]
    assert fronts, "front.decide spans missing"
    rids = {s["rid"] for s in fronts}
    fills = []
    for i in range(2):
        fills += [s for s in
                  read_trace(trace_path(served.path, f"replica{i}"))
                  if s["phase"] == "serve.fill" and "rid" in s]
    # Every front rid that caused a fill shows up replica-side; the
    # decide(42) above certainly missed the cache at least once overall.
    assert rids & {s["rid"] for s in fills}
    assert all("-" in r for r in rids)      # pid-qualified ids


# ---------------------------------------------------------------------------
# Supervisor status durability: SUPERVISOR.json via ckpt.write_json.
# ---------------------------------------------------------------------------

def test_supervisor_publish_routes_through_write_json(tmp_path, monkeypatch):
    calls = []
    real = ckpt.write_json

    def spy(root, name, doc):
        calls.append((name, dict(doc)))
        return real(root, name, doc)

    monkeypatch.setattr(ckpt, "write_json", spy)
    sup = Supervisor(tmp_path, {"kind": "solve"}, cfg=SupervisorConfig(),
                     devices=2)
    sup._publish("watching")
    assert calls and calls[-1][0] == "SUPERVISOR.json"
    doc = calls[-1][1]
    assert doc["state"] == "watching" and doc["devices"] == 2
    assert set(doc) == {"ok", "state", "spawns", "crash_restarts",
                        "hang_takeovers", "restarts", "kills_injected",
                        "stops_injected", "degraded_spawns",
                        "max_lease_age", "term", "devices", "last_rc",
                        "worker", "updated_wall"}
    assert doc["worker"] is None            # no worker has announced yet
    # And the durable file is what health() will read back.
    on_disk = json.loads((tmp_path / "SUPERVISOR.json").read_text())
    assert on_disk["state"] == "watching"
