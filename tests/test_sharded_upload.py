"""The sharded host-fed runtime's column upload (``_ShardedRuntime._produce``).

Each chip's ``(spd, chunk, K)`` piece of a column is assembled on the
host from its slots' chunks (a view at one slot a chip, a stack of the
chip's slots otherwise) and uploaded straight to that chip. The column
has to be, bit for bit and in sharding, the one a host stack of all S
chunks followed by one sharded ``device_put`` gives: a full column, the
ragged last chunk, the inert chunks past the last real one, and a
screened column whose retired slot is fed zeros. The ``ingest.stack``
span carries the host bytes copied for the column.

The mesh needs four host devices, forced in a subprocess as
``tests/test_obs.py`` does, so they never leak into this process's JAX.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
from types import SimpleNamespace
import jax
import numpy as np
from repro.core import SolverConfig
from repro.core.prefetch import (HostChunkSource, _ShardedRuntime,
                                 host_array_source)
from repro.obs import Tracer, read_trace, trace_path

root = sys.argv[1]
assert len(jax.devices()) == 4, jax.devices()
# 808 users in chunks of 64: 13 chunks, the last ragged (40 users), so
# 4 or 8 slots hold 3 inert chunks past the last real one.
N, K, CHUNK = 808, 6, 64
rng = np.random.default_rng(7)
P_ = rng.random((N, K), dtype=np.float32)
B_ = rng.random((N, K), dtype=np.float32)
cfg = SolverConfig(reduce="bucketed", max_iters=5, checkpoint_every=0)


def source(wide):
    src = host_array_source(P_, B_, np.full(K, N / 8.0), CHUNK)
    if not wide:
        return src
    # A source producing float64 chunks, cast to cfg.dtype on the host.
    fn = lambda i: tuple(a.astype(np.float64) for a in src.fn(i))
    return HostChunkSource(n=src.n, k=K, chunk=CHUNK, budgets=src.budgets,
                           fn=fn)


def stacked_put(rt, j, screen):
    dt = np.dtype(cfg.dtype)
    ps, bs = rt._fetch_cols(j, screen, dt)
    return tuple(jax.device_put(np.ascontiguousarray(np.stack(cs), dtype=dt),
                                rt.slot_sh) for cs in (ps, bs))


def same(out, ref, rt):
    for o, r in zip(out, ref):
        if o.sharding != rt.slot_sh or o.dtype != r.dtype:
            return False
        if not np.array_equal(np.asarray(o), np.asarray(r)):
            return False
        rs = {s.device: np.asarray(s.data) for s in r.addressable_shards}
        for s in o.addressable_shards:
            if not np.array_equal(np.asarray(s.data), rs[s.device]):
                return False
    return True


res = {}
for name, slots, devs, wide in [("s4d4", 4, 4, False), ("s8d4", 8, 4, False),
                                ("s4d1", 4, 1, False),
                                ("s4d4_f64", 4, 4, True)]:
    mesh = jax.make_mesh((devs,), ("slots",), devices=jax.devices()[:devs])
    rt = _ShardedRuntime(source(wide), cfg, 1, mesh, slots, True)
    with Tracer(trace_path(root, name)) as tr:
        rt.tracer = tr
        cols = [same(rt._produce(j), stacked_put(rt, j, False), rt)
                for j in range(rt.cps)]
        # Screen column 0: slot 1 retired (fed the shared zero chunk).
        active = np.ones(slots * rt.cps, bool)
        active[1 * rt.cps + 0] = False
        rt.scr = SimpleNamespace(active=active,
                                 note_bound=lambda g, p, b: None)
        screened = same(rt._produce(0, screen=True),
                        stacked_put(rt, 0, True), rt)
    spans = read_trace(tr.path)
    res[name] = {
        "slots": slots, "devs": devs, "cps": rt.cps, "cols": cols,
        "screened": screened,
        "stack_bytes": [s["bytes"] for s in spans
                        if s["phase"] == "ingest.stack"],
        "n_h2d": sum(s["phase"] == "ingest.h2d" for s in spans),
    }
print(json.dumps(res))
"""

CASES = ["s4d4", "s8d4", "s4d1", "s4d4_f64"]
CHUNKS, CHUNK, K = 13, 64, 6


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(tmp_path_factory.mktemp("up"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_columns_match_stacked_sharded_put(results, case):
    """Every column, the ragged and the inert chunks among them, is the
    stacked sharded put bit for bit, in the same sharding."""
    r = results[case]
    assert r["cps"] == -(-CHUNKS // r["slots"])   # 4 or 2 columns
    assert r["cols"] == [True] * r["cps"]


@pytest.mark.parametrize("case", CASES)
def test_screened_column_matches_stacked_sharded_put(results, case):
    """A screened column, whose retired slot is fed the shared zero
    chunk, goes through the same per-chip assembly."""
    assert results[case]["screened"]


@pytest.mark.parametrize("case", CASES)
def test_stack_span_counts_host_bytes_copied(results, case):
    """One ``ingest.stack`` per ``ingest.h2d``; its ``bytes`` are 0 at one
    slot a chip (each piece is a view of its chunk), else the stack of
    every chip's slots, p and b: ``2·spd·chunk·K·4`` a chip. The last
    span is the screened column's."""
    r = results[case]
    slots, cps, devs = r["slots"], r["cps"], r["devs"]
    assert r["n_h2d"] == len(r["stack_bytes"]) == cps + 1
    chunk_bytes = 2 * CHUNK * K * 4                   # p and b, float32
    if case == "s4d4":
        want = [0] * (cps + 1)
    elif case == "s4d4_f64":
        # One slot a chip, float64 chunks: only the casts copy, and the
        # inert and retired chunks are float32 zeros already.
        real = [sum(s * cps + j < CHUNKS for s in range(slots))
                for j in range(cps)]
        want = [n * chunk_bytes for n in real + [real[0] - 1]]
    else:
        want = [devs * (slots // devs) * chunk_bytes] * (cps + 1)
    assert r["stack_bytes"] == want
