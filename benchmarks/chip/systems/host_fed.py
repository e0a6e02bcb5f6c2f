"""System under test: the daily refresh, ``repro.serve.RefreshEngine``.

The instance is a dataset held in host memory (made on the device from
the seed and copied to the host in set-up), served to the engine chunk
by chunk through the program's ``host_array_source``, the way a
memory-mapped file in the page cache would be. Each ``refresh``
operation is one ``RefreshEngine.refresh`` with the day's budget scale,
warm-started from the live generation and timed until it returns
published: record saved, ``LIVE.json`` flipped, both fsynced with their
directories. The engine's root lies in the checkout (``state_dir``), on
the checkout's disk. On ``mesh_devices`` > 1 the engine feeds ``slots``
virtual shards over a mesh of that many chips through the sharded
runtime.

The comparison reads published generations back from disk the way a
reader gets them: the record through the engine (the last one through
``LIVE.json``), every user's decision through the program's export
(``chunked.decisions_rows`` chunk by chunk at the record's multipliers
and threshold), and a sample of users through ``decision_service()``
lookups, which have to agree with the export bit for bit.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time
import types

import numpy as np

from chipbench import compare, instance, reference

LOOKUP_USERS = 4096


class SyncWitness:
    """The paths of the descriptors that the process fsyncs while armed.

    Installed over ``os.fsync`` for the life of the system, it lets the
    comparison hold each refresh to the durability guarantee: the record
    and ``LIVE.json`` synced before their renames, their directories
    after."""

    def __init__(self):
        self.paths = None
        self._real = os.fsync

    def _fsync(self, fd):
        if self.paths is not None:
            try:
                self.paths.add(os.readlink(f"/proc/self/fd/{fd}"))
            except OSError:
                pass
        return self._real(fd)

    def install(self):
        os.fsync = self._fsync

    def uninstall(self):
        os.fsync = self._real


def durable_paths(root, gen) -> set:
    """What publishing generation ``gen`` under ``root`` has to fsync."""
    rec = f"{root}/gen_{gen:06d}/record"
    return {f"{rec}/step_00000000.tmp/manifest.json", rec,
            f"{root}/LIVE.json.tmp", str(root)}


class System:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.root = self.witness = None

    def setup(self):
        import jax

        from repro.core import SolverConfig
        from repro.core.prefetch import host_array_source
        from repro.serve import RefreshEngine, WorkloadSpec

        c = self.cfg
        n, k, chunk = c["n_users"], c["k"], c["chunk"]
        p, b = instance.device_instance(self.ctx.seed, n, k,
                                        self.ctx.devices[0])
        self.p, self.b = np.asarray(p), np.asarray(b)
        del p, b
        self.base = instance.budgets(n, k, c["q"], c["tightness"])
        P, B, base = self.p, self.b, self.base

        def make_source(spec):
            budgets = (base * np.float32(spec.budget_scale)).astype(np.float32)
            return host_array_source(P, B, budgets, chunk)

        mesh = None
        if c["mesh_devices"] > 1:
            mesh = jax.make_mesh((c["mesh_devices"],), ("slots",),
                                 devices=self.ctx.devices[:c["mesh_devices"]])
        self.root = os.path.realpath(tempfile.mkdtemp(
            prefix="refresh_", dir=self.ctx.state_dir))
        self.witness = SyncWitness()
        self.witness.install()
        self.engine = RefreshEngine(
            self.root,
            WorkloadSpec(seed=self.ctx.seed, n=n, k=k, chunk=chunk,
                         q=c["q"], tightness=c["tightness"]),
            make_source=make_source, cfg=SolverConfig(**c["solver"]),
            mesh=mesh, slots=c["slots"],
            obs=types.SimpleNamespace(tracer=self.ctx.collector))

    def serve(self, op):
        if op["op"] != "refresh":
            raise ValueError(f"the host-fed system cannot serve {op['op']!r}")
        self.witness.paths = set()
        t0 = time.perf_counter()
        gen = self.engine.refresh(budget_scale=op["budget_scale"])
        seconds = time.perf_counter() - t0
        synced, self.witness.paths = self.witness.paths, None
        return {"seconds": seconds, "iters": int(gen.iters), "gen": gen.gen,
                "budget_scale": op["budget_scale"],
                "unsynced": len(durable_paths(self.root, gen.gen) - synced)}

    def begin_window(self):
        pass

    def export(self, gen):
        """(n, K) bool: every user's published decision, exported chunk by
        chunk through the program's decision arithmetic at the record's
        multipliers and removal threshold."""
        import jax
        import jax.numpy as jnp

        from repro.core.chunked import decisions_rows

        q = gen.spec.q
        fn = jax.jit(lambda p, b, lam, valid, tau:
                     decisions_rows(p, b, lam, q, valid, tau))
        source = self.engine.make_source(gen.spec)
        lam, tau = jnp.asarray(gen.lam), jnp.asarray(gen.tau)
        n, chunk = source.n, source.chunk
        x = np.empty((n, source.k), bool)
        for ci in range(-(-n // chunk)):
            lo = ci * chunk
            p, b = source.fn(ci)
            valid = jnp.asarray(lo + np.arange(chunk) < n)
            rows = np.asarray(fn(jnp.asarray(p), jnp.asarray(b), lam, valid,
                                 tau))
            x[lo:lo + chunk] = rows[:n - lo]
        return x

    def published(self, rec, live=False):
        """(generation fields, every user's decision, lookup users, their
        rows as the decision service answers them)."""
        eng = self.engine
        gen = eng.live() if live else eng.generation(rec["gen"])
        if gen.spec.budget_scale != rec["budget_scale"]:
            raise RuntimeError(f"generation {gen.gen} was published for "
                               f"budget scale {gen.spec.budget_scale}, "
                               f"asked {rec['budget_scale']}")
        svc = eng.decision_service() if live else eng.decision_service(
            generation=gen, fallback=False)
        users = np.sort(self.ctx.rng.choice(self.cfg["n_users"],
                                            LOOKUP_USERS, replace=False))
        rows = np.asarray(svc.decide_batch(users), bool)
        fields = {"lam": np.asarray(gen.lam), "tau": float(gen.tau),
                  "r": np.asarray(gen.r), "primal": float(gen.primal),
                  "dual": float(gen.dual)}
        return fields, self.export(gen), users, rows

    def sample(self, records):
        """The refreshes compared: the one with most iterations and the
        last one (read through the live pointer), plus one drawn from
        the seed."""
        longest = max(range(len(records)), key=lambda i: records[i]["iters"])
        picks = {longest, len(records) - 1}
        picks.add(int(self.ctx.rng.integers(len(records))))
        return sorted(picks)

    def check(self, records):
        c = self.cfg
        data = compare.Data(self.p, self.b, self.base, c["q"])
        s = c["solver"]
        ladder = (s["profit_buckets"], s["profit_ladder_lo"],
                  s["profit_ladder_hi"])
        live = self.engine.live()
        per_unit = [{"live_lag": float(records[-1]["gen"] - live.gen),
                     "unsynced": float(sum(r["unsynced"] > 0
                                           for r in records))}]
        self.data, self.ladder, self.compared = data, ladder, []
        for i in self.sample(records):
            rec = records[i]
            fields, x, users, rows = self.published(
                rec, live=(i == len(records) - 1))
            budgets = (self.base * np.float32(rec["budget_scale"])).astype(
                np.float32)
            self.compared.append((budgets, fields, rec["gen"]))
            nums = compare.published(data, budgets, fields, x, ladder)
            nums["lookup_mismatch"] = compare.lookup_mismatch(x, users, rows)
            del x
            nums["fixed_point"] = reference.fixed_point(
                self.p, self.b, budgets, fields["lam"], c["q"])
            self.ctx.log(f"[check] generation {rec['gen']} (scale "
                         f"{rec['budget_scale']}, {rec['iters']} iters): "
                         f"{nums}")
            per_unit.append(nums)
        return compare.judge(per_unit, self.ctx.limits)

    def control(self, dtype):
        """The numbers of the reference in ``dtype`` put in the program's
        place, at each compared generation's multipliers (after
        ``check``)."""
        out = {}
        for budgets, fields, _ in self.compared:
            nums = compare.published(self.data, budgets, fields, None,
                                     self.ladder, dtype=dtype)
            for k, v in nums.items():
                out[k] = max(out.get(k, v), v)
        return out

    def fault_unchanged(self):
        """fixed_point of refreshes whose multiplier step leaves the state
        unchanged: each compared day publishes its parent's multipliers
        for its own budgets. The largest over the compared days, as a run
        is judged by its worst compared refresh."""
        out = []
        for budgets, _, gen in self.compared:
            parent = self.engine.generation(gen - 1)
            out.append(reference.fixed_point(self.p, self.b, budgets,
                                             np.asarray(parent.lam),
                                             self.cfg["q"]))
        return max(out)

    def close(self):
        if self.witness is not None:
            self.witness.uninstall()
        if self.root is not None:
            shutil.rmtree(self.root, ignore_errors=True)
            self.root = None


def build(ctx):
    return System(ctx)
