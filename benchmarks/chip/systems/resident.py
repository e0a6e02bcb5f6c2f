"""System under test: the resident single-device solve, ``repro.core.solver``.

The instance lives on the chip; each ``solve`` operation is one cold
solve from all-ones multipliers through the jitted entry that
``solver.solve_fn`` builds (the program ``solver.solve`` runs), timed
from the call to the completion of its result on the device. Every
solve's multipliers and totals are kept; the full decisions ``x`` of one
solve, drawn from the seed by reservoir sampling, are kept for the
comparison.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import compare, instance, reference


class System:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.count = 0
        self.kept = None            # (index, x on device) of the sample

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core import SolverConfig
        from repro.core.solver import solve_fn
        from repro.core.types import SparseKP

        c = self.cfg
        dev = self.ctx.devices[0]
        p, b = instance.device_instance(self.ctx.seed, c["n_users"], c["k"],
                                        dev)
        self.budgets = instance.budgets(c["n_users"], c["k"], c["q"],
                                        c["tightness"])
        self.kp = SparseKP(p=p, b=b,
                           budgets=jax.device_put(jnp.asarray(self.budgets),
                                                  dev))
        self.lam0 = jax.device_put(jnp.ones((c["k"],), jnp.float32), dev)
        self.fn = solve_fn(SolverConfig(**c["solver"]), c["q"])
        jax.block_until_ready(self.kp)

    def serve(self, op):
        import jax

        if op["op"] != "solve":
            raise ValueError(f"the resident system cannot serve {op['op']!r}")
        t0 = time.perf_counter()
        res = jax.block_until_ready(self.fn(self.kp, self.lam0))
        seconds = time.perf_counter() - t0
        out = {"seconds": seconds, "iters": int(res.iters),
               "lam": np.asarray(res.lam), "r": np.asarray(res.r),
               "primal": float(res.primal), "dual": float(res.dual)}
        self.count += 1
        if self.ctx.rng.random() < 1.0 / self.count:
            self.kept = (self.count - 1, res.x)
        return out

    def begin_window(self):
        """Sample only among the window's solves, not the warm-up."""
        self.count, self.kept = 0, None

    def _release(self):
        """Host copies of what the comparison needs; device state freed."""
        p = np.asarray(self.kp.p)
        b = np.asarray(self.kp.b)
        idx, x = self.kept
        x = np.asarray(x)
        self.kp = self.lam0 = self.fn = self.kept = None
        return p, b, idx, x

    def check(self, records):
        p, b, idx, x = self._release()
        self.data = compare.Data(p, b, self.budgets, self.cfg["q"])
        self.sample_lam = records[idx]["lam"]
        return compare.resident(self.data, records, idx, x, self.ctx.limits,
                                self.ctx.log)

    def control(self, dtype):
        """The numbers of the reference in ``dtype`` put in the program's
        place, at the sampled solve's multipliers (after ``check``)."""
        return compare.resident_control(self.data, self.sample_lam, dtype)

    def fault_unchanged(self):
        """fixed_point of a solve whose multiplier step leaves its state
        unchanged: the all-ones start returned as the answer."""
        d = self.data
        return reference.fixed_point(d.p, d.b, d.budgets,
                                     np.ones(d.p.shape[1]), d.q)

    def close(self):
        self.kp = self.kept = self.data = None


def build(ctx):
    return System(ctx)
