"""One operation per simulated day, budgets scaled by a daily draw.

The arithmetic of the program's refresh launcher
(``repro.launch.refresh._budget_schedule``): day 0 at scale 1.0, then
independent days uniform on ``1 +- spread``, rounded to 4 places, drawn
from ``numpy.random.default_rng(schedule_seed + 1000)``. The schedule
seed is fixed in the mix, so every run seed sees the same days in the
same order: the seed changes the data, never the work. Day 0 runs in
set-up (it builds the generation the window's refreshes start from);
the window runs days 1, 2, ... and starts again at day 1 after the last.
"""
import itertools

import numpy as np


def schedule(days, schedule_seed, spread):
    rng = np.random.default_rng(schedule_seed + 1000)
    return [1.0] + [round(float(s), 4)
                    for s in 1.0 + rng.uniform(-spread, spread, days - 1)]


def plan(mix):
    scales = schedule(mix["days"], mix["schedule_seed"], mix["spread"])
    ops = [{"op": mix["op"], "budget_scale": s} for s in scales]
    return [ops[0]], (dict(op) for op in itertools.cycle(ops[1:]))
