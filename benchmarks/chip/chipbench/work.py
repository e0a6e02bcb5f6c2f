"""Work of one SCD map + bucketed reduce pass, counted from its shapes.

The bytes are those the algorithm needs, whatever layout or kernel runs
it: p and b read once, the multipliers and the edge ladder read, the
(K, E+1) histogram and the (K,) running maximum written. The operations
are per user and knapsack: the adjusted profit (2), its clip (1), the two
order statistics of Algorithm 5 (2 compares), the candidate's threshold
level and ratio (3), and a binary search of the E edges (log2 E
compares), plus the histogram add (1).
"""
from __future__ import annotations

import math

F32 = 4


def scd_pass(n: int, k: int, edges: int) -> tuple[float, float]:
    """(operations, bytes) of one SCD pass over n users and K knapsacks."""
    nbytes = (2 * n * k + k + k * edges + k * (edges + 1) + k) * F32
    per_item = 2 + 1 + 2 + 3 + math.ceil(math.log2(edges + 1)) + 1
    return float(n * k * per_item), float(nbytes)


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
