"""Pallas TPU kernels for the solver's compute hot-spots (+ jnp oracles).

adjusted_topc   — fused adjusted-profit + top-Q select + consumption (DD map)
scd_candidates  — Algorithm 5 linear-time candidate generation (SCD map)
bucket_hist     — Section 5.2 bucketed-reduce histogram (SCD reduce, map side)
scd_fused_hist  — scd_candidates + bucket_hist in one streaming pass: the
                  (n, K) candidate intermediates never leave VMEM. Accepts
                  ``hist_init``/``top_init`` accumulator seeds so the
                  out-of-core chunked solve can carry the (K, E+1)
                  histogram across chunk calls with the identical f32
                  addition chain as one unchunked call (bit-identity
                  contract: core/solver.py).

All wrappers take a user-axis tile (``pick_tile`` chooses; ragged shards
are padded with inert rows inside the wrapper). ``scd_fused_hist`` puts
users on lanes, in (K, tile) blocks: its tile comes from
``scd_fused.LANE_TILE`` and it masks a ragged last block inside the
kernel. They compile on a TPU and
run under the Pallas interpreter on the CPU; any other backend raises
(``_util.resolve_interpret``). ``use_pallas=False`` dispatches to the
pure-jnp oracles in ``ref``.
"""
from . import ops, ref  # noqa: F401
from .ops import (  # noqa: F401
    adjusted_topc,
    bucket_hist,
    pick_tile,
    scd_candidates,
    scd_fused_hist,
)
