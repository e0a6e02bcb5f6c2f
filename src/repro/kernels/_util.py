"""Shared helpers for the kernel wrappers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def resolve_interpret(interpret):
    """The ``interpret`` flag a wrapper passes to ``pl.pallas_call``.

    An explicit bool is kept (tests and AOT compiles for a described
    TPU pass ``False``). ``None`` compiles on a TPU and interprets on the
    CPU, where the kernel bodies run through XLA for correctness tests.
    Any other backend raises: the kernels are written for the TPU, and an
    interpreter there would hide that the hot path never ran compiled.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"backend {backend!r} is neither")


def pad_rows(x, pad, value=0.0):
    """Append ``pad`` constant rows on the user axis (no-op if pad == 0).

    Wrappers pad ragged shards up to a tile multiple; the pad values are
    chosen per kernel so padded rows are inert (see each caller).
    """
    if not pad:
        return x
    return jnp.pad(x, ((0, pad), (0, 0)), constant_values=value)
