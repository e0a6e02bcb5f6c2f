"""Logical-axis sharding context for the model zoo.

Models annotate activations with *logical* axes ("batch", "seq", "heads",
"ffn", "experts", "vocab"); the launcher binds logical axes to mesh axes
once (`set_rules`), and `constrain()` becomes `with_sharding_constraint`
under the active mesh — or a no-op on a single device (smoke tests).

Default production binding (launch/mesh.py):
    batch   -> ("pod", "data")     [DP]
    seq     -> "model"             [Megatron-style sequence parallelism for
                                    the residual stream between blocks]
    heads/ffn/experts/vocab -> "model"  [TP/EP]
    fsdp    -> "data"              [parameter + optimizer-state sharding]
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P, get_abstract_mesh

_state = threading.local()


DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": "model",
    "kv_seq": "model",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "experts": "model",
    "vocab": "model",
    "fsdp": "data",
    "d_model": None,
    "state": None,
    None: None,
}


def set_rules(rules: Optional[dict]):
    """Bind logical axes to mesh axes. None disables all constraints."""
    _state.rules = rules


def get_rules() -> Optional[dict]:
    return getattr(_state, "rules", None)


def spec(*logical) -> P:
    """PartitionSpec for a tuple of logical axis names (None entries ok)."""
    rules = get_rules()
    if rules is None:
        return P()
    return P(*[rules.get(ax, None) for ax in logical])


def constrain(x, *logical):
    """with_sharding_constraint by logical axes; no-op without rules."""
    rules = get_rules()
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec(*logical))


def mesh_axis(logical: str):
    """The mesh axis (name or tuple) bound to a logical axis, or None."""
    rules = get_rules()
    if rules is None:
        return None
    return rules.get(logical, None)


def axis_size(logical: str) -> int:
    """Size of the mesh axis bound to a logical name (1 if unbound)."""
    ax = mesh_axis(logical)
    if ax is None:
        return 1
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return 1
    names = ax if isinstance(ax, tuple) else (ax,)
    size = 1
    for n in names:
        size *= dict(zip(mesh.axis_names, mesh.axis_sizes))[n]
    return size
