"""Mixture-of-Experts FFN with expert parallelism.

Two routers: heuristic ``topk`` and the paper's ``scd`` (knapsack-priced,
exact global capacity — see core/moe_router.py). Two compute paths:

* ``moe_train`` — sort-free scatter dispatch + all_to_all over the expert
  (model) mesh axis inside shard_map: tokens travel to the shard owning
  their expert, grouped GEMMs run per local expert, results return by the
  inverse all_to_all. This is the compute-efficient path for train/prefill.

* ``moe_decode`` — dense einsum over the (expert-sharded) E axis with a
  combine mask, in plain pjit/GSPMD. At decode the MoE is bound by reading
  expert weights (which EP reads exactly once per shard either way), and
  the E/topk compute overhead is irrelevant, so this avoids the a2a
  round-trip entirely for one-token steps.

Shared experts (DeepSeek-style) are ordinary dense MLPs handled by the
caller; this module owns routed experts only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import get_abstract_mesh

from ..core.moe_router import scd_route, topk_route
from .layers import truncnorm
from . import sharding


def init_moe(key, cfg):
    m = cfg.moe
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": truncnorm(k1, (d, m.n_experts), jnp.float32, d ** -0.5),
        "wi": truncnorm(k2, (m.n_experts, d, 2, m.d_ff), cfg.param_dtype, d ** -0.5),
        "wo": truncnorm(k3, (m.n_experts, m.d_ff, d), cfg.param_dtype, m.d_ff ** -0.5),
    }


def _route(logits, cfg, decode=False):
    m = cfg.moe
    if m.router == "scd" and not decode:
        out = scd_route(logits, q=m.topk, capacity_factor=m.capacity_factor,
                        iters=m.scd_iters)
    else:
        # Decode always uses plain top-k: a one-token step has no batch-wide
        # capacity to price (knapsack budgets are a throughput-time concept);
        # matches production MoE serving practice.
        out = topk_route(logits, q=m.topk)
    # renormalise combine weights over the chosen experts
    denom = jnp.maximum(out.combine.sum(-1, keepdims=True), 1e-9)
    return out.combine / denom, out.mask


def moe_decode(p, cfg, x, act="silu"):
    """One-token MoE: dense over the expert-sharded axis (see module doc).

    x: (B, 1, D) -> (B, 1, D).
    """
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    logits = t.astype(jnp.float32) @ p["router"]
    combine, _ = _route(logits, cfg, decode=True)           # (T, E)
    h = jnp.einsum("td,edgf->tegf", t, p["wi"].astype(t.dtype))
    gate, up = h[..., 0, :], h[..., 1, :]
    g = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate, approximate=True)
    y = jnp.einsum("tef,efd->ted", g * up, p["wo"].astype(t.dtype))
    y = jnp.einsum("ted,te->td", y, combine.astype(t.dtype))
    return y.reshape(b, s, d)


def moe_train(p, cfg, x, act="silu"):
    """Training/prefill MoE with a2a expert parallelism.

    x: (B, S, D) global view. Runs in shard_map over the full mesh when
    sharding rules are active (batch over data axes, seq + experts over
    the model axis); falls back to a single-device local dispatch when not.
    """
    rules = sharding.get_rules()
    model_ax = sharding.mesh_axis("experts")
    if rules is None or model_ax is None:
        return _moe_local(p, cfg, x, act)

    mesh = get_abstract_mesh()
    batch_ax = sharding.mesh_axis("batch")
    seq_ax = sharding.mesh_axis("seq")
    P = jax.sharding.PartitionSpec
    x_spec = P(batch_ax, seq_ax, None)
    # Experts sharded over the model axis; the fsdp ("data") shards of the
    # weights are re-gathered on shard_map entry (the FSDP all-gather) so
    # the body sees full D / d_ff.
    p_spec = {
        "router": P(),
        "wi": P(model_ax, None, None, None),
        "wo": P(model_ax, None, None),
    }
    # capacity reduction for the scd router spans every token shard
    all_axes = tuple(
        a for a in (batch_ax if isinstance(batch_ax, tuple) else (batch_ax,))
        if a is not None
    ) + ((seq_ax,) if seq_ax else ())

    def body(pp, xx):
        return _moe_a2a(pp, cfg, xx, act, model_ax, all_axes)

    return shard_map(
        body, mesh=mesh, in_specs=(p_spec, x_spec), out_specs=x_spec,
        check_vma=False,
    )(p, x)


def _moe_local(p, cfg, x, act):
    """Reference path (1 device): dense-over-experts with combine mask."""
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    logits = t.astype(jnp.float32) @ p["router"]
    combine, _ = _route(logits, cfg)
    h = jnp.einsum("td,edgf->tegf", t, p["wi"].astype(t.dtype))
    gate, up = h[..., 0, :], h[..., 1, :]
    g = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate, approximate=True)
    y = jnp.einsum("tef,efd->ted", g * up, p["wo"].astype(t.dtype))
    y = jnp.einsum("ted,te->td", y, combine.astype(t.dtype))
    return y.reshape(b, s, d)


def _moe_a2a(p, cfg, x, act, model_ax, token_axes):
    """shard_map body: local tokens -> a2a -> local expert GEMMs -> a2a back.

    x: (B_l, S_l, D) local shard. Expert weights arrive sharded over
    model_ax (E_l local experts) and gathered over the fsdp axis by
    shard_map's in_spec slicing... they arrive as (E_l, D_l?, ...) — we
    keep D unsharded here and shard only E (fsdp on experts' D is applied
    outside via the parameter specs; shard_map re-gathers it).
    """
    m = cfg.moe
    b_l, s_l, d = x.shape
    t_l = b_l * s_l
    xt = x.reshape(t_l, d)
    n_ms = jax.lax.psum(1, model_ax)
    e_l = p["wi"].shape[0]                                  # local experts

    # --- routing (global capacity via psum'd histograms for scd) ---------
    logits = xt.astype(jnp.float32) @ p["router"]           # (T_l, E)
    if m.router == "scd":
        from ..core.moe_router import scd_route_shmap
        axes = tuple(dict.fromkeys(
            token_axes + ((model_ax,) if model_ax else ())))  # dedupe, ordered
        combine, mask = scd_route_shmap(
            logits, q=m.topk, capacity_factor=m.capacity_factor,
            iters=m.scd_iters, axis=axes,
        )
    else:
        combine, mask = _route(logits, cfg)
    wsel, eid = jax.lax.top_k(jnp.where(mask, combine, -1.0), m.topk)  # (T_l,k)
    valid = wsel > 0

    # --- build per-target-shard send buffers ------------------------------
    k = m.topk
    pairs = t_l * k
    eid_f = eid.reshape(pairs)
    valid_f = valid.reshape(pairs)
    target = eid_f // e_l                                   # (pairs,) in [0, n_ms)
    onehot = jax.nn.one_hot(jnp.where(valid_f, target, n_ms), n_ms + 1,
                            dtype=jnp.int32)[:, :n_ms]      # invalid -> dropped
    pos = jnp.cumsum(onehot, axis=0) - onehot               # rank within target
    pos = (pos * onehot).sum(-1)                            # (pairs,)
    cap_send = int(cfg.moe.capacity_factor * pairs / n_ms) + 1
    ok = valid_f & (pos < cap_send)
    slot = jnp.where(ok, target * cap_send + pos, n_ms * cap_send)
    src = xt[jnp.repeat(jnp.arange(t_l), k)]                # (pairs, D)
    send_x = jnp.zeros((n_ms * cap_send + 1, d), x.dtype).at[slot].set(src)[:-1]
    send_le = jnp.full((n_ms * cap_send + 1,), e_l, jnp.int32).at[slot].set(
        eid_f % e_l)[:-1]
    send_x = send_x.reshape(n_ms, cap_send, d)
    send_le = send_le.reshape(n_ms, cap_send)

    # --- a2a to expert shards ---------------------------------------------
    recv_x = jax.lax.all_to_all(send_x, model_ax, 0, 0, tiled=True)
    recv_le = jax.lax.all_to_all(send_le, model_ax, 0, 0, tiled=True)
    rt = n_ms * cap_send
    rx = recv_x.reshape(rt, d)
    rle = recv_le.reshape(rt)                               # e_l == invalid

    # --- group by local expert, grouped GEMM ------------------------------
    r_onehot = jax.nn.one_hot(rle, e_l + 1, dtype=jnp.int32)[:, :e_l]
    r_pos = (jnp.cumsum(r_onehot, axis=0) - r_onehot)
    r_pos = (r_pos * r_onehot).sum(-1)
    cap_e = int(cfg.moe.capacity_factor * rt / e_l) + 1
    r_ok = (rle < e_l) & (r_pos < cap_e)
    r_slot = jnp.where(r_ok, rle * cap_e + r_pos, e_l * cap_e)
    buf = jnp.zeros((e_l * cap_e + 1, d), x.dtype).at[r_slot].set(rx)[:-1]
    buf = buf.reshape(e_l, cap_e, d)
    h = jnp.einsum("ecd,edgf->ecgf", buf, p["wi"].astype(x.dtype))
    gate, up = h[..., 0, :], h[..., 1, :]
    g = jax.nn.silu(gate) if act == "silu" else jax.nn.gelu(gate, approximate=True)
    y_buf = jnp.einsum("ecf,efd->ecd", g * up, p["wo"].astype(x.dtype))

    # --- ungroup, a2a back, combine ---------------------------------------
    y_r = jnp.where(
        r_ok[:, None], y_buf.reshape(e_l * cap_e, d)[jnp.clip(r_slot, 0, e_l * cap_e - 1)],
        0.0,
    )
    y_send = y_r.reshape(n_ms, cap_send, d)
    y_back = jax.lax.all_to_all(y_send, model_ax, 0, 0, tiled=True)
    y_flat = y_back.reshape(n_ms * cap_send, d)
    y_pairs = jnp.where(
        ok[:, None], y_flat[jnp.clip(slot, 0, n_ms * cap_send - 1)], 0.0
    )                                                       # (pairs, D)
    w_pairs = jnp.where(ok, wsel.reshape(pairs), 0.0)
    y = (y_pairs * w_pairs[:, None].astype(x.dtype)).reshape(t_l, k, d).sum(1)
    return y.reshape(b_l, s_l, d)
