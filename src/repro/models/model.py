"""Top-level model API: build, init, steps, input specs, sharding specs.

This is the single entry point used by smoke tests, the launcher, and the
multi-pod dry-run:

    cfg     = configs.registry.get("yi-34b")
    params  = jax.eval_shape(lambda k: init(cfg, k), key)   # no allocation
    specs   = shardings(cfg, cell)                          # PartitionSpec trees
    step    = make_train_step(cfg, opt_cfg)                 # jit-able fn
    inputs  = input_specs(cfg, cell)                        # ShapeDtypeStructs

Shape cells (the assignment's 4 input shapes): ``train_4k`` lowers
train_step; ``prefill_32k`` lowers the prefill serve step;
``decode_32k``/``long_500k`` lower one-token serve_step against a KV/SSM
cache of the given length.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, get_abstract_mesh

from ..optim import OptConfig, apply_updates, init_opt_state
from . import encdec as ed
from . import lm, sharding
from .config import ModelConfig


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> Optional[str]:
    """None if the (arch, cell) pair runs; otherwise the skip reason."""
    if cell.name == "long_500k":
        has_ssm = "mamba" in cfg.pattern
        if not has_ssm and not cfg.window:
            return ("long_500k needs sub-quadratic attention; "
                    f"{cfg.name} is pure full attention (skip per spec)")
    return None


# ---------------------------------------------------------------------------
# init / steps
# ---------------------------------------------------------------------------

def init(cfg: ModelConfig, key):
    if cfg.kind == "encdec":
        return ed.init_encdec(key, cfg)
    return lm.init_lm(key, cfg)


def _frames_len(cfg, seq_len):
    # audio stub: encoder frames take half the cell's token budget
    return max(seq_len // 2, 8)


def _text_len(cfg, seq_len):
    if cfg.kind == "encdec":
        return max(seq_len - _frames_len(cfg, seq_len), 8)
    if cfg.n_patches:
        return max(seq_len - cfg.n_patches, 8)
    return seq_len


def loss_fn(params, cfg, batch):
    if cfg.kind == "encdec":
        return ed.encdec_loss(params, cfg, batch["frames"], batch["tokens"],
                              batch["targets"])
    extra = batch.get("patches")
    return lm.lm_loss(params, cfg, batch["tokens"], batch["targets"],
                      mask=batch.get("mask"), extra_embeds=extra)


def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
        params, opt_state, metrics = apply_updates(params, grads, opt_state, opt_cfg)
        return params, opt_state, {"loss": loss, **metrics}
    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        if cfg.kind == "encdec":
            memory = ed.encode(params, cfg, batch["frames"])
            h = ed.decode_train(params, cfg, batch["tokens"], memory)
            logits = jnp.einsum("bd,vd->bv", h[:, -1],
                                params["head"]["e"].astype(h.dtype))
        else:
            h = lm.forward(params, cfg, batch["tokens"],
                           extra_embeds=batch.get("patches"))
            head = params.get("head", params["embed"])
            logits = jnp.einsum("bd,vd->bv", h[:, -1], head["e"].astype(h.dtype))
        return sharding.constrain(logits, "batch", "vocab")
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, caches, token, pos):
        if cfg.kind == "encdec":
            return ed.encdec_decode_step(params, cfg, caches, token, pos)
        return lm.decode_step(params, cfg, caches, token, pos)
    return decode_step


def init_cache(cfg: ModelConfig, params, batch, seq_len, frames=None):
    if cfg.kind == "encdec":
        return ed.init_encdec_cache(params, cfg, frames, batch, seq_len)
    return lm.init_cache(cfg, batch, seq_len)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — dry-run currency)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, cell: ShapeCell):
    """Abstract inputs for the cell. For decode cells this includes the
    cache tree (built with eval_shape; zero allocation)."""
    b = cell.global_batch
    sds = jax.ShapeDtypeStruct
    tl = _text_len(cfg, cell.seq_len)
    if cell.kind in ("train", "prefill"):
        batch = {
            "tokens": sds((b, tl), jnp.int32),
        }
        if cell.kind == "train":
            batch["targets"] = sds((b, tl), jnp.int32)
        if cfg.kind == "encdec":
            batch["frames"] = sds((b, _frames_len(cfg, cell.seq_len), cfg.d_model),
                                  cfg.dtype)
        if cfg.n_patches:
            batch["patches"] = sds((b, cfg.n_patches, cfg.d_model), cfg.dtype)
        return batch
    # decode: cache of seq_len, one new token at position seq_len - 1
    def build(key):
        params = init(cfg, key)
        frames = (jnp.zeros((b, _frames_len(cfg, cell.seq_len), cfg.d_model),
                            cfg.dtype) if cfg.kind == "encdec" else None)
        return init_cache(cfg, params, b, cell.seq_len, frames=frames)

    caches = jax.eval_shape(build, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return {
        "caches": caches,
        "token": sds((b, 1), jnp.int32),
        "pos": sds((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------

BATCH = ("pod", "data")         # logical batch binding (multi-pod aware)
FSDP = "data"
MODEL = "model"


def production_rules(multi_pod: bool, fsdp_mode: str = "full"):
    batch = BATCH if multi_pod else ("data",)
    rules = {
        "batch": batch,
        "seq": MODEL,
        "kv_seq": MODEL,
        "heads": MODEL,
        "kv_heads": MODEL,
        "ffn": MODEL,
        "experts": MODEL,
        "vocab": MODEL,
        "fsdp": FSDP,
        None: None,
    }
    if fsdp_mode == "fsdp_only":
        # No tensor parallelism on heads/ffn — the model axis only carries
        # sequence parallelism and the vocab shard. Weight storage spreads
        # over the whole mesh (see _leaf_spec).
        rules["heads"] = None
        rules["kv_heads"] = None
        rules["ffn"] = None
    elif fsdp_mode == "dp_full":
        # Pure data parallelism over the intra-pod mesh: batch is sharded
        # across data x model (1 sequence/chip at global_batch=256), the
        # residual is never resharded, and the only per-layer collective
        # is the FSDP weight gather. Wins whenever
        #   ~3 * layer_param_bytes  <  ~12 * B_local * S * D bytes,
        # i.e. exactly the train_4k cells where SP/TP was collective-bound.
        # Multi-pod: the pod axis carries sequence parallelism (256
        # sequences don't split 512 ways), so cross-pod traffic is one
        # cheap residual gather per layer instead of weight gathers.
        rules["batch"] = ("data", "model")
        rules["seq"] = "pod" if multi_pod else None
        rules["kv_seq"] = None
        rules["heads"] = None
        rules["kv_heads"] = None
        rules["ffn"] = None
        rules["vocab"] = None
    return rules


def _axis_sizes() -> dict:
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return {"pod": 2, "data": 16, "model": 16}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _entry_size(entry, sizes) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for name in names:
        n *= sizes.get(name, 1)
    return n


def sanitize(spec: P, shape, sizes=None) -> P:
    """Drop spec entries whose mesh-axis size does not divide the dim.

    jit/shard_map argument shardings require exact divisibility (unlike
    intermediate constraints, which GSPMD pads); odd vocabs (92553), small
    KV-head counts (1, 2, 8) and batch=1 cells all hit this.
    """
    sizes = sizes or _axis_sizes()
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        out.append(entry if dim % _entry_size(entry, sizes) == 0 else None)
    return P(*out)


def _leaf_spec(path: str, ndim: int, shape=None) -> P:
    """Pattern-matched PartitionSpec for one (unstacked) parameter."""
    sizes = _axis_sizes()
    last = path.rsplit("/", 1)[-1]
    in_ffn = "/ffn/" in path or path.endswith("ffn") or "/shared/" in path
    if last == "e":                     # embed / head tables (V, D)
        return P(MODEL, FSDP)
    if last in ("wq", "wk", "wv"):      # (D, H, hd)
        if shape is not None and shape[1] % _entry_size(MODEL, sizes) != 0:
            # few KV heads (GQA/MQA): shard head_dim over model instead
            return P(FSDP, None, MODEL)
        return P(FSDP, MODEL, None)
    if last == "wo" and not in_ffn:     # attention out (H, hd, D)
        return P(MODEL, None, FSDP)
    if last == "wi":
        if ndim == 4:                   # moe (E, D, 2, F)
            return P(MODEL, FSDP, None, None)
        return P(FSDP, None, MODEL)     # dense (D, 2, F)
    if last == "wo" and in_ffn:
        if ndim == 3:                   # moe (E, F, D)
            return P(MODEL, None, FSDP)
        return P(MODEL, FSDP)           # dense (F, D)
    if last == "router":
        return P(FSDP, None)
    if last == "in_proj":               # mamba (D, X)
        return P(FSDP, MODEL)
    if last == "conv_w":
        return P(None, MODEL)
    if last in ("conv_b",):
        return P(MODEL)
    if last in ("a_log", "d_skip", "dt_bias"):
        return P(MODEL)
    if last == "out_proj":              # mamba (d_inner, D)
        return P(MODEL, FSDP)
    if last == "wq_a" or last == "wkv_a":   # mla (D, r)
        return P(FSDP, None)
    if last in ("wq_b", "wk_b", "wv_b"):    # mla (r, H, hd)
        return P(None, MODEL, None)
    if last == "scale":
        if "out_norm" in path:          # mamba gated norm over d_inner
            return P(MODEL)
        return P(None)
    return P(*([None] * ndim))


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(cfg: ModelConfig, params_shape):
    """PartitionSpec tree matching the (abstract) parameter tree."""
    stacked_prefixes = ("slots", "enc", "dec")

    sizes = _axis_sizes()

    def strip_fsdp(spec: P) -> P:
        out = []
        for e in spec:
            if e == FSDP:
                out.append(None)
            elif isinstance(e, tuple):
                kept = tuple(a for a in e if a != FSDP)
                out.append(kept if kept else None)
            else:
                out.append(e)
        return P(*out)

    def fsdp_only_spec(shape) -> P:
        """Spread weight storage over the flattened mesh: the largest dim
        divisible by |model|x|data| gets both axes (fallback: |data|)."""
        both = _entry_size((MODEL, FSDP), sizes)
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if shape[i] % both == 0:
                return P(*[(MODEL, FSDP) if j == i else None
                           for j in range(len(shape))])
        for i in order:
            if shape[i] % _entry_size(FSDP, sizes) == 0:
                return P(*[FSDP if j == i else None for j in range(len(shape))])
        return P(*([None] * len(shape)))

    def one(path, leaf):
        s = _path_str(path)
        stacked = s.split("/", 1)[0] in stacked_prefixes
        shape = leaf.shape[1:] if stacked else leaf.shape
        # MoE expert weights keep expert-parallel sharding in every mode:
        # the a2a dispatch needs E on the model axis; re-sharding them to
        # the generic fsdp layout costs full expert-weight reshards/layer.
        is_expert = ("/ffn/" in s or s.endswith("router")) and len(shape) >= 3
        if (cfg.fsdp_mode in ("fsdp_only", "dp_full") and len(shape) >= 2
                and not is_expert):
            base = fsdp_only_spec(shape)
        else:
            base = _leaf_spec(s, len(shape), shape)
            if cfg.fsdp_mode in ("zero1", "none"):
                base = strip_fsdp(base)
        base = sanitize(base, shape, sizes)
        if stacked:
            return P(None, *base)
        return base

    return jax.tree_util.tree_map_with_path(one, params_shape)


def opt_specs(cfg: ModelConfig, pspecs, pshape=None):
    from ..optim.adamw import OptState
    mspecs = pspecs
    if cfg.fsdp_mode == "zero1" and pshape is not None:
        sizes = _axis_sizes()

        def shard_first_free(spec, leaf):
            entries = list(spec) + [None] * (leaf.ndim - len(spec))
            for i, (e, dim) in enumerate(zip(entries, leaf.shape)):
                if e is None and dim % sizes.get(FSDP, 1) == 0:
                    entries[i] = FSDP
                    break
            return P(*entries)

        mspecs = jax.tree.map(
            shard_first_free, pspecs, pshape,
            is_leaf=lambda x: isinstance(x, P))
    return OptState(step=P(), mu=mspecs, nu=mspecs, err=None)


def batch_specs(cfg: ModelConfig, cell: ShapeCell, multi_pod: bool):
    batch = production_rules(multi_pod, cfg.fsdp_mode)["batch"]
    sizes = _axis_sizes()
    inputs = input_specs(cfg, cell)
    seq_ax = production_rules(multi_pod, cfg.fsdp_mode)["seq"]
    if cell.kind in ("train", "prefill"):
        specs = {"tokens": P(batch, None)}
        if cell.kind == "train":
            specs["targets"] = P(batch, None)
        if cfg.kind == "encdec":
            specs["frames"] = P(batch, seq_ax, None)
        if cfg.n_patches:
            specs["patches"] = P(batch, seq_ax, None)
        return {k: sanitize(v, inputs[k].shape, sizes) for k, v in specs.items()}
    cspecs = cache_specs(cfg, cell, inputs["caches"], multi_pod)
    return {
        "caches": cspecs,
        "token": sanitize(P(batch, None), inputs["token"].shape, sizes),
        "pos": P(),
    }


def cache_specs(cfg: ModelConfig, cell: ShapeCell, caches_shape, multi_pod: bool,
                model_size: int = 16):
    """Decode-cache PartitionSpecs.

    Batch shards over the data axes when divisible, otherwise the cache
    sequence dim shards there (long_500k, B=1). KV heads shard over model
    when there are enough of them; otherwise (MQA, MLA's headless c_kv) the
    cache sequence dim takes the model axis — flash-decoding style, GSPMD
    psums the partial softmax.
    """
    batch_axes = BATCH if multi_pod else ("data",)
    b = cell.global_batch
    batch_ok = b >= (32 if multi_pod else 16)
    b_ax = batch_axes if batch_ok else None
    kv_ok = cfg.n_kv_heads >= model_size

    def seq_ax(take_model: bool):
        """Axes assigned to the cache sequence dim."""
        axes = () if batch_ok else tuple(
            a for a in (batch_axes if isinstance(batch_axes, tuple) else (batch_axes,))
        )
        if take_model:
            axes = axes + (MODEL,)
        return axes if axes else None

    def one(path, leaf):
        s = _path_str(path)
        nd = leaf.ndim
        last = s.rsplit("/", 1)[-1]
        lead = (None,) if s.split("/", 1)[0] in ("slots", "self", "cross") else ()
        if last in ("k", "v"):          # [lead] (B, S, KV, hd)
            spec = lead + (b_ax, seq_ax(not kv_ok), MODEL if kv_ok else None, None)
        elif last in ("c_kv", "k_rope"):  # [lead] (B, S, r) — headless: seq->model
            spec = lead + (b_ax, seq_ax(True), None)
        elif last == "conv":            # [lead] (B, K, C)
            spec = lead + (b_ax, None, MODEL)
        elif last == "ssm":             # [lead] (B, H, hd, N)
            spec = lead + (b_ax, MODEL, None, None)
        else:
            spec = (None,) * nd
        if len(spec) != nd:
            spec = (None,) * nd
        return sanitize(P(*spec), leaf.shape)

    return jax.tree_util.tree_map_with_path(one, caches_shape)


def shardings(cfg: ModelConfig, cell: ShapeCell, multi_pod: bool,
              opt: bool = True):
    """(param_specs, opt_specs, batch_specs) for a cell."""
    pshape = jax.eval_shape(
        lambda k: init(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    ps = param_specs(cfg, pshape)
    os_ = opt_specs(cfg, ps, pshape) if (opt and cell.kind == "train") else None
    bs = batch_specs(cfg, cell, multi_pod)
    return ps, os_, bs
