"""Sharding rules: Megatron tensor-parallel over "model" × ZeRO-3 (FSDP)
over "data" × pure data-parallel over "pod".

Rules are name-based over the last dims of each leaf; leading layer-stack
dims (the scan R axis) are unsharded. XLA SPMD inserts the collectives:
per-layer all-gather of FSDP-sharded weights, all-reduce/reduce-scatter for
tensor-parallel matmuls, all-to-all for expert-parallel MoE dispatch, psum
over (pod, data) for gradients.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.config import ModelConfig

# name -> spec for the *trailing* dims. "dp" is replaced by the FSDP axis
# ("data"), "tp" by the tensor axis ("model"), "ep" by the expert axis
# ("model").
_MATRIX_RULES = {
    # embeddings / head
    "tok": ("tp", "dp"),              # vocab-parallel embedding (V, d)
    "lm_head": ("dp", "tp"),          # (d, V)
    "media_proj": ("dp", "tp"),
    # column-parallel (out dim over model)
    "wq": ("dp", "tp"), "wk": ("dp", "tp"), "wv": ("dp", "tp"),
    "wi": ("dp", "tp"), "wg": ("dp", "tp"),
    "in_proj": ("dp", "tp"), "x_proj": ("tp", None),
    "mix_a": ("dp", None), "dec_a": ("dp", None),
    # row-parallel (in dim over model)
    "wo": ("tp", "dp"), "out_proj": ("tp", "dp"),
    "dt_proj": (None, "tp"),
    "mix_b": (None, None, "dp"), "dec_b": (None, "dp"),
    # misc
    "router": ("dp", None),
    "conv": (None, "tp"), "A_log": ("tp", None),
    "mu": (None, "dp"),
}
# MoE expert tensors (E, d, f) / (E, f, d): experts over "model" (EP).
_MOE_3D = {"wi": ("ep", "dp", None), "wg": ("ep", "dp", None),
           "wo": ("ep", None, "dp")}


def _axis(mesh: Mesh, tag):
    if tag is None:
        return None
    if tag in mesh.axis_names:          # literal axis passthrough
        return tag
    if "kvg" in mesh.axis_names:        # GQA-grouped serve mesh
        return {"dp": "data", "tp": ("kvg", "model"), "ep": ("kvg", "model"),
                "kvh": "kvg"}[tag]
    return {"dp": "data", "tp": "model", "ep": "model", "kvh": "model"}[tag]


def param_pspec(path, leaf, mesh: Mesh, cfg: Optional[ModelConfig] = None,
                *, serve_decode: bool = False) -> P:
    """PartitionSpec for one parameter leaf given its tree path. Leaves under
    "body" carry a leading layer-stack (scan) dim which is never sharded."""
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    in_moe = "moe" in names and "shared" not in names
    nd = leaf.ndim
    base = nd - (1 if "body" in names else 0)   # rank without the stack dim

    if base <= 1 or name in ("beta", "u", "w_base", "dt_bias", "D", "conv_b"):
        return P()                     # scalars / norms / small vectors

    if in_moe and name in _MOE_3D and base >= 3:
        tags = _MOE_3D[name]
    elif name in _MATRIX_RULES:
        tags = _MATRIX_RULES[name]
    else:
        tags = ("dp", "tp")

    if "kvg" in mesh.axis_names and "attn" in names and name in (
            "wq", "wk", "wv", "wo"):
        # GQA-grouped serve mesh: q/k/v heads shard over "kvg" (group-
        # aligned: head h = g*rep + r, so a kvg-contiguous block is one kv
        # group); the "model" (within-group) axis is reserved for the cache
        # LENGTH, so head dims must not touch it
        tags = {"wq": ("model", "kvh"), "wk": ("model", "kvh"),
                "wv": ("model", "kvh"), "wo": ("kvh", "model")}[name]
        tags = tags[-base:] if len(tags) > base else tags
        spec = [None] * nd
        for i, tag in enumerate(reversed(tags)):
            spec[nd - 1 - i] = _axis(mesh, tag)
        return _divisible(P(*spec), leaf.shape, mesh)

    kv_indivisible = (cfg is not None and
                      cfg.num_kv_heads % mesh.shape.get("model", 1) != 0
                      and "kvg" not in mesh.axis_names)
    # GQA with kv_heads not divisible by TP: sub-head sharding of wk/wv makes
    # XLA all-gather K/V blocks inside EVERY attention scan step (94% of
    # llama prefill collective bytes — hillclimb B). Replicate the kv
    # projections over "model" instead: tiny redundant compute, no gathers.
    if kv_indivisible and name in ("wk", "wv") and "attn" in names:
        tags = ("dp", None)
    # decode against an L-sharded (split-KV) cache additionally needs the
    # q heads replicated — otherwise the heads-vs-length sharding conflict
    # makes XLA all-gather the whole cache per layer per token
    if serve_decode and kv_indivisible and name == "wq" and "attn" in names:
        tags = ("dp", None)

    tags = tags[-base:] if len(tags) > base else tags
    spec = [None] * nd
    for i, tag in enumerate(reversed(tags)):
        spec[nd - 1 - i] = _axis(mesh, tag)
    return _divisible(P(*spec), leaf.shape, mesh)


def _divisible(spec: P, shape, mesh: Mesh) -> P:
    """pjit requires argument dims to divide their mesh-axis product; drop
    the sharding on any dim that doesn't (e.g. hymba's vocab of 32001)."""
    fixed = []
    for i, ax in enumerate(spec):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        fixed.append(ax if shape[i] % size == 0 else None)
    return P(*fixed)


def params_shardings(params_shape, mesh: Mesh, *, serve_tp_only: bool = False,
                     serve_decode: bool = False,
                     cfg: Optional[ModelConfig] = None):
    """Tree of NamedSharding matching a params (shape-)pytree.

    ``serve_tp_only``: drop the FSDP ("data") axis from every weight —
    tensor-parallel only. Inference has no optimizer state and ZeRO-style
    weight sharding makes XLA all-gather every layer's weights per step
    (per-token, for decode!); replicating over "data" removes those
    collectives entirely. Only valid when bf16 params / TP fit in HBM —
    callers gate on :func:`serve_fits_tp_only`."""
    def one(path, leaf):
        spec = param_pspec(path, leaf, mesh, cfg, serve_decode=serve_decode)
        if serve_tp_only:
            spec = P(*[None if ax == "data" else ax for ax in spec])
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params_shape)


def serve_fits_tp_only(cfg: ModelConfig, mesh: Mesh, *,
                       budget_bytes: float = 8e9) -> bool:
    """Would bf16 weights, TP-sharded only, fit the per-chip budget?"""
    tp = 1
    for a, n in mesh.shape.items():
        if a not in ("data", "pod"):
            tp *= n
    return 2.0 * cfg.param_count() / tp <= budget_bytes


def opt_state_shardings(params_shape, mesh: Mesh, cfg=None):
    ps = params_shardings(params_shape, mesh, cfg=cfg)
    return {"m": ps, "v": ps,
            "step": NamedSharding(mesh, P())}


# ---------------------------------------------------------------------------
# activation / batch shardings
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def train_batch_shardings(mesh: Mesh, *, has_media: bool = False):
    dp = batch_axes(mesh)
    s = lambda *spec: NamedSharding(mesh, P(*spec))
    out = {
        "tokens": s(dp, None),
        "loss_mask": s(dp, None),
        "behaviour_logp": s(dp, None),
        "advantages": s(dp),
    }
    if has_media:
        out["media"] = s(dp, None, None)
    return out


def cache_pspec(path, leaf, cfg: ModelConfig, mesh: Mesh, *,
                shard_seq: bool = False) -> P:
    """KV/state cache sharding for serving.

    Default: slot/batch dim over the data axes, kv-head (or head_dim for
    MQA) over "model". ``shard_seq``: additionally shard the cache length
    dim over "data" (sequence-parallel KV for long_500k, batch=1).
    """
    names = [p.key for p in path if hasattr(p, "key")]
    name = names[-1] if names else ""
    nd = leaf.ndim
    body = "body" in names             # leading layer-stack dim
    off = 1 if body else 0
    dp = batch_axes(mesh)
    tp_size = mesh.shape["model"]

    spec = [None] * nd
    if name in ("k", "v") and "kvg" in mesh.axis_names:
        # GQA-grouped serve mesh: kv heads over "kvg", length over "model";
        # whole heads per shard of the dense (KV*hd) or paged (KV) head axis
        spec[off + 0] = dp
        spec[off + 1] = "model"
        if cfg.num_kv_heads % mesh.shape["kvg"] == 0:
            spec[off + 2] = "kvg"
    elif name in ("mk", "mv") and "kvg" in mesh.axis_names:
        spec[off + 0] = dp
        spec[off + 2] = "kvg"
        spec[off + 3] = "model"
    elif name in ("k", "v"):
        # dense (R?, B, L, KV*hd): "model" on the merged axis holds whole
        # heads when it divides KV; paged pools are (R?, NP, ps, KV, hd)
        if cfg.num_kv_heads % tp_size == 0:
            if not shard_seq:
                spec[off + 0] = dp
            else:
                spec[off + 1] = "data"
            spec[off + 2] = "model"
        else:
            # kv heads indivisible by TP: K/V are computed replicated over
            # "model" (see param rule), so shard the cache LENGTH over
            # "model" — flash-decode / split-KV style; softmax stats psum
            # is tiny (hillclimb B)
            if not shard_seq:
                spec[off + 0] = dp
                spec[off + 1] = "model"
            else:
                spec[off + 1] = ("data", "model")
    elif name in ("mk", "mv"):         # (R?, B, M, KV, hd) — media K/V
        spec[off + 0] = dp
        if cfg.num_kv_heads % tp_size == 0:
            spec[off + 2] = "model"
        elif cfg.head_dim % tp_size == 0:
            spec[off + 3] = "model"
    elif name == "wkv":                # (R?, B, H, hd, hd)
        spec[off + 0] = None if shard_seq else dp
        spec[off + 1] = "model"
    elif name in ("tm_prev", "cm_prev"):   # (R?, B, d)
        spec[off + 0] = None if shard_seq else dp
        spec[off + 1] = "model" if shard_seq else None
    elif name == "ssm":                # (R?, B, di, N)
        spec[off + 0] = None if shard_seq else dp
        spec[off + 1] = "model"
    elif name == "conv":               # (R?, B, K-1, di)
        spec[off + 0] = None if shard_seq else dp
        spec[off + 2] = "model"
    return _divisible(P(*spec), leaf.shape, mesh)


def cache_shardings(cache_shape, cfg: ModelConfig, mesh: Mesh, *,
                    shard_seq: bool = False):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, cache_pspec(path, leaf, cfg, mesh, shard_seq=shard_seq)),
        cache_shape)
