"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh and extract roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod

Per combination this records: per-device HLO FLOPs / bytes accessed
(``compiled.cost_analysis()``), per-device memory image
(``compiled.memory_analysis()``), and per-device collective bytes parsed
from the partitioned HLO (all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute operand sizes).

Importing this module pins JAX to the CPU platform and appends 512 host
devices to ``XLA_FLAGS`` (unless a count is already set): the dry run
lowers over host devices and never takes an accelerator.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 "=512").strip()

import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.config import (INPUT_SHAPES, InputShape, ModelConfig,
                                 TrainConfig)
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch.hlo_cost import parse_hlo_cost
from repro.core.copris import make_train_step
from repro.launch import sharding as shd
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models import model as M
from repro.optim import adam

F32 = jnp.float32
BF16 = jnp.bfloat16
I32 = jnp.int32

# long_500k runs only for sub-quadratic archs (DESIGN.md §4)
LONG_CTX_ARCHS = ("rwkv6-1.6b", "hymba-1.5b", "gemma2-2b")

# per-(arch) microbatch count for train_4k: keeps activations/device sane
TRAIN_MICROBATCHES = {
    "llama-3.2-vision-90b": 16, "granite-34b": 16, "qwen3-moe-235b-a22b": 16,
    "qwen3-14b": 8,
    # 16 microbatches -> 65536 tokens = exactly one MoE dispatch chunk
    # (chunking under the VJP replicates buffers, §Perf D1)
    "deepseek-moe-16b": 16,
}


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _cast_tree(tree, dtype):
    def c(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(x.shape, dtype)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return jax.tree.map(c, tree)


def param_count(cfg: ModelConfig, active_only=False) -> int:
    return cfg.param_count(active_only=active_only)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no device allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: InputShape, mesh, *,
                serve_dtype=BF16):
    """Returns (step_fn, args: tuple of SDS pytrees, in_shardings,
    donate_argnums, meta)."""
    B, S = shape.global_batch, shape.seq_len
    has_media = cfg.uses_media
    media_sds = None
    if has_media:
        xa = cfg.cross_attn
        media_sds = sds((B, xa.num_media_tokens, xa.d_media), serve_dtype)

    params_shape = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                  jax.random.PRNGKey(0))

    if shape.kind == "train":
        k = TRAIN_MICROBATCHES.get(cfg.name, 8)
        tcfg = TrainConfig(microbatches=k, remat=True)
        step = make_train_step(cfg, tcfg)
        opt_shape = jax.eval_shape(adam.init, params_shape)
        batch = {
            "tokens": sds((B, S), I32),
            "loss_mask": sds((B, S), F32),
            "behaviour_logp": sds((B, S), F32),
            "advantages": sds((B,), F32),
        }
        if has_media:
            batch["media"] = media_sds
        p_sh = shd.params_shardings(params_shape, mesh, cfg=cfg)
        o_sh = shd.opt_state_shardings(params_shape, mesh, cfg=cfg)
        b_sh = shd.train_batch_shardings(mesh, has_media=has_media)
        lr_sh = NamedSharding(mesh, P())
        args = (params_shape, opt_shape, batch, sds((), F32))
        in_sh = (p_sh, o_sh, b_sh, lr_sh)
        return step, args, in_sh, (0, 1), {"microbatches": k}

    # ---- serving ----------------------------------------------------
    # TP-only weights when they fit: inference pays per-step weight
    # all-gathers under ZeRO sharding (hillclimb B, EXPERIMENTS.md §Perf)
    params_bf16 = _cast_tree(params_shape, serve_dtype)
    tp_only = shd.serve_fits_tp_only(cfg, mesh)
    p_sh_prefill = shd.params_shardings(params_bf16, mesh,
                                        serve_tp_only=tp_only, cfg=cfg)
    p_sh_decode = shd.params_shardings(params_bf16, mesh,
                                       serve_tp_only=tp_only,
                                       serve_decode=True, cfg=cfg)
    dp = shd.batch_axes(mesh)

    if shape.kind == "prefill":
        cache_shape = jax.eval_shape(
            lambda: M.init_cache(cfg, B, S + 8, serve_dtype))
        c_sh = shd.cache_shardings(cache_shape, cfg, mesh)

        def prefill_step(params, tokens, lengths, cache, media=None):
            logits, cache = M.prefill(params, cfg, tokens, lengths, cache,
                                      media=media)
            # pin the output cache to the declared (batch-sharded) cache
            # layout: XLA otherwise propagates the head-sharded layout of
            # the K/V projections to the output, which (a) silently
            # un-aliases the donated input cache (full-size HBM copy,
            # caught by irlint IR402) and (b) defers the reshard to the
            # decode step that consumes the cache
            cache = jax.lax.with_sharding_constraint(cache, c_sh)
            return logits, cache

        args = [params_bf16, sds((B, S), I32), sds((B,), I32), cache_shape]
        in_sh = [p_sh_prefill, NamedSharding(mesh, P(dp, None)),
                 NamedSharding(mesh, P(dp)), c_sh]
        if has_media:
            args.append(media_sds)
            in_sh.append(NamedSharding(mesh, P(dp, None, None)))
        return prefill_step, tuple(args), tuple(in_sh), (3,), {}

    # decode: ONE new token against a seq_len cache
    shard_seq = (B == 1)
    cache_shape = jax.eval_shape(
        lambda: M.init_cache(cfg, B, S, serve_dtype))
    c_sh = shd.cache_shardings(cache_shape, cfg, mesh, shard_seq=shard_seq)
    tok_sh = NamedSharding(mesh, P(None if shard_seq else dp))

    def serve_step(params, token, cache, cache_len, media=None):
        logits, cache = M.decode_step(params, cfg, token, cache, cache_len,
                                      media=media)
        return logits, cache

    # decode does NOT take media: the media K/V live in the cache
    # (hillclimb C — recomputing them per token dominated the VLM budget)
    args = [params_bf16, sds((B,), I32), cache_shape, sds((B,), I32)]
    in_sh = [p_sh_decode, tok_sh, c_sh, tok_sh]
    return serve_step, tuple(args), tuple(in_sh), (2,), {}


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\S+?))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(s: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the (partitioned,
    per-device) HLO. ``-done`` ops are skipped to avoid double counting."""
    out = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        shape_str = m.group(1) or m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_str)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


# ---------------------------------------------------------------------------
# per-combination dry run
# ---------------------------------------------------------------------------


def dryrun_config(cfg: ModelConfig) -> ModelConfig:
    """The production lowering variant of ``cfg``: one-hot embedding
    partitions as a matmul under SPMD (no gather remat); select-based cache
    writes shard along the cache length dim; MoE uses the shard_map ragged
    all-to-all dispatch (hillclimb D final: 5.2x memory term, 3x
    collectives vs the auto-SPMD scatter). ``repro.analysis.contracts``
    lowers the same variant — what we dry-run is what we gate."""
    cfg = dataclasses.replace(cfg, embed_impl="onehot", cache_update="onehot")
    if cfg.moe is not None and cfg.moe.dispatch == "sparse":
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="shardmap"))
    return cfg


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            mesh=None, verbose: bool = True, cfg_override=None) -> dict:
    cfg = dryrun_config(cfg_override or get_config(arch))
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "status": "skip"}

    if shape_name == "long_500k" and arch not in LONG_CTX_ARCHS:
        rec["reason"] = ("pure full-attention arch; long_500k requires "
                         "sub-quadratic attention (DESIGN.md §4)")
        return rec

    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.perf_counter()
    step, args, in_sh, donate, meta = input_specs(cfg, shape, mesh)

    from repro.common.partitioning import set_activation_mesh
    set_activation_mesh(mesh)
    try:
        with mesh:
            jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
    finally:
        set_activation_mesh(None)

    # ---- memory ------------------------------------------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            if hasattr(ma, f):
                mem[f] = int(getattr(ma, f))
        mem["total_nonalias"] = (mem.get("argument_size_in_bytes", 0)
                                 + mem.get("output_size_in_bytes", 0)
                                 + mem.get("temp_size_in_bytes", 0)
                                 - mem.get("alias_size_in_bytes", 0))
    except Exception as e:                                  # pragma: no cover
        mem["error"] = str(e)

    # ---- cost ----------------------------------------------------------
    # compiled.cost_analysis() counts while-loop bodies ONCE (verified), so
    # the scan-over-layers programs need the trip-count-aware HLO walker.
    # We record both: raw XLA numbers as a cross-check, walker as primary.
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    walked = parse_hlo_cost(hlo_text)
    flops = float(walked["flops"])
    # bytes excl. pure-layout ops (copies/converts the TPU backend fuses)
    bytes_accessed = float(walked["bytes"])
    layout_bytes = float(walked["layout_bytes"])
    coll = {k: float(v) for k, v in walked["collectives"].items()}

    # ---- roofline terms (per device; single-pod table) ----------------
    compute_t = flops / PEAK_FLOPS_BF16
    memory_t = bytes_accessed / HBM_BW
    coll_t = coll.get("total", 0) / ICI_BW
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": coll_t}
    dominant = max(terms, key=terms.get)

    n_params = param_count(cfg)
    n_active = param_count(cfg, active_only=True)
    if shape.kind == "train":
        D = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * D
    elif shape.kind == "prefill":
        D = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * D
    else:
        D = shape.global_batch            # one token per sequence
        model_flops = 2 * n_active * D
    useful_ratio = model_flops / max(flops * chips, 1.0)

    rec.update(
        status="ok", chips=chips, lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops_per_device=flops, bytes_per_device=bytes_accessed,
        layout_bytes_per_device=layout_bytes,
        xla_raw_flops=float(cost.get("flops", 0.0)),
        xla_raw_bytes=float(cost.get("bytes accessed", 0.0)),
        collective_bytes=coll, memory=mem, roofline=terms,
        dominant=dominant.replace("_s", ""),
        model_flops_total=model_flops, params=n_params,
        active_params=n_active, useful_flops_ratio=useful_ratio,
        meta=meta,
    )
    if verbose:
        print(f"  [{rec['mesh']}] {arch} × {shape_name}: "
              f"compute={compute_t*1e3:.2f}ms memory={memory_t*1e3:.2f}ms "
              f"collective={coll_t*1e3:.2f}ms dominant={rec['dominant']} "
              f"useful={useful_ratio:.2f} "
              f"mem/device={mem.get('total_nonalias', 0)/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def run_reshard(arch: str, *, multi_pod: bool = False, mesh=None,
                verbose: bool = True, cfg_override=None) -> dict:
    """Lower + compile the versioned weight-sync reshard on the production
    mesh: train layout (Megatron TP × FSDP) in, rollout layout
    (``serve_tp_only`` — FSDP axis replicated) out.

    This is the exact jitted transfer ``ParamStore.publish`` runs per
    version in disaggregated mode (built by the same
    ``core/weight_sync.make_param_resharder``) — what we dry-run is what we
    sync. The interesting number is the collective bill: one all-gather of
    every FSDP-sharded leaf per published version, paid off the decode
    critical path instead of per decode step."""
    from repro.core.weight_sync import make_param_resharder

    cfg = cfg_override or get_config(arch)
    rec = {"arch": arch, "shape": "weight_sync",
           "mesh": "2x16x16" if multi_pod else "16x16", "status": "ok"}
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    params_shape = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                  jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    reshard, _out_sh = make_param_resharder(cfg, params_shape, mesh)
    lowered = reshard.lower(params_shape)
    t_lower = time.perf_counter() - t0
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
            if hasattr(ma, f):
                mem[f] = int(getattr(ma, f))
    except Exception as e:                                  # pragma: no cover
        mem["error"] = str(e)

    n_params = param_count(cfg)
    sync_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(params_shape))
    rec.update(
        chips=chips, lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        params=n_params, sync_bytes_per_version=sync_bytes,
        collective_bytes={k: float(v) for k, v in coll.items()},
        collective_s=coll.get("total", 0) / ICI_BW, memory=mem,
    )
    if verbose:
        print(f"  [{rec['mesh']}] {arch} × weight_sync: "
              f"{sync_bytes/2**30:.2f}GiB/version, collective "
              f"{coll.get('total', 0)/2**30:.2f}GiB/device "
              f"({rec['collective_s']*1e3:.2f}ms) "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--weight-sync", action="store_true",
                    help="additionally lower the ParamStore reshard "
                         "(train layout -> rollout serve_tp_only layout) "
                         "for each arch")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    if args.out and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skip")}

    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        mname = "2x16x16" if mp else "16x16"
        for arch in archs:
            arch_shapes = list(shapes)
            if args.weight_sync:
                arch_shapes.append("weight_sync")
            for shape in arch_shapes:
                if (arch, shape, mname) in done:
                    continue
                try:
                    if shape == "weight_sync":
                        rec = run_reshard(arch, multi_pod=mp, mesh=mesh)
                    else:
                        rec = run_one(arch, shape, multi_pod=mp, mesh=mesh)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mname,
                           "status": "error", "error": str(e),
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"  [{mname}] {arch} × {shape}: ERROR {e}")
                results.append(rec)
                if args.out:
                    os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                                exist_ok=True)
                    json.dump(results, open(args.out, "w"), indent=1)

    ok = sum(1 for r in results if r["status"] == "ok")
    skip = sum(1 for r in results if r["status"] == "skip")
    err = sum(1 for r in results if r["status"] == "error")
    print(f"\ndry-run: {ok} ok, {skip} documented skips, {err} errors")
    return 1 if err else 0


if __name__ == "__main__":
    sys.exit(main())
