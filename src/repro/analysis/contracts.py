"""Lowering contracts: the checked-in per-(arch, shape, mesh) budget the
IR lint diffs against.

A *target* is one real hot path lowered + compiled on a fake-device mesh
(the exact programs ``launch/dryrun.py`` lowers — what we dry-run is what
we gate):

* ``tiny`` on a 4x2 mesh — train step, bucketed prefill, decode step,
  and the ParamStore weight-sync reshard (small shapes; compiles in
  seconds, so the full donation/callback/collective surface is gated on
  every run);
* ``llama3.2-1b`` and ``deepseek-moe-16b`` on the 16x16 production mesh
  — decode_32k, prefill_32k, and weight_sync (dense + MoE serving paths
  at the real sharding).

``measure_target`` is the only JAX-touching step: it returns a plain
:class:`repro.analysis.irlint.MeasuredTarget` that the pure-Python IR
checks consume. The contract file (``lowering_contracts.json``) stores
per-device collective bytes per kind (trip-count-aware, via
``launch/hlo_cost``) plus donation/alias counts as review context.
Regenerate with ``repro-analysis --write-contracts`` and justify the diff
in review — the file is a budget, not a cache.

NOTE: importing this module pins JAX to the CPU platform and appends
``--xla_force_host_platform_device_count=512`` to ``XLA_FLAGS`` (unless a
count is already set) so the meshes exist over host devices — a dry run
never takes an accelerator. That only works if JAX's backend has not
initialized yet. Import it only from fresh processes (the
``repro-analysis`` CLI qualifies); under pytest, monkeypatch
``irlint.measure_all`` instead.
"""
from __future__ import annotations

import os

# must happen before JAX's backend initializes: the targets below need up
# to 256 fake host devices. An explicit caller-provided count (tests use
# 8) is respected.
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                                 "=512").strip()

import inspect
import json
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.analysis.irlint import (
    DonatedLeaf,
    MeasuredTarget,
    aliased_params,
    find_callback_prims,
)

CONTRACTS_DEFAULT = "lowering_contracts.json"

TINY_MESH = (4, 2)
PROD_MESH = (16, 16)

PROD_ARCHS = ("llama3.2-1b", "deepseek-moe-16b")
PROD_SHAPES = ("decode_32k", "prefill_32k", "weight_sync")


@dataclass(frozen=True)
class Target:
    arch: str
    #: an INPUT_SHAPES name, "weight_sync", or a repro InputShape
    shape: Union[str, object]
    mesh_shape: Tuple[int, int]

    @property
    def shape_name(self) -> str:
        return self.shape if isinstance(self.shape, str) else self.shape.name

    @property
    def mesh_name(self) -> str:
        return "x".join(str(d) for d in self.mesh_shape)

    @property
    def key(self) -> str:
        return f"{self.arch}|{self.shape_name}|{self.mesh_name}"


def default_targets(archs: Optional[Sequence[str]] = None) -> List[Target]:
    from repro.common.config import InputShape

    tiny_shapes = [
        InputShape("train_tiny", 256, 16, "train"),
        InputShape("prefill_tiny", 256, 8, "prefill"),
        InputShape("decode_tiny", 256, 8, "decode"),
        "weight_sync",
    ]
    out = [Target("tiny", s, TINY_MESH) for s in tiny_shapes]
    for arch in PROD_ARCHS:
        out.extend(Target(arch, s, PROD_MESH) for s in PROD_SHAPES)
    if archs:
        out = [t for t in out if t.arch in archs]
    return out


# ---------------------------------------------------------------------------
# measurement (the only JAX-touching step)
# ---------------------------------------------------------------------------


def _rel(path: str) -> str:
    return os.path.relpath(path).replace(os.sep, "/")


def _flat_donated(args, donate) -> List[Tuple[str, int, int, str]]:
    """(leaf name, flat entry-param index, nbytes, dtype) for every leaf
    of every donated positional arg. Entry-parameter numbering in the
    compiled module is flat leaf order over all args (verified against
    the partitioned HLO's entry_computation_layout)."""
    import jax
    import numpy as np

    out = []
    offset = 0
    for argnum, arg in enumerate(args):
        leaves_paths = jax.tree_util.tree_flatten_with_path(arg)[0]
        for i, (kp, leaf) in enumerate(leaves_paths):
            if argnum in donate:
                name = f"arg{argnum}" + jax.tree_util.keystr(kp)
                nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                out.append((name, offset + i, nbytes, str(leaf.dtype)))
        offset += len(leaves_paths)
    return out


def _float_leaves(args) -> List[Tuple[str, str]]:
    import jax
    import jax.numpy as jnp

    out = []
    for argnum, arg in enumerate(args):
        for kp, leaf in jax.tree_util.tree_flatten_with_path(arg)[0]:
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                out.append((f"arg{argnum}" + jax.tree_util.keystr(kp),
                            str(leaf.dtype)))
    return out


def measure_target(t: Target) -> MeasuredTarget:
    import time

    import jax
    import numpy as np

    from repro.common.config import INPUT_SHAPES
    from repro.common.partitioning import set_activation_mesh
    from repro.configs import get_config
    from repro.launch.dryrun import dryrun_config, input_specs
    from repro.launch.hlo_cost import parse_hlo_cost
    from repro.launch.mesh import make_mesh

    needed = int(np.prod(t.mesh_shape))
    if jax.device_count() < needed:
        raise RuntimeError(
            f"target {t.key} needs {needed} devices but only "
            f"{jax.device_count()} exist — run in a fresh process so "
            "importing repro.analysis.contracts can set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before JAX "
            "initializes (the repro-analysis CLI does this)")

    cfg = dryrun_config(get_config(t.arch))
    mesh = make_mesh(t.mesh_shape, ("data", "model"))
    t0 = time.perf_counter()

    if t.shape == "weight_sync":
        from repro.core import weight_sync
        from repro.models import model as M

        params_shape = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                      jax.random.PRNGKey(0))
        reshard, _ = weight_sync.make_param_resharder(cfg, params_shape,
                                                      mesh)
        kind = "weight_sync"
        with mesh:
            lowered = reshard.lower(params_shape)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            jaxpr = jax.make_jaxpr(reshard)(params_shape)
        args: tuple = (params_shape,)
        donate: tuple = ()
        anchor = weight_sync.make_param_resharder
    else:
        shape = (INPUT_SHAPES[t.shape] if isinstance(t.shape, str)
                 else t.shape)
        kind = shape.kind
        step, args, in_sh, donate, _meta = input_specs(cfg, shape, mesh)
        set_activation_mesh(mesh)
        try:
            with mesh:
                jitted = jax.jit(step, in_shardings=in_sh,
                                 donate_argnums=donate)
                lowered = jitted.lower(*args)
                t_lower = time.perf_counter() - t0
                compiled = lowered.compile()
                t_compile = time.perf_counter() - t0 - t_lower
                jaxpr = jax.make_jaxpr(step)(*args)
        finally:
            set_activation_mesh(None)
        anchor = step

    text = compiled.as_text()
    aliased = aliased_params(text)
    donated = [DonatedLeaf(name, param, nbytes, dt, param in aliased)
               for name, param, nbytes, dt in _flat_donated(args, donate)]
    walked = parse_hlo_cost(text)
    try:
        src = _rel(inspect.getsourcefile(anchor))
        line = inspect.getsourcelines(anchor)[1]
    except (TypeError, OSError):                         # pragma: no cover
        src, line = "src/repro/launch/dryrun.py", 1
    return MeasuredTarget(
        key=t.key, arch=t.arch, shape=t.shape_name, mesh=t.mesh_name,
        kind=kind, path=src, line=line, chips=needed, donated=donated,
        callbacks=find_callback_prims(jaxpr),
        collectives={k: float(v)
                     for k, v in walked["collectives"].items()},
        float_leaves=_float_leaves(args) if kind != "weight_sync" else [],
        weak_invars=sum(1 for v in jaxpr.jaxpr.invars
                        if getattr(v.aval, "weak_type", False)),
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2))


# ---------------------------------------------------------------------------
# contract file I/O
# ---------------------------------------------------------------------------


def load_contracts(path: str) -> Dict[str, dict]:
    """key -> entry. Missing file = empty (every target then fails IR404
    with a 'no contract' finding until one is written and reviewed in)."""
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return dict(data.get("entries", {}))


def write_contracts(measured: Sequence[MeasuredTarget], path: str) -> int:
    entries = {}
    for mt in sorted(measured, key=lambda m: m.key):
        entries[mt.key] = {
            "arch": mt.arch,
            "shape": mt.shape,
            "mesh": mt.mesh,
            "kind": mt.kind,
            "chips": mt.chips,
            "collective_bytes": {k: mt.collectives.get(k, 0.0)
                                 for k in sorted(mt.collectives)},
            "donated_leaves": len(mt.donated),
            "aliased_leaves": sum(1 for d in mt.donated if d.aliased),
        }
    doc = {
        "_comment": ("Per-(arch, shape, mesh) lowering contracts: "
                     "per-device collective bytes (trip-count-aware) the "
                     "IR lint (IR404) gates against. Regenerate with "
                     "`repro-analysis --write-contracts` and justify the "
                     "diff in review — this file is a budget, not a "
                     "cache."),
        "version": 1,
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    return len(entries)
