"""IR-level lint (IR4xx + PAL205): check the *lowered* program, not the
source text.

Everything CoPRIS's wall-clock wins depend on — donation aliasing, fused
hot loops, collective budgets, Pallas block bounds — lives below the AST.
This module lowers the repo's real hot paths (decode scan, prefill
buckets, train step, ParamStore reshard) on fake-device meshes via
``repro.analysis.contracts`` (which reuses ``launch/dryrun.input_specs``)
and checks the compiled artifacts:

* **IR401** recompilation hazards — the prefill bucketing must map every
  raw batch in a bucket to ONE static jit signature, and lowered inputs
  must not carry weak types or off-policy dtypes (each drifting signature
  is a full recompile on the serving critical path).
* **IR402** donation integrity — every buffer declared in
  ``donate_argnums`` must actually be aliased in the compiled
  executable's ``input_output_alias`` map; a silently un-aliased donation
  is a full-size copy and an HBM spike.
* **IR403** host callbacks — ``pure_callback`` / ``io_callback`` / debug
  prints inside the decode/prefill/train jaxpr sync the host every step.
* **IR404** collective-budget regressions — per-step collective bytes
  (trip-count-aware, from ``launch/hlo_cost``) diffed against the
  checked-in per-(arch, shape, mesh) lowering contract file.
* **PAL205** Pallas interval analysis — propagate grid bounds through
  every kernel family's ``index_map`` to prove block accesses in-bounds,
  and estimate the double-buffered VMEM footprint against the ~16 MiB
  per-core budget.

This module stays importable without JAX (rule registration + docs); all
JAX work happens inside the ``run_*``/``measure`` entry points, which the
``repro-analysis --ir`` CLI calls in a fresh process so the fake-device
``XLA_FLAGS`` can be set before JAX initializes.
"""
from __future__ import annotations

import inspect
import math
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.analysis.core import (
    SEV_ERROR,
    SEV_WARNING,
    Finding,
    ModuleCtx,
    Rule,
    register,
)

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "total")

#: donated leaves smaller than this are not worth flagging (scalar step
#: counters etc. — the copy is noise, not an HBM spike)
MIN_ALIAS_BYTES = 1024

#: relative tolerance for IR404 collective-budget comparison; HLO text
#: parsing is deterministic, but leave headroom for jaxlib version drift
CONTRACT_REL_TOL = 0.02
CONTRACT_ABS_TOL = 1024.0

#: per-core VMEM budget for PAL205 (see /opt/skills/guides: ~16 MiB/core)
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

#: exhaustive index_map evaluation cap; beyond this only grid corners are
#: checked and the call is flagged as not exhaustively proven
MAX_GRID_POINTS = 8192

CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "debug_print", "callback")


# ---------------------------------------------------------------------------
# compiled-HLO alias-map parsing (IR402)
# ---------------------------------------------------------------------------

_ALIAS_HDR = "input_output_alias={"


def parse_alias_map(hlo_text: str) -> List[Tuple[Tuple[int, ...], int]]:
    """``[(output_index_tuple, parameter_index), ...]`` from the compiled
    module header, e.g. ``input_output_alias={ {1}: (13, {}, may-alias) }``.
    The map nests braces, so the segment is extracted by brace balancing,
    not regex. Missing map = no aliasing = empty list."""
    i = hlo_text.find(_ALIAS_HDR)
    if i < 0:
        return []
    start = i + len(_ALIAS_HDR) - 1          # the opening '{'
    depth = 0
    end = start
    for end in range(start, len(hlo_text)):
        if hlo_text[end] == "{":
            depth += 1
        elif hlo_text[end] == "}":
            depth -= 1
            if depth == 0:
                break
    seg = hlo_text[start:end + 1]
    pairs: List[Tuple[Tuple[int, ...], int]] = []
    for m in re.finditer(r"\{([\d,\s]*)\}\s*:\s*\((\d+)", seg):
        oidx = tuple(int(x) for x in m.group(1).replace(" ", "").split(",")
                     if x)
        pairs.append((oidx, int(m.group(2))))
    return pairs


def aliased_params(hlo_text: str) -> set:
    return {p for _, p in parse_alias_map(hlo_text)}


# ---------------------------------------------------------------------------
# measured-target record (produced by contracts.measure_target)
# ---------------------------------------------------------------------------


@dataclass
class DonatedLeaf:
    name: str        # pytree path, e.g. "arg1['mu']['blocks']['wq']"
    param: int       # flat entry-parameter index in the compiled module
    nbytes: int      # per-device bytes
    dtype: str
    aliased: bool


@dataclass
class MeasuredTarget:
    """Everything the IR rules need about one lowered hot path; built by
    ``contracts.measure_target`` (the only JAX-touching step), checked by
    the pure-Python ``check_*`` functions below."""
    key: str                     # "arch|shape|mesh"
    arch: str
    shape: str
    mesh: str
    kind: str                    # train | prefill | decode | weight_sync
    path: str                    # repo-relative anchor (the step's source)
    line: int
    chips: int
    donated: List[DonatedLeaf] = field(default_factory=list)
    callbacks: List[str] = field(default_factory=list)
    collectives: Dict[str, float] = field(default_factory=dict)
    float_leaves: List[Tuple[str, str]] = field(default_factory=list)
    weak_invars: int = 0
    lower_s: float = 0.0
    compile_s: float = 0.0


def _finding(rule: "Rule", mt_or_path, message: str, *, line: int = 1,
             context: str = "<ir>", src_line: str = "",
             severity: Optional[str] = None) -> Finding:
    if isinstance(mt_or_path, MeasuredTarget):
        path, line, context = mt_or_path.path, mt_or_path.line, mt_or_path.key
    else:
        path = mt_or_path
    return Finding(rule=rule.id, severity=severity or rule.severity,
                   path=path, line=line, col=1, message=message,
                   context=context, src_line=src_line)


# ---------------------------------------------------------------------------
# IR401 — recompilation hazards
# ---------------------------------------------------------------------------


@register
class RecompilationHazard(Rule):
    """The serving hot loop is only fast if every raw batch inside one
    prefill bucket lowers to the SAME static jit signature: the bucketing
    in ``core/rollout.py`` rounds sequence length up to ``PREFILL_BUCKET``
    and row/scatter counts up to powers of two, bounding compilation count
    at O(#buckets). This rule (a) sweeps representative raw batches
    through ``rollout.prefill_pad_dims`` and flags any pair inside one
    bucket cell that yields different padded dims — each such pair is an
    extra XLA compile (seconds to minutes) triggered at serve time; and
    (b) scans each lowered target's jaxpr inputs for ``weak_type`` leaves
    and serve-path float leaves that are not the serve dtype (bf16) —
    both split the compilation cache and force silent recompiles or
    upcasts on the critical path.

    Fix: route all shape padding through ``prefill_pad_dims`` and cast
    serve inputs to the serve dtype at the boundary.
    """

    id = "IR401"
    severity = SEV_ERROR
    title = "bucketed hot path lowers to more than one static signature"
    requires_lowering = True

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        return []


def check_bucket_stability() -> List[Finding]:
    """IR401(a): pure-Python sweep over the real rollout bucketing."""
    rule = RecompilationHazard()
    from repro.core import rollout
    path = _relsrc(rollout)
    fn = getattr(rollout, "prefill_pad_dims", None)
    if fn is None:
        return [_finding(rule, path, "rollout.prefill_pad_dims is missing "
                         "— prefill padding is no longer centralized and "
                         "bucket stability cannot be checked",
                         context="prefill_pad_dims",
                         src_line="prefill_pad_dims missing")]
    line = inspect.getsourcelines(fn)[1]
    out: List[Finding] = []
    bucket = rollout.PREFILL_BUCKET
    # raw variants that must share one signature: (lens, rows, pending)
    cells = [
        [([1], 1, 1), ([bucket], 1, 1)],
        [([5, 9], 2, 2), ([bucket // 2, bucket], 2, 2)],
        [([bucket + 1], 3, 5), ([2 * bucket], 4, 8)],
        [([3 * bucket - 7, 11], 5, 9), ([2 * bucket + 1], 8, 16)],
    ]
    for cell in cells:
        sigs = {(tuple(lens), r, p): fn(lens, r, p) for lens, r, p in cell}
        distinct = set(sigs.values())
        if len(distinct) != 1:
            out.append(_finding(
                rule, path, line=line, context="prefill_pad_dims",
                src_line=f"cell:{cell[0]}",
                message=("raw batches inside one prefill bucket cell lower "
                         f"to {len(distinct)} static signatures {sigs} — "
                         "each extra signature is a full XLA recompile on "
                         "the serving critical path")))
    return out


def check_signature(mt: MeasuredTarget) -> List[Finding]:
    """IR401(b): weak types and serve-dtype drift in a lowered target."""
    rule = RecompilationHazard()
    out: List[Finding] = []
    if mt.weak_invars:
        out.append(_finding(
            rule, mt, src_line=f"weak_invars:{mt.weak_invars}",
            message=(f"{mt.key}: {mt.weak_invars} jaxpr input(s) carry "
                     "weak_type=True — weak types split the jit cache "
                     "(python scalar vs array calls recompile) and "
                     "promote unpredictably")))
    if mt.kind in ("prefill", "decode"):
        bad = [(n, d) for n, d in mt.float_leaves if d != "bfloat16"]
        for name, dt in bad[:4]:
            out.append(_finding(
                rule, mt, src_line=f"dtype:{name}",
                message=(f"{mt.key}: serve-path input {name} is {dt}, not "
                         "bfloat16 — mixed dtypes on the decode path force "
                         "per-step converts and a second compiled "
                         "signature")))
    return out


# ---------------------------------------------------------------------------
# IR402 — donation integrity
# ---------------------------------------------------------------------------


@register
class DonationNotAliased(Rule):
    """A buffer listed in ``donate_argnums`` is only actually reused when
    the compiled executable records it in ``input_output_alias``. XLA can
    silently decline (sharding mismatch between the donated input and
    every output, dtype/layout change, or the buffer being used after the
    would-be overwrite) — the step then keeps BOTH copies live, which for
    the KV cache or the optimizer state is a per-device HBM spike equal
    to the full buffer, exactly the OOM class partial rollout is supposed
    to avoid. This rule maps every donated pytree leaf (>= 1 KiB) to its
    flat entry-parameter index and fails if the compiled alias map does
    not contain it.

    Fix: make the output layout/sharding match the donated input (don't
    reshard inside the step), or drop the donation so the cost is
    explicit.
    """

    id = "IR402"
    severity = SEV_ERROR
    title = "declared donation is not aliased by the compiled executable"
    requires_lowering = True

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        return []


def check_donation(mt: MeasuredTarget) -> List[Finding]:
    rule = DonationNotAliased()
    out: List[Finding] = []
    for leaf in mt.donated:
        if leaf.aliased or leaf.nbytes < MIN_ALIAS_BYTES:
            continue
        out.append(_finding(
            rule, mt, src_line=f"donated:{leaf.name}",
            message=(f"{mt.key}: donated buffer {leaf.name} ({leaf.dtype}, "
                     f"{leaf.nbytes / 2**20:.2f} MiB/device, entry param "
                     f"{leaf.param}) is NOT in the compiled "
                     "input_output_alias map — the donation degrades to a "
                     "silent copy (HBM spike of the same size)")))
    return out


# ---------------------------------------------------------------------------
# IR403 — host callbacks in the hot loop
# ---------------------------------------------------------------------------


@register
class HostCallbackInHotLoop(Rule):
    """``jax.pure_callback`` / ``io_callback`` / ``debug_callback`` /
    ``jax.debug.print`` inside the decode scan, prefill, train step, or
    weight-sync reshard round-trips to the host EVERY step: the TPU
    pipeline drains, the dispatch queue empties, and the overlap the
    scheduler fights for is gone. Debug prints left in by accident are
    the classic case — invisible in a code review, catastrophic at 256
    chips. This rule traces each hot-path target to a jaxpr and walks it
    (recursing through scan/while/pjit/cond sub-jaxprs) for callback
    primitives.

    Fix: delete the callback or hoist it out of the jitted step; for
    debugging, guard prints behind a flag that is False in production
    configs.
    """

    id = "IR403"
    severity = SEV_ERROR
    title = "host callback primitive inside a jitted hot path"
    requires_lowering = True

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        return []


def find_callback_prims(jaxpr) -> List[str]:
    """All callback primitive names in a (Closed)Jaxpr, recursively."""
    found: List[str] = []
    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        inner = getattr(jx, "jaxpr", jx)      # ClosedJaxpr -> Jaxpr
        for eqn in getattr(inner, "eqns", []):
            name = eqn.primitive.name
            if any(name.startswith(p) for p in CALLBACK_PRIMS):
                found.append(name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        walk(sub)

    walk(jaxpr)
    return found


def check_callbacks(mt: MeasuredTarget) -> List[Finding]:
    rule = HostCallbackInHotLoop()
    out: List[Finding] = []
    for prim in sorted(set(mt.callbacks)):
        n = mt.callbacks.count(prim)
        out.append(_finding(
            rule, mt, src_line=f"callback:{prim}",
            message=(f"{mt.key}: {n} `{prim}` primitive(s) inside the "
                     f"jitted {mt.kind} step — every execution round-trips "
                     "to the host and drains the device pipeline")))
    return out


# ---------------------------------------------------------------------------
# IR404 — collective-budget contract
# ---------------------------------------------------------------------------


@register
class CollectiveBudgetRegression(Rule):
    """Per-step collective bytes are the serving/train wall-clock at scale
    — one accidental all-gather of ZeRO-sharded weights on the decode path
    erases the paper's 1.94x. This rule measures trip-count-aware
    per-device collective bytes (``launch/hlo_cost``) for every lowered
    target and diffs them against the checked-in lowering contract file
    (``lowering_contracts.json``, analogous to ``analysis_baseline.json``).
    An increase beyond tolerance (2% rel, 1 KiB abs) fails; a decrease is
    reported as a warning so the contract gets refreshed; a target with no
    contract entry fails until one is reviewed in.

    Fix: if the increase is intentional, regenerate with
    ``repro-analysis --write-contracts`` and justify the diff in review;
    otherwise find the resharding/gather that crept into the step.
    """

    id = "IR404"
    severity = SEV_ERROR
    title = "per-step collective bytes exceed the lowering contract"
    requires_lowering = True

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        return []


def check_contract(mt: MeasuredTarget, contracts: Dict[str, dict],
                   *, rel_tol: float = CONTRACT_REL_TOL,
                   abs_tol: float = CONTRACT_ABS_TOL) -> List[Finding]:
    rule = CollectiveBudgetRegression()
    entry = contracts.get(mt.key)
    if entry is None:
        return [_finding(
            rule, mt, src_line=f"missing-contract:{mt.key}",
            message=(f"{mt.key}: no lowering contract entry — run "
                     "`repro-analysis --write-contracts` and check the "
                     "diff in"))]
    out: List[Finding] = []
    expected = entry.get("collective_bytes", {})
    for kind in COLLECTIVE_KINDS:
        want = float(expected.get(kind, 0.0))
        got = float(mt.collectives.get(kind, 0.0))
        diff = got - want
        if abs(diff) <= max(abs_tol, rel_tol * max(want, got)):
            continue
        if diff > 0:
            out.append(_finding(
                rule, mt, src_line=f"coll:{kind}",
                message=(f"{mt.key}: {kind} bytes/device regressed "
                         f"{want:.3e} -> {got:.3e} "
                         f"({diff / max(want, 1.0):+.1%}) vs the lowering "
                         "contract — an unbudgeted collective crept into "
                         "the step")))
        else:
            out.append(_finding(
                rule, mt, src_line=f"coll:{kind}", severity=SEV_WARNING,
                message=(f"{mt.key}: {kind} bytes/device improved "
                         f"{want:.3e} -> {got:.3e} — refresh the contract "
                         "(`repro-analysis --write-contracts`) so the win "
                         "is locked in")))
    return out


def check_stale_contracts(measured: Sequence[MeasuredTarget],
                          contracts: Dict[str, dict]) -> List[Finding]:
    rule = CollectiveBudgetRegression()
    keys = {mt.key for mt in measured}
    out = []
    for k in sorted(set(contracts) - keys):
        out.append(_finding(
            rule, "lowering_contracts.json", context=k,
            src_line=f"stale:{k}", severity=SEV_WARNING,
            message=(f"contract entry {k} matches no measured target — "
                     "remove it or restore the target")))
    return out


# ---------------------------------------------------------------------------
# PAL205 — Pallas interval analysis
# ---------------------------------------------------------------------------


@register
class PallasIntervalAnalysis(Rule):
    """For every kernel family, capture its real ``pallas_call`` (grid,
    BlockSpecs, scalar-prefetch operands) from a representative harness
    invocation and prove, by propagating grid bounds through each
    ``index_map``, that every block index stays inside
    ``ceil(dim / block_dim)`` for every grid point — an out-of-bounds
    index map is a silent DMA from unrelated memory on hardware (interpret
    mode hides it). Scalar-prefetch index maps (paged attention's block
    table) are evaluated against the concrete prefetch arrays, so the
    sentinel-clamping logic is what's actually proven. The double-buffered
    VMEM footprint (2x every in/out block + scratch) is also estimated
    against the ~16 MiB/core budget. Grids too large to enumerate are
    corner-checked and flagged as not exhaustively proven (warning).

    Fix: clamp computed indices into range (see paged_decode_attn's
    sentinel clamp) or shrink block shapes to fit VMEM.
    """

    id = "PAL205"
    severity = SEV_ERROR
    title = "Pallas index_map out of bounds / VMEM over budget"
    requires_lowering = True

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        return []


@dataclass
class CapturedSpec:
    role: str                    # "in" | "out"
    pos: int
    block_shape: Tuple[int, ...]
    index_map: Any
    array_shape: Tuple[int, ...]
    dtype_size: int


@dataclass
class CapturedCall:
    family: str
    path: str
    line: int
    grid: Tuple[int, ...]
    specs: List[CapturedSpec]
    scratch_bytes: int
    num_scalar_prefetch: int
    prefetch: List[Any]          # concrete numpy arrays


def _spec_fields(spec):
    bs = getattr(spec, "block_shape", None)
    im = getattr(spec, "index_map", None)
    return bs, im


def _dtype_size(dt) -> int:
    import numpy as np
    return int(np.dtype(dt).itemsize)


def capture_pallas_calls(thunk) -> List[CapturedCall]:
    """Run ``thunk`` with ``pl.pallas_call`` replaced by a recorder that
    never executes the kernel: each call site's grid/BlockSpecs/operands
    are captured and zeros of ``out_shape`` are returned so the harness's
    surrounding jnp code still runs."""
    import numpy as np
    import jax
    from jax.experimental import pallas as pl

    captured: List[CapturedCall] = []
    real = pl.pallas_call

    def fake(kernel, *, grid=None, grid_spec=None, in_specs=None,
             out_specs=None, out_shape=None, scratch_shapes=None, **kw):
        caller = inspect.stack()[1]
        nsp = 0
        if grid_spec is not None:
            grid = tuple(grid_spec.grid)
            in_specs = list(grid_spec.in_specs)
            out_specs = grid_spec.out_specs
            scratch_shapes = list(getattr(grid_spec, "scratch_shapes", []))
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0))
        grid_t = (grid,) if isinstance(grid, int) else tuple(grid or ())
        outs = (list(out_shape) if isinstance(out_shape, (list, tuple))
                else [out_shape])
        out_list = not isinstance(out_shape, type(outs[0]))
        out_specs_l = (list(out_specs) if isinstance(out_specs, (list, tuple))
                       else [out_specs])
        scratch = 0
        for s in scratch_shapes or []:
            shp = getattr(s, "shape", None)
            dt = getattr(s, "dtype", None)
            if shp is not None and dt is not None:
                scratch += math.prod(shp) * _dtype_size(dt)

        def runner(*operands):
            prefetch = [np.asarray(o) for o in operands[:nsp]]
            arrays = operands[nsp:]
            specs: List[CapturedSpec] = []
            for i, (sp, arr) in enumerate(zip(in_specs or [], arrays)):
                bs, im = _spec_fields(sp)
                if bs is None:
                    continue
                specs.append(CapturedSpec(
                    "in", i, tuple(bs), im, tuple(arr.shape),
                    _dtype_size(arr.dtype)))
            for i, (sp, o) in enumerate(zip(out_specs_l, outs)):
                bs, im = _spec_fields(sp)
                if bs is None:
                    continue
                specs.append(CapturedSpec(
                    "out", i, tuple(bs), im, tuple(o.shape),
                    _dtype_size(o.dtype)))
            captured.append(CapturedCall(
                family="", path=_rel(caller.filename), line=caller.lineno,
                grid=grid_t, specs=specs, scratch_bytes=scratch,
                num_scalar_prefetch=nsp, prefetch=prefetch))
            zeros = [jax.numpy.zeros(o.shape, o.dtype) for o in outs]
            return zeros if out_list else zeros[0]

        return runner

    pl.pallas_call = fake
    try:
        thunk()
    finally:
        pl.pallas_call = real
    return captured


def _grid_points(grid: Tuple[int, ...], cap: int):
    """(points, exhaustive): all grid points if the grid fits under the
    cap, else the corner combinations."""
    import itertools
    total = math.prod(grid) if grid else 0
    if total <= cap:
        return list(itertools.product(*(range(g) for g in grid))), True
    corners = [sorted({0, g - 1}) for g in grid]
    return list(itertools.product(*corners)), False


def analyze_captured(call: CapturedCall, *,
                     vmem_budget: int = VMEM_BUDGET_BYTES,
                     max_points: int = MAX_GRID_POINTS) -> List[Finding]:
    rule = PallasIntervalAnalysis()
    out: List[Finding] = []
    points, exhaustive = _grid_points(call.grid, max_points)
    vmem = call.scratch_bytes
    for spec in call.specs:
        bd = [b if b is not None else d
              for b, d in zip(spec.block_shape, spec.array_shape)]
        vmem += 2 * math.prod(bd) * spec.dtype_size     # double-buffered
        if spec.index_map is None:
            continue
        nblocks = [max(1, -(-d // b)) for d, b in zip(spec.array_shape, bd)]
        bad = 0
        for pt in points:
            try:
                idx = spec.index_map(*pt, *call.prefetch)
            except Exception as e:
                out.append(_finding(
                    rule, call.path, line=call.line, context=call.family,
                    src_line=f"{call.family}:{spec.role}{spec.pos}:raise",
                    message=(f"{call.family}: index_map of {spec.role}_spec"
                             f"[{spec.pos}] raised {e!r} at grid point "
                             f"{pt} — cannot be proven in-bounds")))
                bad = -1
                break
            idx = tuple(int(v) for v in (idx if isinstance(idx, tuple)
                                         else (idx,)))
            if len(idx) != len(nblocks):
                out.append(_finding(
                    rule, call.path, line=call.line, context=call.family,
                    src_line=f"{call.family}:{spec.role}{spec.pos}:rank",
                    message=(f"{call.family}: index_map of {spec.role}_spec"
                             f"[{spec.pos}] returns rank {len(idx)} for a "
                             f"rank-{len(nblocks)} block")))
                bad = -1
                break
            oob = [d for d in range(len(idx))
                   if not 0 <= idx[d] < nblocks[d]]
            if oob:
                bad += 1
                if bad <= 2:
                    out.append(_finding(
                        rule, call.path, line=call.line, context=call.family,
                        src_line=(f"{call.family}:{spec.role}{spec.pos}:"
                                  f"oob{oob[0]}"),
                        message=(f"{call.family}: {spec.role}_spec"
                                 f"[{spec.pos}] block index {idx} at grid "
                                 f"point {pt} is out of bounds (valid: "
                                 f"{[f'[0,{n})' for n in nblocks]}) — on "
                                 "hardware this DMAs unrelated memory")))
    if not exhaustive:
        out.append(_finding(
            rule, call.path, line=call.line, context=call.family,
            severity=SEV_WARNING,
            src_line=f"{call.family}:unproven",
            message=(f"{call.family}: grid {call.grid} exceeds "
                     f"{max_points} points — only corners checked, "
                     "in-bounds not exhaustively proven")))
    if vmem > vmem_budget:
        out.append(_finding(
            rule, call.path, line=call.line, context=call.family,
            src_line=f"{call.family}:vmem",
            message=(f"{call.family}: estimated VMEM footprint "
                     f"{vmem / 2**20:.2f} MiB (2x blocks + scratch) "
                     f"exceeds the {vmem_budget / 2**20:.0f} MiB/core "
                     "budget — shrink block shapes")))
    return out


# --- kernel-family harnesses -----------------------------------------------
# Representative (production-block-size, small-batch) invocations of each
# family's low-level entry point. Only shapes matter: pallas_call is faked
# during capture, the kernel body never runs.


def _harness_decode_attn():
    import jax.numpy as jnp
    from repro.kernels.decode_attn.decode_attn import decode_attention_kernel
    # L off the 512 block: the ragged last block and the live-block clamp
    B, H, KV, hd, L = 2, 8, 2, 128, 2496
    q = jnp.zeros((B, H, hd), jnp.bfloat16)
    k = jnp.zeros((B, L, KV * hd), jnp.bfloat16)
    cl = jnp.array([L, L // 2], jnp.int32)
    decode_attention_kernel(q, k, k, cl, block_l=512)


def _harness_paged_decode_attn():
    import jax.numpy as jnp
    from repro.kernels.paged_decode_attn.paged_decode_attn import (
        paged_decode_attention_kernel,
    )
    B, H, KV, hd, NP, ps, mp = 2, 8, 2, 128, 7, 128, 4
    q = jnp.zeros((B, H, hd), jnp.bfloat16)
    pool = jnp.zeros((NP, KV, ps, hd), jnp.bfloat16)
    # includes the unmapped-page sentinel NP: the clamp is what gets proven
    bt = jnp.array([[0, 1, 2, NP], [3, 4, NP, NP]], jnp.int32)
    cl = jnp.array([3 * ps - 5, 2 * ps], jnp.int32)
    paged_decode_attention_kernel(q, pool, pool, bt, cl)


def _harness_flash_attn():
    import jax.numpy as jnp
    from repro.kernels.flash_attn.flash_attn import flash_attention_bhsd
    BH, S, hd = 4, 1024, 128
    q = jnp.zeros((BH, S, hd), jnp.bfloat16)
    flash_attention_bhsd(q, q, q, block_q=256, block_k=256)


def _harness_fused_logprob():
    import jax.numpy as jnp
    from repro.kernels.fused_logprob.fused_logprob import fused_logprob_rows
    R, d, V = 512, 1024, 4096
    h = jnp.zeros((R, d), jnp.float32)
    w = jnp.zeros((d, V), jnp.float32)
    t = jnp.zeros((R,), jnp.int32)
    fused_logprob_rows(h, w, t)


def _harness_ssm_scan():
    import jax.numpy as jnp
    from repro.kernels.ssm_scan.ssm_scan import selective_scan_kernel
    B, T, di, N = 2, 512, 512, 16
    x = jnp.zeros((B, T, di), jnp.float32)
    A = jnp.zeros((di, N), jnp.float32)
    bc = jnp.zeros((B, T, N), jnp.float32)
    D = jnp.zeros((di,), jnp.float32)
    s0 = jnp.zeros((B, di, N), jnp.float32)
    selective_scan_kernel(x, x, A, bc, bc, D, s0, block_d=256, chunk=128)


def _harness_rwkv6_scan():
    import jax.numpy as jnp
    from repro.kernels.rwkv6_scan.rwkv6_scan import wkv6_bh
    BH, T, hd = 4, 512, 64
    r = jnp.zeros((BH, T, hd), jnp.float32)
    u = jnp.zeros((BH, 1, hd), jnp.float32)
    s0 = jnp.zeros((BH, hd, hd), jnp.float32)
    wkv6_bh(r, r, r, r, u, s0, chunk=128)


def _harness_fused_is_grpo():
    import jax.numpy as jnp
    from repro.kernels.fused_is_grpo.fused_is_grpo import (
        fused_is_grpo_bwd_rows,
        fused_is_grpo_fwd_rows,
    )
    R, d, V = 512, 1024, 4096
    h = jnp.zeros((R, d), jnp.float32)
    w = jnp.zeros((d, V), jnp.float32)
    t = jnp.zeros((R,), jnp.int32)
    r = jnp.zeros((R,), jnp.float32)
    fused_is_grpo_fwd_rows(h, w, t, r, r, logit_softcap=30.0,
                           entropy_coef=0.01)
    # both backward kernels (dh: grid (nr, nv); dw: grid (nv, nr))
    fused_is_grpo_bwd_rows(h, w, t, r, r, r, r, logit_softcap=30.0)


def _harness_fused_sample():
    import jax.numpy as jnp
    from repro.kernels.fused_sample.fused_sample import fused_sample_rows_kernel
    B, V = 64, 4096
    keys = jnp.zeros((B, 2), jnp.uint32)
    logits = jnp.zeros((B, V), jnp.float32)
    # top-k AND top-p active: the full [stats, 4x topk, 4x topp, draw]
    # phase schedule is what gets interval-checked
    fused_sample_rows_kernel(keys, logits, temperature=0.8, top_k=50,
                             top_p=0.9)


HARNESSES = {
    "decode_attn": _harness_decode_attn,
    "paged_decode_attn": _harness_paged_decode_attn,
    "flash_attn": _harness_flash_attn,
    "fused_logprob": _harness_fused_logprob,
    "fused_is_grpo": _harness_fused_is_grpo,
    "fused_sample": _harness_fused_sample,
    "ssm_scan": _harness_ssm_scan,
    "rwkv6_scan": _harness_rwkv6_scan,
}


def run_pallas_interval(families: Optional[Sequence[str]] = None,
                        ) -> List[Finding]:
    rule = PallasIntervalAnalysis()
    out: List[Finding] = []
    for fam in (families or sorted(HARNESSES)):
        thunk = HARNESSES[fam]
        try:
            calls = capture_pallas_calls(thunk)
        except Exception as e:                          # pragma: no cover
            out.append(_finding(
                rule, f"src/repro/kernels/{fam}", context=fam,
                src_line=f"{fam}:harness",
                message=f"{fam}: capture harness failed: {e!r}"))
            continue
        if not calls:
            out.append(_finding(
                rule, f"src/repro/kernels/{fam}", context=fam,
                src_line=f"{fam}:nocall", severity=SEV_WARNING,
                message=(f"{fam}: harness captured no pallas_call — the "
                         "family's kernel path is unreachable")))
        for call in calls:
            call.family = fam
            out.extend(analyze_captured(call))
    return out


# ---------------------------------------------------------------------------
# the --ir entry point
# ---------------------------------------------------------------------------


def _rel(path: str) -> str:
    rp = os.path.relpath(path)
    return rp.replace(os.sep, "/")


def _relsrc(obj) -> str:
    try:
        return _rel(inspect.getsourcefile(obj))
    except TypeError:
        return "<unknown>"


def _want(rid: str, select, ignore) -> bool:
    if select and not any(rid.startswith(s) for s in select):
        return False
    if ignore and any(rid.startswith(s) for s in ignore):
        return False
    return True


def measure_all(archs: Optional[Sequence[str]] = None,
                ) -> List[MeasuredTarget]:
    """Measure every default contract target (see ``contracts.py``).
    Importing ``contracts`` sets the fake-device ``XLA_FLAGS`` before JAX
    initializes, so this must run in a process that has not imported JAX
    yet (the CLI does; pytest monkeypatches this function instead)."""
    from repro.analysis import contracts
    return [contracts.measure_target(t)
            for t in contracts.default_targets(archs=archs)]


def run_ir(select: Optional[Sequence[str]] = None,
           ignore: Optional[Sequence[str]] = None,
           contracts_path: str = "lowering_contracts.json",
           archs: Optional[Sequence[str]] = None,
           ) -> Tuple[List[Finding], int]:
    """Run the IR rule suite; returns (findings, targets_analyzed)."""
    findings: List[Finding] = []
    scanned = 0
    if _want("IR401", select, ignore):
        findings.extend(check_bucket_stability())
    if any(_want(r, select, ignore)
           for r in ("IR401", "IR402", "IR403", "IR404")):
        measured = measure_all(archs=archs)
        scanned += len(measured)
        for mt in measured:
            if _want("IR401", select, ignore):
                findings.extend(check_signature(mt))
            if _want("IR402", select, ignore):
                findings.extend(check_donation(mt))
            if _want("IR403", select, ignore):
                findings.extend(check_callbacks(mt))
        if _want("IR404", select, ignore):
            from repro.analysis import contracts
            cdata = contracts.load_contracts(contracts_path)
            for mt in measured:
                findings.extend(check_contract(mt, cdata))
            if archs is None:
                findings.extend(check_stale_contracts(measured, cdata))
    if _want("PAL205", select, ignore):
        findings.extend(run_pallas_interval())
        scanned += len(HARNESSES)
    return findings, scanned
