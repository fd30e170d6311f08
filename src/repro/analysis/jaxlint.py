"""jaxlint (JAX1xx): host/device-boundary hazards in JAX code.

These are the bug classes that have already bitten this repo by hand:
PR 1 existed because host syncs inside the decode loop went unnoticed, and
PR 2 fixed a ~200x timing lie from a missing ``block_until_ready``. All
rules are intra-module AST analyses — conservative by design: a finding
means the hazard is visible locally, absence of findings is not a proof.

Shared machinery: a module pre-scan collects every *jit-wrapped callable*
visible in the module — ``@jax.jit`` / ``@functools.partial(jax.jit, ...)``
decorated defs, ``name = jax.jit(fn_or_lambda, ...)`` assignments, and
``self.attr = jax.jit(...)`` / ``self.attr = jitted_def`` bindings — along
with their ``static_argnames`` and ``donate_argnums``.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

from repro.analysis.core import (
    SEV_ERROR,
    SEV_WARNING,
    Finding,
    ModuleCtx,
    Rule,
    call_name,
    const_ints,
    const_strs,
    dotted,
    func_defs,
    kw,
    param_names,
    register,
    walk_stmts_in_order,
)

# call names (matched on the LAST dotted component) that dispatch async
# device work in this repo even though they are not module-local jits:
# the rollout engine's stage-driving methods and the ParamStore reshard.
# Documented contract — extend when a new async-dispatch surface lands.
DISPATCHING_CALLS = {"collect", "step_stage", "begin_stage", "_reshard",
                     "device_put"}

# last-component call names that force dispatched work to completion
SYNCING_CALLS = {"block_until_ready", "device_get", "effects_barrier",
                 "item"}

# jax.random.* functions that do NOT consume a key's randomness
NON_CONSUMING_RANDOM = {"split", "fold_in", "PRNGKey", "key", "key_data",
                        "wrap_key_data", "clone", "key_impl"}

# attribute reads that yield STATIC (non-traced) values
UNTAINT_ATTRS = {"shape", "dtype", "ndim", "size", "sharding", "weak_type"}
UNTAINT_FUNCS = {"len", "type", "isinstance", "hasattr", "getattr", "range",
                 "enumerate", "zip"}


# ---------------------------------------------------------------------------
# module pre-scan: jit-wrapped callables
# ---------------------------------------------------------------------------


@dataclass
class JitBinding:
    name: str                    # plain name, or "self.attr" dotted form
    fn: Optional[ast.AST]        # FunctionDef/Lambda when body is analyzable
    static_argnames: Set[str] = field(default_factory=set)
    donate_argnums: List[int] = field(default_factory=list)
    node: Optional[ast.AST] = None


def _jit_call_parts(call: ast.Call):
    """If ``call`` is ``jax.jit(...)`` or ``functools.partial(jax.jit, ...)``
    return (inner_arg_or_None, static_argnames, donate_argnums), else None.
    For the partial form inner_arg is None (it decorates a def)."""
    name = call_name(call)
    if name and name.endswith("jax.jit") or name == "jit":
        inner = call.args[0] if call.args else None
        return inner, set(const_strs(kw(call, "static_argnames"))), \
            const_ints(kw(call, "donate_argnums"))
    if name and name.endswith("partial") and call.args:
        first = dotted(call.args[0])
        if first in ("jax.jit", "jit"):
            return None, set(const_strs(kw(call, "static_argnames"))), \
                const_ints(kw(call, "donate_argnums"))
    return None


def collect_jit_bindings(tree: ast.AST) -> Dict[str, JitBinding]:
    """name -> JitBinding for every jit-wrapped callable in the module.
    Names are plain identifiers or ``self.attr`` dotted strings."""
    out: Dict[str, JitBinding] = {}
    local_defs = {f.name: f for f in func_defs(tree)}

    # decorated defs
    for f in func_defs(tree):
        for dec in f.decorator_list:
            parts = None
            if isinstance(dec, ast.Call):
                parts = _jit_call_parts(dec)
            elif dotted(dec) in ("jax.jit", "jit"):
                parts = (None, set(), [])
            if parts is not None:
                out[f.name] = JitBinding(f.name, f, parts[1], parts[2], f)
                break

    # assignments: x = jax.jit(...) / self.attr = jax.jit(...) /
    # self.attr = jitted_local_def
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = dotted(node.targets[0])
        if tgt is None:
            continue
        if isinstance(node.value, ast.Call):
            parts = _jit_call_parts(node.value)
            if parts is None:
                continue
            inner, statics, donate = parts
            fn = None
            if isinstance(inner, ast.Lambda):
                fn = inner
            elif isinstance(inner, ast.Name) and inner.id in local_defs:
                fn = local_defs[inner.id]
            out[tgt] = JitBinding(tgt, fn, statics, donate, node)
        elif isinstance(node.value, ast.Name) and node.value.id in out:
            src = out[node.value.id]
            out[tgt] = JitBinding(tgt, src.fn, src.static_argnames,
                                  src.donate_argnums, node)
    return out


def _np_aliases(tree: ast.AST) -> Set[str]:
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    names.add(a.asname or "numpy")
    return names


def _random_aliases(tree: ast.AST) -> Set[str]:
    """Dotted prefixes that mean jax.random ('jax.random', plus aliases)."""
    out = {"jax.random"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.random" and a.asname:
                    out.add(a.asname)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name == "random":
                        out.add(a.asname or "random")
    return out


# ---------------------------------------------------------------------------
# JAX101 — host sync inside a traced function
# ---------------------------------------------------------------------------


@register
class HostSyncInJit(Rule):
    """A jit-traced function body forces a host/device sync or a trace-time
    branch on a traced value.

    Inside ``@jax.jit`` (and functions handed to ``jax.lax.scan``), calling
    ``.item()``, ``float()``/``int()``/``bool()`` on a traced value,
    applying ``np.*`` to a traced array, or branching (``if``/``while``) on
    a traced value either fails at trace time or — worse — silently
    constant-folds the Python branch into the compiled program and syncs
    the device every call. PR 1 rewrote the decode loop precisely because
    per-token host syncs of this shape went unnoticed.

    Taint model: the traced function's parameters (minus
    ``static_argnames``) are traced; assignment propagates; ``.shape`` /
    ``.dtype`` / ``len()`` reads are static and strip taint. Nested defs'
    own parameters are unknown, not traced — conservative, so closure
    ints like ``if axis == 0:`` inside jitted helpers never false-positive.

    Fix: keep host logic outside the jit; use ``jnp.where`` /
    ``lax.cond`` / ``lax.select`` for value-dependent control flow.
    """

    id = "JAX101"
    severity = SEV_ERROR
    title = "host sync / Python branch on traced value inside jit"

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        findings: List[Finding] = []
        bindings = collect_jit_bindings(ctx.tree)
        np_names = _np_aliases(ctx.tree)
        traced: List[tuple] = []
        seen_fns = set()
        for b in bindings.values():
            if b.fn is not None and id(b.fn) not in seen_fns:
                seen_fns.add(id(b.fn))
                traced.append((b.fn, b.static_argnames))
        # functions handed to jax.lax.scan trace their body too
        local_defs = {f.name: f for f in func_defs(ctx.tree)}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and call_name(node) in (
                    "jax.lax.scan", "lax.scan") and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Name) and a0.id in local_defs:
                    f = local_defs[a0.id]
                    if id(f) not in seen_fns:
                        seen_fns.add(id(f))
                        traced.append((f, set()))
        for fn, statics in traced:
            findings.extend(self._check_traced(ctx, fn, statics, np_names))
        return findings

    # -- taint engine --------------------------------------------------
    def _check_traced(self, ctx, fn, statics, np_names) -> List[Finding]:
        out: List[Finding] = []
        if isinstance(fn, ast.Lambda):
            taint = {p for p in param_names(fn)} - statics
            self._scan_expr(ctx, fn.body, taint, np_names, out)
            return out
        taint = set(param_names(fn)) - statics - {"self"}
        self._scan_block(ctx, fn.body, taint, np_names, out)
        return out

    def _tainted(self, node, taint) -> bool:
        if isinstance(node, ast.Name):
            return node.id in taint
        if isinstance(node, ast.Attribute):
            if node.attr in UNTAINT_ATTRS:
                return False
            return self._tainted(node.value, taint)
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name and name.split(".")[-1] in UNTAINT_FUNCS | {"shape"}:
                return False
            return (any(self._tainted(a, taint) for a in node.args)
                    or any(self._tainted(k.value, taint)
                           for k in node.keywords)
                    or self._tainted(node.func, taint))
        if isinstance(node, (ast.BinOp,)):
            return self._tainted(node.left, taint) or \
                self._tainted(node.right, taint)
        if isinstance(node, ast.UnaryOp):
            return self._tainted(node.operand, taint)
        if isinstance(node, ast.BoolOp):
            return any(self._tainted(v, taint) for v in node.values)
        if isinstance(node, ast.Compare):
            return self._tainted(node.left, taint) or \
                any(self._tainted(c, taint) for c in node.comparators)
        if isinstance(node, ast.Subscript):
            return self._tainted(node.value, taint)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self._tainted(e, taint) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return (self._tainted(node.body, taint)
                    or self._tainted(node.orelse, taint))
        if isinstance(node, ast.Starred):
            return self._tainted(node.value, taint)
        return False

    def _scan_block(self, ctx, body, taint, np_names, out):
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = set(taint) - set(param_names(stmt))
                self._scan_block(ctx, stmt.body, inner, np_names, out)
                continue
            if isinstance(stmt, (ast.If, ast.While)):
                if self._tainted(stmt.test, taint):
                    out.append(ctx.finding(
                        self, stmt.test,
                        "Python control flow on a traced value inside a "
                        "jitted function — use lax.cond/jnp.where"))
                else:
                    self._scan_expr(ctx, stmt.test, taint, np_names, out)
                self._scan_block(ctx, stmt.body, taint, np_names, out)
                self._scan_block(ctx, stmt.orelse, taint, np_names, out)
                continue
            if isinstance(stmt, ast.Assign):
                self._scan_expr(ctx, stmt.value, taint, np_names, out)
                is_t = self._tainted(stmt.value, taint)
                for tgt in stmt.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            (taint.add if is_t else taint.discard)(n.id)
                continue
            if isinstance(stmt, ast.AugAssign):
                self._scan_expr(ctx, stmt.value, taint, np_names, out)
                if isinstance(stmt.target, ast.Name) and \
                        self._tainted(stmt.value, taint):
                    taint.add(stmt.target.id)
                continue
            if isinstance(stmt, ast.Delete):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        taint.discard(t.id)
                continue
            if isinstance(stmt, ast.For):
                self._scan_expr(ctx, stmt.iter, taint, np_names, out)
                if self._tainted(stmt.iter, taint):
                    for n in ast.walk(stmt.target):
                        if isinstance(n, ast.Name):
                            taint.add(n.id)
                self._scan_block(ctx, stmt.body, taint, np_names, out)
                self._scan_block(ctx, stmt.orelse, taint, np_names, out)
                continue
            for attr in ("body", "orelse", "finalbody"):
                inner = getattr(stmt, attr, None)
                if inner:
                    self._scan_block(ctx, inner, taint, np_names, out)
            for h in getattr(stmt, "handlers", []) or []:
                self._scan_block(ctx, h.body, taint, np_names, out)
            for v in ast.iter_child_nodes(stmt):
                if isinstance(v, ast.expr):
                    self._scan_expr(ctx, v, taint, np_names, out)

    def _scan_expr(self, ctx, expr, taint, np_names, out):
        for node in ast.walk(expr):
            if isinstance(node, ast.Lambda):
                continue
            if isinstance(node, ast.IfExp) and self._tainted(node.test,
                                                             taint):
                out.append(ctx.finding(
                    self, node,
                    "conditional expression on a traced value inside a "
                    "jitted function — use jnp.where/lax.select"))
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item":
                out.append(ctx.finding(
                    self, node, ".item() inside a jitted function forces a "
                    "device->host sync at trace time"))
            elif name in ("float", "int", "bool") and node.args and \
                    self._tainted(node.args[0], taint):
                out.append(ctx.finding(
                    self, node,
                    f"{name}() on a traced value inside a jitted function "
                    "forces a host sync — keep it device-side (jnp)"))
            elif name and "." in name and name.split(".")[0] in np_names \
                    and any(self._tainted(a, taint) for a in node.args):
                out.append(ctx.finding(
                    self, node,
                    f"numpy call {name}() on a traced value inside a "
                    "jitted function — use jnp instead"))


# ---------------------------------------------------------------------------
# JAX102 — PRNG key reuse
# ---------------------------------------------------------------------------


@register
class PRNGKeyReuse(Rule):
    """The same PRNG key object is consumed by more than one random call.

    ``jax.random`` keys are pure values: feeding one key to two sampling
    calls yields CORRELATED (often bit-identical) streams — e.g. benchmark
    K and V tensors that are the same array, or two "independent" samples
    that agree everywhere. Every consumption must use a fresh key from
    ``jax.random.split`` / ``fold_in``.

    Detection: within one function scope, a name (or ``self.attr``) passed
    as the key argument to a consuming ``jax.random.*`` call twice without
    an intervening reassignment — including a single consumption inside a
    loop body that never refreshes the key. ``split``/``fold_in``/
    ``PRNGKey`` are not consumers.

    Fix: ``k1, k2 = jax.random.split(key)`` (or ``split(key, n)`` /
    ``fold_in(key, i)`` in loops), one subkey per consumption.
    """

    id = "JAX102"
    severity = SEV_WARNING
    title = "PRNG key reused by multiple random calls"

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        findings: List[Finding] = []
        rand = _random_aliases(ctx.tree)
        scopes: List[List[ast.stmt]] = [ctx.tree.body]
        for f in func_defs(ctx.tree):
            scopes.append(f.body)
        for body in scopes:
            consumed: Dict[str, ast.AST] = {}
            self._scan(ctx, body, rand, consumed, findings, set(),
                       top=True)
        return findings

    def _key_of(self, call: ast.Call, rand) -> Optional[str]:
        name = call_name(call)
        if not name or "." not in name:
            return None
        prefix, last = name.rsplit(".", 1)
        if prefix not in rand or last in NON_CONSUMING_RANDOM:
            return None
        if call.args:
            return dotted(call.args[0])
        k = kw(call, "key")
        return dotted(k) if k is not None else None

    def _scan(self, ctx, body, rand, consumed, findings, flagged, *,
              top=False, repass=False):
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                if top:
                    continue           # handled as their own scope
                continue
            if isinstance(stmt, ast.If):
                pre = dict(consumed)
                self._scan(ctx, stmt.body, rand, consumed, findings,
                           flagged, repass=repass)
                other = dict(pre)
                self._scan(ctx, stmt.orelse, rand, other, findings,
                           flagged, repass=repass)
                consumed.update(other)     # union of branch consumptions
                continue
            if isinstance(stmt, (ast.For, ast.While)):
                # two passes: a loop-body consumption with no refresh in
                # the loop meets its OWN record on the second pass (the
                # repass flag lets the same node flag itself)
                self._scan(ctx, stmt.body, rand, consumed, findings,
                           flagged, repass=repass)
                self._scan(ctx, stmt.body, rand, consumed, findings,
                           flagged, repass=True)
                self._scan(ctx, stmt.orelse, rand, consumed, findings,
                           flagged, repass=repass)
                continue
            for attr in ("body", "orelse", "finalbody"):
                inner = getattr(stmt, attr, None)
                if inner:
                    self._scan(ctx, inner, rand, consumed, findings,
                               flagged, repass=repass)
            for h in getattr(stmt, "handlers", []) or []:
                self._scan(ctx, h.body, rand, consumed, findings,
                           flagged, repass=repass)
            # consumptions in this statement's expressions (source order)
            hits = []
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    key = self._key_of(node, rand)
                    if key is not None:
                        hits.append((node.lineno, node.col_offset, key,
                                     node))
            for _, _, key, node in sorted(hits, key=lambda h: (h[0], h[1])):
                prev = consumed.get(key)
                if prev is not None and (prev is not node or repass) \
                        and id(node) not in flagged:
                    flagged.add(id(node))
                    findings.append(ctx.finding(
                        self, node,
                        f"PRNG key {key!r} already consumed at line "
                        f"{prev.lineno} — split/fold_in before reusing"))
                consumed[key] = node
            # reassignments clear consumption state
            tgts = []
            if isinstance(stmt, ast.Assign):
                tgts = stmt.targets
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                tgts = [stmt.target]
            elif isinstance(stmt, ast.Delete):
                tgts = stmt.targets
            for tgt in tgts:
                for n in ast.walk(tgt):
                    d = dotted(n)
                    if d is not None:
                        consumed.pop(d, None)


# ---------------------------------------------------------------------------
# JAX103 — donated buffer used after donation
# ---------------------------------------------------------------------------


@register
class UseAfterDonation(Rule):
    """An argument donated to a jitted call is referenced after the call.

    ``donate_argnums`` hands the buffer's memory to XLA: after the call the
    old array is invalid, and touching it raises (or, across async
    dispatch, silently reads garbage on some backends). The engine's KV
    cache is donated on every decode chunk — a second reference is a
    use-after-free.

    Detection: for module-local jit bindings with ``donate_argnums``, every
    call site is checked — if the donated positional argument is a plain
    name / ``self.attr`` and the enclosing function reads it again before
    rebinding it, the read is flagged. Rebinding in the same statement
    (``cache, ys = f(params, cache)``) is the sanctioned pattern.

    Fix: rebind the donated name from the call's result immediately, or
    drop the donation.
    """

    id = "JAX103"
    severity = SEV_ERROR
    title = "donated buffer referenced after the jitted call"

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        findings: List[Finding] = []
        bindings = collect_jit_bindings(ctx.tree)
        donating = {n: b for n, b in bindings.items() if b.donate_argnums}
        if not donating:
            return findings
        for fn in func_defs(ctx.tree):
            self._check_fn(ctx, fn, donating, findings)
        return findings

    def _check_fn(self, ctx, fn, donating, findings):
        stmts = list(walk_stmts_in_order(fn.body))
        donated: Dict[str, ast.AST] = {}     # name -> donating call node
        for stmt in stmts:
            reads = self._names_read(stmt)
            stores = self._names_stored(stmt)
            calls = [n for n in ast.walk(stmt) if isinstance(n, ast.Call)]
            donated_here: Dict[str, ast.AST] = {}
            skip_reads: Set[str] = set()
            for call in calls:
                cn = call_name(call)
                if cn not in donating:
                    continue
                for i in donating[cn].donate_argnums:
                    if i < len(call.args):
                        nm = dotted(call.args[i])
                        if nm is not None:
                            donated_here[nm] = call
                            skip_reads.add(nm)
            # reads of previously-donated names (not cleared yet)
            for nm, node in reads:
                if nm in donated and nm not in skip_reads:
                    findings.append(ctx.finding(
                        self, node,
                        f"{nm!r} was donated to "
                        f"{call_name(donated[nm])}() at line "
                        f"{donated[nm].lineno} and is referenced here "
                        "before rebinding — use-after-donation"))
                    donated.pop(nm, None)      # report once
            for nm in stores:
                donated.pop(nm, None)
            for nm, call in donated_here.items():
                if nm not in stores:           # same-stmt rebind sanctions it
                    donated[nm] = call

    def _names_read(self, stmt):
        out = []
        for node in ast.walk(stmt):
            if isinstance(node, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(node, "ctx", None), ast.Load):
                d = dotted(node)
                if d is not None:
                    out.append((d, node))
        return out

    def _names_stored(self, stmt) -> Set[str]:
        out: Set[str] = set()
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        elif isinstance(stmt, (ast.For,)):
            targets = [stmt.target]
        elif isinstance(stmt, ast.Delete):
            targets = stmt.targets
        for tgt in targets:
            for n in ast.walk(tgt):
                d = dotted(n)
                if d is not None:
                    out.add(d)
        return out


# ---------------------------------------------------------------------------
# JAX104 — wall-clock timing of un-synced dispatch
# ---------------------------------------------------------------------------


@register
class AsyncDispatchTiming(Rule):
    """A wall-clock interval spans async-dispatched device work without
    forcing completion before the clock is read.

    ``jax.jit`` dispatch is asynchronous: the Python call returns as soon
    as the computation is ENQUEUED. Timing it with ``time.perf_counter()``
    measures dispatch overhead, not compute — PR 2 found this overstating
    ``overlap_saved_time`` by ~200x on CPU. Benchmarks and stage timers
    must call ``jax.block_until_ready`` (or otherwise consume the result)
    before stamping the end time.

    Detection: inside one function, for every ``a - b`` where both sides
    are ``time.perf_counter()``-family stamps, the statements between the
    two stamps are checked for dispatching calls — module-local jitted
    callables (including ``self.attr`` bindings), ``jax.device_put``, and
    the repo's known async-dispatch methods (``collect`` / ``step_stage``
    / ``begin_stage`` / ``_reshard``) — with no
    ``block_until_ready``/``device_get``/``.item()`` between the dispatch
    and the closing stamp.

    A phase span (``with Span(...)``, ``repro.common.spans``) reads the
    clock when its block is entered and left, so its block is an interval
    too: a dispatch inside it must be followed, inside it, by a sync.

    Fix: ``jax.block_until_ready(result)`` (for the engine: its cache)
    before reading the end-of-interval clock.
    """

    id = "JAX104"
    severity = SEV_WARNING
    title = "timing interval spans un-synced async dispatch"

    CLOCKS = {"time.perf_counter", "time.time", "time.monotonic",
              "perf_counter", "monotonic"}
    # context managers that time their block (last dotted component)
    SPANS = {"Span"}

    def check(self, ctx: ModuleCtx) -> List[Finding]:
        findings: List[Finding] = []
        bindings = collect_jit_bindings(ctx.tree)
        jit_names = set(bindings)
        scopes: List[List[ast.stmt]] = [ctx.tree.body]
        for f in func_defs(ctx.tree):
            scopes.append(f.body)
        for body in scopes:
            self._check_scope(ctx, body, jit_names, findings)
            self._check_spans(ctx, body, jit_names, findings)
        return findings

    def _call_kind(self, node, jit_names) -> Optional[str]:
        """The call's kind: "sync", "dispatch" or None."""
        name = call_name(node) or ""
        last = name.split(".")[-1]
        func = node.func
        if (isinstance(func, ast.Attribute) and func.attr in SYNCING_CALLS) \
                or last in SYNCING_CALLS:
            return "sync"
        if name in jit_names or last in DISPATCHING_CALLS:
            return "dispatch"
        return None

    def _check_spans(self, ctx, body, jit_names, findings):
        for stmt in walk_stmts_in_order(body):
            if not isinstance(stmt, (ast.With, ast.AsyncWith)) or not any(
                    isinstance(it.context_expr, ast.Call)
                    and (call_name(it.context_expr) or "").split(".")[-1]
                    in self.SPANS for it in stmt.items):
                continue
            events = []
            for inner in stmt.body:
                for node in ast.walk(inner):
                    if isinstance(node, ast.Call):
                        kind = self._call_kind(node, jit_names)
                        if kind is not None:
                            events.append((node.lineno, kind, call_name(node)))
            pending = None
            for _, kind, name in sorted(events):
                pending = name if kind == "dispatch" else None
            if pending is not None:
                findings.append(ctx.finding(
                    self, stmt,
                    f"span closes over async dispatch {pending}() with no "
                    "block_until_ready/device_get after it — times the "
                    "enqueue, not the compute"))

    def _is_clock_call(self, node) -> bool:
        return isinstance(node, ast.Call) and call_name(node) in self.CLOCKS

    def _check_scope(self, ctx, body, jit_names, findings):
        stamps: Dict[str, int] = {}          # name -> lineno of stamp
        events: List[tuple] = []             # (line, kind, payload)
        for stmt in walk_stmts_in_order(body):
            if isinstance(stmt, ast.Assign):
                stamped = False
                if self._is_clock_call(stmt.value):
                    stamped = True
                    for tgt in stmt.targets:
                        d = dotted(tgt)
                        if d:
                            stamps[d] = stmt.lineno
                elif (len(stmt.targets) == 1
                      and isinstance(stmt.targets[0], ast.Tuple)
                      and isinstance(stmt.value, ast.Tuple)
                      and len(stmt.targets[0].elts)
                      == len(stmt.value.elts)):
                    # t0, x = time.perf_counter(), 0
                    for tgt, val in zip(stmt.targets[0].elts,
                                        stmt.value.elts):
                        if self._is_clock_call(val):
                            d = dotted(tgt)
                            if d:
                                stamps[d] = stmt.lineno
                                stamped = True
                if stamped:
                    continue
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    kind = self._call_kind(node, jit_names)
                    if kind == "sync":
                        events.append((node.lineno, "sync", None))
                    elif kind == "dispatch":
                        events.append((node.lineno, "dispatch",
                                       call_name(node) or ""))
                if isinstance(node, ast.BinOp) and \
                        isinstance(node.op, ast.Sub):
                    hi = self._stamp_line(node.left, stamps, node.lineno)
                    lo = self._stamp_line(node.right, stamps, None)
                    if hi is not None and lo is not None and lo < hi:
                        events.append((node.lineno, "read", (lo, hi, node)))
        for line, kind, payload in events:
            if kind != "read":
                continue
            lo, hi, node = payload
            pending = None
            for eline, ekind, ep in sorted(e for e in events
                                           if e[1] != "read"):
                if eline < lo or eline > hi:
                    continue
                if ekind == "dispatch":
                    pending = ep
                elif ekind == "sync":
                    pending = None
            if pending is not None:
                findings.append(ctx.finding(
                    self, node,
                    f"elapsed-time read spans async dispatch "
                    f"{pending}() with no block_until_ready/device_get "
                    "before the closing clock stamp — measures dispatch, "
                    "not compute"))
        return findings

    def _stamp_line(self, node, stamps, self_line) -> Optional[int]:
        """Line at which this side of the subtraction was stamped: a direct
        clock call stamps at its own line, a name at its assignment."""
        if self._is_clock_call(node):
            return self_line if self_line is not None else node.lineno
        d = dotted(node)
        if d is not None and d in stamps:
            return stamps[d]
        return None
