"""Reduction of a JAX profiler trace to device busy time, device time per
operation, and idle gaps named by what the host was doing.

A device operation is an event on an ``XLA Ops`` line of a ``/device:``
plane (TPU), or, on the CPU, an event that carries an ``hlo_op`` stat.
Each operation is named ``<program>/<op>``: the jitted program whose
``XLA Modules`` event contains it (or its ``hlo_module`` stat), and the
operation with its numeric suffix removed, so names stay stable across
compilations.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

_SUFFIX = re.compile(r"([.:](\d+|clone))+$")
_MODULE = re.compile(r"\(\d+\)$")


def stable(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``;
    ``jit_step(42)`` -> ``jit_step``."""
    name = (name or "").split(" = ")[0].strip().lstrip("%")
    return _SUFFIX.sub("", _MODULE.sub("", name)).strip()


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # union of op intervals, mean over chips
    op_s: dict = field(default_factory=dict)      # name -> self seconds
    gaps: list = field(default_factory=list)      # (seconds, host label)

    def time_of(self, pattern: str) -> float:
        """Device seconds of every operation whose name matches."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        by_label = {}
        for s, label in self.gaps:
            by_label[label] = by_label.get(label, 0.0) + s
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    total, end = 0, None
    start = None
    out = []
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                out.append((start, end))
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        out.append((start, end))
    total = sum(e - s for s, e in out)
    return total, out


def reduce(path: str, t0_ns: float = None, t1_ns: float = None) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb``) over the window
    [t0_ns, t1_ns] on the trace's clock (default: the span named
    ``bench.window``, else the whole trace)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev_ops = {}            # plane name -> list of (start, end, name)
    modules = {}            # plane name -> list of (start, end, module)
    host = []               # (start, end, name) on host threads
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns
                e = s + ev.duration_ns
                if is_dev and line.name == "XLA Ops":
                    dev_ops.setdefault(plane.name, []).append((s, e, ev.name))
                elif is_dev and line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).append(
                        (s, e, stable(ev.name)))
                elif not is_dev:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        key = "cpu:%s" % stats.get("device_ordinal", 0)
                        dev_ops.setdefault(key, []).append(
                            (s, e, "%s/%s" % (stable(str(stats.get(
                                "hlo_module", ""))), stable(ev.name))))
                    elif ev.duration_ns > 0 and not ev.name.startswith(
                            ("end: ", "ThreadpoolListener", "$")):
                        host.append((s, e, ev.name, (plane.name, line.name)))
    win = [h for h in host if h[2] == "bench.window"]
    if win:        # label gaps from the thread that drove the window
        host = [h for h in host if h[3] == win[-1][3]]
    host = sorted(h[:3] for h in host)
    if t0_ns is None:
        if win:
            t0_ns, t1_ns = win[-1][0], win[-1][1]
        else:
            allev = [o for v in dev_ops.values() for o in v] + host
            t0_ns = min(o[0] for o in allev)
            t1_ns = max(o[1] for o in allev)
    window = t1_ns - t0_ns
    op_s, busy = {}, []
    gaps = []
    for plane, ops in dev_ops.items():
        mods = sorted(modules.get(plane, []))
        clipped = []
        for (s, e, name), own in zip(ops, _self_ns(ops)):
            cs, ce = max(s, t0_ns), min(e, t1_ns)
            if ce <= cs:
                continue
            if mods:
                name = "%s/%s" % (_module_at(mods, s), stable(name))
            # self time (children excluded), in the share inside the window
            op_s[name] = op_s.get(name, 0.0) + own * (ce - cs) / (e - s) * 1e-9
            clipped.append((cs, ce))
        b, merged = _union(clipped)
        busy.append(b)
        prev = t0_ns
        for s, e in merged + [(t1_ns, t1_ns)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    n_dev = max(1, len(dev_ops))
    starts = [h[0] for h in host]
    labelled = [((e - s) * 1e-9, _host_label(host, starts, (s + e) / 2))
                for s, e in gaps]
    return Reduced(window_s=window * 1e-9, busy_s=sum(busy) / n_dev * 1e-9,
                   op_s=op_s, gaps=labelled)


def _self_ns(ops):
    """Each op's duration less the ops nested inside it (a loop holds its
    body's ops on the same line), for ``ops`` of one device."""
    ops.sort(key=lambda o: (o[0], -o[1]))
    own = [e - s for s, e, _ in ops]
    stack = []                          # indices of open enclosing ops
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _module_at(mods, t):
    lo, hi = 0, len(mods)
    while lo < hi:                      # last module starting at or before t
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][1] >= t:
        return mods[lo - 1][2]
    return "?"


def _host_label(host, starts, t, lookback: int = 4096):
    """The innermost span of the driving thread that covers time ``t``
    (spans on one thread nest, so it is the latest-starting one that has
    not ended): what the host was doing while the device sat idle."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - lookback, -1), -1):
        s, e, name = host[j]
        if e >= t and name != "bench.window":
            return name
    return "(no host span)"
