"""Per-rule fixture tests for the jaxlint group (JAX1xx): one known-bad
and one known-good snippet per rule, asserting exact finding/no-finding."""
import textwrap

from repro.analysis.core import ModuleCtx, all_rules


def findings(src, rule, path="src/repro/core/mod.py"):
    ctx = ModuleCtx(path, textwrap.dedent(src))
    r = all_rules()[rule]()
    assert r.applies_to(path)
    return [f for f in r.check(ctx) if f.rule == rule]


# ---------------------------------------------------------------------- 101
def test_jax101_bad_python_branch_on_tracer():
    src = """
    import jax

    @jax.jit
    def f(x):
        if x > 0:
            return x
        return -x
    """
    fs = findings(src, "JAX101")
    assert len(fs) == 1 and "control flow" in fs[0].message


def test_jax101_bad_float_and_item():
    src = """
    import jax

    @jax.jit
    def f(x):
        a = float(x.sum())
        b = x.mean().item()
        return a + b
    """
    msgs = [f.message for f in findings(src, "JAX101")]
    assert any("float()" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


def test_jax101_bad_numpy_on_tracer_in_scan_body():
    src = """
    import jax
    import numpy as np

    def step(carry, x):
        return carry, np.abs(x)

    def run(xs):
        return jax.lax.scan(step, 0.0, xs)
    """
    fs = findings(src, "JAX101")
    assert len(fs) == 1 and "numpy call np.abs" in fs[0].message


def test_jax101_good_shape_branch_and_nested_def():
    # .shape reads are static; nested-def params are NOT treated as traced
    # (the kv_cache `upd(axis, ...)` closure idiom); static_argnames are
    # excluded from taint
    src = """
    import functools
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("mode",))
    def f(x, mode):
        B, T = x.shape
        if B > 2 and mode == "wide":
            x = x * 2
        def upd(axis, v):
            if axis == 0:
                return v + 1
            return v
        return jnp.where(x > 0, x, upd(0, x))
    """
    assert findings(src, "JAX101") == []


# ---------------------------------------------------------------------- 102
def test_jax102_bad_key_reused():
    src = """
    import jax

    def make():
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (2,))
        b = jax.random.normal(key, (2,))
        return a, b
    """
    fs = findings(src, "JAX102")
    assert len(fs) == 1 and "'key'" in fs[0].message


def test_jax102_bad_loop_never_refreshes():
    src = """
    import jax

    def make(key):
        out = []
        for i in range(4):
            out.append(jax.random.normal(key, (2,)))
        return out
    """
    assert len(findings(src, "JAX102")) >= 1


def test_jax102_good_split_per_consumption():
    src = """
    import jax

    def make():
        key = jax.random.PRNGKey(0)
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (2,))
        b = jax.random.normal(k2, (2,))
        for i in range(4):
            key, k = jax.random.split(key)
            a = a + jax.random.normal(k, (2,))
        return a, b
    """
    assert findings(src, "JAX102") == []


def test_jax102_good_branches_are_independent():
    src = """
    import jax

    def pick(key, flag):
        if flag:
            return jax.random.normal(key, (2,))
        else:
            return jax.random.uniform(key, (2,))
    """
    assert findings(src, "JAX102") == []


# ---------------------------------------------------------------------- 103
def test_jax103_bad_use_after_donation():
    src = """
    import jax

    f = jax.jit(lambda c: c * 2, donate_argnums=(0,))

    def g(cache):
        out = f(cache)
        return out + cache.sum()
    """
    fs = findings(src, "JAX103")
    assert len(fs) == 1 and "'cache'" in fs[0].message


def test_jax103_good_same_statement_rebind():
    # the engine idiom: the donated name is rebound from the call result
    src = """
    import functools
    import jax

    class Eng:
        def __init__(self):
            self._step = jax.jit(lambda p, c: (c, p),
                                 donate_argnums=(1,))

        def run(self, params):
            self.cache, ys = self._step(params, self.cache)
            self.cache, ys = self._step(params, self.cache)
            return ys
    """
    assert findings(src, "JAX103") == []


# ---------------------------------------------------------------------- 104
def test_jax104_bad_timing_without_sync():
    src = """
    import time
    import jax

    f = jax.jit(lambda x: x * 2)

    def bench(x):
        t0 = time.perf_counter()
        y = f(x)
        return time.perf_counter() - t0
    """
    fs = findings(src, "JAX104")
    assert len(fs) == 1 and "f()" in fs[0].message


def test_jax104_bad_tuple_assigned_stamp():
    src = """
    import time
    import jax

    def bench(eng, params):
        t0, n = time.perf_counter(), 0
        eng.collect(params)
        dt = time.perf_counter() - t0
        return dt, n
    """
    assert len(findings(src, "JAX104")) == 1


def test_jax104_good_block_until_ready():
    src = """
    import time
    import jax

    f = jax.jit(lambda x: x * 2)

    def bench(x):
        t0 = time.perf_counter()
        y = f(x)
        jax.block_until_ready(y)
        return time.perf_counter() - t0
    """
    assert findings(src, "JAX104") == []


def test_jax104_good_interval_without_dispatch():
    src = """
    import time

    def bench(rows):
        t0 = time.perf_counter()
        total = sum(len(r) for r in rows)
        return time.perf_counter() - t0, total
    """
    assert findings(src, "JAX104") == []


def test_jax104_bad_span_closes_before_sync():
    src = """
    import jax
    from repro.common.spans import Span

    f = jax.jit(lambda x: x * 2)

    def step(x, stats):
        with Span("engine.decode", stats, "decode_time"):
            y = f(x)
        return jax.device_get(y)
    """
    fs = findings(src, "JAX104")
    assert len(fs) == 1 and "span closes" in fs[0].message


def test_jax104_good_span_syncs_inside():
    src = """
    import jax
    from repro.common.spans import Span

    f = jax.jit(lambda x: x * 2)

    def step(x, stats):
        with Span("engine.decode", stats, "decode_time"):
            y = f(x)
            y = jax.device_get(y)
        with Span("engine.replay", stats, "replay_time"):
            n = sum(int(v) for v in y)
        return n
    """
    assert findings(src, "JAX104") == []
