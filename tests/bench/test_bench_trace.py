"""The trace reduction on a small trace recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace


def test_union_and_gaps():
    total, merged = trace._union([(0, 4), (2, 6), (10, 12)])
    assert total == 8 and merged == [(0, 6), (10, 12)]
    assert trace.stable("fusion.123") == "fusion"
    assert trace.stable("jit__decode_chunk(42)") == "jit__decode_chunk"
    # TPU op events carry the HLO instruction text
    assert trace.stable("%pad.31.clone.2 = bf16[2,64]{1,0} pad(%x)") == "pad"
    assert trace.stable("%transpose_jvp___.3 = f32[8] custom-call()") == \
        "transpose_jvp___"


def test_self_time_of_nested_ops():
    # a loop (0-100) holding two body ops and a later leaf op
    ops = [(0, 100, "while"), (10, 30, "dot"), (40, 90, "fusion"),
           (120, 130, "copy")]
    assert trace._self_ns(ops) == [30, 20, 50, 10]


def test_reduce_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.prompt"):
                sum(range(200000))          # host work, device idle
    jax.profiler.stop_trace()
    red = trace.reduce(trace.find_xplane(str(tmp_path)))
    assert 0 < red.busy_s <= red.window_s
    assert red.time_of(r"^jit__lambda/") == pytest.approx(
        sum(red.op_s.values()))
    labels = {label for _, label in red.gaps}
    assert "bench.prompt" in labels
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][1] > 0
