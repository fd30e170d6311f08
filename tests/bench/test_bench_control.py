"""The control (the float32 reference computed in float8, in the system's
place) fails the first cell's limits, and the system passes them, on three
seeds at a tiny size on the CPU. On the chip the same procedure runs at the
cell's own size (``bench/control.py``)."""
from bench import control, spec
from bench_tiny import make_root


def test_control_fails_system_passes(tmp_path):
    first = spec.load_benchmark()["workloads"][0]["name"]
    limits = spec.load_limits(first)
    root = make_root(tmp_path)
    for seed in (1, 2, 3):
        system, ctl = control.one_seed("tiny.mix", seed, control=True,
                                       root=root)
        assert all(system[k] <= limits[k] for k in limits), system
        assert any(ctl[k] > limits[k] for k in limits), ctl
