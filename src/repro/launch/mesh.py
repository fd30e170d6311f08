"""Production mesh construction.

Called as a FUNCTION so importing this module never touches jax device
state. The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import; smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

# TPU v5e hardware constants used by the roofline analysis (benchmarks/).
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link


def make_mesh(shape, axes, *, devices=None):
    """Mesh with ``Auto`` axes over ``devices`` (default: all visible).

    The model code shards through ``with_sharding_constraint`` and leaves
    the rest to XLA's propagation, which needs ``Auto`` axes;
    ``jax.make_mesh`` defaults to ``Explicit`` ones. Every mesh in the
    repository is built here."""
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_gqa_serve_mesh(*, data: int = 4, kv_groups: int = 8,
                        within: int = 8):
    """Serve-optimised 3D view of the same 256 chips for GQA models whose
    kv-head count doesn't divide a flat TP axis: attention projections and
    the KV cache's head dim shard over "kvg" (= num_kv_heads), the cache
    LENGTH and the MLP's second factor shard over "model", batch over
    "data". See EXPERIMENTS.md §Perf hillclimb C."""
    return make_mesh((data, kv_groups, within), ("data", "kvg", "model"))


def make_cpu_mesh():
    """One-device mesh on the first device, with the production axis
    names: the trainer's layout when a single device is visible."""
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def make_disaggregated_meshes(train_shape=None, rollout_shape=None):
    """Disjoint train and rollout meshes over the visible devices: the
    first ``prod(train_shape)`` devices train, the next
    ``prod(rollout_shape)`` serve rollout. With disjoint device sets the
    ParamStore reshard between the two layouts is a ``jax.device_put``
    (ICI/DCN weight transfer) instead of a same-device relayout — the
    Laminar-style separated rollout/train deployment.

    By default the visible devices split in half: training shards over
    ``data`` (FSDP), rollout over ``model`` (tensor parallel, the
    ``serve_tp_only`` layout the weights are resharded to)."""
    devs = jax.devices()
    if train_shape is None or rollout_shape is None:
        half = len(devs) // 2
        if half < 1:
            raise ValueError(
                "disaggregated meshes need at least 2 visible devices, "
                f"have {len(devs)}")
        train_shape, rollout_shape = (half, 1), (1, half)
    nt = int(np.prod(train_shape))
    nr = int(np.prod(rollout_shape))
    if nt + nr > len(devs):
        raise ValueError(
            f"disaggregated meshes need {nt}+{nr} devices, have "
            f"{len(devs)} — shrink the shapes or raise "
            "--xla_force_host_platform_device_count")
    train = make_mesh(train_shape, ("data", "model"), devices=devs[:nt])
    rollout = make_mesh(rollout_shape, ("data", "model"),
                        devices=devs[nt:nt + nr])
    return train, rollout


def data_axes(mesh) -> tuple:
    """The axes a global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
