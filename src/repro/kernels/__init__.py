# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
# (This __init__ runs for the pure-jnp reference imports too: keep
# pallas imports out of it.)
import jax


def pallas_by_default(mesh=None) -> bool:
    """Whether a program takes its Pallas kernels when the caller does not
    say: on a TPU, for a program over one device.

    Mosaic kernels cannot be partitioned by XLA, so a program over a
    multi-device ``mesh`` takes the XLA paths. On the CPU the kernels run
    only in interpret mode, which the tests ask for explicitly."""
    one_device = mesh is None or mesh.devices.size == 1
    return jax.default_backend() == "tpu" and one_device
