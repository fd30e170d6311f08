"""The main-path Pallas kernels compile for a described v5e chip at
llama3.2-1b widths (d_model 2048, 32 query / 8 KV heads, head_dim 64,
vocab 128256), and decode attention at the benchmark cell's widths too.

Nothing runs: the TPU compiler, installed on the host, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(block shapes off the tiling, scoped VMEM over budget, primitives Mosaic
cannot lower) — faults interpret mode never shows. The topology is
described inside a fixture, never while a module is imported: only the
worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, KV, HD, D, V = 32, 8, 64, 2048, 128256
BF16, F32, I32, U32 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise log under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    """Lower and compile ``fn`` for the described chip; the program must
    hold a Mosaic kernel (compiled, not interpreted)."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attn(one_chip):
    from repro.kernels.flash_attn import ops
    _compile(one_chip,
             lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
             ((8, 256, H, HD), BF16), ((8, 256, KV, HD), BF16),
             ((8, 256, KV, HD), BF16))


@pytest.mark.parametrize("b,l,h,kv,hd", [
    (48, 320, H, KV, HD),        # llama3.2-1b: 128-lane blocks of two heads
    (64, 2496, 12, 2, 128),      # r1-distill-qwen-1.5b cell: ragged last block
])
def test_decode_attn(one_chip, b, l, h, kv, hd):
    from repro.kernels.decode_attn import ops
    _compile(one_chip,
             lambda q, k, v, n: ops.decode_attention(q, k, v, n,
                                                     interpret=False),
             ((b, 1, h, hd), BF16), ((b, l, kv * hd), BF16),
             ((b, l, kv * hd), BF16), ((b,), I32))


def test_paged_decode_attn(one_chip):
    from repro.kernels.paged_decode_attn import ops
    pages, page_size, max_pages = 48 * 20, 16, 20
    _compile(one_chip,
             lambda q, k, v, bt, n: ops.paged_decode_attention(
                 q, k, v, bt, page_size, n, interpret=False),
             ((48, 1, H, HD), BF16), ((pages, page_size, KV, HD), BF16),
             ((pages, page_size, KV, HD), BF16), ((48, max_pages), I32),
             ((48,), I32))


def _is_grpo_loss(h, w, t, b, a):
    from repro.kernels.fused_is_grpo import ops
    out = ops.fused_is_grpo(h, w, t, b, a, impl="pallas", interpret=False)
    return out[0].sum() + out[3].sum()


_IS_GRPO_ARGS = (((2, 64, D), BF16), ((D, V), F32), ((2, 64), I32),
                 ((2, 64), F32), ((2, 64), F32))


def test_fused_is_grpo_forward(one_chip):
    _compile(one_chip, _is_grpo_loss, *_IS_GRPO_ARGS)


def test_fused_is_grpo_backward(one_chip):
    # the dw kernel's (d, block_v) blocks exceed the default scoped VMEM
    # at d=2048 unless the kernel raises its limit
    _compile(one_chip, jax.grad(_is_grpo_loss, argnums=(0, 1)),
             *_IS_GRPO_ARGS)


@pytest.mark.parametrize("temperature,top_p,top_k", [
    (1.0, 1.0, -1), (1.0, 1.0, 50), (1.0, 0.9, -1), (0.8, 0.95, 40)])
def test_fused_sample(one_chip, temperature, top_p, top_k):
    from repro.kernels.fused_sample import ops
    _compile(one_chip,
             lambda k, lg: ops.fused_sample_rows(
                 k, lg, temperature=temperature, top_p=top_p, top_k=top_k,
                 interpret=False),
             ((48, 2), U32), ((48, V), F32))
