"""The delta-rule kernels compiled by the chip's own compiler for a TPU v5e that
is described and not attached, at the hybrid cell's widths: what Mosaic refuses
(a slice off the tiling, too much VMEM) the interpreter accepts, so the CPU
tests of tests/test_hybrid_layers.py cannot see it. Nothing runs. Every test
that describes a topology lives in this one file (one worker loads the TPU's
library, inside a fixture, never at import)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import delta_rule_kernel


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An executable for a described device is written to the cache and cannot be read back.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def operands(one_chip, rows, tokens, heads, d):
    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (rows, tokens, heads, d)
    return (of(wide, jnp.bfloat16),) * 3 + (of(wide, jnp.float32), of((rows, tokens, heads), jnp.float32))


# the cell's call (32 heads of 128, 1024 tokens) at two rows, and one head group of two heads
@pytest.mark.parametrize("heads, tokens", [(32, 1024), (2, 128)], ids=["cell", "two-heads"])
def test_the_delta_rule_kernels_compile_for_a_v5e(one_chip, heads, tokens):
    args = operands(one_chip, 2, tokens, heads, 128)
    forward = jax.jit(delta_rule_kernel).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1

    def loss(*a):
        return (delta_rule_kernel(*a).astype(jnp.float32) ** 2).sum()

    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2  # kda_fwd writing the states, kda_bwd
    assert [x.shape for x in both.out_info] == [a.shape for a in args]
