"""The delta-rule kernels, latent attention's kernel pair, windowed chunk
attention's, the state-space recurrence's and the short convolution's compiled by the chip's own compiler
for a TPU v5e that is described and not attached, at the cells' widths: what
Mosaic refuses (a slice off the tiling, too much VMEM) the interpreter accepts,
so the CPU tests of tests/test_hybrid_layers.py and tests/test_nemotron_tower.py
cannot see it. Nothing runs. Every test
that describes a topology lives in this one file (one worker loads the TPU's
library, inside a fixture, never at import)."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import delta_rule_kernel
from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import latent_attention_kernel, latent_attention_plan


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


MOSAIC_VMEM_LIMIT = 16 * 2**20  # what a Mosaic kernel may use on a v5e unless it asks for more (these do not)


def kernel_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, through the bodies of jits and custom rules."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from kernel_calls(inner)


def vmem_asked(jaxpr) -> list:
    """Per ``pallas_call`` of a jaxpr (name, bytes): every block twice (the
    pipeline holds the next one) and the scratch."""
    found = []
    for eqn in kernel_calls(jaxpr):
        mapping = eqn.params["grid_mapping"]
        blocks = sum(
            math.prod(getattr(x, "block_size", None) or 1 for x in m.block_shape) * m.array_aval.dtype.itemsize
            for m in mapping.block_mappings)
        scratch = eqn.params["jaxpr"].invars[len(eqn.params["jaxpr"].invars) - mapping.num_scratch_operands:]
        found.append((eqn.params["name"], 2 * blocks + sum(
            math.prod(v.aval.shape) * v.aval.dtype.itemsize for v in scratch)))
    return found


def block_shapes(jaxpr) -> dict:
    """Per ``pallas_call`` of a jaxpr, by name: the shapes of its blocks."""
    return {
        eqn.params["name"]: [tuple(getattr(x, "block_size", None) or 1 for x in m.block_shape)
                             for m in eqn.params["grid_mapping"].block_mappings]
        for eqn in kernel_calls(jaxpr)}


# The mixer's call at the cell's shape (two rows of it): raw q and k, o over its head's rms, the
# heads on the lanes, four heads a program; the norms run on the tile the program holds.
@pytest.mark.parametrize("heads, tokens", [(32, 1024), (2, 128)], ids=["cell", "two-heads"])
def test_the_kernels_with_the_norms_inside_compile_for_a_v5e(one_chip, heads, tokens):
    from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import heads_per_program

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (2, tokens, heads * 128)
    args = (of(wide, jnp.bfloat16),) * 3 + (of(wide, jnp.float32), of((2, tokens, heads), jnp.float32))
    assert heads_per_program(heads) == (4 if heads == 32 else 2)

    def normed(q, k, v, g, beta):
        return delta_rule_kernel(q, k, v, g, beta, qk_norm=True, o_eps=1e-5)

    def loss(*a):
        return (normed(*a).astype(jnp.float32) ** 2).sum()

    forward = jax.jit(normed).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    grads = jax.grad(loss, argnums=tuple(range(5)))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2  # kda_fwd writing the states, kda_bwd
    assert [x.shape for x in both.out_info] == [a.shape for a in args]
    traced = jax.make_jaxpr(grads)(*args).jaxpr
    asked = dict(vmem_asked(traced))
    assert set(asked) == {"kda_fwd", "kda_bwd"} and max(asked.values()) < MOSAIC_VMEM_LIMIT // 2, asked
    # what the forward solved and keeps for the backward (T, A / beta, P: a program's heads side by side on the lanes)
    # is among the blocks counted: written by the one kernel, read by the other, two copies of each
    per = heads_per_program(heads)
    solved = 2 * 64 * per * 64 * (4 + 4 + 2)
    for name, blocks in block_shapes(traced).items():
        assert blocks.count((1, 1, 1, 64, per * 64)) == 3, (name, blocks)
        assert asked[name] > solved


# Latent attention's core as the mixer calls it, the heads on the lanes: the GLM cell's (20 heads of 256 / 256 at 4096
# tokens), Kimi's (32 heads of 192 / 128 zero-padded to 256 / 128 at 1024), and a length that is padded to its block.
@pytest.mark.parametrize("heads, tokens, dqk, dv", [(20, 4096, 256, 256), (32, 1024, 256, 128), (2, 700, 128, 128)],
                         ids=["glm-cell", "kimi-cell", "padded-length"])
def test_latent_attentions_kernel_pair_compiles_for_a_v5e(one_chip, heads, tokens, dqk, dv):
    def of(d):
        return jax.ShapeDtypeStruct((2, tokens, heads * d), jnp.bfloat16, sharding=one_chip)

    args = (of(dqk), of(dqk), of(dv))
    core = lambda q, k, v: latent_attention_kernel(q, k, v, head_dims=(dqk, dv))  # noqa: E731
    forward = jax.jit(core).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1

    def loss(*a):
        return (core(*a).astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2  # mla_attn_fwd leaving the log-sum-exp column, mla_attn_bwd
    assert [x.shape for x in both.out_info] == [a.shape for a in args]
    asked = dict(vmem_asked(jax.make_jaxpr(grads)(*args).jaxpr))
    plan = latent_attention_plan(tokens, dqk, dv)
    assert set(asked) == {"mla_attn_fwd", "mla_attn_bwd"} and max(asked.values()) <= plan["vmem_bytes"], (asked, plan)


# The same pair under grouped heads, as ``Attention`` calls it: the one-sub-layer cell's call (32 query heads over 2 key /
# value heads of 128 at 4096 tokens: k and v read where they lie, nothing repeated) and a small one padded to its block.
@pytest.mark.parametrize("heads, kv_heads, tokens", [(32, 2, 4096), (4, 2, 300)], ids=["nemotron-cell", "padded-length"])
def test_the_pair_under_grouped_heads_compiles_for_a_v5e(one_chip, heads, kv_heads, tokens):
    def of(h):
        return jax.ShapeDtypeStruct((2, tokens, h * 128), jnp.bfloat16, sharding=one_chip)

    args = (of(heads), of(kv_heads), of(kv_heads))
    core = lambda q, k, v: latent_attention_kernel(q, k, v, head_dims=(128, 128), kv_heads=kv_heads)  # noqa: E731
    forward = jax.jit(core).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1 and forward.out_info.shape == args[0].shape

    def loss(*a):
        return (core(*a).astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2
    assert [x.shape for x in both.out_info] == [a.shape for a in args]  # a group's dk and dv summed to its one head
    asked = dict(vmem_asked(jax.make_jaxpr(grads)(*args).jaxpr))
    plan = latent_attention_plan(tokens, 128, 128)
    assert set(asked) == {"mla_attn_fwd", "mla_attn_bwd"} and max(asked.values()) <= plan["vmem_bytes"], (asked, plan)


# The pair with a window (a causal band), as a window layer's ``Attention`` calls it: the window / full attention cell's
# call (32 query heads over 4 key / value heads of 128 at 8192 tokens, 2048 keys a query: 70 of the 136 block pairs), a
# window that is no whole number of blocks over a padded length, and a window inside one block.
@pytest.mark.parametrize("heads, kv_heads, tokens, window", [(32, 4, 8192, 2048), (4, 2, 1300, 700), (4, 4, 1024, 100)],
                         ids=["trinity-cell", "odd-window-padded-length", "window-inside-a-block"])
def test_the_pair_with_a_window_compiles_for_a_v5e(one_chip, heads, kv_heads, tokens, window):
    from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import visited_block_pairs

    def of(h):
        return jax.ShapeDtypeStruct((2, tokens, h * 128), jnp.bfloat16, sharding=one_chip)

    args = (of(heads), of(kv_heads), of(kv_heads))
    core = lambda q, k, v: latent_attention_kernel(q, k, v, head_dims=(128, 128), kv_heads=kv_heads, window=window)  # noqa: E731
    forward = jax.jit(core).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1 and forward.out_info.shape == args[0].shape

    def loss(*a):
        return (core(*a).astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2
    assert [x.shape for x in both.out_info] == [a.shape for a in args]
    asked = dict(vmem_asked(jax.make_jaxpr(grads)(*args).jaxpr))
    plan = latent_attention_plan(tokens, 128, 128)
    assert set(asked) == {"mla_attn_fwd", "mla_attn_bwd"} and max(asked.values()) <= plan["vmem_bytes"], (asked, plan)
    if tokens == 8192:  # the band's block pairs alone: what the cost estimate tells the scheduler
        assert (visited_block_pairs(8192, 512, 2048), visited_block_pairs(8192, 512)) == (70, 136)


# Windowed chunk attention's core as the mixer calls it, the heads on the lanes: the cell's call (32 heads of 128, 8192
# tokens in windows of 2048, chunks of 16: 512 summaries a sequence) and a small one (two windows of 256).
@pytest.mark.parametrize("heads, tokens, window", [(32, 8192, 2048), (2, 512, 256)], ids=["evabyte-cell", "two-windows"])
def test_windowed_chunk_attentions_kernel_pair_compiles_for_a_v5e(one_chip, heads, tokens, window):
    from distributed_sigmoid_loss_tpu.ops.pallas_eva_attention import eva_attention_kernel, eva_attention_plan

    def of(length):
        return jax.ShapeDtypeStruct((2, length, heads * 128), jnp.bfloat16, sharding=one_chip)

    args = (of(tokens),) * 3 + (of(tokens // 16),) * 2
    assert eva_attention_plan(tokens, window, 16, 128)["windows"] == tokens // window
    core = lambda *a: eva_attention_kernel(*a, head_dim=128, window=window)  # noqa: E731
    forward = jax.jit(core).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1

    def loss(*a):
        return (core(*a).astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=tuple(range(5)))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2  # eva_attn_fwd leaving the log-sum-exp column, eva_attn_bwd
    assert [x.shape for x in both.out_info] == [a.shape for a in args]
    asked = dict(vmem_asked(jax.make_jaxpr(grads)(*args).jaxpr))
    assert set(asked) == {"eva_attn_fwd", "eva_attn_bwd"} and max(asked.values()) < 32 * 2**20, asked


# The state-space recurrence's pair as ``ssm_scan`` calls it, the heads and groups on the lanes: the cell's call (4 rows of
# 4096 tokens, 64 heads of 64 in 8 groups, state 128: a program holds a chunk of 128 tokens of one group's 512 lanes) and a
# two-chunk call of one group of two heads (its dt blocks are two lanes and two sublanes wide).
@pytest.mark.parametrize("rows, tokens, heads, groups", [(4, 4096, 64, 8), (2, 256, 2, 1)], ids=["nemotron-cell", "two-chunks"])
def test_the_state_space_kernel_pair_compiles_for_a_v5e(one_chip, rows, tokens, heads, groups):
    from distributed_sigmoid_loss_tpu.ops.pallas_ssm import ssd_kernel

    def of(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((rows, tokens, width), dtype, sharding=one_chip)

    per_head = jax.ShapeDtypeStruct((heads,), jnp.float32, sharding=one_chip)
    args = (of(heads * 64), of(groups * 128), of(groups * 128), of(heads, jnp.float32), per_head, per_head)
    core = lambda *a: ssd_kernel(*a, heads=heads, groups=groups, chunk=128)  # noqa: E731
    forward = jax.jit(core).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    assert (forward.out_info.shape, forward.out_info.dtype) == ((rows, tokens, heads * 64), jnp.float32)

    def loss(*a):
        return (core(*a) ** 2).sum()

    grads = jax.grad(loss, argnums=tuple(range(6)))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2  # ssd_fwd writing each chunk's incoming state, ssd_bwd
    assert [(x.shape, x.dtype) for x in both.out_info] == [(a.shape, a.dtype) for a in args]
    asked = dict(vmem_asked(jax.make_jaxpr(grads)(*args).jaxpr))
    assert set(asked) == {"ssd_fwd", "ssd_bwd"} and max(asked.values()) < MOSAIC_VMEM_LIMIT // 2, asked


# The mixers' short convolution with its bias and silu as ``short_conv_silu`` calls it: the delta-rule cell's branches (16
# rows of 1024 tokens, 4096 channels, no bias: a program holds the whole sequence of 512 lanes), the state-space cell's x' and
# its B and C (4 rows of 4096 tokens, 4096 and 1024 channels, with their bias: 2048 tokens of 512 lanes, so a block's
# neighbour rows come in as blocks of their own), and a small odd call (three 16-row tiles of 384 lanes, two taps). The
# sublane rotations that make the shifted copies and the tiles' dynamic starts are what the interpreter would accept anyway.
@pytest.mark.parametrize("shape, taps, bias, tile", [
    ((16, 1024, 4096), 4, False, (1024, 512)), ((4, 4096, 4096), 4, True, (2048, 512)), ((4, 4096, 1024), 4, True, (2048, 512)),
    ((2, 48, 384), 2, True, (48, 384))], ids=["kimi-branch", "nemotron-x", "nemotron-b-c", "small-odd"])
def test_the_short_convolutions_kernel_pair_compiles_for_a_v5e(one_chip, shape, taps, bias, tile):
    from distributed_sigmoid_loss_tpu.ops.pallas_short_conv import _VMEM_LIMIT, short_conv_kernel, short_conv_plan

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plan = short_conv_plan(shape[1], shape[2], taps)
    assert (plan["tokens"], plan["channels"]) == tile
    args = (of(shape, jnp.bfloat16), of((taps, shape[2]), jnp.float32)) + ((of(shape[2:], jnp.float32),) if bias else ())
    core = lambda x, w, b=None: short_conv_kernel(x, w, b)  # noqa: E731
    forward = jax.jit(core).lower(*args).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    assert (forward.out_info.shape, forward.out_info.dtype) == (shape, jnp.bfloat16)

    def loss(*a):
        return (core(*a).astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=tuple(range(len(args))))
    both = jax.jit(grads).lower(*args).compile()
    assert both.as_text().count("tpu_custom_call") == 2  # short_conv_fwd for the loss's y, short_conv_bwd
    assert [(x.shape, x.dtype) for x in both.out_info] == [(a.shape, a.dtype) for a in args]
    traced = jax.make_jaxpr(grads)(*args).jaxpr
    asked = dict(vmem_asked(traced))
    assert set(asked) == {"short_conv_fwd", "short_conv_bwd"} and max(asked.values()) < _VMEM_LIMIT // 2, asked
    # x, its sixteen rows before the block and after it, dy and its sixteen after; the taps (and the bias); dx and the sums
    blocks = block_shapes(traced)["short_conv_bwd"]
    assert blocks == [(1, *tile), (1, 16, tile[1]), (1, 16, tile[1]), (1, *tile), (1, 16, tile[1]), (taps, tile[1]),
                      *([(1, tile[1])] if bias else []), (1, *tile), (1, 8, tile[1])]


# The routed layers' two loops (models/moe.py routed_experts and its backward) at the three cells' calls: 16384 tokens a
# microbatch, 8 held experts, blocks of 512 rows. What the tile-aligned sums rest on, read off the chip's own compiler:
# the float32 sums y and dx are scattered into as (T, n, 8, 128) and never as (T, d), nothing copies a carried sum,
# d_weight is written by slices, and one block's scatter-add moves under half the bytes of the 2-D form's (93.6 / 80.2 /
# 71.3 MB at 2688 / 2304 / 2048: eight tokens' rows to add one), under a limit set from what the new form reads (43.3 /
# 42.5 / 21.0 MB: the scatter, and the block's rows padded and turned into tiles).
@pytest.mark.parametrize("d, hidden, kind, scatter_mb", [(2048, 1536, "swiglu", 24), (2304, 1024, "swiglu", 47), (2688, 1856, "relu2", 48)],
                         ids=["glm-cell", "kimi-cell", "nemotron-cell"])
def test_the_routed_loops_scatter_into_whole_tiles_on_a_v5e(one_chip, d, hidden, kind, scatter_mb):
    from distributed_sigmoid_loss_tpu.models import moe

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tokens, held, top, block = 16384, 8, 6, 512
    n = moe._row_tiles(d)[0]
    shapes = ([(held, d, hidden)] if kind == "swiglu" else []) + [(held, d, hidden), (held, hidden, d)]
    args = (of((tokens, d), jnp.bfloat16), tuple(of(s, jnp.float32) for s in shapes), of((tokens * top,), jnp.int32),
            of((tokens * top,), jnp.float32), of((held,), jnp.int32), of((held,), jnp.int32))

    def loss(x, stacks, token, row_weight, starts, counts):
        return (moe.routed_experts(x, stacks, token, row_weight, starts, counts, block)[0].astype(jnp.float32) ** 2).sum()

    both = jax.jit(jax.grad(loss, argnums=(0, 1, 3))).lower(*args).compile()
    text = both.as_text()
    scattered = re.findall(r"= (f32\[[\d,]+\])\S* scatter\(", text)
    assert scattered == [f"f32[{tokens},{n},8,128]"] * 2, scattered  # y's and dx's; no (T, d) sum, no 1-D d_weight scatter
    assert not re.findall(rf"= f32\[{tokens},[\d,]+\]\S* copy\(", text)  # no copy of a carried sum, in a body or outside
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(both.out_info)] == [(a.shape, a.dtype) for a in jax.tree.leaves((args[:2], args[3]))]

    rows = of((block, d), jnp.float32)
    one = jax.jit(moe._scatter_add_rows, donate_argnums=0).lower(of((tokens, n, 8, 128), jnp.float32), of((block,), jnp.int32), rows)
    flat = jax.jit(lambda y, to, r: y.at[to].add(r, mode="drop"), donate_argnums=0).lower(of((tokens, d), jnp.float32), of((block,), jnp.int32), rows)
    moved, moved_2d = (c.compile().cost_analysis()["bytes accessed"] for c in (one, flat))
    assert moved < scatter_mb * 1e6 and moved < 0.55 * moved_2d, (moved, moved_2d)
