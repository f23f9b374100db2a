"""A text tower that mixes window and full attention layers in one stack
(``TextConfig.attn_windows``): a causal band with rotary positions beside full
layers with none (``rope_layers="window"``), grouped heads with a per-head norm on
q and k and a sigmoid gate on the heads' outputs (``attn_qk_norm``, ``attn_gate``),
sandwich norms around a dense or a routed feed-forward part, the embedding scaled
(``embed_scale``), against its plain reference (``benchmark/reference_trinity.py``):
the kernel pair with a window against the masked dense core, the counts of the
band, the layer, the eight shares of a routed layer, the tower, the train step,
the benchmark's job with its planted faults, the configuration and the count, the
defaults left alone and the refusals by name."""

import dataclasses
import json
import os
import sys
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.models.moe import MOE_ROUTE_SCOPE, SELECT_BIAS, SharedExpertMoe
from distributed_sigmoid_loss_tpu.models.text import TextTransformer, layer_specs
from distributed_sigmoid_loss_tpu.models.transformer import (
    ATTN_CORE_SCOPE,
    ATTN_GATE_SCOPE,
    WINDOW_CORE_SCOPE,
    Attention,
    Block,
    BlockStyle,
    attention_core,
)
from distributed_sigmoid_loss_tpu.ops import pallas_latent_attention
from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import (
    admitted_pairs,
    latent_attention_kernel,
    visited_block_pairs,
)
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import sigmoid_loss
from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention
from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig, TextConfig, ViTConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import flops_trinity  # noqa: E402
import harness  # noqa: E402
import reference_trinity  # noqa: E402

CELL = "trinity-b16-p8-s8192"
# The cell's stack at a toy size: a dense layer and four routed ones, window (8 keys of 24), window, full, window,
# window; 4 query heads over 2 key / value heads of 16 (4 x 16 = 64 over a 32-wide stream); 16 experts of which 4 are
# held, top-3, a shared expert.
TRINITY = dict(
    context_length=24, depth=5, causal=True, pool="last", norm="rmsnorm", norm_eps=1e-5, sandwich_norm=True, mlp="swiglu",
    use_bias=False, pos="rope", rope_theta=10000.0, rope_layers="window", attn_windows=(8, 8, 0, 8, 8), attn_qk_norm=True,
    attn_gate=True, embed_scale=32**0.5, num_heads=4, num_kv_heads=2, head_dim=16, mlp_ratio=3, leading_dense_layers=1,
    moe_experts=16, moe_num_selected=3, moe_router="sigmoid", moe_route_scale=2.826, moe_shared_experts=1,
    moe_hidden=24, moe_experts_held=4,
)


def tiny_trinity(**over) -> SigLIPConfig:
    text = dataclasses.replace(TextConfig.tiny_test(), **{**TRINITY, **over})
    return SigLIPConfig(vision=ViTConfig.tiny_test(), text=text)


def tiny_batch(n=4, s=24):
    images = jax.random.normal(jax.random.key(1), (n, 16, 16, 3), jnp.float32)
    tokens = jax.random.randint(jax.random.key(2), (n, s), 0, 64)
    return images, tokens


def moved(params, scale=0.05):
    """Every leaf away from its initial value (norm scales start at one, the
    selection bias at zero): a dropped scale or a dropped leaf then shows."""
    leaves, tree = jax.tree.flatten(nn.meta.unbox(params))
    keys = jax.random.split(jax.random.key(3), len(leaves))
    return jax.tree.unflatten(tree, [
        x + scale * jax.random.normal(k, x.shape, x.dtype) if x.ndim else x for x, k in zip(leaves, keys)
    ])


def brute_force_mask(s, window):
    t = np.arange(s)
    mask = t[None, :] <= t[:, None]
    return mask & (t[None, :] > t[:, None] - window) if window else mask


# -- (a) the kernel pair with a window against the masked dense core ---------------------------

# tokens, window at blocks of 128: a whole number of blocks, one that is not, a sequence that is not whole blocks, a
# window of 1 (a token and itself), a window inside one block, a window of at least the sequence
BANDS = {"whole-blocks": (512, 256), "odd-window": (512, 200), "padded-length": (400, 130), "window-of-1": (384, 1),
         "inside-a-block": (384, 100), "whole-sequence": (384, 384), "past-the-sequence": (384, 600)}


@pytest.fixture(scope="module")
def banded_pairs():
    """Each band through the interpreted pair and through ``dense_attention`` with the band as a mask,
    4 query heads over 2 key / value heads of 128: o and the gradients of a weighted sum."""
    was, pallas_latent_attention._BLOCK = pallas_latent_attention._BLOCK, 128
    b, h, kv, d = 2, 4, 2, 128
    out = {}
    try:
        for name, (s, window) in BANDS.items():
            k = jax.random.split(jax.random.key(s + window), 4)
            q, key, v = (jax.random.normal(k[i], (b, s, n * d)) for i, n in enumerate((h, kv, kv)))
            weights = jax.random.normal(k[3], (b, s, h * d))

            def by_kernel(q, key, v, window=window):
                return latent_attention_kernel(q, key, v, head_dims=(d, d), kv_heads=kv, interpret=True, window=window)

            def by_mask(q, key, v, s=s, window=window):
                rep = lambda t: jnp.repeat(t.reshape(b, s, kv, d), h // kv, axis=2)  # noqa: E731
                return dense_attention(q.reshape(b, s, h, d), rep(key), rep(v), causal=True, window=window).reshape(b, s, h * d)

            def both(core):
                return jax.value_and_grad(lambda *a: jnp.sum(core(*a) * weights), argnums=(0, 1, 2))(q, key, v)

            with jax.default_matmul_precision("highest"):
                causal = by_kernel(q, key, v, window=0) if window >= s else None
                out[name] = (by_kernel(q, key, v), by_mask(q, key, v), causal), both(by_kernel), both(by_mask)
    finally:
        pallas_latent_attention._BLOCK = was
    return out


@pytest.mark.parametrize("band", sorted(BANDS))
def test_the_kernel_pair_with_a_window_is_the_masked_dense_core(banded_pairs, band):
    (got, want, causal), _, _ = banded_pairs[band]
    s, window = BANDS[band]
    assert got.shape == (2, s, 4 * 128)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if window >= s:  # a window that holds the whole sequence is the causal call, bit for bit
        assert np.array_equal(np.asarray(got), np.asarray(causal))
    else:  # and a shorter one is not the causal layer
        mask = brute_force_mask(s, window)
        assert mask.sum() < s * (s + 1) // 2


@pytest.mark.parametrize("leaf", ["q", "k", "v"])
@pytest.mark.parametrize("band", sorted(BANDS))
def test_the_kernel_pairs_backward_with_a_window_is_jax_grad_of_the_masked_core(banded_pairs, band, leaf):
    _, (_, got), (_, want) = banded_pairs[band]
    i = "qkv".index(leaf)
    assert got[i].shape == want[i].shape == (2, BANDS[band][0], (4 if leaf == "q" else 2) * 128)
    # a window of 1 has no gradient for q and k (a softmax over one key): the scale is v's then
    scale = max(float(np.abs(np.asarray(want[i])).max()), float(np.abs(np.asarray(want[2])).max()) * 1e-2)
    np.testing.assert_allclose(got[i], want[i], atol=3e-4 * scale)


@pytest.mark.parametrize("tokens, block, window", [
    (8192, 512, 2048), (8192, 512, 0), (4096, 512, 2048), (512, 128, 200), (512, 128, 1), (512, 128, 128), (384, 128, 100),
    (512, 128, 511), (1024, 256, 700),
])
def test_the_counts_of_a_band_are_a_brute_force_count_of_the_mask(tokens, block, window):
    mask = brute_force_mask(tokens, window)
    assert admitted_pairs(tokens, window) == int(mask.sum())
    n = tokens // block
    assert visited_block_pairs(tokens, block, window) == int(mask.reshape(n, block, n, block).any((1, 3)).sum())
    assert flops_trinity.admitted_pairs(tokens, window) == admitted_pairs(tokens, window)
    if (tokens, window) in ((8192, 2048), (8192, 0)):  # ISSUE 47's numbers
        assert admitted_pairs(tokens, window) == (14681088 if window else 33558528)
        assert visited_block_pairs(tokens, block, window) == (70 if window else 136)


@pytest.mark.parametrize("attn_impl, dtype, tpu, kv, head_dim, tokens, window, core, repeated, block, pairs", [
    ("auto", jnp.bfloat16, True, 4, 128, 8192, 2048, "kernel", False, 512, 70),  # the cell's window layers
    ("auto", jnp.bfloat16, True, 4, 128, 8192, 0, "kernel", False, 512, 136),  # and its full layer
    ("auto", jnp.bfloat16, True, 4, 128, 8192, 8192, "kernel", False, 512, 136),  # a window of the whole sequence is none
    ("auto", jnp.bfloat16, True, 32, 128, 8192, 2048, "kernel", False, 512, 70),  # every head its own: a window still takes the pair
    ("auto", jnp.bfloat16, True, 32, 128, 8192, 0, "flash", False, 512, None),  # without one, as it always was
    ("auto", jnp.bfloat16, True, 32, 64, 8192, 2048, "dense", False, None, None),  # heads the pair does not admit: the mask
    ("auto", jnp.bfloat16, True, 32, 64, 64, 16, "dense", False, None, None),  # the short kernels take no window: passed by
    ("auto", jnp.float32, True, 4, 128, 8192, 2048, "dense", True, None, None),
    ("auto", jnp.bfloat16, False, 4, 128, 8192, 2048, "dense", True, None, None),
    ("dense", jnp.bfloat16, True, 4, 128, 8192, 2048, "dense", True, None, None),
    ("flash", jnp.float32, True, 4, 128, 8192, 2048, "kernel", False, 512, 70),
])
def test_which_core_a_window_layer_takes_follows_from_what_the_call_can_see(monkeypatch, attn_impl, dtype, tpu, kv, head_dim,
                                                                            tokens, window, core, repeated, block, pairs):
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    sizes = attention_core(attn_impl, dtype, tokens, 32, kv, head_dim, True, window=window)
    assert (sizes["core"], sizes["kv_repeated"], sizes["block"], sizes["block_pairs"]) == (core, repeated, block, pairs)
    assert sizes["window"] == (window if 0 < window < tokens else None)
    assert sizes["admitted_pairs"] == int(brute_force_mask(tokens, window if window < tokens else 0).sum())


def test_the_fused_cores_that_take_no_window_are_refused_for_one_by_name(monkeypatch):
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    with pytest.raises(ValueError, match="attn_impl='flash' with window=16"):
        attention_core("flash", jnp.bfloat16, 64, 32, 32, 64, True, window=16)  # the short kernel would run it without
    with pytest.raises(ValueError, match="attn_impl='flash' with window=2048"):
        attention_core("flash", jnp.bfloat16, 65536, 32, 4, 128, True, window=2048)  # a sequence the pair does not admit
    assert attention_core("auto", jnp.bfloat16, 65536, 32, 4, 128, True, window=2048)["core"] == "dense"


# -- (b) the layer ----------------------------------------------------------------------------


def text_namespace(**over):
    return types.SimpleNamespace(**{**dataclasses.asdict(tiny_trinity().text), **over})


def attention_of(spec, cfg=None, **over):
    t = (cfg or tiny_trinity()).text
    return Attention(t.width, t.num_heads, jnp.float32, causal=True, use_bias=False, norm_eps=t.norm_eps,
                     **{"rope_theta": None, **dict(spec.mixer_fields), **over})


@pytest.mark.parametrize("layer", [0, 2], ids=["window", "full"])
def test_the_attention_layer_matches_the_reference(layer):
    """Grouped heads, the head norms before the rotation, the band, the gate: both kinds of layer."""
    cfg = tiny_trinity()
    spec, window = layer_specs(cfg.text)[layer], TRINITY["attn_windows"][layer]
    module = attention_of(spec)
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    params = moved(module.init(jax.random.key(1), x)["params"])
    assert sorted(params) == ["gate", "k", "k_norm", "out", "q", "q_norm", "v"]
    assert params["q_norm"]["scale"].shape == params["k_norm"]["scale"].shape == (16,)  # one scale, shared by the heads
    assert params["gate"]["kernel"].shape == (32, 64) and params["k"]["kernel"].shape == (32, 32)
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": params}, x)
        want = jnp.stack([reference_trinity.attention(row, params, text_namespace(), window) for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_window_layers_rotate_and_full_layers_take_no_position():
    """With another base of the rotation a window layer's output moves and a full
    layer's is the same to the bit: it was never rotated."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    outs = {}
    for theta in (10000.0, 100.0):
        specs = layer_specs(tiny_trinity(rope_theta=theta).text)
        assert [dict(s.mixer_fields)["rope_theta"] for s in specs] == [theta, theta, None, theta, theta]
        for i in (0, 2):
            module = attention_of(specs[i])
            params = moved(module.init(jax.random.key(1), x)["params"])
            outs[theta, i] = np.asarray(module.apply({"params": params}, x))
    assert np.array_equal(outs[10000.0, 2], outs[100.0, 2])
    assert np.abs(outs[10000.0, 0] - outs[100.0, 0]).max() > 1e-3
    # rope_layers="all" rotates the full layer too: today's rule, one theta for the tower
    specs = layer_specs(tiny_trinity(rope_layers="all").text)
    assert all("rope_theta" not in dict(s.mixer_fields) for s in specs)
    assert [dict(s.mixer_fields).get("window", 0) for s in specs] == [8, 8, 0, 8, 8]


@pytest.mark.parametrize("tokens, dh", [(8192, 128), (5000, 64), (100, 16)])
def test_the_tables_made_in_the_program_are_the_constant_ones(tokens, dh):
    """``long_rope_tables`` (position 64 a + b by the angle-sum rule from two short
    tables) against ``rope_tables`` from float64: two roundings apart."""
    from distributed_sigmoid_loss_tpu.models.transformer import long_rope_tables, rope_tables

    for made, constant in zip(jax.jit(lambda: long_rope_tables(tokens, dh, 10000.0))(), rope_tables(tokens, dh, 10000.0)):
        assert made.shape == constant.shape == (tokens, dh) and made.dtype == jnp.float32
        np.testing.assert_allclose(made, constant, atol=2.5e-7, rtol=0)


def test_a_long_sequences_rotation_carries_no_table_in_its_program(monkeypatch):
    """At the cell's 8192 tokens of 128-wide heads the two tables are 8 MB a use and
    were 0.27 GB of the serialized step; made in the program, the lowered rotation
    holds the short tables alone, and rotates as the constant tables do. Up to
    ``ROPE_CONSTANT_ELEMENTS`` (every older cell) the tables stay constants."""
    from distributed_sigmoid_loss_tpu.models import transformer

    lowered = lambda s: len(jax.jit(lambda x: transformer.rope(x, 10000.0)).lower(  # noqa: E731
        jax.ShapeDtypeStruct((1, s, 2, 128), jnp.bfloat16)).as_text())
    assert 8192 * 128 > transformer.ROPE_CONSTANT_ELEMENTS >= 4096 * 64
    assert lowered(8192) < 1e6 < 4e6 < lowered(2048)  # text: two hex digits a byte of a constant
    x = jax.random.normal(jax.random.key(0), (1, 4100, 2, 128), jnp.float32)
    made = transformer.rope(x, 10000.0)
    monkeypatch.setattr(transformer, "ROPE_CONSTANT_ELEMENTS", 1 << 30)
    np.testing.assert_allclose(made, transformer.rope(x, 10000.0), atol=3e-6)


def test_a_window_layer_on_the_kernel_path_hands_the_pair_its_window(monkeypatch):
    """With the backend read as a TPU's and the pair interpreted, a window layer
    hands the pair q at 4 heads, k and v at 2 and its window, a full layer none,
    and the numbers are the dense path's with the band as a mask."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    seen = []
    kernel = pallas_latent_attention.latent_attention_kernel

    def noted(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw["kv_heads"], kw["window"]))
        return kernel(q, k, v, interpret=True, **kw)

    x = jax.random.normal(jax.random.key(0), (2, 40, 32))
    for window in (16, 0, 64):
        layer = Attention(32, 4, jnp.float32, causal=True, use_bias=False, num_kv_heads=2, head_dim=128, attn_impl="flash",
                          window=window, qk_norm=True, out_gate=True, rope_theta=10000.0)
        dense = layer.clone(attn_impl="dense")
        params = dense.init(jax.random.key(1), x)
        want = dense.apply(params, x)
        with monkeypatch.context() as patched:
            patched.setattr(pallas_latent_attention, "latent_attention_kernel", noted)
            patched.setattr(flash_attention, "flash_attention_available", lambda: True)
            np.testing.assert_allclose(layer.apply(params, x), want, atol=2e-5)
            lowered = jax.jit(layer.apply).lower(params, x).as_text(debug_info=True)
        scope = WINDOW_CORE_SCOPE if window == 16 else ATTN_CORE_SCOPE  # a window of 64 holds the 40 tokens: a full layer
        assert f"/{scope}/" in lowered and f"/{ATTN_GATE_SCOPE}/" in lowered and "/rope/" in lowered
        assert (f"/{WINDOW_CORE_SCOPE}/" in lowered) == (window == 16)
    assert [s[-1] for s in seen[::2]] == [16, 0, 0] and seen[0][:4] == ((2, 40, 512), (2, 40, 256), (2, 40, 256), 2)
    without = Attention(32, 4, jnp.float32, causal=True, use_bias=False, num_kv_heads=2, head_dim=128, window=16)
    assert np.abs(np.asarray(without.apply(without.init(jax.random.key(1), x), x)
                             - without.clone(window=0).apply(without.init(jax.random.key(1), x), x))).max() > 1e-3


def test_sandwich_norms_around_the_routed_feed_forward_part():
    """``sandwich_norm`` with ``SharedExpertMoe``: four norms a layer, ``ln2_post`` on the
    routed part's output, and the layer is the reference's."""
    cfg = tiny_trinity()
    t, spec = cfg.text, layer_specs(cfg.text)[1]
    block = Block(t.width, t.num_heads, t.mlp_ratio, jnp.float32, causal=True, moe_experts=t.moe_experts,
                  moe_num_selected=t.moe_num_selected, style=BlockStyle.of(t), spec=spec)
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    params = moved(block.init(jax.random.key(1), x)["params"])
    assert sorted(params) == ["attn", "ln1", "ln1_post", "ln2", "ln2_post", "moe"]
    with jax.default_matmul_precision("highest"):
        got = block.apply({"params": params}, x)
        want = jnp.stack([reference_trinity.layer(row, params, text_namespace(), 8) for row in x])
        plain = jnp.stack([reference_trinity.layer(row, params, text_namespace(sandwich_norm=False), 8) for row in x])
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert np.abs(np.asarray(want - plain)).max() > 1e-2  # the two output norms are no rounding


def held_loads(model, params, tokens):
    """Each routed layer's held experts' tokens on ``tokens``: (layers, held)."""
    _, state = model.apply({"params": params}, tokens, method="encode_text", mutable=["intermediates"])
    return np.asarray([x for p, x in jax.tree_util.tree_leaves_with_path(state["intermediates"])
                       if getattr(p[-1], "key", None) == "tokens"])


def test_a_start_balanced_on_the_batch_itself_gives_every_held_expert_its_share_of_it():
    """``moe_balanced_init`` where the sample batch carries its ids (the trainer's
    first batch, the benchmark's job for this cell): on those ids every held expert
    of every routed layer is chosen by T k / E tokens, to a few of them; from shapes alone
    the bias is found on other ids and this stack, whose norms hand a sequence's
    tokens a common part, loads the same experts far from their share; and the two
    starts differ in the selection biases alone."""
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    model = SigLIP(tiny_trinity(moe_balanced_init=True, context_length=48, attn_windows=(16, 16, 0, 16, 16)))
    batch = {"images": jnp.zeros((8, 16, 16, 3), jnp.float32), "tokens": jax.random.randint(jax.random.key(3), (8, 48), 0, 64)}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    on_batch, on_other_ids = (nn.meta.unbox(init_params(jax.random.key(5), model, sample, make_mesh(1))) for sample in (batch, shapes))
    share = 8 * 48 * 3 // 16
    loads = held_loads(model, on_batch, batch["tokens"])
    assert loads.shape == (4, 4) and np.abs(loads - share).max() <= 0.05 * share, loads  # twenty rounds of the fit
    assert np.abs(held_loads(model, on_other_ids, batch["tokens"]) - share).max() > 0.25 * share
    moved_leaves = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(on_batch), jax.tree.leaves(on_other_ids)) if not np.array_equal(a, b)]
    assert moved_leaves == [f"['textual']['encoder']['block{i}']['moe']['{SELECT_BIAS}']" for i in (1, 2, 3, 4)]


def test_eight_shares_of_sixteen_experts_and_the_shared_expert_once_are_the_whole_layer():
    """128 experts over 8 chips, top-8, scale 2.826: each share routes over all 128
    and computes its 16; the routed parts add up, with the shared expert counted
    once, to what the reference gives for the layer with all 128 held."""
    def routed_layer(held, first=0):
        return SharedExpertMoe(8, 12, 128, 8, jnp.float32, route_scale=2.826, shared_experts=1, experts_held=held,
                               first_held=first, kind="swiglu")

    whole = routed_layer(held=0)
    x = jax.random.normal(jax.random.key(0), (2, 40, 8))
    params = moved(whole.init(jax.random.key(1), x)["params"])
    t = types.SimpleNamespace(moe_num_selected=8, moe_route_scale=2.826)
    flat = x.reshape(80, 8)
    with jax.default_matmul_precision("highest"):
        want = reference_trinity.moe(flat, params, t).reshape(x.shape)
        s = params["shared"]
        shared = reference_trinity.swiglu(x, s["wg"]["kernel"], s["wi"]["kernel"], s["wo"]["kernel"])
        total, seen = shared, 0
        for share in range(8):
            part = dict(params, **{n: params[n][16 * share : 16 * share + 16] for n in ("wg", "wi", "wo")})
            y, state = routed_layer(held=16, first=16 * share).apply({"params": part}, x, mutable=["intermediates"])
            total = total + (y - shared)
            seen += int(state["intermediates"]["moe_load"][0]["tokens"].sum())
            if share == 0:  # the share the cell holds: what this chip's layer gives is the reference's on the same share
                np.testing.assert_allclose(y, reference_trinity.moe(flat, part, t).reshape(x.shape), atol=3e-5)
    assert seen == 2 * 40 * 8  # every assignment ran on exactly one share
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert np.abs(np.asarray(total - shared)).max() > 0.05  # the routed parts are no rounding


# -- (c) the whole tower, the train step, and one accumulated step through run.py -------------


@pytest.fixture(scope="module")
def tower_and_reference():
    cfg = tiny_trinity(remat=True)
    model = SigLIP(cfg)
    images, tokens = tiny_batch()
    params = moved(model.init(jax.random.key(0), images, tokens)["params"])

    def system_loss(p):
        zimg, ztxt, lp = model.apply({"params": p}, images, tokens)
        return sigmoid_loss(zimg, ztxt, lp["t_prime"], lp["bias"]), ztxt

    def reference_loss(p):
        zimg, ztxt = reference_trinity.embeddings(cfg, p, images, tokens)
        return reference_trinity._base.sigmoid_loss(zimg, ztxt, p["t_prime"], p["bias"]), ztxt

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
        want = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(params)
        _, first = reference_trinity.make_sample_grads(cfg)(params, images, tokens)
    return params, got, want, first


@pytest.mark.parametrize("what", ["embeddings", "loss", "gradients", "first_blocks", "the_band_bites"])
def test_the_tower_matches_the_plain_reference(tower_and_reference, what):
    params, ((got_loss, got_z), got), ((want_loss, want_z), want), first = tower_and_reference
    if what == "embeddings":
        np.testing.assert_allclose(got_z, want_z, atol=1e-5)
    elif what == "loss":
        np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    elif what == "gradients":
        errs = reference_trinity.tree_max_rel_err(got, want)
        assert len(errs) == len(jax.tree.leaves(params))
        assert max(errs.values()) < 3e-4, max(errs, key=errs.get)
        bias_grads = [v for k, v in jax.tree_util.tree_leaves_with_path(got) if SELECT_BIAS in jax.tree_util.keystr(k)]
        assert len(bias_grads) == 4 and not any(np.asarray(g).any() for g in bias_grads)
    elif what == "first_blocks":
        # The interface `correct` calls: the dense layer and the first routed one whole (without the router), and
        # every other layer's attention, the full layer's among them.
        assert set(first["textual"]) == {f"block{i}" for i in range(5)}
        assert set(first["textual"]["block0"]) == {"attn", "ln1", "ln1_post", "ln2", "ln2_post", "mlp"}
        assert set(first["textual"]["block1"]) == {"attn", "ln1", "ln1_post", "ln2", "ln2_post", "moe"}
        assert set(first["textual"]["block1"]["moe"]) == {"wg", "wi", "wo", "shared"}
        assert all(set(first["textual"][f"block{i}"]) == {"attn"} for i in (2, 3, 4))
        assert set(first["textual"]["block2"]["attn"]) == {"gate", "k", "k_norm", "out", "q", "q_norm", "v"}
        compared = reference_trinity.first_blocks(got)["textual"]
        assert max(reference_trinity.tree_max_rel_err(compared, first["textual"]).values()) < 3e-4
    else:
        # The window is shorter than the sequence: the reference without it (every layer full) is another tower.
        cfg = tiny_trinity(attn_windows=(0,) * 5)
        images, tokens = tiny_batch()
        with jax.default_matmul_precision("highest"):
            _, full_z = reference_trinity.embeddings(cfg, params, images, tokens)
        assert np.abs(np.asarray(full_z - want_z)).max() > 1e-3


def test_the_reference_refuses_another_stack():
    with pytest.raises(ValueError, match="reference_trinity.py has the equations"):
        reference_trinity.make_sample_grads(tiny_trinity(mlp="relu2"))
    with pytest.raises(ValueError, match="reference_trinity.py has the equations"):
        reference_trinity.make_batch_loss(tiny_trinity(attn_windows=(), rope_layers="all", mixers=("mla",) * 5), 1, np.arange(4)[None])


def test_the_tower_through_the_train_step():
    """The normal path: ``make_train_step`` with accumulation. The trace-time
    record tells a window layer from a full one and says which core each took,
    with the counts of its band; the metrics line carries the routed layers'
    counters; the program carries the names the benchmark's readers look for."""
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack, mixed_stack_line
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import STACK_RECORD_FIELDS, validate_metrics
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

    cfg = tiny_trinity(remat=True, scan_layers=True)  # scan_layers does not apply: unlike layers
    model, mesh = SigLIP(cfg), make_mesh(1)
    images, tokens = tiny_batch(8)
    shapes = {"images": jax.ShapeDtypeStruct(images.shape, images.dtype),
              "tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)}
    state = create_train_state(jax.random.key(0), model, make_optimizer(TrainConfig(weight_decay=0.1)), shapes, mesh)
    encoder = state.params["textual"]["encoder"]
    assert set(encoder) == {"block0", "block1", "block2", "block3", "block4", "ln_final"}
    assert [sorted(encoder[f"block{i}"]) for i in range(5)] == [
        ["attn", "ln1", "ln1_post", "ln2", "ln2_post", "mlp"]] + [["attn", "ln1", "ln1_post", "ln2", "ln2_post", "moe"]] * 4
    assert "pos_embed" not in state.params["textual"]
    step, _ = make_train_step(model, mesh, cfg.loss, accum_steps=2, accum_dtype="bfloat16")
    lowered = step.lower(state, {"images": images, "tokens": tokens}).as_text(debug_info=True)
    for name in ("attn", WINDOW_CORE_SCOPE, ATTN_CORE_SCOPE, ATTN_GATE_SCOPE, "rope", "moe", MOE_ROUTE_SCOPE):
        assert f"/{name}/" in lowered or f"/{name}\"" in lowered, name
    assert f"block0/attn/{WINDOW_CORE_SCOPE}/" in lowered and f"block2/attn/{ATTN_CORE_SCOPE}/" in lowered
    assert f"block2/attn/{WINDOW_CORE_SCOPE}/" not in lowered and "block2/attn/rope/" not in lowered
    assert "block0/attn/rope/" in lowered and f"block2/attn/{ATTN_GATE_SCOPE}/q_norm" in lowered
    before = encoder["block2"]["attn"]["gate"]["kernel"]
    for _ in range(2):
        state, metrics = step(state, {"images": images, "tokens": tokens})
    metrics = {k: float(v) for k, v in metrics.items()}
    validate_metrics(metrics)
    assert np.isfinite(metrics["loss"]) and metrics["moe_dropped_tokens"] == 0
    assert 0 < metrics["moe_local_assignments"] <= 2 * 4 * 96 * 3  # two microbatches, four routed layers
    assert not np.array_equal(state.params["textual"]["encoder"]["block2"]["attn"]["gate"]["kernel"], before)
    assert not np.asarray(state.params["textual"]["encoder"]["block1"]["moe"][SELECT_BIAS]).any()  # never updated
    record = mixed_stack(step)
    assert set(record) <= set(STACK_RECORD_FIELDS)
    assert record["layer_kinds"] == ["window_attn+mlp", "window_attn+moe", "attn+moe", "window_attn+moe", "window_attn+moe"]
    assert (record["experts_held"], record["experts_total"], record["experts_per_token"]) == (4, 16, 3)
    window = {"core": "dense", "block": None, "heads": 4, "kv_heads": 2, "head_dim": 16, "kv_repeated": True, "window": 8,
              "block_pairs": None, "admitted_pairs": int(brute_force_mask(24, 8).sum()), "rotated": True, "qk_norm": True,
              "gated": True}
    full = {**window, "window": None, "admitted_pairs": 24 * 25 // 2, "rotated": False}
    assert record["attn"] == {0: window, 1: window, 2: full, 3: window, 4: window}
    line = mixed_stack_line(record)
    assert line.startswith("stack: window_attn+mlp window_attn+moe attn+moe window_attn+moe window_attn+moe; "
                           "attn[0] core=dense window 8, 164 pairs a head, 4/2 heads of 16 (keys and values repeated) "
                           "rotated qk_norm gated; ")
    assert "attn[2] core=dense 4/2 heads of 16 (keys and values repeated) qk_norm gated; " in line
    assert step._cache_size() == 1


def cell_config(name=CELL):
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", "train_step.py"))
    cell = harness.Cell(name, rehearse=False)
    return cell, job.build_config(cell.config, cell.traffic)


def test_the_record_of_the_cells_stack_on_a_tpu(monkeypatch):
    """By the rule the layers run by: on a TPU in bf16 the cell's four window layers
    take the kernel pair over 70 block pairs a head, 14 681 088 admitted pairs, its
    full layer over 136 and 33 558 528, nothing repeated."""
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack_line
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train.train_step import stack_record_of

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    _, cfg = cell_config()
    record = stack_record_of(cfg.text, (2, 8192))
    assert record["layer_kinds"] == ["window_attn+mlp", "window_attn+moe", "attn+moe", "window_attn+moe", "window_attn+moe"]
    window = {"core": "kernel", "block": 512, "heads": 32, "kv_heads": 4, "head_dim": 128, "kv_repeated": False,
              "window": 2048, "block_pairs": 70, "admitted_pairs": 14681088, "rotated": True, "qk_norm": True, "gated": True}
    full = {**window, "window": None, "block_pairs": 136, "admitted_pairs": 33558528, "rotated": False}
    assert record["attn"] == {0: window, 1: window, 2: full, 3: window, 4: window}
    assert (record["experts_held"], record["experts_total"], record["expected_local_assignments_per_token"]) == (16, 128, 1.0)
    assert record["tokens_per_microbatch"] == 16384 and record["dispatch_rows_bound"] == 16384 * 8
    line = mixed_stack_line(record)
    assert ("attn[0] core=kernel window 2048, 14681088 pairs a head, 32/4 heads of 128 rotated qk_norm gated, "
            "blocks of 512 tokens, 70 block pairs a head") in line
    assert "attn[2] core=kernel 32/4 heads of 128 qk_norm gated, blocks of 512 tokens, 136 block pairs a head" in line
    # the cells the benchmark had keep their kinds: no layer of theirs has a window
    for other, shape in (("kimi-b16-p64-s1024", (16, 1024)), ("glm-b16-p16-s4096", (4, 4096)), ("nemotron-b16-p16-s4096", (4, 4096))):
        assert not [k for k in stack_record_of(cell_config(other)[1].text, shape)["layer_kinds"] if "window" in k]


CONTROLS = ("no_window", "rope_all", "no_gate", "no_qk_norm", "fp8")


def test_the_job_rehearses_through_run_py_and_each_planted_fault_fails_it(tmp_path, monkeypatch, capsys):
    """benchmark/run.py end to end on the CPU at a toy size, through the cell's own
    job (jobs/train_step_balanced_on_batch.py) with the configuration's own modules:
    the program balances the routers on the very ids the job then trains on,
    `correct` against reference_trinity.py on the accumulated step, sound true, and
    each of the five faults planted through the same tool
    (benchmark/tests/controls_trinity.py) read outside a bound."""
    import run

    from distributed_sigmoid_loss_tpu.train import train_step

    tiny = tiny_trinity(remat=True, moe_balanced_init=True)
    balanced_on = []
    balance_routers = train_step.balance_routers
    monkeypatch.setattr(train_step, "balance_routers", lambda model, params, ids, *rest: (
        balanced_on.append(np.asarray(ids)), balance_routers(model, params, ids, *rest))[1])
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    fields = lambda cfg, names: {n: getattr(cfg, n) for n in names}  # noqa: E731
    modules = harness.Cell(CELL, rehearse=False).config["modules"]
    assert modules == {"reference": "reference_trinity", "count": "flops_trinity"}
    (tmp_path / "configs" / "tiny-trinity.json").write_text(json.dumps({
        "modules": modules,
        "vision": fields(tiny.vision, ["image_size", "patch_size", "width", "depth", "num_heads", "embed_dim", "use_proj", "dtype"]),
        "text": fields(tiny.text, ["vocab_size", "width", "embed_dim", "dtype", "moe_balanced_init", *TRINITY]),
        "loss": {},
    }))
    mix = dict(harness.Cell(CELL, rehearse=False).traffic)
    assert mix["job"] == "train_step_balanced_on_batch"
    mix.update(pairs_per_chip_per_step=8, microbatch=4)
    mix["step"]["accum_steps"] = 2
    (tmp_path / "traffic" / "p8-s24.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-trinity", "file": "configs/tiny-trinity.json"}],
        "workloads": [{"name": "tiny-trinity-p8-s24", "config": "tiny-trinity", "traffic": "p8-s24", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }))
    monkeypatch.setattr(harness, "REHEARSAL_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "prepare_environment", lambda cell, rehearse: None)
    seed = "4700000007"
    assert run.main(["--rehearse", "--workload", "tiny-trinity-p8-s24", "--seed", seed, "--seconds", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0 and lines[-1]["metrics"] == {}
    assert all(lines[0]["verdicts"].values()), lines[0]
    assert lines[0]["grad_err"] < 1e-3 and lines[0]["embed_err"] < 1e-4
    # the parameters that were checked and the state that was trained: both balanced on the seed's own batch
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", mix["job"] + ".py"))
    built = job.build_step(harness.Cell("tiny-trinity-p8-s24", rehearse=True), jax.devices()[:1])
    assert isinstance(built.batch_shapes["tokens"], jax.ShapeDtypeStruct)  # the tools that describe a chip get shapes
    the_batch = job.make_batch(built, jax.random.split(jax.random.key(int(seed)))[1])["tokens"]
    assert len(balanced_on) == 2 and all(np.array_equal(ids, the_batch) for ids in balanced_on)
    balanced_on.clear()

    from distributed_sigmoid_loss_tpu.models import text, transformer
    from distributed_sigmoid_loss_tpu.parallel import ring_attention

    controls = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_trinity.py"))
    kept = (transformer.attention_core, text.layer_specs, nn.Dense.__call__, nn.RMSNorm.__call__,
            ring_attention.dense_attention, pallas_latent_attention.latent_attention_kernel)
    reads = [arg for name in ("sound", *CONTROLS[1:]) for arg in ("--read", f"{name}:{seed}")]
    assert controls.main(["--rehearse", "--workload", "tiny-trinity-p8-s24", "--seconds", "1", "--run", f"no_window:{seed}",
                          *reads]) == 0
    assert kept == (transformer.attention_core, text.layer_specs, nn.Dense.__call__, nn.RMSNorm.__call__,
                    ring_attention.dense_attention, pallas_latent_attention.latent_attention_kernel)  # it undoes its patches
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    (ran,) = lines[-1]["runs"]  # one fault through run.py's own main: its `correct` is false, no step failed
    assert (ran["variant"], ran["correct"], ran["failed"]) == ("no_window", False, 0)
    assert ran["compared"]["embed_err"][0] > ran["compared"]["embed_err"][1]
    reads = {line["variant"]: line for line in lines if line.get("info") == "controls.read"}
    assert sorted(reads) == sorted(("sound", *CONTROLS[1:]))
    assert reads["sound"]["grad_err"] < 1e-3 and reads["sound"]["embed_err"] < 1e-4
    over = {name: sorted(k for k in ("embed_err", "grad_err") if r[k] > r["bounds"][k])
            + ["loss_err"] * (r["sample_loss_err"] > r["bounds"]["loss_err"]) for name, r in reads.items()}
    assert not over["sound"]
    for name in ("rope_all", "no_gate", "no_qk_norm"):
        assert over[name], (name, reads[name])
    gates = [v for k, v in reads["no_gate"]["grad_errs"].items() if "['gate']" in k]
    assert len(gates) == 5 and all(v == pytest.approx(1.0) for v in gates)  # the gates' kernels get nothing
    # fp8 in the cores' operands: at this toy size (a float32 tower) it moves the embeddings by hundreds of times a
    # sound run's error; whether it passes a limit is the chip's to say at the cell's sizes (reference_trinity.py has
    # the readings)
    assert reads["fp8"]["embed_err"] > 100 * reads["sound"]["embed_err"]
    assert reads["fp8"]["grad_err"] > 100 * reads["sound"]["grad_err"]


# -- (d) the benchmark's configuration and count -------------------------------------------


def test_the_cell_is_the_published_model_cut_as_written():
    cell, cfg = cell_config()
    t, published = cfg.text, cell.config
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(guide):  # every published key, as the catalog has it
        catalog = next(row for row in map(json.loads, open(guide)) if row["name"] == "Trinity-Mini")
        assert published["source"] == catalog["source_url"]
        assert {k: published[k] for k in catalog["config"]} == catalog["config"]
    assert published["model_type"] == "afmoe" and published["mup_enabled"] and published["score_func"] == "sigmoid"
    assert (t.width, t.norm_eps, t.embed_scale) == (published["hidden_size"], published["rms_norm_eps"], 2048**0.5) == (2048, 1e-5, 2048**0.5)
    assert (t.num_heads, t.num_kv_heads, t.head_dim) == (
        published["num_attention_heads"], published["num_key_value_heads"], published["head_dim"]) == (32, 4, 128)
    assert int(round(t.width * t.mlp_ratio)) == published["intermediate_size"] == 6144
    assert (t.moe_hidden, t.moe_experts, t.moe_num_selected, t.moe_shared_experts, t.moe_route_scale) == (
        published["moe_intermediate_size"], published["num_experts"], published["num_experts_per_tok"],
        published["num_shared_experts"], published["route_scale"]) == (1024, 128, 8, 1, 2.826)
    assert t.moe_router == "sigmoid" and published["route_norm"] and published["n_group"] == published["topk_group"] == 1
    assert t.mlp == "swiglu" and published["hidden_act"] == "silu" and t.rope_theta == published["rope_theta"] == 10000
    # layers 1-5 of the published pattern: a window layer is a sliding_attention layer, at the published window
    kinds = published["layer_types"][1:6]
    assert kinds == ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"]
    assert list(t.attn_windows) == [published["sliding_window"] if k == "sliding_attention" else 0 for k in kinds]
    assert published["sliding_window"] == 2048 and published["global_attn_every_n_layers"] == 4
    assert t.leading_dense_layers == 1 and published["num_dense_layers"] == 2  # layer 1 is the second of the two
    assert (t.pos, t.rope_layers, t.attn_qk_norm, t.attn_gate, t.sandwich_norm) == ("rope", "window", True, True, True)
    assert (t.causal, t.pool, t.norm, t.use_bias, t.dtype, tuple(t.mixers)) == (True, "last", "rmsnorm", False, "bfloat16", ())
    # the cut: depth, the experts held, the vocabulary; nothing else
    assert published["reduced"] == ["text.depth", "text.moe_experts_held", "text.vocab_size"]
    assert (t.depth, published["num_hidden_layers"], len(published["layer_types"])) == (5, 32, 32)
    assert (t.moe_experts_held, published["num_experts"]) == (16, 128)
    assert (t.vocab_size * 8, published["vocab_size"]) == (200192, 200192)
    assert "8 chips share each layer" in published["deployment"] and t.context_length == 8192
    assert t.moe_balanced_init is True  # the routers start balanced on a batch of ids, as Nemotron's do
    for stated in ("output gate", "QK norms", "rotation on window layers only", "muP", "four norms a layer", "window",
                   "rotate-half", "text.context_length", "vision", "offline", "left out", "text.moe_balanced_init"):
        assert stated in published["assumed"], stated
    b16 = harness.load_json(os.path.join(BENCH_DIR, "configs", "siglip-b16-224.json"))
    assert published["vision"] == b16["vision"]  # the B/16 image tower, field for field
    mix = cell.traffic
    assert (mix["pairs_per_chip_per_step"], mix["microbatch"], mix["step"]["accum_steps"], cell.chips) == (8, 2, 4, 1)
    # the mix is p8-s8192's (the one the benchmark has, evabyte-b16-p8-s8192's), number for number: the job apart,
    # which hands the program's initialisation the batch and does nothing else that p8-s8192's does not
    had = harness.Cell("evabyte-b16-p8-s8192", rehearse=False).traffic
    words = lambda m: {k: v for k, v in m.items() if k not in ("job", "note", "batch")}  # noqa: E731
    assert words(mix) == words(had) and mix["batch"]["source"] == had["batch"]["source"]
    assert (had["job"], mix["job"]) == ("train_step_check_first", "train_step_balanced_on_batch")


def test_the_text_tower_has_the_parameters_the_shapes_say():
    _, cfg = cell_config()
    shapes = jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32), jax.ShapeDtypeStruct((1, 8192), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(nn.meta.unbox(tree)))  # noqa: E731
    w = 2048
    attn = w * 4096 + 2 * w * 512 + w * 4096 + 4096 * w + 2 * 128  # q, k, v, the gate, out; the two head norms' scales
    dense, shared, experts, router = 3 * w * 6144, 3 * w * 1024, 16 * 3 * w * 1024, w * 128 + 128
    encoder = shapes["textual"]["encoder"]
    assert count(encoder["block0"]) == attn + dense + 4 * w and count(encoder["block2"]) == attn + shared + experts + router + 4 * w
    assert [round(n / 1e6, 2) for n in (attn, dense, shared, router, experts)] == [27.26, 37.75, 6.29, 0.26, 100.66]
    assert round((attn + dense) / 1e6, 1) == 65.0 and round((attn + shared + experts + router) / 1e6, 1) == 134.5  # ISSUE 47
    stack = 5 * attn + dense + 4 * (shared + experts + router) + 5 * 4 * w + w
    assert count(encoder) == stack and round(stack / 1e6, 1) == 603.0  # ISSUE 47: layer 1 + layers 2-5
    assert count(shapes["textual"]) == stack + 25024 * w + w * 768 + 768
    assert round(count(shapes) / 1e6, 1) == 748.7  # ISSUE 47: with the embedding's eighth, the head and the B/16 image tower
    assert "pos_embed" not in shapes["textual"]


def test_the_count_is_the_issues():
    """benchmark/tests/test_flops_trinity.py has the count by hand; tier-1 keeps its ends."""
    _, cfg = cell_config()
    assert round(flops_trinity.text_forward_flops(cfg.text) / 8192 / 1e6) == 635  # MFLOP a text token
    assert round(flops_trinity.train_flops_per_pair(cfg) / 1e12, 1) == 15.7  # ISSUE 47: a pair trained
    assert flops_trinity.expected_assignments_per_token(cfg.text) == 1.0
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops_trinity.attention_least_s(cfg, "textual", 8, peaks) == 0.0
    assert flops_trinity.attention_least_s(cfg, "visual", 8, peaks) > 0.0
    assert round(1e3 * flops_trinity.window_core_least_s(cfg, 8, peaks) / 4, 1) == 29.3
    assert round(1e3 * flops_trinity.full_core_least_s(cfg, 8, peaks), 1) == 67.0


# -- (e) the defaults are left alone, and what cannot run it refuses by name --------------


# The pair as the three cells that had it call it (rows, tokens, heads, key / value heads, dqk, dv), and the sha256 of
# the call's jaxpr text, forward and differentiated: the grid, both kernels' bodies and the cost estimate, to the
# letter. The hashes were read off the PARENT's file (PR 46, fd1437e: it has no `window`) on this installation, where
# this file's gave the same six: with no window the edited pair traces to the kernels those cells ran before.
PAIR_BEFORE_THE_WINDOW = {
    "nemotron-b16-p16-s4096": ((4, 4096, 32, 2, 128, 128), {
        "fwd": "e36475f6f9c2dcb1d999cc32414684cc366b6bf7e8e264ae50fc6ec3889e0906",
        "grad": "fdbdc033e2fb2bc48077c7e1d8107deab39c64171192d915f11ea85eceebe69d"}),
    "glm-b16-p16-s4096": ((4, 4096, 20, 20, 256, 256), {
        "fwd": "eb0526e35807c656fccfbaab024d4301c3e3180e73ccf8f30dac2248cc081bc1",
        "grad": "1b4d78479b1a58e0700a5999a88d2e6448607e55e79d02797863df96ed3d5a98"}),
    "kimi-b16-p64-s1024": ((16, 1024, 32, 32, 256, 128), {
        "fwd": "95d7645be054a4de1a447a6c6629f7a881d3e3a4aec32471feffd45669d7325f",
        "grad": "b62f376ca923d0887d68b6466200303540cae6da458c680989134b74e677a580"}),
}


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("cell", sorted(PAIR_BEFORE_THE_WINDOW))
def test_the_pair_without_a_window_traces_to_the_kernels_the_other_cells_had(cell, direction):
    import hashlib

    (b, s, h, kv, dqk, dv), before = PAIR_BEFORE_THE_WINDOW[cell]
    q, k, v = (jax.ShapeDtypeStruct((b, s, n * d), jnp.bfloat16) for n, d in ((h, dqk), (kv, dqk), (kv, dv)))
    options = dict(head_dims=(dqk, dv), scale=dqk**-0.5, **({"kv_heads": kv} if kv != h else {}))

    def fwd(q, k, v):
        return latent_attention_kernel(q, k, v, **options)

    traced = fwd if direction == "fwd" else jax.grad(lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    assert hashlib.sha256(str(jax.make_jaxpr(traced)(q, k, v)).encode()).hexdigest() == before[direction]


def test_the_defaults_leave_every_other_tree_and_program_alone():
    """No new field set: no layer specs, the trees the parent had, ``Attention``
    told its defaults outright lowers to the program it lowers to untold, and the
    pair called without a window (or with one that holds the sequence) lowers to
    the call it always was. (The nine cells' step programs hash as the parent's:
    ``benchmark/tests/lowered_hash.py`` from both checkouts, CHANGES.md PR 47.)"""
    for cfg in (SigLIPConfig.tiny_test(), SigLIPConfig.b16(), SigLIPConfig.so400m()):
        assert layer_specs(cfg.text) == ()
        t = cfg.text
        assert (t.attn_windows, t.rope_layers, t.attn_qk_norm, t.attn_gate, t.embed_scale) == ((), "all", False, False, 1.0)
    t = TextConfig.tiny_test()
    tokens = jax.ShapeDtypeStruct((2, t.context_length), jnp.int32)
    shapes = jax.eval_shape(TextTransformer(t).init, jax.random.key(0), tokens)["params"]
    paths = {jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(nn.meta.unbox(shapes))}
    assert not [p for p in paths if any(f"'{n}'" in p for n in ("gate", "q_norm", "k_norm", "ln1_post", "moe"))]
    ids = jnp.zeros((2, t.context_length), jnp.int32)
    lower_tower = lambda cfg: jax.jit(TextTransformer(cfg).apply).lower(  # noqa: E731
        jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tokens), ids).as_text()
    assert lower_tower(t) == lower_tower(dataclasses.replace(t, embed_scale=1.0, rope_layers="all", attn_windows=()))
    x = jnp.ones((2, 8, 32))
    untold = Attention(32, 2, jnp.float32, causal=True)
    told = untold.clone(window=0, qk_norm=False, out_gate=False, norm_eps=1e-5)
    params = untold.init(jax.random.key(0), x)
    lower = lambda m: jax.jit(m.apply).lower(params, x).as_text()  # noqa: E731
    assert lower(untold) == lower(told) == lower(untold.clone(window=8)) == lower(untold.clone(window=99))
    assert lower(untold) != lower(untold.clone(window=4))
    q = jnp.ones((1, 256, 256), jnp.bfloat16)
    call = lambda **kw: jax.jit(lambda q: latent_attention_kernel(q, q, q, head_dims=(128, 128), interpret=True, **kw)).lower(q).as_text()  # noqa: E731
    assert call() == call(window=0) == call(window=256) == call(window=4096)
    assert call() != call(window=255)


@pytest.mark.parametrize("over, named", [
    (dict(causal=False), "causal=False"),
    (dict(sequence_parallel_axis="sp"), "sequence_parallel_axis='sp'"),
    (dict(quant_train="int8"), "quant='int8_ste'"),
    (dict(attn_windows=(8, 8, 0)), r"attn_windows=\(8, 8, 0\) \(one number a layer, depth=5"),
    (dict(attn_windows=(8, 8, -1, 8, 8)), "one number a layer"),
    (dict(rope_layers="window", pos="none"), "rope_layers='window' with pos='none'"),
    (dict(rope_layers="full"), "rope_layers='full'"),
    (dict(mixers=("attn", "mla", "attn", "attn", "attn"), attn_windows=(8, 8, 0, 8, 8), rope_layers="all", pos="none",
          sandwich_norm=False), "a window is an 'attn' layer's"),
])
def test_what_a_window_does_not_run_with_is_refused_by_name(over, named):
    cfg = tiny_trinity(**over).text
    tokens = jax.ShapeDtypeStruct((2, cfg.context_length), jnp.int32)
    with pytest.raises(ValueError, match=named):
        jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tokens)


def test_a_window_refuses_cross_attention_by_name():
    x = jnp.ones((2, 8, 32))
    with pytest.raises(ValueError, match="window=4 .* cross-attention"):
        Attention(32, 2, jnp.float32, causal=True, window=4).init(jax.random.key(0), x, x)
    with pytest.raises(ValueError, match="window=4 is a causal band"):
        dense_attention(x.reshape(2, 8, 2, 16), x.reshape(2, 8, 2, 16), x.reshape(2, 8, 2, 16), window=4)


def test_a_stack_of_like_window_layers_scans():
    cfg = tiny_trinity(attn_windows=(8,) * 5, leading_dense_layers=0, moe_experts=0, moe_router="softmax", scan_layers=True).text
    tokens = jax.ShapeDtypeStruct((2, cfg.context_length), jnp.int32)
    shapes = jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tokens)["params"]
    assert set(shapes["encoder"]) == {"blocks", "ln_final"}
    assert nn.meta.unbox(shapes)["encoder"]["blocks"]["block"]["attn"]["gate"]["kernel"].shape == (5, 32, 64)


NEW_OPTIONS = dict(attn_windows=(4, 0), rope_layers="window", attn_qk_norm=True, attn_gate=True, embed_scale=2.0)


@pytest.mark.parametrize("option", sorted(NEW_OPTIONS))
def test_pipelined_towers_and_hf_import_refuse_the_new_options_by_name(option):
    from distributed_sigmoid_loss_tpu.models.hf_import import params_from_hf
    from distributed_sigmoid_loss_tpu.parallel.pp_towers import validate_pp_tower
    from distributed_sigmoid_loss_tpu.utils.config import BLOCK_OPTIONS

    assert option in BLOCK_OPTIONS and getattr(TextConfig(), option) == BLOCK_OPTIONS[option]
    text = dataclasses.replace(TextConfig.tiny_test(), scan_layers=True, **{option: NEW_OPTIONS[option]})
    with pytest.raises(ValueError, match=f"{option}="):
        validate_pp_tower(text, 2, "text")
    hf_shaped = SigLIPConfig(
        vision=dataclasses.replace(ViTConfig.tiny_test(), use_proj=False),
        text=dataclasses.replace(TextConfig.tiny_test(), pool="last", **{option: NEW_OPTIONS[option]}),
    )
    with pytest.raises(ValueError, match=f"{option}="):
        params_from_hf({}, hf_shaped)
