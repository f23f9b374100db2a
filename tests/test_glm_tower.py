"""A text tower with latent attention in every layer, rotated
(``TextConfig.mla_q_rank``, ``pos="rope"`` with "mla" mixers: queries through a
normalised latent, rotary positions on the shared-width parts only, value heads
wider than the unrotated key part), a leading dense layer and sigmoid-routed
experts with a shared one and a chip's share of them, against its plain reference
(``benchmark/reference_glm.py``): layer, tower, train step and the benchmark's job
with its planted faults, the benchmark's configuration and count, and what the
unrotated layer of ``tests/test_hybrid_*.py`` must keep bit for bit."""

import dataclasses
import json
import os
import sys
import types
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.models.mixers import (
    MLA_CORE_SCOPE,
    MLA_ROPE_SCOPE,
    LatentAttention,
    latent_attention_core,
)
from distributed_sigmoid_loss_tpu.models.moe import SELECT_BIAS, SharedExpertMoe
from distributed_sigmoid_loss_tpu.models.transformer import rope
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import sigmoid_loss
from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention
from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig, TextConfig, ViTConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import flops_glm  # noqa: E402
import harness  # noqa: E402
import reference_glm  # noqa: E402

CELL = "glm-b16-p16-s4096"
# The cell's stack at a toy size: a dense and two routed layers, 16 experts of which 4 are held.
GLM = dict(
    context_length=16, depth=3, causal=True, pool="last", norm="rmsnorm", norm_eps=1e-5,
    mlp="swiglu", use_bias=False, pos="rope", rope_theta=1e6, mixers=("mla", "mla", "mla"),
    leading_dense_layers=1, mla_qk_nope_dim=12, mla_qk_shared_dim=8, mla_v_dim=16, mla_kv_rank=12,
    mla_q_rank=10, moe_experts=16, moe_num_selected=4, moe_router="sigmoid", moe_route_scale=1.8,
    moe_shared_experts=1, moe_hidden=24, moe_experts_held=4,
)


def tiny_glm(**over) -> SigLIPConfig:
    text = dataclasses.replace(TextConfig.tiny_test(), **{**GLM, **over})
    return SigLIPConfig(vision=ViTConfig.tiny_test(), text=text)


def tiny_batch(n=4, s=16):
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


# -- (a) the rotation and the layer ----------------------------------------------------


def by_complex_numbers(x, theta):
    """Lane i and lane i + d/2 of a head as one complex number, turned by p theta^(-2i/d)."""
    x = np.asarray(x, np.float64)
    s, d = x.shape[1], x.shape[-1]
    z = x[..., : d // 2] + 1j * x[..., d // 2 :]
    turned = z * np.exp(1j * np.arange(s)[:, None] * theta ** (-np.arange(0, d, 2) / d))[None, :, None, :]
    return np.concatenate([turned.real, turned.imag], -1)


@pytest.mark.parametrize("theta", [1e4, 1e6])
@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_rotation_is_a_turn_of_each_lane_pair_by_its_position(which, theta):
    x = jax.random.normal(jax.random.key(0), (2, 24, 3, 64), jnp.float32)
    turn = (lambda x: rope(x, theta)) if which == "program" else jax.vmap(lambda row: reference_glm.rotate(row, theta))
    got = np.asarray(turn(x))
    np.testing.assert_allclose(got, by_complex_numbers(x, theta), atol=2e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])  # position 0 stays
    # a score depends on the distance alone: both moved on by five positions, the products stay
    later = np.asarray(turn(jnp.pad(x, ((0, 0), (5, 0), (0, 0), (0, 0)))))
    near = (got[:, 5:15] * got[:, 3:13]).sum(-1)
    np.testing.assert_allclose(near, (later[:, 10:20] * later[:, 8:18]).sum(-1), atol=2e-4)


def glm_layer(**over):
    fields = dict(width=48, num_heads=2, nope_dim=24, shared_dim=8, v_dim=32, kv_rank=20, dtype=jnp.float32,
                  q_rank=16, rope_theta=1e6)
    return LatentAttention(**{**fields, **over})


def test_the_rotated_layer_with_a_query_rank_and_wide_values_matches_the_reference():
    t = types.SimpleNamespace(num_heads=2, mla_qk_nope_dim=24, mla_qk_shared_dim=8, mla_v_dim=32, mla_kv_rank=20,
                              mla_q_rank=16, norm_eps=1e-5, pos="rope", rope_theta=1e6)
    layer = glm_layer()
    x = jax.random.normal(jax.random.key(0), (2, 12, 48), jnp.float32)
    params = moved(layer.init(jax.random.key(1), x)["params"])
    assert set(params) == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "out"}
    assert params["q_a"]["kernel"].shape == (48, 16) and params["q_b"]["kernel"].shape == (16, 2 * 32)
    assert params["kv_b"]["kernel"].shape == (20, 2 * (24 + 32)) and params["out"]["kernel"].shape == (2 * 32, 48)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        want = jax.vmap(lambda row: reference_glm.mla(row, params, t))(x)
        unrotated = glm_layer(rope_theta=None).apply({"params": params}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(np.asarray(unrotated - want)).max() > 1e-3  # the rotation is no rounding
    np.testing.assert_array_equal(unrotated[:, 0], got[:, 0])  # but position 0 sees itself alone, unturned
    later = layer.apply({"params": params}, x.at[:, 8:].add(1.0))  # causal
    np.testing.assert_array_equal(got[:, :8], later[:, :8])


def test_the_references_blocks_of_queries_are_the_whole_softmax(monkeypatch):
    keys = jax.random.split(jax.random.key(5), 3)
    q, k = (jax.random.normal(key, (12, 3, 16)) for key in keys[:2])
    v = jax.random.normal(keys[2], (12, 3, 20))
    want = dense_attention(q[None], k[None], v[None], causal=True, scale=0.25)[0]
    for block in (512, 4, 5):  # 5 divides nothing: the largest divisor under it, 4
        monkeypatch.setattr(reference_glm, "QUERY_BLOCK", block)
        np.testing.assert_allclose(reference_glm.causal_attention(q, k, v, 0.25), want, atol=2e-6)


@pytest.mark.parametrize("dn, dr, dv, ran_at", [
    (192, 64, 256, 256),  # this tower's: the blocked kernel's own size, nothing padded
    (128, 64, 128, 256),  # the unrotated layer's, rotated
    (24, 8, 48, 128),  # values wider than keys
])
def test_heads_reach_the_library_kernel_at_one_size(monkeypatch, dn, dr, dv, ran_at):
    """Past the pair's VMEM the rotated layer hands the library's kernel (here a dense
    stand-in) (b, s, h, ``ran_at``) views of its wide q, k and v, the heads zero-padded by
    the weights' columns: the layer on the dense path at its own sizes, values and gradients."""
    from test_hybrid_layers import library_kernel_stand_in, moved, ripple

    sizes = dict(width=48, num_heads=3, nope_dim=dn, shared_dim=dr, v_dim=dv, kv_rank=20, dtype=jnp.float32,
                 q_rank=24, rope_theta=1e4)
    dense, fused = LatentAttention(**sizes, attn_impl="dense"), LatentAttention(**sizes, attn_impl="flash")
    x = jax.random.normal(jax.random.key(0), (2, 12, 48), jnp.float32)
    params = moved(dense.init(jax.random.key(1), x)["params"])

    def loss(layer):
        return lambda p, x: (layer.apply({"params": p}, x) * ripple((2, 12, 48))).sum()

    want, want_grads = jax.value_and_grad(loss(dense), argnums=(0, 1))(params, x)
    seen = []
    library_kernel_stand_in(monkeypatch, seen)
    got, got_grads = jax.value_and_grad(loss(fused), argnums=(0, 1))(params, x)
    assert seen == [((2, 12, 3, ran_at),) * 3]
    np.testing.assert_allclose(got, want, rtol=1e-4)  # a sum with cancellation
    assert max(reference_glm._base.tree_max_rel_err(got_grads, want_grads).values()) < 1e-4


def test_the_benchmarks_planted_faults_reach_the_layer_on_the_kernel_path(monkeypatch):
    """``benchmark/tests/controls_glm.py`` plants ``no_rope`` by swapping ``models/transformer.py rope``
    for the identity and ``fp8`` by wrapping ``nn.Dense.__call__``. With the core through the pair
    (interpreted) both still reach the whole layer: the queries' rotation reads its tables off
    ``rope``, and every projection, the recut ones too, is an ``nn.Dense`` call with one output."""
    from distributed_sigmoid_loss_tpu.models import transformer
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_latent_attention

    monkeypatch.setattr(pallas_latent_attention, "latent_attention_kernel",
                        partial(pallas_latent_attention.latent_attention_kernel, interpret=True))
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    sizes = dict(width=48, num_heads=2, nope_dim=64, shared_dim=64, v_dim=128, kv_rank=32, dtype=jnp.float32,
                 q_rank=24, attn_impl="flash")
    rotated, unrotated = LatentAttention(**sizes, rope_theta=1e4), LatentAttention(**sizes)
    x = jax.random.normal(jax.random.key(0), (2, 40, 48), jnp.float32)
    params = rotated.init(jax.random.key(1), x)
    sound, without = rotated.apply(params, x), unrotated.apply(params, x)
    assert float(jnp.abs(sound - without).max()) > 1e-2
    with monkeypatch.context() as planted:  # no_rope: neither the queries' parts nor the shared key part turn
        planted.setattr(transformer, "rope", lambda x, theta: x)
        np.testing.assert_allclose(rotated.apply(params, x), without, rtol=1e-6, atol=1e-7)
    seen, exact = {}, nn.Dense.__call__

    def noted(self, x):
        seen[self.name] = exact(self, x)
        return seen[self.name]

    with monkeypatch.context() as planted:  # fp8 rounds what these calls return
        planted.setattr(nn.Dense, "__call__", noted)
        np.testing.assert_array_equal(rotated.apply(params, x), sound)
    assert {name: y.shape[-1] for name, y in seen.items()} == {
        "q_a": 24, "q_b": 2 * 2 * 128, "kv_a": 32 + 64, "kv_b": 2 * 128 + 2 * 128, "out": 48}  # q beside its exchanged halves; k beside v


@pytest.mark.parametrize("attn_impl, dtype, tpu, core", [
    ("auto", "bfloat16", True, "kernel"), ("auto", "float32", True, "dense"), ("auto", "bfloat16", False, "dense"),
    ("dense", "bfloat16", True, "dense"), ("flash", "float32", True, "kernel"),
])
def test_which_core_latent_attention_takes_follows_from_dtype_and_backend(monkeypatch, attn_impl, dtype, tpu, core):
    """At this cell's call, 4096 tokens of 256-wide heads (tests/test_hybrid_layers.py has the rule by head size and
    length): a fused core is the repo's own kernel pair."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    assert latent_attention_core(attn_impl, dtype, 4096, 256, 256)["core"] == core
    if not tpu:
        with pytest.raises(ValueError, match="attn_impl='flash'"):
            latent_attention_core("flash", dtype, 4096, 256, 256)


# -- (b) rank 0 and no rotation are the unrotated layer, bit for bit --------------------


class LatentAttentionBeforeTheRank(nn.Module):
    """``LatentAttention`` as it stood before it took a query rank and a rotation
    (PR 32), on the dense path and the per-head (b, s, h, d) form: the oracle for
    "nothing changed where neither is set"."""

    width: int
    num_heads: int
    nope_dim: int
    shared_dim: int
    v_dim: int
    kv_rank: int

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, dn, dr, dv = self.num_heads, self.nope_dim, self.shared_dim, self.v_dim
        dense = partial(nn.Dense, use_bias=False, dtype=jnp.float32, kernel_init=nn.initializers.xavier_uniform())
        q = dense(h * (dn + dr), name="q")(x).reshape(b, s, h, dn + dr)
        latent = dense(self.kv_rank + dr, name="kv_a")(x)
        c, shared = latent[..., : self.kv_rank], latent[..., self.kv_rank :]
        c = nn.RMSNorm(epsilon=1e-5, dtype=jnp.float32, name="kv_norm")(c)
        expanded = dense(h * (dn + dv), name="kv_b")(c).reshape(b, s, h, dn + dv)
        k = jnp.concatenate([expanded[..., :dn], jnp.broadcast_to(shared[:, :, None, :], (b, s, h, dr))], -1)
        out = dense_attention(q, k, expanded[..., dn:], causal=True, scale=(dn + dr) ** -0.5)
        return dense(self.width, name="out")(out.astype(jnp.float32).reshape(b, s, h * dv))


def test_rank_zero_and_no_rotation_are_the_layer_as_it_was_bit_for_bit():
    sizes = dict(width=48, num_heads=2, nope_dim=24, shared_dim=8, v_dim=16, kv_rank=20)
    now, before = LatentAttention(**sizes, dtype=jnp.float32), LatentAttentionBeforeTheRank(**sizes)
    x = jax.random.normal(jax.random.key(0), (2, 12, 48), jnp.float32)
    p_now, p_before = now.init(jax.random.key(1), x)["params"], before.init(jax.random.key(1), x)["params"]
    assert jax.tree.structure(p_now) == jax.tree.structure(p_before)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(p_now), jax.tree.leaves(p_before)))
    # the same numbers from another program: since PR 38 the layer cuts and joins the weights' columns, not (b, s, h, d) views
    np.testing.assert_array_equal(now.apply({"params": p_now}, x), before.apply({"params": p_before}, x))


def test_the_hybrid_towers_tree_is_untouched_by_the_new_fields():
    """The unrotated cell's text tower (tests/test_hybrid_tower.py's toy): no leaf
    of the query latent, no rotation in the program."""
    from test_hybrid_tower import tiny_hybrid

    cfg = tiny_hybrid()
    assert cfg.text.mla_q_rank == 0 and cfg.text.pos == "none"
    images, tokens = tiny_batch()
    model = SigLIP(cfg)
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0), images, tokens)["params"])
    assert set(shapes["textual"]["encoder"]["block3"]["mla"]) == {"q", "kv_a", "kv_norm", "kv_b", "out"}
    params = model.init(jax.random.key(0), images, tokens)["params"]
    text = jax.jit(model.apply).lower({"params": params}, images, tokens).as_text(debug_info=True)
    assert MLA_CORE_SCOPE in text and MLA_ROPE_SCOPE not in text


# -- (c) what the stack admits and refuses -----------------------------------------------


@pytest.mark.parametrize("mixers, pos, refused", [
    (("mla", "mla"), "rope", None),
    (("mla", "mla"), "none", None),
    (("attn", "mla"), "rope", None),
    (("mla", "mla"), "learned", r"pos='learned'"),
    (("kda", "mla"), "rope", r"'kda'.*pos='rope'|pos='rope'.*'kda'"),
    (("kda", "kda"), "rope", r"'kda'.*pos='rope'|pos='rope'.*'kda'"),
])
def test_layer_specs_admits_a_rotation_with_latent_attention_and_refuses_it_with_a_recurrence(mixers, pos, refused):
    from distributed_sigmoid_loss_tpu.models.text import TextTransformer, layer_specs

    cfg = dataclasses.replace(tiny_glm().text, depth=2, mixers=mixers, pos=pos, kda_head_dim=16)
    if refused:
        with pytest.raises(ValueError, match=refused):
            layer_specs(cfg)
        return
    specs = layer_specs(cfg)
    for spec in specs:
        if spec.mixer == "mla":
            fields = dict(spec.mixer_fields)
            assert fields["q_rank"] == 10 and fields["rope_theta"] == (1e6 if pos == "rope" else None)
    jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tiny_batch()[1])


def test_pipelined_towers_and_hf_import_refuse_the_query_rank_by_name():
    from distributed_sigmoid_loss_tpu.analysis import config_space
    from distributed_sigmoid_loss_tpu.models.hf_import import params_from_hf
    from distributed_sigmoid_loss_tpu.parallel.pp_towers import validate_pp_tower

    text = dataclasses.replace(TextConfig.tiny_test(), pool="last", mla_q_rank=8)
    with pytest.raises(ValueError, match=r"\bmla_q_rank=8"):
        validate_pp_tower(dataclasses.replace(text, scan_layers=True), 2, "text")
    cfg = SigLIPConfig(vision=dataclasses.replace(ViTConfig.tiny_test(), use_proj=False), text=text)
    with pytest.raises(ValueError, match=r"\bmla_q_rank=8"):
        params_from_hf({}, cfg)
    assert "mla_q_rank" in config_space.PP_REFUSES
    assert any(pair[:2] == ({"mixers": ("kda", "mla")}, {"pos": "rope"}) for pair in config_space.TOWER_EXCLUSIONS)


def test_the_reference_refuses_another_stack():
    with pytest.raises(ValueError, match="mixers of 'mla' alone"):
        reference_glm.make_sample_grads(tiny_glm(mixers=("attn", "mla", "mla")))
    with pytest.raises(ValueError, match="moe_router"):
        reference_glm.make_sample_grads(tiny_glm(moe_router="softmax", mlp="gelu", use_bias=True, moe_num_selected=2))


# -- (d) the shares add up ----------------------------------------------------------------


def test_eight_shares_of_eight_experts_and_the_shared_expert_once_are_the_whole_layer():
    """64 experts over 8 chips, top-4, scale 1.8: each share routes over all 64 and
    computes its 8; the routed parts add up, with the shared expert counted once,
    to what the reference gives for the layer with all 64 held."""
    def routed_layer(held, first=0):
        return SharedExpertMoe(8, 12, 64, 4, jnp.float32, route_scale=1.8, shared_experts=1,
                               experts_held=held, first_held=first)

    whole = routed_layer(held=0)
    x = jax.random.normal(jax.random.key(0), (2, 40, 8))
    params = moved(whole.init(jax.random.key(1), x)["params"])
    t = types.SimpleNamespace(moe_num_selected=4, moe_route_scale=1.8)
    with jax.default_matmul_precision("highest"):
        want = reference_glm.moe(x, params, t)
        s = params["shared"]
        shared = reference_glm.swiglu(x, s["wg"]["kernel"], s["wi"]["kernel"], s["wo"]["kernel"])
        total, seen = shared, 0
        for share in range(8):
            part = dict(params, **{n: params[n][8 * share : 8 * share + 8] for n in ("wg", "wi", "wo")})
            y, state = routed_layer(held=8, first=8 * share).apply({"params": part}, x, mutable=["intermediates"])
            total = total + (y - shared)
            seen += int(state["intermediates"]["moe_load"][0]["tokens"].sum())
            if share == 0:  # the share the cell holds: what this chip's layer gives is the reference's on the same share
                np.testing.assert_allclose(y, reference_glm.moe(x, part, t), atol=3e-5)
    assert seen == 2 * 40 * 4  # every assignment ran on exactly one share
    np.testing.assert_allclose(total, want, atol=3e-5)
    assert np.abs(np.asarray(total - shared)).max() > 0.05  # the routed parts are no rounding


# -- (e) the whole tower, the train step, and one accumulated step through run.py ---------


def test_loss_and_every_gradient_match_the_plain_reference():
    cfg = tiny_glm(remat=True)
    model = SigLIP(cfg)
    images, tokens = tiny_batch()
    params = moved(model.init(jax.random.key(0), images, tokens)["params"])

    def system_loss(p):
        zimg, ztxt, lp = model.apply({"params": p}, images, tokens)
        return sigmoid_loss(zimg, ztxt, lp["t_prime"], lp["bias"]), ztxt

    def reference_loss(p):
        zimg, ztxt = reference_glm.embeddings(cfg, p, images, tokens)
        return reference_glm._base.sigmoid_loss(zimg, ztxt, p["t_prime"], p["bias"]), ztxt

    with jax.default_matmul_precision("highest"):
        (got_loss, got_z), got = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
        (want_loss, want_z), want = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(got_z, want_z, atol=1e-5)
    errs = reference_glm.tree_max_rel_err(got, want)
    assert len(errs) == len(jax.tree.leaves(params))
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)
    bias_grads = [v for k, v in jax.tree_util.tree_leaves_with_path(got) if SELECT_BIAS in jax.tree_util.keystr(k)]
    assert len(bias_grads) == 2 and not any(np.asarray(g).any() for g in bias_grads)
    # The interface `correct` calls: layer 0 (dense) and layer 1 (routed, without its router), every leaf of both.
    _, first = reference_glm.make_sample_grads(cfg)(params, images, tokens)
    assert set(first["textual"]) == {"block0", "block1"}
    assert set(first["textual"]["block0"]) == {"ln1", "mla", "ln2", "mlp"}
    assert set(first["textual"]["block1"]) == {"ln1", "mla", "ln2", "moe"}
    assert set(first["textual"]["block1"]["mla"]) == {"q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "out"}
    assert set(first["textual"]["block1"]["moe"]) == {"wg", "wi", "wo", "shared"}
    compared = reference_glm.first_blocks(got)["textual"]
    assert max(reference_glm.tree_max_rel_err(compared, first["textual"]).values()) < 2e-4


def test_the_tower_through_the_train_step():
    """The normal path: ``make_train_step`` with accumulation. The trace-time
    record says what the latent-attention stack is made of, beside the routed
    layers' counters in the metrics line; the program carries the names the
    benchmark's readers look for."""
    from distributed_sigmoid_loss_tpu.models.moe import MOE_ROUTE_SCOPE
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        STACK_RECORD_FIELDS,
        STACK_RECORD_MLA_FIELDS,
        validate_metrics,
    )
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

    cfg = tiny_glm(remat=True, scan_layers=True)  # scan_layers does not apply: a dense layer, then routed ones
    model, mesh = SigLIP(cfg), make_mesh(1)
    images, tokens = tiny_batch(8)
    shapes = {"images": jax.ShapeDtypeStruct(images.shape, images.dtype),
              "tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)}
    state = create_train_state(jax.random.key(0), model, make_optimizer(TrainConfig(weight_decay=0.1)), shapes, mesh)
    encoder = state.params["textual"]["encoder"]
    assert set(encoder) == {"block0", "block1", "block2", "ln_final"} and "pos_embed" not in state.params["textual"]
    assert "mlp" in encoder["block0"] and "moe" in encoder["block1"] and "q_a" in encoder["block1"]["mla"]
    step, _ = make_train_step(model, mesh, cfg.loss, accum_steps=2, accum_dtype="bfloat16")
    lowered = step.lower(state, {"images": images, "tokens": tokens}).as_text(debug_info=True)
    for name in ("mla", MLA_CORE_SCOPE, MLA_ROPE_SCOPE, "moe", MOE_ROUTE_SCOPE):
        assert f"/{name}/" in lowered or f"/{name}\"" in lowered, name
    assert f"/mla/{MLA_ROPE_SCOPE}/rope" in lowered  # the rotation sits inside the mixer's path
    before = encoder["block1"]["mla"]["q_norm"]["scale"]
    for _ in range(2):
        state, metrics = step(state, {"images": images, "tokens": tokens})
    metrics = {k: float(v) for k, v in metrics.items()}
    validate_metrics(metrics)
    assert np.isfinite(metrics["loss"]) and metrics["moe_dropped_tokens"] == 0
    assert 0 < metrics["moe_local_assignments"] <= 2 * 2 * 64 * 4  # two microbatches, two routed layers
    assert not np.array_equal(state.params["textual"]["encoder"]["block1"]["mla"]["q_norm"]["scale"], before)
    record = mixed_stack(step)
    assert set(record) <= set(STACK_RECORD_FIELDS) and "kda_core" not in record
    assert record["layer_kinds"] == ["mla+mlp", "mla+moe", "mla+moe"]
    assert (record["experts_held"], record["experts_total"], record["experts_per_token"]) == (4, 16, 4)
    assert set(record["mla"]) == {0, 1, 2} and set(record["mla"][0]) == set(STACK_RECORD_MLA_FIELDS)
    assert record["mla"][1] == {
        "q_rank": 10, "kv_rank": 12, "rotated_dim": 8, "rope_theta": 1e6, "core": "dense",
        "qk_dim": 20, "v_dim": 16, "core_head_dim": 20, "core_v_dim": 16, "padded": False, "block": None, "core_tokens": 16,
    }
    assert step._cache_size() == 1


def test_the_record_of_the_cells_stack_on_a_tpu(monkeypatch):
    """By the rule the mixer runs by: on a TPU in bf16 every latent-attention layer of both cells takes the repo's
    kernel pair in blocks of 512 tokens; this cell's 256-wide heads reach it unpadded, the unrotated cell's 192 / 128
    padded to whole registers, 256 / 128."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train.train_step import stack_record_of

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    _, cfg = cell_config()
    made_of = stack_record_of(cfg.text, (4, 4096))["mla"]
    assert sorted(made_of) == [0, 1, 2, 3, 4]
    assert made_of[0] == {
        "q_rank": 768, "kv_rank": 512, "rotated_dim": 64, "rope_theta": 1e6, "core": "kernel",
        "qk_dim": 256, "v_dim": 256, "core_head_dim": 256, "core_v_dim": 256, "padded": False, "block": 512, "core_tokens": 4096,
    }
    assert all(made_of[i] == made_of[0] for i in made_of)
    _, kimi = cell_config("kimi-b16-p64-s1024")
    assert stack_record_of(kimi.text, (16, 1024))["mla"] == {3: {
        "q_rank": 0, "kv_rank": 512, "rotated_dim": 0, "rope_theta": None, "core": "kernel",
        "qk_dim": 192, "v_dim": 128, "core_head_dim": 256, "core_v_dim": 128, "padded": True, "block": 512, "core_tokens": 1024,
    }}
    # the same on one line, as `train` prints it once a run: a run says without a trace which cores engaged
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack_line

    line = mixed_stack_line(stack_record_of(cfg.text, (4, 4096)))
    assert line.startswith("stack: mla+mlp mla+moe mla+moe mla+moe mla+moe; mla[0] core=kernel heads 256/256, 8 blocks of 512 tokens;")
    line = mixed_stack_line(stack_record_of(kimi.text, (16, 1024)))
    assert "mla[3] core=kernel heads 256/128 (zero-padded), 2 blocks of 512 tokens" in line
    assert "kda[0] core=kernel qk_norm=kernel o_norm=kernel, 16 chunks" in line and mixed_stack_line(None) is None


CONTROLS = ("no_rope", "no_q_norm", "lost_expert", "select_by_score", "fp8")


def test_the_job_rehearses_through_run_py_and_each_planted_fault_fails_it(tmp_path, monkeypatch, capsys):
    """benchmark/run.py end to end on the CPU at a toy size, through
    jobs/train_step.py with the configuration's own modules: `correct` against
    reference_glm.py on the accumulated step, sound true, and false for each of the
    five faults planted through the same `main` (benchmark/tests/controls_glm.py)."""
    import run

    tiny = tiny_glm(remat=True)
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    fields = lambda cfg, names: {n: getattr(cfg, n) for n in names}  # noqa: E731
    modules = harness.Cell(CELL, rehearse=False).config["modules"]
    assert modules == {"reference": "reference_glm", "count": "flops_glm"}
    (tmp_path / "configs" / "tiny-glm.json").write_text(json.dumps({
        "modules": modules,
        "vision": fields(tiny.vision, ["image_size", "patch_size", "width", "depth", "num_heads", "embed_dim", "use_proj", "dtype"]),
        "text": fields(tiny.text, ["vocab_size", "width", "num_heads", "embed_dim", "dtype", *GLM]),
        "loss": {},
    }))
    mix = harness.load_json(os.path.join(BENCH_DIR, "traffic", "p16-s4096.json"))
    mix.update(pairs_per_chip_per_step=8, microbatch=4)
    mix["step"]["accum_steps"] = 2
    (tmp_path / "traffic" / "p8-s16.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-glm", "file": "configs/tiny-glm.json"}],
        "workloads": [{"name": "tiny-glm-p8-s16", "config": "tiny-glm", "traffic": "p8-s16", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }))
    monkeypatch.setattr(harness, "REHEARSAL_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "prepare_environment", lambda cell, rehearse: None)
    seed = "3500000007"
    assert run.main(["--rehearse", "--workload", "tiny-glm-p8-s16", "--seed", seed, "--seconds", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0 and lines[-1]["metrics"] == {}
    assert all(lines[0]["verdicts"].values()), lines[0]
    assert lines[0]["grad_err"] < 1e-3 and lines[0]["embed_err"] < 1e-4

    from distributed_sigmoid_loss_tpu import train
    from distributed_sigmoid_loss_tpu.models import transformer

    controls = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_glm.py"))
    kept = (train.create_train_state, transformer.rope, nn.RMSNorm.__call__, nn.Dense.__call__)
    runs = [arg for name in CONTROLS for arg in ("--run", f"{name}:{seed}")]
    assert controls.main(["--rehearse", "--workload", "tiny-glm-p8-s16", "--seconds", "1",
                          "--read", f"biased:{seed}", "--read", f"sound:{seed}", *runs]) == 0
    assert kept == (train.create_train_state, transformer.rope, nn.RMSNorm.__call__, nn.Dense.__call__)  # it undoes its patches
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    done = {r["variant"]: r for r in lines[-1]["runs"]}
    assert sorted(done) == sorted(CONTROLS)
    assert [(r["correct"], r["failed"]) for r in done.values()] == [(False, 0)] * 5
    over = {name: sorted(k for k, (value, limit) in r["compared"].items() if isinstance(value, float) and value > limit)
            for name, r in done.items()}
    assert "embed_err" in over["no_rope"] and "embed_err" in over["select_by_score"] and "embed_err" in over["fp8"]
    assert done["lost_expert"]["compared"]["grad_err"][0] == 1.0  # the lost expert's gate and up stacks: no gradient at all
    assert done["no_q_norm"]["compared"]["grad_err"][0] >= 1.0  # the norm's scale gets none either
    reads = {line["variant"]: line for line in lines if line.get("info") == "controls.read"}
    for variant in ("biased", "sound"):  # the program selects by s + b, as the reference does
        assert reads[variant]["grad_err"] < 1e-3 and reads[variant]["embed_err"] < 1e-4


# -- (f) the benchmark's configuration and count -------------------------------------------


def cell_config(name=CELL):
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", "train_step.py"))
    cell = harness.Cell(name, rehearse=False)
    return cell, job.build_config(cell.config, cell.traffic)


def test_the_cell_is_the_published_model_cut_as_written():
    cell, cfg = cell_config()
    t, published = cfg.text, cell.config
    catalog = None
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(guide):  # every published key, as the catalog has it
        catalog = next(row for row in map(json.loads, open(guide)) if row["name"] == "GLM-4.7-Flash")
        assert published["source"] == catalog["source_url"]
        assert {k: published[k] for k in catalog["config"]} == catalog["config"]
    assert published["model_type"] == "glm4_moe_lite"
    assert (t.width, t.norm_eps, t.num_heads) == (published["hidden_size"], published["rms_norm_eps"], published["num_attention_heads"])
    assert (t.mla_qk_nope_dim, t.mla_qk_shared_dim, t.mla_v_dim, t.mla_kv_rank, t.mla_q_rank) == (
        published["qk_nope_head_dim"], published["qk_rope_head_dim"], published["v_head_dim"], published["kv_lora_rank"],
        published["q_lora_rank"]) == (192, 64, 256, 512, 768)
    assert t.pos == "rope" and t.rope_theta == published["rope_theta"] == 1e6 and published["partial_rotary_factor"] == 1
    assert round(t.width * t.mlp_ratio) == published["intermediate_size"] == 10240
    assert (t.moe_hidden, t.moe_experts, t.moe_num_selected, t.moe_shared_experts, t.moe_route_scale) == (
        published["moe_intermediate_size"], published["n_routed_experts"], published["num_experts_per_tok"],
        published["n_shared_experts"], published["routed_scaling_factor"]) == (1536, 64, 4, 1, 1.8)
    assert t.moe_router == "sigmoid" and published["norm_topk_prob"] and published["n_group"] == published["topk_group"] == 1
    assert list(t.mixers) == ["mla"] * 5 and t.leading_dense_layers == published["first_k_dense_replace"] == 1
    assert (t.causal, t.pool, t.norm, t.mlp, t.use_bias, t.dtype) == (True, "last", "rmsnorm", "swiglu", False, "bfloat16")
    # the cut: depth, the experts held, the vocabulary; nothing else
    assert published["reduced"] == ["text.depth", "text.moe_experts_held", "text.vocab_size"]
    assert (t.depth, published["num_hidden_layers"]) == (5, 47)
    assert (t.moe_experts_held, published["n_routed_experts"]) == (8, 64)
    assert (t.vocab_size * 8, published["vocab_size"]) == (154880, 154880)
    assert "8 chips share each layer" in published["deployment"] and t.context_length == 4096
    b16 = harness.load_json(os.path.join(BENCH_DIR, "configs", "siglip-b16-224.json"))
    assert published["vision"] == b16["vision"]  # the B/16 image tower, field for field
    mix = cell.traffic
    assert (mix["pairs_per_chip_per_step"], mix["microbatch"], mix["step"]["accum_steps"], cell.chips) == (16, 4, 4, 1)
    kimi = harness.load_json(os.path.join(BENCH_DIR, "traffic", "p64-s1024.json"))
    same = ("job", "chips", "mesh", "towers", "loss", "optimizer", "step", "batch", "sync_every", "trace_steps", "check_rows")
    assert {k: mix[k] for k in same} == {k: kimi[k] for k in same} and mix["reference_slice_rows"] == 1


def test_the_text_tower_has_the_parameters_the_shapes_say():
    _, cfg = cell_config()
    shapes = jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32), jax.ShapeDtypeStruct((1, 4096), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(nn.meta.unbox(tree)))  # noqa: E731
    w = 2048
    mla = w * 768 + 768 + 768 * 20 * 256 + w * 576 + 512 + 512 * 20 * 448 + 20 * 256 * w  # + the two latents' norms
    dense, shared, experts, router = 3 * w * 10240, 3 * w * 1536, 8 * 3 * w * 1536, w * 64 + 64
    norms = 2 * w
    encoder = shapes["textual"]["encoder"]
    assert count(encoder["block0"]) == mla + dense + norms
    assert count(encoder["block1"]) == mla + shared + experts + router + norms
    assert [round(n / 1e6, 2) for n in (mla, dense, shared, router, experts)] == [21.76, 62.91, 9.44, 0.13, 75.5]
    assert round((mla + shared + experts + router + norms) / 1e6, 1) == 106.8  # ISSUE 35: a routed layer
    stack = 5 * mla + dense + 4 * (shared + experts + router) + 5 * norms + w
    assert count(encoder) == stack and round(stack / 1e6, 1) == 512.0
    assert count(shapes["textual"]) == stack + 19360 * w + w * 768 + 768
    assert round(count(shapes["textual"]) / 1e6, 1) == 553.2  # 512.0 + 39.6 + 1.6
    assert round(count(shapes) / 1e6, 1) == 646.1  # with the B/16 image tower and the loss's two scalars
    assert "pos_embed" not in shapes["textual"]


def test_the_count_is_the_issues():
    """benchmark/tests/test_flops_glm.py has the count by hand; tier-1 keeps its ends."""
    _, cfg = cell_config()
    assert round(flops_glm.text_forward_flops(cfg.text) / 4096 / 1e6) == 667  # MFLOP a text token
    assert round(flops_glm.train_flops_per_pair(cfg) / 1e12, 2) == 8.31  # ISSUE 35: 8.3 TFLOP a pair
    assert flops_glm.expected_assignments_per_token(cfg.text) == 0.5
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops_glm.attention_least_s(cfg, "textual", 16, peaks) == 0.0
    assert flops_glm.attention_least_s(cfg, "visual", 16, peaks) > 0.0
    assert round(1e3 * flops_glm.mla_least_s(cfg, 16, peaks), 1) == 209.3
