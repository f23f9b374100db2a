"""The text tower's block options (``TextConfig.norm`` / ``sandwich_norm`` / ``mlp`` /
``use_bias`` / ``pos`` / ``loops``): a language-model-class text tower whose stack
runs several times on one set of weights, against its plain reference
(``benchmark/reference_looped.py``), against the SigLIP block it must leave
alone, and against the code that re-implements that block and must refuse it."""

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
from distributed_sigmoid_loss_tpu.models.transformer import (
    Attention,
    Encoder,
    Mlp,
    rope,
)
from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
    short_self_attention,
)
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import sigmoid_loss
from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention
from distributed_sigmoid_loss_tpu.utils.config import (
    BLOCK_OPTIONS,
    SigLIPConfig,
    TextConfig,
    ViTConfig,
)

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import flops_looped  # noqa: E402
import harness  # noqa: E402
import reference_looped  # noqa: E402

# The options of the looped language-model block, at a toy size.
LOOPED = dict(
    norm="rmsnorm", sandwich_norm=True, mlp="swiglu", use_bias=False, pos="rope",
    rope_theta=1e6, loops=4, causal=True, pool="last", mlp_ratio=2.75,
)


def tiny_looped(scan_layers: bool, **over) -> SigLIPConfig:
    text = dataclasses.replace(TextConfig.tiny_test(), scan_layers=scan_layers, **{**LOOPED, **over})
    vision = dataclasses.replace(ViTConfig.tiny_test(), scan_layers=scan_layers)
    return SigLIPConfig(vision=vision, text=text)


def unboxed(tree):
    return nn.meta.unbox(tree)


def tiny_batch(n=4):
    images = jax.random.normal(jax.random.key(1), (n, 16, 16, 3), jnp.float32)
    tokens = jax.random.randint(jax.random.key(2), (n, 8), 0, 64)
    return images, tokens


# -- the system against the plain reference ------------------------------------


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_loss_and_every_gradient_match_the_plain_reference(scan_layers):
    cfg = tiny_looped(scan_layers)
    model = SigLIP(cfg)
    images, tokens = tiny_batch()
    params = unboxed(model.init(jax.random.key(0), images, tokens)["params"])
    # Norm scales start at one and the head's bias at zero: move every leaf so a
    # dropped scale or a dropped leaf shows.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(3), len(leaves))
    params = jax.tree.unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype) if x.ndim else x
        for x, k in zip(leaves, keys)
    ])

    def system_loss(p):
        zimg, ztxt, lp = model.apply({"params": p}, images, tokens)
        return sigmoid_loss(zimg, ztxt, lp["t_prime"], lp["bias"])

    def reference_loss(p):
        zimg, ztxt = reference_looped.embeddings(cfg, p, images, tokens)
        return reference_looped._base.sigmoid_loss(zimg, ztxt, p["t_prime"], p["bias"])

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(system_loss))(params)
        want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    errs = reference_looped.tree_max_rel_err(got, want)
    assert len(errs) == len(leaves)
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)
    # The interface `correct` calls: the first layer's gradient, summed over the passes.
    _, first = reference_looped.make_sample_grads(cfg)(params, images, tokens)
    stack = reference_looped.first_blocks(got)["textual"]
    assert max(reference_looped.tree_max_rel_err(stack, first["textual"]).values()) < 2e-4


def test_the_reference_refuses_another_block():
    cfg = tiny_looped(True, mlp="gelu")
    with pytest.raises(ValueError, match="mlp"):
        reference_looped.make_batch_loss(cfg, 2, reference_looped.microbatch_rows(4, 1, 1))


# -- the SigLIP block is left alone --------------------------------------------


class _SeedBlock(nn.Module):
    """The block as it stood before the options: what `loops = 1` with the old
    options has to stay, leaf for leaf and bit for bit."""

    width: int
    num_heads: int
    mlp_ratio: float
    dtype: object
    causal: bool = False

    @nn.compact
    def __call__(self, x):
        x = x + Attention(self.width, self.num_heads, self.dtype, causal=self.causal, name="attn")(
            nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        )
        return x + Mlp(self.width, self.mlp_ratio, self.dtype, name="mlp")(
            nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        )


class _SeedEncoder(nn.Module):
    depth: int
    scan_layers: bool

    @nn.compact
    def __call__(self, x):
        args = (32, 2, 4, jnp.float32)
        if self.scan_layers:
            class Body(nn.Module):
                @nn.compact
                def __call__(self, carry, _):
                    return _SeedBlock(*args, causal=True, name="block")(carry), None

            x, _ = nn.scan(
                Body, variable_axes={"params": 0}, split_rngs={"params": True},
                length=self.depth, metadata_params={nn.PARTITION_NAME: None},
            )(name="blocks")(x, None)
        else:
            for i in range(self.depth):
                x = _SeedBlock(*args, causal=True, name=f"block{i}")(x)
        return nn.LayerNorm(dtype=jnp.float32, name="ln_final")(x)


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_one_loop_with_the_old_options_is_the_seed_encoder_bit_for_bit(scan_layers):
    x = jax.random.normal(jax.random.key(4), (2, 8, 32), jnp.float32)
    new = Encoder(32, 2, 2, 4, jnp.float32, scan_layers=scan_layers, causal=True)
    old = _SeedEncoder(2, scan_layers)

    def run(module):
        params = unboxed(module.init(jax.random.key(0), x)["params"])
        out, grads = jax.value_and_grad(lambda p: (module.apply({"params": p}, x) ** 2).sum())(params)
        return params, out, grads

    for got, want in zip(jax.tree.leaves_with_path(run(new)), jax.tree.leaves_with_path(run(old))):
        assert got[0] == want[0]
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1])), jax.tree_util.keystr(got[0])


def test_the_default_text_tower_keeps_its_parameter_tree():
    model = SigLIP(SigLIPConfig.tiny_test())
    params = unboxed(jax.eval_shape(model.init, jax.random.key(0), *tiny_batch())["params"])
    text = params["textual"]
    assert set(text) == {"token_embed", "pos_embed", "encoder", "map_head", "proj"}
    assert set(text["encoder"]) == {"block0", "block1", "ln_final"}
    assert set(text["encoder"]["block0"]) == {"ln1", "attn", "ln2", "mlp"}
    assert set(text["encoder"]["block0"]["mlp"]) == {"wi", "wo"}
    assert set(text["encoder"]["block0"]["attn"]["q"]) == {"kernel", "bias"}


# -- the loop -------------------------------------------------------------------


def looped_encoder(loops, scan_layers=True):
    from distributed_sigmoid_loss_tpu.models.transformer import BlockStyle

    style = BlockStyle(norm="rmsnorm", sandwich_norm=True, mlp="swiglu", use_bias=False, rope_theta=1e4)
    return Encoder(32, 2, 2, 2.75, jnp.float32, scan_layers=scan_layers, remat=True,
                   causal=True, style=style, loops=loops)


def test_four_loops_are_four_calls_of_one_stack_and_the_gradient_is_their_sum():
    x = jax.random.normal(jax.random.key(5), (2, 8, 32), jnp.float32)
    looped, once = looped_encoder(4), looped_encoder(1)
    params = unboxed(looped.init(jax.random.key(0), x)["params"])
    assert set(params) == {"loop"}  # one set of weights, under a path of its own
    stack = params["loop"]
    assert jax.tree.structure(stack) == jax.tree.structure(
        unboxed(jax.eval_shape(once.init, jax.random.key(0), x)["params"])
    )

    def explicit(per_pass):  # one copy of the weights for each pass
        h = x
        for p in per_pass:
            h = once.apply({"params": p}, h)
        return h

    np.testing.assert_allclose(
        looped.apply({"params": params}, x), explicit([stack] * 4), rtol=1e-5, atol=1e-5
    )
    # A random direction: after the final norm, sum(out^2) hardly depends on the weights.
    w = jax.random.normal(jax.random.key(6), x.shape, jnp.float32)
    got = jax.grad(lambda p: (looped.apply({"params": p}, x) * w).sum())(params)["loop"]
    per_pass = jax.grad(lambda ps: (explicit(ps) * w).sum())([stack] * 4)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    errs = reference_looped.tree_max_rel_err(got, summed)
    assert max(errs.values()) < 1e-5, errs
    # Not one pass's gradient four times over: the passes differ.
    first = jax.tree.leaves(per_pass[0])[0]
    assert not np.allclose(4 * first, jax.tree.leaves(summed)[0], rtol=1e-2)


def test_the_looped_tower_through_the_train_step_names_its_parts():
    """`make_train_step` on the looped tower: the lowered program carries the
    flax path `loop` and the scope `rope` (what benchmark/scopes_looped.py cuts
    a profile by), and one step moves the one set of weights."""
    import re

    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig, TrainConfig

    model = SigLIP(tiny_looped(True, remat=True))
    mesh = make_mesh(1, devices=jax.devices()[:1])
    images, tokens = tiny_batch(8)
    batch = {"images": images, "tokens": tokens}
    step, _ = make_train_step(model, mesh, LossConfig(variant="ring"), accum_steps=2, accum_dtype="bfloat16")
    state = create_train_state(
        jax.random.key(0), model, make_optimizer(TrainConfig(warmup_steps=0)), batch, mesh
    )
    before = jax.device_get(state.params["textual"]["encoder"]["loop"]["blocks"]["block"]["mlp"]["wg"]["kernel"])
    assert before.shape == (2, 32, 88)  # (depth, width, hidden): not four copies
    compiled = step.lower(state, batch).compile()
    paths = set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))
    parts = [set(re.split(r"[/()]", p)) for p in paths]
    # As benchmark/scopes_looped.py cuts a profile: by whole components of the path.
    assert any({"textual", "loop", "rope", "transpose"} <= p for p in parts)  # rope's backward
    assert any({"textual", "loop", "ln_final"} <= p for p in parts)  # the final norm, inside the loop
    assert not any({"visual", "loop"} <= p or {"visual", "rope"} <= p for p in parts)
    state, metrics = compiled(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    after = jax.device_get(state.params["textual"]["encoder"]["loop"]["blocks"]["block"]["mlp"]["wg"]["kernel"])
    assert not np.array_equal(before, after)


# -- rotary positions -----------------------------------------------------------


def test_rotary_matches_the_complex_form():
    """rotate-half pairs lane i with lane i + dh/2: as complex numbers
    x_i + j x_{i+dh/2}, position p multiplies pair i by exp(j p theta^(-2i/dh))."""
    b, s, h, dh, theta = 2, 16, 3, 8, 1e6
    x = np.asarray(jax.random.normal(jax.random.key(6), (b, s, h, dh), jnp.float32), np.float64)
    z = x[..., : dh // 2] + 1j * x[..., dh // 2:]
    angle = np.arange(s)[:, None] * theta ** (-np.arange(0, dh, 2) / dh)
    rotated = z * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([rotated.real, rotated.imag], -1)
    np.testing.assert_allclose(rope(jnp.asarray(x, jnp.float32), theta), want, atol=1e-5)
    np.testing.assert_array_equal(rope(jnp.asarray(x, jnp.float32), theta)[:, 0], x[:, 0].astype(np.float32))


# -- the attention kernel at a head of 128 lanes --------------------------------


def test_causal_short_attention_at_heads_of_128_matches_dense():
    """One head fills one 128-lane slab (the looped tower's dh): the kernels' lane
    slices are whole slabs, and causal masks the key-major backward's tile."""
    rng = np.random.default_rng(0)
    q, k, v, w = (jnp.asarray(rng.standard_normal((2, 64, 2, 128)), jnp.float32) for _ in range(4))
    np.testing.assert_allclose(
        short_self_attention(q, k, v, True, None, True), dense_attention(q, k, v, causal=True), atol=2e-5
    )

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda q, k, v: short_self_attention(q, k, v, True, None, True))
    want = grads(lambda q, k, v: dense_attention(q, k, v, causal=True))
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=2e-4)


# -- code that re-implements or maps the SigLIP block refuses another ------------

CHANGED = dict(
    norm="rmsnorm", sandwich_norm=True, mlp="swiglu", use_bias=False, pos="rope", loops=4,
    # the options of a stack of several layer kinds (tests/test_hybrid_tower.py)
    norm_eps=1e-5, mixers=("kda", "mla"), leading_dense_layers=1, moe_router="sigmoid",
    moe_route_scale=2.446, moe_shared_experts=1, moe_hidden=24, moe_experts_held=4,
    # latent attention's query latent (tests/test_glm_tower.py)
    mla_q_rank=8,
    # an RMSNorm stored as an offset from 1 (tests/test_eva_tower.py)
    norm_unit_offset=True,
    # a one-sub-layer stack's, grouped heads' and a shared expert's own width (tests/test_nemotron_tower.py)
    sublayers="single", num_kv_heads=1, head_dim=8, moe_shared_hidden=40,
    # an attention layer's window, rotation by layer kind, head norms and gate, and the embedding's scale
    # (tests/test_trinity_tower.py)
    attn_windows=(4, 0), rope_layers="window", attn_qk_norm=True, attn_gate=True, embed_scale=2.0,
)


def test_every_block_option_has_a_refusal_case():
    assert set(CHANGED) == set(BLOCK_OPTIONS)


@pytest.mark.parametrize("option", sorted(CHANGED))
def test_pipelined_towers_refuse_the_option_by_name(option):
    from distributed_sigmoid_loss_tpu.parallel.pp_towers import validate_pp_tower

    cfg = dataclasses.replace(TextConfig.tiny_test(), scan_layers=True, **{option: CHANGED[option]})
    with pytest.raises(ValueError, match=rf"\b{option}="):
        validate_pp_tower(cfg, 2, "text")
    validate_pp_tower(dataclasses.replace(TextConfig.tiny_test(), scan_layers=True), 2, "text")
    validate_pp_tower(dataclasses.replace(ViTConfig.tiny_test(), scan_layers=True), 2, "vision")


def test_hf_import_refuses_another_block_by_name():
    from distributed_sigmoid_loss_tpu.models.hf_import import params_from_hf

    cfg = SigLIPConfig(
        vision=dataclasses.replace(ViTConfig.tiny_test(), use_proj=False),
        text=dataclasses.replace(TextConfig.tiny_test(), pool="last", mlp="swiglu", loops=4),
    )
    with pytest.raises(ValueError, match=r"mlp='swiglu', loops=4"):
        params_from_hf({}, cfg)


def test_the_step_lattice_has_no_block_axis_and_its_pp_axis_refuses():
    """analysis/config_space.py: block options are tower shape, outside the
    lattice; `pp`, the one axis whose builder re-implements the block, refuses."""
    from distributed_sigmoid_loss_tpu.analysis import config_space

    assert not set(BLOCK_OPTIONS) & set(config_space.AXES)
    assert "BLOCK_OPTIONS" in config_space.probe_imperative.__doc__


@pytest.mark.parametrize("bad, match", [
    (dict(pos="rope", sequence_parallel_axis="sp"), "rope"),
    (dict(mlp="swiglu", moe_experts=2), "moe_experts"),
    (dict(norm="batchnorm"), "norm"),
    (dict(pos="alibi"), "pos"),
])
def test_combinations_that_are_not_built_say_so(bad, match):
    cfg = dataclasses.replace(TextConfig.tiny_test(), **bad)
    from distributed_sigmoid_loss_tpu.models.text import TextTransformer

    with pytest.raises(ValueError, match=match):
        jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tiny_batch()[1])


# -- the benchmark's configuration, count and job --------------------------------


def cell_config():
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", "train_step_looped.py"))
    cell = harness.Cell("ouro-b16-mb32x2", rehearse=False)
    return cell, job.build_config(cell.config, cell.traffic)


def test_the_cell_is_the_published_model_with_one_set_of_weights():
    cell, cfg = cell_config()
    t = cfg.text
    published = cell.config
    assert (t.width, t.num_heads, t.width // t.num_heads) == (
        published["hidden_size"], published["num_attention_heads"], published["head_dim"])
    assert round(t.width * t.mlp_ratio) == published["intermediate_size"] == 5632
    assert (t.vocab_size, t.loops, t.rope_theta) == (
        published["vocab_size"], published["total_ut_steps"], published["rope_theta"])
    assert published["num_hidden_layers"] == 48 and t.depth == 8 and cell.config["reduced"] == ["text.depth"]
    b16 = harness.load_json(os.path.join(BENCH_DIR, "configs", "siglip-b16-224.json"))
    assert cell.config["vision"] == b16["vision"]  # the B/16 image tower, field for field
    shapes = jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32), jax.ShapeDtypeStruct((1, 256), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(unboxed(tree)))  # noqa: E731
    layer = 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048
    assert count(shapes["textual"]) == 8 * layer + 49152 * 2048 + 2048 + 2048 * 768 + 768  # 513.3 M, not 4 x
    assert round(count(shapes["textual"]) / 1e6, 1) == 513.3


def test_the_looped_count_is_the_issues_and_flops_py_where_nothing_loops():
    """benchmark/tests/test_flops_looped.py has the count by hand; tier-1 keeps its
    two ends: 894.4 G a pair here, flops.py for the cells that were there."""
    import flops

    _, cfg = cell_config()
    assert round(flops_looped.forward_flops_per_pair(cfg) / 1e9, 1) == 894.4
    for name in ("siglip-b16-224", "siglip-so400m-14-224"):
        body = harness.load_json(os.path.join(BENCH_DIR, "configs", name + ".json"))
        old = types.SimpleNamespace(vision=types.SimpleNamespace(**body["vision"]),
                                    text=types.SimpleNamespace(**body["text"]))
        assert flops_looped.train_flops_per_pair(old) == flops.train_flops_per_pair(old)


def test_the_looped_job_rehearses_through_run_py(tmp_path, monkeypatch, capsys):
    """benchmark/run.py end to end on the CPU at a toy size, through
    jobs/train_step_looped.py: `correct` against reference_looped.py on the
    accumulated step, and the job's two counters."""
    import run

    tiny = tiny_looped(True, remat=True)
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    fields = lambda cfg, names: {n: getattr(cfg, n) for n in names}  # noqa: E731
    (tmp_path / "configs" / "tiny-looped.json").write_text(json.dumps({
        "vision": fields(tiny.vision, ["image_size", "patch_size", "width", "depth", "num_heads", "embed_dim", "use_proj", "dtype"]),
        "text": fields(tiny.text, ["vocab_size", "context_length", "width", "depth", "num_heads", "embed_dim", "dtype", *LOOPED]),
        "loss": {},
    }))
    mix = harness.load_json(os.path.join(BENCH_DIR, "traffic", "mb32x2.json"))
    mix.update(pairs_per_chip_per_step=8, microbatch=4, reference_slice_rows=4)
    (tmp_path / "traffic" / "mb4x2.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-looped", "file": "configs/tiny-looped.json"}],
        "workloads": [{"name": "tiny-looped-mb4x2", "config": "tiny-looped", "traffic": "mb4x2", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }))
    monkeypatch.setattr(harness, "REHEARSAL_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "prepare_environment", lambda cell, rehearse: None)
    seen = {}
    real_load = harness.load_module

    def load(path):  # the job's counters are not printed by a rehearsal: keep them
        module = real_load(path)
        if path.endswith("train_step_looped.py"):
            inner = module.run
            module.run = lambda ctx: seen.setdefault("out", inner(ctx))
        return module

    monkeypatch.setattr(harness, "load_module", load)
    assert run.main(["--rehearse", "--workload", "tiny-looped-mb4x2", "--seed", "2500000007", "--seconds", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0 and lines[-1]["metrics"] == {}
    assert all(lines[0]["verdicts"].values()), lines[0]
    assert seen["out"]["counters"]["loops"] == 4 and seen["out"]["counters"]["layer_applications"] == 8
