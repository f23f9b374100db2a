"""A text tower of several layer kinds (``TextConfig.mixers``: gated delta-rule
and latent-attention layers, a leading dense layer, sigmoid-routed dropless
experts with a shared expert and a chip's share of them) against its plain
reference (``benchmark/reference_kimi.py``), through the train step and the
benchmark's job, the benchmark's configuration and count, and the defaults it
must leave alone. tests/test_hybrid_layers.py has the layers one by one."""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.models.moe import SELECT_BIAS
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import sigmoid_loss
from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig, TextConfig, ViTConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import flops_kimi  # noqa: E402
import harness  # noqa: E402
import reference_kimi  # noqa: E402

# The cell's stack at a toy size: five layers in its order, 16 experts of which 4 are held.
HYBRID = dict(
    context_length=16, depth=5, causal=True, pool="last", norm="rmsnorm", norm_eps=1e-5,
    mlp="swiglu", use_bias=False, pos="none", mixers=("kda", "kda", "kda", "mla", "kda"),
    leading_dense_layers=1, kda_head_dim=16, mla_qk_nope_dim=16, mla_qk_shared_dim=8,
    mla_v_dim=16, mla_kv_rank=12, moe_experts=16, moe_num_selected=4, moe_router="sigmoid",
    moe_route_scale=2.446, moe_shared_experts=1, moe_hidden=24, moe_experts_held=4,
)


def tiny_hybrid(**over) -> SigLIPConfig:
    text = dataclasses.replace(TextConfig.tiny_test(), **{**HYBRID, **over})
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


# -- (e) the whole tower, and one accumulated step through run.py ---------------------


def test_loss_and_every_gradient_match_the_plain_reference():
    cfg = tiny_hybrid(remat=True)
    model = SigLIP(cfg)
    images, tokens = tiny_batch()
    params = moved(model.init(jax.random.key(0), images, tokens)["params"])

    def system_loss(p):
        zimg, ztxt, lp = model.apply({"params": p}, images, tokens)
        return sigmoid_loss(zimg, ztxt, lp["t_prime"], lp["bias"])

    def reference_loss(p):
        zimg, ztxt = reference_kimi.embeddings(cfg, p, images, tokens)
        return reference_kimi._base.sigmoid_loss(zimg, ztxt, p["t_prime"], p["bias"])

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(system_loss))(params)
        want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(params)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    errs = reference_kimi.tree_max_rel_err(got, want)
    assert len(errs) == len(jax.tree.leaves(params))
    assert max(errs.values()) < 2e-4, max(errs, key=errs.get)
    bias_grads = [v for k, v in jax.tree_util.tree_leaves_with_path(got) if SELECT_BIAS in jax.tree_util.keystr(k)]
    assert len(bias_grads) == 4 and not any(np.asarray(g).any() for g in bias_grads)
    # The interface `correct` calls: layer 1 (kda) and layer 4 (mla + experts), every leaf of both.
    _, first = reference_kimi.make_sample_grads(cfg)(params, images, tokens)
    assert set(first["textual"]) == {"block0", "block3"}
    assert set(first["textual"]["block3"]) == {"ln1", "mla", "ln2", "moe"}
    assert set(first["textual"]["block3"]["moe"]) == {"wg", "wi", "wo", "shared"}  # the router is compared above, in float32
    compared = reference_kimi.first_blocks(got)["textual"]
    assert max(reference_kimi.tree_max_rel_err(compared, first["textual"]).values()) < 2e-4


def test_the_measures_let_a_token_go_the_other_way_and_no_expert_idle():
    """`correct`'s measures on this configuration (reference_kimi.py): an expert's
    gradient from three rows may be one late token's, and that token may go to
    another expert in bf16, so of the experts' stacks the measure asks only that
    no expert the reference trains is left without any gradient; of three
    embedding rows the best one counts; every other leaf by its l2 norm."""
    rng = np.random.default_rng(0)
    per_token = rng.normal(size=(96, 8, 6)) * np.r_[np.full(95, 0.01), 1.0][:, None, None]  # the last token weighs it all
    stack = np.stack([per_token.sum(0), 100 * rng.normal(size=(8, 6)), np.zeros((8, 6))])  # the third: no token

    def errs(got, scale=np.ones(4)):
        tree = lambda x, s: {"textual": {"block3": {"moe": {"wg": x}, "ln1": {"scale": s}}}}  # noqa: E731
        return reference_kimi.tree_max_rel_err(tree(got, scale), tree(stack, np.ones(4)))

    def err(got):
        return errs(got)["['textual']['block3']['moe']['wg']"]

    flipped, lost, idle = stack.copy(), stack.copy(), stack.copy()
    flipped[0] -= per_token[-1]  # what is left is a tenth of the expert's gradient
    lost[0] = 0.0
    idle[2] = 1.0  # a gradient where the reference has none: nothing to be idle against
    assert np.linalg.norm(flipped[0]) < 0.2 * np.linalg.norm(stack[0])
    assert err(stack) == 0.0 and err(flipped) == 0.0 and err(idle) == 0.0 and err(lost) == 1.0
    assert np.linalg.norm(lost - stack) / np.linalg.norm(stack) < 0.2  # the whole leaf's norm would hide the lost one
    assert errs(stack, np.full(4, 1.5))["['textual']['block3']['ln1']['scale']"] == pytest.approx(0.5)
    z = rng.normal(size=(3, 16))
    z /= np.linalg.norm(z, axis=-1, keepdims=True)
    two_rows, every_row = z.copy(), z + 0.1 * rng.normal(size=z.shape)
    two_rows[1:] += 0.1 * rng.normal(size=(2, 16))
    assert reference_kimi.max_rel_err(two_rows, z) == 0.0 and 0.2 < reference_kimi.max_rel_err(every_row, z) < 0.6
    assert reference_kimi.max_rel_err(np.full_like(z, np.nan), z) == float("inf")


def test_the_reference_refuses_another_stack():
    with pytest.raises(ValueError, match="moe_router"):
        reference_kimi.make_sample_grads(tiny_hybrid(moe_router="softmax", mlp="gelu", use_bias=True,
                                                     moe_num_selected=2, mixers=()))


def test_the_hybrid_tower_through_the_train_step(tmp_path):
    """The normal path: ``make_train_step`` with accumulation. The selection bias
    gets no optimizer state and does not move; the counters are in the metrics
    line; the trace-time record says what the stack is; the program carries the
    names the benchmark's readers look for."""
    import optax

    from distributed_sigmoid_loss_tpu.models.mixers import KDA_CORE_SCOPE, MLA_CORE_SCOPE
    from distributed_sigmoid_loss_tpu.models.moe import MOE_ROUTE_SCOPE
    from distributed_sigmoid_loss_tpu.obs.attribution import accum_placement, mixed_stack
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import validate_metrics
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.train.train_step import NO_UPDATE_LEAVES
    from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

    assert NO_UPDATE_LEAVES == (SELECT_BIAS,)
    cfg = tiny_hybrid(remat=True, scan_layers=True)  # scan_layers does not apply to a mixed stack
    model, mesh = SigLIP(cfg), make_mesh(1)
    images, tokens = tiny_batch(8)
    shapes = {"images": jax.ShapeDtypeStruct(images.shape, images.dtype),
              "tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)}
    state = create_train_state(jax.random.key(0), model, make_optimizer(TrainConfig(weight_decay=0.1)), shapes, mesh)
    encoder = state.params["textual"]["encoder"]
    assert set(encoder) == {"block0", "block1", "block2", "block3", "block4", "ln_final"}
    assert "mlp" in encoder["block0"] and "moe" in encoder["block1"] and "mla" in encoder["block3"]
    masked = [x for x in jax.tree.leaves(state.opt_state, is_leaf=lambda x: isinstance(x, optax.MaskedNode))
              if isinstance(x, optax.MaskedNode)]
    assert len(masked) == 2 * 4  # both adam moments of four selection biases: no state
    bias = {"b": 0.3 * jax.random.normal(jax.random.key(9), (16,))}
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: jax.device_put(bias["b"] + 0, x.sharding) if SELECT_BIAS in jax.tree_util.keystr(path) else x, state.params)
    state = state.replace(params=params)
    step, _ = make_train_step(model, mesh, cfg.loss, accum_steps=2, accum_dtype="bfloat16")
    assert mixed_stack(step) is None
    lowered = step.lower(state, {"images": images, "tokens": tokens}).as_text(debug_info=True)
    for name in ("kda", KDA_CORE_SCOPE, MLA_CORE_SCOPE, "moe", MOE_ROUTE_SCOPE):
        assert f"/{name}/" in lowered or f"/{name}\"" in lowered, name
    before = state.params["textual"]["encoder"]["block2"]["kda"]["A_log"]
    for _ in range(2):
        state, metrics = step(state, {"images": images, "tokens": tokens})
    metrics = {k: float(v) for k, v in metrics.items()}
    validate_metrics(metrics)
    assert np.isfinite(metrics["loss"]) and metrics["moe_dropped_tokens"] == 0
    assert 0 < metrics["moe_local_assignments"] <= 2 * 4 * 64 * 4  # two microbatches, four layers
    assert metrics["moe_max_expert_tokens"] >= metrics["moe_mean_expert_tokens"] > 0
    after = state.params["textual"]["encoder"]
    for i in (1, 2, 3, 4):  # read, never written: no gradient, no decay, no update
        np.testing.assert_array_equal(after[f"block{i}"]["moe"][SELECT_BIAS], bias["b"])
    assert not np.array_equal(after["block2"]["kda"]["A_log"], before)
    record = mixed_stack(step)
    assert record["layer_kinds"] == ["kda+mlp", "kda+moe", "kda+moe", "mla+moe", "kda+moe"]
    assert (record["experts_held"], record["experts_total"], record["experts_per_token"]) == (4, 16, 4)
    assert record["expected_local_assignments_per_token"] == 1.0
    assert record["tokens_per_microbatch"] == 64 and record["dispatch_rows_bound"] == 256
    assert accum_placement(step)["layer_loop_bytes"] == 0  # ViTConfig.tiny_test() is unrolled too
    assert step._cache_size() == 1


def test_the_hybrid_job_rehearses_through_run_py(tmp_path, monkeypatch, capsys):
    """benchmark/run.py end to end on the CPU at a toy size, through
    jobs/train_step.py with the configuration's own modules: `correct` against
    reference_kimi.py on the accumulated step."""
    import run

    tiny = tiny_hybrid(remat=True)
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    fields = lambda cfg, names: {n: getattr(cfg, n) for n in names}  # noqa: E731
    (tmp_path / "configs" / "tiny-hybrid.json").write_text(json.dumps({
        "modules": {"reference": "reference_kimi", "count": "flops_kimi"},
        "vision": fields(tiny.vision, ["image_size", "patch_size", "width", "depth", "num_heads", "embed_dim", "use_proj", "dtype"]),
        "text": fields(tiny.text, ["vocab_size", "width", "num_heads", "embed_dim", "dtype", *HYBRID]),
        "loss": {},
    }))
    mix = harness.load_json(os.path.join(BENCH_DIR, "traffic", "p64-s1024.json"))
    mix.update(pairs_per_chip_per_step=8, microbatch=4, reference_slice_rows=2)
    mix["step"]["accum_steps"] = 2
    (tmp_path / "traffic" / "p8-s16.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-hybrid", "file": "configs/tiny-hybrid.json"}],
        "workloads": [{"name": "tiny-hybrid-p8-s16", "config": "tiny-hybrid", "traffic": "p8-s16", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }))
    monkeypatch.setattr(harness, "REHEARSAL_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "prepare_environment", lambda cell, rehearse: None)
    assert run.main(["--rehearse", "--workload", "tiny-hybrid-p8-s16", "--seed", "3200000007", "--seconds", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0 and lines[-1]["metrics"] == {}
    assert all(lines[0]["verdicts"].values()), lines[0]
    assert lines[0]["grad_err"] < 1e-3 and lines[0]["embed_err"] < 1e-4

    # The planted faults the bounds were set against, each through the same `main`
    # (benchmark/tests/controls_kimi.py keeps the programs between them): a left-out
    # term fails a limit, a drawn selection bias alone does not.
    from distributed_sigmoid_loss_tpu import train

    controls = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_kimi.py"))
    create_train_state = train.create_train_state
    seed = "3200000007"
    assert controls.main(["--rehearse", "--workload", "tiny-hybrid-p8-s16", "--seconds", "1", "--read", f"biased:{seed}",
                          "--run", f"lost_expert:{seed}", "--run", f"select_by_score:{seed}"]) == 0
    assert train.create_train_state is create_train_state and harness.REHEARSAL_DIR == str(tmp_path)  # it undoes its patches
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    done = lines[-1]["runs"]
    assert [(r["variant"], r["correct"], r["failed"]) for r in done] == [("lost_expert", False, 0), ("select_by_score", False, 0)]
    lost, by_score = (r["compared"] for r in done)
    assert lost["grad_err"][0] == 1.0 > lost["grad_err"][1]  # the lost expert's gate and up stacks: no gradient at all
    assert by_score["grad_err"][0] > by_score["grad_err"][1] and by_score["embed_err"][0] > by_score["embed_err"][1]
    biased = next(line for line in lines if line.get("info") == "controls.read")
    assert biased["grad_err"] < 1e-3 and biased["embed_err"] < 1e-4  # the program selects by s + b, as the reference does


# -- (f) the benchmark's configuration and count ---------------------------------------


def cell_config():
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", "train_step.py"))
    cell = harness.Cell("kimi-b16-p64-s1024", rehearse=False)
    return cell, job.build_config(cell.config, cell.traffic)


def test_the_cell_is_the_published_model_cut_as_written():
    cell, cfg = cell_config()
    t, published = cfg.text, cell.config
    kda = published["linear_attn_config"]
    assert (t.width, t.norm_eps, t.num_heads) == (published["hidden_size"], published["rms_norm_eps"], published["num_attention_heads"])
    assert (t.kda_head_dim, t.num_heads, t.kda_conv_size) == (kda["head_dim"], kda["num_heads"], kda["short_conv_kernel_size"])
    assert (t.mla_qk_nope_dim, t.mla_qk_shared_dim, t.mla_v_dim, t.mla_kv_rank) == (
        published["qk_nope_head_dim"], published["qk_rope_head_dim"], published["v_head_dim"], published["kv_lora_rank"])
    assert published["q_lora_rank"] is None and published["mla_use_nope"] is True and t.pos == "none"
    assert round(t.width * t.mlp_ratio) == published["intermediate_size"] == 9216
    assert (t.moe_hidden, t.moe_experts, t.moe_num_selected, t.moe_shared_experts, t.moe_route_scale) == (
        published["moe_intermediate_size"], published["num_experts"], published["num_experts_per_token"],
        published["num_shared_experts"], published["routed_scaling_factor"])
    assert published["moe_router_activation_func"] == t.moe_router == "sigmoid" and published["moe_renormalize"]
    layers = range(1, t.depth + 1)
    assert list(t.mixers) == ["kda" if n in kda["kda_layers"] else "mla" for n in layers]
    assert [n for n in layers if n in kda["full_attn_layers"]] == [4]
    assert t.leading_dense_layers == published["first_k_dense_replace"] == 1
    # the cut: depth, the experts held, the vocabulary; nothing else
    assert published["reduced"] == ["text.depth", "text.moe_experts_held", "text.vocab_size"]
    assert (t.depth, published["num_hidden_layers"]) == (5, 27)
    assert (t.moe_experts_held, published["num_experts"]) == (8, 256)
    assert (t.vocab_size * 8, published["vocab_size"]) == (163840, 163840)
    assert "32 chips" in published["deployment"] and t.context_length == 1024
    b16 = harness.load_json(os.path.join(BENCH_DIR, "configs", "siglip-b16-224.json"))
    assert published["vision"] == b16["vision"]  # the B/16 image tower, field for field
    mix = cell.traffic
    assert (mix["pairs_per_chip_per_step"], mix["microbatch"], mix["step"]["accum_steps"]) == (64, 16, 4)


def test_the_text_tower_has_the_parameters_the_shapes_say():
    _, cfg = cell_config()
    shapes = jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32), jax.ShapeDtypeStruct((1, 1024), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(nn.meta.unbox(tree)))  # noqa: E731
    w, hd = 2304, 32 * 128
    kda = 4 * w * hd + 2 * (w * 128 + 128 * hd) + w * 32 + 3 * 4 * hd + hd + 32 + 128  # + taps, dt_bias, A_log, head norm
    mla = w * 32 * 192 + w * 576 + 512 + 512 * 32 * 256 + 32 * 128 * w
    dense, shared, experts, router = 3 * w * 9216, 3 * w * 1024, 8 * 3 * w * 1024, w * 256 + 256
    norms = 2 * w
    encoder = shapes["textual"]["encoder"]
    assert count(encoder["block0"]) == kda + dense + norms
    assert count(encoder["block1"]) == kda + shared + experts + router + norms
    assert count(encoder["block3"]) == mla + shared + experts + router + norms
    assert [round(n / 1e6, 1) for n in (kda, mla, dense, shared, router, experts)] == [39.5, 29.1, 63.7, 7.1, 0.6, 56.6]
    stack = 4 * kda + mla + dense + 4 * (shared + experts + router) + 5 * norms + w
    assert count(encoder) == stack and round(stack / 1e6) == 508
    assert count(shapes["textual"]) == stack + 20480 * w + w * 768 + 768
    assert round(count(shapes["textual"]) / 1e6) == 557  # 508 + 47 + 2
    assert "pos_embed" not in shapes["textual"]


def test_the_count_is_the_issues():
    """benchmark/tests/test_flops_kimi.py has the count by hand; tier-1 keeps its ends."""
    _, cfg = cell_config()
    assert round(flops_kimi.text_forward_flops(cfg.text) / 1024 / 1e6) == 596  # MFLOP a text token
    assert round(flops_kimi.forward_flops_per_pair(cfg) / 1e9, 1) == 645.4  # ISSUE 32: about 646
    assert flops_kimi.expected_assignments_per_token(cfg.text) == 0.25
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops_kimi.attention_least_s(cfg, "textual", 64, peaks) == 0.0
    assert flops_kimi.attention_least_s(cfg, "visual", 64, peaks) > 0.0


# -- (g) the defaults are left alone, and code that re-implements the block refuses ------


@pytest.mark.parametrize("preset", ["tiny_test", "b16", "so400m"])
def test_the_presets_keep_their_parameter_trees(preset):
    cfg = getattr(SigLIPConfig, preset)()
    t, v = cfg.text, cfg.vision
    assert t.mixers == () and t.moe_router == "softmax" and t.norm_eps == 1e-6 and t.pos == "learned"
    shapes = nn.meta.unbox(jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, v.image_size, v.image_size, 3), jnp.float32),
        jax.ShapeDtypeStruct((1, t.context_length), jnp.int32),
    )["params"])
    paths = {jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(shapes["textual"])}
    new = ("kda", "mla", "moe", SELECT_BIAS, "shared", "wg")
    assert not [p for p in paths if any(f"'{n}'" in p for n in new)]
    hidden = int(round(t.width * t.mlp_ratio))
    block = 4 * (t.width**2 + t.width) + 2 * t.width * hidden + hidden + t.width + 4 * t.width
    stack = shapes["textual"]["encoder"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(stack)) == t.depth * block + 2 * t.width
    assert shapes["textual"]["pos_embed"].shape == (1, t.context_length, t.width)


NEW_OPTIONS = dict(mixers=("kda", "mla"), leading_dense_layers=1, norm_eps=1e-5, moe_router="sigmoid",
                   moe_route_scale=2.446, moe_shared_experts=1, moe_hidden=24, moe_experts_held=4, mla_q_rank=8,
                   norm_unit_offset=True,  # an RMSNorm stored as an offset from 1 (tests/test_eva_tower.py)
                   # a one-sub-layer stack's, grouped heads' and a shared expert's own width (tests/test_nemotron_tower.py)
                   sublayers="single", num_kv_heads=1, head_dim=8, moe_shared_hidden=40,
                   # an attention layer's window, rotation by layer kind, head norms and gate, and the embedding's
                   # scale (tests/test_trinity_tower.py)
                   attn_windows=(4, 0), rope_layers="window", attn_qk_norm=True, attn_gate=True, embed_scale=2.0)


@pytest.mark.parametrize("option", sorted(NEW_OPTIONS))
def test_pipelined_towers_and_hf_import_refuse_the_new_options_by_name(option):
    from distributed_sigmoid_loss_tpu.models.hf_import import params_from_hf
    from distributed_sigmoid_loss_tpu.parallel.pp_towers import validate_pp_tower

    text = dataclasses.replace(TextConfig.tiny_test(), pool="last", **{option: NEW_OPTIONS[option]})
    with pytest.raises(ValueError, match=rf"\b{option}="):
        validate_pp_tower(dataclasses.replace(text, scan_layers=True), 2, "text")
    cfg = SigLIPConfig(vision=dataclasses.replace(ViTConfig.tiny_test(), use_proj=False), text=text)
    with pytest.raises(ValueError, match=rf"\b{option}="):
        params_from_hf({}, cfg)


@pytest.mark.parametrize("bad, match", [
    (dict(sequence_parallel_axis="sp"), "sequence_parallel_axis="),
    (dict(quant_train="int8"), "quant="),
    (dict(pos="learned"), "pos="),
    (dict(causal=False), "causal=False"),
    (dict(loops=2), "loops=2"),
    (dict(mixers=("kda",)), "depth=5"),
    (dict(mixers=("kda", "kda", "kda", "gru", "kda")), "mixers"),
    (dict(mlp="gelu"), "moe_router='sigmoid'"),
])
def test_what_a_mixed_stack_does_not_run_with_says_so_by_name(bad, match):
    from distributed_sigmoid_loss_tpu.models.text import TextTransformer

    cfg = dataclasses.replace(tiny_hybrid().text, **bad)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tiny_batch()[1])


def test_the_configuration_space_states_what_the_new_options_exclude():
    """analysis/config_space.py: the block options stay outside the step lattice;
    what they exclude (sequence parallelism, int8, a position table, the pipeline)
    is one table, and every line of it is what the modules really refuse."""
    from distributed_sigmoid_loss_tpu.analysis import config_space
    from distributed_sigmoid_loss_tpu.utils.config import BLOCK_OPTIONS

    assert not set(BLOCK_OPTIONS) & set(config_space.AXES)
    assert set(config_space.PP_REFUSES) <= set(BLOCK_OPTIONS) and set(NEW_OPTIONS) == set(config_space.PP_REFUSES)
    excluded = {next(iter(pair[1])) for pair in config_space.TOWER_EXCLUSIONS}
    assert {"sequence_parallel_axis", "quant_train", "pos"} <= excluded
    assert config_space.tower_exclusion_drift() == []
