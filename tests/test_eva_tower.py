"""A text tower of windowed chunk attention (``TextConfig.mixers`` of "eva":
softmax attention exact inside a window, every earlier window read as one pooled
key and value per chunk, one softmax over both; an RMSNorm stored as an offset
from 1) against its plain reference (``benchmark/reference_eva.py``): the
staircase, the layer, the tower, the kernel pair in interpret mode, the scanned
stack with the accumulator in the layer loop, the benchmark's job with its
planted faults, the configuration and the count, and the refusals by name."""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.models import mixers
from distributed_sigmoid_loss_tpu.models.mixers import (
    EVA_CORE_SCOPE,
    EVA_SUMMARY_SCOPE,
    EvaAttention,
    eva_attention_core,
    eva_core_dense,
    eva_summaries,
    rope_on_the_lanes,
)
from distributed_sigmoid_loss_tpu.models.text import TextTransformer, layer_specs
from distributed_sigmoid_loss_tpu.models.transformer import Attention, rope
from distributed_sigmoid_loss_tpu.ops.pallas_eva_attention import eva_attention_kernel, eva_attention_plan
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import sigmoid_loss
from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig, TextConfig, ViTConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import flops_eva  # noqa: E402
import harness  # noqa: E402
import reference_eva  # noqa: E402

CELL = "evabyte-b16-p8-s8192"
# The cell's stack at a toy size: four windows of 8 tokens, chunks of 2, two heads of 16, float32.
EVA = dict(
    context_length=32, depth=3, causal=True, pool="last", norm="rmsnorm", norm_eps=1e-5, norm_unit_offset=True,
    mlp="swiglu", mlp_ratio=2.6875, use_bias=False, pos="rope", rope_theta=1e5, mixers=("eva", "eva", "eva"),
    eva_window=8, eva_chunk=2,
)


def tiny_eva(**over) -> SigLIPConfig:
    text = dataclasses.replace(TextConfig.tiny_test(), **{**EVA, **over})
    return SigLIPConfig(vision=ViTConfig.tiny_test(), text=text)


def tiny_batch(n=4, s=32):
    images = jax.random.normal(jax.random.key(1), (n, 16, 16, 3), jnp.float32)
    tokens = jax.random.randint(jax.random.key(2), (n, s), 0, 64)
    return images, tokens


def moved(params, scale=0.05):
    """Every leaf away from its initial value (the norms' offsets start at
    zero): a dropped offset or a dropped leaf then shows."""
    leaves, tree = jax.tree.flatten(nn.meta.unbox(params))
    keys = jax.random.split(jax.random.key(3), len(leaves))
    return jax.tree.unflatten(tree, [
        x + scale * jax.random.normal(k, x.shape, x.dtype) if x.ndim else x for x, k in zip(leaves, keys)
    ])


def unrolled_from(params, depth):
    """A scanned text stack's tree as the unrolled layout holds it."""
    encoder = dict(params["textual"]["encoder"])
    stack = encoder.pop("blocks")["block"]
    for i in range(depth):
        encoder[f"block{i}"] = jax.tree.map(lambda x: x[i], stack)
    return {**params, "textual": {**params["textual"], "encoder": encoder}}


# -- (a) the staircase and the layer ----------------------------------------------------

B, S, H, D, W, C = 2, 32, 2, 16, 8, 2


def core_operands(seed=0, s=S, h=H, d=D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (B, s, h * d), dtype) for i in range(3))
    phi, mu = (jax.random.normal(ks[i], (h, d), jnp.float32) for i in (3, 4))
    return q, k, v, phi, mu


def by_every_pair(q, k, v, phi, mu, window, chunk):
    """The equations with every query against every key and every summary under
    the two masks: no windows cut, no blocks."""
    b, s, width = q.shape
    h, d = phi.shape
    q4, k4, v4 = (np.asarray(t, np.float64).reshape(b, s, h, d) for t in (q, k, v))
    logits = np.einsum("bshd,hd->bsh", k4, np.asarray(phi, np.float64)) * d**-0.5
    logits = logits.reshape(b, s // chunk, chunk, h)
    a = np.exp(logits - logits.max(2, keepdims=True))
    a = (a / a.sum(2, keepdims=True))[..., None]
    kc = (a * k4.reshape(b, s // chunk, chunk, h, d)).sum(2) + np.asarray(mu, np.float64)
    vc = (a * v4.reshape(b, s // chunk, chunk, h, d)).sum(2)
    t = np.arange(s)
    exact = np.einsum("bqhd,bkhd->bhqk", q4, k4) * d**-0.5
    exact = np.where((t[None, :] <= t[:, None]) & (t[None, :] // window == t[:, None] // window), exact, -np.inf)
    remote = np.einsum("bqhd,bchd->bhqc", q4, kc) * d**-0.5
    remote = np.where((np.arange(s // chunk)[None, :] * chunk // window) < (t[:, None] // window), remote, -np.inf)
    scores = np.concatenate([exact, remote], -1)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("bhqk,bkhd->bqhd", p[..., :s], v4) + np.einsum("bhqc,bchd->bqhd", p[..., s:], vc)
    return out.reshape(b, s, width)


def dense_core(q, k, v, phi, mu, window=W, chunk=C):
    kc, vc = eva_summaries(k, v, phi, mu, chunk, q.dtype)
    return eva_core_dense(q, k, v, kc, vc, heads=phi.shape[0], window=window, scale=phi.shape[1] ** -0.5)


def test_the_dense_core_is_the_equations_over_every_pair():
    q, k, v, phi, mu = core_operands()
    with jax.default_matmul_precision("highest"):
        got = dense_core(q, k, v, phi, mu)
    np.testing.assert_allclose(got, by_every_pair(q, k, v, phi, mu, W, C), atol=2e-6)


@pytest.mark.parametrize("moved_window", [0, 1, 2, 3])
def test_a_query_sees_the_summaries_of_earlier_windows_only(moved_window):
    """The staircase: another set of summaries for window j moves the outputs of
    the windows after j, every one of them, and of no other; window 0 sees none."""
    q, k, v, phi, mu = core_operands()
    kc, vc = eva_summaries(k, v, phi, mu, C, jnp.float32)
    at = slice(moved_window * W // C, (moved_window + 1) * W // C)
    core = lambda kc, vc: np.asarray(eva_core_dense(q, k, v, kc, vc, heads=H, window=W, scale=D**-0.5))  # noqa: E731
    before, after = core(kc, vc), core(kc.at[:, at].add(1.0), vc.at[:, at].add(1.0))
    changed = np.abs(after - before).reshape(B, S // W, W, -1).max((0, 2, 3)) > 0
    assert changed.tolist() == [w > moved_window for w in range(S // W)]
    # and every query of a later window: none is left out
    rows = np.abs(after - before).max((0, 2)) > 0
    assert rows[(moved_window + 1) * W:].all() and not rows[: (moved_window + 1) * W].any()


def test_a_sequence_of_one_window_is_plain_causal_attention_bit_for_bit():
    """No summary is admitted: the layer is ``Attention`` with rotary positions,
    from the same q, k, v and out leaves (phi and mu take no part)."""
    x = jax.random.normal(jax.random.key(0), (2, 8, 32), jnp.float32)
    eva = EvaAttention(width=32, num_heads=2, window=8, chunk=2, rope_theta=1e5, dtype=jnp.float32)
    params = eva.init(jax.random.key(1), x)["params"]
    assert set(params) == {"q", "k", "v", "out", "phi", "mu"} and params["phi"].shape == params["mu"].shape == (2, 16)
    plain = Attention(32, 2, jnp.float32, causal=True, use_bias=False, rope_theta=1e5, attn_impl="dense")
    shared = {name: params[name] for name in ("q", "k", "v", "out")}
    np.testing.assert_array_equal(eva.apply({"params": params}, x), plain.apply({"params": shared}, x))
    moved_on = {**params, "phi": params["phi"] + 1.0, "mu": params["mu"] - 1.0}
    np.testing.assert_array_equal(eva.apply({"params": params}, x), eva.apply({"params": moved_on}, x))


def test_the_rotation_on_the_lanes_is_the_per_head_rotation_bit_for_bit():
    x = jax.random.normal(jax.random.key(0), (2, 24, 3 * 16), jnp.float32)
    want = rope(x.reshape(2, 24, 3, 16), 1e5).reshape(2, 24, 48)
    np.testing.assert_array_equal(rope_on_the_lanes(x, 3, 1e5), want)
    want = jax.vmap(lambda row: reference_eva.rotate(row, 1e5))(x.reshape(2, 24, 3, 16)).reshape(2, 24, 48)
    np.testing.assert_allclose(rope_on_the_lanes(x, 3, 1e5), want, atol=2e-6)


def test_the_layer_matches_the_reference():
    t = tiny_eva().text
    layer = EvaAttention(width=32, num_heads=2, window=8, chunk=2, rope_theta=1e5, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 32, 32), jnp.float32)
    params = moved(layer.init(jax.random.key(1), x)["params"])
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        want = jax.vmap(lambda row: reference_eva.eva(row, params, t))(x)
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_the_references_blocks_are_the_whole_softmax(monkeypatch):
    q, k, v, phi, mu = core_operands()
    want = by_every_pair(q, k, v, phi, mu, W, C)[0]
    monkeypatch.setattr(reference_eva, "QUERY_BLOCK", 4)  # two blocks a window
    with jax.default_matmul_precision("highest"):
        q4, k4, v4 = (t[0].reshape(S, H, D) for t in (q, k, v))
        kc, vc = reference_eva.summaries(k4, v4, phi, mu, C)
        got = reference_eva.windowed_chunk_attention(q4, k4, v4, kc, vc, W, C)
    np.testing.assert_allclose(got.reshape(S, -1), want, atol=2e-6)


# -- (b) the kernel pair in interpret mode -------------------------------------------------


def kernel_and_dense(window, windows, seed=0):
    """The published head and chunk (d 128, C 16), one head, float32."""
    s = window * windows
    q, k, v, phi, mu = core_operands(seed, s=s, h=1, d=128)
    cotangent = jax.random.normal(jax.random.key(9), q.shape)

    def through(core):
        def loss(q, k, v, phi, mu):
            kc, vc = eva_summaries(k, v, phi, mu, 16, jnp.float32)
            out = core(q, k, v, kc, vc)
            return (out * cotangent).sum(), out
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(q, k, v, phi, mu)

    (_, out), grads = through(lambda *a: eva_attention_kernel(*a, head_dim=128, window=window, interpret=True))
    (_, want), want_grads = through(lambda *a: eva_core_dense(*a, heads=1, window=window, scale=128**-0.5))
    return out, grads, want, want_grads


@pytest.fixture(scope="module")
def three_windows():
    with jax.default_matmul_precision("highest"):
        return kernel_and_dense(256, 3)


def test_the_kernel_pair_forward_is_the_dense_core(three_windows):
    out, _, want, _ = three_windows
    np.testing.assert_allclose(out, want, atol=5e-6)


@pytest.mark.parametrize("leaf", ["q", "k", "v", "phi", "mu"])
def test_the_kernel_pair_backward_is_the_dense_cores(three_windows, leaf):
    """Through the pooling too: dkc and dvc, summed over the later windows'
    programs in the kernel's scratch, reach k, v, phi and mu."""
    _, grads, _, want = three_windows
    i = ["q", "k", "v", "phi", "mu"].index(leaf)
    assert float(jnp.abs(want[i]).max()) > 0
    np.testing.assert_allclose(grads[i], want[i], atol=2e-5 * float(jnp.abs(want[i]).max()))


@pytest.mark.slow
def test_the_kernel_pair_at_the_published_window():
    with jax.default_matmul_precision("highest"):
        out, grads, want, want_grads = kernel_and_dense(2048, 2)
    np.testing.assert_allclose(out, want, atol=1e-5)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(g, w, atol=5e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("attn_impl, dtype, tpu, core", [
    ("auto", "bfloat16", True, "kernel"), ("auto", "bfloat16", False, "dense"), ("auto", "float32", True, "dense"),
    ("flash", "float32", True, "kernel"), ("dense", "bfloat16", True, "dense"),
])
def test_which_core_the_layer_takes_follows_from_dtype_and_backend(monkeypatch, attn_impl, dtype, tpu, core):
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    sizes = eva_attention_core(attn_impl, dtype, 8192, 2048, 16, 128)
    assert sizes == {"core": core, "block": 512 if core == "kernel" else None, "windows": 4, "summaries": 512}
    # shapes the kernels do not take go to XLA under "auto" and are refused under "flash"
    assert eva_attention_plan(32, 8, 2, 16) is None and eva_attention_plan(8192, 2048, 16, 128)["per_window"] == 128
    assert eva_attention_core("auto", "bfloat16", 32, 8, 2, 16)["core"] == "dense"
    if tpu:
        with pytest.raises(ValueError, match="do not take"):
            eva_attention_core("flash", dtype, 32, 8, 2, 16)
    else:
        with pytest.raises(ValueError, match="requires a TPU"):
            eva_attention_core("flash", dtype, 8192, 2048, 16, 128)


def test_the_layer_on_the_kernel_path_keeps_the_heads_on_the_lanes(monkeypatch):
    """Steered onto the kernels (interpret mode), the bf16 layer equals its XLA
    core to bf16 grade, and no per-head (b, s, h, d) value is in its jaxpr."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_eva_attention

    layer = EvaAttention(width=256, num_heads=2, window=128, chunk=8, rope_theta=1e5, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(0), (2, 256, 256), jnp.bfloat16)
    params = layer.init(jax.random.key(1), x)["params"]
    want = layer.apply({"params": params}, x)
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    kernel = pallas_eva_attention.eva_attention_kernel
    monkeypatch.setattr(pallas_eva_attention, "eva_attention_kernel", lambda *a, **kw: kernel(*a, **kw, interpret=True))
    got = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2)
    jaxpr = jax.make_jaxpr(lambda p, x: layer.apply({"params": p}, x))(params, x)
    assert "pallas_call" in str(jaxpr)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars}
    assert not any(len(shape) == 4 and shape[-2:] == (2, 128) for shape in shapes), shapes


# -- (c) the tower against the reference ------------------------------------------------------


@pytest.fixture(scope="module", params=["scanned", "unrolled"])
def tower_and_reference(request):
    cfg = tiny_eva(remat=True, scan_layers=request.param == "scanned")
    model = SigLIP(cfg)
    images, tokens = tiny_batch()
    params = moved(model.init(jax.random.key(0), images, tokens)["params"])
    encoder = params["textual"]["encoder"]
    assert ("blocks" in encoder) == (request.param == "scanned") and ("block0" in encoder) != ("blocks" in encoder)

    def system_loss(p):
        zimg, ztxt, lp = model.apply({"params": p}, images, tokens)
        return sigmoid_loss(zimg, ztxt, lp["t_prime"], lp["bias"]), ztxt

    def reference_loss(p):
        zimg, ztxt = reference_eva.embeddings(cfg, p, images, tokens)
        return reference_eva._base.sigmoid_loss(zimg, ztxt, p["t_prime"], p["bias"]), ztxt

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
        want = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(params)
        _, first = reference_eva.make_sample_grads(cfg)(params, images, tokens)
    return params, got, want, first


@pytest.mark.parametrize("what", ["embeddings", "loss", "gradients", "first_blocks"])
def test_the_tower_matches_the_plain_reference(tower_and_reference, what):
    params, ((got_loss, got_z), got), ((want_loss, want_z), want), first = tower_and_reference
    if what == "embeddings":
        np.testing.assert_allclose(got_z, want_z, atol=1e-5)
    elif what == "loss":
        np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    elif what == "gradients":
        errs = reference_eva.tree_max_rel_err(got, want)
        assert len(errs) == len(jax.tree.leaves(params))
        assert max(errs.values()) < 2e-4, max(errs, key=errs.get)
        offsets = [k for k in errs if "offset" in k]
        assert len(offsets) in (3, 7) and any("phi" in k for k in errs) and any("mu" in k for k in errs)
    else:  # the interface `correct` calls: the loss scalars and the first layer of each tower, every leaf
        assert set(first["textual"]) == {"ln1", "eva", "ln2", "mlp"}
        assert set(first["textual"]["eva"]) == {"q", "k", "v", "out", "phi", "mu"}
        compared = reference_eva.first_blocks(got)
        assert max(reference_eva.tree_max_rel_err(compared, first).values()) < 2e-4


def test_the_reference_refuses_another_stack():
    with pytest.raises(ValueError, match="reference_eva.py has the equations"):
        reference_eva.make_sample_grads(tiny_eva(norm_unit_offset=False))
    with pytest.raises(ValueError, match="reference_eva.py has the equations"):
        reference_eva.make_batch_loss(tiny_eva(mixers=("eva", "attn", "eva")), 1, np.arange(4)[None])


# -- (d) the scanned stack, the accumulator in the layer loop, the unit offset ----------------


def accumulated(cfg, params, monkeypatch=None, sink=True):
    """The parameters after one sgd(1.0) step accumulated over two microbatches:
    the accumulated gradient, to a sign."""
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_train_step
    from distributed_sigmoid_loss_tpu.train import train_step as train_step_module

    model, mesh = SigLIP(cfg), make_mesh(1)
    images, tokens = tiny_batch(8)
    batch = {"images": images, "tokens": tokens}
    state = create_train_state(jax.random.key(0), model, optax.sgd(1.0), batch, mesh)
    state = state.replace(params=jax.tree.map(jnp.copy, params if params is not None else state.params))
    if not sink:
        monkeypatch.setattr(train_step_module, "split_grad_sink", lambda tree: ({}, tree))
    step, _ = make_train_step(model, mesh, cfg.loss, accum_steps=2, accum_dtype="bfloat16")
    before = jax.tree.map(np.asarray, state.params)
    after, metrics = step(state, batch)
    return step, before, jax.tree.map(np.asarray, after.params), float(metrics["loss"])


@pytest.fixture(scope="module")
def scanned_step():
    cfg = tiny_eva(remat=True, scan_layers=True)
    return (cfg, *accumulated(cfg, None))


def test_the_like_stack_scans_with_the_accumulator_in_the_layer_loop(scanned_step):
    from distributed_sigmoid_loss_tpu.obs.attribution import accum_placement, mixed_stack, mixed_stack_line
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import STACK_RECORD_EVA_FIELDS, STACK_RECORD_FIELDS

    cfg, step, before, after, loss = scanned_step
    stack = before["textual"]["encoder"]["blocks"]["block"]
    assert stack["eva"]["phi"].shape == (3, 2, 16) and stack["ln1"]["offset"].shape == (3, 32)
    placement = accum_placement(step)
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))  # noqa: E731
    assert placement["layer_loop_bytes"] == nbytes(before["textual"]["encoder"]["blocks"]) + nbytes(
        before["visual"]["encoder"].get("blocks", {}))
    record = mixed_stack(step)
    assert set(record) <= set(STACK_RECORD_FIELDS) and record["scanned"] is True
    assert record["layer_kinds"] == ["eva+mlp"] * 3 and "experts_held" not in record
    assert set(record["eva"]) == {0, 1, 2} and set(record["eva"][0]) == set(STACK_RECORD_EVA_FIELDS)
    assert record["eva"][1] == {"window": 8, "chunk": 2, "rope_theta": 1e5, "core": "dense", "block": None,
                                "windows": 4, "summaries": 16}
    assert mixed_stack_line(record) == (
        "stack: eva+mlp eva+mlp eva+mlp; " + "; ".join(
            f"eva[{i}] core=dense 4 windows of 8, 16 summaries" for i in range(3)) + "; scanned")
    assert np.isfinite(loss) and not np.array_equal(after["textual"]["encoder"]["blocks"]["block"]["eva"]["mu"], stack["eva"]["mu"])


def test_the_sink_is_bit_identical_to_accum_add_on_the_scanned_stack(scanned_step, monkeypatch):
    cfg, _, before, with_sink, loss = scanned_step
    step, _, without, loss_off = accumulated(cfg, before, monkeypatch, sink=False)
    from distributed_sigmoid_loss_tpu.obs.attribution import accum_placement

    assert accum_placement(step)["layer_loop_share"] == 0.0 and loss == loss_off
    for a, b in zip(jax.tree.leaves(with_sink), jax.tree.leaves(without)):
        np.testing.assert_array_equal(a, b)


def test_the_scanned_stack_accumulates_what_the_unrolled_layers_do(scanned_step):
    """The same layers unrolled, every leaf through ``accum_add``: equal
    accumulators to rounding (the bf16 accumulator's last bit)."""
    cfg, _, before, scanned_after, loss = scanned_step
    unrolled_cfg = tiny_eva(remat=True, scan_layers=False)
    step, _, after, unrolled_loss = accumulated(unrolled_cfg, unrolled_from(before, 3))
    assert step.stack_record["scanned"] is False and loss == pytest.approx(unrolled_loss, rel=1e-6)
    want = unrolled_from(scanned_after, 3)
    assert jax.tree.structure(want) == jax.tree.structure(after)
    update = jax.tree.map(lambda a, b: a - b, unrolled_from(before, 3), want)
    for (path, got), want_leaf, was in zip(jax.tree_util.tree_leaves_with_path(after), jax.tree.leaves(want),
                                           jax.tree.leaves(update)):
        scale = max(float(np.abs(was).max()), 1e-12)
        assert float(np.abs(got - want_leaf).max()) <= 2**-7 * scale, jax.tree_util.keystr(path)


@pytest.mark.parametrize("unit_offset", [True, False])
def test_weight_decay_pulls_a_unit_offset_norm_to_one(unit_offset):
    """``norm_unit_offset``: the leaf is the scale's distance from 1, so decay
    shrinks the distance; the plain RMSNorm's scale itself shrinks towards 0."""
    from distributed_sigmoid_loss_tpu.models.transformer import BlockStyle

    norm = BlockStyle(norm="rmsnorm", norm_eps=1e-5, norm_unit_offset=unit_offset).make_norm(jnp.float32, "ln")
    x = jax.random.normal(jax.random.key(0), (4, 32), jnp.float32)
    params = norm.init(jax.random.key(1), x)["params"]
    leaf = "offset" if unit_offset else "scale"
    assert set(params) == {leaf} and float(params[leaf][0]) == (0.0 if unit_offset else 1.0)
    plain = nn.RMSNorm(epsilon=1e-5, dtype=jnp.float32)
    np.testing.assert_allclose(norm.apply({"params": params}, x), plain.apply({"params": {"scale": jnp.ones(32)}}, x), atol=1e-6)
    moved_on = {leaf: params[leaf] + 0.5}
    tx = optax.adamw(1e-1, weight_decay=0.5)
    updates, _ = tx.update(jax.tree.map(jnp.zeros_like, moved_on), tx.init(moved_on), moved_on)
    decayed = optax.apply_updates(moved_on, updates)
    scale = lambda p: float(p[leaf][0]) + (1.0 if unit_offset else 0.0)  # noqa: E731
    assert scale(moved_on) == 1.5
    if unit_offset:
        assert 1.0 < scale(decayed) < 1.5 and scale(decayed) == pytest.approx(1.0 + 0.5 * (1 - 0.05))
    else:
        assert scale(decayed) == pytest.approx(1.5 * (1 - 0.05))
    with pytest.raises(ValueError, match="norm_unit_offset"):
        BlockStyle(norm="layernorm", norm_unit_offset=True).make_norm(jnp.float32, "ln")


def test_the_defaults_leave_every_other_tree_alone():
    """The new fields at their defaults: the SigLIP text tower's tree, and a
    stack of several kinds keeps its unrolled layers."""
    t = TextConfig.tiny_test()
    assert (t.eva_window, t.eva_chunk, t.norm_unit_offset) == (2048, 16, False) and layer_specs(t) == ()
    tokens = jnp.zeros((2, t.context_length), jnp.int32)
    shapes = jax.eval_shape(TextTransformer(dataclasses.replace(t, scan_layers=True)).init, jax.random.key(0), tokens)
    assert set(shapes["params"]["encoder"]) == {"blocks", "ln_final"}
    mixed = dataclasses.replace(t, causal=True, pos="none", norm="rmsnorm", mlp="swiglu", use_bias=False, pool="last",
                                mixers=("kda", "mla"), kda_head_dim=16, scan_layers=True)
    shapes = jax.eval_shape(TextTransformer(mixed).init, jax.random.key(0), tokens)
    assert set(shapes["params"]["encoder"]) == {"block0", "block1", "ln_final"}
    assert "scale" in shapes["params"]["encoder"]["ln_final"]


# -- (e) the refusals, by name ---------------------------------------------------------------


@pytest.mark.parametrize("over, named", [
    (dict(pos="none"), "pos='none'"),
    (dict(pos="learned"), "pos='learned'"),
    (dict(context_length=36), "context_length=36"),
    (dict(eva_window=8, eva_chunk=3), "eva_chunk=3"),
    (dict(sequence_parallel_axis="sp"), "sequence_parallel_axis='sp'"),
    (dict(loops=2), "loops=2"),
    (dict(causal=False), "causal=False"),
    (dict(quant_train="int8"), "quant='int8_ste'"),
    (dict(norm="layernorm"), "norm_unit_offset"),
])
def test_what_the_mixer_does_not_run_with_is_refused_by_name(over, named):
    cfg = dataclasses.replace(tiny_eva().text, **over)
    tokens = jax.ShapeDtypeStruct((2, cfg.context_length), jnp.int32)
    with pytest.raises(ValueError) as refusal:
        jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tokens)
    assert named in str(refusal.value) and ("eva" in str(refusal.value) or "norm_unit_offset" in str(refusal.value))


def test_pipelined_towers_and_hf_import_refuse_the_mixer_and_the_offset_by_name():
    from distributed_sigmoid_loss_tpu.models.hf_import import params_from_hf
    from distributed_sigmoid_loss_tpu.parallel.pp_towers import validate_pp_tower

    t = tiny_eva(scan_layers=True).text
    with pytest.raises(ValueError, match=r"mixers=\('eva', 'eva', 'eva'\).*norm_unit_offset=True"):
        validate_pp_tower(t, 1, "text")
    hf_shaped = SigLIPConfig(
        vision=dataclasses.replace(ViTConfig.tiny_test(), use_proj=False, embed_dim=32),
        text=dataclasses.replace(t, scan_layers=False, embed_dim=32),
    )
    with pytest.raises(ValueError, match=r"mixers=\('eva', 'eva', 'eva'\)"):
        params_from_hf({}, hf_shaped)


# -- (f) the benchmark's job, configuration and count --------------------------------------------

CONTROLS = ("no_summaries", "no_mu", "shifted_mask", "uniform_pooling", "fp8")


def test_the_job_rehearses_through_run_py_and_each_planted_fault_fails_it(tmp_path, monkeypatch, capsys):
    """benchmark/run.py end to end on the CPU at a toy size, through
    jobs/train_step.py with the configuration's own modules: `correct` against
    reference_eva.py on the accumulated, scanned step, sound true, and false for
    each of the five faults planted through the same `main`
    (benchmark/tests/controls_eva.py)."""
    import run

    tiny = tiny_eva(remat=True)
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    fields = lambda cfg, names: {n: getattr(cfg, n) for n in names}  # noqa: E731
    modules = harness.Cell(CELL, rehearse=False).config["modules"]
    assert modules == {"reference": "reference_eva", "count": "flops_eva"}
    (tmp_path / "configs" / "tiny-eva.json").write_text(json.dumps({
        "modules": modules,
        "vision": fields(tiny.vision, ["image_size", "patch_size", "width", "depth", "num_heads", "embed_dim", "use_proj", "dtype"]),
        "text": fields(tiny.text, ["vocab_size", "width", "num_heads", "embed_dim", "dtype", *EVA]),
        "loss": {},
    }))
    mix = harness.load_json(os.path.join(BENCH_DIR, "traffic", "p8-s8192.json"))
    assert mix["towers"]["scan_layers"] and mix["microbatch"] * mix["step"]["accum_steps"] == mix["pairs_per_chip_per_step"] == 8
    mix.update(pairs_per_chip_per_step=8, microbatch=4)
    mix["step"]["accum_steps"] = 2
    (tmp_path / "traffic" / "p8-s32.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-eva", "file": "configs/tiny-eva.json"}],
        "workloads": [{"name": "tiny-eva-p8-s32", "config": "tiny-eva", "traffic": "p8-s32", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }))
    monkeypatch.setattr(harness, "REHEARSAL_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "prepare_environment", lambda cell, rehearse: None)
    seed = "3900000007"
    assert run.main(["--rehearse", "--workload", "tiny-eva-p8-s32", "--seed", seed, "--seconds", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0 and lines[-1]["metrics"] == {}
    assert all(lines[0]["verdicts"].values()), lines[0]
    assert lines[0]["grad_err"] < 1e-3 and lines[0]["embed_err"] < 1e-4

    controls = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_eva.py"))
    kept = (mixers.eva_summaries, mixers.eva_core_dense, nn.Dense.__call__)
    runs = [arg for name in CONTROLS for arg in ("--run", f"{name}:{seed}")]
    assert controls.main(["--rehearse", "--workload", "tiny-eva-p8-s32", "--seconds", "1",
                          "--read", f"sound:{seed}", "--read", f"fp8_dense:{seed}", *runs]) == 0
    assert kept == (mixers.eva_summaries, mixers.eva_core_dense, nn.Dense.__call__)  # it undoes its patches
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    done = {r["variant"]: r for r in lines[-1]["runs"]}
    assert sorted(done) == sorted(CONTROLS)
    assert [(r["correct"], r["failed"]) for r in done.values()] == [(False, 0)] * 5
    over = {name: sorted(k for k, (value, limit) in r["compared"].items() if isinstance(value, float) and value > limit)
            for name, r in done.items()}
    assert all("embed_err" in over[name] for name in ("no_summaries", "shifted_mask")), over
    assert done["no_mu"]["compared"]["grad_err"][0] >= 1.0  # mu gets no gradient at all
    assert done["uniform_pooling"]["compared"]["grad_err"][0] >= 1.0  # nor phi
    reads = {line["variant"]: line for line in lines if line.get("info") == "controls.read"}
    assert reads["sound"]["grad_err"] < 1e-3 and reads["sound"]["embed_err"] < 1e-4
    assert reads["fp8_dense"]["embed_err"] > reference_eva.EMBED_BOUND


def cell_config(name=CELL):
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", "train_step.py"))
    cell = harness.Cell(name, rehearse=False)
    return cell, job.build_config(cell.config, cell.traffic)


def test_the_cell_is_the_published_model_cut_as_written():
    cell, cfg = cell_config()
    t, published = cfg.text, cell.config
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(guide):  # every published key, as the catalog has it
        catalog = next(row for row in map(json.loads, open(guide)) if row["name"] == "EvaByte")
        assert published["source"] == catalog["source_url"]
        assert {k: published[k] for k in catalog["config"]} == catalog["config"]
    assert published["model_type"] == "evabyte" and published["attention_class"] == "eva"
    assert (t.width, t.norm_eps, t.num_heads) == (published["hidden_size"], published["rms_norm_eps"], published["num_attention_heads"])
    assert (t.width, t.num_heads, t.width // t.num_heads) == (4096, 32, 128) and published["num_key_value_heads"] == 32
    assert round(t.width * t.mlp_ratio) == published["intermediate_size"] == 11008
    assert (t.eva_window, t.eva_chunk, t.vocab_size) == (published["window_size"], published["chunk_size"], published["vocab_size"]) == (2048, 16, 320)
    assert t.pos == "rope" and t.rope_theta == published["rope_theta"] == 1e5 and published["rope_scaling"] is None
    assert t.norm_unit_offset and published["norm_add_unit_offset"] and t.norm == "rmsnorm"
    assert list(t.mixers) == ["eva"] * 4 and t.leading_dense_layers == 0 and t.moe_experts == 0
    assert (t.causal, t.pool, t.mlp, t.use_bias, t.dtype) == (True, "last", "swiglu", False, "bfloat16")
    assert t.context_length == 8192 and t.context_length % t.eva_window == 0 and t.context_length <= published["max_seq_length"]
    # the cut: depth; nothing else
    assert published["reduced"] == ["text.depth"] and (t.depth, published["num_hidden_layers"]) == (4, 32)
    b16 = harness.load_json(os.path.join(BENCH_DIR, "configs", "siglip-b16-224.json"))
    assert published["vision"] == b16["vision"]
    mix = cell.traffic
    assert (mix["pairs_per_chip_per_step"], cell.chips, mix["towers"]["scan_layers"]) == (8, 1, True)
    assert mix["pairs_per_chip_per_step"] * t.context_length == 65536  # text tokens a step, as the two routed cells'


def test_the_text_tower_has_the_parameters_the_shapes_say():
    _, cfg = cell_config()
    shapes = jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32), jax.ShapeDtypeStruct((1, 8192), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(nn.meta.unbox(tree)))  # noqa: E731
    w = 4096
    layer = 4 * w * w + 3 * w * 11008 + 2 * 32 * 128 + 2 * w  # + phi, mu and the two norms' offsets
    encoder = shapes["textual"]["encoder"]
    assert set(encoder) == {"blocks", "ln_final"} and count(encoder["blocks"]) == 4 * layer
    assert round(layer / 1e6, 1) == 202.4  # ISSUE 39: a layer
    assert count(shapes["textual"]) == 4 * layer + w + 320 * w + w * 768 + 768
    assert round(count(shapes["textual"]) / 1e6) == 814 and round(count(shapes) / 1e6) == 907  # with the B/16 image tower
    assert "pos_embed" not in shapes["textual"]


def test_the_record_of_the_cells_stack_on_a_tpu(monkeypatch):
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack_line
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train.train_step import stack_record_of

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    _, cfg = cell_config()
    record = stack_record_of(cfg.text, (1, 8192))
    assert record["scanned"] is True and record["layer_kinds"] == ["eva+mlp"] * 4 and record["tokens_per_microbatch"] == 8192
    assert record["eva"] == {i: {"window": 2048, "chunk": 16, "rope_theta": 1e5, "core": "kernel", "block": 512,
                                 "windows": 4, "summaries": 512} for i in range(4)}
    assert "eva[3] core=kernel 4 windows of 2048, 512 summaries, blocks of 512 tokens; scanned" in mixed_stack_line(record)


def test_the_count_is_the_issues():
    """benchmark/tests/test_flops_eva.py has the count by hand; tier-1 keeps its ends."""
    _, cfg = cell_config()
    assert flops_eva.core_pairs(cfg.text) == (4 * 2048 * 2049 // 2, 2048 * 2048 // 16 * 6)
    per_token_layer = (flops_eva.text_forward_flops(cfg.text) - 2 * 4096 * 768) / 8192 / 4
    assert round(per_token_layer / 1e6, 1) == 424.7  # ISSUE 39: 404.7 + 19.9 + the pooling
    assert round(flops_eva.forward_flops_per_pair(cfg) / 1e12, 2) == 13.95
    assert round(flops_eva.train_flops_per_pair(cfg) / 1e12, 1) == 41.9
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops_eva.attention_least_s(cfg, "textual", 8, peaks) == 0.0
    assert flops_eva.attention_least_s(cfg, "visual", 8, peaks) > 0.0
    assert 0 < flops_eva.eva_core_least_s(cfg, 8, peaks) < 1.0
