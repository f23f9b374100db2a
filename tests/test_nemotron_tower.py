"""A text tower whose every layer is ONE sub-layer (``TextConfig.sublayers =
"single"``): a Mamba-2 state-space mixer ("ssm"), an attention with grouped key /
value heads and a head size of its own, or a routed feed-forward part of ungated
relu2 experts with a wider shared one, against its plain reference
(``benchmark/reference_nemotron.py``): the chunked core against the token-by-token
recurrence, the layers, grouped heads against repeated ones, the relu2 expert's
hand-written backward, the sixteen shares of a routed layer, the tower, the train
step, the benchmark's job with its planted faults, the configuration and the
count, the defaults left alone and the refusals by name."""

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
from distributed_sigmoid_loss_tpu.models.mixers import SSM_CONV_SCOPE, SSM_CORE_SCOPE, SsmMixer
from distributed_sigmoid_loss_tpu.models.moe import MOE_ROUTE_SCOPE, SELECT_BIAS, SharedExpertMoe, routed_experts
from distributed_sigmoid_loss_tpu.models.text import TextTransformer, layer_specs
from distributed_sigmoid_loss_tpu.models.transformer import ATTN_CORE_SCOPE, Attention, Mlp, attention_core
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import sigmoid_loss
from distributed_sigmoid_loss_tpu.ops.ssm import ssm_core, ssm_recurrent, ssm_scan
from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig, TextConfig, ViTConfig

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import flops_nemotron  # noqa: E402
import harness  # noqa: E402
import reference_nemotron  # noqa: E402

CELL = "nemotron-b16-p16-s4096"
# The cell's stack at a toy size: two state-space layers, two routed ones and one attention layer; 4 state-space heads
# of 8 in 2 groups, state 16, chunks of 8; 4 query heads over 2 key / value heads of 16 (4 x 16 = 64 over a 32-wide
# stream); 16 experts of which 4 are held, top-3, a shared expert of a width of its own.
NEMO = dict(
    context_length=24, depth=5, causal=True, pool="last", norm="rmsnorm", norm_eps=1e-5, mlp="relu2", use_bias=False,
    pos="none", sublayers="single", mixers=("ssm", "moe", "ssm", "attn", "moe"), num_heads=4, num_kv_heads=2, head_dim=16,
    ssm_num_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv_size=4, ssm_chunk=8,
    moe_experts=16, moe_num_selected=3, moe_router="sigmoid", moe_route_scale=2.5, moe_shared_experts=1,
    moe_hidden=24, moe_shared_hidden=40, moe_experts_held=4,
)


def tiny_nemo(**over) -> SigLIPConfig:
    text = dataclasses.replace(TextConfig.tiny_test(), **{**NEMO, **over})
    return SigLIPConfig(vision=ViTConfig.tiny_test(), text=text)


def tiny_batch(n=4, s=24):
    images = jax.random.normal(jax.random.key(1), (n, 16, 16, 3), jnp.float32)
    tokens = jax.random.randint(jax.random.key(2), (n, s), 0, 64)
    return images, tokens


def moved(params, scale=0.05):
    """Every leaf away from its initial value (norm scales and D start at one,
    the selection bias at zero): a dropped scale or a dropped leaf then shows."""
    leaves, tree = jax.tree.flatten(nn.meta.unbox(params))
    keys = jax.random.split(jax.random.key(3), len(leaves))
    return jax.tree.unflatten(tree, [
        x + scale * jax.random.normal(k, x.shape, x.dtype) if x.ndim else x for x, k in zip(leaves, keys)
    ])


# -- (a) the chunked core against the token-by-token recurrence ---------------------------

B, S, H, G, P, N = 3, 40, 4, 2, 8, 16
OPERANDS = ("x", "B", "C", "dt", "A", "D")


def core_operands(seed=0, s=S):
    k = jax.random.split(jax.random.key(seed), 6)
    x, big_b, big_c = (jax.random.normal(k[i], (B, s, w)) for i, w in enumerate((H * P, G * N, G * N)))
    dt = jax.nn.softplus(jax.random.normal(k[3], (B, s, H)))
    return x, big_b, big_c, dt, -jnp.exp(jax.random.normal(k[4], (H,))), jax.random.normal(k[5], (H,))


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunked_core_is_the_recurrence_whatever_the_chunk(chunk):
    """40 tokens: five chunks of 8, two and a half of 16 (the last zero-padded),
    one of 64 cut to the sequence: chunk_size is how, not what."""
    args = core_operands()
    want = ssm_recurrent(*args, heads=H, groups=G)
    with jax.default_matmul_precision("highest"):
        got = ssm_scan(*args, heads=H, groups=G, chunk=chunk)
    assert got.shape == want.shape == (B, S, H * P) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-5)
    plan = ssm_core(B, S, H, P, G, N, jnp.float32, chunk)
    assert (plan["core"], plan["chunk"], plan["chunks"], plan["kept_bytes"]) == ("chunked", min(chunk, S), -(-S // min(chunk, S)), 0)


@pytest.fixture(scope="module")
def core_gradients():
    args = core_operands(seed=1)
    weights = jax.random.normal(jax.random.key(9), (B, S, H * P))

    def loss(core):
        return lambda *a: jnp.sum(core(*a) * weights)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(partial(ssm_recurrent, heads=H, groups=G)), argnums=range(6))(*args)
        got = {c: jax.grad(loss(partial(ssm_scan, heads=H, groups=G, chunk=c)), argnums=range(6))(*args) for c in (8, 16)}
    return want, got


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("operand", OPERANDS)
def test_the_chunked_cores_backward_is_jax_grad_of_the_recurrence(core_gradients, operand, chunk):
    want, got = core_gradients
    i = OPERANDS.index(operand)
    assert np.abs(np.asarray(want[i])).max() > 1e-2
    np.testing.assert_allclose(got[chunk][i], want[i], atol=2e-3 * float(np.abs(np.asarray(want[i])).max()))


def test_a_pass_of_the_core_holds_the_rows_that_fit_and_the_passes_agree(monkeypatch):
    """At the cell's sizes one row a pass (4096 tokens x 64 heads x 128 x 4 bytes = 134 MB of scores);
    rows run a pass at a time give what all rows at once give."""
    from distributed_sigmoid_loss_tpu.ops import ssm

    assert ssm_core(4, 4096, 64, 64, 8, 128, jnp.bfloat16, 128)["rows_per_pass"] == 1
    assert ssm_core(4, 1024, 64, 64, 8, 128, jnp.bfloat16, 128)["rows_per_pass"] == 4
    args = core_operands(seed=2)
    whole = ssm_scan(*args, heads=H, groups=G, chunk=8)
    monkeypatch.setattr(ssm, "_PASS_BYTES", S * H * 8 * 4)  # one row's scores
    assert ssm_core(B, S, H, P, G, N, jnp.float32, 8)["rows_per_pass"] == 1
    np.testing.assert_allclose(ssm_scan(*args, heads=H, groups=G, chunk=8), whole, atol=1e-5)


def test_the_core_refuses_shapes_that_do_not_cut_into_heads_and_groups():
    x, big_b, big_c, dt, rate, skip = core_operands()
    with pytest.raises(ValueError, match="heads"):
        ssm_scan(x, big_b, big_c, dt, rate, skip, heads=H, groups=3)


# -- (a') the Pallas pair (ops/pallas_ssm.py) in the interpreter, at a small whole-register shape ------

KB, KS, KH, KG, KP, KN, KL = 2, 256, 4, 2, 64, 128, 128  # 2 rows x 256 tokens x 4 heads of 64 in 2 groups, state 128, chunks of 128


def kernel_operands(seed=0, s=KS, dtype=jnp.float32):
    """As the mixer's: steps of a tenth of a token's decay time, B over sqrt(N)."""
    k = jax.random.split(jax.random.key(seed), 6)
    x, big_b, big_c = (jax.random.normal(k[i], (KB, s, w)).astype(dtype) for i, w in enumerate((KH * KP, KG * KN, KG * KN)))
    dt = jax.nn.softplus(jax.random.normal(k[3], (KB, s, KH)) - 2.0)
    return x, big_b * KN**-0.5, big_c, dt, -jnp.exp(jax.random.normal(k[4], (KH,))), jax.random.normal(k[5], (KH,))


def interpreted_pair():
    from distributed_sigmoid_loss_tpu.ops.pallas_ssm import ssd_kernel

    return partial(ssd_kernel, heads=KH, groups=KG, chunk=KL, interpret=True)


def l2_error(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_the_kernel_pairs_y_is_the_recurrences():
    """Two chunks a row, so the state crosses one boundary in scratch; two heads
    of 64 share each 128-lane column."""
    args = kernel_operands()
    want = ssm_recurrent(*args, heads=KH, groups=KG)
    with jax.default_matmul_precision("highest"):
        got = interpreted_pair()(*args)
    assert got.shape == want.shape == (KB, KS, KH * KP) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


@pytest.fixture(scope="module")
def kernel_gradients():
    args = kernel_operands(seed=1)
    weights = jax.random.normal(jax.random.key(9), (KB, KS, KH * KP))

    def loss(core):
        return lambda *a: jnp.sum(core(*a) * weights)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(partial(ssm_recurrent, heads=KH, groups=KG)), argnums=range(6))(*args)
        got = jax.grad(loss(interpreted_pair()), argnums=range(6))(*args)
    return want, got, args


@pytest.mark.parametrize("operand", OPERANDS)
def test_the_kernel_pairs_backward_is_jax_grad_of_the_recurrence(kernel_gradients, operand):
    want, got, args = kernel_gradients
    i = OPERANDS.index(operand)
    assert got[i].shape == args[i].shape and got[i].dtype == args[i].dtype
    assert np.abs(np.asarray(want[i])).max() > 1e-2
    np.testing.assert_allclose(got[i], want[i], atol=2e-5 * float(np.abs(np.asarray(want[i])).max()))


def test_the_kernel_pair_without_decay_is_a_running_sum():
    """A = 0 (the benchmark's ``no_decay`` control passes it): every exponent is
    0, the state only grows, and the gradients stay what ``jax.grad`` says."""
    x, big_b, big_c, dt, rate, skip = kernel_operands(seed=2)
    args = (x, big_b, big_c, dt, jnp.zeros_like(rate), skip)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(ssm_recurrent(*a, heads=KH, groups=KG) ** 2), (0, 3, 4))(*args)
        got, got_grads = jax.value_and_grad(lambda *a: jnp.sum(interpreted_pair()(*a) ** 2), (0, 3, 4))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        assert np.isfinite(np.asarray(g)).all() and l2_error(g, w) < 1e-4


@pytest.fixture()
def kernel_path_on_the_cpu(monkeypatch):
    """``ssm_scan`` as on a TPU, its kernels (and the convolution's) in the interpreter; the shapes they were given."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_short_conv, pallas_ssm

    seen, real = [], pallas_ssm.ssd_kernel

    def interpreted(x, *a, **kw):
        seen.append(x.shape)
        return real(x, *a, interpret=True, **kw)

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    monkeypatch.setattr(pallas_ssm, "ssd_kernel", interpreted)
    monkeypatch.setattr(pallas_short_conv, "short_conv_kernel", partial(pallas_short_conv.short_conv_kernel, interpret=True))
    return seen


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_padded_for_the_kernels(kernel_path_on_the_cpu):
    """200 tokens: the second chunk's last 56 are x = 0, dt = 0, which neither
    decay nor write; their outputs and cotangents are cut off."""
    args = kernel_operands(seed=3, s=200, dtype=jnp.bfloat16)
    weights = jax.random.normal(jax.random.key(9), (KB, 200, KH * KP))

    def loss(core):
        return lambda *a: jnp.sum(core(*a) * weights)

    got = ssm_scan(*args, heads=KH, groups=KG, chunk=KL)
    assert kernel_path_on_the_cpu == [(KB, 256, KH * KP)] and got.shape == (KB, 200, KH * KP) and got.dtype == jnp.float32
    want = ssm_recurrent(*args, heads=KH, groups=KG)
    assert l2_error(got, want) < 5e-3  # bfloat16 operands
    got_grads = jax.grad(loss(partial(ssm_scan, heads=KH, groups=KG, chunk=KL)), argnums=range(6))(*args)
    want_grads = jax.grad(loss(partial(ssm_recurrent, heads=KH, groups=KG)), argnums=range(6))(*args)
    for name, g, w, a in zip(OPERANDS, got_grads, want_grads, args):
        assert g.shape == a.shape and g.dtype == a.dtype and l2_error(g, w) < 2e-2, name


@pytest.fixture(scope="module")
def pair_beside_chunked():
    """Both forms on the same bfloat16 operands: value and the six gradients."""
    from distributed_sigmoid_loss_tpu.ops.ssm import _chunked

    args = kernel_operands(seed=4, dtype=jnp.bfloat16)
    weights = jax.random.normal(jax.random.key(9), (KB, KS, KH * KP))
    chunked = partial(_chunked, heads=KH, groups=KG, chunk=KL, dt_=jnp.dtype(jnp.bfloat16))
    out = {}
    for name, core in (("kernel", interpreted_pair()), ("chunked", chunked)):
        out[name] = jax.value_and_grad(lambda *a, core=core: jnp.sum(core(*a) * weights), argnums=range(6))(*args)
        out[name + "_y"] = core(*args)
    return out


@pytest.mark.parametrize("what", ("y",) + OPERANDS)
def test_the_kernel_pair_rounds_where_the_chunked_form_does(pair_beside_chunked, what):
    """Products on bfloat16 operands with float32 accumulation, the mixed scores
    and the decayed x rounded before their products, everything else float32, in
    both: far inside the cell's limits (2.5e-2 on a row, 6.5e-1 on a leaf)."""
    if what == "y":
        assert l2_error(pair_beside_chunked["kernel_y"], pair_beside_chunked["chunked_y"]) < 1e-3
        return
    i = OPERANDS.index(what)
    got, want = pair_beside_chunked["kernel"][1][i], pair_beside_chunked["chunked"][1][i]
    assert got.dtype == want.dtype and l2_error(got, want) < 1e-2


@pytest.mark.parametrize("dtype, tpu, chunk, tokens, state, heads, head_dim, groups, core", [
    (jnp.bfloat16, True, 128, 4096, 128, 64, 64, 8, "kernel"),  # the cell's call
    (jnp.bfloat16, True, 128, 256, 128, 4, 64, 2, "kernel"),  # the tests' shape: two heads share a column
    (jnp.bfloat16, True, 256, 4096, 256, 8, 128, 8, "kernel"),  # a head a column
    (jnp.bfloat16, True, 128, 4096, 128, 8, 256, 4, "kernel"),  # a head two columns
    (jnp.float32, True, 128, 4096, 128, 64, 64, 8, "chunked"),  # float32 is XLA's
    (jnp.bfloat16, False, 128, 4096, 128, 64, 64, 8, "chunked"),  # no TPU
    (jnp.bfloat16, True, 64, 4096, 128, 64, 64, 8, "chunked"),  # half a register of tokens
    (jnp.bfloat16, True, 128, 100, 128, 64, 64, 8, "chunked"),  # a sequence shorter than a chunk is one odd chunk
    (jnp.bfloat16, True, 128, 4096, 64, 64, 64, 8, "chunked"),  # half a register of state
    (jnp.bfloat16, True, 128, 4096, 128, 8, 64, 8, "chunked"),  # a group of 64 lanes
    (jnp.bfloat16, True, 128, 4096, 128, 16, 96, 4, "chunked"),  # heads that straddle columns unevenly
])
def test_which_core_the_recurrence_takes_follows_from_what_the_call_can_see(monkeypatch, dtype, tpu, chunk, tokens, state,
                                                                           heads, head_dim, groups, core):
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.ops.ssm import _rows_per_pass

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    plan = ssm_core(4, tokens, heads, head_dim, groups, state, dtype, chunk)
    length = min(chunk, tokens)
    chunks = -(-tokens // length)
    assert plan == {
        "core": core, "chunk": length, "chunks": chunks, "heads": heads, "groups": groups, "head_dim": head_dim, "state": state,
        # the kernels hold no (rows, chunks, heads, L, L) array: all rows in one call, and each chunk's incoming state kept
        "rows_per_pass": 4 if core == "kernel" else _rows_per_pass(4, chunks * length, heads, length),
        "kept_bytes": 4 * chunks * state * heads * head_dim * 4 if core == "kernel" else 0}


def test_the_recurrences_kernels_sit_in_a_shard_map_under_a_jit_over_several_chips(kernel_path_on_the_cpu):
    """As the delta rule's: traced on a mesh the pair sees a chip's rows (every
    head: A and D are whole), bare without one; the same values and gradients."""
    import contextlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh, trace_on

    mesh = make_mesh(2)
    one = kernel_operands(seed=5, dtype=jnp.bfloat16)
    args = tuple(jax.device_put(jnp.concatenate([t, t[::-1]]), NamedSharding(mesh, P("dp"))) for t in one[:4]) + one[4:]

    def grads(on_mesh):
        def loss(*a):
            with trace_on(mesh) if on_mesh else contextlib.nullcontext():
                return (ssm_scan(*a, heads=KH, groups=KG, chunk=KL) ** 2).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))(*args)

    want, want_grads = grads(False)
    assert set(kernel_path_on_the_cpu) == {(4, KS, KH * KP)}
    kernel_path_on_the_cpu.clear()
    got, got_grads = grads(True)
    assert set(kernel_path_on_the_cpu) == {(2, KS, KH * KP)}  # a chip's rows
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, w in zip(OPERANDS, got_grads, want_grads):
        assert l2_error(g, w) < 1e-5, name
    assert got_grads[0].sharding.spec == P("dp")


# -- (b) the layers against the reference --------------------------------------------------


def text_namespace(**over):
    return types.SimpleNamespace(**{**dataclasses.asdict(tiny_nemo().text), **over})


def test_the_state_space_layer_matches_the_reference():
    layer = SsmMixer(width=32, num_heads=4, head_dim=8, state=16, groups=2, conv_size=4, chunk=8, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    params = moved(layer.init(jax.random.key(1), x)["params"], scale=0.2)
    assert set(params) == {"in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D", "norm", "out"}
    assert params["in_proj"]["kernel"].shape == (32, 32 + (32 + 2 * 32) + 4) and params["conv"].shape == (4, 96)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        want = jnp.stack([reference_nemotron.ssm(row, params, text_namespace()) for row in x])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # every leaf matters: the bias of the convolution, D and the gated norm's scale among them
    for leaf in ("conv_bias", "D", "norm", "dt_bias", "A_log"):
        other = layer.apply({"params": {**params, leaf: params[leaf] + 0.3}}, x)
        assert float(jnp.abs(other - got).max()) > 1e-3, leaf


def pallas_calls(jaxpr, under="") -> list:
    """(kernel name, name stack) of every ``pallas_call`` of a jaxpr, through the bodies of jits and custom rules
    (an inner jaxpr's stacks start where its equation's ends)."""
    found = []
    for eqn in jaxpr.eqns:
        scope = f"{under}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], scope))
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                found += pallas_calls(inner, scope)
    return found


@pytest.mark.parametrize("tokens, kernels", [(32, 6), (24, 0)], ids=["whole-tiles", "odd-sequence"])
def test_a_state_space_layers_convolution_through_its_kernel_pair_is_the_layer_with_xlas(kernel_path_on_the_cpu, monkeypatch,
                                                                                        tokens, kernels):
    """In bf16 on a TPU x', B and C (128 lanes each here) go through ``short_conv_fwd`` / ``short_conv_bwd`` with their
    columns of the taps and the bias, under the ``ssm_conv`` scope; a sequence that is no whole 16-row tiles keeps
    XLA's. (The recurrence stays chunked: these sizes are no whole registers.) Value, every leaf's gradient and x's."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    layer = SsmMixer(width=32, num_heads=4, head_dim=32, state=64, groups=2, conv_size=4, chunk=8, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(0), (2, tokens, 32), jnp.bfloat16)
    params = moved(layer.init(jax.random.key(1), x)["params"], scale=0.2)
    weight = jax.random.normal(jax.random.key(2), (2, tokens, 32))

    def run():
        loss = lambda p, x: (layer.apply({"params": p}, x).astype(jnp.float32) * weight).sum()  # noqa: E731
        return jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x), *jax.value_and_grad(loss, (0, 1))(params, x)

    traced, got, (got_p, got_x) = run()
    calls = pallas_calls(traced.jaxpr)
    assert kernel_path_on_the_cpu == []  # no ssd kernel
    assert sorted(name for name, _ in calls) == ["short_conv_bwd"] * (kernels // 2) + ["short_conv_fwd"] * (kernels // 2)
    assert all(SSM_CONV_SCOPE in scope for _, scope in calls)  # where ssm_conv_ms reads them
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: False)
    traced, want, (want_p, want_x) = run()
    assert "pallas_call" not in str(traced)
    np.testing.assert_allclose(got, want, rtol=2e-2)
    assert set(got_p) == set(want_p) and l2_error(got_x, want_x) < 2e-2
    for leaf in ("conv", "conv_bias", "in_proj", "out", "dt_bias", "A_log", "D", "norm"):
        g, w = (jax.tree.leaves(t[leaf])[0] for t in (got_p, want_p))
        assert g.shape == w.shape and g.dtype == w.dtype and l2_error(g, w) < 3e-2, leaf


def test_the_gated_norms_sums_over_a_groups_lanes_are_those_of_the_view():
    """The norm takes each group's mean square by a 0/1 product on (b, s, inner),
    never a (b, s, groups, width) view: the same values and gradients."""
    from distributed_sigmoid_loss_tpu.models.mixers import gated_group_norm

    k = jax.random.split(jax.random.key(0), 3)
    y, z, scale = jax.random.normal(k[0], (2, 5, 24)), jax.random.normal(k[1], (2, 5, 24)), 1.0 + jax.random.normal(k[2], (24,))

    def by_view(y, z, scale):
        gated = (y * nn.silu(z)).reshape(2, 5, 3, 8)
        return (gated * jax.lax.rsqrt(jnp.mean(gated * gated, -1, keepdims=True) + 1e-5)).reshape(2, 5, 24) * scale

    np.testing.assert_allclose(gated_group_norm(y, z, scale, 3, 1e-5), by_view(y, z, scale), atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(gated_group_norm(*a, 3, 1e-5) ** 3), (0, 1, 2))(y, z, scale)
    want = jax.grad(lambda *a: jnp.sum(by_view(*a) ** 3), (0, 1, 2))(y, z, scale)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    assert "reshape" not in str(jax.make_jaxpr(lambda *a: gated_group_norm(*a, 3, 1e-5))(y, z, scale))


def test_the_state_space_layers_leaves_start_as_mamba_2s():
    layer = SsmMixer(width=32, num_heads=64, head_dim=8, state=16, groups=8, conv_size=4, chunk=8, dtype=jnp.float32)
    p = layer.init(jax.random.key(0), jnp.zeros((1, 8, 32)))["params"]
    rate, step = np.exp(np.asarray(p["A_log"])), np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert rate.min() >= 1.0 and rate.max() < 16.0 and step.min() >= 1e-3 * 0.999 and step.max() <= 1e-1 * 1.001
    assert np.all(np.asarray(p["D"]) == 1.0) and np.all(np.asarray(p["norm"]) == 1.0)
    assert all(p[leaf].dtype == jnp.float32 for leaf in ("A_log", "dt_bias", "D", "conv", "conv_bias", "norm"))
    assert np.abs(np.asarray(p["conv"])).max() <= 0.5 and np.abs(np.asarray(p["conv_bias"])).max() <= 0.5


def grouped(**over):
    return Attention(32, 4, jnp.float32, causal=True, use_bias=False, num_kv_heads=2, head_dim=16, **over)


def test_grouped_heads_are_attention_with_the_keys_and_values_repeated():
    """32 -> 4 x 16 queries over 2 x 16 keys and values: the same numbers as the
    layer with every head its own, its k and v kernels the group's repeated."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 32))
    params = nn.meta.unbox(grouped().init(jax.random.key(1), x)["params"])
    assert {n: params[n]["kernel"].shape for n in "qkv"} == {"q": (32, 64), "k": (32, 32), "v": (32, 32)}
    assert params["out"]["kernel"].shape == (64, 32)
    every = Attention(32, 4, jnp.float32, causal=True, use_bias=False, head_dim=16)
    repeated = {**params, **{n: {"kernel": jnp.repeat(params[n]["kernel"].reshape(32, 2, 16), 2, axis=1).reshape(32, 64)} for n in "kv"}}
    with jax.default_matmul_precision("highest"):
        got, want = grouped().apply({"params": params}, x), every.apply({"params": repeated}, x)
        ref = jnp.stack([reference_nemotron.attention(row, params, text_namespace()) for row in x])
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got, ref, atol=2e-6)
    # query head h reads head h // 2, not h % 2: the other sharing gives other numbers
    wrongly = {**params, **{n: {"kernel": jnp.tile(params[n]["kernel"].reshape(32, 2, 16), (1, 2, 1)).reshape(32, 64)} for n in "kv"}}
    assert float(jnp.abs(every.apply({"params": wrongly}, x) - got).max()) > 1e-2


@pytest.fixture(scope="module")
def pair_under_groups():
    from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import latent_attention_kernel
    from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention

    k = jax.random.split(jax.random.key(0), 4)
    b, s, h, kv, d = 2, 200, 4, 2, 128  # padded to its block of 256
    q, key, v = (jax.random.normal(k[i], (b, s, n * d)) for i, n in enumerate((h, kv, kv)))
    weights = jax.random.normal(k[3], (b, s, h * d))

    def by_kernel(q, key, v):
        return latent_attention_kernel(q, key, v, head_dims=(d, d), kv_heads=kv, interpret=True)

    def by_repeating(q, key, v):
        rep = lambda t: jnp.repeat(t.reshape(b, s, kv, d), h // kv, axis=2)  # noqa: E731
        return dense_attention(q.reshape(b, s, h, d), rep(key), rep(v), causal=True).reshape(b, s, h * d)

    def both(core):
        return jax.value_and_grad(lambda *a: jnp.sum(core(*a) * weights), argnums=(0, 1, 2))(q, key, v)

    with jax.default_matmul_precision("highest"):
        return (by_kernel(q, key, v), by_repeating(q, key, v)), both(by_kernel), both(by_repeating)


def test_the_kernel_pair_reads_a_groups_one_head_where_it_lies(pair_under_groups):
    (got, want), _, _ = pair_under_groups
    assert got.shape == (2, 200, 4 * 128)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("leaf", ["q", "k", "v"])
def test_the_kernel_pairs_backward_sums_a_groups_dk_and_dv(pair_under_groups, leaf):
    _, (_, got), (_, want) = pair_under_groups
    i = "qkv".index(leaf)
    assert got[i].shape == want[i].shape == (2, 200, (4 if leaf == "q" else 2) * 128)
    np.testing.assert_allclose(got[i], want[i], atol=3e-4 * float(np.abs(np.asarray(want[i])).max()))


@pytest.mark.parametrize("attn_impl, dtype, tpu, kv, causal, tokens, core, repeated, block", [
    ("auto", jnp.bfloat16, True, 2, True, 4096, "kernel", False, 512),  # the cell's call
    ("auto", jnp.bfloat16, True, 2, True, 256, "kernel", False, 256),  # short too: the pair admits it
    ("auto", jnp.bfloat16, True, 2, False, 4096, "flash", True, 512),  # the pair is causal
    ("auto", jnp.float32, True, 2, True, 4096, "dense", True, None),
    ("auto", jnp.bfloat16, False, 2, True, 4096, "dense", True, None),
    ("flash", jnp.float32, True, 2, True, 4096, "kernel", False, 512),
    ("auto", jnp.bfloat16, True, 32, True, 4096, "flash", False, 512),  # every head its own: as it always was
    ("auto", jnp.bfloat16, True, 32, True, 64, "short", False, None),
    ("dense", jnp.bfloat16, True, 2, True, 4096, "dense", True, None),
])
def test_which_core_attention_takes_follows_from_what_the_call_can_see(monkeypatch, attn_impl, dtype, tpu, kv, causal,
                                                                       tokens, core, repeated, block):
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    sizes = attention_core(attn_impl, dtype, tokens, 32, kv, 128, causal)
    assert (sizes["core"], sizes["kv_repeated"], sizes["block"]) == (core, repeated, block)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"]) == (32, kv, 128)


def test_grouped_heads_on_the_kernel_path_repeat_nothing(monkeypatch):
    """With the backend read as a TPU's and the pair interpreted, the layer hands
    the pair q at 4 heads and k, v at 2, and its numbers are the dense path's."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_latent_attention

    seen = []
    kernel = pallas_latent_attention.latent_attention_kernel

    def noted(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape, kw["kv_heads"]))
        return kernel(q, k, v, interpret=True, **kw)

    layer = Attention(32, 4, jnp.float32, causal=True, use_bias=False, num_kv_heads=2, head_dim=128, attn_impl="flash")
    x = jax.random.normal(jax.random.key(0), (2, 40, 32))
    dense = layer.clone(attn_impl="dense")
    params = dense.init(jax.random.key(1), x)
    want = dense.apply(params, x)
    monkeypatch.setattr(pallas_latent_attention, "latent_attention_kernel", noted)
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    np.testing.assert_allclose(layer.apply(params, x), want, atol=2e-5)
    assert seen == [((2, 40, 512), (2, 40, 256), (2, 40, 256), 2)]


# -- (c) the relu2 experts ------------------------------------------------------------------


def dense_relu2_experts(x, up, down, idx, weights):
    """Every expert on every token, a mask for the chosen: what jax.grad differentiates."""
    hidden = jnp.square(jax.nn.relu(jnp.einsum("td,edn->ten", x, up)))
    weight = jnp.where(idx[..., None, :] == jnp.arange(up.shape[0])[:, None], weights[..., None, :], 0.0).sum(-1)
    return jnp.einsum("ten,end->td", hidden * weight[..., None], down)


@pytest.fixture(scope="module")
def relu2_gradients():
    from distributed_sigmoid_loss_tpu.models.moe import dispatch_plan

    k = jax.random.split(jax.random.key(0), 6)
    t, d, n, e, top = 50, 8, 12, 6, 2
    x, up, down = jax.random.normal(k[0], (t, d)), jax.random.normal(k[1], (e, d, n)) * 0.3, jax.random.normal(k[2], (e, n, d)) * 0.3
    idx = jnp.stack([jax.random.permutation(kk, e)[:top] for kk in jax.random.split(k[3], t)])
    weights, cot = jax.random.uniform(k[4], (t, top)) + 0.2, jax.random.normal(k[5], (t, d))

    def by_blocks(x, up, down, weights):
        token, row_weight, starts, counts = dispatch_plan(idx, weights, 0, e)
        y, done = routed_experts(x, (up, down), token, row_weight, starts, counts, 16)
        return jnp.sum(y * cot), done

    def by_mask(x, up, down, weights):
        return jnp.sum(dense_relu2_experts(x, up, down, idx, weights) * cot)

    with jax.default_matmul_precision("highest"):
        (got_loss, done), got = jax.value_and_grad(by_blocks, argnums=(0, 1, 2, 3), has_aux=True)(x, up, down, weights)
        want_loss, want = jax.value_and_grad(by_mask, argnums=(0, 1, 2, 3))(x, up, down, weights)
    assert int(done) == t * top
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    return got, want


@pytest.mark.parametrize("leaf", ["x", "up", "down", "weights"])
def test_the_relu2_experts_hand_written_backward_is_jax_grads(relu2_gradients, leaf):
    got, want = relu2_gradients
    i = ("x", "up", "down", "weights").index(leaf)
    assert np.abs(np.asarray(want[i])).max() > 1e-2
    np.testing.assert_allclose(got[i], want[i], atol=2e-5 * max(1.0, float(np.abs(np.asarray(want[i])).max())))


def test_the_loop_runs_the_blocks_a_skewed_load_needs_and_drops_nothing():
    """The selection bias sends every token to held expert 0 and none to held expert
    3: the loop runs expert 0's three blocks of 512 (its trip count is what the load
    needs, nothing besides), and the layer is the reference's on the same tree."""
    layer = SharedExpertMoe(8, 12, 16, 3, jnp.float32, route_scale=2.5, shared_experts=1, experts_held=4,
                            kind="relu2", shared_hidden=20)
    x = jax.random.normal(jax.random.key(0), (2, 600, 8))
    params = moved(layer.init(jax.random.key(1), x)["params"])
    params[SELECT_BIAS] = jnp.zeros(16).at[0].set(4.0).at[3].set(-4.0)
    t = types.SimpleNamespace(moe_num_selected=3, moe_route_scale=2.5)
    with jax.default_matmul_precision("highest"):
        y, state = layer.apply({"params": params}, x, mutable=["intermediates"])
        want = reference_nemotron.moe(x.reshape(1200, 8), params, t).reshape(x.shape)
    load = state["intermediates"]["moe_load"][0]
    assert int(load["tokens"][0]) == 1200 and int(load["tokens"][3]) == 0 and int(load["dropped"]) == 0
    np.testing.assert_allclose(y, want, atol=3e-5)
    from distributed_sigmoid_loss_tpu.models.moe import _block_plan

    total, first = _block_plan(jnp.asarray([0, 1200, 1300, 1812]), jnp.asarray([1200, 100, 512, 0]), 512)
    assert int(total) == 3 + 1 + 1 + 0 and first.tolist() == [0, 3, 4, 5]


def test_a_relu2_layer_has_two_stacks_and_a_shared_expert_of_its_own_width():
    layer = SharedExpertMoe(8, 12, 16, 3, jnp.float32, route_scale=2.5, shared_experts=1, experts_held=4,
                            kind="relu2", shared_hidden=20)
    x = jax.random.normal(jax.random.key(0), (2, 10, 8))
    params = nn.meta.unbox(layer.init(jax.random.key(1), x)["params"])
    assert set(params) == {"router", SELECT_BIAS, "wi", "wo", "shared"} and set(params["shared"]) == {"wi", "wo"}
    assert params["wi"].shape == (4, 8, 12) and params["shared"]["wi"]["kernel"].shape == (8, 20)
    mlp = Mlp(8, 20 / 8, jnp.float32, kind="relu2", use_bias=False)
    y = mlp.apply({"params": params["shared"]}, x)
    s = params["shared"]
    np.testing.assert_allclose(y, jnp.square(jax.nn.relu(x @ s["wi"]["kernel"])) @ s["wo"]["kernel"], atol=1e-6)
    with pytest.raises(ValueError, match="unknown expert kind"):
        dataclasses.replace(layer, kind="gelu").init(jax.random.key(1), x)
    with pytest.raises(ValueError, match="unknown mlp"):
        Mlp(8, 2, jnp.float32, kind="relu3").init(jax.random.key(1), x)


def test_sixteen_shares_of_eight_experts_and_the_shared_expert_once_are_the_whole_layer():
    """128 experts over 16 chips, top-6, scale 2.5: each share routes over all 128
    and computes its 8; the routed parts add up, with the shared expert counted
    once, to what the reference gives for the layer with all 128 held."""
    def routed_layer(held, first=0):
        return SharedExpertMoe(8, 12, 128, 6, jnp.float32, route_scale=2.5, shared_experts=1, experts_held=held,
                               first_held=first, kind="relu2", shared_hidden=20)

    whole = routed_layer(held=0)
    x = jax.random.normal(jax.random.key(0), (2, 40, 8))
    params = moved(whole.init(jax.random.key(1), x)["params"])
    t = types.SimpleNamespace(moe_num_selected=6, moe_route_scale=2.5)
    flat = x.reshape(80, 8)
    with jax.default_matmul_precision("highest"):
        want = reference_nemotron.moe(flat, params, t).reshape(x.shape)
        s = params["shared"]
        shared = reference_nemotron.relu2(x, s["wi"]["kernel"], s["wo"]["kernel"])
        total, seen = shared, 0
        for share in range(16):
            part = dict(params, **{n: params[n][8 * share : 8 * share + 8] for n in ("wi", "wo")})
            y, state = routed_layer(held=8, first=8 * share).apply({"params": part}, x, mutable=["intermediates"])
            total = total + (y - shared)
            seen += int(state["intermediates"]["moe_load"][0]["tokens"].sum())
            if share == 0:  # the share the cell holds: what this chip's layer gives is the reference's on the same share
                np.testing.assert_allclose(y, reference_nemotron.moe(flat, part, t).reshape(x.shape), atol=3e-5)
    assert seen == 2 * 40 * 6  # every assignment ran on exactly one share
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert np.abs(np.asarray(total - shared)).max() > 0.05  # the routed parts are no rounding


# -- (c') a start from balanced routers ----------------------------------------------------


def chosen_counts(scores, bias, k):
    _, idx = jax.lax.top_k(scores + bias, k)
    return np.bincount(np.asarray(idx).reshape(-1), minlength=scores.shape[1])


@pytest.mark.parametrize("shared_part", [0.2, 1.0, 3.0])
def test_the_balanced_bias_gives_every_expert_its_share_however_uneven_the_scores(shared_part):
    """Scores with a part every token shares (what routers drawn at random see:
    the sub-layers before them give every token a common vector): unbiased, some
    experts take many times their share and some nothing; under the balanced bias
    each takes T k / E of the tokens it was found on, and of fresh ones as many
    to a sample's noise."""
    from distributed_sigmoid_loss_tpu.models.moe import balanced_select_bias

    keys = jax.random.split(jax.random.key(int(10 * shared_part)), 4)
    offset = shared_part * jax.random.normal(keys[0], (32,))
    mix = jax.random.normal(keys[1], (16, 32)) / 4
    scores = lambda key: jax.nn.sigmoid(offset + jax.random.normal(key, (4096, 16)) @ mix)  # noqa: E731
    seen, fresh, share = scores(keys[2]), scores(keys[3]), 4096 * 4 // 32
    unbiased = chosen_counts(seen, 0.0, 4)
    assert unbiased.max() > 1.5 * share and unbiased.min() < 0.6 * share
    bias = jax.jit(balanced_select_bias, static_argnums=1)(seen, 4)
    assert bias.shape == (32,) and bias.dtype == jnp.float32
    assert np.abs(chosen_counts(seen, bias, 4) - share).max() <= 0.03 * share
    assert np.abs(chosen_counts(fresh, bias, 4) - share).max() <= 0.25 * share  # sqrt(512) is 4 %: six deviations


def test_the_balanced_bias_of_a_router_that_takes_every_expert_is_zero():
    from distributed_sigmoid_loss_tpu.models.moe import balanced_select_bias

    assert not np.asarray(balanced_select_bias(jnp.full((10, 3), 0.5), 3)).any()


@pytest.fixture(scope="module")
def balanced_start():
    """The toy stack at 256 tokens a caption over 512 ids, initialised twice from
    one key: as drawn, and with ``moe_balanced_init``."""
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    shapes = {"images": jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.float32), "tokens": jax.ShapeDtypeStruct((8, 256), jnp.int32)}
    out = {}
    for balanced in (False, True):
        cfg = tiny_nemo(context_length=256, vocab_size=512, moe_balanced_init=balanced)
        out[balanced] = (SigLIP(cfg), init_params(jax.random.key(5), SigLIP(cfg), shapes, make_mesh(1)))
    return out


def test_a_balanced_start_moves_the_selection_biases_and_nothing_else(balanced_start):
    drawn, balanced = (nn.meta.unbox(balanced_start[b][1]) for b in (False, True))
    moved_leaves = []
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(drawn), jax.tree.leaves(balanced)):
        assert a.shape == b.shape and a.dtype == b.dtype and a.sharding == b.sharding
        if not np.array_equal(a, b):
            moved_leaves.append(jax.tree_util.keystr(path))
    assert moved_leaves == [f"['textual']['encoder']['block{i}']['moe']['{SELECT_BIAS}']" for i in (1, 4)]
    assert not np.asarray(drawn["textual"]["encoder"]["block1"]["moe"][SELECT_BIAS]).any()


def test_a_balanced_start_loads_the_held_experts_alike_on_other_ids(balanced_start):
    """Ids the initialisation never saw: as drawn the four held experts' loads
    lie far apart; balanced, each is within a sample's noise of T k / E."""
    tokens = jax.random.randint(jax.random.key(77), (8, 256), 0, 512)
    share, spread = 8 * 256 * 3 / 16, {}
    for balanced, (model, params) in balanced_start.items():
        _, state = model.apply({"params": params}, tokens, method="encode_text", mutable=["intermediates"])
        loads = np.asarray([
            x for p, x in jax.tree_util.tree_leaves_with_path(state["intermediates"]) if getattr(p[-1], "key", None) == "tokens"
        ])
        assert loads.shape == (2, 4)
        spread[balanced] = np.abs(loads - share).max() / share
    assert spread[True] < 0.25 and spread[True] < spread[False]


def test_the_step_of_a_balanced_start_is_the_step(balanced_start):
    """The option acts at initialisation alone: the step's program is the same."""
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

    batch = {"images": jnp.zeros((4, 16, 16, 3), jnp.float32), "tokens": jnp.zeros((4, 256), jnp.int32)}
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    programs = []
    for balanced in (False, True):
        model, mesh = balanced_start[balanced][0], make_mesh(1)
        state = create_train_state(jax.random.key(0), model, make_optimizer(TrainConfig()), shapes, mesh, zeros=True)
        programs.append(make_train_step(model, mesh, model.cfg.loss)[0].lower(state, batch).as_text())
    assert programs[0] == programs[1]


def test_a_balanced_start_needs_a_sigmoid_router():
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    cfg = SigLIPConfig(vision=ViTConfig.tiny_test(), text=dataclasses.replace(TextConfig.tiny_test(), moe_balanced_init=True))
    shapes = {"images": jax.ShapeDtypeStruct((2, 16, 16, 3), jnp.float32), "tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    with pytest.raises(ValueError, match="moe_balanced_init=True"):
        init_params(jax.random.key(0), SigLIP(cfg), shapes, make_mesh(1))


# -- (d) the whole tower, the train step, and one accumulated step through run.py ---------


@pytest.fixture(scope="module")
def tower_and_reference():
    cfg = tiny_nemo(remat=True)
    model = SigLIP(cfg)
    images, tokens = tiny_batch()
    params = moved(model.init(jax.random.key(0), images, tokens)["params"])

    def system_loss(p):
        zimg, ztxt, lp = model.apply({"params": p}, images, tokens)
        return sigmoid_loss(zimg, ztxt, lp["t_prime"], lp["bias"]), ztxt

    def reference_loss(p):
        zimg, ztxt = reference_nemotron.embeddings(cfg, p, images, tokens)
        return reference_nemotron._base.sigmoid_loss(zimg, ztxt, p["t_prime"], p["bias"]), ztxt

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(system_loss, has_aux=True))(params)
        want = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))(params)
        _, first = reference_nemotron.make_sample_grads(cfg)(params, images, tokens)
    return params, got, want, first


@pytest.mark.parametrize("what", ["embeddings", "loss", "gradients", "first_blocks"])
def test_the_tower_matches_the_plain_reference(tower_and_reference, what):
    params, ((got_loss, got_z), got), ((want_loss, want_z), want), first = tower_and_reference
    if what == "embeddings":
        np.testing.assert_allclose(got_z, want_z, atol=1e-5)
    elif what == "loss":
        np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    elif what == "gradients":
        errs = reference_nemotron.tree_max_rel_err(got, want)
        assert len(errs) == len(jax.tree.leaves(params))
        assert max(errs.values()) < 3e-4, max(errs, key=errs.get)
        bias_grads = [v for k, v in jax.tree_util.tree_leaves_with_path(got) if SELECT_BIAS in jax.tree_util.keystr(k)]
        assert len(bias_grads) == 2 and not any(np.asarray(g).any() for g in bias_grads)
    else:
        # The interface `correct` calls: the first layer of each kind, the routed one without its router.
        assert set(first["textual"]) == {"block0", "block1", "block3"}
        assert set(first["textual"]["block0"]) == {"ln1", "ssm"} and set(first["textual"]["block3"]) == {"ln1", "attn"}
        assert set(first["textual"]["block1"]) == {"ln1", "moe"}
        assert set(first["textual"]["block1"]["moe"]) == {"wi", "wo", "shared"}
        compared = reference_nemotron.first_blocks(got)["textual"]
        assert max(reference_nemotron.tree_max_rel_err(compared, first["textual"]).values()) < 3e-4


def test_the_reference_refuses_another_stack():
    with pytest.raises(ValueError, match="reference_nemotron.py has the equations"):
        reference_nemotron.make_sample_grads(tiny_nemo(mlp="swiglu"))
    with pytest.raises(ValueError, match="reference_nemotron.py has the equations"):
        reference_nemotron.make_batch_loss(tiny_nemo(sublayers="pair", mixers=("ssm",) * 5), 1, np.arange(4)[None])


def test_the_tower_through_the_train_step():
    """The normal path: ``make_train_step`` with accumulation. The trace-time
    record names each layer's one kind and says which cores ran; the metrics line
    carries the routed layers' counters; the program carries the names the
    benchmark's readers look for."""
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack, mixed_stack_line
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import STACK_RECORD_FIELDS, validate_metrics
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

    cfg = tiny_nemo(remat=True, scan_layers=True)  # scan_layers does not apply: unlike layers
    model, mesh = SigLIP(cfg), make_mesh(1)
    images, tokens = tiny_batch(8)
    shapes = {"images": jax.ShapeDtypeStruct(images.shape, images.dtype),
              "tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype)}
    state = create_train_state(jax.random.key(0), model, make_optimizer(TrainConfig(weight_decay=0.1)), shapes, mesh)
    encoder = state.params["textual"]["encoder"]
    assert set(encoder) == {"block0", "block1", "block2", "block3", "block4", "ln_final"}
    assert [sorted(encoder[f"block{i}"]) for i in range(5)] == [
        ["ln1", "ssm"], ["ln1", "moe"], ["ln1", "ssm"], ["attn", "ln1"], ["ln1", "moe"]]  # one norm a layer, no ln2
    assert "pos_embed" not in state.params["textual"]
    step, _ = make_train_step(model, mesh, cfg.loss, accum_steps=2, accum_dtype="bfloat16")
    lowered = step.lower(state, {"images": images, "tokens": tokens}).as_text(debug_info=True)
    for name in ("ssm", SSM_CONV_SCOPE, SSM_CORE_SCOPE, ATTN_CORE_SCOPE, "moe", MOE_ROUTE_SCOPE):
        assert f"/{name}/" in lowered or f"/{name}\"" in lowered, name
    assert f"/ssm/{SSM_CORE_SCOPE}/" in lowered and f"/attn/{ATTN_CORE_SCOPE}/" in lowered
    before = encoder["block0"]["ssm"]["A_log"]
    for _ in range(2):
        state, metrics = step(state, {"images": images, "tokens": tokens})
    metrics = {k: float(v) for k, v in metrics.items()}
    validate_metrics(metrics)
    assert np.isfinite(metrics["loss"]) and metrics["moe_dropped_tokens"] == 0
    assert 0 < metrics["moe_local_assignments"] <= 2 * 2 * 96 * 3  # two microbatches, two routed layers
    assert not np.array_equal(state.params["textual"]["encoder"]["block0"]["ssm"]["A_log"], before)
    assert not np.asarray(state.params["textual"]["encoder"]["block1"]["moe"][SELECT_BIAS]).any()  # never updated
    record = mixed_stack(step)
    assert set(record) <= set(STACK_RECORD_FIELDS)
    assert record["layer_kinds"] == ["ssm", "moe", "ssm", "attn", "moe"]
    assert (record["experts_held"], record["experts_total"], record["experts_per_token"]) == (4, 16, 3)
    assert set(record["ssm"]) == {0, 2} and record["ssm"][0] == {
        "core": "chunked", "chunk": 8, "chunks": 3, "rows_per_pass": 4, "heads": 4, "groups": 2, "head_dim": 8, "state": 16,
        "kept_bytes": 0}
    assert record["attn"] == {3: {"core": "dense", "block": None, "heads": 4, "kv_heads": 2, "head_dim": 16, "kv_repeated": True,
                                  "window": None, "block_pairs": None, "admitted_pairs": 24 * 25 // 2, "rotated": False,
                                  "qk_norm": False, "gated": False}}
    # on this CPU the convolution of x' (32 lanes) and of B and C (32) is XLA's in both state-space layers
    assert record["short_conv"] == {i: [{"form": "xla", "channels": 32, "tile": None}] * 2 for i in (0, 2)}
    line = mixed_stack_line(record)
    assert line.startswith("stack: ssm moe ssm attn moe; ssm[0] core=chunked 3 chunks of 8, 4 heads of 8 in 2 groups, state 16")
    assert "conv[2] xla of 32, xla of 32" in line
    assert "attn[3] core=dense 4/2 heads of 16 (keys and values repeated)" in line
    assert step._cache_size() == 1


def cell_config(name=CELL):
    job = harness.load_module(os.path.join(BENCH_DIR, "jobs", "train_step.py"))
    cell = harness.Cell(name, rehearse=False)
    return cell, job.build_config(cell.config, cell.traffic)


def test_the_record_of_the_cells_stack_on_a_tpu(monkeypatch):
    """By the rules the layers run by: on a TPU in bf16 the cell's three state-space
    layers take the kernel pair, 32 chunks of 128 a sequence, a microbatch's four rows
    in one call, each chunk's incoming state kept for the backward (268 MB a call),
    and its attention layer the kernel pair with nothing repeated."""
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack_line
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train.train_step import stack_record_of

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    _, cfg = cell_config()
    record = stack_record_of(cfg.text, (4, 4096))
    assert record["layer_kinds"] == ["ssm", "moe", "ssm", "moe", "ssm", "attn", "moe"]
    assert sorted(record["ssm"]) == [0, 2, 4] and record["ssm"][0] == {
        "core": "kernel", "chunk": 128, "chunks": 32, "rows_per_pass": 4, "heads": 64, "groups": 8, "head_dim": 64,
        "state": 128, "kept_bytes": 4 * 32 * 128 * 64 * 64 * 4}
    assert record["attn"] == {5: {"core": "kernel", "block": 512, "heads": 32, "kv_heads": 2, "head_dim": 128, "kv_repeated": False,
                                  "window": None, "block_pairs": 36, "admitted_pairs": 4096 * 4097 // 2, "rotated": False,
                                  "qk_norm": False, "gated": False}}
    assert (record["experts_held"], record["experts_total"], record["expected_local_assignments_per_token"]) == (8, 128, 0.375)
    assert record["tokens_per_microbatch"] == 16384 and record["dispatch_rows_bound"] == 16384 * 6
    line = mixed_stack_line(record)
    assert ("ssm[4] core=kernel 32 chunks of 128, 64 heads of 64 in 8 groups, state 128, 4 rows a pass, "
            "268 MB kept for the backward") in line
    assert "attn[5] core=kernel 32/2 heads of 128, blocks of 512 tokens" in line
    # the convolution of x' (4096 lanes) and of B and C (1024 each) takes its kernel pair in every state-space layer
    conv = [{"form": "kernel", "channels": 4096, "tile": (2048, 512)}, {"form": "kernel", "channels": 1024, "tile": (2048, 512)}]
    assert record["short_conv"] == {0: conv, 2: conv, 4: conv}
    assert "conv[4] kernel 2048x512 of 4096, kernel 2048x512 of 1024" in line
    # the cells the benchmark had keep their records: no state-space or attention entry
    for other, shape in (("kimi-b16-p64-s1024", (16, 1024)), ("glm-b16-p16-s4096", (4, 4096))):
        assert not {"ssm", "attn"} & set(stack_record_of(cell_config(other)[1].text, shape))


CONTROLS = ("no_decay", "no_gate", "gated_expert", "kv_shared", "fp8")


def test_the_job_rehearses_through_run_py_and_each_planted_fault_fails_it(tmp_path, monkeypatch, capsys):
    """benchmark/run.py end to end on the CPU at a toy size, through
    jobs/train_step.py with the configuration's own modules: `correct` against
    reference_nemotron.py on the accumulated step, sound true, and false for each
    of the five faults planted through the same `main` (benchmark/tests/controls_nemotron.py)."""
    import run

    tiny = tiny_nemo(remat=True)
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    fields = lambda cfg, names: {n: getattr(cfg, n) for n in names}  # noqa: E731
    modules = harness.Cell(CELL, rehearse=False).config["modules"]
    assert modules == {"reference": "reference_nemotron", "count": "flops_nemotron"}
    (tmp_path / "configs" / "tiny-nemo.json").write_text(json.dumps({
        "modules": modules,
        "vision": fields(tiny.vision, ["image_size", "patch_size", "width", "depth", "num_heads", "embed_dim", "use_proj", "dtype"]),
        "text": fields(tiny.text, ["vocab_size", "width", "embed_dim", "dtype", *NEMO]),
        "loss": {},
    }))
    mix = harness.load_json(os.path.join(BENCH_DIR, "traffic", "p16-s4096.json"))
    mix.update(pairs_per_chip_per_step=8, microbatch=4)
    mix["step"]["accum_steps"] = 2
    (tmp_path / "traffic" / "p8-s24.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-nemo", "file": "configs/tiny-nemo.json"}],
        "workloads": [{"name": "tiny-nemo-p8-s24", "config": "tiny-nemo", "traffic": "p8-s24", "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }))
    monkeypatch.setattr(harness, "REHEARSAL_DIR", str(tmp_path))
    monkeypatch.setattr(harness, "prepare_environment", lambda cell, rehearse: None)
    seed = "4300000007"
    assert run.main(["--rehearse", "--workload", "tiny-nemo-p8-s24", "--seed", seed, "--seconds", "1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0 and lines[-1]["metrics"] == {}
    assert all(lines[0]["verdicts"].values()), lines[0]
    assert lines[0]["grad_err"] < 1e-3 and lines[0]["embed_err"] < 1e-4

    from distributed_sigmoid_loss_tpu.models import mixers, moe
    from distributed_sigmoid_loss_tpu.ops import ssm

    controls = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_nemotron.py"))
    kept = (ssm.ssm_scan, mixers.gated_group_norm, moe._expert_mlp, moe._expert_mlp_bwd, nn.Dense.__call__)
    reads = [arg for name in ("sound", *CONTROLS[1:]) for arg in ("--read", f"{name}:{seed}")]
    assert controls.main(["--rehearse", "--workload", "tiny-nemo-p8-s24", "--seconds", "1", "--run", f"no_decay:{seed}", *reads,
                          "--forced", seed]) == 0
    assert kept == (ssm.ssm_scan, mixers.gated_group_norm, moe._expert_mlp, moe._expert_mlp_bwd, nn.Dense.__call__)  # it undoes its patches
    lines = [json.loads(line) for line in capsys.readouterr().out.strip().splitlines()]
    (ran,) = lines[-1]["runs"]  # one fault through run.py's own main: its `correct` is false, no step failed
    assert (ran["variant"], ran["correct"], ran["failed"]) == ("no_decay", False, 0)
    assert ran["compared"]["grad_err"][0] > ran["compared"]["grad_err"][1]
    reads = {line["variant"]: line for line in lines if line.get("info") == "controls.read"}
    assert sorted(reads) == sorted(("sound", *CONTROLS[1:]))
    assert reads["sound"]["grad_err"] < 1e-3 and reads["sound"]["embed_err"] < 1e-4
    over = {name: sorted(k for k in ("embed_err", "grad_err") if r[k] > r["bounds"][k])
            + ["loss_err"] * (r["sample_loss_err"] > r["bounds"]["loss_err"]) for name, r in reads.items()}
    assert not over["sound"]
    for name in ("no_gate", "gated_expert", "kv_shared"):
        assert over[name], (name, reads[name])
    # --forced: where the program's and the reference's selections differ (a float32 toy tower: nowhere), each sample
    # caption's pooled token with both selections, and the comparison with the reference on the program's selection
    selections = {line["layer"]: line for line in lines if line.get("info") == "controls.forced.selection"}
    assert sorted(selections) == ["block1", "block4"]
    for line in selections.values():
        assert line["tokens"] == 3 * 24 and line["tokens_whose_experts_differ"] == 0 and len(line["pooled_tokens"]) == 3
        assert all(t["program"] == t["reference"] and t["reference_margin"] > 0 for t in line["pooled_tokens"])
    for info in ("controls.forced.sound", "controls.forced.read"):  # the reference on its own selection, then on the program's
        (forced,) = [line for line in lines if line.get("info") == info]
        assert forced["grad_err"] < 1e-3 and max(forced["text_rows"]) < 1e-4
    # fp8 in the core's operands: at this toy size (a float32 tower, two small state-space layers in a stream the
    # embedding dominates) it moves the embeddings by hundreds of times a sound run's error; whether it passes a limit
    # is the chip's to say at the cell's sizes (reference_nemotron.py has the readings)
    assert reads["fp8"]["embed_err"] > 100 * reads["sound"]["embed_err"]
    assert reads["fp8"]["grad_err"] > 100 * reads["sound"]["grad_err"]


# -- (e) the benchmark's configuration and count -------------------------------------------


def test_the_cell_is_the_published_model_cut_as_written():
    cell, cfg = cell_config()
    t, published = cfg.text, cell.config
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(guide):  # every published key, as the catalog has it
        catalog = next(row for row in map(json.loads, open(guide)) if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert published["source"] == catalog["source_url"]
        assert {k: published[k] for k in catalog["config"]} == catalog["config"]
    assert published["model_type"] == "nemotron_h" and published["hybrid_override_pattern"].startswith("MEMEM*E")
    assert (t.width, t.norm_eps) == (published["hidden_size"], published["layer_norm_epsilon"]) == (2688, 1e-5)
    assert (t.num_heads, t.num_kv_heads, t.head_dim) == (
        published["num_attention_heads"], published["num_key_value_heads"], published["head_dim"]) == (32, 2, 128)
    assert (t.ssm_num_heads, t.ssm_head_dim, t.ssm_state, t.ssm_groups, t.ssm_conv_size, t.ssm_chunk) == (
        published["mamba_num_heads"], published["mamba_head_dim"], published["ssm_state_size"], published["n_groups"],
        published["conv_kernel"], published["chunk_size"]) == (64, 64, 128, 8, 4, 128)
    assert (t.moe_hidden, t.moe_shared_hidden, t.moe_experts, t.moe_num_selected, t.moe_shared_experts, t.moe_route_scale) == (
        published["moe_intermediate_size"], published["moe_shared_expert_intermediate_size"], published["n_routed_experts"],
        published["num_experts_per_tok"], published["n_shared_experts"], published["routed_scaling_factor"]) == (1856, 3712, 128, 6, 1, 2.5)
    assert t.moe_router == "sigmoid" and published["norm_topk_prob"] and published["n_group"] == published["topk_group"] == 1
    assert t.mlp == published["mlp_hidden_act"] == "relu2" and published["use_conv_bias"] and not published["use_bias"]
    letters = {"M": "ssm", "E": "moe", "*": "attn"}
    assert list(t.mixers) == [letters[c] for c in published["hybrid_override_pattern"][:7]] and t.sublayers == "single"
    assert (t.causal, t.pool, t.norm, t.use_bias, t.pos, t.dtype) == (True, "last", "rmsnorm", False, "none", "bfloat16")
    # the cut: depth, the experts held, the vocabulary; nothing else
    assert published["reduced"] == ["text.depth", "text.moe_experts_held", "text.vocab_size"]
    assert (t.depth, published["num_hidden_layers"], len(published["hybrid_override_pattern"])) == (7, 52, 52)
    assert (t.moe_experts_held, published["n_routed_experts"]) == (8, 128)
    assert (t.vocab_size * 8, published["vocab_size"]) == (131072, 131072)
    assert "16 chips share each layer" in published["deployment"] and t.context_length == 4096
    assert t.moe_balanced_init  # the routers start balanced, as trained ones are held: the load is not the seed's
    for stated in ("no rotation", "gated norm", "ssm", "dt", "A_log, dt_bias, D", "attention", "rescale_prenorm_residual", "left out",
                   "text.moe_balanced_init"):
        assert stated in published["assumed"], stated
    b16 = harness.load_json(os.path.join(BENCH_DIR, "configs", "siglip-b16-224.json"))
    assert published["vision"] == b16["vision"]  # the B/16 image tower, field for field
    mix = cell.traffic
    assert (mix["pairs_per_chip_per_step"], mix["microbatch"], mix["step"]["accum_steps"], cell.chips) == (16, 4, 4, 1)
    assert harness.Cell("glm-b16-p16-s4096", rehearse=False).traffic == mix  # the mix the benchmark has


def test_the_text_tower_has_the_parameters_the_shapes_say():
    _, cfg = cell_config()
    shapes = jax.eval_shape(
        SigLIP(cfg).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32), jax.ShapeDtypeStruct((1, 4096), jnp.int32),
    )["params"]
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(nn.meta.unbox(tree)))  # noqa: E731
    w = 2688
    ssm = w * 10304 + 4096 * w + 4 * 6144 + 6144 + 3 * 64 + 4096  # + the taps, their bias, A_log / dt_bias / D, the gated norm
    attn = w * 4096 + 2 * w * 256 + 4096 * w
    shared, experts, router = 2 * w * 3712, 8 * 2 * w * 1856, w * 128 + 128
    encoder = shapes["textual"]["encoder"]
    assert count(encoder["block0"]) == ssm + w and count(encoder["block5"]) == attn + w
    assert count(encoder["block1"]) == shared + experts + router + w
    assert [round(n / 1e6, 2) for n in (ssm, attn, shared, router, experts)] == [38.74, 23.4, 19.96, 0.34, 79.82]
    assert round((shared + experts + router) / 1e6, 1) == 100.1  # ISSUE 43: a routed layer
    stack = 3 * ssm + attn + 3 * (shared + experts + router) + 7 * w + w
    assert count(encoder) == stack and round(stack / 1e6, 1) == 440.0  # ISSUE 43: layers 0-6
    assert count(shapes["textual"]) == stack + 16384 * w + w * 768 + 768
    assert round(count(shapes) / 1e6) == 579  # ISSUE 43: with the embedding's eighth, the head and the B/16 image tower
    assert "pos_embed" not in shapes["textual"]


def test_the_count_is_the_issues():
    """benchmark/tests/test_flops_nemotron.py has the count by hand; tier-1 keeps its ends."""
    _, cfg = cell_config()
    assert round(flops_nemotron.text_forward_flops(cfg.text) / 4096 / 1e6) == 467  # MFLOP a text token
    assert round(16 * flops_nemotron.train_flops_per_pair(cfg) / 1e12) == 94  # ISSUE 43: 94 TFLOP a step
    assert flops_nemotron.expected_assignments_per_token(cfg.text) == 0.375
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops_nemotron.attention_least_s(cfg, "textual", 16, peaks) == 0.0
    assert flops_nemotron.attention_least_s(cfg, "visual", 16, peaks) > 0.0
    assert round(1e3 * flops_nemotron.ssm_core_least_s(cfg, 16, peaks), 1) == 10.2
    assert round(1e3 * flops_nemotron.gqa_core_least_s(cfg, 16, peaks), 1) == 33.5


# -- (f) the defaults are left alone, and what cannot run it refuses by name --------------


def test_the_defaults_leave_every_other_tree_and_program_alone():
    """No new field set: no layer specs, the trees PR 40 had, and ``Attention``
    told its default head sizes outright lowers to the program it lowers to
    untold."""
    for cfg in (SigLIPConfig.tiny_test(), SigLIPConfig.b16(), SigLIPConfig.so400m()):
        assert layer_specs(cfg.text) == ()
        t = cfg.text
        assert (t.num_kv_heads, t.head_dim, t.sublayers, t.moe_shared_hidden, t.moe_balanced_init) == (0, 0, "pair", 0, False)
    t = TextConfig.tiny_test()
    tokens = jax.ShapeDtypeStruct((2, t.context_length), jnp.int32)
    shapes = jax.eval_shape(TextTransformer(t).init, jax.random.key(0), tokens)["params"]
    paths = {jax.tree_util.keystr(k) for k, _ in jax.tree_util.tree_leaves_with_path(nn.meta.unbox(shapes))}
    assert not [p for p in paths if any(f"'{n}'" in p for n in ("ssm", "moe", "conv", "A_log"))]
    assert sorted(shapes["encoder"]["block0"]) == ["attn", "ln1", "ln2", "mlp"]
    x = jnp.ones((2, 8, 32))
    untold, told = Attention(32, 2, jnp.float32), Attention(32, 2, jnp.float32, num_kv_heads=2, head_dim=16)
    params = untold.init(jax.random.key(0), x)
    lower = lambda m: jax.jit(m.apply).lower(params, x).as_text()  # noqa: E731
    assert lower(untold) == lower(told)


@pytest.mark.parametrize("over, named", [
    (dict(sequence_parallel_axis="sp"), "sequence_parallel_axis="),
    (dict(quant_train="int8"), "quant="),
    (dict(loops=2), "loops=2"),
    (dict(causal=False), "causal=False"),
    (dict(pos="learned"), "pos="),
    (dict(pos="rope"), "pos="),
    (dict(mixers=("ssm", "moe", "gru", "attn", "moe")), "unknown mixer"),
    (dict(mixers=()), r"mixers=\(\)"),
    (dict(leading_dense_layers=1), "leading_dense_layers=1"),
    (dict(moe_experts=0), "moe_experts=0"),
    (dict(mlp="gelu"), "moe_router='sigmoid'"),
    (dict(sublayers="triple"), "unknown sublayers"),
    (dict(ssm_groups=3), "ssm_groups=3"),
    (dict(num_kv_heads=3), "num_kv_heads=3"),
    (dict(sublayers="pair"), "unknown mixer"),  # "moe" names a layer of a one-sub-layer stack only
])
def test_what_the_stack_does_not_run_with_is_refused_by_name(over, named):
    cfg = dataclasses.replace(tiny_nemo().text, **over)
    with pytest.raises(ValueError, match=named):
        jax.eval_shape(TextTransformer(cfg).init, jax.random.key(0), tiny_batch()[1])


def test_grouped_heads_refuse_sequence_parallel_attention_by_name():
    layer = Attention(32, 4, jnp.float32, num_kv_heads=2, sp_axis="sp")
    with pytest.raises(ValueError, match="num_kv_heads=2"):
        jax.eval_shape(layer.init, jax.random.key(0), jnp.ones((2, 8, 32)))


@pytest.mark.parametrize("over, named", [
    (dict(sublayers="pair", mixers=("ssm", "attn"), depth=2, moe_experts=0, moe_router="softmax"), r"unknown mixer.*'ssm'.*sublayers='pair'"),
    (dict(mixers=("ssm", "mlp", "moe"), depth=3), r"unknown mixer.*'mlp'.*sublayers='single'"),
    (dict(mixers=("kda", "moe"), depth=2), r"unknown mixer.*'kda'.*sublayers='single'"),
])
def test_a_stack_takes_the_layers_a_configuration_runs_and_no_others(over, named):
    """The state-space mixer is a layer alone and a one-sub-layer stack is made
    of "ssm", "attn" and "moe": no cell runs "ssm" beside an MLP, a dense MLP
    alone, or another mixer alone, so each is refused by name."""
    with pytest.raises(ValueError, match=named):
        layer_specs(dataclasses.replace(tiny_nemo().text, **over))


def test_a_stack_of_like_one_sub_layer_layers_scans():
    tokens = tiny_batch()[1]
    like = dataclasses.replace(tiny_nemo().text, mixers=("ssm",) * 3, depth=3, scan_layers=True, moe_experts=0, moe_router="softmax")
    assert sorted(jax.eval_shape(TextTransformer(like).init, jax.random.key(0), tokens)["params"]["encoder"]) == ["blocks", "ln_final"]
    single = jax.eval_shape(TextTransformer(tiny_nemo().text).init, jax.random.key(0), tokens)["params"]["encoder"]
    assert [sorted(single[f"block{i}"]) for i in range(5)] == [["ln1", "ssm"], ["ln1", "moe"], ["ln1", "ssm"], ["attn", "ln1"], ["ln1", "moe"]]


NEW_OPTIONS = dict(sublayers="single", num_kv_heads=2, head_dim=16, moe_shared_hidden=40)


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
    whole = dataclasses.replace(tiny_nemo().text, scan_layers=True)
    with pytest.raises(ValueError, match=r"mixers=.*sublayers='single'"):
        validate_pp_tower(whole, 2, "text")
