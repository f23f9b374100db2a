"""The layers a mixed text stack brings (tests/test_hybrid_tower.py has the tower):
the chunked gated delta rule against the token-by-token recurrence, latent attention
against the plain reference (``benchmark/reference_kimi.py``) and its padded-value
path, the sigmoid router's rules, the dropless dispatch, and a chip's share of the
experts against the whole layer."""

import os
import sys
import types
from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.models.mixers import LatentAttention
from distributed_sigmoid_loss_tpu.models.moe import (
    SELECT_BIAS,
    SharedExpertMoe,
    dispatch_plan,
    sigmoid_route,
)
from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import (
    chunk_gated_delta_rule,
    delta_rule_core,
    gated_delta_rule_recurrent,
    normed_chunk_gated_delta_rule,
    l2norm,
)
from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import reference_kimi  # noqa: E402


def moved(params, scale=0.05):
    """Every leaf away from its initial value (norm scales start at one, the
    selection bias at zero): a dropped scale or a dropped leaf then shows."""
    leaves, tree = jax.tree.flatten(nn.meta.unbox(params))
    keys = jax.random.split(jax.random.key(3), len(leaves))
    return jax.tree.unflatten(tree, [
        x + scale * jax.random.normal(k, x.shape, x.dtype) if x.ndim else x for x, k in zip(leaves, keys)
    ])


# -- (a) the chunked delta rule against the recurrence ----------------------------


def delta_rule_inputs(s, dtype, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(s), 5)
    q, k = (jax.random.normal(key, (b, s, h, dk)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    # log-decays from 2.5e-3 to 33 a token: slow channels, and channels that
    # forget within a chunk (a quotient of decays would overflow there)
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, dk), minval=-6.0, maxval=3.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


# bf16: the operands of the chunk's products are rounded to 8 bits (2^-9 relative
# each) and summed in float32; measured here 4e-3 to 9e-3 forward, to 2.4e-2 on
# the gradients over these seeds.
@pytest.mark.parametrize("dtype, bound", [(jnp.float32, 1e-5), (jnp.bfloat16, 5e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_chunked_delta_rule_is_the_recurrence(chunks, dtype, bound):
    chunk = 8
    args = delta_rule_inputs(chunks * chunk, dtype)
    weight = jnp.cos(jnp.arange(2 * chunks * chunk * 3 * 8, dtype=jnp.float32)).reshape(2, -1, 3, 8)

    def grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(), argnums=(0, 1, 2, 3, 4)
        ))(*args)

    want_out = jax.jit(gated_delta_rule_recurrent)(*args)
    got_out = jax.jit(lambda *a: chunk_gated_delta_rule(*a, chunk=chunk))(*args)
    assert got_out.dtype == dtype and bool(jnp.isfinite(got_out.astype(jnp.float32)).all())
    assert reference_kimi._base.max_rel_err(got_out, want_out) < bound
    (_, got), (_, want) = grads(lambda *a: chunk_gated_delta_rule(*a, chunk=chunk)), grads(gated_delta_rule_recurrent)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert reference_kimi._base.max_rel_err(g, w) < bound, name


def test_a_sequence_that_is_no_multiple_of_the_chunk_and_a_batch_run_in_passes(monkeypatch):
    from distributed_sigmoid_loss_tpu.ops import gated_delta_rule

    args = delta_rule_inputs(20, jnp.float32, b=4)
    want = gated_delta_rule_recurrent(*args)
    assert reference_kimi._base.max_rel_err(chunk_gated_delta_rule(*args, chunk=8), want) < 1e-5
    monkeypatch.setattr(gated_delta_rule, "_PASS_BYTES", 24 * 3 * 16 * 4 * 2)  # two rows a pass
    assert gated_delta_rule._rows_per_pass(4, 24, 3, 16) == 2
    assert reference_kimi._base.max_rel_err(chunk_gated_delta_rule(*args, chunk=8), want) < 1e-5


# -- (a') the same through the Pallas kernels (ops/pallas_delta_rule.py), interpreted ----


def kernel_rule(*args, **kw):
    from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import delta_rule_kernel

    return delta_rule_kernel(*args, interpret=True, **kw)


def value_and_grads(fn, args, weight):
    return jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(), argnums=(0, 1, 2, 3, 4)
    ))(*args)


def ripple(shape):
    return jnp.cos(jnp.arange(np.prod(shape), dtype=jnp.float32)).reshape(shape)


# At the cell's chunk and head size (64 tokens, dk = dv = 128). Against the
# recurrence at the chunked form's bound; against the chunked form in bf16 the
# forward rounds where it rounds (measured here: o to 5e-4, a bf16 step of a few
# entries; the gradients to 8e-3, a cotangent rounded to bf16 at another place).
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_kernels_are_the_recurrence_and_the_chunked_form(chunks):
    args = delta_rule_inputs(chunks * 64, jnp.bfloat16, b=1, h=2, dk=128, dv=128)
    weight = ripple(args[2].shape)
    want_out = jax.jit(gated_delta_rule_recurrent)(*args)
    twin_out = jax.jit(lambda *a: chunk_gated_delta_rule(*a, chunk=64))(*args)
    _, got = value_and_grads(kernel_rule, args, weight)
    out = jax.jit(kernel_rule)(*args)
    assert out.dtype == jnp.bfloat16 and bool(jnp.isfinite(out.astype(jnp.float32)).all())
    assert reference_kimi._base.max_rel_err(out, want_out) < 5e-2
    assert reference_kimi._base.max_rel_err(out, twin_out) < 5e-3
    _, want = value_and_grads(gated_delta_rule_recurrent, args, weight)
    _, twin = value_and_grads(lambda *a: chunk_gated_delta_rule(*a, chunk=64), args, weight)
    for name, g, w, t in zip("q k v g beta".split(), got, want, twin):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert reference_kimi._base.max_rel_err(g, w) < 5e-2, name
        assert reference_kimi._base.max_rel_err(g, t) < 1.5e-2, name


def test_the_kernels_in_float32_are_the_recurrence_to_rounding():
    """The dispatcher never hands the kernels float32 operands; their arithmetic
    (the decays from g itself, the float32 inverse) shows at that precision."""
    args = delta_rule_inputs(2 * 16, jnp.float32, h=2, dk=32, dv=32)
    weight = ripple(args[2].shape)
    _, got = value_and_grads(lambda *a: kernel_rule(*a, chunk=16), args, weight)
    _, want = value_and_grads(gated_delta_rule_recurrent, args, weight)
    assert reference_kimi._base.max_rel_err(kernel_rule(*args, chunk=16), gated_delta_rule_recurrent(*args)) < 1e-5
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert reference_kimi._base.max_rel_err(g, w) < 1e-5, name


@pytest.mark.parametrize("rows, heads", [(1, 1), (3, 2), (16, 1), (2, 8)], ids=["b1", "b3", "b16", "two-head-groups"])
def test_the_kernels_take_any_batch_and_several_head_groups(rows, heads):
    from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import heads_per_program

    args = delta_rule_inputs(32, jnp.bfloat16, b=rows, h=heads, dk=32, dv=32)
    assert heads // heads_per_program(heads) == (2 if heads == 8 else 1)
    weight = ripple(args[2].shape)
    _, got = value_and_grads(lambda *a: kernel_rule(*a, chunk=16), args, weight)
    _, want = value_and_grads(gated_delta_rule_recurrent, args, weight)
    assert reference_kimi._base.max_rel_err(kernel_rule(*args, chunk=16), gated_delta_rule_recurrent(*args)) < 5e-2
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert reference_kimi._base.max_rel_err(g, w) < 5e-2, name


def as_kernel_call(monkeypatch):
    """Send :func:`chunk_gated_delta_rule` down the kernel path on this CPU: the
    backend question answered as on a TPU, the kernels interpreted."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_delta_rule, pallas_short_conv

    interpreted = partial(pallas_delta_rule.delta_rule_kernel, interpret=True)
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    monkeypatch.setattr(pallas_delta_rule, "delta_rule_kernel", interpreted)
    monkeypatch.setattr(pallas_short_conv, "short_conv_kernel", partial(pallas_short_conv.short_conv_kernel, interpret=True))


def test_a_sequence_that_is_no_multiple_of_the_chunk_through_the_kernels(monkeypatch):
    as_kernel_call(monkeypatch)
    args = delta_rule_inputs(70, jnp.bfloat16, b=1, h=1, dk=128, dv=128)
    weight = ripple(args[2].shape)
    assert delta_rule_core(1, 70, 1, 128, 128, jnp.bfloat16) == {
        "core": "kernel", "qk_norm": "kernel", "o_norm": "kernel", "rows": 1, "heads": 1, "chunks": 2,
        "kept_bytes": 2 * (128 * 128 * 4 + 64 * 64 * (4 + 4 + 2) + 2 * 64 * 128 * 2 + 64 * 4)}  # a chunk: the state; T, A / beta, P; W, U; 1 / rms
    got_out, got = value_and_grads(lambda *a: chunk_gated_delta_rule(*a).astype(jnp.float32), args, weight)
    want_out, want = value_and_grads(gated_delta_rule_recurrent, args, weight)
    out = chunk_gated_delta_rule(*args)
    assert out.shape == (1, 70, 1, 128) and out.dtype == jnp.bfloat16
    assert reference_kimi._base.max_rel_err(out, gated_delta_rule_recurrent(*args)) < 5e-2
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert g.shape == w.shape and reference_kimi._base.max_rel_err(g, w) < 5e-2, name


def test_a_channel_that_forgets_fast_stays_finite_in_the_kernels():
    """g about -30 a token: exp(-G) passes float32's range within a chunk, so a
    quotient of decays would overflow; the halving's exponents are all <= 0."""
    q, k, v, g, beta = delta_rule_inputs(128, jnp.bfloat16, b=1, h=1, dk=128, dv=128)
    g = jnp.where(jnp.arange(128) % 3 == 0, -30.0, g)  # every third channel
    assert float(jnp.cumsum(g, 1).min()) < -1900  # exp(1900) is not a float32
    args = (q, k, v, g, beta)
    weight = ripple(v.shape)
    _, got = value_and_grads(kernel_rule, args, weight)
    _, want = value_and_grads(gated_delta_rule_recurrent, args, weight)
    assert reference_kimi._base.max_rel_err(kernel_rule(*args), gated_delta_rule_recurrent(*args)) < 5e-2
    for name, g_, w in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(g_.astype(jnp.float32)).all()), name
        assert reference_kimi._base.max_rel_err(g_, w) < 5e-2, name


def test_repeated_keys_do_not_cancel_in_the_kernels_inverse():
    """Every token writes the same key at full strength and nothing decays:
    I + A is ones below the diagonal times beta, its Neumann series alternates
    through terms of 1e17 before it ends, and the block recursion does not."""
    q, k, v, g, beta = delta_rule_inputs(64, jnp.bfloat16, b=1, h=1, dk=128, dv=128)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g = jnp.zeros_like(g) - 1e-4
    beta = jnp.full_like(beta, 0.98)
    args = (q, k, v, g, beta)
    weight = ripple(v.shape)
    _, got = value_and_grads(kernel_rule, args, weight)
    _, want = value_and_grads(gated_delta_rule_recurrent, args, weight)
    assert reference_kimi._base.max_rel_err(kernel_rule(*args), gated_delta_rule_recurrent(*args)) < 5e-2
    for name, g_, w in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(g_.astype(jnp.float32)).all()), name
        assert reference_kimi._base.max_rel_err(g_, w) < 5e-2, name


# -- (a'') the norms inside the kernels: raw q and k, the heads on the lanes --------------

O_EPS = 1e-5


def raw_inputs(s, h, norm=None, zero_key_at=None, d=128):
    """The mixer's operands as it hands them to the core: q, k raw (any row
    norm; ``norm`` sets it), every operand (b, s, h x d) in bf16, g float32,
    and the head norm's scale (d,)."""
    q, k, v, g, beta = delta_rule_inputs(s, jnp.float32, b=1, h=h, dk=d, dv=d)
    rows = jnp.exp(jax.random.normal(jax.random.key(s + h), (2, 1, s, h, 1))) if norm is None else jnp.full((2, 1, 1, 1, 1), norm)
    q, k = q * d**0.5 * rows[0], k * rows[1]  # delta_rule_inputs normalised them: undo, then scale
    if zero_key_at is not None:
        k = k.at[:, zero_key_at].set(0.0)
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.key(7), (d,))
    return tuple(x.reshape(1, s, -1).astype(jnp.bfloat16) for x in (q, k, v)) + (g.reshape(1, s, -1), beta, scale)


def on_raw(rule):
    """A rule of normalised per-head operands as a function of the raw wide
    ones, with the head norm of its output where a scale comes too: what the
    mixer runs where the kernels do not."""
    def fn(q, k, v, g, beta, scale=None):
        b, s, h = beta.shape
        q, k, v, g = (x.reshape(b, s, h, -1) for x in (q, k, v, g))
        q, k = (l2norm(q) * q.shape[-1] ** -0.5).astype(q.dtype), l2norm(k).astype(k.dtype)
        o = rule(q, k, v, g, beta)
        if scale is not None:
            o = o.astype(jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + O_EPS) * scale
        return o.reshape(b, s, -1)
    return fn


def six_grads(fn, args, weight):
    return jax.jit(jax.grad(lambda *a: (fn(*a).astype(jnp.float32) * weight).sum(), argnums=tuple(range(6))))(*args)


@pytest.mark.parametrize("tokens, heads, norm, zero_key_at", [
    (128, 1, None, None), (64, 4, None, None), (70, 2, None, None), (64, 1, None, 5), (64, 1, 1e-3, None), (64, 1, 1e3, None),
], ids=["one-head", "four-heads", "padded", "zero-key", "rows-of-1e-3", "rows-of-1e3"])
def test_the_kernels_with_the_norms_inside_are_the_norms_around_the_rule(monkeypatch, tokens, heads, norm, zero_key_at):
    """The normalised o and all six cotangents (of the RAW q and k, of v, g,
    beta and the head norm's scale) from the kernels that normalise on the
    head's tile, against l2norm in XLA, the chunked form or the recurrence, and
    the head norm in XLA: the bounds of the kernel tests above, but that the
    kernels round o after its norm where the chunked form rounds before it (a
    bf16 step on entries of any size: 1e-2). A zero key takes the eps path
    (rs = 1e3, its cotangent rs d), rows of norm 1e-3 stand beside eps, rows of
    1e3 far above it."""
    from distributed_sigmoid_loss_tpu.ops import gated_delta_rule

    as_kernel_call(monkeypatch)
    args = raw_inputs(tokens, heads, norm, zero_key_at)
    weight = ripple(args[2].shape)
    pad = -tokens % 64
    chunked = on_raw(lambda *a: jax.checkpoint(partial(gated_delta_rule._chunked, chunk=64, dt=jnp.bfloat16))(
        *(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in a))[:, :tokens].astype(jnp.bfloat16))
    kernels = partial(normed_chunk_gated_delta_rule, o_eps=O_EPS)
    out = jax.jit(kernels)(*args)
    assert out.shape == args[2].shape and out.dtype == jnp.float32
    assert reference_kimi._base.max_rel_err(out, jax.jit(on_raw(gated_delta_rule_recurrent))(*args)) < 5e-2
    assert reference_kimi._base.max_rel_err(out, jax.jit(chunked)(*args)) < 1e-2
    got, want, twin = (six_grads(fn, args, weight) for fn in (kernels, on_raw(gated_delta_rule_recurrent), chunked))
    # the zero key's row is a thousand times the others (rs = 1e3), so there the measure is one row's own
    # relative error, not a tensor's largest entry's: bf16's grade, as against the recurrence
    twin_bound = 1.5e-2 if zero_key_at is None else 5e-2
    for name, g, w, t in zip("q k v g beta scale".split(), got, want, twin):
        assert g.dtype == w.dtype and g.shape == w.shape and bool(jnp.isfinite(g.astype(jnp.float32)).all()), name
        assert reference_kimi._base.max_rel_err(g, w) < 5e-2, name
        assert reference_kimi._base.max_rel_err(g, t) < twin_bound, name
    if zero_key_at is not None:  # the eps path moved something: the zero key's cotangent is not zero
        assert float(jnp.abs(got[1][0, zero_key_at].astype(jnp.float32)).max()) > 0


def test_the_per_head_call_form_and_the_wide_one_are_one_kernel():
    """The kernels' entry takes the heads on the lanes, as the mixer hands them,
    or (b, s, h, d), as the tests above do: free views around one call. Each
    norm is an option of its own: without ``qk_norm`` q and k are used as they
    come, without ``o_eps`` o is returned as it is."""
    q, k, v, g, beta, _ = raw_inputs(64, 2)
    per_head = tuple(x.reshape(1, 64, 2, 128) for x in (q, k, v, g))
    for kw in ({}, {"qk_norm": True}, {"o_eps": O_EPS}, {"qk_norm": True, "o_eps": O_EPS}):
        wide = kernel_rule(q, k, v, g, beta, **kw)
        assert wide.shape == (1, 64, 256) and wide.dtype == jnp.bfloat16
        np.testing.assert_array_equal(kernel_rule(*per_head, beta, **kw).reshape(1, 64, 256), wide)
    normed = on_raw(kernel_rule)(q, k, v, g, beta)
    # the same arithmetic in the same order: l2norm in XLA before the kernels, or inside them
    assert reference_kimi._base.max_rel_err(kernel_rule(q, k, v, g, beta, qk_norm=True), normed) < 1e-6
    assert reference_kimi._base.max_rel_err(kernel_rule(q, k, v, g, beta), normed) > 1e-2


def test_the_kernels_sit_in_a_shard_map_under_a_jit_over_several_chips(monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically: under a jit traced on
    a mesh (``parallel.mesh.trace_on``, as the train step does) the mixer's call
    hands the kernels their LOCAL rows, the heads on the lanes; with no mesh in
    the trace the kernels are called bare. The same numbers either way."""
    import contextlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_delta_rule
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh, trace_on

    seen, real = [], pallas_delta_rule.delta_rule_kernel

    def interpreted(q, *a, **kw):
        seen.append(q.shape)
        return real(q, *a, interpret=True, **kw)

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    monkeypatch.setattr(pallas_delta_rule, "delta_rule_kernel", interpreted)
    mesh = make_mesh(2)
    one = raw_inputs(64, 2)
    args = tuple(jax.device_put(jnp.concatenate([x, x[::-1]] * 2), NamedSharding(mesh, P("dp"))) for x in one[:5]) + one[5:]
    weight = ripple(args[2].shape)

    def grads(on_mesh):
        def loss(*a):
            with trace_on(mesh) if on_mesh else contextlib.nullcontext():
                return (normed_chunk_gated_delta_rule(*a, o_eps=O_EPS) * weight).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6))))(*args)

    want, want_grads = grads(False)
    assert seen and {shape for shape in seen} == {(4, 64, 256)}
    seen.clear()
    got, got_grads = grads(True)
    assert seen and {shape for shape in seen} == {(2, 64, 256)}  # a chip's rows
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, w in zip("q k v g beta scale".split(), got_grads, want_grads):
        assert reference_kimi._base.max_rel_err(g, w) < 1e-5, name
    assert got_grads[0].sharding.spec == P("dp")


# -- (a+) what the differentiated forward keeps for the backward (PERF.md section 6, PR 40) --------


def dense_chunks(q, k, v, g, beta, chunk):
    """A / beta, T = (I + A)^-1 and P of every chunk and head, (b, chunks, h, C, C), W = T (beta K e^G), (b, chunks,
    h, C, dk), and of each sequence's first chunk (no state yet) U = T (beta V), from the equations of
    ops/gated_delta_rule.py written out in float64 (decays as exp(G_t - G_i) outright: these g stay in range): what
    ``_chunked`` holds quadrant by quadrant while it grows T and P, whole."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (*(x.astype(jnp.float32) for x in (q, k, v)), g, beta))
    b, s, h, dk = q.shape
    n = s // chunk
    q, k, v, g = (np.moveaxis(x.reshape(b, n, chunk, h, -1), 3, 2) for x in (q, k, v, g))  # (b, n, h, C, d)
    beta = np.moveaxis(beta.reshape(b, n, chunk, h), 3, 2)[..., None]  # (b, n, h, C, 1)
    run = np.cumsum(g, -2)
    decay = np.exp(np.minimum(run[..., :, None, :] - run[..., None, :, :], 0.0))  # (…, t, i, dk); i <= t is all that is read
    a0 = np.tril(np.einsum("...tc,...ic,...tic->...ti", k, k, decay), -1)
    p = np.tril(np.einsum("...tc,...ic,...tic->...ti", q, k, decay))
    t_inv = np.linalg.inv(np.eye(chunk) + beta * a0)
    return a0, t_inv, p, t_inv @ (beta * k * np.exp(run)), (t_inv @ (beta * v))[:, 0]


def by_head(x, heads):
    """A kept block array (b, chunks, h / heads, C, heads x C) as (b, chunks, h, C, C)."""
    b, n, groups, chunk, _ = x.shape
    return jnp.moveaxis(x.reshape(b, n, groups, chunk, heads, chunk), 4, 3).reshape(b, n, groups * heads, chunk, chunk)


# float32 operands show the arithmetic (the dispatcher hands the kernels bf16 only); in bf16 the levels' products take
# operands rounded to 8 bits as ``_chunked``'s do, and P is kept as its cast, the backward's only use of it.
@pytest.mark.parametrize("dtype, chunk, d, bound, p_bound", [(jnp.float32, 16, 32, 2e-5, 2e-5), (jnp.bfloat16, 64, 128, 1e-2, 1.5e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [1, 2, 4, 8], ids=lambda h: f"{h}-heads")
def test_the_differentiated_forward_keeps_what_it_solved(heads, dtype, chunk, d, bound, p_bound):
    """Beside each chunk's incoming state the forward of a differentiated call writes the chunk's T = (I + A)^-1
    and A / beta in float32 and P in the tower's dtype, a program's heads side by side on the lanes, and W and U
    where o lies: the values the backward made again before PR 40, now read."""
    from distributed_sigmoid_loss_tpu.ops import pallas_delta_rule

    per = pallas_delta_rule.heads_per_program(heads)
    q, k, v, g, beta = delta_rule_inputs(2 * chunk, dtype, b=2, h=heads, dk=d, dv=d)
    g = jnp.maximum(g, -2.0)  # exp(G_t - G_i) outright stays a float64 over a chunk
    wide = tuple(x.reshape(2, 2 * chunk, -1) for x in (q, k, v, g))
    out, kept = pallas_delta_rule._vjp_fwd(*wide, beta, chunk, True, False, None)
    states, t_inv, a0, pb, wb, ub = kept[1]
    assert [x.shape for x in (t_inv, a0, pb)] == [(2, 2, heads // per, chunk, per * chunk)] * 3 and wb.shape == ub.shape == out.shape
    assert [x.dtype for x in (states, t_inv, a0, pb, wb, ub)] == [jnp.float32] * 3 + [dtype] * 3
    np.testing.assert_array_equal(out, pallas_delta_rule.delta_rule_kernel(*wide, beta, chunk=chunk, interpret=True))
    np.testing.assert_array_equal(states[:, 0], 0.0)  # a sequence starts from nothing
    want_a0, want_t, want_p, want_w, want_u0 = dense_chunks(q, k, v, g, beta, chunk)
    wb, ub = (jnp.moveaxis(x.reshape(2, 2, chunk, heads, d), 3, 2) for x in (wb, ub))  # (b, n, h, C, d)
    assert reference_kimi._base.max_rel_err(wb, want_w) < p_bound and reference_kimi._base.max_rel_err(ub[:, 0], want_u0) < p_bound
    assert reference_kimi._base.max_rel_err(by_head(a0, per), want_a0) < bound
    assert reference_kimi._base.max_rel_err(by_head(t_inv, per), want_t) < bound
    assert reference_kimi._base.max_rel_err(by_head(pb, per), want_p) < p_bound
    strict = np.tril(np.ones((chunk, chunk)), -1)
    np.testing.assert_array_equal(by_head(a0, per) * (1 - strict), 0.0)  # nothing on or above the diagonal
    np.testing.assert_array_equal(by_head(t_inv, per) * (1 - strict), jnp.broadcast_to(jnp.eye(chunk), (2, 2, heads, chunk, chunk)))


# The gradients through the backward that reads T, A / beta and P, under the bounds of
# test_the_kernels_are_the_recurrence_and_the_chunked_form, at 1, 2 and 4 heads a program, whole chunks and not.
@pytest.mark.parametrize("heads, tokens", [(1, 128), (2, 64), (4, 128), (1, 70), (4, 70)],
                         ids=["1-head", "2-heads", "4-heads", "1-head-padded", "4-heads-padded"])
def test_the_backward_that_reads_what_the_forward_solved_is_the_recurrences_and_the_chunked_forms(monkeypatch, heads, tokens):
    from distributed_sigmoid_loss_tpu.ops import gated_delta_rule

    as_kernel_call(monkeypatch)
    args = delta_rule_inputs(tokens, jnp.bfloat16, b=1, h=heads, dk=128, dv=128)
    weight = ripple(args[2].shape)
    pad = -tokens % 64
    chunked = lambda *a: jax.checkpoint(partial(gated_delta_rule._chunked, chunk=64, dt=jnp.bfloat16))(  # noqa: E731
        *(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in a))[:, :tokens].astype(jnp.bfloat16)
    out, want_out, twin_out = (jax.jit(fn)(*args) for fn in (chunk_gated_delta_rule, gated_delta_rule_recurrent, chunked))
    assert out.shape == args[2].shape and out.dtype == jnp.bfloat16
    assert reference_kimi._base.max_rel_err(out, want_out) < 5e-2 and reference_kimi._base.max_rel_err(out, twin_out) < 5e-3
    (_, got), (_, want), (_, twin) = (
        value_and_grads(fn, args, weight) for fn in (chunk_gated_delta_rule, gated_delta_rule_recurrent, chunked))
    for name, g, w, t in zip("q k v g beta".split(), got, want, twin):
        assert g.dtype == w.dtype and g.shape == w.shape and bool(jnp.isfinite(g.astype(jnp.float32)).all()), name
        assert reference_kimi._base.max_rel_err(g, w) < 5e-2, name
        assert reference_kimi._base.max_rel_err(g, t) < 1.5e-2, name


def inner_jaxprs(eqn):
    """The jaxprs an equation carries: a jit's, a remat's or a custom rule's body, the branches of a ``pl.when``."""
    for value in eqn.params.values():
        for inner in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def kernel_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, by the kernel's name."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn
        else:
            for inner in inner_jaxprs(eqn):
                found.update(kernel_calls(inner))
    return found


def products(jaxpr):
    """The precision of every ``dot_general`` of a kernel's body."""
    found = [eqn.params["precision"] for eqn in jaxpr.eqns if eqn.primitive.name == "dot_general"]
    return found + [p for eqn in jaxpr.eqns for inner in inner_jaxprs(eqn) for p in products(inner)]


def highest(precisions):
    return sum(p is not None and jax.lax.Precision.HIGHEST in (p if isinstance(p, tuple) else (p,)) for p in precisions)


@pytest.mark.parametrize("norms", [{}, {"qk_norm": True, "o_eps": O_EPS}], ids=["bare", "norms-inside"])
def test_the_plain_forward_writes_o_and_nothing_else(norms):
    """Nothing is kept where nothing is differentiated (the first forward under remat, inference): ``kda_fwd`` has
    the one output it had before PR 40; differentiated, it has what ``kept_for_backward`` lists beside o."""
    from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import kept_for_backward

    q, k, v, g, beta = delta_rule_inputs(128, jnp.bfloat16, b=2, h=4, dk=128, dv=128)
    args = tuple(x.reshape(2, 128, -1) for x in (q, k, v, g)) + (beta,)
    rule = partial(kernel_rule, **norms)
    plain = kernel_calls(jax.make_jaxpr(rule)(*args).jaxpr)
    assert list(plain) == ["kda_fwd"] and [x.aval.shape for x in plain["kda_fwd"].outvars] == [(2, 128, 512)]
    both = kernel_calls(jax.make_jaxpr(jax.grad(lambda *a: rule(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*args).jaxpr)
    assert list(both) == ["kda_fwd", "kda_bwd"]
    kept = [x for _, x in kept_for_backward(2, 128, 4, 128, 128, jnp.bfloat16, 64, o_norm=bool(norms))]
    assert len(kept) == (7 if norms else 6)  # the state, T, A / beta, P, W, U, and 1 / rms
    assert [(x.aval.shape, x.aval.dtype) for x in both["kda_fwd"].outvars[1:]] == [(x.shape, x.dtype) for x in kept]
    # and the backward takes them all in: the operands' five, the kept blocks, do, and with the head norm o
    assert len(both["kda_bwd"].invars) == 2 + 5 + len(kept) + 1 + bool(norms)


@pytest.mark.parametrize("heads", [1, 2, 4], ids=lambda h: f"{h}-heads")
def test_the_backward_solves_nothing_again(heads):
    """A head of ``kda_bwd`` holds three products at the MXU's full precision, the inverse's cotangent pair
    (-T^T dT T^T) and d_g's reversed running sum, and 23 others: the running sums' three and its own twenty. No level
    of the inverse (ten at full precision a head in ``kda_fwd``), none of the levels' seven score products, neither W
    and U nor o and the next state (47 products a head before PR 40)."""
    q, k, v, g, beta = delta_rule_inputs(64, jnp.bfloat16, b=1, h=heads, dk=128, dv=128)
    args = tuple(x.reshape(1, 64, -1) for x in (q, k, v, g)) + (beta,)
    rule = partial(kernel_rule, qk_norm=True, o_eps=O_EPS)
    calls = kernel_calls(jax.make_jaxpr(jax.grad(lambda *a: rule(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*args).jaxpr)
    forward, backward = (products(calls[name].params["jaxpr"]) for name in ("kda_fwd", "kda_bwd"))
    assert (len(forward), highest(forward)) == (24 * heads, 10 * heads)  # the search finds the inverse's levels where they are
    assert (len(backward), highest(backward)) == (26 * heads, 3 * heads)


# -- (a3) the layer around the kernels stays on (b, s, h x d) -----------------------------


def kda_layer_loss(tokens=70, heads=2, d=128):
    """A delta-rule layer in bf16 at the kernels' head size, moved parameters, and
    a loss of its output as a function of (params, x)."""
    from distributed_sigmoid_loss_tpu.models.mixers import KdaMixer

    layer = KdaMixer(width=64, num_heads=heads, head_dim=d, conv_size=4, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(0), (2, tokens, 64), jnp.bfloat16)
    params = moved(layer.init(jax.random.key(1), x)["params"])
    weight = ripple((2, tokens, 64))
    return (lambda p, x: (layer.apply({"params": p}, x).astype(jnp.float32) * weight).sum()), layer, params, x


# 70 tokens: the branches' convolution stays XLA's (no whole 16-row tiles); 64: it runs its kernel pair too
def kernel_call_sites(jaxpr) -> dict:
    """How many ``pallas_call`` equations a jaxpr holds, by the kernel's name (a jit's body counts once a call)."""
    found = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = found.get(eqn.params["name"], 0) + 1
        for inner in inner_jaxprs(eqn):
            for name, n in kernel_call_sites(inner).items():
                found[name] = found.get(name, 0) + n
    return found


@pytest.mark.parametrize("tokens, convolutions", [(70, 0), (64, 3)], ids=["odd-sequence", "convolution-kernels-too"])
def test_a_delta_rule_layer_on_the_kernel_path_is_the_layer_on_the_chunked_path(monkeypatch, tokens, convolutions):
    loss, layer, params, x = kda_layer_loss(tokens)

    def run():  # fresh functions a call: each trace asks the backend question again
        out = jax.jit(lambda p, x: layer.apply({"params": p}, x))(params, x)
        return out, *jax.jit(jax.grad(lambda p, x: loss(p, x), argnums=(0, 1)))(params, x)

    want_out, want, want_x = run()
    as_kernel_call(monkeypatch)
    got_out, got, got_x = run()
    traced = jax.make_jaxpr(jax.grad(lambda p, x: loss(p, x), argnums=(0, 1)))(params, x)
    pair = {"short_conv_fwd": convolutions, "short_conv_bwd": convolutions} if convolutions else {}  # of q, k and v
    assert kernel_call_sites(traced.jaxpr) == {"kda_fwd": 1, "kda_bwd": 1, **pair}
    assert got_out.dtype == jnp.bfloat16 and not np.array_equal(got_out, want_out)  # another path did run
    assert reference_kimi._base.max_rel_err(got_out, want_out) < 1.5e-2
    errs = reference_kimi.tree_max_rel_err(got, want)
    assert len(errs) == len(jax.tree.leaves(params)) == 15
    assert max(errs.values()) < 5e-2, max(errs, key=errs.get)
    assert reference_kimi._base.max_rel_err(got_x, want_x) < 5e-2


def per_head_values(jaxpr, heads, d):
    """Every rank-4 (b, s, h, d) value of a jaxpr outside its ``pallas_call``s, as
    (primitive, shape): what a per-head view of a (b, s, h x d) array leaves."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += [(eqn.primitive.name, v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                  if getattr(v.aval, "ndim", 0) == 4 and v.aval.shape[2:] == (heads, d)]
        for inner in inner_jaxprs(eqn):
            found += per_head_values(inner, heads, d)
    return found


def test_no_per_head_array_exists_around_the_kernels(monkeypatch):
    """On a TPU (b, s, h, d) and (b, s, h x d) are two tilings, so a per-head
    view of a wide array around a reduction over d is a copy through HBM each
    way (PERF.md section 6, PR 36). On the kernel path the layer, forward and
    backward, holds no such value: no reshape to or from one, no
    ``broadcast_in_dim`` to one; the per-head statistics run inside the
    kernels. The chunked path has them, so the search does find what it looks
    for."""
    loss, _, params, x = kda_layer_loss()
    chunked = per_head_values(jax.make_jaxpr(jax.value_and_grad(lambda p, x: loss(p, x)))(params, x).jaxpr, 2, 128)
    assert {"reshape", "broadcast_in_dim"} <= {name for name, _ in chunked}
    as_kernel_call(monkeypatch)
    traced = jax.make_jaxpr(jax.value_and_grad(lambda p, x: loss(p, x), argnums=(0, 1)))(params, x)
    assert str(traced).count("pallas_call") == 2  # kda_fwd writing the states, kda_bwd
    assert per_head_values(traced.jaxpr, 2, 128) == []


@pytest.mark.parametrize("dtype, tpu, d, core", [
    (jnp.bfloat16, True, 128, "kernel"), (jnp.bfloat16, True, 256, "kernel"),
    (jnp.float32, True, 128, "chunked"), (jnp.bfloat16, False, 128, "chunked"), (jnp.bfloat16, True, 64, "chunked"),
], ids=["bf16-tpu-128", "bf16-tpu-256", "float32", "cpu", "head-64"])
def test_which_core_a_call_takes_follows_from_dtype_backend_and_head_size(monkeypatch, dtype, tpu, d, core):
    from distributed_sigmoid_loss_tpu.ops import flash_attention, gated_delta_rule, pallas_delta_rule

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    assert delta_rule_core(3, 100, 2, d, d, dtype) == {
        "core": core, **dict.fromkeys(("qk_norm", "o_norm"), "kernel" if core == "kernel" else "xla"), "rows": 3, "heads": 2, "chunks": 2,
        "kept_bytes": 3 * 2 * 2 * (d * d * 4 + 64 * 64 * (4 + 4 + 2) + 2 * 64 * d * 2 + 64 * 4) if core == "kernel" else 0}
    taken = []
    monkeypatch.setattr(pallas_delta_rule, "delta_rule_kernel",
                        lambda q, *a, **kw: taken.append("kernel") or jnp.zeros_like(q))
    chunked = gated_delta_rule._chunked
    monkeypatch.setattr(gated_delta_rule, "_chunked", lambda *a, **kw: taken.append("chunked") or chunked(*a, **kw))
    args = delta_rule_inputs(16, dtype, b=1, h=1, dk=d, dv=d)
    chunk_gated_delta_rule(*args, chunk=8)
    assert taken == [core]
    # the mixer's entry, on raw q and k with the heads on the lanes: the norms go where the core goes
    normed = []
    monkeypatch.setattr(gated_delta_rule, "l2norm", lambda x: normed.append("xla") or x.astype(jnp.float32))
    normed_chunk_gated_delta_rule(*(x.reshape(1, 16, -1) for x in args[:4]), args[4], jnp.ones(d), o_eps=1e-5, chunk=8)
    assert taken == [core, core] and normed == ([] if core == "kernel" else ["xla", "xla"])


def test_the_steps_record_names_the_core_by_the_same_rule(monkeypatch):
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train.train_step import stack_record_of
    from distributed_sigmoid_loss_tpu.utils.config import TextConfig

    t = TextConfig(width=256, depth=3, num_heads=2, mixers=("kda", "mla", "kda"), pos="none", dtype="bfloat16",
                   moe_experts=4, moe_router="sigmoid", kda_head_dim=128)
    assert stack_record_of(t, (16, 1024))["kda_core"] == {
        i: {"core": "chunked", "qk_norm": "xla", "o_norm": "xla", "rows": 16, "heads": 2, "chunks": 16, "kept_bytes": 0}
        for i in (0, 2)}  # this CPU
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    assert stack_record_of(t, (16, 1024))["kda_core"] == {
        i: {"core": "kernel", "qk_norm": "kernel", "o_norm": "kernel", "rows": 16, "heads": 2, "chunks": 16,
            "kept_bytes": (536870912 + 335544320 + 268435456 + 2097152) // 16} for i in (0, 2)}
    # the hybrid cell's call (16 rows x 1024 tokens x 32 heads of 128): 537 MB of states, 335 MB of T, A / beta and P,
    # 268 MB of W and U, 2 MB of 1 / rms kept from a layer's second forward to its backward
    cell = TextConfig(width=256, depth=1, num_heads=32, mixers=("kda",), pos="none", dtype="bfloat16",
                      moe_experts=4, moe_router="sigmoid", kda_head_dim=128)
    assert stack_record_of(cell, (16, 1024))["kda_core"][0]["kept_bytes"] == 536870912 + 335544320 + 268435456 + 2097152 == 1142947840
    float32 = TextConfig(width=256, depth=1, num_heads=2, mixers=("kda",), pos="none", dtype="float32",
                         moe_experts=4, moe_router="sigmoid")
    assert stack_record_of(float32, (3, 100))["kda_core"] == {
        0: {"core": "chunked", "qk_norm": "xla", "o_norm": "xla", "rows": 3, "heads": 2, "chunks": 2, "kept_bytes": 0}}
    assert "kda_core" not in stack_record_of(TextConfig(depth=2, moe_experts=4, moe_router="sigmoid"), (4, 8))


# -- (a4) the branches' short convolution through its kernel pair (ops/pallas_short_conv.py), interpreted ----


def conv_operands(rows, tokens, channels, bias, taps=4, seed=11):
    keys = jax.random.split(jax.random.key(seed), 4)
    x = (2 * jax.random.normal(keys[0], (rows, tokens, channels))).astype(jnp.bfloat16)
    d_y = jax.random.normal(keys[1], (rows, tokens, channels)).astype(jnp.bfloat16)
    w = jax.random.uniform(keys[2], (taps, channels), jnp.float32, -0.5, 0.5)
    return x, w, (jax.random.uniform(keys[3], (channels,), jnp.float32, -0.5, 0.5) if bias else None), d_y


def float32_form(x, w, bias):
    """silu(short_causal_conv(x, taps) + bias) in float32 on the taps as the products take them, rounded to
    bfloat16 (their cotangent passes the rounding unrounded)."""
    from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import short_causal_conv

    w = w + jax.lax.stop_gradient(w.astype(jnp.bfloat16).astype(jnp.float32) - w)
    y = short_causal_conv(x.astype(jnp.float32), w)
    return jax.nn.silu(y if bias is None else y + bias)


def pair_and_form(monkeypatch, x, w, bias, d_y, rows=None, block_bytes=None, lanes=None):
    """Value and the cotangents of (x, taps, bias) under ``d_y``, through the interpreted pair and the float32 form."""
    from distributed_sigmoid_loss_tpu.ops import pallas_short_conv

    for name, value in (("_ROWS", rows), ("_BLOCK_BYTES", block_bytes), ("_MAX_CHANNELS", lanes)):
        if value:
            monkeypatch.setattr(pallas_short_conv, name, value)
    got, got_pull = jax.vjp(partial(pallas_short_conv.short_conv_kernel, interpret=True), x, w, bias)
    want, want_pull = jax.vjp(float32_form, x.astype(jnp.float32), w, bias)  # x's cotangent in float32 too
    return (got, *got_pull(d_y)), (want, *want_pull(d_y.astype(jnp.float32)))


def assert_the_pair_is_the_form(got, want, x, w, bias):
    y, d_x, d_w, d_bias = got
    assert (y.shape, y.dtype, d_x.shape, d_x.dtype) == (x.shape, x.dtype, x.shape, x.dtype)
    assert (d_w.shape, d_w.dtype) == (w.shape, jnp.float32)
    # one rounding of a float32 sum: half a bfloat16 step of the value, at most 2^-8 of it
    for name, g, t in (("y", y, want[0]), ("dx", d_x, want[1])):
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(t, np.float32), rtol=1.01 * 2**-8, atol=1e-6, err_msg=name)
    np.testing.assert_allclose(d_w, want[2], rtol=1e-5, atol=1e-4, err_msg="taps")  # float32 sums in another order
    if bias is None:
        assert d_bias is None and want[3] is None
    else:
        assert d_bias.dtype == jnp.float32
        np.testing.assert_allclose(d_bias, want[3], rtol=1e-5, atol=1e-4, err_msg="bias")


@pytest.mark.parametrize("bias", [False, True], ids=["no-bias", "bias"])
@pytest.mark.parametrize("tokens, rows, block", [(48, None, None), (128, 32, None), (128, 32, 64)],
                         ids=["one-tile", "four-tiles", "two-blocks-of-two-tiles"])
@pytest.mark.parametrize("channels, lanes", [(128, None), (256, None), (256, 128)],
                         ids=["one-column", "two-columns", "two-channel-blocks"])
def test_the_short_convolutions_pair_is_the_float32_form_forward_and_in_its_cotangents(monkeypatch, channels, lanes, tokens,
                                                                                       rows, block, bias):
    """y, dx, the taps' and the bias's cotangents (none where there is no bias): whatever the tiles a column is walked
    in, the blocks a sequence is cut into (a tile's neighbour rows come in registers, a block's as blocks of their
    own) and the columns and channel blocks side by side."""
    from distributed_sigmoid_loss_tpu.ops.pallas_short_conv import short_conv_plan

    x, w, b, d_y = conv_operands(2, tokens, channels, bias)
    got, want = pair_and_form(monkeypatch, x, w, b, d_y, rows, block and block * (lanes or channels) * 2, lanes)
    assert short_conv_plan(tokens, channels, 4) == {"tokens": block or tokens, "channels": lanes or channels, "rows": rows or tokens}
    assert_the_pair_is_the_form(got, want, x, w, b)


@pytest.mark.parametrize("taps", [2, 4, 7])
def test_nothing_lies_before_a_sequence_and_nothing_after_it(monkeypatch, taps):
    """The first taps - 1 tokens of y see zeros before them, the last taps - 1 of dx zeros after them, in every batch
    row and at every block's edge: batch rows whose neighbours in memory are large do not see them, and a token's y
    is its own x times the last tap where everything before it is zero."""
    x, w, b, d_y = conv_operands(3, 64, 128, True, taps=taps)
    x = x.at[0, -8:].set(300.0).at[2, :8].set(-300.0)  # what row 1 would see of its neighbours
    (y, d_x, d_w, d_b), want = pair_and_form(monkeypatch, x, w, b, d_y, rows=16, block_bytes=32 * 128 * 2)
    assert_the_pair_is_the_form((y, d_x, d_w, d_b), want, x, w, b)
    rounded = w.astype(jnp.bfloat16).astype(jnp.float32)
    first = jax.nn.silu(x[:, 0].astype(jnp.float32) * rounded[-1] + b)  # token 0: its own product alone
    np.testing.assert_allclose(np.asarray(y[:, 0], np.float32), first, rtol=1.01 * 2**-8, atol=1e-6)
    # the last token's x reaches its own pre alone: dx = taps[-1] dy silu'(pre)
    alone = jax.vjp(lambda x: float32_form(x, w, b), x.astype(jnp.float32))[1](d_y.astype(jnp.float32).at[:, :-1].set(0.0))[0]
    np.testing.assert_allclose(np.asarray(d_x[:, -1], np.float32), alone[:, -1], rtol=1.01 * 2**-8, atol=1e-6)


@pytest.mark.parametrize("dtype, tpu, tokens, channels, taps, form", [
    (jnp.bfloat16, True, 1024, 4096, 4, "kernel"),  # the delta-rule cell's call
    (jnp.bfloat16, True, 4096, 1024, 4, "kernel"),  # the state-space cell's B and C
    (jnp.bfloat16, True, 48, 128, 2, "kernel"),
    (jnp.float32, True, 1024, 4096, 4, "xla"),  # float32 is XLA's
    (jnp.bfloat16, False, 1024, 4096, 4, "xla"),  # no TPU
    (jnp.bfloat16, True, 1024, 4000, 4, "xla"),  # a width off the 128 lanes
    (jnp.bfloat16, True, 70, 4096, 4, "xla"),  # a sequence off the 16-row tiles
    (jnp.bfloat16, True, 1024, 4096, 9, "xla"),  # taps that reach past a register's eight rows
], ids=["kimi", "nemotron-bc", "small", "float32", "cpu", "odd-width", "odd-sequence", "nine-taps"])
def test_which_form_the_short_convolution_takes_follows_from_what_the_call_can_see(monkeypatch, dtype, tpu, tokens, channels,
                                                                                  taps, form):
    """No flag: dtype, backend and shape decide, as for the cores; and the XLA form is the expression the mixers
    had, bit for bit."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_short_conv
    from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import short_causal_conv, short_conv_core, short_conv_silu

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    core = short_conv_core(tokens, channels, taps, dtype)
    assert core["form"] == form and core["channels"] == channels and (core["tile"] is None) == (form == "xla")
    if form == "kernel":
        assert core["tile"] == {1024: (1024, 512), 4096: (2048, 512), 48: (48, 128)}[tokens]
    taken = []
    monkeypatch.setattr(pallas_short_conv, "short_conv_kernel", lambda x, *a, **kw: taken.append("kernel") or x)
    x, w, b, _ = conv_operands(1, min(tokens, 96) if tokens % 16 == 0 else tokens, channels, True, taps=taps)
    y = short_conv_silu(x.astype(dtype), w, b)
    assert taken == (["kernel"] if form == "kernel" else [])
    if form == "xla":
        assert "pallas_call" not in str(jax.make_jaxpr(short_conv_silu)(x.astype(dtype), w, b))
        was = nn.silu(short_causal_conv(x.astype(dtype), w) + b.astype(dtype))
        assert y.dtype == dtype and np.array_equal(np.asarray(y, np.float32), np.asarray(was, np.float32))
        bare = short_conv_silu(x.astype(dtype), w)
        assert np.array_equal(np.asarray(bare, np.float32), np.asarray(nn.silu(short_causal_conv(x.astype(dtype), w)), np.float32))


def test_the_convolutions_pair_sits_in_a_shard_map_under_a_jit_over_several_chips(monkeypatch):
    """Rows over ``dp``, the channels, the taps and the bias whole: the same values and cotangents, the taps' and the
    bias's summed over the chips."""
    import contextlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sigmoid_loss_tpu.ops import pallas_short_conv
    from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import short_conv_silu
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh, trace_on

    seen, real = [], pallas_short_conv.short_conv_kernel
    as_kernel_call(monkeypatch)
    monkeypatch.setattr(pallas_short_conv, "short_conv_kernel",
                        lambda x, *a, **kw: seen.append(x.shape) or real(x, *a, interpret=True, **kw))
    mesh = make_mesh(2)
    x, w, b, d_y = conv_operands(4, 32, 128, True)
    x = jax.device_put(x, NamedSharding(mesh, P("dp")))

    def grads(on_mesh):
        def loss(x, w, b):
            with trace_on(mesh) if on_mesh else contextlib.nullcontext():
                return (short_conv_silu(x, w, b).astype(jnp.float32) * d_y.astype(jnp.float32)).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, w, b)

    want, want_grads = grads(False)
    assert set(seen) == {(4, 32, 128)}
    seen.clear()
    got, got_grads = grads(True)
    assert set(seen) == {(2, 32, 128)}  # a chip's rows
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, t in zip(("x", "taps", "bias"), got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(t, np.float32), rtol=1e-5, atol=1e-5, err_msg=name)
    assert got_grads[0].sharding.spec == P("dp")


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
def test_the_steps_record_names_the_convolutions_form_by_the_same_rule(monkeypatch, tpu):
    """Per delta-rule layer the form its q, k and v branches' convolution takes and a program's tile: "xla" on this
    CPU, the pair in bf16 on a TPU, and the line `train` prints says so."""
    from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack_line
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import STACK_RECORD_FIELDS
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train.train_step import stack_record_of
    from distributed_sigmoid_loss_tpu.utils.config import TextConfig

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    cell = TextConfig(width=256, depth=3, num_heads=32, mixers=("kda", "mla", "kda"), pos="none", dtype="bfloat16",
                      moe_experts=4, moe_router="sigmoid", kda_head_dim=128)
    record = stack_record_of(cell, (16, 1024))
    assert set(record) <= set(STACK_RECORD_FIELDS)
    entry = {"form": "kernel", "channels": 4096, "tile": (1024, 512)} if tpu else {"form": "xla", "channels": 4096, "tile": None}
    assert record["short_conv"] == {0: [entry], 2: [entry]}
    assert ("conv[2] kernel 1024x512 of 4096" if tpu else "conv[2] xla of 4096") in mixed_stack_line(record)
    float32 = stack_record_of(TextConfig(width=256, depth=1, num_heads=2, mixers=("kda",), pos="none", dtype="float32",
                                         moe_experts=4, moe_router="sigmoid"), (3, 100))
    assert float32["short_conv"] == {0: [{"form": "xla", "channels": 2 * 128, "tile": None}]}
    assert "short_conv" not in stack_record_of(TextConfig(depth=2, moe_experts=4, moe_router="sigmoid"), (4, 8))


# -- (b) latent attention ----------------------------------------------------------


def test_latent_attention_at_heads_of_192_and_128_matches_the_reference():
    t = types.SimpleNamespace(num_heads=2, mla_qk_nope_dim=128, mla_qk_shared_dim=64, mla_v_dim=128,
                              mla_kv_rank=32, norm_eps=1e-5)
    layer = LatentAttention(48, 2, 128, 64, 128, 32, jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 12, 48), jnp.float32)
    params = moved(layer.init(jax.random.key(1), x)["params"])
    assert params["q"]["kernel"].shape == (48, 2 * 192) and params["kv_a"]["kernel"].shape == (48, 32 + 64)
    assert params["kv_b"]["kernel"].shape == (32, 2 * 256) and params["out"]["kernel"].shape == (2 * 128, 48)
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, x)
        want = reference_kimi.mla(x, params, t)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # causal: a later token does not reach an earlier output
    later = layer.apply({"params": params}, x.at[:, 8:].add(1.0))
    np.testing.assert_array_equal(got[:, :8], later[:, :8])


def library_kernel_stand_in(monkeypatch, seen):
    """A TPU whose pair's VMEM holds no head: the layer takes the library's blocked
    kernel, here dense attention that notes the per-head shapes it is given."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_latent_attention

    def attend(q, k, v, **kw):
        seen.append((q.shape, k.shape, v.shape))
        return dense_attention(q, k, v, **kw)

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    monkeypatch.setattr(pallas_latent_attention, "_VMEM_LIMIT", 0)
    monkeypatch.setattr(flash_attention, "flash_self_attention", attend)


def test_past_the_pairs_vmem_the_layer_runs_the_library_kernel_at_one_padded_head_size(monkeypatch):
    """192 / 128 heads reach a core that takes one head size as (b, s, h, 256) views of the
    same wide q, k and v (zero columns of the weights pad them, which is exact: a zero
    value channel stays zero, zero channels add nothing to a score), and the output's zero
    lanes are cut: the layer on the dense path, values and gradients."""
    sizes = dict(width=48, num_heads=3, nope_dim=128, shared_dim=64, v_dim=128, kv_rank=32, dtype=jnp.float32)
    dense, fused = LatentAttention(**sizes, attn_impl="dense"), LatentAttention(**sizes, attn_impl="flash")
    x = jax.random.normal(jax.random.key(0), (2, 12, 48), jnp.float32)
    params = moved(dense.init(jax.random.key(1), x)["params"])

    def loss(layer):
        return lambda p, x: (layer.apply({"params": p}, x) * ripple((2, 12, 48))).sum()

    want, want_grads = jax.value_and_grad(loss(dense), argnums=(0, 1))(params, x)
    seen = []
    library_kernel_stand_in(monkeypatch, seen)
    got, got_grads = jax.value_and_grad(loss(fused), argnums=(0, 1))(params, x)
    assert seen == [((2, 12, 3, 256),) * 3]
    np.testing.assert_allclose(got, want, rtol=1e-4)  # a sum with cancellation
    assert max(reference_kimi._base.tree_max_rel_err(got_grads, want_grads).values()) < 1e-4


# -- (b') latent attention's core through the Pallas pair (ops/pallas_latent_attention.py), interpreted ----


def attention_inputs(tokens, dqk, dv, dtype=jnp.float32, rows=1, heads=2, seed=7):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k = (jax.random.normal(key, (rows, tokens, heads, dqk), jnp.float32).astype(dtype) for key in keys[:2])
    v, weight = (jax.random.normal(key, (rows, tokens, heads, dv), jnp.float32).astype(dtype) for key in keys[2:])
    return q, k, v, weight


def pair_and_dense(monkeypatch, block, dqk, dv):
    """The kernel pair, interpreted, on heads zero-padded to whole registers (the layer
    pads them by its weights' columns), at ``block`` tokens a block; and dense causal
    attention in float32."""
    from distributed_sigmoid_loss_tpu.ops import pallas_latent_attention

    monkeypatch.setattr(pallas_latent_attention, "_BLOCK", block)
    scale = dqk**-0.5

    def pair(q, k, v):  # the heads on the lanes, each in whole registers
        (b, s, h, _), wq, wv = q.shape, -(-dqk // 128) * 128, -(-dv // 128) * 128
        wide = (jnp.pad(t, ((0, 0),) * 3 + ((0, w - t.shape[-1]),)).reshape(b, s, h * w) for t, w in ((q, wq), (k, wq), (v, wv)))
        out = pallas_latent_attention.latent_attention_kernel(*wide, head_dims=(wq, wv), scale=scale, interpret=True)
        return out.reshape(b, s, h, wv)[..., :dv]

    def dense(q, k, v):
        return dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)), causal=True, scale=scale)

    return pair, dense


def attention_value_and_grads(core, q, k, v, weight):
    def loss(q, k, v):
        return (core(q, k, v).astype(jnp.float32) * weight.astype(jnp.float32)).sum()
    return core(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype, out_tol, grad_tol", [(jnp.float32, 2e-5, 2e-5), (jnp.bfloat16, 2e-2, 5e-2)],
                         ids=["f32", "bf16"])  # bf16: the library kernel's own tolerances (tests/test_flash_attention.py)
@pytest.mark.parametrize("dqk, dv", [(256, 256), (192, 128)], ids=["glm-256-256", "kimi-192-128-padded"])
@pytest.mark.parametrize("tokens, block", [(128, 128), (384, 128), (200, 128), (640, 512)],
                         ids=["one-block", "three-blocks", "padded-to-two", "512-blocks-padded"])
def test_the_pair_is_dense_causal_attention_forward_and_in_its_three_gradients(
        monkeypatch, tokens, block, dqk, dv, dtype, out_tol, grad_tol):
    pair, dense = pair_and_dense(monkeypatch, block, dqk, dv)
    q, k, v, weight = attention_inputs(tokens, dqk, dv, dtype)
    got, got_grads = attention_value_and_grads(pair, q, k, v, weight)
    want, want_grads = attention_value_and_grads(dense, q, k, v, weight)
    assert got.shape == v.shape and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=out_tol, atol=out_tol)
    for name, g, w in zip("qkv", got_grads, want_grads):
        assert g.shape == w.shape and g.dtype == dtype, name
        np.testing.assert_allclose(g.astype(jnp.float32), w, rtol=grad_tol, atol=grad_tol * float(jnp.abs(w).max()), err_msg=name)


@pytest.mark.parametrize("tokens, block", [(128, 128), (384, 128), (200, 128)], ids=["one-block", "three-blocks", "padded"])
def test_the_forwards_saved_column_is_the_dense_log_sum_exp(monkeypatch, tokens, block):
    from distributed_sigmoid_loss_tpu.ops import pallas_latent_attention

    monkeypatch.setattr(pallas_latent_attention, "_BLOCK", block)
    q, k, v, _ = attention_inputs(tokens, 128, 128, rows=2)
    whole = ((0, 0), (0, -tokens % block), (0, 0))  # the entry's zero rows at the end
    out, lse = pallas_latent_attention._forward(
        *(jnp.pad(t.reshape(2, tokens, 2 * 128), whole) for t in (q, k, v)), 2, 128**-0.5, True)
    out, lse = out[:, :tokens].reshape(v.shape), lse.reshape(2, 2, -1)[..., :tokens]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 128**-0.5
    scores = jnp.where(jnp.tril(jnp.ones((tokens, tokens), bool)), scores, -jnp.inf)
    assert lse.shape == (2, 2, tokens) and lse.dtype == jnp.float32  # one column a row: nothing 128 lanes wide
    np.testing.assert_allclose(lse, jax.nn.logsumexp(scores, axis=-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, dense_attention(q, k, v, causal=True, scale=128**-0.5), atol=2e-5)


def test_no_block_above_the_diagonal_is_visited(monkeypatch):
    """Keys and values of the LAST block poisoned with NaN: a masked score would
    still be NaN (0 x NaN in the product with the values), so every output row
    and every dq row of the earlier blocks stays finite and equal to the clean
    run only if their programs never read a block above the diagonal."""
    pair, _ = pair_and_dense(monkeypatch, 128, 128, 128)
    q, k, v, weight = attention_inputs(384, 128, 128)
    weight = weight.at[:, 256:].set(0.0)  # the poisoned rows' own outputs carry no cotangent
    clean, clean_grads = attention_value_and_grads(pair, q, k, v, weight)
    got, got_grads = attention_value_and_grads(pair, q, k.at[:, 256:].set(jnp.nan), v.at[:, 256:].set(jnp.nan), weight)
    assert bool(jnp.isnan(got[:, 256:]).all())  # the last block's own rows do see them
    np.testing.assert_array_equal(got[:, :256], clean[:, :256])
    np.testing.assert_array_equal(got_grads[0][:, :256], clean_grads[0][:, :256])  # dq of the earlier rows


@pytest.mark.parametrize("attn_impl, dtype, tpu, tokens, dqk, dv, want", [
    ("auto", "bfloat16", True, 4096, 256, 256, dict(core="kernel", core_head_dim=256, core_v_dim=256, block=512, core_tokens=4096, padded=False)),
    ("auto", "bfloat16", True, 1024, 192, 128, dict(core="kernel", core_head_dim=256, core_v_dim=128, block=512, core_tokens=1024, padded=True)),
    ("auto", "bfloat16", True, 700, 256, 256, dict(core="kernel", core_head_dim=256, core_v_dim=256, block=512, core_tokens=1024, padded=False)),
    ("auto", "bfloat16", True, 200, 64, 64, dict(core="kernel", core_head_dim=128, core_v_dim=128, block=256, core_tokens=256, padded=True)),
    ("auto", "bfloat16", True, 16384, 256, 256, dict(core="flash", core_head_dim=256, core_v_dim=256, block=512, core_tokens=16384, padded=False)),
    ("auto", "bfloat16", True, 16200, 192, 128, dict(core="flash", core_head_dim=256, core_v_dim=256, block=128, core_tokens=16256, padded=True)),
    ("auto", "float32", True, 4096, 256, 256, dict(core="dense", core_head_dim=256, core_v_dim=256, block=None, core_tokens=4096, padded=False)),
    ("auto", "bfloat16", False, 1024, 192, 128, dict(core="dense", core_head_dim=192, core_v_dim=128, block=None, core_tokens=1024, padded=False)),
    ("dense", "bfloat16", True, 4096, 256, 256, dict(core="dense", core_head_dim=256, core_v_dim=256, block=None, core_tokens=4096, padded=False)),
    ("flash", "float32", True, 4096, 256, 256, dict(core="kernel", core_head_dim=256, core_v_dim=256, block=512, core_tokens=4096, padded=False)),
], ids=["glm-cell", "kimi-cell", "padded-length", "narrow-heads", "past-vmem", "past-vmem-padded", "float32", "cpu",
        "asked-dense", "asked-fused"])
def test_which_core_latent_attention_takes_follows_from_dtype_backend_heads_and_length(
        monkeypatch, attn_impl, dtype, tpu, tokens, dqk, dv, want):
    from distributed_sigmoid_loss_tpu.models.mixers import latent_attention_core
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_latent_attention
    from distributed_sigmoid_loss_tpu.parallel import ring_attention

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: tpu)
    assert latent_attention_core(attn_impl, dtype, tokens, dqk, dv) == want
    # the mixer runs what the rule says, at the sizes it says
    taken = []

    def core_named(name):
        def core(q, k, v, **kw):  # the pair takes the heads on the lanes, (b, s, h x d), and their sizes; the others a view
            taken.append((name, q.shape[1], *kw.get("head_dims", (q.shape[-1], v.shape[-1]))))
            return jnp.zeros_like(v)
        return core

    monkeypatch.setattr(pallas_latent_attention, "latent_attention_kernel", core_named("kernel"))
    monkeypatch.setattr(flash_attention, "flash_self_attention", core_named("flash"))
    monkeypatch.setattr(ring_attention, "dense_attention", core_named("dense"))
    short = min(tokens, 16)  # the length is the rule's; the stand-in cores see what reaches them
    if short == tokens or want["core"] == "dense":
        layer = LatentAttention(32, 2, dqk - 8, 8, dv, 12, jnp.dtype(dtype), attn_impl=attn_impl)
        x = jnp.zeros((1, short, 32), jnp.dtype(dtype))
        jax.eval_shape(lambda x: layer.init_with_output(jax.random.key(0), x)[0], x)
        short_rule = latent_attention_core(attn_impl, dtype, short, dqk, dv)
        assert taken == [(short_rule["core"], short, short_rule["core_head_dim"], short_rule["core_v_dim"])]


def test_a_latent_attention_layer_on_the_kernel_path_is_the_layer_on_the_dense_path(monkeypatch):
    """The whole layer, values and parameter gradients, with its core through the
    interpreted pair (192 / 128 heads padded to 256 / 128, a sequence padded to
    its block) against the same layer on the dense path: the same parameter tree,
    the projections' columns recut where the dense path cuts and joins per-head
    activations. Around the kernels no transpose and no per-head (b, s, h, d)
    array but the one shared key part's rotation as a single head."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_latent_attention

    real = pallas_latent_attention.latent_attention_kernel
    monkeypatch.setattr(pallas_latent_attention, "latent_attention_kernel", partial(real, interpret=True))
    sizes = dict(width=48, num_heads=2, nope_dim=128, shared_dim=64, v_dim=128, kv_rank=32, dtype=jnp.float32,
                 q_rank=24, rope_theta=1e4)
    dense, fused = LatentAttention(**sizes, attn_impl="dense"), LatentAttention(**sizes, attn_impl="flash")
    x = jax.random.normal(jax.random.key(0), (2, 40, 48), jnp.float32)
    params = moved(dense.init(jax.random.key(1), x)["params"])
    weight = ripple((2, 40, 48))

    def loss(layer):
        return lambda p, x: (layer.apply({"params": p}, x) * weight).sum()

    want, want_grads = jax.value_and_grad(loss(dense), argnums=(0, 1))(params, x)
    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    got, got_grads = jax.value_and_grad(loss(fused), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    worst = reference_kimi._base.tree_max_rel_err(got_grads, want_grads)
    assert max(worst.values()) < 1e-3, worst
    traced = jax.make_jaxpr(jax.value_and_grad(loss(fused), argnums=(0, 1)))(params, x)
    assert str(traced).count("pallas_call") == 2  # mla_attn_fwd, mla_attn_bwd
    assert "transpose[" not in str(traced).replace("transpose[permutation=(1, 0)]", "")  # but the weights' own
    assert per_head_values(traced.jaxpr, 2, 256) == [] and per_head_values(traced.jaxpr, 2, 128) == []


@pytest.mark.parametrize("dp, tp, shard", [(2, 1, (2, 24, 4 * 128)), (2, 2, (2, 24, 2 * 128)), (1, 4, (4, 24, 1 * 128))],
                         ids=["dp2", "dp2-tp2", "tp4"])
def test_the_pair_sits_in_a_shard_map_under_a_jit_over_several_chips(monkeypatch, dp, tp, shard):
    """As the delta rule's kernels above: traced on a mesh the pair sees a chip's
    rows and, over ``tp``, a chip's whole heads (it reads their number off the
    widths it is given); bare without one. The same values and gradients."""
    import contextlib

    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sigmoid_loss_tpu.ops import flash_attention, pallas_latent_attention
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_2d_mesh, trace_on

    seen, real = [], pallas_latent_attention.latent_attention_kernel

    def interpreted(q, k, v, **kw):
        seen.append((q.shape, v.shape, kw["head_dims"]))
        return real(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(flash_attention, "flash_attention_available", lambda: True)
    monkeypatch.setattr(pallas_latent_attention, "latent_attention_kernel", interpreted)
    mesh = make_2d_mesh(dp, tp)
    layer = LatentAttention(32, 4, 120, 8, 128, 12, jnp.float32, attn_impl="flash", rope_theta=1e4)
    x = jax.device_put(jax.random.normal(jax.random.key(0), (4, 24, 32), jnp.float32), NamedSharding(mesh, P("dp")))
    params = layer.init(jax.random.key(1), x)["params"]
    seen.clear()

    def grads(on_mesh):
        def loss(p, x):
            with trace_on(mesh) if on_mesh else contextlib.nullcontext():
                return (layer.apply({"params": p}, x) ** 2).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)

    want, want_grads = grads(False)
    assert set(seen) == {((4, 24, 4 * 128), (4, 24, 4 * 128), (128, 128))}  # the heads on the lanes
    seen.clear()
    got, got_grads = grads(True)
    assert set(seen) == {(shard, shard, (128, 128))}  # a chip's rows and heads, at the same head sizes
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert max(reference_kimi._base.tree_max_rel_err(got_grads, want_grads).values()) < 1e-4


# -- (c) routing -------------------------------------------------------------------


def test_selection_is_by_score_plus_bias_and_weights_are_by_score():
    x = jax.random.normal(jax.random.key(0), (32, 8))
    wr = jax.random.normal(jax.random.key(1), (8, 16))
    scores = jax.nn.sigmoid(x @ wr)
    idx0, w0 = sigmoid_route(x, wr, jnp.zeros(16), 4, 2.446)
    np.testing.assert_array_equal(np.sort(idx0, -1), np.sort(np.argsort(-scores, -1)[:, :4], -1))
    np.testing.assert_allclose(w0.sum(-1), 2.446, rtol=1e-6)  # renormalised, then scaled
    np.testing.assert_allclose(w0, 2.446 * np.take_along_axis(np.asarray(scores), np.asarray(idx0), -1)
                               / np.take_along_axis(np.asarray(scores), np.asarray(idx0), -1).sum(-1, keepdims=True), rtol=1e-6)
    # a bias on expert 7 puts it into every token's set and leaves the scores that weigh alone
    bias = jnp.zeros(16).at[7].set(10.0)
    idx1, w1 = sigmoid_route(x, wr, bias, 4, 2.446)
    assert bool((idx1 == 7).any(-1).all()) and not bool((idx0 == 7).any(-1).all())
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx1), -1)
    np.testing.assert_allclose(w1, 2.446 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: sigmoid_route(x, wr, b, 4, 2.446)[1].sum())(bias)
    assert not np.asarray(grad).any()


@pytest.fixture(autouse=True)
def eight_rows_a_block(monkeypatch):
    """The toy layers cut an expert's segment into blocks of 8 rows (the cell's
    512 would hold every toy expert in one): a loaded expert runs several."""
    import sys

    monkeypatch.setattr(sys.modules["distributed_sigmoid_loss_tpu.models.moe"], "BLOCK_ROWS", 8)


def routed_layer(held, first=0, shared=1, experts=16, k=4):
    return SharedExpertMoe(8, 12, experts, k, jnp.float32, route_scale=2.446, shared_experts=shared,
                           experts_held=held, first_held=first)


def test_nothing_is_dropped_when_every_token_picks_one_held_expert():
    layer = routed_layer(held=4)
    x = jax.random.normal(jax.random.key(0), (3, 20, 8))
    params = moved(layer.init(jax.random.key(1), x)["params"])
    # expert 2 is in every token's set: 60 tokens on one expert of four, eight rows a block
    params[SELECT_BIAS] = jnp.zeros(16).at[2].set(10.0)
    y, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    load = state["intermediates"]["moe_load"][0]
    assert int(load["tokens"][2]) == 60 and int(load["dropped"]) == 0
    t = types.SimpleNamespace(moe_num_selected=4, moe_route_scale=2.446)
    np.testing.assert_allclose(y, reference_kimi.moe(x, params, t), atol=2e-5)


def test_the_shared_expert_alone_answers_where_no_held_expert_is_chosen():
    layer = routed_layer(held=4)
    x = jax.random.normal(jax.random.key(0), (2, 10, 8))
    params = moved(layer.init(jax.random.key(1), x)["params"])
    params[SELECT_BIAS] = jnp.zeros(16).at[:4].set(-10.0)  # the held experts are never chosen
    y, state = layer.apply({"params": params}, x, mutable=["intermediates"])
    assert not np.asarray(state["intermediates"]["moe_load"][0]["tokens"]).any()
    s = params["shared"]
    shared = reference_kimi.swiglu(x, s["wg"]["kernel"], s["wi"]["kernel"], s["wo"]["kernel"])
    np.testing.assert_allclose(y, shared, atol=1e-6)
    # and the gradient of a routed layer with nothing routed here is the shared expert's
    grads = jax.grad(lambda p: layer.apply({"params": p}, x).sum())(params)
    assert not np.asarray(grads["wg"]).any() and np.asarray(grads["shared"]["wg"]["kernel"]).any()


def test_the_sorted_plan_lists_every_assignment_to_a_held_expert_once():
    idx = jnp.array([[0, 5, 9], [5, 1, 2], [9, 8, 5], [3, 5, 0]])
    weights = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    token, row_weight, starts, counts = dispatch_plan(idx, weights, first=4, held=4)  # experts 4..7
    np.testing.assert_array_equal(counts, [0, 4, 0, 0])
    np.testing.assert_array_equal(starts, [0, 0, 4, 4])
    np.testing.assert_array_equal(token[:4], [0, 1, 2, 3])
    np.testing.assert_array_equal(row_weight[:4], [1.0, 3.0, 8.0, 10.0])


# -- (d) the share ties to the model -------------------------------------------------


def test_four_shares_of_four_experts_and_the_shared_expert_once_are_the_whole_layer():
    """16 experts over 4 chips: each share routes over all 16 and computes its 4;
    the routed parts add up, with the shared expert counted once, to what the
    reference gives for the layer with all 16 held."""
    whole = routed_layer(held=0)
    x = jax.random.normal(jax.random.key(0), (2, 24, 8))
    params = moved(whole.init(jax.random.key(1), x)["params"])
    t = types.SimpleNamespace(moe_num_selected=4, moe_route_scale=2.446)
    with jax.default_matmul_precision("highest"):
        want = reference_kimi.moe(x, params, t)
        s = params["shared"]
        shared = reference_kimi.swiglu(x, s["wg"]["kernel"], s["wi"]["kernel"], s["wo"]["kernel"])
        total, seen = shared, 0
        for share in range(4):
            part = dict(params, **{n: params[n][4 * share : 4 * share + 4] for n in ("wg", "wi", "wo")})
            y, state = routed_layer(held=4, first=4 * share).apply({"params": part}, x, mutable=["intermediates"])
            total = total + (y - shared)
            seen += int(state["intermediates"]["moe_load"][0]["tokens"].sum())
        np.testing.assert_allclose(whole.apply({"params": params}, x), want, atol=2e-5)
    assert seen == 2 * 24 * 4  # every assignment ran on exactly one share
    np.testing.assert_allclose(total, want, atol=3e-5)
