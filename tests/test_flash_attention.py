"""CPU coverage for the flash-attention wrapper and the VMEM-aware dispatcher.

The Pallas flash kernel itself is TPU-only, but everything the wrapper adds —
layout transpose, zero-padding to block multiples, segment-id masking, block-size
selection, output slicing — is pure jnp plumbing. These tests run that plumbing on
CPU against a dense stand-in kernel that honors the exact kernel interface
(segment_ids / causal / sm_scale / block_sizes), so only the upstream kernel's own
numerics remain TPU-only (covered by the tpu-marked parity test at the bottom).
"""

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.ops import flash_attention as fa
from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
    SHORT_ATTENTION_MAX_SEQ,
    short_attention_fits,
    short_attention_vmem_bytes,
)
from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention


def _dense_stand_in(qt, kt, vt, *, segment_ids, causal, sm_scale, block_sizes):
    """Dense attention in the kernel's (b, h, s, dh) layout implementing the Pallas
    kernel's masking contract: different segments never attend each other."""
    assert block_sizes is not None  # wrapper must always pick block sizes
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", qt.astype(jnp.float32), kt.astype(jnp.float32)
    ) * sm_scale
    if segment_ids is not None:
        mask = segment_ids.q[:, None, :, None] == segment_ids.kv[:, None, None, :]
        logits = jnp.where(mask, logits, -1e30)
    if causal:
        s = logits.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        logits = jnp.where(rows >= cols, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vt.astype(jnp.float32)).astype(qt.dtype)


def _qkv(b, s, h, dh, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, dh)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("s,expect_pad", [(128, 128), (196, 256), (300, 384),
                                          (1500, 1536)])
def test_prepare_inputs_padding_and_ids(s, expect_pad):
    q, k, v = _qkv(2, s, 2, 8)
    qt, kt, vt, ids, s_pad = fa._prepare_inputs(q, k, v)
    assert s_pad == expect_pad
    assert qt.shape == (2, 2, s_pad, 8)
    if s_pad == s:
        assert ids is None
    else:
        assert ids.shape == (2, s_pad)
        np.testing.assert_array_equal(np.asarray(ids[0, :s]), 1)
        np.testing.assert_array_equal(np.asarray(ids[0, s:]), 0)
        # Padded tail must be zeros (finite logits for pad-pad attention).
        assert float(jnp.abs(qt[:, :, s:, :]).sum()) == 0.0
    # Block size must divide the padded length in both grid directions.
    block = fa._block_size(s_pad)
    assert s_pad % block == 0 and block in (128, 256, 512)


@pytest.mark.parametrize("s", [196, 256, 300])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_plumbing_matches_dense(s, causal):
    """With a dense stand-in kernel, the wrapper's pad/mask/slice plumbing must be
    exactly equivalent to plain dense attention on the unpadded inputs."""
    q, k, v = _qkv(2, s, 2, 8)
    got = fa.flash_self_attention(
        q, k, v, causal=causal, kernel_fn=_dense_stand_in
    )
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_flash_custom_scale_passes_through():
    q, k, v = _qkv(1, 196, 2, 8, seed=3)
    got = fa.flash_self_attention(q, k, v, scale=0.25, kernel_fn=_dense_stand_in)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.25
    probs = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---- VMEM-aware dispatch (models/transformer.py routes via short_attention_fits) ----


def test_short_attention_fits_envelope():
    # Tower shapes comfortably fit.
    assert short_attention_fits(196, 768, 2)
    assert short_attention_fits(64, 768, 2)
    assert short_attention_fits(512, 1024, 2)
    # Over the sequence cap: never the short kernel, however narrow.
    assert not short_attention_fits(SHORT_ATTENTION_MAX_SEQ + 1, 64, 2)
    # Wide-model/long-seq combos inside the cap that would blow VMEM route away
    # (previously a Mosaic compile failure with no fallback).
    assert not short_attention_fits(1024, 4096, 2)
    assert not short_attention_fits(1024, 2048, 4)
    # The estimate is monotone in each argument.
    assert short_attention_vmem_bytes(512, 1024, 2) < short_attention_vmem_bytes(
        1024, 1024, 2
    )


def test_dispatch_wide_config_routes_to_flash(monkeypatch):
    """A bf16 config inside the seq cap but over the VMEM budget must take the
    blockwise flash path, not the VMEM-resident short kernel."""
    from distributed_sigmoid_loss_tpu.models import transformer as tr
    from distributed_sigmoid_loss_tpu.ops import pallas_short_attention as sa

    calls = []

    def fake_flash(q, k, v, *, causal=False, scale=None, kernel_fn=None):
        calls.append("flash")
        return dense_attention(q, k, v, causal=causal)

    def fake_short(q, k, v, causal=False, scale=None, interpret=False):
        calls.append("short")
        return dense_attention(q, k, v, causal=causal)

    monkeypatch.setattr(fa, "flash_attention_available", lambda: True)
    monkeypatch.setattr(fa, "flash_self_attention", fake_flash)
    monkeypatch.setattr(sa, "short_self_attention", fake_short)

    def run(s, width, heads):
        attn = tr.Attention(width=width, num_heads=heads, dtype=jnp.bfloat16,
                            attn_impl="auto")
        x = jnp.zeros((1, s, width), jnp.bfloat16)
        attn.init(jax.random.key(0), x)

    run(1024, 4096, 32)  # fits seq cap, blows VMEM -> flash
    assert calls[-1] == "flash"
    run(196, 768, 12)  # tower shape -> short kernel
    assert calls[-1] == "short"


@pytest.mark.skipif(jax.default_backend() != "tpu", reason="Pallas kernel needs TPU")
@pytest.mark.parametrize("s", [1500])
def test_flash_kernel_matches_dense_on_tpu(s):
    """Real-kernel parity for a >1024 sequence (the dispatch regime the CPU suite
    can't execute): forward and input grads vs the dense path, bf16."""
    q, k, v = _qkv(2, s, 4, 64, dtype=jnp.bfloat16, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_self_attention(q, k, v, causal=False) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=False) ** 2)

    out_f = fa.flash_self_attention(q, k, v)
    out_d = dense_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_f, np.float32), np.asarray(out_d, np.float32),
        rtol=2e-2, atol=2e-2,
    )
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-2, atol=5e-2,
        )


def test_fused_kernel_runs_per_shard_under_a_sharded_jit(monkeypatch):
    """A Mosaic kernel cannot be auto-partitioned: under a jit traced on a
    multi-device mesh (parallel.mesh.trace_on, as the train step does) the
    dispatch must hand the kernel its LOCAL rows inside a shard_map — the real
    interpret-mode kernel here, forward and gradients against dense. With no
    mesh in the trace the kernel is called bare, as on one chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sigmoid_loss_tpu.models import transformer as tr
    from distributed_sigmoid_loss_tpu.ops import pallas_short_attention as sa
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh, trace_on

    seen = []
    real_kernel = sa.short_self_attention

    def interpreted_short(q, k, v, causal=False):
        seen.append(q.shape)
        return real_kernel(q, k, v, causal, None, True)

    monkeypatch.setattr(fa, "flash_attention_available", lambda: True)
    monkeypatch.setattr(sa, "short_self_attention", interpreted_short)

    mesh = make_mesh(4)
    b, s, width, heads = 8, 16, 64, 2
    fused = tr.Attention(width=width, num_heads=heads, dtype=jnp.bfloat16)
    dense = tr.Attention(width=width, num_heads=heads, dtype=jnp.bfloat16,
                         attn_impl="dense")
    x = jax.device_put(
        jax.random.normal(jax.random.key(0), (b, s, width), jnp.bfloat16),
        NamedSharding(mesh, P("dp")),
    )
    params = nn.meta.unbox(dense.init(jax.random.key(1), x))

    def loss_and_grad(module, on_mesh):
        def loss(p, xx):
            with trace_on(mesh) if on_mesh else contextlib.nullcontext():
                return jnp.sum(module.apply(p, xx).astype(jnp.float32) ** 2)

        return jax.jit(jax.value_and_grad(loss, argnums=1))

    want, gwant = loss_and_grad(dense, False)(params, x)
    got, ggot = loss_and_grad(fused, True)(params, x)
    assert seen and all(shape[0] == b // 4 for shape in seen), seen
    np.testing.assert_allclose(float(got), float(want), rtol=2e-2)
    ggot32, gwant32 = np.asarray(ggot, np.float32), np.asarray(gwant, np.float32)
    # bf16 on both sides: judge the worst element against the gradient's scale
    assert np.max(np.abs(ggot32 - gwant32)) <= 2e-2 * np.max(np.abs(gwant32))
    assert ggot.sharding.spec == P("dp")

    seen.clear()
    loss_and_grad(fused, False)(params, x)
    assert seen and all(shape[0] == b for shape in seen), seen
