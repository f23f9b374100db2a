"""Fused short-sequence attention kernel vs the dense reference (interpret mode).

The Pallas TPU kernel runs in the interpreter on CPU — same kernel code, Python
execution — so these tests gate the kernel's math; the TPU-compiled path is covered
by the bench and by the driver's real-chip runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
    short_self_attention,
)
from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention

CASES = [
    # (b, s, h, dh, causal) — s=196 is the ViT-B/16 shape (not tile-aligned),
    # s=64 the text-tower shape, s=256 aligned + causal.
    (2, 196, 4, 32, False),
    (2, 64, 4, 32, False),
    (1, 128, 2, 32, True),
]

# The shapes the benchmark's cells run and CASES does not: so400m's heads
# (16 x 72: no head starts on a 128-lane boundary) at its two sequence lengths,
# and causal at the text shape, where the backward's key-major mask is the
# transposed one. Then one case for each way the backward cuts dh = 64 heads out
# of a 128-lane slab (CASES' dh = 32 takes the lane-slice chain): s = 196 and
# causal s = 80, two heads masked in turn; s = 64 non-causal, the two heads'
# logits packed into one lane tile. Not in the batched-parity test:
# batch_heads=True refuses the so400m shape in f32.
CELL_CASES = [
    (2, 64, 16, 72, False),
    (1, 256, 16, 72, False),
    (2, 64, 12, 64, True),
    (1, 196, 4, 64, False),
    (1, 80, 2, 64, True),
    (2, 64, 4, 64, False),
]


@pytest.mark.parametrize("b,s,h,dh,causal", CASES + CELL_CASES)
def test_forward_matches_dense(b, s, h, dh, causal):
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
        for _ in range(3)
    )
    ref = dense_attention(q, k, v, causal=causal)
    out = short_self_attention(q, k, v, causal, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,s,h,dh,causal", CASES + CELL_CASES)
def test_gradients_match_dense(b, s, h, dh, causal):
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
        for _ in range(3)
    )
    # Non-uniform cotangent: exercises the softmax VJP beyond the all-ones case.
    w = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    g_ref = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, causal=causal)),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss(lambda q, k, v: short_self_attention(q, k, v, causal, None, True)),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


def test_bf16_gradients_match_dense_at_the_vision_shape():
    """bf16 operands at B/16's vision shape (s = 196: the last row tile is
    partial, which a reduction over rows has to mask), under the on-chip bounds
    chip_smoke.py holds the compiled kernel to: 2e-2 forward, 5e-2 gradients,
    max-norm relative."""
    rng = np.random.default_rng(4)
    q, k, v, w = (
        jnp.asarray(rng.standard_normal((2, 196, 12, 64)), jnp.bfloat16)
        for _ in range(4)
    )

    def fwd_bwd(fn):
        def loss(q, k, v):
            return jnp.sum((fn(q, k, v) * w).astype(jnp.float32))

        return (fn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    got = fwd_bwd(lambda q, k, v: short_self_attention(q, k, v, False, None, True))
    want = fwd_bwd(dense_attention)
    for name, a, b_, tol in zip(("out", "dq", "dk", "dv"), got, want,
                                (2e-2, 5e-2, 5e-2, 5e-2)):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        err = np.max(np.abs(a - b_)) / np.max(np.abs(b_))
        assert err <= tol, f"{name}: {err:.3e} > {tol}"


@pytest.mark.parametrize("b,s,h,dh,causal", CASES)
def test_batched_bwd_matches_per_head_loop(b, s, h, dh, causal):
    """The head-batched backward (round-3 attribution candidate, bench
    --attn-bwd batched) must reproduce the per-head loop's gradients — same
    chain, same f32 softmax/logits numerics, different MXU dispatch shape."""
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
        for _ in range(3)
    )
    w = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)

    def grads(batch_heads):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                short_self_attention(q, k, v, causal, None, True, batch_heads)
                * w
            ),
            argnums=(0, 1, 2),
        )(q, k, v)

    for g_b, g_l in zip(grads(True), grads(False)):
        np.testing.assert_allclose(
            np.asarray(g_b), np.asarray(g_l), atol=2e-5
        )


def test_traced_bwd_choice_is_recorded_at_trace_time():
    """The bench record cross-check's data source: tracing the backward must
    record the kernel choice RESOLVED (default or explicit), so a step traced
    before a set_bwd_batch_heads flip is detectable (advisor, round 5)."""
    from distributed_sigmoid_loss_tpu.ops import pallas_short_attention as psa

    rng = np.random.default_rng(3)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 8, 2, 4)), jnp.float32)
        for _ in range(3)
    )
    psa.reset_traced_bwd_batch_heads()
    try:
        assert psa.traced_bwd_batch_heads() == ()
        jax.grad(
            lambda q: jnp.sum(short_self_attention(q, k, v, False, None, True))
        )(q)
        assert psa.traced_bwd_batch_heads() == (False,)  # default: per-head loop
        jax.grad(
            lambda q: jnp.sum(
                short_self_attention(q, k, v, False, None, True, True)
            )
        )(q)
        assert psa.traced_bwd_batch_heads() == (False, True)  # mixed → detectable
    finally:
        psa.reset_traced_bwd_batch_heads()


def test_batched_bwd_fits_check():
    from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
        short_attention_bwd_batched_fits,
        short_self_attention as ssa,
    )

    # ViT-B/16 and text shapes fit; a 1024-seq 16-head tower does not.
    assert short_attention_bwd_batched_fits(196, 768, 12, 2)
    assert short_attention_bwd_batched_fits(64, 768, 12, 2)
    assert not short_attention_bwd_batched_fits(1024, 1024, 16, 2)
    q = jnp.zeros((1, 1024, 16, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="batch_heads"):
        jax.grad(
            lambda q: jnp.sum(
                ssa(q, q, q, False, None, True, True).astype(jnp.float32)
            )
        )(q)


def test_custom_scale():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    ref = dense_attention(q, q, q, scale=0.25)
    out = short_self_attention(q, q, q, False, 0.25, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
