"""Fused short-sequence attention kernel vs the dense reference (interpret mode).

The Pallas TPU kernel runs in the interpreter on CPU — same kernel code, Python
execution — so these tests gate the kernel's math; the TPU-compiled path is covered
by the bench and by the driver's real-chip runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.ops import pallas_short_attention as psa
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
# logits packed into one lane tile.
CELL_CASES = [
    (2, 64, 16, 72, False),
    (1, 256, 16, 72, False),
    (2, 64, 12, 64, True),
    (1, 196, 4, 64, False),
    (1, 80, 2, 64, True),
    (2, 64, 4, 64, False),
]

# One small instance of every way the forward reaches a head (PR 27): the aligned
# window _head_windows gives it, over one, two or four rows a program (_fwd_plan:
# the batch decides among those the sequence allows). dh = 64 at an s that is no
# multiple of 8, as 196 is not, four rows; the dh-64 text shape, where the
# backward packs the pair and the forward masks it, causal too (no cell runs
# that); dh = 72 with an even head count (the windows end on the width) and an odd
# one (the last window is clipped: width 216), one and two rows; dh = 128 causal,
# a head a slab and no mask; an odd number of dh-64 heads (the backward falls back
# to slices, the forward's last slab is half a head's); a batch no row count
# divides; heads of 32 and of 256 lanes; a sequence so long that the plan
# normalises before the product (s = 400 at 16 x 72, f32).
FORWARD_CASES = [
    (4, 100, 2, 64, False),
    (4, 64, 2, 64, True),
    (8, 32, 4, 64, False),
    (1, 64, 16, 72, False),
    (2, 72, 3, 72, True),
    (2, 256, 2, 128, True),
    (2, 64, 3, 64, False),
    (3, 64, 2, 64, False),
    (2, 40, 8, 32, True),
    (1, 24, 1, 256, False),
    (1, 400, 16, 72, False),
]


@pytest.mark.parametrize("b,s,h,dh,causal", CASES + CELL_CASES + FORWARD_CASES)
def test_forward_matches_dense(b, s, h, dh, causal):
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
        for _ in range(3)
    )
    ref = dense_attention(q, k, v, causal=causal)
    out = short_self_attention(q, k, v, causal, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,s,h,dh,causal", CASES + CELL_CASES + FORWARD_CASES[4:7])
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


@pytest.mark.parametrize("b,s,h,dh,causal", [
    (2, 196, 12, 64, False),  # B/16's vision shape
    (2, 256, 4, 128, True),  # the looped text tower's heads
    (2, 64, 16, 72, False),  # so400m's text shape: windows of one and of two slabs
])
def test_bf16_gradients_match_dense_at_the_cell_shapes(b, s, h, dh, causal):
    """bf16 operands at B/16's vision shape (s = 196: the last row tile is
    partial, which a reduction over rows has to mask), the looped tower's and
    so400m's, under the on-chip bounds chip_smoke.py holds the compiled kernel
    to: 2e-2 forward, 5e-2 gradients, max-norm relative."""
    rng = np.random.default_rng(4)
    q, k, v, w = (
        jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
        for _ in range(4)
    )

    def fwd_bwd(fn):
        def loss(q, k, v):
            return jnp.sum((fn(q, k, v) * w).astype(jnp.float32))

        return (fn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    got = fwd_bwd(lambda q, k, v: short_self_attention(q, k, v, causal, None, True))
    want = fwd_bwd(lambda q, k, v: dense_attention(q, k, v, causal=causal))
    for name, a, b_, tol in zip(("out", "dq", "dk", "dv"), got, want,
                                (2e-2, 5e-2, 5e-2, 5e-2)):
        a, b_ = np.asarray(a, np.float32), np.asarray(b_, np.float32)
        err = np.max(np.abs(a - b_)) / np.max(np.abs(b_))
        assert err <= tol, f"{name}: {err:.3e} > {tol}"


def test_no_mutable_global_left_in_the_attention_kernel():
    """PR 28: the backward is chosen from the shapes alone, so the kernel file
    mutates no module-level state and repo_lint's allowlist exempts nothing in
    it. The one ``ops/`` entry left is the streaming loss kernel's trace
    recorder (ROADMAP D11)."""
    from distributed_sigmoid_loss_tpu.analysis import repo_lint

    rel = "ops/pallas_short_attention.py"
    with open(psa.__file__, encoding="utf-8") as f:
        findings = repo_lint.check_mutable_globals({rel: f.read()}, allowlist={})
    assert findings == [], [str(f) for f in findings]
    assert [k for k in repo_lint.MUTABLE_GLOBAL_ALLOWLIST if k.startswith("ops/")] == [
        "ops/pallas_sigmoid_loss.py::_TRACED_LOSS_KERNELS"
    ]


def test_custom_scale():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    ref = dense_attention(q, q, q, scale=0.25)
    out = short_self_attention(q, q, q, False, 0.25, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,s,h,dh,causal", [FORWARD_CASES[0], FORWARD_CASES[4], CELL_CASES[0]])
def test_two_forward_calls_give_equal_bits(b, s, h, dh, causal):
    """Remat runs the forward a second time in the backward pass: one code path,
    chosen from shapes alone, so the two runs agree to the bit."""
    rng = np.random.default_rng(5)
    q, k, v = (
        jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
        for _ in range(3)
    )
    fwd = jax.jit(lambda q, k, v: short_self_attention(q, k, v, causal, None, True))
    first, second = fwd(q, k, v), jax.jit(
        jax.checkpoint(lambda q, k, v: short_self_attention(q, k, v, causal, None, True))
    )(q, k, v)
    np.testing.assert_array_equal(np.asarray(first, np.float32), np.asarray(second, np.float32))


# One microbatch of each benchmark cell, per tower, bf16: (name, b, s, h, dh), the
# way the backward cuts its heads, the forward's rows a program, and how many of
# the forward's heads need a second slab. Every cell normalises after the product.
CELL_PATHS = [
    ("b16 text", 256, 64, 12, 64, "packed", 4, 0),
    ("b16 vision", 256, 196, 12, 64, "masked", 2, 0),
    ("b16 vision, microbatch 128", 128, 196, 12, 64, "masked", 2, 0),
    ("so400m text", 32, 64, 16, 72, "sliced", 4, 8),
    ("so400m vision", 32, 256, 16, 72, "sliced", 2, 8),
    ("looped text", 32, 256, 16, 128, "sliced", 1, 0),
]


@pytest.mark.parametrize("name,b,s,h,dh,cut,rows,straddling", CELL_PATHS)
def test_the_path_each_cell_shape_takes(name, b, s, h, dh, cut, rows, straddling):
    """What the chip measured fastest at each cell's shape (PERF.md section 6, PR
    24 and 27), pinned: an edit to the choosers that sends a cell down another
    path fails here, not in a benchmark three PRs later."""
    assert psa._head_cut(s, dh, h) == cut
    assert psa._fwd_plan(b, s, h * dh, h, 2) == (rows, True)
    windows = psa._head_windows(h, dh, h * dh)
    # No forward load or store is a lane slice: every window is whole slabs.
    assert all(start % 128 == 0 and stop % 128 == 0 for start, stop, _, _ in windows)
    assert all(start <= lo < hi <= stop for start, stop, lo, hi in windows)
    assert sum(stop - start > 128 and (stop - start) != hi - lo
               for start, stop, lo, hi in windows) == straddling
    if dh % 128 == 0:  # a head is its own window: nothing to mask
        assert all((start, stop) == (lo, hi) for start, stop, lo, hi in windows)


def test_head_cut_falls_back_to_slices():
    assert psa._head_cut(64, 64, 3) == "sliced"  # an odd head leaves half a slab
    assert psa._head_cut(64, 32, 4) == "sliced"
    assert psa._head_cut(48, 64, 2) == "packed"
    assert psa._head_cut(72, 64, 2) == "masked"  # 2 s > 128
    assert psa._head_cut(40, 64, 2) == "masked"  # bf16 rows pack by 16
    # A width that is no multiple of 128 clips the last window.
    assert psa._head_windows(3, 72, 216)[-1] == (128, 216, 144, 216)


def test_forward_plan_follows_batch_sequence_and_vmem():
    assert psa._fwd_plan(6, 64, 768, 12, 2) == (2, True)  # four does not divide six
    assert psa._fwd_plan(7, 64, 768, 12, 2) == (1, True)
    assert psa._fwd_plan(8, 128, 768, 12, 2) == (4, True)
    assert psa._fwd_plan(8, 264, 768, 12, 2) == (1, True)  # two rows pass _FWD_MAX_TOKENS
    assert psa._fwd_plan(8, 256, 2048, 16, 2) == (1, True)  # two rows pass the VMEM share
    # Past that a tile of exponentials a head does not fit: normalise first.
    assert psa._fwd_plan(8, 480, 768, 12, 2) == (1, False)
    assert psa._fwd_plan(8, 400, 1152, 16, 2) == (1, False)
    assert psa._fwd_plan(1, 400, 1152, 16, 4) == (1, False)


@pytest.mark.parametrize("width,heads", [(768, 12), (1152, 16), (2048, 16)])
def test_forward_footprint_is_within_what_the_dispatcher_checks(width, heads):
    """short_attention_fits budgets for the backward's 7 blocks and 3 tiles. The
    forward counts everything Mosaic allocates for it (both copies of its blocks,
    a tile a head when it defers the normalisation) and has to stay under
    Mosaic's limit at the cells' shapes and at every sequence the dispatcher
    admits, up to the longest; in the dispatcher's own count (blocks once, 2
    tiles) it is below the figure checked."""
    admitted = [s for s in range(8, 1025, 8) if psa.short_attention_fits(s, width, 2)]
    cells = [s for _, _, s, h, dh, *_ in CELL_PATHS if h * dh == width]
    assert max(admitted) >= max(cells)
    for s in cells + admitted:
        for b in (1, 2, 4, 256):
            rows, defer = psa._fwd_plan(b, s, width, heads, 2)
            assert psa._fwd_vmem_bytes(rows, s, width, heads, 2, defer) <= psa._VMEM_BYTES
            if defer:
                assert (psa._fwd_vmem_bytes(rows, s, width, heads, 2, True)
                        <= psa._VMEM_BYTES * psa._FWD_VMEM_FRACTION)
        assert 4 * s * width * 2 + 2 * s * s * 4 < psa.short_attention_vmem_bytes(s, width, 2)
    # The longest admitted sequence normalises before the product, one row.
    assert psa._fwd_plan(256, max(admitted), width, heads, 2) == (1, False)
