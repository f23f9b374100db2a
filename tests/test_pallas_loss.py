"""Streaming 2-D Pallas loss kernel (interpret mode on CPU): parity, int8
STE pins, chunked/ring unification, engagement recording.

Oracles, per the round-10 acceptance gate:

- loss AND grads parity vs the XLA paths (block level and under shard_map)
  at shapes where the kernel genuinely engages, including 2-D grids where
  BOTH operands stream (the local_b-unbounded structural pin);
- ``use_pallas × loss_impl='chunked'`` accepted end-to-end and parity-oracled
  against both the chunked XLA scan and the fused path;
- int8 forward bit-identical to the ``int8_dot_general_ste`` composition on
  the same operands, backward the exact full-precision STE VJP;
- the trace-time engagement recorder distinguishes kernel vs XLA fallback.

The standard tier covers every structural case; the exhaustive
W∈{1..8} × dtype × impl × quant sweep is slow-tier (--durations=15 rule).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
    DEFAULT_TILE_B,
    DEFAULT_TILE_N,
    NEGATIVE_ONLY_OFFSET,
    pallas_compatible,
    reset_traced_loss_kernels,
    streaming_block_loss_or_none,
    streaming_block_loss_sum,
    traced_loss_kernels,
)
from distributed_sigmoid_loss_tpu.ops.quant import int8_dot_general_ste
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import (
    init_loss_params,
    l2_normalize,
    pairwise_logits,
    sigmoid_loss_block,
    sigmoid_xent,
)
from distributed_sigmoid_loss_tpu.parallel import make_mesh, make_sharded_loss_fn

RTOL_F32 = 1e-5
GRAD_RTOL = 1e-4


def batch(b, n, d, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    zimg = l2_normalize(jnp.asarray(rng.standard_normal((b, d)), jnp.float32))
    ztxt = l2_normalize(jnp.asarray(rng.standard_normal((n, d)), jnp.float32))
    return zimg.astype(dtype), ztxt.astype(dtype)


def xla_block_loss(zimg, ztxt, t_prime, bias, offset=0):
    """The reference block math with the kernel's offset-diagonal labels."""
    logits = pairwise_logits(zimg, ztxt, t_prime, bias)
    rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    labels = jnp.where(cols == rows + offset, 1.0, -1.0).astype(logits.dtype)
    return sigmoid_xent(logits, labels).sum() / zimg.shape[0]


def assert_grads_close(ga, gb, rtol=GRAD_RTOL, atol=1e-6):
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=rtol, atol=atol,
        ),
        ga, gb,
    )


# ---------------------------------------------------------------------------
# block-level parity (values + grads)
# ---------------------------------------------------------------------------


# (8, 128, 128): single tile; (16, 512, 128): 2×64 grid with the default
# tiles clamped; (256, 512, 128): a true 2-D grid (2, 2) at the DEFAULT tile
# sizes — BOTH operands stream, nothing is whole-block VMEM-resident.
@pytest.mark.parametrize("b,n,d", [(8, 128, 128), (16, 512, 128),
                                   (256, 512, 128)])
def test_streaming_matches_xla_block(b, n, d):
    assert pallas_compatible(b, n, d)
    zimg, ztxt = batch(b, n, d)
    p = init_loss_params()

    def fused(zimg, ztxt, tp, bias):
        return streaming_block_loss_or_none(zimg, ztxt, tp, bias, 0.0)

    def xla(zimg, ztxt, tp, bias):
        return xla_block_loss(zimg, ztxt, tp, bias)

    args = (zimg, ztxt, p["t_prime"], p["bias"])
    np.testing.assert_allclose(
        float(fused(*args)), float(xla(*args)), rtol=RTOL_F32
    )
    g_fused = jax.grad(fused, argnums=(0, 1, 2, 3))(*args)
    g_xla = jax.grad(xla, argnums=(0, 1, 2, 3))(*args)
    assert_grads_close(g_fused, g_xla)


def test_negative_only_and_offset_blocks():
    zimg, ztxt = batch(8, 256, 128, seed=1)
    p = init_loss_params()
    got = streaming_block_loss_or_none(
        zimg, ztxt, p["t_prime"], p["bias"], NEGATIVE_ONLY_OFFSET
    )
    want = sigmoid_loss_block(
        zimg, ztxt, p["t_prime"], p["bias"], negative_only=True
    )
    np.testing.assert_allclose(float(got), float(want), rtol=RTOL_F32)
    # Shifted positive diagonal (the all-gather variant's idx*local_b):
    got = streaming_block_loss_or_none(
        zimg, ztxt, p["t_prime"], p["bias"], 128.0
    )
    want = xla_block_loss(zimg, ztxt, p["t_prime"], p["bias"], offset=128)
    np.testing.assert_allclose(float(got), float(want), rtol=RTOL_F32)


def test_engagement_recorder_truths():
    """The trace-time recorder: kernel engagement, int8 engagement, and the
    XLA fallback are all distinguishable — what chip_smoke.py reads to see
    that a use_pallas request engaged the kernel."""
    zimg, ztxt = batch(32, 32, 128, seed=2)
    p = init_loss_params()
    reset_traced_loss_kernels()
    assert traced_loss_kernels() == ()
    assert streaming_block_loss_or_none(
        zimg, ztxt, p["t_prime"], p["bias"], 0.0
    ) is not None
    assert traced_loss_kernels() == ("streaming",)
    assert streaming_block_loss_or_none(
        zimg, ztxt, p["t_prime"], p["bias"], 0.0, quant="int8"
    ) is not None
    assert traced_loss_kernels() == ("streaming", "streaming_int8")
    reset_traced_loss_kernels()
    # d not lane-aligned -> fallback, recorded:
    assert streaming_block_loss_or_none(
        zimg[:, :100], ztxt[:, :100], p["t_prime"], p["bias"], 0.0
    ) is None
    assert traced_loss_kernels() == ("xla",)
    # int8 sublane quantum (32) stricter than f32's (8):
    assert pallas_compatible(8, 8, 128) and not pallas_compatible(
        8, 8, 128, quant=True
    )
    reset_traced_loss_kernels()


# ---------------------------------------------------------------------------
# int8 MXU path: STE semantics pinned against ops/quant
# ---------------------------------------------------------------------------


def ste_reference_loss(zimg, ztxt, tp, bias, offset=0):
    """The loss composed through int8_dot_general_ste — THE semantics the
    kernel's quant path must match: quantized forward product, sigmoid
    evaluated at the quantized logits, full-precision VJP through the dot."""
    raw = int8_dot_general_ste(zimg, ztxt, (((1,), (1,)), ((), ())))
    logits = raw * jnp.exp(tp) + bias
    rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    labels = jnp.where(cols == rows + offset, 1.0, -1.0)
    return jax.nn.softplus(-labels * logits).sum() / zimg.shape[0]


@pytest.mark.parametrize("b,n", [(32, 32), (64, 96)])
def test_int8_forward_bit_identical_to_ste_dot(b, n):
    """Forward bit-identity on the same operands: the kernel's in-tile
    product (``_tile_raw_int8`` — int32 MXU dot + int8_dot_general's exact
    dequant arithmetic) run through a pallas_call on the SAME quantized
    operands as the inference dot, single-tile AND multi-tile — each output
    element's int32 accumulation spans the full contraction axis inside one
    tile, so tiling cannot change a single bit. (The end-to-end loss is
    additionally pinned at 1-ulp grade below: ``quantize_int8``'s scale
    division may round one ulp differently across compile contexts, which is
    a property of the shared quantizer, not of this kernel.)"""
    from jax.experimental import pallas as pl

    from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
        _tile_raw_int8,
    )
    from distributed_sigmoid_loss_tpu.ops.quant import (
        int8_dot_general,
        quantize_int8,
    )

    d = 128
    zimg, ztxt = batch(b, n, d, seed=3)
    ziq, zis = quantize_int8(zimg, axis=1)
    ztq, zts = quantize_int8(ztxt, axis=1)

    def tiled_raw(tile_b, tile_n):
        def kernel(ziq_ref, zis_ref, ztq_ref, zts_ref, out_ref):
            out_ref[...] = _tile_raw_int8(
                ziq_ref[:], zis_ref[:], ztq_ref[:], zts_ref[:]
            )

        from jax.experimental.pallas import tpu as pltpu

        def vspec(shape, index_map):
            return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

        return pl.pallas_call(
            kernel,
            grid=(b // tile_b, n // tile_n),
            in_specs=[
                vspec((tile_b, d), lambda i, j: (i, 0)),
                vspec((tile_b, 1), lambda i, j: (i, 0)),
                vspec((tile_n, d), lambda i, j: (j, 0)),
                vspec((tile_n, 1), lambda i, j: (j, 0)),
            ],
            out_specs=vspec((tile_b, tile_n), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
            interpret=True,
        )(ziq, zis, ztq, zts)

    want = int8_dot_general(zimg, ztxt, (((1,), (1,)), ((), ())))
    for tile_b, tile_n in [(b, n), (32, 32)]:
        got = tiled_raw(tile_b, tile_n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_end_to_end_loss_matches_ste_composition():
    """End-to-end int8 kernel loss vs the int8_dot_general_ste composition,
    to the rounding of an f32 sum of 1024 terms taken in two orders.

    Measured on jax 0.9.0 (PR 28): kernel 9.9488058, composition 9.9488163,
    relative difference 1.054e-6 (11 ulp). The same terms summed in float64
    give 9.9488097: the kernel's tile sum is 3.9e-6 under it, XLA's
    sequential CPU reduce 6.6e-6 over it (numpy's sequential f32 sum of the
    composition's terms reproduces its value to the bit). The logits are
    bit-identical (test_int8_forward_bit_identical_to_ste_dot); only the
    order of the additions differs. rtol is under ten times that reading."""
    zimg, ztxt = batch(32, 32, 128, seed=3)
    p = init_loss_params()
    got = streaming_block_loss_or_none(
        zimg, ztxt, p["t_prime"], p["bias"], 0.0, quant="int8",
        tile_b=32, tile_n=32,
    )
    want = ste_reference_loss(zimg, ztxt, p["t_prime"], p["bias"])
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_int8_backward_is_full_precision_vjp():
    """Backward = the exact STE composition gradient: the sigmoid factor at
    the QUANTIZED logits, the dzimg/dztxt dots on the full-precision
    operands (ops/quant.int8_dot_general_ste contract)."""
    zimg, ztxt = batch(64, 32, 128, seed=4)
    p = init_loss_params()

    def kernel_loss(zi, zt, tp, bi):
        return streaming_block_loss_or_none(
            zi, zt, tp, bi, 0.0, quant="int8", tile_b=32, tile_n=32
        )

    def ref_loss(zi, zt, tp, bi):
        return ste_reference_loss(zi, zt, tp, bi)

    args = (zimg, ztxt, p["t_prime"], p["bias"])
    gk = jax.grad(kernel_loss, argnums=(0, 1, 2, 3))(*args)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2, 3))(*args)
    assert_grads_close(gk, gr, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# under shard_map: the kernel as fused gather / chunk-scan body / ring hop
# ---------------------------------------------------------------------------


def sharded_loss_and_grads(mesh, p, zi, zt, **kw):
    fn = make_sharded_loss_fn(mesh, **kw)
    return jax.value_and_grad(fn, argnums=(0, 1, 2))(p, zi, zt)


@pytest.mark.parametrize("variant", ["all_gather", "ring"])
def test_sharded_pallas_matches_xla(variant):
    w, local_b, d = 2, 128, 128
    zi, zt = batch(w * local_b, w * local_b, d, seed=5)
    p = init_loss_params()
    mesh = make_mesh(w)
    l1, g1 = sharded_loss_and_grads(mesh, p, zi, zt, variant=variant)
    reset_traced_loss_kernels()
    l2, g2 = sharded_loss_and_grads(
        mesh, p, zi, zt, variant=variant, use_pallas=True
    )
    assert "streaming" in traced_loss_kernels()
    np.testing.assert_allclose(float(l1), float(l2), rtol=RTOL_F32)
    assert_grads_close(g1, g2)


def test_pallas_chunked_accepted_and_parity_oracled():
    """THE unification pin: use_pallas × loss_impl='chunked' builds and its
    loss/grads match BOTH the chunked XLA scan and the fused path."""
    w, local_b, d = 4, 32, 128
    zi, zt = batch(w * local_b, w * local_b, d, seed=6)
    p = init_loss_params()
    mesh = make_mesh(w)
    lf, gf = sharded_loss_and_grads(mesh, p, zi, zt, variant="all_gather")
    lc, gc = sharded_loss_and_grads(
        mesh, p, zi, zt, variant="all_gather", loss_impl="chunked"
    )
    reset_traced_loss_kernels()
    lp, gp = sharded_loss_and_grads(
        mesh, p, zi, zt, variant="all_gather", loss_impl="chunked",
        use_pallas=True,
    )
    assert traced_loss_kernels() == ("streaming",)
    np.testing.assert_allclose(float(lp), float(lc), rtol=RTOL_F32)
    np.testing.assert_allclose(float(lp), float(lf), rtol=RTOL_F32)
    assert_grads_close(gp, gc)
    assert_grads_close(gp, gf)


def test_pallas_ring_overlap_parity():
    w, local_b, d = 4, 32, 128
    zi, zt = batch(w * local_b, w * local_b, d, seed=7)
    p = init_loss_params()
    mesh = make_mesh(w)
    ls, gs = sharded_loss_and_grads(mesh, p, zi, zt, variant="ring")
    lo, go = sharded_loss_and_grads(
        mesh, p, zi, zt, variant="ring", ring_overlap=True, use_pallas=True
    )
    np.testing.assert_allclose(float(ls), float(lo), rtol=RTOL_F32)
    assert_grads_close(gs, go)


def test_pallas_int8_sharded_impls_agree():
    """int8 under shard_map: the fused-gather, chunk-scan and ring kernels
    quantize the same rows to the same scales, so the three compositions
    agree tightly with each other (and with full precision at int8 grade)."""
    w, local_b, d = 4, 32, 128
    zi, zt = batch(w * local_b, w * local_b, d, seed=8)
    p = init_loss_params()
    mesh = make_mesh(w)
    ref, _ = sharded_loss_and_grads(mesh, p, zi, zt, variant="all_gather")
    reset_traced_loss_kernels()
    results = [
        sharded_loss_and_grads(mesh, p, zi, zt, use_pallas=True, quant="int8",
                               **kw)
        for kw in (
            dict(variant="all_gather"),
            dict(variant="all_gather", loss_impl="chunked"),
            dict(variant="ring"),
        )
    ]
    assert traced_loss_kernels() == ("streaming_int8",)
    for li, gi in results[1:]:
        np.testing.assert_allclose(float(li), float(results[0][0]), rtol=1e-5)
        assert_grads_close(gi, results[0][1], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(results[0][0]), float(ref), rtol=2e-2)


# ---------------------------------------------------------------------------
# build/CLI acceptance + refusals
# ---------------------------------------------------------------------------


def test_api_accepts_pallas_chunked_and_refuses_quant_without_pallas():
    from distributed_sigmoid_loss_tpu.parallel.api import make_per_shard_loss

    # The round-7 conflict is GONE: this must build.
    make_per_shard_loss(
        variant="all_gather", loss_impl="chunked", use_pallas=True
    )
    make_per_shard_loss(variant="ring", ring_overlap=True, use_pallas=True,
                        quant="int8")
    with pytest.raises(ValueError, match="requires use_pallas"):
        make_per_shard_loss(variant="all_gather", quant="int8")
    with pytest.raises(ValueError, match="sigmoid family only"):
        make_per_shard_loss(family="softmax", use_pallas=True)
    with pytest.raises(ValueError, match="unknown loss quant"):
        make_per_shard_loss(use_pallas=True, quant="int4")


def test_cli_train_accepts_pallas_chunked_exit_0(tmp_path):
    """End-to-end CLI acceptance: `train --use-pallas --loss-impl chunked`
    exits 0 (one tiny step on synthetic data). The tiny embed (16) falls
    back to the XLA block per shape — engagement at kernel shapes is pinned
    by the shard_map tests above; THIS pins that the CLI/config plumbing
    accepts the composition end-to-end."""
    from distributed_sigmoid_loss_tpu.cli import main

    rc = main([
        "train", "--tiny", "--steps", "1", "--batch", "16",
        "--use-pallas", "--loss-impl", "chunked",
    ])
    assert rc == 0


def test_cli_train_pallas_softmax_exit_2():
    from distributed_sigmoid_loss_tpu.cli import main

    rc = main([
        "train", "--tiny", "--steps", "1",
        "--use-pallas", "--loss-family", "softmax",
    ])
    assert rc == 2


def test_train_step_resolves_loss_quant_from_towers():
    import dataclasses

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train.train_step import resolve_loss_quant
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
    )

    cfg = SigLIPConfig.tiny_test()
    qt = dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, quant_train="int8"),
        text=dataclasses.replace(cfg.text, quant_train="int8"),
    )
    assert resolve_loss_quant(SigLIP(qt), LossConfig(use_pallas=True)) == "int8"
    assert resolve_loss_quant(SigLIP(qt), LossConfig()) == ""
    assert resolve_loss_quant(SigLIP(cfg), LossConfig(use_pallas=True)) == ""


# ---------------------------------------------------------------------------
# attribution: pallas_call is no longer opaque to the FLOP walk
# ---------------------------------------------------------------------------


def test_attribution_counts_pallas_flops_exactly():
    """mfu_est's flops basis under --use-pallas: the jaxpr walk multiplies
    the kernel body's per-tile dot by the grid product, landing EXACTLY on
    the XLA path's count (= the closed form 2·local_b·(W·local_b)·d per
    device) — the undercount the round-10 satellite closes."""
    from distributed_sigmoid_loss_tpu.obs.attribution import (
        roofline_estimate,
        static_attribution,
    )

    w, local_b, d = 4, 32, 128
    zi, zt = batch(w * local_b, w * local_b, d, seed=10)
    p = init_loss_params()
    mesh = make_mesh(w)
    xla = make_sharded_loss_fn(mesh, variant="all_gather", jit=False)
    pal = make_sharded_loss_fn(
        mesh, variant="all_gather", use_pallas=True, jit=False
    )
    cx = static_attribution(xla, p, zi, zt)
    cp = static_attribution(pal, p, zi, zt)
    closed_form = 2.0 * local_b * (w * local_b) * d
    assert cp["flops_est"] == cx["flops_est"] == closed_form
    # chunked × pallas: scan trip count × per-chunk grid, same total
    pc = make_sharded_loss_fn(
        mesh, variant="all_gather", loss_impl="chunked", use_pallas=True,
        jit=False,
    )
    assert static_attribution(pc, p, zi, zt)["flops_est"] == closed_form
    est = roofline_estimate(cp["flops_est"], cp["comm_bytes_total"])
    assert est["mfu_est"] > 0


# ---------------------------------------------------------------------------
# exhaustive acceptance sweep (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("world_size", list(range(1, 9)))
def test_pallas_exhaustive_sweep(world_size):
    """W∈{1..8} × dtype × {fused, chunked, ring, ring-overlap} × {f32, int8}
    parity under interpret-mode shard_map: loss AND grads vs the XLA
    baseline of the same impl (f32 rtol 1e-5; bf16 inputs at bf16 grade;
    int8 compositions vs each other tightly and vs f32 at int8 grade)."""
    w = world_size
    local_b, d = 32, 128
    mesh = make_mesh(w)
    p = init_loss_params()
    impls = [
        dict(variant="all_gather"),
        dict(variant="all_gather", loss_impl="chunked"),
        dict(variant="ring"),
        dict(variant="ring", ring_overlap=True),
    ]
    for dtype, rtol, gr_atol in [
        (jnp.float32, RTOL_F32, 1e-6), (jnp.bfloat16, 3e-2, 1e-2)
    ]:
        zi, zt = batch(w * local_b, w * local_b, d, seed=w, dtype=dtype)
        for kw in impls:
            lx, gx = sharded_loss_and_grads(mesh, p, zi, zt, **kw)
            lp, gp = sharded_loss_and_grads(
                mesh, p, zi, zt, use_pallas=True, **kw
            )
            np.testing.assert_allclose(
                np.float32(lp), np.float32(lx), rtol=rtol, err_msg=str(kw)
            )
            assert_grads_close(gp, gx, rtol=max(GRAD_RTOL, rtol),
                               atol=gr_atol)
    # int8: all four compositions agree with each other
    zi, zt = batch(w * local_b, w * local_b, d, seed=100 + w)
    results = [
        sharded_loss_and_grads(
            mesh, p, zi, zt, use_pallas=True, quant="int8", **kw
        )
        for kw in impls
    ]
    base_l, base_g = results[0]
    for li, gi in results[1:]:
        np.testing.assert_allclose(float(li), float(base_l), rtol=1e-5)
        assert_grads_close(gi, base_g, rtol=1e-4, atol=1e-6)
    ref, _ = sharded_loss_and_grads(mesh, p, zi, zt, variant="all_gather")
    np.testing.assert_allclose(float(base_l), float(ref), rtol=2e-2)
