"""Adaptive error-feedback DCN compression (graftsqueeze).

Oracles, in the established compression-suite style (test_grad_compression):

- pack/unpack roundtrips are EXACT (int4 nibbles sign-exact via arithmetic
  shifts; sign bits 8-per-byte), and the payload table is pinned in bytes;
- the adaptive mean inside shard_map matches the exact mean per scheme, its
  wire-byte accounting is pinned to the payload table, and error feedback
  telescopes even under the 1-bit rung;
- the adaptive STEP tracks the uncompressed step (sgd delta oracle), scheme
  changes are operand-value changes (``_cache_size() == 1`` across a swap —
  the no-recompile acceptance property), and a synthetic bandwidth drop
  (EWMA override) narrows the table within one decision round while the wire
  bytes land at or under 0.25x the bf16 all-gather baseline read from
  obs/attribution;
- the BitController is deterministic, narrows lowest-EF-ratio-first, and
  widens again on recovery;
- exact top-k selection (``topk_approximate=False``) is bit-reproducible
  across runs and across dp ranks;
- the ``jaxpr-ef-threaded`` graftlint rule trips on dropped / passed-through
  residual fixtures (plain and shard_map-wrapped) and the new schema /
  config-space rows are registered, with unregistered-neighbor falsification.

Tiering (tier-1's time limit): the module is conftest-standard, but the
step-level oracles that compile the full (2, 4) hybrid step — parity vs the
uncompressed step, the scheme-swap no-recompile pin, the 0.25x-bf16 wire
oracle, the zero1+accum composition, and the full config-product ef-indices
arming — are ``slow``-marked; run the module unfiltered for those.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
    N_SCHEMES,
    SCHEME_INT4,
    SCHEME_INT8,
    SCHEME_SIGN1,
    SCHEME_TOPK,
    SCHEME_TOPK_LOW,
    BitController,
    adaptive_axis_mean,
    leaf_sizes,
    pack_int4,
    pack_signs,
    payload_bytes_table,
    quantize_tensor_int4,
    unpack_int4,
    unpack_signs,
)
from distributed_sigmoid_loss_tpu.parallel.compression import (
    init_error_feedback,
)


def hybrid_mesh(dcn=2, dp=4):
    devs = np.array(jax.devices()[: dcn * dp]).reshape(dcn, dp)
    return Mesh(devs, ("dcn", "dp"))


# ---------------------------------------------------------------- packing --


def test_int4_pack_roundtrip_exact():
    rng = np.random.default_rng(0)
    for size in (7, 8, 33):
        q = jnp.asarray(rng.integers(-7, 8, (size,)), jnp.int8)
        out = unpack_int4(pack_int4(q), size)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(q))


def test_int4_quantize_bound():
    rng = np.random.default_rng(1)
    t = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    q, s = quantize_tensor_int4(t)
    assert q.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(q))) <= 7
    # Half a bucket at scale = max|t| / 7.
    err = jnp.max(jnp.abs(q.astype(jnp.float32) * s - t))
    assert float(err) <= float(s) * 0.5 + 1e-7


def test_sign_pack_roundtrip_exact():
    rng = np.random.default_rng(2)
    for size in (5, 8, 17):
        t = jnp.asarray(rng.standard_normal((size,)), jnp.float32)
        signs = unpack_signs(pack_signs(t), size)
        np.testing.assert_array_equal(
            np.asarray(signs), np.where(np.asarray(t) >= 0, 1.0, -1.0)
        )


def test_payload_bytes_table_pinned():
    # size=1000, topk_frac=1%: int8 1000+4; int4 500+4; sign1 125+4;
    # topk 8*k(10); topk_low 8*k(round(2.5)=2) — 8 B per kept entry
    # (f32 value + int32 index), 4 B per f32 scale; learned
    # 16 latents/64-block int8-on-wire: 16*ceil(1000/64)+4 = 260.
    np.testing.assert_array_equal(
        payload_bytes_table(1000, 0.01), [1004, 504, 129, 80, 16, 260]
    )
    # Tiny tensors: k clamps at 1, so the "sparse" rungs can be the widest
    # and the learned rung (one full latent block) is the widest of all.
    np.testing.assert_array_equal(
        payload_bytes_table(1, 0.01), [5, 5, 5, 8, 8, 20]
    )


# ------------------------------------------------- adaptive mean (shard_map)


def _mean_fn(mesh, shapes, topk_approximate=True):
    """jit of adaptive_axis_mean over dcn for a dict of (2, *shape) arrays."""

    def body(tree, ef, scheme):
        local = jax.tree.map(lambda t: jnp.squeeze(t, 0), tree)
        return adaptive_axis_mean(
            local, "dcn", ef, scheme, topk_approximate=topk_approximate
        )

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("dcn"), P("dcn"), P()),
            out_specs=(P(), P("dcn"), P(), P()),
            check_vma=False,
        )
    )


def test_adaptive_mean_accuracy_per_scheme_no_recompile():
    mesh = hybrid_mesh()
    rng = np.random.default_rng(3)
    g = {"g": jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)}
    ef = init_error_feedback({"g": jnp.zeros((16, 8))}, 2)
    fn = _mean_fn(mesh, {"g": (16, 8)})
    exact = jnp.mean(g["g"], axis=0)
    for code, tol in ((SCHEME_INT8, 0.02), (SCHEME_INT4, 0.2)):
        mean, _, stats, _ = fn(g, ef, jnp.full((1,), code, jnp.int32))
        rel = float(
            jnp.max(jnp.abs(mean["g"] - exact)) / jnp.max(jnp.abs(exact))
        )
        assert rel < tol, (code, rel)
        assert np.isfinite(float(stats["gnorm"][0]))
    # Scheme swaps are operand VALUE changes: one compiled program total.
    assert fn._cache_size() == 1


def test_adaptive_mean_wire_bytes_pinned():
    mesh = hybrid_mesh()
    rng = np.random.default_rng(4)
    tree = {
        "a": jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((2, 50)), jnp.float32),
    }
    ef = init_error_feedback(
        {"a": jnp.zeros((16, 8)), "b": jnp.zeros((50,))}, 2
    )
    fn = _mean_fn(mesh, None)
    # all-int8: (2-1) * ((128+4) + (50+4)).
    _, _, _, wire = fn(tree, ef, jnp.zeros((2,), jnp.int32))
    assert int(wire) == 186
    # sign1 for a (128/8+4=20) + topk for b (k=1 -> 8): 28.
    scheme = jnp.asarray([SCHEME_SIGN1, SCHEME_TOPK], jnp.int32)
    _, _, _, wire = fn(tree, ef, scheme)
    assert int(wire) == 28
    assert fn._cache_size() == 1


def test_error_feedback_telescopes_under_sign1():
    """Sum of K sign1-synced means tracks the exact sum; without EF the 1-bit
    wire is pure bias. Oracle: the no-EF error grows ~linearly in K (fixed
    reconstruction-error pattern each round) while the EF error stays bounded
    by the final residual — at K=60 they separate by well over 5x."""
    mesh = hybrid_mesh()
    rng = np.random.default_rng(5)
    K = 60
    # A persistent gradient direction + per-round jitter: the per-round
    # sign1 reconstruction error is then a FIXED pattern, so without EF it
    # accumulates linearly over K rounds while EF telescopes it away.
    base = rng.standard_normal((1, 2, 8, 4)) * 0.01
    jitter = rng.standard_normal((K, 2, 8, 4)) * 0.001
    gs = jnp.asarray(base + jitter, jnp.float32)
    scheme = jnp.full((1,), SCHEME_SIGN1, jnp.int32)

    def body(seq, ef, carry_ef):
        def one(e, t):
            mean, e2, _, _ = adaptive_axis_mean(
                {"g": jnp.squeeze(t, 0)}, "dcn", {"g": e}, scheme
            )
            e_next = e2["g"] if carry_ef else e
            return e_next, mean["g"]

        ef2, means = lax.scan(one, ef["g"], seq)
        return jnp.sum(means, axis=0), {"g": ef2}

    def run(carry_ef):
        summed, _ = jax.jit(
            jax.shard_map(
                lambda s, e: body(s, e, carry_ef), mesh=mesh,
                in_specs=(P(None, "dcn"), P("dcn")),
                out_specs=(P(), P("dcn")),
                check_vma=False,
            )
        )(gs, init_error_feedback({"g": jnp.zeros((8, 4))}, 2))
        exact = jnp.sum(jnp.mean(gs, axis=1), axis=0)
        return float(jnp.max(jnp.abs(summed - exact)))

    err_ef, err_no_ef = run(True), run(False)
    assert err_ef < 0.2 * err_no_ef, (err_ef, err_no_ef)


def test_topk_exact_selection_is_bit_reproducible():
    """topk_approximate=False: identical results across two runs AND across
    dp ranks (each rank selects on the same replicated tensor; any
    nondeterminism in selection would diverge the stacked rows)."""
    mesh = hybrid_mesh()
    rng = np.random.default_rng(6)
    g = {"g": jnp.asarray(rng.standard_normal((2, 64)), jnp.float32)}
    ef = init_error_feedback({"g": jnp.zeros((64,))}, 2)
    scheme = jnp.full((1,), SCHEME_TOPK, jnp.int32)

    def body(tree, e, s):
        local = jax.tree.map(lambda t: jnp.squeeze(t, 0), tree)
        mean, _, _, _ = adaptive_axis_mean(
            local, "dcn", e, s, topk_approximate=False
        )
        return mean["g"][None]                      # stacked over dp ranks

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("dcn"), P("dcn"), P()),
            out_specs=P("dp"),
            check_vma=False,
        )
    )
    out1 = np.asarray(fn(g, ef, scheme))
    out2 = np.asarray(fn(g, ef, scheme))
    np.testing.assert_array_equal(out1, out2)       # run-to-run
    for row in out1[1:]:
        np.testing.assert_array_equal(out1[0], row)  # rank-to-rank


def test_adaptive_mean_requires_ef():
    with pytest.raises(ValueError, match="error feedback"):
        adaptive_axis_mean(
            {"g": jnp.zeros((4,))}, "dcn", None, jnp.zeros((1,), jnp.int32)
        )


# ------------------------------------------------------------ BitController


def test_controller_widest_start_budget_descent_and_order():
    sizes = [1000, 64]
    c = BitController(sizes, n_dcn=2)
    # No bandwidth signal, no budget: stays widest (int8 for real tensors).
    np.testing.assert_array_equal(c.decide(), [SCHEME_INT8, SCHEME_INT8])
    # Starved: every tensor lands on its narrowest rung by actual bytes
    # (compare payloads, not codes — tied rungs make the code ambiguous).
    c.override_bandwidth(1e-6)
    narrowest = c.decide()
    tables = np.stack([payload_bytes_table(s) for s in sizes])
    np.testing.assert_array_equal(
        tables[np.arange(len(sizes)), narrowest], tables.min(axis=1)
    )
    # Moderate budget + EF ratios: the LOW-ratio tensor gives up bits first.
    c2 = BitController(sizes, n_dcn=2)
    c2.override_bandwidth(None)
    # Budget that forces exactly one rung of narrowing somewhere: the full
    # int8 egress is (1004+68) = 1072 B; allow slightly less.
    c2.dcn_budget_mbps = (1070 * 8.0 / 0.1) / 1e6
    scheme = c2.decide(np.asarray([0.5, 0.1]))
    assert scheme[0] == SCHEME_INT8                  # high ratio: untouched
    assert scheme[1] != SCHEME_INT8                  # low ratio: narrowed


def test_controller_ewma_reacts_and_recovers():
    c = BitController([10_000], n_dcn=2)
    # Healthy observed bandwidth (~8 Mbps -> 100 kB allowed per round): the
    # 10004-byte int8 egress fits.
    c.observe(0.01, 10_004.0)
    assert c.bw_est_mbps == pytest.approx(8.0032)
    assert c.decide()[0] == SCHEME_INT8
    # Bandwidth collapse: the EWMA follows and the table narrows.
    for _ in range(20):
        c.observe(10.0, 10_004.0)                    # ~0.008 Mbps inst
    assert c.decide()[0] != SCHEME_INT8
    # Recovery: decisions are recomputed from scratch, so it widens again.
    for _ in range(20):
        c.observe(0.001, 10_004.0)                   # ~80 Mbps inst
    assert c.decide()[0] == SCHEME_INT8


def test_controller_deterministic():
    a = BitController([100, 200, 300], n_dcn=4, dcn_budget_mbps=0.005)
    b = BitController([100, 200, 300], n_dcn=4, dcn_budget_mbps=0.005)
    ratios = np.asarray([0.3, 0.1, 0.2])
    np.testing.assert_array_equal(a.decide(ratios), b.decide(ratios))
    assert a.scheme.dtype == np.int32
    with pytest.raises(ValueError, match="n_dcn"):
        BitController([10], n_dcn=1)


# ------------------------------------------------------------ the full step


def _tiny_model_and_batch():
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig

    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    rng = np.random.default_rng(7)
    b = 16
    images = jnp.asarray(
        rng.standard_normal(
            (b, cfg.vision.image_size, cfg.vision.image_size, 3)
        ),
        jnp.float32,
    )
    tokens = jnp.asarray(
        rng.integers(0, cfg.text.vocab_size, (b, cfg.text.context_length)),
        jnp.int32,
    )
    return model, {"images": images, "tokens": tokens}


@pytest.fixture(scope="module")
def adaptive_setup():
    """One shared build of the adaptive + uncompressed steps on a (2, 4)
    mesh — the compile is the expensive part; every step-level test below
    reuses it (states are rebuilt per test from the same key)."""
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_compressed_train_step,
        make_train_step,
        with_adaptive_compression,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig

    mesh = hybrid_mesh()
    model, batch = _tiny_model_and_batch()
    tx = optax.sgd(1e-2)
    cfg = LossConfig(variant="all_gather")
    step_a, shard_a = make_compressed_train_step(
        model, mesh, cfg, compression="adaptive"
    )
    step_u, shard_u = make_train_step(model, mesh, cfg)

    def fresh_adaptive():
        st = create_train_state(jax.random.key(0), model, tx, batch, mesh)
        return with_adaptive_compression(st, mesh)

    def fresh_plain():
        return create_train_state(jax.random.key(0), model, tx, batch, mesh)

    return {
        "mesh": mesh, "model": model, "batch": batch,
        "step_a": step_a, "step_u": step_u,
        "shard_a": shard_a, "shard_u": shard_u,
        "fresh_adaptive": fresh_adaptive, "fresh_plain": fresh_plain,
    }


@pytest.mark.slow
def test_adaptive_step_matches_uncompressed(adaptive_setup):
    """sgd delta oracle (the int8 suite's): at the initial all-widest scheme
    the adaptive sync is int8 for every real tensor, so one-step param deltas
    must agree to quantization error; metrics carry the full wire accounting."""
    s = adaptive_setup
    state_a, state_u = s["fresh_adaptive"](), s["fresh_plain"]()
    p0 = jax.tree.map(jnp.copy, state_u.params)
    state_a, ma = s["step_a"](state_a, jax.device_put(s["batch"], s["shard_a"]))
    state_u, mu = s["step_u"](state_u, jax.device_put(s["batch"], s["shard_u"]))
    np.testing.assert_allclose(
        float(ma["loss"]), float(mu["loss"]), rtol=1e-5
    )
    for dc, du in zip(
        jax.tree.leaves(jax.tree.map(lambda a, b: a - b, state_a.params, p0)),
        jax.tree.leaves(jax.tree.map(lambda a, b: a - b, state_u.params, p0)),
    ):
        scale = float(jnp.max(jnp.abs(du)))
        if scale < 1e-8:
            continue  # zero-gradient directions: roundoff, not signal
        rel = float(jnp.max(jnp.abs(dc - du))) / scale
        assert rel < 0.02, rel
    # Wire accounting on the line: egress bytes, bits/param, residual norm,
    # per-scheme histogram summing to the tensor count.
    n_tensors = len(leaf_sizes(state_a.params))
    hist = np.asarray(ma["compression_scheme_hist"])
    assert hist.shape == (N_SCHEMES,) and int(hist.sum()) == n_tensors
    assert float(ma["dcn_wire_bytes"]) > 0
    assert 0 < float(ma["bits_per_param"]) <= 8.5
    assert float(ma["ef_residual_norm"]) >= 0.0
    # The step wrote its per-tensor stats back into the carry.
    assert np.asarray(state_a.comp["gnorm"]).shape == (n_tensors,)
    assert float(np.max(np.asarray(state_a.comp["ef_ratio"]))) >= 0.0


@pytest.mark.slow
def test_scheme_swap_reacts_without_recompile(adaptive_setup):
    """The acceptance pin: a synthetic bandwidth drop (EWMA override) narrows
    >= 1 tensor within two sync rounds, the staged swap changes the measured
    wire bytes, and the compile count stays flat (_cache_size() == 1)."""
    from distributed_sigmoid_loss_tpu.train import stage_scheme

    s = adaptive_setup
    mesh, batch = s["mesh"], jax.device_put(s["batch"], s["shard_a"])
    state = s["fresh_adaptive"]()
    controller = BitController(leaf_sizes(state.params), n_dcn=2)

    state, m1 = s["step_a"](state, batch)
    wide_wire = float(m1["dcn_wire_bytes"])
    wide_hist = np.asarray(m1["compression_scheme_hist"])

    # Round 1: bandwidth collapses. Decide from the step's own stats.
    controller.override_bandwidth(0.001)
    scheme = controller.decide(np.asarray(state.comp["ef_ratio"]))
    assert int(np.sum(scheme != controller.tables.argmax(axis=1))) >= 1
    state = stage_scheme(state, scheme, mesh)

    # Round 2: the narrowed table is live — less wire, same executable.
    state, m2 = s["step_a"](state, batch)
    assert float(m2["dcn_wire_bytes"]) < wide_wire
    assert not np.array_equal(
        np.asarray(m2["compression_scheme_hist"]), wide_hist
    )
    assert float(m2["loss"]) > 0 and np.isfinite(float(m2["loss"]))
    assert s["step_a"]._cache_size() == 1

    # Recovery: controller recomputes from scratch, table widens again.
    controller.override_bandwidth(None)
    controller.observe(1e-3, wide_wire)              # healthy round
    recovered = controller.decide(np.asarray(state.comp["ef_ratio"]))
    assert int(np.sum(recovered == SCHEME_INT8)) > int(
        np.sum(scheme == SCHEME_INT8)
    )


@pytest.mark.slow
def test_wire_bytes_quarter_of_bf16_baseline(adaptive_setup):
    """Budget-starved adaptive wire <= 0.25x the bf16 all-gather baseline,
    with the baseline READ FROM obs/attribution (the (W-1)*s all_gather
    charge on a bf16 gather of the same params over the same axis)."""
    from distributed_sigmoid_loss_tpu.obs.attribution import jaxpr_costs
    from distributed_sigmoid_loss_tpu.train import stage_scheme

    s = adaptive_setup
    mesh = s["mesh"]
    state = s["fresh_adaptive"]()

    def bf16_sync(params):
        return jax.tree.map(
            lambda t: jnp.mean(
                lax.all_gather(t.astype(jnp.bfloat16), "dcn").astype(
                    jnp.float32
                ),
                axis=0,
            ),
            params,
        )

    gathered = jax.shard_map(
        bf16_sync, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    )
    baseline = jaxpr_costs(jax.make_jaxpr(gathered)(state.params))[
        "comm_bytes_all_gather"
    ]
    n_params = sum(leaf_sizes(state.params))
    # Sanity: attribution's (W-1)*s charge at 2 B/param, W=2.
    assert baseline == pytest.approx(n_params * 2.0, rel=0.05)

    controller = BitController(leaf_sizes(state.params), n_dcn=2)
    controller.override_bandwidth(0.001)             # starve: narrowest rungs
    state = stage_scheme(state, controller.decide(), mesh)
    state, m = s["step_a"](state, jax.device_put(s["batch"], s["shard_a"]))
    assert float(m["dcn_wire_bytes"]) <= 0.25 * baseline, (
        float(m["dcn_wire_bytes"]),
        baseline,
    )
    assert np.isfinite(float(m["loss"]))


@pytest.mark.slow
def test_adaptive_composes_with_zero1_and_accum():
    """adaptive x zero1 x accum under shard_map: parity against the FIXED
    int8 compressed step at the same config — same builder, same accum
    microbatch chunking, so the sgd-delta oracle isolates exactly the
    adaptive switch (whose all-widest rungs are int8 for real tensors and a
    lossless keep-1 topk for scalars). The fixed step's own parity against
    the regular step is test_grad_compression's oracle."""
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_compressed_train_step,
        with_adaptive_compression,
        with_error_feedback,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig

    mesh = hybrid_mesh()
    model, batch = _tiny_model_and_batch()
    tx = optax.sgd(1e-2)
    cfg = LossConfig(variant="all_gather")
    step_a, shard_a = make_compressed_train_step(
        model, mesh, cfg, compression="adaptive", zero1=True, accum_steps=2
    )
    step_u, shard_u = make_compressed_train_step(
        model, mesh, cfg, compression="int8", zero1=True, accum_steps=2
    )

    def fresh():
        return create_train_state(
            jax.random.key(0), model, tx, batch, mesh, zero1=True
        )

    state_a = with_adaptive_compression(fresh(), mesh)
    state_u = with_error_feedback(fresh(), mesh)
    p0 = jax.tree.map(jnp.copy, state_u.params)
    state_a, ma = step_a(state_a, jax.device_put(batch, shard_a))
    state_u, mu = step_u(state_u, jax.device_put(batch, shard_u))
    np.testing.assert_allclose(
        float(ma["loss"]), float(mu["loss"]), rtol=1e-5
    )
    checked = 0
    for dc, du in zip(
        jax.tree.leaves(jax.tree.map(lambda a, b: a - b, state_a.params, p0)),
        jax.tree.leaves(jax.tree.map(lambda a, b: a - b, state_u.params, p0)),
    ):
        scale = float(jnp.max(jnp.abs(du)))
        if scale < 1e-8:
            continue
        assert float(jnp.max(jnp.abs(dc - du))) / scale < 0.02
        checked += 1
    assert checked, "all leaves skipped — the oracle compared nothing"


@pytest.mark.slow
def test_adaptive_composes_with_moe():
    """adaptive x MoE towers (experts replicated): finite and descending
    under scheme churn (controller re-staged every step)."""
    import dataclasses

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_compressed_train_step,
        stage_scheme,
        with_adaptive_compression,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig

    model, batch = _tiny_model_and_batch()
    cfg = dataclasses.replace(
        model.cfg,
        vision=dataclasses.replace(
            model.cfg.vision, moe_experts=2, moe_group_size=8
        ),
        text=dataclasses.replace(
            model.cfg.text, moe_experts=2, moe_num_selected=2,
            moe_group_size=16,
        ),
    )
    model = SigLIP(cfg)
    mesh = hybrid_mesh()
    step, shard = make_compressed_train_step(
        model, mesh, LossConfig(variant="all_gather"),
        compression="adaptive", moe_aux_weight=0.01,
    )
    state = with_adaptive_compression(
        create_train_state(
            jax.random.key(0), model, optax.sgd(1e-2), batch, mesh
        ),
        mesh,
    )
    controller = BitController(
        leaf_sizes(state.params), n_dcn=2, dcn_budget_mbps=0.05
    )
    b = jax.device_put(batch, shard)
    losses = []
    for _ in range(4):
        state, m = step(state, b)
        losses.append(float(m["loss"]))
        controller.observe(0.1, float(m["dcn_wire_bytes"]))
        state = stage_scheme(
            state,
            controller.decide(np.asarray(state.comp["ef_ratio"])),
            mesh,
        )
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    assert step._cache_size() == 1


@pytest.mark.slow
def test_adaptive_convergence_parity_sweep():
    """Loss-curve parity vs uncompressed over a 10-step sweep WITH the
    controller in the loop under a budget that forces narrow schemes — the
    in-repo half of the convergence oracle (the driver's color-retrieval run
    is the chip-side half)."""
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_compressed_train_step,
        make_train_step,
        stage_scheme,
        with_adaptive_compression,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig

    mesh = hybrid_mesh()
    model, batch = _tiny_model_and_batch()
    tx = optax.sgd(1e-2)
    cfg = LossConfig(variant="all_gather")
    step_a, shard_a = make_compressed_train_step(
        model, mesh, cfg, compression="adaptive"
    )
    step_u, shard_u = make_train_step(model, mesh, cfg)
    state_a = with_adaptive_compression(
        create_train_state(jax.random.key(0), model, tx, batch, mesh), mesh
    )
    state_u = create_train_state(jax.random.key(0), model, tx, batch, mesh)
    controller = BitController(leaf_sizes(state_a.params), n_dcn=2)
    controller.override_bandwidth(0.001)             # force narrow schemes
    ba, bu = jax.device_put(batch, shard_a), jax.device_put(batch, shard_u)
    la, lu = [], []
    for _ in range(10):
        state_a, ma = step_a(state_a, ba)
        state_u, mu = step_u(state_u, bu)
        la.append(float(ma["loss"]))
        lu.append(float(mu["loss"]))
        state_a = stage_scheme(
            state_a,
            controller.decide(np.asarray(state_a.comp["ef_ratio"])),
            mesh,
        )
    assert all(np.isfinite(la)), la
    assert la[-1] < la[0] and lu[-1] < lu[0], (la, lu)
    # EF keeps the starved trajectory TRACKING the uncompressed curve: at
    # the narrowest rungs (sign1 / keep-0.25% topk) a ~20% loss lag at step
    # 10 is the measured cost of ~100x less wire; what must NOT happen is a
    # stall (no descent) or a blow-up. Exact parity at int8 rungs is
    # test_adaptive_step_matches_uncompressed; a chip-side A/B would be the
    # long-horizon half of the oracle (none has been run).
    np.testing.assert_allclose(la[-1], lu[-1], rtol=0.25)
    assert la[-1] < lu[0], (la, lu)


@pytest.mark.slow
def test_budgeted_matches_or_beats_greedy_on_starved_sweep(adaptive_setup):
    """graftcodec's controller A/B, on the SAME compiled step and the same
    moderate starvation (a budget forcing real narrowing but not the floor —
    at the floor both policies collapse to the identical all-narrowest
    table and the A/B is vacuous): the budgeted policy must land within the
    byte budget greedy lands in while matching or beating its loss —
    spending reconstruction error on low-``gnorm^2*(1+ef_ratio)`` tensors
    must not lose to spending it on low-ef_ratio ones."""
    from distributed_sigmoid_loss_tpu.train import stage_scheme

    s = adaptive_setup

    def run(mode):
        state = s["fresh_adaptive"]()
        c = BitController(
            leaf_sizes(state.params), n_dcn=2, controller=mode
        )
        # bytes_allowed = 2.4 Mbps * 0.1 s / 8 = 30 kB — ~1/3 of the tiny
        # model's ~86 kB int8 egress, a mid-ladder working point.
        c.override_bandwidth(2.4)
        b = jax.device_put(s["batch"], s["shard_a"])
        losses, wire = [], 0.0
        for _ in range(10):
            scheme = c.decide(
                np.asarray(state.comp["ef_ratio"]),
                gnorm=np.asarray(state.comp["gnorm"]),
                gvar=np.asarray(state.comp["gvar"]),
            )
            state = stage_scheme(state, scheme, s["mesh"])
            state, m = s["step_a"](state, b)
            losses.append(float(m["loss"]))
            wire += float(m["dcn_wire_bytes"])
        return losses, wire, c

    lg, wg, cg = run("greedy")
    lb, wb, cb = run("budgeted")
    assert all(np.isfinite(lg)) and all(np.isfinite(lb)), (lg, lb)
    # Equal bytes: both descents stop at the same 30 kB budget, so the
    # cumulative wire may differ only by the one-rung stopping granularity.
    assert wb <= wg * 1.1, (wb, wg)
    # Match-or-beat at that budget (2% slack for CPU-order noise).
    assert lb[-1] <= lg[-1] * 1.02, (lb[-1], lg[-1])
    assert cb.mode == "budgeted" and cb.last_error_budget > 0
    # Same executable served both policies: scheme tables are operands.
    assert s["step_a"]._cache_size() == 1


# -------------------------------------------------- derived-state lifecycle


def test_checkpoint_strips_comp_like_ef(adaptive_setup):
    from distributed_sigmoid_loss_tpu.train.checkpoint import _strip_ef

    state = adaptive_setup["fresh_adaptive"]()
    assert state.ef is not None and state.comp is not None
    bare = _strip_ef(state)
    assert bare.ef is None and bare.comp is None


def test_validate_args_refusals():
    from distributed_sigmoid_loss_tpu.train.compressed_step import (
        validate_compressed_step_args,
    )

    kw = dict(
        accum_steps=1, accum_dtype=None, accum_negatives="local",
        pp_microbatches=0, zero1=False, moe_aux_weight=None,
        gradcache_embed_dtype=None, topk_frac=0.01,
        loss_variant="all_gather",
    )
    with pytest.raises(ValueError, match="error feedback"):
        validate_compressed_step_args(
            compression="adaptive", error_feedback=False, **kw
        )
    with pytest.raises(ValueError, match="pp_microbatches"):
        validate_compressed_step_args(
            compression="adaptive", error_feedback=True,
            **dict(kw, pp_microbatches=2),
        )
    with pytest.raises(ValueError, match="compression"):
        validate_compressed_step_args(
            compression="int5", error_feedback=True, **kw
        )


def test_adaptive_step_requires_comp_carry(adaptive_setup):
    s = adaptive_setup
    from distributed_sigmoid_loss_tpu.train import with_error_feedback

    state = with_error_feedback(s["fresh_plain"](), s["mesh"])
    with pytest.raises(ValueError, match="comp"):
        s["step_a"](state, jax.device_put(s["batch"], s["shard_a"]))


# ------------------------------------------------- graftlint dataflow rule


def test_ef_threaded_rule_registered():
    from distributed_sigmoid_loss_tpu import analysis
    from distributed_sigmoid_loss_tpu.analysis import shard_flow

    # graftshard (PR 17) appended jaxpr-gather-placement after this rule, so
    # the pin is membership in both catalogs, not last position.
    assert "jaxpr-ef-threaded" in shard_flow.SHARD_FLOW_RULES
    assert "jaxpr-ef-threaded" in analysis.JAXPR_RULES


def _ef_findings(fn, args, ef_indices):
    from distributed_sigmoid_loss_tpu.analysis.shard_flow import (
        audit_shard_flow,
    )

    closed = jax.make_jaxpr(fn)(*args)
    return [
        f for f in audit_shard_flow(closed, label="fix", ef_indices=ef_indices)
        if f.rule == "jaxpr-ef-threaded"
    ]


def test_ef_threaded_rule_falsified_on_bad_fixtures():
    g, e = jnp.ones((4,)), jnp.zeros((1, 4))

    @jax.jit
    def bad_passthrough(grad, ef):
        return grad + jnp.squeeze(ef, 0), ef

    @jax.jit
    def bad_rezeroed(grad, ef):
        return grad + jnp.squeeze(ef, 0), jnp.zeros_like(ef)

    @jax.jit
    def good(grad, ef):
        target = grad + jnp.squeeze(ef, 0)
        sent = jnp.round(target)
        return sent, (target - sent)[None]

    idx = ((1,), (1,))
    found = _ef_findings(bad_passthrough, (g, e), idx)
    assert len(found) == 1 and "un-updated" in found[0].detail
    found = _ef_findings(bad_rezeroed, (g, e), idx)
    assert len(found) == 1 and "dropped or re-zeroed" in found[0].detail
    assert _ef_findings(good, (g, e), idx) == []


def test_ef_threaded_rule_sees_through_shard_map():
    """The passthrough hidden INSIDE a jitted shard_map body — the positional
    recursion must follow it rather than go conservative."""
    mesh = hybrid_mesh(dcn=2, dp=1)
    g, e = jnp.ones((4,)), jnp.zeros((2, 4))

    def make(fix):
        def body(grad, ef):
            if fix == "pass":
                return grad + jnp.mean(ef, 0), ef
            target = grad + jnp.mean(ef, 0)
            sent = jnp.round(target)
            return sent, jnp.broadcast_to(target - sent, ef.shape)

        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P("dcn")),
                out_specs=(P(), P("dcn")), check_vma=False,
            )
        )

    idx = ((1,), (1,))
    found = _ef_findings(make("pass"), (g, e), idx)
    assert len(found) == 1 and "un-updated" in found[0].detail
    assert _ef_findings(make("good"), (g, e), idx) == []


@pytest.mark.slow
def test_step_config_jaxprs_arm_ef_indices():
    """Every EF config in the tier-1 sample (including the new adaptive one)
    traces with resolved ef_indices; the shipped steps stay green."""
    from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
        step_config_jaxprs,
    )
    from distributed_sigmoid_loss_tpu.analysis.shard_flow import (
        audit_shard_flow,
    )

    jaxprs = step_config_jaxprs(8)
    armed = {
        label: kw["ef_indices"]
        for label, (_, kw) in jaxprs.items()
        if "ef_indices" in kw
    }
    assert "compression=adaptive+error_feedback" in armed
    for label, (ins, outs) in armed.items():
        assert ins and outs, label
    label = "compression=adaptive+error_feedback"
    closed, kw = jaxprs[label]
    found = [
        f
        for f in audit_shard_flow(
            closed, label=label, ef_indices=kw["ef_indices"]
        )
        if f.rule == "jaxpr-ef-threaded"
    ]
    assert found == [], found


# ------------------------------------------- schema / config space / CLI --


def test_new_fields_registered_with_falsification():
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        validate_metrics,
    )

    line = {
        "dcn_wire_bytes": 2254.0, "bits_per_param": 0.21,
        "ef_residual_norm": 1.0, "compression_scheme_hist": [0, 0, 4, 0, 105],
        "dcn_bw_est_mbps": 12.5,
    }
    assert validate_metrics(line) == []
    assert validate_metrics({"dcn_wire_bytez": 1.0}) != []
    assert validate_metrics({**line, "scheme_hist": []}) != []


def test_config_space_adaptive_rows():
    from distributed_sigmoid_loss_tpu.analysis.config_space import (
        AXES,
        StepConfig,
        is_legal,
        tier1_sample,
        violations,
    )

    assert "adaptive" in AXES["compression"]
    assert is_legal(StepConfig(compression="adaptive", error_feedback=True))
    bad_no_ef = violations(StepConfig(compression="adaptive"))
    assert any(v.name == "adaptive-needs-error-feedback" for v in bad_no_ef)
    bad_pp = violations(
        StepConfig(compression="adaptive", error_feedback=True, pp=True)
    )
    assert any(v.name == "adaptive-excludes-pp" for v in bad_pp)
    assert "compression=adaptive+error_feedback" in tier1_sample()


def _run_cli(*argv, timeout=240):
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "distributed_sigmoid_loss_tpu", *argv],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=repo,
    )


def test_cli_adaptive_without_dcn_axis_exits_2():
    """The pinned refusal: --compression adaptive (the alias) without a dcn
    mesh axis is exit 2 with the real reason, not a trace-time crash."""
    proc = _run_cli(
        "train", "--cpu-devices", "8", "--tiny", "--steps", "1",
        "--batch", "16", "--compression", "adaptive",
    )
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-500:])
    assert "--dcn-slices >= 2" in proc.stderr


def test_cli_dcn_budget_without_adaptive_exits_2():
    proc = _run_cli(
        "train", "--cpu-devices", "8", "--tiny", "--steps", "1",
        "--batch", "16", "--dcn-slices", "2", "--grad-compression", "int8",
        "--dcn-budget-mbps", "50",
    )
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-500:])
    assert "--dcn-budget-mbps" in proc.stderr


@pytest.mark.parametrize("kw, msg", [
    # a dcn axis nothing compresses
    (dict(dcn_slices=2), "--dcn-slices without --grad-compression is a silent no-op"),
    # the compressed sync has no ring form
    (dict(dcn_slices=2, grad_compression="adaptive", variant="ring"),
     "--variant all_gather or unset"),
])
def test_train_conflict_predicate_pins_dcn_refusals(kw, msg):
    """The two refusals of `train`'s argument check that no CLI run above
    reaches: in process, on the predicate `cmd_train` exits 2 with."""
    import argparse

    from distributed_sigmoid_loss_tpu.cli import _train_config_conflicts

    base = dict(
        ep=1, moe_aux_weight=None, moe_experts=0, pp=1, pp_microbatches=0,
        accum=1, accum_bf16=False, accum_negatives="local",
        gradcache_bf16=False, loss_impl="fused", variant="all_gather",
        ring_overlap=False, zero1=False, update_sharding="",
        grad_compression="", use_pallas=False, loss_family="sigmoid",
        ema_decay=None, watchdog="warn", ckpt_dir="",
        topk_frac=0.01, topk_exact=False, dcn_slices=1,
        dcn_budget_mbps=None, controller=None, emu_dcn_mbps=None,
    )
    assert _train_config_conflicts(argparse.Namespace(**base)) is None
    got = _train_config_conflicts(argparse.Namespace(**{**base, **kw}))
    assert got and msg in got, got


@pytest.mark.slow
def test_cli_train_adaptive_smoke():
    """End to end through the CLI: the controller loop stages schemes between
    steps and every metrics line carries the adaptive wire accounting."""
    import json

    proc = _run_cli(
        "train", "--cpu-devices", "8", "--tiny", "--steps", "3",
        "--batch", "16", "--dcn-slices", "2", "--compression", "adaptive",
        "--dcn-budget-mbps", "50", timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = [
        json.loads(ln) for ln in proc.stdout.splitlines()
        if ln.startswith("{") and "step" in ln
    ]
    recs = [r for r in recs if "loss" in r]
    assert [r["step"] for r in recs] == [1, 2, 3]
    for r in recs:
        for field in (
            "dcn_wire_bytes", "bits_per_param", "ef_residual_norm",
            "compression_scheme_hist", "dcn_bw_est_mbps",
        ):
            assert field in r, (field, r)
        assert len(r["compression_scheme_hist"]) == N_SCHEMES
    # The 50 Mbps budget starves the (CPU-emulated) wire: the controller
    # must have narrowed at least one tensor off int8 by step 2.
    assert r["bits_per_param"] < recs[0]["bits_per_param"]
