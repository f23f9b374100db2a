"""graftcodec: learned compression rung + error-budgeted bit controller.

Oracles, in the adaptive-suite style (test_adaptive_compression):

- the DCT cold-start codec is orthonormal (dec is enc's exact least-squares
  inverse on the latent subspace) and the group split is static per shape;
- :class:`CodecTrainer` is deterministic, warmup-gated, poison-safe, and its
  closed-form eigh recovers a planted 16-dim block subspace (the PCA-equals-
  linear-AE identity the module banks on), beating the DCT prior on data the
  prior does not fit;
- the learned rung inside ``adaptive_axis_mean`` reconstructs a trained-
  subspace mean to int8-latent precision, pins its wire bytes to the payload
  table, emits the codec-training stats (``blockmoment``,
  ``codec_recon_err``), and codec-WEIGHT swaps are operand value changes
  (``_cache_size() == 1`` — the graftcodec no-recompile acceptance pin);
- the budgeted controller spends narrowing where gradients can afford it
  (low ``gnorm^2 * (1+ef_ratio)`` weight first), gates the learned rung
  behind ``learned=True``, and exposes ``mode`` / ``last_error_budget``;
- the full learned STEP (``compression="learned"``) tracks the uncompressed
  step over a 10-step sweep with the CodecTrainer retraining online (codec
  re-staged every round, jit cache stays at 1) while the scheme hist shows
  rung 6 engaged;
- the CLI refuses the new knobs where they would be silent no-ops
  (``--controller`` without an adaptive family, ``--emu-dcn-mbps`` without a
  dcn mesh axis), exit 2 with the real reason.

Tiering: the step-level sweep compiles the full (2, 4) hybrid step — slow-
marked; everything else is numpy/small-shard_map and stays standard.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
    CODEC_BLOCK,
    CODEC_GROUPS,
    CODEC_LATENT,
    N_SCHEMES,
    SCHEME_INT8,
    SCHEME_LEARNED,
    BitController,
    CodecTrainer,
    adaptive_axis_mean,
    codec_group,
    default_codec,
    leaf_sizes,
    payload_bytes_table,
)
from distributed_sigmoid_loss_tpu.parallel.compression import (
    init_error_feedback,
)


def hybrid_mesh(dcn=2, dp=4):
    devs = np.array(jax.devices()[: dcn * dp]).reshape(dcn, dp)
    return Mesh(devs, ("dcn", "dp"))


def _planted_subspace(rng):
    """An orthonormal 16-row block basis W (L, B) that is NOT the DCT."""
    q, _ = np.linalg.qr(rng.standard_normal((CODEC_BLOCK, CODEC_BLOCK)))
    return q[:, :CODEC_LATENT].T.astype(np.float32)


# ----------------------------------------------------------------- codec --


def test_default_codec_shapes_and_orthonormality():
    c = default_codec()
    assert c["enc"].shape == (CODEC_GROUPS, CODEC_BLOCK, CODEC_LATENT)
    assert c["dec"].shape == (CODEC_GROUPS, CODEC_LATENT, CODEC_BLOCK)
    for g in range(CODEC_GROUPS):
        # Orthonormal DCT rows: dec @ enc == I on the latent subspace, so
        # decode(encode(x)) is the exact projection of x onto the prior.
        np.testing.assert_allclose(
            c["dec"][g] @ c["enc"][g], np.eye(CODEC_LATENT), atol=1e-5
        )


def test_codec_group_static_split():
    assert codec_group((16, 8)) == 0
    assert codec_group((4, 4, 4)) == 0
    assert codec_group((50,)) == 1
    assert codec_group(()) == 1


def test_codec_trainer_warmup_determinism_and_poison():
    rng = np.random.default_rng(0)
    w = _planted_subspace(rng)
    moment = np.stack([w.T @ w] * CODEC_GROUPS)      # (G, B, B), rank L
    a, b = CodecTrainer(), CodecTrainer()
    # Round 1 < warmup_rounds=2: the DCT prior survives one noisy moment.
    c1 = a.update(moment)
    np.testing.assert_array_equal(c1["enc"], default_codec()["enc"])
    # Round 2: the eigh re-solve replaces the prior.
    c2 = a.update(moment)
    assert not np.allclose(c2["enc"], default_codec()["enc"])
    assert a.rounds == 2
    # Deterministic: an identically-fed twin lands on bit-equal weights.
    b.update(moment)
    np.testing.assert_array_equal(b.update(moment)["enc"], c2["enc"])
    # Poisoned rounds are skipped wholesale (no EWMA fold, no round count).
    c3 = a.update(np.full_like(moment, np.nan))
    assert a.rounds == 2
    np.testing.assert_array_equal(c3["enc"], c2["enc"])
    with pytest.raises(ValueError, match="blockmoment"):
        a.update(np.zeros((2, 2)))


def test_codec_trainer_recovers_planted_subspace():
    """The PCA identity: blocks drawn from a 16-dim subspace give a trained
    codec that reconstructs them near-exactly, while the DCT cold start
    (built for a smoothness prior this basis deliberately violates) leaves
    a large residual."""
    rng = np.random.default_rng(1)
    w = _planted_subspace(rng)
    z = rng.standard_normal((256, CODEC_LATENT)).astype(np.float32)
    blocks = z @ w                                   # (256, B) in span(W)
    moment = np.stack([blocks.T @ blocks / len(blocks)] * CODEC_GROUPS)
    tr = CodecTrainer()
    tr.update(moment)
    codec = tr.update(moment)

    def recon_err(c):
        out = (blocks @ c["enc"][0]) @ c["dec"][0]
        return float(
            np.linalg.norm(out - blocks) / np.linalg.norm(blocks)
        )

    trained, cold = recon_err(codec), recon_err(default_codec())
    assert trained < 1e-4, trained                   # subspace recovered
    assert cold > 0.5, cold                          # the prior can't fit it
    # dec stays the least-squares inverse after retraining too.
    np.testing.assert_allclose(
        codec["dec"][0] @ codec["enc"][0], np.eye(CODEC_LATENT), atol=1e-5
    )


# -------------------------------------------- learned rung in the manual --


def test_learned_mean_trained_codec_wire_and_no_recompile():
    """Rung 6 end to end inside shard_map: a trained codec reconstructs the
    subspace mean to int8-latent precision, wire bytes pin to the payload
    table, the codec-training stats come back pmean'd, and swapping codec
    WEIGHTS (trained vs cold) is a value change — one compiled program."""
    mesh = hybrid_mesh()
    rng = np.random.default_rng(2)
    w = _planted_subspace(rng)
    # "a" (16, 8): 2 blocks/slice in span(W); "b" (50,): int8 control.
    z = rng.standard_normal((2, 2, CODEC_LATENT)).astype(np.float32)
    a = (z @ w).reshape(2, 16, 8)
    tree = {
        "a": jnp.asarray(a),
        "b": jnp.asarray(rng.standard_normal((2, 50)), jnp.float32),
    }
    ef = init_error_feedback(
        {"a": jnp.zeros((16, 8)), "b": jnp.zeros((50,))}, 2
    )
    scheme = jnp.asarray([SCHEME_LEARNED, SCHEME_INT8], jnp.int32)
    blocks = (z @ w).reshape(4, CODEC_BLOCK)
    moment0 = blocks.T @ blocks / len(blocks)
    tr = CodecTrainer()
    tr.update(np.stack([moment0, np.eye(CODEC_BLOCK, dtype=np.float32)]))
    trained = tr.update(
        np.stack([moment0, np.eye(CODEC_BLOCK, dtype=np.float32)])
    )

    def body(t, e, s, codec):
        local = jax.tree.map(lambda x: jnp.squeeze(x, 0), t)
        return adaptive_axis_mean(local, "dcn", e, s, codec=codec)

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("dcn"), P("dcn"), P(), P()),
            out_specs=(P(), P("dcn"), P(), P()),
            check_vma=False,
        )
    )
    codec_dev = {k: jnp.asarray(v) for k, v in trained.items()}
    mean, _, stats, wire = fn(tree, ef, scheme, codec_dev)
    exact = jnp.mean(tree["a"], axis=0)
    rel = float(
        jnp.max(jnp.abs(mean["a"] - exact)) / jnp.max(jnp.abs(exact))
    )
    assert rel < 0.05, rel                           # int8-latent precision
    # Wire pin: learned(128) = 16*2+4 = 36, int8(50) = 54, (n-1) = 1.
    assert int(wire) == int(
        payload_bytes_table(128)[SCHEME_LEARNED]
        + payload_bytes_table(50)[SCHEME_INT8]
    ) == 90
    # Codec-training stats: pmean'd moment + live recon error (> 0: the
    # int8 latent quantization is lossy even on the exact subspace).
    assert stats["blockmoment"].shape == (
        CODEC_GROUPS, CODEC_BLOCK, CODEC_BLOCK,
    )
    assert float(jnp.sum(jnp.abs(stats["blockmoment"][0]))) > 0
    assert 0 < float(stats["codec_recon_err"]) < 0.05
    # Weight swap = operand value change: same executable serves both.
    cold = {k: jnp.asarray(v) for k, v in default_codec().items()}
    fn(tree, ef, scheme, cold)
    assert fn._cache_size() == 1


# ---------------------------------------------------- budgeted controller --


def test_budgeted_narrows_where_gradients_afford_it():
    """Two same-size tensors, budget forcing exactly one narrowing: greedy's
    tie-break narrows index 0; budgeted protects the high-gnorm tensor and
    narrows the weak one instead — same bytes, error spent differently."""
    sizes = [1000, 1000]
    gnorm = np.asarray([10.0, 0.1])
    ef = np.zeros(2)
    # int8 egress = 2 * 1004 B (n_dcn=2); allow slightly less.
    budget_mbps = (2000 * 8.0 / 0.1) / 1e6

    def run(mode):
        c = BitController(sizes, n_dcn=2, controller=mode)
        c.dcn_budget_mbps = budget_mbps
        return c, c.decide(ef, gnorm=gnorm)

    cg, sg = run("greedy")
    cb, sb = run("budgeted")
    assert cg.mode == "greedy" and cb.mode == "budgeted"
    assert sg[0] != SCHEME_INT8 and sg[1] == SCHEME_INT8
    assert sb[0] == SCHEME_INT8 and sb[1] != SCHEME_INT8
    # Equal bytes: symmetric sizes make the two policies' egress identical.
    assert cg._egress(np.asarray([1, 0])) == cb._egress(np.asarray([0, 1]))
    # The spent error budget is the distortion-weighted mean — higher when
    # the high-gnorm tensor is the one narrowed.
    assert 0 < cb.last_error_budget < cg.last_error_budget


def test_budgeted_degrades_to_uniform_weights_without_stats():
    c = BitController([100, 200], n_dcn=2, controller="budgeted",
                      dcn_budget_mbps=0.005)
    first = c.decide()                               # no stats yet: safe
    assert first.dtype == np.int32 and first.shape == (2,)
    assert np.isfinite(c.last_error_budget)


def test_learned_rung_gated_by_controller_flag():
    size = 1000
    # Budget between learned (260 B) and int4 (504 B) egress at n_dcn=2:
    # with the rung allowed the descent stops ON learned; without it the
    # ladder skips straight past to sign1.
    budget_mbps = (300 * 8.0 / 0.1) / 1e6
    on = BitController([size], n_dcn=2, controller="budgeted", learned=True,
                       dcn_budget_mbps=budget_mbps)
    off = BitController([size], n_dcn=2, controller="budgeted",
                        dcn_budget_mbps=budget_mbps)
    assert on.decide()[0] == SCHEME_LEARNED
    assert off.decide()[0] != SCHEME_LEARNED
    assert SCHEME_LEARNED not in off.ladders
    # Starved to the floor, even learned=True leaves the rung behind: the
    # narrowest format wins (the controller never pays 260 B for sentiment).
    on.dcn_budget_mbps = 1e-9
    assert on.decide()[0] != SCHEME_LEARNED


# ----------------------------------------------------- the full step (slow)


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
def learned_setup():
    """One shared build of the learned + uncompressed steps on a (2, 4)
    mesh — the compile dominates; states are rebuilt per test."""
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
    step_l, shard_l = make_compressed_train_step(
        model, mesh, cfg, compression="learned"
    )
    step_u, shard_u = make_train_step(model, mesh, cfg)

    def fresh_learned():
        st = create_train_state(jax.random.key(0), model, tx, batch, mesh)
        return with_adaptive_compression(st, mesh, learned=True)

    def fresh_plain():
        return create_train_state(jax.random.key(0), model, tx, batch, mesh)

    return {
        "mesh": mesh, "model": model, "batch": batch,
        "step_l": step_l, "step_u": step_u,
        "shard_l": shard_l, "shard_u": shard_u,
        "fresh_learned": fresh_learned, "fresh_plain": fresh_plain,
    }


@pytest.mark.slow
def test_learned_step_tracks_uncompressed_with_online_retraining(
    learned_setup,
):
    """The graftcodec acceptance sweep: matrices pinned to rung 6, vectors
    on int8, the CodecTrainer retraining (and re-staging) the codec every
    round. decode∘encode + EF telescoping must TRACK the uncompressed curve
    within the starved-sweep tolerance, the scheme hist must show rung 6,
    and ten codec-weight swaps must leave the jit cache at one entry."""
    from distributed_sigmoid_loss_tpu.train import stage_codec, stage_scheme

    s = learned_setup
    mesh = s["mesh"]
    state_l, state_u = s["fresh_learned"](), s["fresh_plain"]()
    # Group-0 matrices ride the learned rung; the vector/scalar tail stays
    # int8 (its blocks are mostly zero-pad — rung 6 there is all overhead).
    scheme = np.asarray(
        [
            SCHEME_LEARNED if p.ndim >= 2 else SCHEME_INT8
            for p in jax.tree.leaves(state_l.params)
        ],
        np.int32,
    )
    state_l = stage_scheme(state_l, scheme, mesh)
    trainer = CodecTrainer()
    bl, bu = (
        jax.device_put(s["batch"], s["shard_l"]),
        jax.device_put(s["batch"], s["shard_u"]),
    )
    ll, lu, hists = [], [], []
    for _ in range(10):
        state_l, ml = s["step_l"](state_l, bl)
        state_u, mu = s["step_u"](state_u, bu)
        ll.append(float(ml["loss"]))
        lu.append(float(mu["loss"]))
        hists.append(np.asarray(ml["compression_scheme_hist"]))
        assert float(ml["codec_recon_err"]) >= 0.0
        new_codec = trainer.update(np.asarray(state_l.comp["blockmoment"]))
        if trainer.rounds >= trainer.warmup_rounds:
            state_l = stage_codec(state_l, new_codec, mesh)
    assert all(np.isfinite(ll)), ll
    assert ll[-1] < ll[0] and lu[-1] < lu[0], (ll, lu)
    # Rung 6 engaged, every round, for every matrix.
    n_matrices = int(np.sum(scheme == SCHEME_LEARNED))
    assert n_matrices > 0
    for h in hists:
        assert h.shape == (N_SCHEMES,) and h[SCHEME_LEARNED] == n_matrices
    # The ~16x rung costs descent speed, not convergence: the starved-sweep
    # tolerance (test_adaptive_convergence_parity_sweep's) applies.
    np.testing.assert_allclose(ll[-1], lu[-1], rtol=0.25)
    assert ll[-1] < lu[0], (ll, lu)
    # Eight stage_codec calls later: still ONE compiled program.
    assert s["step_l"]._cache_size() == 1


@pytest.mark.slow
def test_learned_step_requires_codec_carry(learned_setup):
    from distributed_sigmoid_loss_tpu.train import with_adaptive_compression

    s = learned_setup
    state = with_adaptive_compression(s["fresh_plain"](), s["mesh"])
    with pytest.raises(ValueError, match="codec"):
        s["step_l"](state, jax.device_put(s["batch"], s["shard_l"]))


# ------------------------------------------------------------ CLI refusals


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


def test_cli_controller_without_adaptive_exits_2():
    proc = _run_cli(
        "train", "--cpu-devices", "8", "--tiny", "--steps", "1",
        "--batch", "16", "--dcn-slices", "2", "--grad-compression", "int8",
        "--controller", "budgeted",
    )
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-500:])
    assert "--controller" in proc.stderr and "silent no-op" in proc.stderr


def test_cli_emu_without_dcn_axis_exits_2():
    proc = _run_cli(
        "train", "--cpu-devices", "8", "--tiny", "--steps", "1",
        "--batch", "16", "--emu-dcn-mbps", "100",
    )
    assert proc.returncode == 2, (proc.returncode, proc.stderr[-500:])
    assert "--emu-dcn-mbps" in proc.stderr
    assert "--dcn-slices >= 2" in proc.stderr
