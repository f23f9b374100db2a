"""graftshard: automatic cross-replica update sharding (PR 17).

Oracles:

- the ONE placement predicate (``shardable``) pins both modes' rules — zero1
  keeps the exact-divisibility layout (checkpoint compatibility), full shards
  every ``shape[0] >= W`` leaf with a padded ragged tail — and the derived
  helpers (spec, EF slot shape, shard-sized payload table) agree with it;
- sgd-delta parity: ``apply_sharded_update`` under full sharding produces the
  SAME updated params as the plain replicated update, for W in {2, 4, 8},
  including a ``dim % W != 0`` padded tensor and adafactor's factored state;
- the headline memory acceptance: at W=8 the measured at-rest optimizer bytes
  per replica drop >= 0.6*W vs the replicated state (compiler accounting via
  ``opt_mem_bytes_per_replica``);
- full-mode REGULAR step: losses track the replicated step, moments end up
  dp-sharded while published params stay at their model placements, and the
  deferred-capture wrapper never recompiles (``_cache_size() == 1``);
- full-mode COMPRESSED step: the int8+EF hop quantizes the reduce-scattered
  shard, so each shardable tensor's wire is 1/W of the unsharded figure
  (total ratio pinned), the EF residual is shard-local, and an adaptive
  scheme swap stays on one executable;
- zero1-era checkpoints restore onto a full-mode state (the layout-superset
  contract);
- the environment refusals the config-space table deliberately does NOT
  carry (full-requires-dp>1) exit 2 at the CLI with a clear message, and the
  zero1-era constraint rows vanished rather than multiplied.

Tiering: module is conftest-standard; the step-level oracles that compile
full train steps on the 8-device CPU mesh are ``slow``-marked (tier-1 runs
the placement/parity/memory pins; run the module unfiltered for the rest).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_sigmoid_loss_tpu.parallel.mesh import make_2d_mesh, make_mesh
from distributed_sigmoid_loss_tpu.parallel.update_shard import (
    apply_sharded_update,
    capture_shardings,
    ef_slot_shape,
    opt_mem_bytes_per_replica,
    padded_rows,
    psum_scatter_shard,
    resolve_update_sharding,
    shard_leaf_sizes,
    shardable,
    update_shard_spec,
)
from distributed_sigmoid_loss_tpu.train.train_step import TrainState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- the placement rule


def test_shardable_is_the_one_placement_rule():
    # zero1: historical exact divisibility — layouts stay checkpoint-stable.
    assert shardable((64, 4), 8, "zero1")
    assert not shardable((10, 4), 8, "zero1")   # 10 % 8 != 0
    assert not shardable((4,), 8, "zero1")      # fewer rows than replicas
    # full: permissive leading-dim rule, ragged tails pad.
    assert shardable((10, 4), 8, "full")
    assert shardable((8,), 8, "full")
    assert not shardable((4, 512), 8, "full")   # < one row per replica
    assert not shardable((), 8, "full")
    # off / trivial axis: nothing shards.
    assert not shardable((64, 4), 8, "off")
    assert not shardable((64, 4), 1, "full")

    assert padded_rows(10, 8) == 16 and padded_rows(16, 8) == 16
    assert update_shard_spec((10, 4), 8, "dp", "full") == P("dp")
    assert update_shard_spec((10, 4), 8, "dp", "zero1") == P()

    # EF slots: shard-local (padded rows / dcn slices leading) iff shardable.
    assert ef_slot_shape((10, 4), 2, 8, "full") == (2, 16, 4)
    assert ef_slot_shape((10, 4), 2, 8, "off") == (2, 10, 4)
    assert ef_slot_shape((3,), 2, 8, "full") == (2, 3)

    # Payload table the BitController sees under full: padded shard sizes.
    params = {"a": jnp.zeros((10, 4)), "b": jnp.zeros((16,)),
              "c": jnp.zeros(())}
    assert shard_leaf_sizes(params, 8) == [8, 2, 1]

    assert resolve_update_sharding("", zero1=True) == "zero1"
    assert resolve_update_sharding("full", zero1=False) == "full"
    with pytest.raises(ValueError, match="contradicts"):
        resolve_update_sharding("off", zero1=True)
    with pytest.raises(ValueError, match="must be one of"):
        resolve_update_sharding("bogus")


# ------------------------------------------------------ sgd-delta parity


def _parity_tree():
    rng = np.random.default_rng(11)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    # 9 % 2 = 9 % 4 = 9 % 8 = 1: padded at every tested W; (4, 16) is
    # un-shardable at W=8 (row-starved) but shards at 2 and 4; () never.
    params = {"emb": mk(16, 8), "ragged": mk(9, 6), "thin": mk(4, 16),
              "vec": mk(16), "scalar": mk()}
    grads = jax.tree.map(lambda p: mk(*p.shape), params)
    return params, grads


@pytest.mark.parametrize("w", [2, 4, 8])
@pytest.mark.parametrize("opt", ["sgd", "adafactor"])
def test_full_update_delta_matches_replicated(w, opt):
    """The correctness core: constraining the update path to shards must not
    change the math — same grads in, same params out, padded ragged leaf and
    factored adafactor stats included."""
    if opt == "sgd":
        tx = optax.sgd(1e-2)
    else:
        # min_dim small so the tiny leaves actually FACTOR (row/col stats).
        tx = optax.adafactor(learning_rate=1e-2, min_dim_size_to_factor=4)
    params, grads = _parity_tree()
    mesh = make_mesh(w)

    ref = TrainState.create(apply_fn=None, params=params, tx=tx)
    ref = jax.jit(lambda s, g: s.apply_gradients(grads=g))(ref, grads)

    state = TrainState.create(apply_fn=None, params=params, tx=tx)
    repl = NamedSharding(mesh, P())
    state = jax.device_put(state, jax.tree.map(lambda _: repl, state))
    shardings = capture_shardings(state.params)
    out = jax.jit(
        lambda s, g: apply_sharded_update(
            s, g, mesh=mesh, axis_name="dp", mode="full",
            param_shardings=shardings,
        )
    )(state, jax.device_put(grads, jax.tree.map(lambda _: repl, grads)))

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-7
        ),
        out.params, ref.params,
    )
    # The published params are back at their replicated at-rest placement...
    for leaf in jax.tree.leaves(out.params):
        assert leaf.sharding.spec == P(), leaf.sharding
    # ...while every EVENLY-divisible shardable moment leaf genuinely lives
    # on shards. Ragged leaves stay replicated in the constraint path: jax
    # 0.4.x cannot represent uneven shardings, with_sharding_constraint
    # silently degrades them (see the update_shard.py module docstring) —
    # their parity is asserted above, their wire sharding in the compressed
    # oracles below.
    for leaf in jax.tree.leaves(out.opt_state):
        if (hasattr(leaf, "shape") and shardable(leaf.shape, w, "full")
                and leaf.shape[0] % w == 0):
            assert leaf.sharding.spec == P("dp"), (leaf.shape, leaf.sharding)


def test_psum_scatter_shard_pads_and_sums():
    """The manual-region primitive: member i receives the SUM of padded row
    block i — the same rows update_shard_spec assigns it."""
    w = 8
    mesh = make_mesh(w)
    x = jnp.arange(9 * 2, dtype=jnp.float32).reshape(9, 2)

    from jax import shard_map

    fn = shard_map(
        lambda v: psum_scatter_shard(v, "dp", w),
        mesh=mesh, in_specs=(P(),), out_specs=P("dp"), check_vma=False,
    )
    out = np.asarray(jax.jit(fn)(x))
    padded = np.concatenate([np.asarray(x), np.zeros((7, 2), np.float32)])
    np.testing.assert_array_equal(out, padded * w)


# --------------------------------------------- the memory acceptance pin


def test_opt_memory_drops_at_least_point6_w_at_w8():
    """THE acceptance number: full update sharding at W=8 cuts the measured
    at-rest optimizer bytes per replica by >= 0.6*W (adam moments follow the
    shard spec; scalars replicate, which is why the bound is 0.6*W, not W)."""
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        SigLIPConfig,
        TrainConfig,
    )

    w = 8
    mesh = make_mesh(w)
    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    tx = make_optimizer(TrainConfig(warmup_steps=1, total_steps=10))
    rng = np.random.default_rng(3)
    batch = {
        "images": jnp.asarray(
            rng.standard_normal(
                (16, cfg.vision.image_size, cfg.vision.image_size, 3)
            ),
            jnp.float32,
        ),
        "tokens": jnp.asarray(
            rng.integers(0, cfg.text.vocab_size, (16, cfg.text.context_length)),
            jnp.int32,
        ),
    }
    mem = {}
    for mode in ("off", "full"):
        state = create_train_state(
            jax.random.key(0), model, tx, batch, mesh, update_sharding=mode
        )
        mem[mode] = opt_mem_bytes_per_replica(state.opt_state)
        assert mem[mode], mem
    ratio = mem["off"] / mem["full"]
    assert ratio >= 0.6 * w, mem


# ------------------------------------------------- record / schema fixtures


def test_metrics_line_fields_registered():
    """The two graftshard fields every `train` metrics line carries under
    --update-sharding are declared in the train-metrics schema, and a typo'd
    neighbour is not."""
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        validate_metrics,
    )

    good = {
        "loss": 1.0,
        "update_sharding": "full", "opt_mem_bytes_per_replica": 90872,
    }
    assert validate_metrics(good) == []
    assert validate_metrics(
        {**good, "opt_mem_bytes_per_rep1ica": 1}
    ) != []


# ------------------------------ CLI refusals + constraint-table hygiene


def _conflict(**kw):
    import argparse

    from distributed_sigmoid_loss_tpu.cli import _train_config_conflicts

    base = dict(
        ep=1, moe_aux_weight=None, moe_experts=0, pp=1, pp_microbatches=0,
        accum=1, accum_bf16=False, accum_negatives="local",
        gradcache_bf16=False, loss_impl="fused", variant="ring",
        ring_overlap=False, zero1=False, update_sharding="",
        grad_compression="", use_pallas=False, loss_family="sigmoid",
        ema_decay=None, watchdog="warn", ckpt_dir="",
        topk_frac=0.01, topk_exact=False, dcn_slices=1,
        dcn_budget_mbps=None,
    )
    base.update(kw)
    return _train_config_conflicts(argparse.Namespace(**base))


def test_train_conflict_predicate_pins_update_sharding_refusals():
    assert _conflict() is None
    assert _conflict(update_sharding="full") is None
    assert _conflict(zero1=True, update_sharding="zero1") is None  # alias agrees
    msg = _conflict(zero1=True, update_sharding="full")
    assert msg and "deprecated alias" in msg
    for mode in ("zero1", "full"):
        msg = _conflict(pp=2, update_sharding=mode)
        assert msg and "--update-sharding" in msg, (mode, msg)
    # The deprecated spelling hits the same refusal.
    assert _conflict(pp=2, zero1=True)


def test_zero1_constraint_rows_vanished_not_multiplied():
    """ONE mode-agnostic row replaces pp-excludes-zero1; no constraint

    mentions the legacy flag anymore, and full-requires-dp>1 is deliberately
    NOT a row (environment check — pinned by the exit-2 CLI test below)."""
    from distributed_sigmoid_loss_tpu.analysis import config_space as cs

    names = [c.name for c in cs.CONSTRAINTS]
    assert names.count("pp-excludes-update-sharding") == 1
    assert not any("zero1" in n for n in names), names
    assert not any("dp" in n for n in names), names
    assert "update_sharding" in cs.AXES
    assert cs.AXES["update_sharding"] == ("", "zero1", "full")


def _run_cli(args, timeout=300):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "distributed_sigmoid_loss_tpu", *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


@pytest.mark.slow
def test_cli_exit2_pins_for_update_sharding():
    """The refusals the constraint table can't express (mesh environment)
    plus the flag-contradiction — all exit 2 with actionable messages."""
    # full on a dp=1 mesh: the reduce-scatter would be a no-op rename.
    proc = _run_cli(
        ["train", "--cpu-devices", "1", "--tiny", "--steps", "1",
         "--batch", "4", "--update-sharding", "full"]
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "data-parallel axis of size > 1" in proc.stderr
    # pp conflict and the alias contradiction refuse before device bring-up.
    proc = _run_cli(
        ["train", "--cpu-devices", "8", "--tiny", "--steps", "1",
         "--batch", "16", "--pp", "2", "--update-sharding", "full"]
    )
    assert proc.returncode == 2
    assert "--update-sharding full is not supported" in proc.stderr
    proc = _run_cli(
        ["train", "--cpu-devices", "8", "--tiny", "--steps", "1",
         "--batch", "16", "--zero1", "--update-sharding", "full"]
    )
    assert proc.returncode == 2
    assert "deprecated alias" in proc.stderr


@pytest.mark.slow
def test_cli_train_full_emits_placement_metrics():
    """An end-to-end full-mode run: metrics lines carry the mode + the
    measured opt bytes (obs/metrics_schema.py fields)."""
    proc = _run_cli(
        ["train", "--cpu-devices", "8", "--tiny", "--steps", "2",
         "--batch", "16", "--update-sharding", "full"]
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    assert lines and all(
        l["update_sharding"] == "full" for l in lines if "loss" in l
    )
    assert all(
        l["opt_mem_bytes_per_replica"] > 0 for l in lines if "loss" in l
    )


# ------------------------------------------- full-mode regular step oracles


def _tiny_setup(mesh, update_sharding, steps=3, batch=16):
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TrainConfig,
    )
    from distributed_sigmoid_loss_tpu.data.synthetic import SyntheticImageText

    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    tx = make_optimizer(TrainConfig(warmup_steps=1, total_steps=10))
    first = next(iter(SyntheticImageText(cfg, batch)))
    state = create_train_state(
        jax.random.key(0), model, tx, first, mesh,
        update_sharding=update_sharding,
    )
    step, shardings = make_train_step(
        model, mesh, LossConfig(variant="ring"),
        update_sharding=update_sharding,
    )
    losses = []
    batch_dev = jax.device_put(first, shardings)
    for _ in range(steps):
        state, metrics = step(state, batch_dev)
        losses.append(float(metrics["loss"]))
    return state, losses, step


@pytest.mark.slow
def test_full_step_numerics_match_replicated():
    mesh = make_mesh(8)
    state_f, losses_f, step_f = _tiny_setup(mesh, "full")
    state_r, losses_r, _ = _tiny_setup(mesh, "off")
    np.testing.assert_allclose(losses_f, losses_r, rtol=1e-6)
    # Same honest bound as the zero1 oracle: repartitioning reorders the f32
    # reductions; adam amplifies near-zero grads. Loss match is the tight pin.
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=1e-4
        ),
        state_f.params, state_r.params,
    )
    # Deferred-capture wrapper compiled exactly once over the 3 steps.
    assert step_f._cache_size() == 1


@pytest.mark.slow
def test_full_step_moments_sharded_params_published():
    mesh = make_mesh(8)
    state, _, _ = _tiny_setup(mesh, "full", steps=1)
    sharded = unsharded = 0
    for leaf in jax.tree.leaves(state.opt_state):
        if not hasattr(leaf, "sharding"):
            continue
        if shardable(leaf.shape, 8, "full"):
            assert leaf.sharding.spec == P("dp"), (leaf.shape, leaf.sharding)
            sharded += 1
        else:
            unsharded += 1
    assert sharded > 0 and unsharded > 0
    # Published params are back at their model placements (no dp factor on a
    # pure-dp mesh) — the all-gather really ran.
    for leaf in jax.tree.leaves(state.params):
        assert all(e != "dp" for e in tuple(leaf.sharding.spec)), (
            leaf.sharding
        )


@pytest.mark.slow
def test_zero1_checkpoint_restores_onto_full_state(tmp_path):
    """Layout-superset contract: a zero1-era checkpoint restores by value
    onto a full-mode target (orbax reshards into the target's placements)."""
    from distributed_sigmoid_loss_tpu.train import (
        restore_checkpoint,
        save_checkpoint,
    )

    mesh = make_mesh(8)
    state_z, _, _ = _tiny_setup(mesh, "zero1", steps=1)
    path = str(tmp_path / "ck")
    save_checkpoint(path, state_z)
    target, _, _ = _tiny_setup(mesh, "full", steps=1)
    restored = restore_checkpoint(path, target)
    for a, b in ((state_z.params, restored.params),
                 (state_z.opt_state, restored.opt_state)):
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)
            ),
            a, b,
        )
    # ...and the restored moments live at the FULL placement, not zero1's.
    big = [l for l in jax.tree.leaves(restored.opt_state)
           if hasattr(l, "shape") and shardable(l.shape, 8, "full")]
    assert big and all(l.sharding.spec == P("dp") for l in big)


# --------------------------------------------- compressed shard wire oracles


@pytest.fixture(scope="module")
def compressed_shard_setup():
    """One shared compile of the int8+EF steps (off vs full) plus the
    adaptive full step on the (2, 4) hybrid mesh."""
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_compressed_train_step,
        with_adaptive_compression,
        with_error_feedback,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
    )

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dcn", "dp"))
    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    rng = np.random.default_rng(7)
    batch = {
        "images": jnp.asarray(
            rng.standard_normal(
                (16, cfg.vision.image_size, cfg.vision.image_size, 3)
            ),
            jnp.float32,
        ),
        "tokens": jnp.asarray(
            rng.integers(0, cfg.text.vocab_size, (16, cfg.text.context_length)),
            jnp.int32,
        ),
    }
    tx = optax.sgd(1e-2)
    loss_cfg = LossConfig(variant="all_gather")
    steps = {}
    for mode in ("off", "full"):
        steps[mode] = make_compressed_train_step(
            model, mesh, loss_cfg, update_sharding=mode
        )
    step_ad = make_compressed_train_step(
        model, mesh, loss_cfg, compression="adaptive", update_sharding="full"
    )

    def fresh(mode, adaptive=False):
        st = create_train_state(
            jax.random.key(0), model, tx, batch, mesh, update_sharding=mode
        )
        if adaptive:
            return with_adaptive_compression(
                st, mesh, update_sharding=mode
            )
        return with_error_feedback(st, mesh, update_sharding=mode)

    return {"mesh": mesh, "batch": batch, "steps": steps,
            "step_ad": step_ad, "fresh": fresh}


@pytest.mark.slow
def test_compressed_shard_wire_is_one_over_w(compressed_shard_setup):
    """The wire acceptance: compressing the reduce-scattered shard drops the
    DCN payload of every SHARDABLE tensor to exactly 1/W of the unsharded
    per-tensor figure; the total only trails by the replicated scalars, so
    at W=4 the ratio lands in (0.25, 0.30). Losses are identical — the
    decompressed mean is the same mean."""
    s = compressed_shard_setup
    w = 4
    wire = {}
    loss = {}
    for mode in ("off", "full"):
        step, sh = s["steps"][mode]
        state, m = step(s["fresh"](mode), jax.device_put(s["batch"], sh))
        wire[mode] = float(m["dcn_wire_bytes"])
        loss[mode] = float(m["loss"])
        # Shard-local EF under full: the residual carries a dp factor.
        if mode == "full":
            assert any(
                "dp" in tuple(l.sharding.spec)
                for l in jax.tree.leaves(state.ef)
            )
    np.testing.assert_allclose(loss["full"], loss["off"], rtol=1e-6)
    ratio = wire["full"] / wire["off"]
    assert 1.0 / w <= ratio < 0.30, wire


@pytest.mark.slow
def test_adaptive_scheme_swap_on_shards_stays_compiled(compressed_shard_setup):
    """jit cache 1 across a staged scheme swap with the shard-sized payload
    table — the no-recompile acceptance property under full sharding."""
    from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
        BitController,
    )
    from distributed_sigmoid_loss_tpu.train import stage_scheme

    s = compressed_shard_setup
    step, sh = s["step_ad"]
    batch = jax.device_put(s["batch"], sh)
    state = s["fresh"]("full", adaptive=True)
    controller = BitController(
        shard_leaf_sizes(state.params, 4), n_dcn=2
    )
    state, m1 = step(state, batch)
    assert np.isfinite(float(m1["loss"]))
    controller.override_bandwidth(0.001)
    scheme = controller.decide(np.asarray(state.comp["ef_ratio"]))
    state = stage_scheme(state, scheme, s["mesh"])
    state, m2 = step(state, batch)
    assert float(m2["dcn_wire_bytes"]) < float(m1["dcn_wire_bytes"])
    assert np.isfinite(float(m2["loss"]))
    assert step._cache_size() == 1
