"""int8 TRAINING track (ops/quant.py STE + the towers' quant_train mode).

The straight-through estimator's whole contract is two exactness claims, both
pinned here at the op level:

- forward is BIT-IDENTICAL to the inference int8 dot (``int8_dot_general``) —
  the MXU program is the same one the PTQ serving path runs;
- backward EQUALS the unquantized ``lax.dot_general`` VJP exactly — not
  approximately: the custom_vjp replays the full-precision operands, so any
  difference is a wiring bug, not numerics.

Above the op: the mode plumbing (config → towers → train step), the guard
asymmetry (``quant`` rejected in trainable contexts, ``quant_train``
accepted), a short training run with finite decreasing loss, and bitwise
determinism of the quantized step under shard_map. Heavier compositions (pp,
compressed DCN sync) and the convergence-parity oracle live in
tests/test_quant_train_convergence.py (slow tier).

No reference analogue (the reference has no model layer); this is the
TPU-first route to the >bf16-roofline perf target ("Why an int8
training track").
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.ops.quant import (
    int8_dot_general,
    int8_dot_general_ste,
    int8_expert_matmul,
    int8_expert_matmul_ste,
)
from distributed_sigmoid_loss_tpu.utils.config import (
    SigLIPConfig,
    tower_quant_mode,
)

DENSE_DIMS = (((1,), (0,)), ((), ()))


def _quant_train_cfg(cfg):
    return dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, quant_train="int8"),
        text=dataclasses.replace(cfg.text, quant_train="int8"),
    )


def _quant_cfg(cfg):
    return dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, quant="int8"),
        text=dataclasses.replace(cfg.text, quant="int8"),
    )


# ---------------------------------------------------------------------------
# Op-level STE exactness
# ---------------------------------------------------------------------------


def test_ste_forward_bit_identical_to_inference_dot():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.05, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(int8_dot_general_ste(x, w, DENSE_DIMS)),
        np.asarray(int8_dot_general(x, w, DENSE_DIMS)),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ste_backward_equals_unquantized_vjp_exactly(dtype):
    """THE STE contract: for the same cotangent, the backward is bitwise the
    gradient the unquantized layer would have produced."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 32)), dtype)
    w = jnp.asarray(rng.standard_normal((32, 16)) * 0.05, dtype)
    out, vjp_ste = jax.vjp(
        lambda l, r: int8_dot_general_ste(l, r, DENSE_DIMS), x, w
    )
    _, vjp_ref = jax.vjp(lambda l, r: lax.dot_general(l, r, DENSE_DIMS), x, w)
    g = jnp.asarray(rng.standard_normal(out.shape), out.dtype)
    for got, want in zip(vjp_ste(g), vjp_ref(g)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ste_non_dense_pattern_falls_through_with_exact_grads():
    """Batched (non-Dense) patterns fall through unquantized in the forward —
    and the STE backward is then simply the true VJP of that same dot."""
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 16, 8)), jnp.float32)
    dims = (((2,), (1,)), ((0,), (0,)))
    out, vjp_ste = jax.vjp(lambda l, r: int8_dot_general_ste(l, r, dims), a, b)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(lax.dot_general(a, b, dims))
    )
    g = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
    _, vjp_ref = jax.vjp(lambda l, r: lax.dot_general(l, r, dims), a, b)
    for got, want in zip(vjp_ste(g), vjp_ref(g)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ste_expert_matmul_forward_identical_backward_exact():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 3, 4, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 8, 5)) * 0.05, jnp.float32)
    out, vjp_ste = jax.vjp(
        lambda a, b: int8_expert_matmul_ste(a, b, jnp.float32), x, w
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(int8_expert_matmul(x, w, jnp.float32))
    )
    g = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
    _, vjp_ref = jax.vjp(
        lambda a, b: lax.dot_general(
            a, b, (((3,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ),
        x, w,
    )
    for got, want in zip(vjp_ste(g), vjp_ref(g)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mlp_ste_grads_track_unquantized_direction():
    """Module-level sanity: an Mlp with the STE dot produces gradients
    directionally aligned with the unquantized Mlp at the same params — the
    forwards differ by int8 noise, so exact equality is NOT expected here
    (only per-op, for a shared cotangent)."""
    from distributed_sigmoid_loss_tpu.models.transformer import Mlp

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    plain = Mlp(32, 2, jnp.float32)
    ste = Mlp(32, 2, jnp.float32, quant="int8_ste")
    params = plain.init(jax.random.key(0), x)["params"]

    def loss(mod, p):
        return jnp.sum(mod.apply({"params": p}, x).astype(jnp.float32) ** 2)

    g_plain = jax.grad(lambda p: loss(plain, p))(params)
    g_ste = jax.grad(lambda p: loss(ste, p))(params)
    a = np.concatenate([np.asarray(l).ravel() for l in jax.tree.leaves(g_plain)])
    b = np.concatenate([np.asarray(l).ravel() for l in jax.tree.leaves(g_ste)])
    cos = np.dot(a, b) / (np.linalg.norm(a) * np.linalg.norm(b))
    assert cos > 0.99, cos


# ---------------------------------------------------------------------------
# Mode plumbing + guards
# ---------------------------------------------------------------------------


def test_tower_quant_mode_resolution_and_exclusivity():
    cfg = SigLIPConfig.tiny_test()
    assert tower_quant_mode(cfg.vision) == ""
    assert tower_quant_mode(_quant_cfg(cfg).vision) == "int8"
    assert tower_quant_mode(_quant_train_cfg(cfg).text) == "int8_ste"
    both = dataclasses.replace(cfg.vision, quant="int8", quant_train="int8")
    with pytest.raises(ValueError, match="mutually exclusive"):
        tower_quant_mode(both)


def test_quant_train_forward_bit_identical_to_inference_quant_forward():
    """A quant_train tower's FORWARD is the inference-int8 tower's forward,
    bit for bit (the STE only changes the backward) — so the trained model's
    deployment story is exact: serving with quant='int8' replays training's
    forward numerics."""
    cfg = SigLIPConfig.tiny_test()
    key = jax.random.key(0)
    images = jax.random.normal(
        key, (4, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32
    )
    tokens = jax.random.randint(
        key, (4, cfg.text.context_length), 0, cfg.text.vocab_size, jnp.int32
    )
    params = SigLIP(cfg).init(key, images, tokens)["params"]
    zi_q, zt_q, _ = SigLIP(_quant_cfg(cfg)).apply(
        {"params": params}, images, tokens
    )
    zi_t, zt_t, _ = SigLIP(_quant_train_cfg(cfg)).apply(
        {"params": params}, images, tokens
    )
    np.testing.assert_array_equal(np.asarray(zi_q), np.asarray(zi_t))
    np.testing.assert_array_equal(np.asarray(zt_q), np.asarray(zt_t))


def test_train_steps_accept_quant_train_reject_inference_quant():
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_2d_mesh, make_mesh
    from distributed_sigmoid_loss_tpu.train import (
        make_compressed_train_step,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig

    inf_model = SigLIP(_quant_cfg(SigLIPConfig.tiny_test()))
    with pytest.raises(ValueError, match="inference-only"):
        make_train_step(inf_model, make_mesh(1))
    with pytest.raises(ValueError, match="inference-only"):
        make_compressed_train_step(
            inf_model,
            make_2d_mesh(2, 2, axis_names=("dcn", "dp")),
            LossConfig(variant="all_gather"),
        )
    # quant_train builds without raising (the step itself runs in
    # test_quant_train_step_decreases_loss_and_is_deterministic).
    qt_model = SigLIP(_quant_train_cfg(SigLIPConfig.tiny_test()))
    step, _ = make_train_step(qt_model, make_mesh(1))
    assert callable(step)


# ---------------------------------------------------------------------------
# End-to-end: the quantized step trains, deterministically
# ---------------------------------------------------------------------------


def test_quant_train_step_decreases_loss_and_is_deterministic():
    """One compiled quant-train step (ring loss, 4-device dp mesh) carries
    three claims: finite decreasing loss over 8 steps, bitwise-identical
    metrics when replayed from an identical state (determinism under
    shard_map — dynamic quantization adds no data races), and bitwise-equal
    final params across the two runs."""
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        TrainConfig,
    )

    model = SigLIP(_quant_train_cfg(SigLIPConfig.tiny_test()))
    mesh = make_mesh(4)
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(rng.standard_normal((8, 16, 16, 3)), jnp.float32),
        "tokens": jnp.asarray(rng.integers(0, 64, (8, 8)), jnp.int32),
    }
    tx = make_optimizer(
        TrainConfig(learning_rate=3e-3, warmup_steps=1, total_steps=30)
    )
    step, shardings = make_train_step(model, mesh, LossConfig(variant="ring"))
    batch = jax.device_put(batch, shardings)

    def run(n_steps):
        state = create_train_state(jax.random.key(0), model, tx, batch, mesh)
        losses = []
        for _ in range(n_steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses, state

    losses_a, state_a = run(8)
    losses_b, state_b = run(8)
    assert all(np.isfinite(losses_a)), losses_a
    assert losses_a[-1] < losses_a[0], losses_a
    assert losses_a == losses_b  # bitwise determinism of the whole trajectory
    for la, lb in zip(jax.tree.leaves(state_a.params), jax.tree.leaves(state_b.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_quant_train_composes_with_moe_experts():
    """MoE towers under quant_train route the expert MLP matmuls through the
    STE twin (models/moe.py expert_apply): gradients reach the expert kernels
    AND the router."""
    cfg = SigLIPConfig.tiny_test()
    moe_kw = {"moe_experts": 2, "moe_group_size": 8}
    cfg = dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, **moe_kw),
        text=dataclasses.replace(cfg.text, **moe_kw),
    )
    model = SigLIP(_quant_train_cfg(cfg))
    key = jax.random.key(0)
    images = jax.random.normal(key, (4, 16, 16, 3), jnp.float32)
    tokens = jax.random.randint(key, (4, 8), 0, 64, jnp.int32)
    params = model.init(key, images, tokens)["params"]

    def loss(p):
        zi, zt, _ = model.apply({"params": p}, images, tokens)
        return jnp.sum(zi.astype(jnp.float32) ** 2) + jnp.sum(
            zt.astype(jnp.float32) ** 2
        )

    grads = jax.grad(loss)(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    expert_leaves = [
        np.asarray(leaf)
        for path, leaf in flat
        if any(getattr(k, "key", None) == "moe" for k in path)
    ]
    assert expert_leaves, "no MoE grads found"
    assert any(np.abs(leaf).sum() > 0 for leaf in expert_leaves)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
