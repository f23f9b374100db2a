"""Long-context tower: sequence-parallel (ring attention) text transformer produces the
same embeddings as the dense tower with identical params."""

import pytest

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from distributed_sigmoid_loss_tpu.models import TextTransformer
from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
from distributed_sigmoid_loss_tpu.utils.config import TextConfig

# Tier note: excluded from the time-boxed tier-1 gate (-m 'not slow'): sequence-parallel tower suites.
pytestmark = pytest.mark.slow



def test_sequence_parallel_text_tower_matches_dense():
    base = TextConfig(
        vocab_size=64, context_length=32, width=32, depth=2, num_heads=2,
        embed_dim=16, dtype="float32", remat=False, scan_layers=False,
    )
    sp = dataclasses.replace(base, sequence_parallel_axis="sp")

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32
    )
    dense_model = TextTransformer(base)
    sp_model = TextTransformer(sp)

    import flax.linen as nn

    params = nn.meta.unbox(dense_model.init(jax.random.key(0), tokens)["params"])

    want = dense_model.apply({"params": params}, tokens)

    mesh = make_mesh(4, "sp")
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, t: sp_model.apply({"params": p}, t))(params, tokens)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_sequence_parallel_grads_flow():
    cfg = TextConfig(
        vocab_size=64, context_length=16, width=32, depth=1, num_heads=2,
        embed_dim=16, dtype="float32", remat=False, scan_layers=False,
        sequence_parallel_axis="sp",
    )
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 16)), jnp.int32)
    model = TextTransformer(cfg)
    mesh = make_mesh(2, "sp")
    import flax.linen as nn

    # Init through the dense twin (identical param tree) — the tp partitioning
    # metadata can't be constrained against an sp-only mesh at init time.
    dense_twin = TextTransformer(dataclasses.replace(cfg, sequence_parallel_axis=None))
    params = nn.meta.unbox(dense_twin.init(jax.random.key(0), tokens)["params"])
    with jax.set_mesh(mesh):
        g = jax.jit(
            jax.grad(lambda p: (model.apply({"params": p}, tokens) ** 2).sum())
        )(params)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(g)]
    assert all(np.isfinite(norms)) and max(norms) > 0


def test_train_step_with_sequence_parallel_text_tower():
    """Full train step on a (dp × sp) mesh: batch sharded over dp, the text
    tower's attention sequence-parallel over sp, contrastive loss over dp — the
    long-context training composition, end to end."""
    import optax

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from jax.sharding import Mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TextConfig,
        ViTConfig,
    )

    cfg = SigLIPConfig(
        vision=ViTConfig.tiny_test(),
        text=TextConfig(
            vocab_size=64, context_length=16, width=32, depth=2, num_heads=2,
            embed_dim=16, dtype="float32", remat=False, scan_layers=False,
            sequence_parallel_axis="sp",
        ),
    )
    model = SigLIP(cfg)
    # Size-1 tp axis: the tower kernels carry tp partitioning metadata, which an
    # ambient mesh must be able to resolve.
    devices = np.asarray(jax.devices()[:8]).reshape(2, 4, 1)
    mesh = Mesh(devices, ("dp", "sp", "tp"))
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(rng.standard_normal((8, 16, 16, 3)), jnp.float32),
        "tokens": jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32),
    }
    with jax.set_mesh(mesh):
        state = create_train_state(jax.random.key(0), model, optax.adam(1e-3), batch, mesh)
        step, shardings = make_train_step(model, mesh, LossConfig(variant="ring"))
        batch = jax.device_put(batch, shardings)
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_sequence_parallel_vision_tower_matches_dense():
    """High-res vision path: the patch sequence sharded over sp (ring attention
    in the blocks, MAP pooling sequence-global) equals the dense tower."""
    from distributed_sigmoid_loss_tpu.models import ViT
    from distributed_sigmoid_loss_tpu.utils.config import ViTConfig

    base = ViTConfig(
        image_size=32, patch_size=4, width=32, depth=2, num_heads=2,
        embed_dim=16, dtype="float32", remat=False, scan_layers=False,
    )  # 8x8 = 64 patch tokens, divisible by sp=4
    sp = dataclasses.replace(base, sequence_parallel_axis="sp")

    images = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    dense_model = ViT(base)
    sp_model = ViT(sp)

    import flax.linen as nn

    params = nn.meta.unbox(dense_model.init(jax.random.key(0), images)["params"])
    want = dense_model.apply({"params": params}, images)

    mesh = make_mesh(4, "sp")
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, x: sp_model.apply({"params": p}, x))(params, images)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)


def test_sequence_parallel_vision_ulysses_matches_dense():
    from distributed_sigmoid_loss_tpu.models import ViT
    from distributed_sigmoid_loss_tpu.utils.config import ViTConfig

    base = ViTConfig(
        image_size=32, patch_size=4, width=32, depth=2, num_heads=2,
        embed_dim=16, dtype="float32", remat=False, scan_layers=False,
    )
    sp = dataclasses.replace(
        base, sequence_parallel_axis="sp", sequence_parallel_impl="ulysses"
    )
    images = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    import flax.linen as nn

    dense_model = ViT(base)
    params = nn.meta.unbox(dense_model.init(jax.random.key(0), images)["params"])
    want = dense_model.apply({"params": params}, images)

    mesh = make_mesh(2, "sp")  # num_heads=2 must divide the axis
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p, x: ViT(sp).apply({"params": p}, x))(params, images)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
