"""PatchEmbed (reshape+matmul) must be a drop-in for the strided conv.

The patchify layer was rewritten from ``nn.Conv`` to an explicit reshape + one
matmul: measured perf-neutral on the chip (a builder's run before PR 22), kept
because the MXU lowering is explicit rather than trusted to XLA's conv path.
These tests pin the contract that made the swap safe: the
param tree is nn.Conv's exact HWIO layout, and outputs match the conv to f32
noise — so old checkpoints and the HF importer (models/hf_import.py:174) keep
working unchanged.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.models.vit import PatchEmbed, ViT
from distributed_sigmoid_loss_tpu.utils.config import ViTConfig


@pytest.mark.parametrize("patch,size", [(16, 224), (14, 196), (4, 32)])
def test_matches_strided_conv_with_shared_params(patch, size):
    width = 48
    imgs = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, size, size, 3)), jnp.float32
    )
    pe = PatchEmbed(width, patch, jnp.float32)
    params = pe.init(jax.random.key(0), imgs)["params"]
    assert params["kernel"].shape == (patch, patch, 3, width)  # HWIO, as nn.Conv
    assert params["bias"].shape == (width,)

    conv = nn.Conv(width, (patch, patch), strides=(patch, patch), padding="VALID")
    out_conv = conv.apply({"params": params}, imgs)  # identical param tree
    out_pe = pe.apply({"params": params}, imgs)
    n = (size // patch) ** 2
    assert out_pe.shape == (2, n, width)
    np.testing.assert_allclose(
        np.asarray(out_conv).reshape(2, n, width), np.asarray(out_pe),
        rtol=1e-5, atol=1e-5,
    )


def test_vit_sizes_pos_embed_from_actual_input():
    # e.g. 384-res finetune with a 224 config: pos_embed must follow the input.
    cfg = ViTConfig(
        image_size=32, patch_size=4, width=32, depth=1, num_heads=2,
        mlp_ratio=2, embed_dim=16,
    )
    model = ViT(cfg)
    imgs = jnp.ones((2, 48, 48, 3), jnp.float32)  # 144 patches, not 64
    params = model.init(jax.random.key(0), imgs)["params"]
    assert params["pos_embed"].shape == (1, 144, 32)
    assert model.apply({"params": params}, imgs).shape == (2, 16)


def test_vit_forward_still_runs():
    cfg = ViTConfig(
        image_size=32, patch_size=4, width=32, depth=1, num_heads=2,
        mlp_ratio=2, embed_dim=16,
    )
    model = ViT(cfg)
    imgs = jnp.ones((2, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.key(0), imgs)["params"]
    out = model.apply({"params": params}, imgs)
    assert out.shape == (2, 16)
    assert np.isfinite(np.asarray(out)).all()
