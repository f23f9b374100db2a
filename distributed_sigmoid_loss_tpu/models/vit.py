"""ViT image tower (BASELINE.json configs #4/#5: ViT-B/16, ViT-L/14).

Patchify is an explicit reshape + ONE MXU matmul, not a strided conv: with
stride == kernel the conv is mathematically a per-patch dot product, and the
explicit form makes the MXU lowering visible instead of trusting XLA's conv
path. Measured A/B on the chip: perf-NEUTRAL vs nn.Conv (773.4 vs 771.6
pairs/s headline, run noise) — XLA was already lowering this conv well. (A
trace initially suggested otherwise: `convolution_add_fusion` at 11.8% of
device time — but on TPU that op name is XLA's label for MATMUL+bias fusions,
which run at 175 TFLOP/s there; a builder's run from before PR 22.) Params keep
nn.Conv's exact HWIO kernel layout so checkpoints are interchangeable with the
conv form.
Output is the L2-normalizable image embedding; normalization stays OUTSIDE the
model, matching the reference's convention of normalizing outside the loss
(/root/reference/test_distributed_sigmoid_loss.py:96-101, README.md release note).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from distributed_sigmoid_loss_tpu.models.transformer import Encoder, MapHead, _dtype
from distributed_sigmoid_loss_tpu.utils.config import ViTConfig, tower_quant_mode


class PatchEmbed(nn.Module):
    """Non-overlapping patchify as reshape + matmul (see module docstring).

    Param tree is identical to ``nn.Conv(width, (p, p), strides=(p, p),
    padding="VALID")``: ``kernel`` (p, p, 3, width) HWIO + ``bias`` (width,).
    """

    width: int
    patch_size: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, images):
        b, hh, ww, c = images.shape
        p = self.patch_size
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (p, p, c, self.width),
            jnp.float32,
        )
        bias = self.param("bias", nn.initializers.zeros, (self.width,), jnp.float32)
        # (b, H, W, c) -> (b, nh, p, nw, p, c) -> (b, nh·nw, p·p·c); the
        # per-patch (ph, pw, c) order matches the HWIO kernel reshape below.
        x = images.astype(self.dtype)  # promote inputs like nn.Conv(dtype=...) did
        if hh % p or ww % p:
            # nn.Conv(padding="VALID") silently cropped the remainder (e.g.
            # L/14 at 384: 384 % 14 = 6 px); keep that drop-in behavior.
            x = x[:, : hh // p * p, : ww // p * p, :]
        x = x.reshape(b, hh // p, p, ww // p, p, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (hh // p) * (ww // p), p * p * c)
        w = kernel.reshape(p * p * c, self.width)
        return x @ w.astype(self.dtype) + bias.astype(self.dtype)


class ViT(nn.Module):
    cfg: ViTConfig

    @nn.compact
    def __call__(self, images):
        """images: (batch, H, W, 3) → (batch, embed_dim) unnormalized embeddings."""
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)
        x = images.astype(dtype)

        x = PatchEmbed(
            cfg.width, cfg.patch_size, dtype, name="patch_embed"
        )(x)
        n = x.shape[1]  # patch count from the ACTUAL input (e.g. 384-res finetune)

        pos = self.param(
            "pos_embed",
            nn.initializers.normal(stddev=0.02),
            (1, n, cfg.width),
            jnp.float32,
        )
        x = x + pos.astype(dtype)

        x = Encoder(
            cfg.width, cfg.depth, cfg.num_heads, cfg.mlp_ratio, dtype,
            remat=cfg.remat, scan_layers=cfg.scan_layers, attn_impl=cfg.attn_impl,
            remat_policy=cfg.remat_policy,
            sp_axis=cfg.sequence_parallel_axis,
            sp_impl=cfg.sequence_parallel_impl,
            moe_experts=cfg.moe_experts,
            moe_num_selected=cfg.moe_num_selected,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_group_size=cfg.moe_group_size, quant=tower_quant_mode(cfg),
            name="encoder",
        )(x)

        if cfg.pool == "map":
            x = MapHead(cfg.width, cfg.num_heads, cfg.mlp_ratio, dtype, name="map_head")(x)
        else:
            x = x.mean(axis=1)

        if cfg.use_proj:
            x = nn.Dense(cfg.embed_dim, dtype=dtype, name="proj")(x)
        elif cfg.embed_dim != cfg.width:
            raise ValueError(
                f"use_proj=False (HF-format) requires embed_dim == width, got "
                f"{cfg.embed_dim} != {cfg.width}"
            )
        return x.astype(jnp.float32)
