"""Text tower: non-causal transformer over tokenized captions (SigLIP-style), with MAP
("map") or last-token ("last", HF-format) pooling and projection into the shared
embedding space. Embedding normalization stays outside the model (reference
convention, test_distributed_sigmoid_loss.py:96-101). ``TextConfig``'s block
options turn it into a language-model-class encoder: causal, rotary positions
in place of the position table, RMSNorm sandwich blocks with a gated MLP, the
stack run ``loops`` times on one set of weights."""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from distributed_sigmoid_loss_tpu.models.transformer import (
    BlockStyle,
    Encoder,
    MapHead,
    _dtype,
)
from distributed_sigmoid_loss_tpu.utils.config import TextConfig, tower_quant_mode


class TextTransformer(nn.Module):
    cfg: TextConfig

    @nn.compact
    def __call__(self, token_ids):
        """token_ids: (batch, context_length) int32 → (batch, embed_dim)."""
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)

        emb = nn.Embed(
            cfg.vocab_size,
            cfg.width,
            embedding_init=nn.initializers.normal(stddev=0.02),
            name="token_embed",
        )(token_ids)
        x = emb.astype(dtype)
        if cfg.pos == "learned":
            pos = self.param(
                "pos_embed",
                nn.initializers.normal(stddev=0.02),
                (1, cfg.context_length, cfg.width),
                jnp.float32,
            )
            x = x + pos.astype(dtype)
        elif cfg.pos != "rope":  # rotary positions go on q and k, in the blocks
            raise ValueError(f"unknown pos: {cfg.pos!r}")

        x = Encoder(
            cfg.width, cfg.depth, cfg.num_heads, cfg.mlp_ratio, dtype,
            remat=cfg.remat, scan_layers=cfg.scan_layers, attn_impl=cfg.attn_impl,
            remat_policy=cfg.remat_policy,
            sp_axis=cfg.sequence_parallel_axis, sp_impl=cfg.sequence_parallel_impl,
            causal=cfg.causal, moe_experts=cfg.moe_experts,
            moe_num_selected=cfg.moe_num_selected,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_group_size=cfg.moe_group_size, quant=tower_quant_mode(cfg),
            style=BlockStyle.of(cfg), loops=cfg.loops,
            name="encoder",
        )(x)

        if cfg.pool == "map":
            x = MapHead(cfg.width, cfg.num_heads, cfg.mlp_ratio, dtype, name="map_head")(x)
        else:
            # HF-format SigLIP: the LAST token's hidden state is the pooled
            # representation (modeling_siglip.SiglipTextTransformer.forward).
            x = x[:, -1]
        x = nn.Dense(cfg.embed_dim, dtype=dtype, name="proj")(x)
        return x.astype(jnp.float32)
