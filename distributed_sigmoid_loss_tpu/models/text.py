"""Text tower: non-causal transformer over tokenized captions (SigLIP-style), with MAP
("map") or last-token ("last", HF-format) pooling and projection into the shared
embedding space. Embedding normalization stays outside the model (reference
convention, test_distributed_sigmoid_loss.py:96-101). ``TextConfig``'s block
options turn it into a language-model-class encoder: causal, rotary positions
in place of the position table (or none: ``pos="none"``), RMSNorm sandwich
blocks with a gated MLP, the stack run ``loops`` times on one set of weights, or
a stack given layer by layer (``mixers``): several layer kinds with routed
experts, or windowed chunk attention in every layer."""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from distributed_sigmoid_loss_tpu.models.transformer import (
    BlockStyle,
    Encoder,
    LayerSpec,
    MapHead,
    _dtype,
)
from distributed_sigmoid_loss_tpu.utils.config import TextConfig, tower_quant_mode


def layer_specs(cfg: TextConfig) -> tuple[LayerSpec, ...]:
    """``Encoder``'s layers where the configuration gives the stack layer by
    layer (``mixers``, ``leading_dense_layers``) or asks for the sigmoid-routed
    experts; none for the stack every tower had."""
    if not (cfg.mixers or cfg.leading_dense_layers or cfg.moe_router != "softmax"):
        return ()
    mixer_fields = {
        "attn": (),
        "kda": (("head_dim", cfg.kda_head_dim), ("conv_size", cfg.kda_conv_size)),
        "mla": (
            ("nope_dim", cfg.mla_qk_nope_dim), ("shared_dim", cfg.mla_qk_shared_dim),
            ("v_dim", cfg.mla_v_dim), ("kv_rank", cfg.mla_kv_rank), ("q_rank", cfg.mla_q_rank),
            ("rope_theta", cfg.rope_theta if cfg.pos == "rope" else None),
        ),
        "eva": (
            ("window", cfg.eva_window), ("chunk", cfg.eva_chunk), ("rope_theta", cfg.rope_theta),
        ),
    }
    mixers = cfg.mixers or ("attn",) * cfg.depth
    if not set(mixers) <= set(mixer_fields):
        raise ValueError(f"unknown mixer in mixers={mixers}: want one of {sorted(mixer_fields)}")
    # Latent attention rotates its shared-width parts; a recurrence carries the
    # order itself, and neither takes a position table.
    if "kda" in mixers and cfg.pos != "none" or "mla" in mixers and cfg.pos == "learned":
        raise ValueError(
            f"mixers={mixers} is not built for pos={cfg.pos!r}: a recurrence ('kda') takes "
            "pos='none', latent attention ('mla') 'none' or 'rope'"
        )
    if "eva" in mixers:
        # Windowed chunk attention rotates whole heads by the positions of one whole
        # sequence, cut into whole windows of whole chunks.
        refused = {
            f"pos={cfg.pos!r} (it takes 'rope')": cfg.pos != "rope",
            f"context_length={cfg.context_length} (no multiple of eva_window={cfg.eva_window})":
                cfg.eva_window < 1 or cfg.context_length % max(cfg.eva_window, 1) != 0,
            f"eva_window={cfg.eva_window} (no multiple of eva_chunk={cfg.eva_chunk})":
                cfg.eva_chunk < 1 or cfg.eva_window % max(cfg.eva_chunk, 1) != 0,
        }
        if any(refused.values()):
            raise ValueError(
                f"mixers={mixers}: windowed chunk attention ('eva') is not built for "
                + ", ".join(k for k, v in refused.items() if v)
            )
    if cfg.moe_router not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown moe_router: {cfg.moe_router!r}")
    experts_fields = ()
    if cfg.moe_router == "sigmoid":
        experts_fields = (
            ("hidden", cfg.moe_hidden or int(round(cfg.width * cfg.mlp_ratio))),
            ("route_scale", cfg.moe_route_scale), ("shared_experts", cfg.moe_shared_experts),
            ("experts_held", cfg.moe_experts_held),
        )
    return tuple(
        LayerSpec(kind, mixer_fields[kind], i < cfg.leading_dense_layers, experts_fields)
        for i, kind in enumerate(mixers)
    )


class TextTransformer(nn.Module):
    cfg: TextConfig

    @nn.compact
    def __call__(self, token_ids):
        """token_ids: (batch, context_length) int32 → (batch, embed_dim)."""
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)
        layers = layer_specs(cfg)

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
        elif cfg.pos not in ("rope", "none"):  # rotary positions go on q and k, in the blocks
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
            style=BlockStyle.of(cfg), loops=cfg.loops, layers=layers,
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
