"""Text tower: non-causal transformer over tokenized captions (SigLIP-style), with MAP
("map") or last-token ("last", HF-format) pooling and projection into the shared
embedding space. Embedding normalization stays outside the model (reference
convention, test_distributed_sigmoid_loss.py:96-101). ``TextConfig``'s block
options turn it into a language-model-class encoder: causal, rotary positions
in place of the position table (or none: ``pos="none"``), RMSNorm sandwich
blocks with a gated MLP, the stack run ``loops`` times on one set of weights, or
a stack given layer by layer (``mixers``): several layer kinds with routed
experts, windowed chunk attention in every layer, or layers of ONE sub-layer
each (``sublayers="single"``: a state-space mixer, a grouped-head attention or
a routed feed-forward part alone). An "attn" layer may take a window
(``attn_windows``, layer by layer, beside full layers in one stack), rotate or
not by its kind (``rope_layers``), norm the heads of q and k and gate its
output; ``embed_scale`` scales the embedding."""

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


# What a layer of a one-sub-layer stack may be (``mixers`` names it): a state-space mixer alone, an
# attention alone, or the routed feed-forward part alone. Nothing else runs so yet.
SINGLE_LAYERS = ("ssm", "attn", "moe")


def layer_specs(cfg: TextConfig) -> tuple[LayerSpec, ...]:
    """``Encoder``'s layers where the configuration gives the stack layer by
    layer (``mixers``, ``leading_dense_layers``, ``sublayers="single"``), asks
    for the sigmoid-routed experts or gives ``Attention`` head sizes or options
    of its own (``num_kv_heads``, ``head_dim``, ``attn_windows``, ``rope_layers``,
    ``attn_qk_norm``, ``attn_gate``); none for the stack every tower had."""
    single = cfg.sublayers == "single"
    attn_sizes = tuple((k, v) for k, v in (
        ("num_kv_heads", cfg.num_kv_heads), ("head_dim", cfg.head_dim), ("qk_norm", cfg.attn_qk_norm),
        ("out_gate", cfg.attn_gate),
    ) if v)
    by_layer = bool(cfg.attn_windows) or cfg.rope_layers != "all"
    if not (cfg.mixers or cfg.leading_dense_layers or cfg.moe_router != "softmax" or attn_sizes or single or by_layer):
        return ()
    if cfg.sublayers not in ("pair", "single"):
        raise ValueError(f"unknown sublayers: {cfg.sublayers!r}")
    mixers = cfg.mixers or ("attn",) * cfg.depth
    windows = cfg.attn_windows or (0,) * len(mixers)
    refused = {
        f"attn_windows={cfg.attn_windows} (one number a layer, depth={cfg.depth}; 0 = a full layer)":
            len(windows) != len(mixers) or any(w < 0 for w in windows),
        f"attn_windows={cfg.attn_windows} (a window is an 'attn' layer's) with mixers={mixers}":
            any(w and m != "attn" for w, m in zip(windows, mixers)),
        f"rope_layers={cfg.rope_layers!r} (want 'all' or 'window')": cfg.rope_layers not in ("all", "window"),
        f"rope_layers={cfg.rope_layers!r} with pos={cfg.pos!r} (it says which layers pos='rope' rotates)":
            cfg.rope_layers == "window" and cfg.pos != "rope",
    }
    if any(refused.values()):
        raise ValueError("the text tower is not built for " + ", ".join(k for k, v in refused.items() if v))

    def attn_fields(window):
        """An "attn" layer's own: the tower's head sizes and options, its window, and whether it rotates."""
        rotates = (("rope_theta", cfg.rope_theta if window else None),) if cfg.rope_layers == "window" else ()
        return attn_sizes + ((("window", window),) if window else ()) + rotates

    mixer_fields = {
        "kda": (("head_dim", cfg.kda_head_dim), ("conv_size", cfg.kda_conv_size)),
        "mla": (
            ("nope_dim", cfg.mla_qk_nope_dim), ("shared_dim", cfg.mla_qk_shared_dim),
            ("v_dim", cfg.mla_v_dim), ("kv_rank", cfg.mla_kv_rank), ("q_rank", cfg.mla_q_rank),
            ("rope_theta", cfg.rope_theta if cfg.pos == "rope" else None),
        ),
        "eva": (
            ("window", cfg.eva_window), ("chunk", cfg.eva_chunk), ("rope_theta", cfg.rope_theta),
        ),
        "ssm": (
            ("num_heads", cfg.ssm_num_heads), ("head_dim", cfg.ssm_head_dim), ("state", cfg.ssm_state),
            ("groups", cfg.ssm_groups), ("conv_size", cfg.ssm_conv_size), ("chunk", cfg.ssm_chunk),
        ),
    }
    # A pair layer takes the mixers that ran beside an MLP before; the state-space mixer is a layer alone.
    known = set(SINGLE_LAYERS) if single else {"attn"} | set(mixer_fields) - {"ssm"}
    if not set(mixers) <= known:
        raise ValueError(
            f"unknown mixer in mixers={mixers} with sublayers={cfg.sublayers!r}: want one of {sorted(known)} "
            "('ssm' and 'moe' name a layer of sublayers='single'; 'kda', 'mla' and 'eva' one of 'pair')"
        )
    if single:
        # One sub-layer a layer: ``mixers`` names it, a mixer alone or the routed feed-forward part alone.
        refused = {
            "mixers=() (it names every layer's one sub-layer)": not cfg.mixers,
            f"leading_dense_layers={cfg.leading_dense_layers} (no layer of the stack is a dense MLP)":
                cfg.leading_dense_layers != 0,
            f"moe_experts={cfg.moe_experts} (a 'moe' layer routes over experts)":
                "moe" in mixers and cfg.moe_experts < 1,
        }
        if any(refused.values()):
            raise ValueError("sublayers='single' is not built for " + ", ".join(k for k, v in refused.items() if v))
    # Latent attention rotates its shared-width parts; a recurrence carries the
    # order itself, and neither takes a position table.
    recurrent = {"kda", "ssm"} & set(mixers)
    if recurrent and cfg.pos != "none" or "mla" in mixers and cfg.pos == "learned":
        raise ValueError(
            f"mixers={mixers} is not built for pos={cfg.pos!r}: a recurrence ('kda', 'ssm') takes "
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
        ) + ((("shared_hidden", cfg.moe_shared_hidden),) if cfg.moe_shared_hidden else ())
    fields = [attn_fields(w) if kind == "attn" else mixer_fields.get(kind) for kind, w in zip(mixers, windows)]
    if single:
        return tuple(
            LayerSpec("none", experts_fields=experts_fields) if kind == "moe"
            else LayerSpec(kind, of_layer, feed_forward=False)
            for kind, of_layer in zip(mixers, fields)
        )
    return tuple(
        LayerSpec(kind, of_layer, i < cfg.leading_dense_layers, experts_fields)
        for i, (kind, of_layer) in enumerate(zip(mixers, fields))
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
        x = emb.astype(dtype) if cfg.embed_scale == 1.0 else (emb * cfg.embed_scale).astype(dtype)
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
