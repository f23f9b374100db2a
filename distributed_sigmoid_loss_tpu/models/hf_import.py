"""Import HF-format SigLIP checkpoints (``google/siglip-*``) into this framework.

The reference repo implements the SigLIP *loss*; the models people pair it with are
the released SigLIP towers. This module maps a ``transformers`` SigLIP state dict
onto our flax param tree so a reference user can bring their pretrained weights —
covering every tensor: patch/token/position embeddings, the pre-LN encoder stacks,
the MAP vision pooling head (torch ``nn.MultiheadAttention`` packed qkv unpacked),
the last-token text head, and the loss scalars (HF ``logit_scale``/``logit_bias``
≡ our ``t_prime``/``bias`` — same semantics: ``logits = z @ z.T * exp(t') + b``).

Verified numerically by ``tests/test_hf_import.py``: a randomly initialized
``transformers.SiglipModel`` and the converted flax model agree on image/text
embeddings and pairwise logits at fp32.

Layout notes (torch → flax):
- ``nn.Linear.weight`` is (out, in) → dense ``kernel`` (in, out): transpose.
- ``nn.Conv2d.weight`` is (out, in, kh, kw) → conv ``kernel`` (kh, kw, in, out).
- ``nn.MultiheadAttention.in_proj_weight`` is rows-stacked [q; k; v].
- Conversion targets the unscanned layout (``scan_layers=False``, per-block
  subtrees ``block{i}``); :func:`stack_for_scan` restacks for ``scan_layers=True``.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from distributed_sigmoid_loss_tpu.utils.config import (
    SigLIPConfig,
    TextConfig,
    ViTConfig,
    changed_block_options,
)

__all__ = ["config_from_hf", "params_from_hf", "stack_for_scan"]


def config_from_hf(hf_config: Any, dtype: str = "bfloat16") -> SigLIPConfig:
    """Build the matching :class:`SigLIPConfig` from a ``transformers.SiglipConfig``.

    The returned config is HF-shaped: no vision projection (``use_proj=False``,
    ``embed_dim = hidden_size``), last-token text pooling, unscanned layers
    (the layout :func:`params_from_hf` targets).
    """
    v, t = hf_config.vision_config, hf_config.text_config
    if v.hidden_size % v.num_attention_heads or t.hidden_size % t.num_attention_heads:
        raise ValueError(
            f"num_attention_heads must divide hidden_size (got vision "
            f"{v.hidden_size}/{v.num_attention_heads}, text "
            f"{t.hidden_size}/{t.num_attention_heads})"
        )

    def ratio(intermediate: int, hidden: int) -> float:
        # mlp_ratio may be fractional (so400m: 4304/1152); Mlp rounds
        # width*ratio back to an integer — assert the round trip is exact.
        r = intermediate / hidden
        if int(round(hidden * r)) != intermediate:
            raise ValueError(
                f"cannot represent intermediate_size {intermediate} as a ratio "
                f"of hidden_size {hidden}"
            )
        return r

    vision = ViTConfig(
        image_size=v.image_size,
        patch_size=v.patch_size,
        width=v.hidden_size,
        depth=v.num_hidden_layers,
        num_heads=v.num_attention_heads,
        mlp_ratio=ratio(v.intermediate_size, v.hidden_size),
        embed_dim=v.hidden_size,
        pool="map",
        use_proj=False,
        dtype=dtype,
        scan_layers=False,
    )
    text = TextConfig(
        vocab_size=t.vocab_size,
        context_length=t.max_position_embeddings,
        width=t.hidden_size,
        depth=t.num_hidden_layers,
        num_heads=t.num_attention_heads,
        mlp_ratio=ratio(t.intermediate_size, t.hidden_size),
        embed_dim=t.projection_size,
        pool="last",
        dtype=dtype,
        scan_layers=False,
    )
    if vision.embed_dim != text.embed_dim:
        raise ValueError(
            f"HF vision hidden_size ({vision.embed_dim}) must equal text "
            f"projection_size ({text.embed_dim}) for a shared embedding space"
        )
    return SigLIPConfig(vision=vision, text=text)


def _np(t) -> np.ndarray:
    """torch tensor / array-like → float32 numpy (conversion is layout work;
    the model's own dtype policy applies at apply time)."""
    if hasattr(t, "detach"):
        t = t.detach().cpu().numpy()
    return np.asarray(t, np.float32)


def _linear(sd: Mapping, prefix: str) -> dict:
    return {"kernel": _np(sd[f"{prefix}.weight"]).T, "bias": _np(sd[f"{prefix}.bias"])}


def _layernorm(sd: Mapping, prefix: str) -> dict:
    return {"scale": _np(sd[f"{prefix}.weight"]), "bias": _np(sd[f"{prefix}.bias"])}


def _block(sd: Mapping, prefix: str) -> dict:
    return {
        "ln1": _layernorm(sd, f"{prefix}.layer_norm1"),
        "ln2": _layernorm(sd, f"{prefix}.layer_norm2"),
        "attn": {
            "q": _linear(sd, f"{prefix}.self_attn.q_proj"),
            "k": _linear(sd, f"{prefix}.self_attn.k_proj"),
            "v": _linear(sd, f"{prefix}.self_attn.v_proj"),
            "out": _linear(sd, f"{prefix}.self_attn.out_proj"),
        },
        "mlp": {
            "wi": _linear(sd, f"{prefix}.mlp.fc1"),
            "wo": _linear(sd, f"{prefix}.mlp.fc2"),
        },
    }


def _encoder(sd: Mapping, prefix: str, depth: int, final_ln: str) -> dict:
    enc = {f"block{i}": _block(sd, f"{prefix}.layers.{i}") for i in range(depth)}
    enc["ln_final"] = _layernorm(sd, final_ln)
    return enc


def _map_head(sd: Mapping, prefix: str, width: int) -> dict:
    """torch MultiheadAttention packed [q; k; v] in_proj → separate q/k/v denses."""
    in_w = _np(sd[f"{prefix}.attention.in_proj_weight"])
    in_b = _np(sd[f"{prefix}.attention.in_proj_bias"])
    qw, kw, vw = in_w[:width], in_w[width : 2 * width], in_w[2 * width :]
    qb, kb, vb = in_b[:width], in_b[width : 2 * width], in_b[2 * width :]
    return {
        "probe": _np(sd[f"{prefix}.probe"]),
        "attn": {
            "q": {"kernel": qw.T, "bias": qb},
            "k": {"kernel": kw.T, "bias": kb},
            "v": {"kernel": vw.T, "bias": vb},
            "out": _linear(sd, f"{prefix}.attention.out_proj"),
        },
        "ln": _layernorm(sd, f"{prefix}.layernorm"),
        "mlp": {
            "wi": _linear(sd, f"{prefix}.mlp.fc1"),
            "wo": _linear(sd, f"{prefix}.mlp.fc2"),
        },
    }


def params_from_hf(state_dict: Mapping, cfg: SigLIPConfig) -> dict:
    """``transformers.SiglipModel`` state dict → this framework's param pytree.

    ``cfg`` must be HF-shaped (see :func:`config_from_hf`). Every produced leaf is
    float32 numpy; feed the result anywhere ``SigLIP`` params go (train state,
    ``model.apply({"params": ...})``).
    """
    sd = state_dict
    if (cfg.vision.use_proj or cfg.text.pool != "last"
            or cfg.vision.scan_layers or cfg.text.scan_layers):
        raise ValueError(
            "cfg must be HF-shaped (use_proj=False, text pool='last', "
            "scan_layers=False) — build it with config_from_hf"
        )
    changed = changed_block_options(cfg.text)
    if changed:
        # The mapping below is the SigLIP block's, tensor by tensor (ln1, wi,
        # pos_embed): another block's tree must not be filled from it.
        raise ValueError(
            "params_from_hf maps HF SigLIP checkpoints, whose text block is the "
            "default one; cfg.text sets "
            + ", ".join(changed)
        )
    v = {
        "patch_embed": {
            # (out, in, kh, kw) -> (kh, kw, in, out)
            "kernel": _np(
                sd["vision_model.embeddings.patch_embedding.weight"]
            ).transpose(2, 3, 1, 0),
            "bias": _np(sd["vision_model.embeddings.patch_embedding.bias"]),
        },
        "pos_embed": _np(
            sd["vision_model.embeddings.position_embedding.weight"]
        )[None],
        "encoder": _encoder(
            sd, "vision_model.encoder", cfg.vision.depth,
            "vision_model.post_layernorm",
        ),
        "map_head": _map_head(sd, "vision_model.head", cfg.vision.width),
    }
    t = {
        "token_embed": {
            "embedding": _np(sd["text_model.embeddings.token_embedding.weight"])
        },
        "pos_embed": _np(
            sd["text_model.embeddings.position_embedding.weight"]
        )[None],
        "encoder": _encoder(
            sd, "text_model.encoder", cfg.text.depth,
            "text_model.final_layer_norm",
        ),
        "proj": _linear(sd, "text_model.head"),
    }
    return {
        "visual": v,
        "textual": t,
        # HF logit_scale/logit_bias are shape-(1,) params; ours are scalars with
        # identical semantics: logits = zimg @ ztxt.T * exp(t_prime) + bias.
        "t_prime": _np(sd["logit_scale"]).reshape(()),
        "bias": _np(sd["logit_bias"]).reshape(()),
    }


def stack_for_scan(encoder_params: dict, depth: int) -> dict:
    """Restack per-block subtrees (``block{i}``) into the ``scan_layers=True``
    layout (one ``blocks`` subtree with a leading depth axis on every leaf)."""
    import jax

    blocks = [encoder_params[f"block{i}"] for i in range(depth)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *blocks)
    out = {k: v for k, v in encoder_params.items() if not k.startswith("block")}
    out["blocks"] = {"block": stacked}
    return out
