"""Operations of a train step whose text tower is a looped stack with a gated MLP
(configuration ``ouro-2.6b-text-b16-224``): ``flops.py``'s count with the two
things that model changes. A layer runs ``loops`` times a sequence, so the text
tower does ``loops x depth`` layer applications; a SwiGLU MLP is three matmuls of
2 s w hidden, not two. Attention counts its full s^2 as in every other cell: the
one-block causal kernel computes every score and masks, it skips nothing.
Everything else (the image tower, what counts and what does not, 3 x forward a
step whatever is recomputed) is ``flops.py``'s, imported. With ``loops = 1`` and
a GELU MLP this is ``flops.py``.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops
from flops import mlp_hidden, vision_forward_flops

MLP_MATMULS = {"gelu": 2, "swiglu": 3}


def layer_applications(tower) -> int:
    """How often a sequence runs a layer of the tower: every layer, every pass."""
    return tower.depth * getattr(tower, "loops", 1)


def text_forward_flops(t) -> float:
    """One caption through the text tower: per layer application the q, k, v and
    output projections (8 s w^2), the MLP's matmuls (2 s w hidden each) and
    attention's scores and values (4 s^2 w); then the head."""
    if getattr(t, "moe_experts", 0):
        raise ValueError("flops_looped.py counts dense MLPs only")
    s, w = t.context_length, t.width
    hidden = mlp_hidden(w, t.mlp_ratio)
    per_layer = 8 * s * w * w + 2 * MLP_MATMULS[getattr(t, "mlp", "gelu")] * s * w * hidden + 4 * s * s * w
    flops = float(layer_applications(t) * per_layer)
    if t.pool == "map":
        flops += 4.0 * s * w * w
    return flops + 2.0 * w * t.embed_dim  # the text head


def forward_flops_per_pair(cfg) -> float:
    return vision_forward_flops(cfg.vision) + text_forward_flops(cfg.text)


def train_flops_per_pair(cfg) -> float:
    """Forward + backward = 3 x forward: what MFU counts."""
    return 3.0 * forward_flops_per_pair(cfg)


def attention_least_s(cfg, tower: str, sequences: int, peaks: dict) -> float:
    """``flops.attention_least_s`` with every layer application counted: the text
    tower runs ``loops x depth`` of them a caption. At s = 256 that is s/2 = 128
    operations a byte against the v5e's 240: bound by memory. The image tower is
    ``flops.py``'s."""
    if tower == "visual":
        return flops.attention_least_s(cfg, tower, sequences, peaks)
    t = cfg.text
    return flops.attention_layers_least_s(t.context_length, t.width, sequences * layer_applications(t), peaks)
