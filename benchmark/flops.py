"""Operations a SigLIP train step needs, computed from the configuration's shapes.

The yardstick: MFU and the attention roofline share divide by these, so they live
with the benchmark and not in the program. The arithmetic is copied from
``bench.py`` (``transformer_forward_flops``, ``model_forward_flops_per_pair``),
which a later PR may delete. Matmul operations only (a multiply-add is two);
layer norms, gelu, softmax and the loss's pairwise matmul are left out (the loss
is under 0.1 % of a step at every cell's batch). Recomputed operations never
count: a step is 3 x forward whatever the remat policy.

``cfg`` is anything with ``.vision`` and ``.text`` that carry the SigLIPConfig
field names (the dataclass itself, or a namespace made from a configuration file).
"""

from __future__ import annotations


def mlp_hidden(width: int, mlp_ratio: float) -> int:
    """The MLP's hidden size; the ratio may be fractional (so400m: 4304 / 1152)."""
    return int(round(width * mlp_ratio))


def vision_tokens(vision) -> int:
    return (vision.image_size // vision.patch_size) ** 2


def transformer_forward_flops(s: int, width: int, depth: int, mlp_ratio: float) -> float:
    """One sequence of ``s`` tokens through ``depth`` pre-LN blocks: q, k, v and
    output projections (8 s w^2), the two MLP matmuls (4 s w hidden), attention
    scores and values (4 s^2 w)."""
    hidden = mlp_hidden(width, mlp_ratio)
    per_layer = 8 * s * width * width + 4 * s * width * hidden + 4 * s * s * width
    return float(depth * per_layer)


def _moe_extra(tower, s: int) -> float:
    """Each token runs k expert MLPs of the dense hidden size."""
    extra_k = getattr(tower, "moe_num_selected", 1) - 1
    if not getattr(tower, "moe_experts", 0) or extra_k <= 0:
        return 0.0
    hidden = mlp_hidden(tower.width, tower.mlp_ratio)
    return float(extra_k * 4 * s * tower.width * hidden * tower.depth)


def vision_forward_flops(v) -> float:
    s = vision_tokens(v)
    flops = transformer_forward_flops(s, v.width, v.depth, v.mlp_ratio)
    flops += 2.0 * s * v.patch_size * v.patch_size * 3 * v.width  # patch embedding
    if v.pool == "map":
        flops += 4.0 * s * v.width * v.width  # the pooling head's k and v projections
    if v.use_proj:
        flops += 2.0 * v.width * v.embed_dim
    return flops + _moe_extra(v, s)


def text_forward_flops(t) -> float:
    s = t.context_length
    flops = transformer_forward_flops(s, t.width, t.depth, t.mlp_ratio)
    if t.pool == "map":
        flops += 4.0 * s * t.width * t.width
    flops += 2.0 * t.width * t.embed_dim  # the text head
    return flops + _moe_extra(t, s)


def forward_flops_per_pair(cfg) -> float:
    """Forward operations for one image-text pair through both towers."""
    return vision_forward_flops(cfg.vision) + text_forward_flops(cfg.text)


def train_flops_per_pair(cfg) -> float:
    """Forward + backward = 3 x forward: what MFU counts."""
    return 3.0 * forward_flops_per_pair(cfg)


def attention_flops_per_pair(cfg) -> float:
    """Scores and values in the blocks' self-attention, forward + backward, both
    towers: 3 x 4 s^2 w = 12 s^2 w per layer per sequence. What the fused
    attention kernels have to do in a step, recomputation not counted."""
    v, t = cfg.vision, cfg.text
    sv, st = vision_tokens(v), t.context_length
    return float(12 * sv * sv * v.width * v.depth + 12 * st * st * t.width * t.depth)


def attention_bytes_per_pair(cfg, itemsize: int = 2) -> float:
    """The least HBM traffic of those kernels: forward reads q, k, v and writes
    the output (4 s w); backward reads q, k, v, the output and its cotangent and
    writes three cotangents (8 s w)."""
    v, t = cfg.vision, cfg.text
    sv, st = vision_tokens(v), t.context_length
    return float(12 * itemsize * (sv * v.width * v.depth + st * t.width * t.depth))
