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


def attention_layers_least_s(s: int, w: int, applications: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for ``applications`` softmax-attention cores of ``s`` tokens
    at width ``w``, forward + backward, recomputation not counted: the larger of
    12 s^2 w operations each (scores and values, 3 x 4 s^2 w) over the bf16 peak
    and 12 x itemsize s w bytes each over the HBM peak (forward reads q, k, v and
    writes the output; backward reads those four and the output's cotangent and
    writes three)."""
    return max(
        12.0 * s * s * w * applications / (peaks["bf16_tflops"] * 1e12),
        12.0 * itemsize * s * w * applications / (peaks["hbm_gb_per_s"] * 1e9),
    )


def attention_least_s(cfg, tower: str, sequences: int, peaks: dict) -> float:
    """The least time the chip could take for the softmax-attention cores that
    ``tower`` ("visual" | "textual") runs through the program's fused kernels for
    ``sequences`` pairs: a SigLIP tower applies each of its ``depth`` layers once
    a sequence. Memory-bound at these lengths: s/2 operations a byte, 98 (image)
    and 32 (text), against the v5e's 240. A count module whose tower runs no such
    layer returns 0, and the roofline readers then say nothing."""
    t = cfg.vision if tower == "visual" else cfg.text
    s = vision_tokens(t) if tower == "visual" else t.context_length
    return attention_layers_least_s(s, t.width, sequences * t.depth, peaks)
