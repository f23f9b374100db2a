"""Operations of a train step whose text tower is a stack of windowed chunk
attention (configuration ``evabyte-text-b16-224``): softmax attention that is
exact inside a window of W tokens and reads every earlier window as one pooled
key and value per chunk of C tokens. From the configuration's shapes alone,
whatever implements them; recomputation never counts; a step is 3 x forward. Per
text token and layer (a multiply-add is two operations):

- the q, k, v and out projections, 2 x 4 w^2, and the SwiGLU MLP's three matmuls,
  2 x 3 w hidden (the norms and the rotation are no matmul of the model's and not
  counted);
- the pooling: a head's dot with phi (2 d), and the weighted sums of the keys and
  of the values (2 x 2 d), h heads;
- the core, by the pairs the two masks admit: per caption and head (s / W) W (W +
  1) / 2 exact pairs (causal, inside a window) and W^2 / C x (s / W)(s / W - 1) / 2
  summarised ones (a query of window w meets the W / C summaries of each window
  before w), 2 (d + d) operations a pair.

The image tower is ``flops.py``'s. ``attention_least_s`` is 0 for the text tower:
none of its layers runs through ``short_attn_fwd`` / ``short_attn_bwd``, so
``attn_text_*`` say nothing there. ``eva_core_least_s`` is the cores' least time,
the same whichever implementation runs them.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops
from flops import mlp_hidden, vision_forward_flops


def core_pairs(t) -> tuple[int, int]:
    """(exact, summarised) query-key pairs of one head over one sequence."""
    s, w, c = t.context_length, t.eva_window, t.eva_chunk
    windows = s // w
    return windows * w * (w + 1) // 2, (w * w // c) * windows * (windows - 1) // 2


def eva_core_flops(t) -> float:
    """Both score sets and both value products of one layer over one sequence,
    forward."""
    d = t.width // t.num_heads
    return 2.0 * (d + d) * t.num_heads * sum(core_pairs(t))


def eva_pooling_flops(t) -> float:
    """The chunks' weights and the two weighted sums, one layer, one sequence."""
    return 6.0 * t.context_length * t.width


def text_forward_flops(t) -> float:
    if set(t.mixers) != {"eva"}:
        raise ValueError(f"flops_eva.py counts 'eva' layers, got {t.mixers!r}")
    s, w = t.context_length, t.width
    matmul_params = 4 * w * w + 3 * w * mlp_hidden(w, t.mlp_ratio)
    per_layer = 2.0 * s * matmul_params + eva_pooling_flops(t) + eva_core_flops(t)
    return len(t.mixers) * per_layer + 2.0 * w * t.embed_dim  # the text head, on the last token


def forward_flops_per_pair(cfg) -> float:
    return vision_forward_flops(cfg.vision) + text_forward_flops(cfg.text)


def train_flops_per_pair(cfg) -> float:
    """Forward + backward = 3 x forward: what MFU counts."""
    return 3.0 * forward_flops_per_pair(cfg)


def attention_least_s(cfg, tower: str, sequences: int, peaks: dict) -> float:
    """The image tower's softmax attention is ``flops.py``'s; the text tower has
    no layer that runs through the fused short-attention kernels: 0."""
    return flops.attention_least_s(cfg, tower, sequences, peaks) if tower == "visual" else 0.0


def eva_core_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for the windowed-chunk-attention cores of ``sequences``
    captions, forward + backward, recomputation not counted: per layer the
    larger of 3 x ``eva_core_flops`` over the bf16 peak and the compulsory bytes
    over the HBM peak: q, k, v, o, o's cotangent and the three gradients (s x w
    each) and the summaries with their two gradients (s / C x w each), once. At
    the cell's sizes the operations bound it (1216.5 pairs a query on average: 885
    operations a byte against the chip's 240)."""
    t = cfg.text
    s, w = t.context_length, t.width
    nbytes = (8 * s + 4 * s // t.eva_chunk) * w * itemsize
    per_layer = max(3.0 * eva_core_flops(t) / (peaks["bf16_tflops"] * 1e12), nbytes / (peaks["hbm_gb_per_s"] * 1e9))
    return sequences * len(t.mixers) * per_layer
