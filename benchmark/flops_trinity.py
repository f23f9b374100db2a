"""Operations of a train step whose text tower mixes window and full attention
in one stack (configuration ``trinity-mini-text-b16-224``): grouped heads with
per-head norms on q and k and a sigmoid gate on the heads' outputs, a leading
dense SwiGLU layer and sigmoid-routed SwiGLU experts beside a shared one, of
which this chip holds a share. From the configuration's shapes alone, whatever
implements them; recomputation never counts; a step is 3 x forward. Per text
token (a multiply-add is two operations):

- every projection: 2 x its matrix, the gate's among them (the norms, the
  rotation, the gate's sigmoid and product and the router's top-k are no matmul
  of the model's and not counted);
- the attention core, by the pairs its mask ADMITS: 2 (d + d) a query head a
  pair; a full layer admits s (s + 1) / 2 pairs a sequence, a window layer of w
  keys w (w + 1) / 2 + (s - w) w (a query meets its own key and the w - 1
  before it): 14 681 088 against 33 558 528 at 8192 tokens and w = 2048;
- routed experts at the expected load under uniform routing: top_k x held /
  total assignments a token (1.0 here), each 3 matmuls of width x moe_hidden;
  the shared expert runs every token.

The image tower is ``flops.py``'s. ``attention_least_s`` is 0 for the text tower:
none of its layers runs through ``short_attn_fwd`` / ``short_attn_bwd``, so
``attn_text_*`` say nothing there. The two kinds of core have least times of
their own, from the shapes alone, the same whichever form runs them.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops
from flops import mlp_hidden, vision_forward_flops
from flops_kimi import _least_s, expected_assignments_per_token  # noqa: F401


def attn_sizes(t) -> tuple[int, int, int]:
    """(query heads, key / value heads, head size) of an attention layer."""
    return t.num_heads, t.num_kv_heads or t.num_heads, t.head_dim or t.width // t.num_heads


def layer_windows(t) -> tuple[int, ...]:
    """Each layer's window, 0 for a full layer; a window of at least the sequence is a full layer's."""
    windows = tuple(t.attn_windows) or (0,) * t.depth
    return tuple(w if w < t.context_length else 0 for w in windows)


def admitted_pairs(s: int, window: int = 0) -> int:
    """(query, key) pairs one head's mask admits over a sequence of ``s``."""
    w = min(window, s) if window else s
    return w * (w + 1) // 2 + (s - w) * w


def attn_matmul_params(t) -> int:
    """q, k, v, the gate (where the layer has one) and out."""
    h, kv, d = attn_sizes(t)
    gate = t.width * h * d if t.attn_gate else 0
    return t.width * h * d + 2 * t.width * kv * d + gate + h * d * t.width


def core_flops(t, window: int = 0) -> float:
    """Scores and values of one attention layer over one sequence, forward, by the admitted pairs."""
    h, _, d = attn_sizes(t)
    return 2.0 * (d + d) * h * admitted_pairs(t.context_length, window)


def routed_matmul_params(t) -> float:
    """The router, the shared expert and the expected assignments' experts: three matmuls an expert."""
    expert = 3 * t.width * t.moe_hidden
    return t.width * t.moe_experts + t.moe_shared_experts * expert + expected_assignments_per_token(t) * expert


def text_forward_flops(t) -> float:
    if tuple(t.mixers) or t.mlp != "swiglu" or getattr(t, "sublayers", "pair") != "pair":
        raise ValueError("flops_trinity.py counts a stack of attention layers, window or full, each beside a SwiGLU or a "
                         f"routed SwiGLU feed-forward part, got mixers={t.mixers!r}, mlp={t.mlp!r}")
    s, w = t.context_length, t.width
    dense = 3 * w * mlp_hidden(w, t.mlp_ratio)
    total = 0.0
    for i, window in enumerate(layer_windows(t)):
        total += 2.0 * s * attn_matmul_params(t) + core_flops(t, window)
        routed = t.moe_experts and i >= t.leading_dense_layers
        total += 2.0 * s * (routed_matmul_params(t) if routed else dense)
    return total + 2.0 * w * t.embed_dim  # the text head, on the last token


def forward_flops_per_pair(cfg) -> float:
    return vision_forward_flops(cfg.vision) + text_forward_flops(cfg.text)


def train_flops_per_pair(cfg) -> float:
    """Forward + backward = 3 x forward: what MFU counts."""
    return 3.0 * forward_flops_per_pair(cfg)


def attention_least_s(cfg, tower: str, sequences: int, peaks: dict) -> float:
    """The image tower's softmax attention is ``flops.py``'s; the text tower has
    no layer that runs through the fused short-attention kernels: 0."""
    return flops.attention_least_s(cfg, tower, sequences, peaks) if tower == "visual" else 0.0


def _core_least_s(cfg, sequences: int, peaks: dict, windowed: bool, itemsize: int) -> float:
    """Per layer of the kind and caption the larger of 3 x ``core_flops`` over the
    bf16 peak and the bytes of q, k, v, o, o's cotangent and the three gradients,
    once each, k and v at their own (fewer) heads, over the HBM peak."""
    t = cfg.text
    h, kv, d = attn_sizes(t)
    nbytes = (4 * h * d + 4 * kv * d) * itemsize * t.context_length
    return sequences * sum(
        _least_s(3.0 * core_flops(t, w), nbytes, peaks) for w in layer_windows(t) if bool(w) == windowed
    )


def window_core_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for the window layers' cores of ``sequences`` captions,
    forward + backward, recomputation not counted: the band's admitted pairs
    only. 29.3 ms a layer a step of 8 captions at 8192 tokens and w = 2048."""
    return _core_least_s(cfg, sequences, peaks, True, itemsize)


def full_core_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The same for the full layers' cores: the lower triangle. 67.0 ms a layer a
    step of 8 captions at 8192 tokens."""
    return _core_least_s(cfg, sequences, peaks, False, itemsize)
