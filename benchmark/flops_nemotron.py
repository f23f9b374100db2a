"""Operations of a train step whose text tower is a stack of one-sub-layer
layers (configuration ``nemotron-3-nano-30b-text-b16-224``): Mamba-2 state-space
mixers ("ssm"), a grouped-head causal attention ("attn") and sigmoid-routed
ungated relu2 experts with a wider shared one ("moe"), of which this chip holds a
share. From the configuration's shapes alone, whatever implements them;
recomputation never counts; a step is 3 x forward. Per text token (a multiply-add
is two operations):

- every projection: 2 x its matrix (the convolution's four taps, the norms, the
  gates' elementwise part and the router's top-k are no matmul and not counted);
- the state-space core, in the chunked form every implementation of it takes
  (``ssm_chunk`` tokens a chunk, the configuration's ``chunk_size``): the chunk's
  scores C B^T once a group (2 L N g), each head's decayed copy of them times its
  x (2 L P h), the chunk's state from x and B and the incoming state's outputs
  from C (2 P N h each);
- the attention core: causal, so a query meets its own and the earlier keys
  only: 2 (d + d) a query head a pair, s (s + 1) / 2 pairs a sequence;
- routed experts at the expected load under uniform routing: top_k x held /
  total assignments a token (0.375 here), each 2 matmuls of width x moe_hidden (no
  gate matrix); the shared expert (2 matmuls of width x moe_shared_hidden) runs
  every token.

The image tower is ``flops.py``'s. ``attention_least_s`` is 0 for the text tower:
its one attention layer runs through no ``short_attn_*`` kernel, so ``attn_text_*``
say nothing there. The two cores have least times of their own, from the shapes
alone, the same whichever form runs them.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops
from flops import vision_forward_flops
from flops_kimi import _least_s, causal_pairs, expected_assignments_per_token  # noqa: F401

FEED_FORWARD = ("moe",)


def ssm_sizes(t) -> tuple[int, int]:
    """(inner, shared): the heads' channels h x P and the groups' g x N."""
    return t.ssm_num_heads * t.ssm_head_dim, t.ssm_groups * t.ssm_state


def ssm_matmul_params(t) -> int:
    """The fused input projection (z, x, B, C, dt) and out."""
    inner, shared = ssm_sizes(t)
    return t.width * (2 * inner + 2 * shared + t.ssm_num_heads) + inner * t.width


def ssm_core_flops_per_token(t) -> float:
    """The recurrence of one "ssm" layer, forward, a token: scores, intra-chunk
    values and the two products with the state."""
    h, p, g, n, chunk = t.ssm_num_heads, t.ssm_head_dim, t.ssm_groups, t.ssm_state, t.ssm_chunk
    return 2.0 * chunk * n * g + 2.0 * chunk * p * h + 4.0 * p * n * h


def attn_sizes(t) -> tuple[int, int, int]:
    """(query heads, key / value heads, head size) of an "attn" layer."""
    return t.num_heads, t.num_kv_heads or t.num_heads, t.head_dim or t.width // t.num_heads


def attn_matmul_params(t) -> int:
    h, kv, d = attn_sizes(t)
    return t.width * h * d + 2 * t.width * kv * d + h * d * t.width


def gqa_core_flops(t) -> float:
    """Scores and values of one "attn" layer over one sequence, forward, by the lower triangle."""
    h, _, d = attn_sizes(t)
    return 2.0 * (d + d) * h * causal_pairs(t.context_length)


def routed_matmul_params(t) -> float:
    """The router, the shared expert and the expected assignments' experts: two matmuls an expert."""
    shared = 2 * t.width * (t.moe_shared_hidden or t.moe_shared_experts * t.moe_hidden) if t.moe_shared_experts else 0
    return t.width * t.moe_experts + shared + expected_assignments_per_token(t) * 2 * t.width * t.moe_hidden


def text_forward_flops(t) -> float:
    if getattr(t, "sublayers", "pair") != "single" or t.mlp != "relu2":
        raise ValueError("flops_nemotron.py counts a stack of one-sub-layer layers with relu2 experts "
                         f"(sublayers='single', mlp='relu2'), got {getattr(t, 'sublayers', 'pair')!r}, {t.mlp!r}")
    s = t.context_length
    total = 0.0
    for kind in t.mixers:
        if kind == "ssm":
            total += s * (2.0 * ssm_matmul_params(t) + ssm_core_flops_per_token(t))
        elif kind == "attn":
            total += 2.0 * s * attn_matmul_params(t) + gqa_core_flops(t)
        elif kind == "moe":
            total += 2.0 * s * routed_matmul_params(t)
        else:
            raise ValueError(f"flops_nemotron.py counts 'ssm', 'attn' and 'moe' layers, got {kind!r}")
    return total + 2.0 * t.width * t.embed_dim  # the text head, on the last token


def forward_flops_per_pair(cfg) -> float:
    return vision_forward_flops(cfg.vision) + text_forward_flops(cfg.text)


def train_flops_per_pair(cfg) -> float:
    """Forward + backward = 3 x forward: what MFU counts."""
    return 3.0 * forward_flops_per_pair(cfg)


def attention_least_s(cfg, tower: str, sequences: int, peaks: dict) -> float:
    """The image tower's softmax attention is ``flops.py``'s; the text tower has
    no layer that runs through the fused short-attention kernels: 0."""
    return flops.attention_least_s(cfg, tower, sequences, peaks) if tower == "visual" else 0.0


def ssm_core_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for the state-space cores of ``sequences`` captions, forward
    + backward, recomputation not counted: the larger of 3 x the core's products
    over the bf16 peak and its compulsory bytes over the HBM peak: x, B and C (the
    tower's dtype) and dt (float32) in, y out, and their cotangents, once each.
    From the shapes alone: the same whichever form runs."""
    t = cfg.text
    inner, shared = ssm_sizes(t)
    per_token = (2 * inner + 2 * shared) * itemsize + t.ssm_num_heads * 4
    layers = sum(m == "ssm" for m in t.mixers)
    return sequences * layers * _least_s(
        3.0 * ssm_core_flops_per_token(t) * t.context_length, 2.0 * per_token * t.context_length, peaks
    )


def gqa_core_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for the grouped attention cores of ``sequences`` captions,
    forward + backward: 3 x ``gqa_core_flops`` over the bf16 peak, or the bytes
    of q, k, v, o and their cotangents, once each, k and v at their own (fewer)
    heads, over the HBM peak."""
    t = cfg.text
    h, kv, d = attn_sizes(t)
    per_token = 2 * (2 * h * d + 2 * kv * d) * itemsize
    layers = sum(m == "attn" for m in t.mixers)
    return sequences * layers * _least_s(3.0 * gqa_core_flops(t), per_token * t.context_length, peaks)
