"""Operations of a train step whose text tower is a hybrid stack (configuration
``kimi-linear-48b-text-b16-224``): gated delta-rule layers ("kda"), a latent
attention layer ("mla"), a leading dense SwiGLU layer and sigmoid-routed experts
with a shared expert, of which this chip holds a share. From the configuration's
shapes alone, whatever implements them; recomputation never counts; a step is 3 x
forward. Per text token (a multiply-add is two operations):

- every projection: 2 x its matrix (the convolution's four taps, the norms, the
  gates' elementwise part and the router's top-k are no matmul and not counted);
- the delta rule: 4 dk dv a head, the two products with the state (k^T S and
  q^T S / the rank-one write) that no form of the recurrence avoids; a chunked
  form's intra-chunk products are its own choice and not counted;
- latent attention's core: causal, so a query meets its own and the earlier
  keys only: 2 (dqk + dv) a head a pair, s (s + 1) / 2 pairs a sequence;
- routed experts at the expected load under uniform routing: top_k x held /
  total assignments a token (0.25 here), each 3 matmuls of width x moe_hidden;
  the shared experts run every token.

The image tower is ``flops.py``'s. ``attention_least_s`` is 0 for the text tower:
none of its layers runs through ``short_attn_fwd`` / ``short_attn_bwd``, so
``attn_text_*`` say nothing there. The new cores have least times of their own.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops
from flops import mlp_hidden, vision_forward_flops


def kda_matmul_params(t) -> int:
    """q, k, v and out; the two low-rank gates (through ``kda_head_dim``
    channels); beta."""
    w, hd, d = t.width, t.num_heads * t.kda_head_dim, t.kda_head_dim
    return 4 * w * hd + 2 * (w * d + d * hd) + w * t.num_heads


def mla_matmul_params(t) -> int:
    h, dqk = t.num_heads, t.mla_qk_nope_dim + t.mla_qk_shared_dim
    return (
        t.width * h * dqk + t.width * (t.mla_kv_rank + t.mla_qk_shared_dim)
        + t.mla_kv_rank * h * (t.mla_qk_nope_dim + t.mla_v_dim) + h * t.mla_v_dim * t.width
    )


def expected_assignments_per_token(t) -> float:
    """Assignments to experts held here a token makes under uniform routing."""
    return t.moe_num_selected * (t.moe_experts_held or t.moe_experts) / t.moe_experts


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def kda_core_flops(t) -> float:
    """The recurrence of one "kda" layer over one sequence, forward."""
    return 4.0 * t.kda_head_dim * t.kda_head_dim * t.num_heads * t.context_length


def mla_core_flops(t) -> float:
    """Scores and values of one "mla" layer over one sequence, forward, by the
    lower triangle."""
    dqk = t.mla_qk_nope_dim + t.mla_qk_shared_dim
    return 2.0 * (dqk + t.mla_v_dim) * t.num_heads * causal_pairs(t.context_length)


def text_forward_flops(t) -> float:
    s, w = t.context_length, t.width
    dense = 3 * w * mlp_hidden(w, t.mlp_ratio)
    expert = 3 * w * t.moe_hidden
    routed = w * t.moe_experts + t.moe_shared_experts * expert + expected_assignments_per_token(t) * expert
    total = 0.0
    for i, mixer in enumerate(t.mixers):
        if mixer == "kda":
            total += 2.0 * s * kda_matmul_params(t) + kda_core_flops(t)
        elif mixer == "mla":
            total += 2.0 * s * mla_matmul_params(t) + mla_core_flops(t)
        else:
            raise ValueError(f"flops_kimi.py counts 'kda' and 'mla' layers, got {mixer!r}")
        total += 2.0 * s * (dense if i < t.leading_dense_layers else routed)
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


def _least_s(operations: float, nbytes: float, peaks: dict) -> float:
    return max(operations / (peaks["bf16_tflops"] * 1e12), nbytes / (peaks["hbm_gb_per_s"] * 1e9))


def kda_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for the delta-rule cores of ``sequences`` captions, forward
    + backward: the larger of 3 x ``kda_core_flops`` over the bf16 peak and the
    bytes over the HBM peak. Forward reads q, k, v (the tower's dtype), g and
    beta (float32) and writes o; backward reads those five and o's cotangent
    and writes five gradients: 3 x in + 2 x out. Bound by memory at dk = dv =
    128 (45 operations a byte against the chip's 240)."""
    t = cfg.text
    hd = t.num_heads * t.kda_head_dim
    per_token_in = 3 * hd * itemsize + hd * 4 + t.num_heads * 4
    per_token_out = hd * itemsize
    layers = sum(m == "kda" for m in t.mixers)
    return sequences * layers * _least_s(
        3.0 * kda_core_flops(t), t.context_length * (3.0 * per_token_in + 2.0 * per_token_out), peaks
    )


def mla_least_s(cfg, sequences: int, peaks: dict, itemsize: int = 2) -> float:
    """The least time for the latent-attention cores of ``sequences`` captions,
    forward + backward: 3 x ``mla_core_flops`` over the bf16 peak, or the bytes:
    forward reads q, k, v and writes o; backward reads those four and o's
    cotangent and writes three gradients: 3 x (q + k + v) + 3 x o."""
    t = cfg.text
    dqk = t.mla_qk_nope_dim + t.mla_qk_shared_dim
    per_token = 3 * (2 * dqk + t.mla_v_dim) + 3 * t.mla_v_dim
    layers = sum(m == "mla" for m in t.mixers)
    return sequences * layers * _least_s(
        3.0 * mla_core_flops(t), t.context_length * t.num_heads * per_token * itemsize, peaks
    )
