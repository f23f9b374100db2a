"""Operations of a train step whose text tower has latent attention in every
layer (configuration ``glm-4.7-flash-text-b16-224``): queries through a latent of
their own, a leading dense SwiGLU layer and sigmoid-routed experts with a shared
expert, of which this chip holds a share. From the configuration's shapes alone,
whatever implements them; recomputation never counts; a step is 3 x forward. Per
text token (a multiply-add is two operations):

- every projection: 2 x its matrix (the norms, the rotation of the 64-wide
  parts and the router's top-k are no matmul of the model's and not counted);
- latent attention's core: causal, so a query meets its own and the earlier
  keys only: 2 (dqk + dv) a head a pair, s (s + 1) / 2 pairs a sequence
  (``flops_kimi.mla_core_flops``: the same core);
- routed experts at the expected load under uniform routing: top_k x held /
  total assignments a token (0.5 here), each 3 matmuls of width x moe_hidden;
  the shared expert runs every token.

The image tower is ``flops.py``'s. ``attention_least_s`` is 0 for the text tower:
none of its layers runs through ``short_attn_fwd`` / ``short_attn_bwd``, so
``attn_text_*`` say nothing there. The latent-attention cores' least time is
``flops_kimi.py``'s, which reads the head sizes and counts the "mla" layers.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
from flops import mlp_hidden, vision_forward_flops
from flops_kimi import attention_least_s, expected_assignments_per_token, mla_core_flops, mla_least_s  # noqa: F401


def mla_matmul_params(t) -> int:
    """The queries' two projections (one where the rank is 0), the keys' and
    values' two, and out."""
    h, dqk = t.num_heads, t.mla_qk_nope_dim + t.mla_qk_shared_dim
    queries = t.width * t.mla_q_rank + t.mla_q_rank * h * dqk if t.mla_q_rank else t.width * h * dqk
    return (
        queries + t.width * (t.mla_kv_rank + t.mla_qk_shared_dim)
        + t.mla_kv_rank * h * (t.mla_qk_nope_dim + t.mla_v_dim) + h * t.mla_v_dim * t.width
    )


def text_forward_flops(t) -> float:
    if set(t.mixers) != {"mla"}:
        raise ValueError(f"flops_glm.py counts 'mla' layers, got {t.mixers!r}")
    s, w = t.context_length, t.width
    dense = 3 * w * mlp_hidden(w, t.mlp_ratio)
    expert = 3 * w * t.moe_hidden
    routed = w * t.moe_experts + t.moe_shared_experts * expert + expected_assignments_per_token(t) * expert
    total = 0.0
    for i in range(len(t.mixers)):
        total += 2.0 * s * mla_matmul_params(t) + mla_core_flops(t)
        total += 2.0 * s * (dense if i < t.leading_dense_layers else routed)
    return total + 2.0 * w * t.embed_dim  # the text head, on the last token


def forward_flops_per_pair(cfg) -> float:
    return vision_forward_flops(cfg.vision) + text_forward_flops(cfg.text)


def train_flops_per_pair(cfg) -> float:
    """Forward + backward = 3 x forward: what MFU counts."""
    return 3.0 * forward_flops_per_pair(cfg)
