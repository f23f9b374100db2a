"""flops_nemotron.py against values worked out by hand from the published sizes
(ISSUE 43's numbers), and the two cores' least times by hand and by which peak
bounds them."""

import json
import os
import types

import flops
import flops_nemotron
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]


def config(name="nemotron-3-nano-30b-text-b16-224"):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", name + ".json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"])
    )


# A Mamba-2 layer: the fused input projection 2688 x (4096 + 6144 + 64) and out 4096 x 2688.
SSM_PARAMS = 2688 * 10304 + 4096 * 2688
# Its core a token, chunks of 128: a group's scores 2 x 128 x 128 x 8, each head's intra-chunk values
# 2 x 128 x 64 x 64, the chunk's state and the incoming state's outputs 2 x 64 x 128 x 64 each.
SSM_CORE = 262144 + 1048576 + 2 * 1048576
# The attention layer: q 2688 x 4096, k and v 2688 x 256 each, out 4096 x 2688; the lower triangle.
ATTN_PARAMS = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
GQA_CORE = 2 * (128 + 128) * 32 * (4096 * 4097 // 2)
# A routed layer: router 2688 x 128, the shared expert 2 x 2688 x 3712, 6 x 8 / 128 = 0.375 experts of 2 x 2688 x 1856.
EXPERT = 2 * 2688 * 1856
ROUTED_PARAMS = 2688 * 128 + 2 * 2688 * 3712 + 0.375 * EXPERT
TEXT = 4096 * (3 * (2 * SSM_PARAMS + SSM_CORE) + 2 * ATTN_PARAMS + 3 * 2 * ROUTED_PARAMS) + GQA_CORE + 2 * 2688 * 768
B16_VISION = 12 * 2892546048 + 231211008 + 462422016  # test_flops.py


def test_the_tower_by_hand():
    cfg = config()
    t = cfg.text
    assert flops_nemotron.ssm_matmul_params(t) == SSM_PARAMS == 38707200
    assert flops_nemotron.ssm_core_flops_per_token(t) == SSM_CORE == 3407872
    assert flops_nemotron.attn_matmul_params(t) == ATTN_PARAMS == 23396352
    assert flops_nemotron.gqa_core_flops(t) == GQA_CORE
    assert flops_nemotron.expected_assignments_per_token(t) == 0.375
    assert flops_nemotron.routed_matmul_params(t) == ROUTED_PARAMS
    assert flops_nemotron.text_forward_flops(t) == TEXT
    assert flops_nemotron.forward_flops_per_pair(cfg) == TEXT + B16_VISION
    assert flops_nemotron.train_flops_per_pair(cfg) == 3 * (TEXT + B16_VISION)
    # ISSUE 43: 467 MFLOP a text token forward, about 1.91 TFLOP a caption of 4096, 94 TFLOP a step of 16 pairs
    assert round(TEXT / 4096 / 1e6) == 467 and round(TEXT / 1e12, 2) == 1.91
    assert round(16 * 3 * (TEXT + B16_VISION) / 1e12) == 94
    # by kind, MFLOP a token: the three state-space layers 242 (projections 77.4 each, the core 3.4), the three
    # routed layers 144 (shared 39.9, router 0.7, 0.375 x 20.0), the attention layer 80 (46.8 + 33.6)
    per_token = [3 * (2 * SSM_PARAMS + SSM_CORE), 3 * 2 * ROUTED_PARAMS, 2 * ATTN_PARAMS + GQA_CORE / 4096]
    assert [round(x / 1e6) for x in per_token] == [242, 144, 80]
    assert [round(x / 1e6, 1) for x in (2 * SSM_PARAMS, SSM_CORE, 2 * 2 * 2688 * 3712, 2 * 2688 * 128, 2 * EXPERT,
                                        2 * ATTN_PARAMS, GQA_CORE / 4096)] == [77.4, 3.4, 39.9, 0.7, 20.0, 46.8, 33.6]
    assert round(100 * per_token[0] / sum(per_token)) == 52  # the state-space layers are 52 % of the count
    assert round(100 * 3 * SSM_CORE / sum(per_token), 1) == 2.2 and round(100 * SSM_CORE / (2 * SSM_PARAMS + SSM_CORE), 1) == 4.2


def test_holding_every_expert_counts_every_chosen_expert():
    cfg = config()
    cfg.text.moe_experts_held = 0
    assert flops_nemotron.expected_assignments_per_token(cfg.text) == 6
    assert flops_nemotron.text_forward_flops(cfg.text) == TEXT + 4096 * 3 * 2 * (6 - 0.375) * EXPERT


def test_the_shared_experts_width_defaults_to_the_routed_ones():
    t = config().text
    t.moe_shared_hidden = 0
    assert flops_nemotron.routed_matmul_params(t) == 2688 * 128 + EXPERT + 0.375 * EXPERT


@pytest.mark.parametrize("change, match", [
    (dict(mixers=["ssm", "kda", "moe"]), "kda"),
    (dict(sublayers="pair"), "sublayers"),
    (dict(mlp="swiglu"), "relu2"),
])
def test_another_stack_is_refused(change, match):
    cfg = config()
    for k, v in change.items():
        setattr(cfg.text, k, v)
    with pytest.raises(ValueError, match=match):
        flops_nemotron.text_forward_flops(cfg.text)


def test_the_text_tower_runs_no_short_attention_and_the_image_tower_is_flops_py():
    cfg = config()
    assert flops_nemotron.attention_least_s(cfg, "textual", 16, PEAKS) == 0.0
    assert flops_nemotron.attention_least_s(cfg, "visual", 16, PEAKS) == flops.attention_least_s(cfg, "visual", 16, PEAKS) > 0


def test_the_state_space_cores_least_time_by_hand():
    cfg = config()
    # A caption and layer: 3 x the core's products; x and y 4096 wide, B and C 1024 each in bf16, dt 64 float32:
    # in and out once, and their cotangents once.
    operations = 3 * SSM_CORE * 4096
    nbytes = 2 * 4096 * ((4096 + 1024 + 1024 + 4096) * 2 + 64 * 4)
    assert round(SSM_CORE / ((4096 + 1024 + 1024 + 4096) * 2 + 64 * 4)) == 164  # forward: bound by memory (the chip's 240)
    assert round(operations / nbytes) == 247  # forward + backward: three times the products, twice the bytes
    assert operations / 197e12 > nbytes / 819e9
    assert flops_nemotron.ssm_core_least_s(cfg, 16, PEAKS) == pytest.approx(16 * 3 * operations / 197e12, rel=1e-12)
    assert round(1e3 * flops_nemotron.ssm_core_least_s(cfg, 16, PEAKS), 2) == 10.2  # ms a step
    # at a peak ratio above 247 the bytes bound it
    fast = dict(PEAKS, bf16_tflops=400.0)
    assert flops_nemotron.ssm_core_least_s(cfg, 16, fast) == pytest.approx(16 * 3 * nbytes / 819e9, rel=1e-12)


def test_the_grouped_cores_least_time_by_hand():
    cfg = config()
    operations = 3 * GQA_CORE
    nbytes = 4096 * 2 * 2 * (2 * 32 * 128 + 2 * 2 * 128)  # q, o at 32 heads, k, v at 2; and their cotangents; bf16
    assert operations / 197e12 > 10 * nbytes / 819e9  # compute-bound: 2892 operations a byte
    assert flops_nemotron.gqa_core_least_s(cfg, 16, PEAKS) == pytest.approx(16 * operations / 197e12, rel=1e-12)
    assert round(1e3 * flops_nemotron.gqa_core_least_s(cfg, 16, PEAKS), 1) == 33.5  # ms a step
    # every head its own keys and values: the same products, more bytes
    cfg.text.num_kv_heads = 0
    assert flops_nemotron.attn_sizes(cfg.text) == (32, 32, 128)
    assert flops_nemotron.gqa_core_least_s(cfg, 16, PEAKS) == pytest.approx(16 * operations / 197e12, rel=1e-12)
