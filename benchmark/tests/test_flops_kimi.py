"""flops_kimi.py against values worked out by hand from the published sizes, and
its least times by hand and by which peak bounds them."""

import json
import os
import types

import flops
import flops_kimi

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]


def config():
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", "kimi-linear-48b-text-b16-224.json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"])
    )


# Matmul parameters a token crosses. KDA: q, k, v, out 4 x 2304 x 4096 = 37748736; the
# two low-rank gates 2 x (2304 x 128 + 128 x 4096) = 1638400; beta 2304 x 32 = 73728.
KDA_PARAMS = 37748736 + 1638400 + 73728
# MLA: q 2304 x 32 x 192, kv_a 2304 x 576, kv_b 512 x 32 x 256, out 4096 x 2304.
MLA_PARAMS = 14155776 + 1327104 + 4194304 + 9437184
DENSE_PARAMS = 3 * 2304 * 9216
# A routed layer: router 2304 x 256, the shared expert 3 x 2304 x 1024, and 8 x 8 / 256 =
# 0.25 routed experts of the same size.
EXPERT = 3 * 2304 * 1024
ROUTED_PARAMS = 589824 + EXPERT + EXPERT // 4
KDA_CORE = 4 * 128 * 128 * 32  # a token
MLA_CORE = 2 * (192 + 128) * 32 * (1024 * 1025 // 2)  # a caption: the lower triangle
TEXT = 1024 * 2 * (4 * KDA_PARAMS + MLA_PARAMS + DENSE_PARAMS + 4 * ROUTED_PARAMS) + 1024 * 4 * KDA_CORE + MLA_CORE + 2 * 2304 * 768
B16_VISION = 12 * 2892546048 + 231211008 + 462422016  # test_flops.py


def test_the_hybrid_tower_by_hand():
    cfg = config()
    t = cfg.text
    assert flops_kimi.kda_matmul_params(t) == KDA_PARAMS == 39460864
    assert flops_kimi.mla_matmul_params(t) == MLA_PARAMS == 29114368
    assert flops_kimi.expected_assignments_per_token(t) == 0.25
    assert flops_kimi.kda_core_flops(t) == 1024 * KDA_CORE and flops_kimi.mla_core_flops(t) == MLA_CORE
    assert flops_kimi.text_forward_flops(t) == TEXT == 610000044032
    # MFLOP a text token, by part (ISSUE 32: 324, 127, 69, 76; 596 in all)
    per_token = [4 * (2 * KDA_PARAMS + KDA_CORE), 2 * DENSE_PARAMS, 2 * MLA_PARAMS + MLA_CORE / 1024, 4 * 2 * ROUTED_PARAMS]
    assert [round(x / 1e6) for x in per_token] == [324, 127, 69, 75] and round(sum(per_token) / 1e6) == 596
    assert flops_kimi.forward_flops_per_pair(cfg) == TEXT + B16_VISION == 645404229632
    assert flops_kimi.train_flops_per_pair(cfg) == 3 * (TEXT + B16_VISION)
    assert round(TEXT / (TEXT + B16_VISION), 3) == 0.945  # the text tower is the step


def test_holding_every_expert_counts_every_chosen_expert():
    cfg = config()
    cfg.text.moe_experts_held = 0
    assert flops_kimi.expected_assignments_per_token(cfg.text) == 8
    assert flops_kimi.text_forward_flops(cfg.text) == TEXT + 1024 * 2 * 4 * (8 * EXPERT - EXPERT // 4)


def test_the_text_tower_runs_no_short_attention_and_the_image_tower_is_flops_py():
    cfg = config()
    assert flops_kimi.attention_least_s(cfg, "textual", 64, PEAKS) == 0.0
    assert flops_kimi.attention_least_s(cfg, "visual", 64, PEAKS) == flops.attention_least_s(cfg, "visual", 64, PEAKS) > 0


def test_least_times_by_hand():
    cfg = config()
    # KDA, a caption and layer: 3 x 4 dk dv h s operations; q, k, v bf16, g float32, beta float32 in, o bf16 out,
    # 3 x in + 2 x out.
    operations = 3 * 1024 * KDA_CORE
    per_token_in, per_token_out = 3 * 4096 * 2 + 4096 * 4 + 32 * 4, 4096 * 2
    nbytes = 1024 * (3 * per_token_in + 2 * per_token_out)
    by_operations, by_bytes = operations / 197e12, nbytes / 819e9
    assert by_bytes > by_operations  # memory-bound: 45 operations a byte against 240
    assert round(operations / nbytes) == 45
    assert flops_kimi.kda_least_s(cfg, 64, PEAKS) == 64 * 4 * by_bytes
    assert round(1e3 * flops_kimi.kda_least_s(cfg, 64, PEAKS), 1) == 44.7  # ms a step
    # MLA, a caption: 3 x the triangle; q and k 32 x 192, v and o 32 x 128, bf16: 3 (q + k + v) + 3 o.
    operations = 3 * MLA_CORE
    nbytes = 1024 * 32 * 2 * (3 * (192 + 192 + 128) + 3 * 128)
    assert operations / 197e12 > nbytes / 819e9  # compute-bound, just: 256 operations a byte
    assert flops_kimi.mla_least_s(cfg, 64, PEAKS) == 64 * operations / 197e12
    assert round(1e3 * flops_kimi.mla_least_s(cfg, 64, PEAKS), 1) == 10.5
