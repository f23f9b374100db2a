"""flops_glm.py against values worked out by hand from the published sizes, and
the latent-attention cores' least time by hand and by which peak bounds it."""

import json
import os
import types

import flops
import flops_glm
import flops_kimi
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]


def config(name="glm-4.7-flash-text-b16-224"):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", name + ".json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"])
    )


# Matmul parameters a token crosses in a latent-attention layer: q_a 2048 x 768, q_b 768 x 20 x 256,
# kv_a 2048 x (512 + 64), kv_b 512 x 20 x (192 + 256), out 20 x 256 x 2048.
MLA_PARAMS = 1572864 + 3932160 + 1179648 + 4587520 + 10485760
DENSE_PARAMS = 3 * 2048 * 10240
# A routed layer: router 2048 x 64, the shared expert 3 x 2048 x 1536, and 4 x 8 / 64 = 0.5 routed
# experts of the same size.
EXPERT = 3 * 2048 * 1536
ROUTED_PARAMS = 131072 + EXPERT + EXPERT // 2
MLA_CORE = 2 * (256 + 256) * 20 * (4096 * 4097 // 2)  # a caption and layer: the lower triangle
TEXT = 4096 * 2 * (5 * MLA_PARAMS + DENSE_PARAMS + 4 * ROUTED_PARAMS) + 5 * MLA_CORE + 2 * 2048 * 768
B16_VISION = 12 * 2892546048 + 231211008 + 462422016  # test_flops.py


def test_the_tower_by_hand():
    cfg = config()
    t = cfg.text
    assert flops_glm.mla_matmul_params(t) == MLA_PARAMS == 21757952
    assert flops_glm.expected_assignments_per_token(t) == 0.5
    assert flops_glm.mla_core_flops(t) == MLA_CORE == 171840634880
    assert flops_glm.text_forward_flops(t) == TEXT == 2733959544832
    assert flops_glm.forward_flops_per_pair(cfg) == TEXT + B16_VISION
    assert flops_glm.train_flops_per_pair(cfg) == 3 * (TEXT + B16_VISION)
    assert round(3 * (TEXT + B16_VISION) / 1e12, 2) == 8.31  # ISSUE 35: 8.3 TFLOP a pair
    # the step by count (ISSUE 35: the cores 31 %, the attention projections 33 %, the dense layer 19 %,
    # shared and routed experts and routers 17 %)
    whole = TEXT + B16_VISION
    parts = [5 * MLA_CORE, 4096 * 2 * 5 * MLA_PARAMS, 4096 * 2 * DENSE_PARAMS, 4096 * 2 * 4 * ROUTED_PARAMS]
    assert [round(100 * x / whole) for x in parts] == [31, 32, 19, 17]
    assert round(TEXT / whole, 3) == 0.987  # the text tower is the step


def test_one_query_projection_where_the_rank_is_zero():
    """Rank 0 is the unrotated layer's single projection: flops_kimi.py's count of its own cell."""
    kimi = config("kimi-linear-48b-text-b16-224").text
    kimi.mla_q_rank = 0
    assert flops_glm.mla_matmul_params(kimi) == flops_kimi.mla_matmul_params(kimi) == 29114368
    t = config().text
    t.mla_q_rank = 0
    assert flops_glm.mla_matmul_params(t) == MLA_PARAMS - 1572864 - 3932160 + 2048 * 20 * 256


def test_holding_every_expert_counts_every_chosen_expert():
    cfg = config()
    cfg.text.moe_experts_held = 0
    assert flops_glm.expected_assignments_per_token(cfg.text) == 4
    assert flops_glm.text_forward_flops(cfg.text) == TEXT + 4096 * 2 * 4 * (4 * EXPERT - EXPERT // 2)


def test_another_mixer_is_refused():
    cfg = config()
    cfg.text.mixers = ["mla", "kda", "mla", "mla", "mla"]
    with pytest.raises(ValueError, match="mla"):
        flops_glm.text_forward_flops(cfg.text)


def test_the_text_tower_runs_no_short_attention_and_the_image_tower_is_flops_py():
    cfg = config()
    assert flops_glm.attention_least_s(cfg, "textual", 16, PEAKS) == 0.0
    assert flops_glm.attention_least_s(cfg, "visual", 16, PEAKS) == flops.attention_least_s(cfg, "visual", 16, PEAKS) > 0


def test_the_cores_least_time_by_hand():
    cfg = config()
    # A caption and layer: 3 x the triangle; q, k, v and o 20 x 256 each, bf16: 3 (q + k + v) + 3 o.
    operations = 3 * MLA_CORE
    nbytes = 4096 * 20 * 2 * (3 * (256 + 256 + 256) + 3 * 256)
    assert operations / 197e12 > 4 * nbytes / 819e9  # compute-bound: 1024 operations a byte against the chip's 240
    assert round(operations / nbytes) == 1024
    assert flops_glm.mla_least_s(cfg, 16, PEAKS) == pytest.approx(16 * 5 * operations / 197e12, rel=1e-12)
    assert round(1e3 * flops_glm.mla_least_s(cfg, 16, PEAKS), 1) == 209.3  # ms a step
