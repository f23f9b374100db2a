"""flops_trinity.py against values worked out by hand from the published sizes
(ISSUE 47's numbers), and the two kinds of core's least times by hand and by
which peak bounds them."""

import json
import os
import types

import flops
import flops_trinity
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]


def config(name="trinity-mini-text-b16-224"):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", name + ".json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]),
        text=types.SimpleNamespace(**{"mixers": (), "sublayers": "pair", **raw["text"]}),
    )


S = 8192
# An attention layer: q 2048 x 4096, k and v 2048 x 512 each, the gate 2048 x 4096, out 4096 x 2048.
ATTN_PARAMS = 2048 * 4096 + 2 * 2048 * 512 + 2048 * 4096 + 4096 * 2048
# The pairs a head's mask admits: the lower triangle, and the band of 2048 keys that end in the query's own.
FULL_PAIRS = S * (S + 1) // 2
WINDOW_PAIRS = 2048 * 2049 // 2 + (S - 2048) * 2048
FULL_CORE = 2 * (128 + 128) * 32 * FULL_PAIRS
WINDOW_CORE = 2 * (128 + 128) * 32 * WINDOW_PAIRS
# The dense layer's SwiGLU 3 x 2048 x 6144; a routed layer: router 2048 x 128, the shared expert and
# 8 x 16 / 128 = 1.0 expert of 3 x 2048 x 1024 each.
DENSE = 3 * 2048 * 6144
EXPERT = 3 * 2048 * 1024
ROUTED_PARAMS = 2048 * 128 + EXPERT + 1.0 * EXPERT
TEXT = S * (5 * 2 * ATTN_PARAMS + 2 * DENSE + 4 * 2 * ROUTED_PARAMS) + 4 * WINDOW_CORE + FULL_CORE + 2 * 2048 * 768
B16_VISION = 12 * 2892546048 + 231211008 + 462422016  # test_flops.py


def test_the_tower_by_hand():
    cfg = config()
    t = cfg.text
    assert flops_trinity.attn_matmul_params(t) == ATTN_PARAMS == 27262976
    assert flops_trinity.layer_windows(t) == (2048, 2048, 0, 2048, 2048)
    assert flops_trinity.admitted_pairs(S) == FULL_PAIRS == 33558528
    assert flops_trinity.admitted_pairs(S, 2048) == WINDOW_PAIRS == 14681088
    assert flops_trinity.core_flops(t) == FULL_CORE and flops_trinity.core_flops(t, 2048) == WINDOW_CORE
    assert flops_trinity.expected_assignments_per_token(t) == 1.0
    assert flops_trinity.routed_matmul_params(t) == ROUTED_PARAMS
    assert flops_trinity.text_forward_flops(t) == TEXT
    assert flops_trinity.forward_flops_per_pair(cfg) == TEXT + B16_VISION
    assert flops_trinity.train_flops_per_pair(cfg) == 3 * (TEXT + B16_VISION)
    # ISSUE 47: 635 MFLOP a text token forward, 5.24 TFLOP a pair forward, 15.7 trained, 0.64 s of least time a step of 8
    assert round(TEXT / S / 1e6) == 635 and round((TEXT + B16_VISION) / 1e12, 2) == 5.24
    assert round(3 * (TEXT + B16_VISION) / 1e12, 1) == 15.7
    assert round(8 * 3 * (TEXT + B16_VISION) / 197e12, 2) == 0.64
    # by part, MFLOP a token: the projections with the gate 5 x 54.5, the cores 185 (a window layer 29.4, the full
    # one 67.1), the dense SwiGLU 75.5, the four routed layers 4 x 25.7 (shared 12.6, router 0.5, 1.0 x 12.6)
    parts = [5 * 2 * ATTN_PARAMS, (4 * WINDOW_CORE + FULL_CORE) / S, 2 * DENSE, 4 * 2 * ROUTED_PARAMS]
    assert [round(x / 1e6) for x in parts] == [273, 185, 75, 103]  # 75.5: the issue's 76 rounds half up
    assert [round(x / 1e6, 1) for x in (2 * ATTN_PARAMS, WINDOW_CORE / S, FULL_CORE / S, 2 * DENSE, 2 * EXPERT, 2 * 2048 * 128,
                                        2 * ROUTED_PARAMS)] == [54.5, 29.4, 67.1, 75.5, 12.6, 0.5, 25.7]
    assert [round(100 * x / sum(parts)) for x in parts] == [43, 29, 12, 16]
    assert round(100 * (parts[0] + parts[1]) / sum(parts)) == 72  # the gated, normed, window-or-full attention
    assert round(100 * WINDOW_PAIRS / FULL_PAIRS, 1) == 43.7 and round(FULL_PAIRS / WINDOW_PAIRS, 1) == 2.3


def test_a_window_of_at_least_the_sequence_is_a_full_layer():
    t = config().text
    t.attn_windows = [8192, 9000, 0, 2048, 1]
    assert flops_trinity.layer_windows(t) == (0, 0, 0, 2048, 1)
    assert flops_trinity.admitted_pairs(S, S) == flops_trinity.admitted_pairs(S, 3 * S) == FULL_PAIRS
    assert flops_trinity.admitted_pairs(S, 1) == S  # a token and itself
    t.attn_windows = []
    assert flops_trinity.layer_windows(t) == (0,) * 5
    assert flops_trinity.text_forward_flops(t) == TEXT + 4 * (FULL_CORE - WINDOW_CORE)


def test_holding_every_expert_counts_every_chosen_expert_and_no_gate_no_gate():
    cfg = config()
    cfg.text.moe_experts_held = 0
    assert flops_trinity.expected_assignments_per_token(cfg.text) == 8
    assert flops_trinity.text_forward_flops(cfg.text) == TEXT + S * 4 * 2 * 7 * EXPERT
    cfg = config()
    cfg.text.attn_gate = False
    assert flops_trinity.attn_matmul_params(cfg.text) == ATTN_PARAMS - 2048 * 4096
    assert flops_trinity.text_forward_flops(cfg.text) == TEXT - S * 5 * 2 * 2048 * 4096


@pytest.mark.parametrize("change, match", [
    (dict(mixers=["mla"] * 5), "mla"),
    (dict(sublayers="single"), "stack of attention layers"),
    (dict(mlp="relu2"), "relu2"),
])
def test_another_stack_is_refused(change, match):
    cfg = config()
    for k, v in change.items():
        setattr(cfg.text, k, v)
    with pytest.raises(ValueError, match=match):
        flops_trinity.text_forward_flops(cfg.text)


def test_the_text_tower_runs_no_short_attention_and_the_image_tower_is_flops_py():
    cfg = config()
    assert flops_trinity.attention_least_s(cfg, "textual", 8, PEAKS) == 0.0
    assert flops_trinity.attention_least_s(cfg, "visual", 8, PEAKS) == flops.attention_least_s(cfg, "visual", 8, PEAKS) > 0


def test_the_cores_least_times_by_hand():
    cfg = config()
    nbytes = S * 2 * (4 * 32 * 128 + 4 * 4 * 128)  # q, o, do, dq at 32 heads; k, v, dk, dv at 4; bf16
    for pairs, layers, least, ms in ((WINDOW_PAIRS, 4, flops_trinity.window_core_least_s, 29.3),
                                     (FULL_PAIRS, 1, flops_trinity.full_core_least_s, 67.0)):
        operations = 3 * 2 * (128 + 128) * 32 * pairs
        assert operations / 197e12 > 9 * nbytes / 819e9  # compute-bound: over 2000 operations a byte
        assert least(cfg, 8, PEAKS) == pytest.approx(8 * layers * operations / 197e12, rel=1e-12)
        assert round(1e3 * least(cfg, 8, PEAKS) / layers, 1) == ms  # ms a layer a step of 8 captions
    # where the bytes bound it: a window of 8 keys
    cfg.text.attn_windows = [8, 8, 0, 8, 8]
    assert flops_trinity.window_core_least_s(cfg, 8, PEAKS) == pytest.approx(8 * 4 * nbytes / 819e9, rel=1e-12)
    # a stack with no window layer has no window core, and the other way about
    cfg.text.attn_windows = [0] * 5
    assert flops_trinity.window_core_least_s(cfg, 8, PEAKS) == 0.0
    assert flops_trinity.full_core_least_s(cfg, 8, PEAKS) == pytest.approx(5 * 8 * 3 * FULL_CORE / 197e12, rel=1e-12)
