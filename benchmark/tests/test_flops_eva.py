"""flops_eva.py against values worked out by hand from the published sizes, and
the windowed-chunk-attention cores' least time by hand and by which peak bounds it."""

import json
import os
import types

import flops
import flops_eva
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]


def config(name="evabyte-text-b16-224"):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", name + ".json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"])
    )


# Matmul parameters a token crosses in a layer: q, k, v, out 4096 x 4096 each; gate, up, down 4096 x 11008 each.
LAYER_PARAMS = 4 * 16777216 + 3 * 45088768
# A caption and head: 4 windows of 2048 x 2049 / 2 causal pairs; a query of window w meets the 128 summaries of each
# of the w windows before it: 2048 x 128 x (0 + 1 + 2 + 3).
EXACT, SUMMARISED = 4 * 2098176, 2048 * 128 * 6
CORE = 2 * (128 + 128) * 32 * (EXACT + SUMMARISED)  # a caption and layer
POOLING = 6 * 8192 * 4096  # a head's dot with phi (2 d) and two weighted sums (2 x 2 d), 32 heads of 128
LAYER = 8192 * 2 * LAYER_PARAMS + POOLING + CORE
TEXT = 4 * LAYER + 2 * 4096 * 768
B16_VISION = 12 * 2892546048 + 231211008 + 462422016  # test_flops.py


def test_the_tower_by_hand():
    cfg = config()
    t = cfg.text
    assert LAYER_PARAMS == 202375168 and flops.mlp_hidden(t.width, t.mlp_ratio) == 11008
    assert flops_eva.core_pairs(t) == (EXACT, SUMMARISED) == (8392704, 1572864)
    assert flops_eva.eva_core_flops(t) == CORE == 163275866112
    assert flops_eva.eva_pooling_flops(t) == POOLING == 201326592
    assert flops_eva.text_forward_flops(t) == TEXT == 13916774072320
    assert flops_eva.forward_flops_per_pair(cfg) == TEXT + B16_VISION == 13952178257920
    assert flops_eva.train_flops_per_pair(cfg) == 3 * (TEXT + B16_VISION)
    # ISSUE 39: 424.6 MFLOP a text token and layer forward (404.75 + 19.93 + 0.02 = 424.71 exactly), 13.95 TFLOP a
    # pair forward, 41.8 trained (41.86)
    assert round(LAYER / 8192 / 1e6, 2) == 424.71 and round(2 * LAYER_PARAMS / 1e6, 2) == 404.75
    assert round((TEXT + B16_VISION) / 1e12, 2) == 13.95 and round(3 * (TEXT + B16_VISION) / 1e12, 2) == 41.86
    # a query sees on average 1024.5 exact keys and 192 summaries; the summaries are 16 % of the core's pairs
    assert (EXACT / 8192, SUMMARISED / 8192) == (1024.5, 192.0) and round(100 * SUMMARISED / (EXACT + SUMMARISED), 1) == 15.8
    # the core is 4.7 % of the count (ISSUE 39), the text tower 99.7 %
    assert round(100 * 4 * CORE / (TEXT + B16_VISION), 1) == 4.7 and round(TEXT / (TEXT + B16_VISION), 3) == 0.997


@pytest.mark.parametrize("tokens, share", [(4096, 4.2), (8192, 4.7), (32768, 7.4)])
def test_the_cores_share_of_a_layer_grows_with_the_windows(tokens, share):
    """ISSUE 39: 4.2 % at 4096 tokens, 4.7 % at 8192, 7.4 % at the published 32768: no shape makes it most of the work."""
    t = config().text
    t.context_length = tokens
    per_layer = (flops_eva.text_forward_flops(t) - 2 * 4096 * 768) / 4
    assert round(100 * flops_eva.eva_core_flops(t) / per_layer, 1) == share


def test_one_window_has_no_summarised_pair():
    t = config().text
    t.context_length = 2048
    assert flops_eva.core_pairs(t) == (2098176, 0)


def test_another_mixer_is_refused():
    cfg = config()
    cfg.text.mixers = ["eva", "attn", "eva", "eva"]
    with pytest.raises(ValueError, match="eva"):
        flops_eva.text_forward_flops(cfg.text)


def test_the_text_tower_runs_no_short_attention_and_the_image_tower_is_flops_py():
    cfg = config()
    assert flops_eva.attention_least_s(cfg, "textual", 8, PEAKS) == 0.0
    assert flops_eva.attention_least_s(cfg, "visual", 8, PEAKS) == flops.attention_least_s(cfg, "visual", 8, PEAKS) > 0


def test_the_cores_least_time_by_hand():
    cfg = config()
    # A caption and layer: 3 x the admitted pairs' operations; q, k, v, o, o's cotangent and three gradients 8192 x 4096
    # each and the summaries with their gradients 512 x 4096 x 4, bf16, once.
    operations = 3 * CORE
    nbytes = (8 * 8192 + 4 * 512) * 4096 * 2
    assert operations / 197e12 > 3 * nbytes / 819e9  # compute-bound: 885 operations a byte against the chip's 240
    assert round(operations / nbytes) == 885
    assert flops_eva.eva_core_least_s(cfg, 8, PEAKS) == pytest.approx(8 * 4 * operations / 197e12, rel=1e-12)
    assert round(1e3 * flops_eva.eva_core_least_s(cfg, 8, PEAKS), 1) == 79.6  # ms a step
    # where the bytes bound it (one window of 128 tokens, chunks of 16): the larger of the two
    short = config()
    short.text.context_length = short.text.eva_window = 128
    ops_short = 3 * 2 * 256 * 32 * (128 * 129 // 2)
    bytes_short = (8 * 128 + 4 * 8) * 4096 * 2
    assert ops_short / 197e12 < bytes_short / 819e9
    assert flops_eva.eva_core_least_s(short, 1, PEAKS) == pytest.approx(4 * bytes_short / 819e9, rel=1e-12)
