"""flops.py against values worked out by hand from the published sizes."""

import json
import os
import types

import flops
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]


def config(name):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", name + ".json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"])
    )


# Per layer: 8 s w^2 + 4 s w hidden + 4 s^2 w. B/16 image: s 196, w 768, hidden
# 3072 -> 924844032 + 1849688064 + 118013952 = 2892546048, x 12 layers; patch
# embedding 2*196*768*768 = 231211008; MAP head k, v 4*196*768^2 = 462422016.
B16_VISION = 12 * 2892546048 + 231211008 + 462422016
# Text: s 64 -> 301989888 + 603979776 + 12582912 = 918552576, x 12; head 2*768^2.
B16_TEXT = 12 * 918552576 + 1179648
# so400m image: s 256, w 1152, hidden 4304 -> 2717908992 + 5077204992 + 301989888
# = 8097103872, x 27; patch 2*256*588*1152 = 346816512; MAP 4*256*1152^2.
SO_VISION = 27 * 8097103872 + 346816512 + 1358954496
# Text: s 64 -> 679477248 + 1269301248 + 18874368 = 1967652864, x 27; head 2*1152^2.
SO_TEXT = 27 * 1967652864 + 2654208


@pytest.mark.parametrize("name, vision, text", [
    ("siglip-b16-224", B16_VISION, B16_TEXT),
    ("siglip-so400m-14-224", SO_VISION, SO_TEXT),
])
def test_flops_match_hand_computed(name, vision, text):
    cfg = config(name)
    assert flops.vision_forward_flops(cfg.vision) == vision
    assert flops.text_forward_flops(cfg.text) == text
    assert flops.forward_flops_per_pair(cfg) == vision + text
    assert flops.train_flops_per_pair(cfg) == 3 * (vision + text)


def test_totals_are_the_ones_perf_md_quotes():
    assert flops.forward_flops_per_pair(config("siglip-b16-224")) == 46427996160
    assert flops.forward_flops_per_pair(config("siglip-so400m-14-224")) == 273456857088


def test_fractional_mlp_ratio_rounds_to_the_published_hidden_size():
    assert flops.mlp_hidden(1152, 4304 / 1152) == 4304
    assert flops.mlp_hidden(768, 4) == 3072


@pytest.mark.parametrize("name, tower, s, w, depth", [
    ("siglip-b16-224", "visual", 196, 768, 12), ("siglip-b16-224", "textual", 64, 768, 12),
    ("siglip-so400m-14-224", "visual", 256, 1152, 27), ("siglip-so400m-14-224", "textual", 64, 1152, 27),
])
def test_attention_least_time_by_hand_and_memory_bound_on_the_v5e(name, tower, s, w, depth):
    """What the per-tower rooflines divide: per layer per sequence 12 s^2 w
    operations (scores and values, forward + backward) against 24 s w bytes, the
    larger of the two times; s/2 operations a byte, 98 (image) and 32 (text),
    against the chip's 197e12 / 819e9 = 240, so the bytes decide. 256 pairs."""
    least = flops.attention_least_s(config(name), tower, 256, PEAKS)
    by_bytes = 24.0 * s * w * depth * 256 / 819e9
    by_operations = 12.0 * s * s * w * depth * 256 / 197e12
    assert least == pytest.approx(by_bytes, rel=1e-12) and by_bytes > by_operations
    assert flops.attention_least_s(config(name), tower, 512, PEAKS) == pytest.approx(2 * least, rel=1e-12)


def test_attention_least_time_turns_compute_bound_past_480_tokens():
    """The other branch of the larger-of: at s = 1024 the operations decide."""
    cfg = config("siglip-b16-224")
    cfg.text.context_length = 1024
    assert flops.attention_least_s(cfg, "textual", 1, PEAKS) == pytest.approx(12.0 * 1024**2 * 768 * 12 / 197e12)
