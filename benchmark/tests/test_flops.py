"""flops.py against values worked out by hand from the published sizes."""

import json
import os
import types

import flops
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


@pytest.mark.parametrize("name, vision, text, attention", [
    ("siglip-b16-224", B16_VISION, B16_TEXT, 12 * 12 * 768 * (196**2 + 64**2)),
    ("siglip-so400m-14-224", SO_VISION, SO_TEXT, 12 * 27 * 1152 * (256**2 + 64**2)),
])
def test_flops_match_hand_computed(name, vision, text, attention):
    cfg = config(name)
    assert flops.vision_forward_flops(cfg.vision) == vision
    assert flops.text_forward_flops(cfg.text) == text
    assert flops.forward_flops_per_pair(cfg) == vision + text
    assert flops.train_flops_per_pair(cfg) == 3 * (vision + text)
    assert flops.attention_flops_per_pair(cfg) == attention


def test_totals_are_the_ones_perf_md_quotes():
    assert flops.forward_flops_per_pair(config("siglip-b16-224")) == 46427996160
    assert flops.forward_flops_per_pair(config("siglip-so400m-14-224")) == 273456857088


def test_fractional_mlp_ratio_rounds_to_the_published_hidden_size():
    assert flops.mlp_hidden(1152, 4304 / 1152) == 4304
    assert flops.mlp_hidden(768, 4) == 3072


def test_attention_is_memory_bound_on_the_v5e():
    """The roofline share divides by the larger of the two bounds; say which:
    12 s^2 w operations over 24 s w bytes is s/2 per byte, 98 (image) and 32
    (text) against the chip's 197e12 / 819e9 = 240."""
    peaks = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]
    for name in ("siglip-b16-224", "siglip-so400m-14-224"):
        cfg = config(name)
        compute_s = flops.attention_flops_per_pair(cfg) / (peaks["bf16_tflops"] * 1e12)
        memory_s = flops.attention_bytes_per_pair(cfg) / (peaks["hbm_gb_per_s"] * 1e9)
        assert memory_s > compute_s
