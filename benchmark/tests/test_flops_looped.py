"""flops_looped.py against values worked out by hand from the published sizes,
against flops.py where nothing loops, and the readers' arithmetic on recorded
events."""

import collections
import json
import os
import types

import flops
import flops_looped
import reduce
import scopes_looped

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", name + ".json")))
    return types.SimpleNamespace(
        vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"])
    )


# A layer's matmul parameters: 4 x 2048^2 + 3 x 2048 x 5632 = 51380224. Per layer
# application of one caption of 256 tokens: 2 x 256 x 51380224 = 26306674688 in the
# seven matmuls, 4 x 256^2 x 2048 = 536870912 in attention; 8 layers x 4 passes;
# the head 2 x 2048 x 768.
OURO_TEXT = 32 * (26306674688 + 536870912) + 3145728
# The image tower is B/16's (test_flops.py): 12 x 2892546048 + 231211008 + 462422016.
B16_VISION = 12 * 2892546048 + 231211008 + 462422016


def test_looped_tower_by_hand():
    cfg = config("ouro-2.6b-text-b16-224")
    assert flops_looped.layer_applications(cfg.text) == 32
    assert flops_looped.text_forward_flops(cfg.text) == OURO_TEXT
    assert round(OURO_TEXT / 1e9, 1) == 859.0
    assert flops_looped.forward_flops_per_pair(cfg) == OURO_TEXT + B16_VISION
    assert round((OURO_TEXT + B16_VISION) / 1e9, 1) == 894.4
    assert flops_looped.train_flops_per_pair(cfg) == 3 * (OURO_TEXT + B16_VISION)
    assert round(OURO_TEXT / (OURO_TEXT + B16_VISION), 2) == 0.96  # the loop is the work


def test_one_loop_and_gelu_is_flops_py():
    for name in ("siglip-b16-224", "siglip-so400m-14-224"):
        cfg = config(name)
        assert flops_looped.forward_flops_per_pair(cfg) == flops.forward_flops_per_pair(cfg)
        assert flops_looped.train_flops_per_pair(cfg) == flops.train_flops_per_pair(cfg)


def test_attention_least_time_counts_every_application_and_is_memory_bound():
    cfg = config("ouro-2.6b-text-b16-224")
    peaks = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["devices"]["TPU v5 lite"]
    least = flops_looped.attention_least_s(cfg, "textual", 64, peaks)
    by_bytes = 24.0 * 256 * 2048 * 64 * 32 / (peaks["hbm_gb_per_s"] * 1e9)
    by_operations = 12.0 * 256 * 256 * 2048 * 64 * 32 / (peaks["bf16_tflops"] * 1e12)
    assert least == by_bytes > by_operations
    cfg.text.loops = 1
    assert flops_looped.attention_least_s(cfg, "textual", 64, peaks) == least / 4
    # Nothing loops in the image tower, or where loops = 1: flops.py's, tower by tower.
    for tower in ("visual", "textual"):
        assert flops_looped.attention_least_s(cfg, tower, 64, peaks) == flops.attention_least_s(cfg, tower, 64, peaks)


def test_the_readers_cut_a_step_by_loop_and_rope():
    """`scopes_looped.split_events` on hand-made events: one step of 100 us; an
    operation counts under `loop` and under `rope` by whole components of its jax
    path, in the text tower only, nested operations by their self time."""
    Event = reduce.Event
    stack = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/encoder._looped/while/body/closed_call/loop.<lambda>/loop"
    back = stack.replace("jvp(SigLIP)", "transpose(jvp(SigLIP))")

    def op(start, dur, path, category="non-fusion elementwise"):
        return Event("%x = f32[] x()", start, dur, {"tf_op": path, "hlo_category": category})

    ops = [
        op(0, 60, stack + "/while", "while"),  # the layer scan: self time 60 - 10 - 20 - 5
        op(0, 10, stack + "/while/body/closed_call/blocks/block/attn/rope/mul"),
        op(10, 20, stack + "/while/body/closed_call/blocks/block/attn/short_attn_fwd", "custom-call"),
        op(30, 5, stack + "/while/body/closed_call/blocks/block/mlp/wg/dot_general", "convolution fusion"),
        op(60, 10, back + "/while/body/closed_call/checkpoint/blocks/block/attn/rope/mul"),
        op(70, 10, "jit(step)/jvp(SigLIP)/textual/token_embed/take"),  # the tower, outside the loop
        op(80, 10, "jit(step)/jvp(SigLIP)/visual/encoder/block0/attn/short_attn_fwd", "custom-call"),
        op(90, 5, "jit(step)/optimizer/loop_fusion"),  # `loop` in a component is not the component
    ]
    modules = [Event("jit_step", 0, 100, {})]
    groups = collections.Counter(reduce.group_of(e) for e in ops)
    assert groups["custom_call"] == 2 and groups["matmul"] == 1
    got = scopes_looped.split_events(ops, modules)
    assert got["steps"] == 1
    ns = {k: round(v * 1e9, 6) for k, v in got.items() if k != "steps"}
    assert ns == {"loop": 60 + 10, "rope": 10 + 10}
