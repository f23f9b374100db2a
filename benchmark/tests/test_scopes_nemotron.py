"""scopes_nemotron.py: a step cut by the one-sub-layer tower's six names on
hand-made events, the two cores' rooflines by hand, the readers silent on a trace
recorded before the names existed and where the count has no least time, and
every share of the new cell in (0, 100]."""

import json
import os
import types

import harness
import pytest
import reduce
import scopes_nemotron

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
CELL = "nemotron-b16-p16-s4096"
READERS = ("ssm_ms", "ssm_conv_ms", "ssm_core_ms", "ssm_core_roofline_pct", "gqa_core_ms", "gqa_core_roofline_pct",
           "relu2_moe_ms", "relu2_moe_route_ms")
Event = reduce.Event


def op(start, dur, path, category="non-fusion elementwise"):
    return Event("%x = f32[] x()", start, dur, {"tf_op": path, "hlo_category": category})


def unrolled_step(scale: float = 1.0):
    """One step of 1000 (x scale) ns of an unrolled text tower of one-sub-layer
    layers: forward, remat's forward and backward of a state-space layer, the
    attention layer and a routed layer."""
    fwd = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/"
    bwd = "jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/textual/encoder/"
    ops = [
        op(0, 50, fwd + "block0/ssm/in_proj/dot_general", "convolution fusion"),
        op(50, 20, fwd + "block0/ssm/ssm_conv/mul"),
        op(70, 60, fwd + "block0/ssm/ssm_core/checkpoint/dot_general", "convolution fusion"),
        op(130, 20, fwd + "block0/ssm/ssm_core/checkpoint/while/body/mul"),
        op(150, 10, fwd + "block0/ssm/rsqrt"),
        op(160, 30, fwd + "block0/ssm/out/dot_general", "convolution fusion"),
        op(190, 10, fwd + "block0/ln1/mul"),  # the layer's one norm: not the mixer's
        op(200, 30, fwd + "block5/attn/q/dot_general", "convolution fusion"),
        op(230, 40, fwd + "block5/attn/attn_core/mla_attn_fwd", "custom-call"),
        op(270, 30, fwd + "block1/moe/moe_route/sort"),
        op(300, 40, fwd + "block1/moe/while/body/dot_general", "convolution fusion"),
        op(340, 30, fwd + "block1/moe/shared/wi/dot_general", "convolution fusion"),
        op(370, 80, bwd + "block0/ssm/ssm_core/checkpoint/rematted_computation/dot_general", "convolution fusion"),  # remat's forward
        op(450, 140, bwd + "block0/ssm/ssm_core/checkpoint/dot_general", "convolution fusion"),
        op(590, 30, bwd + "block0/ssm/ssm_conv/mul"),
        op(620, 90, bwd + "block5/attn/attn_core/mla_attn_bwd", "custom-call"),
        op(710, 10, bwd + "block5/attn/attn_core/reduce_sum"),  # a group's dk and dv summed
        op(720, 50, bwd + "block1/moe/while/body/moe_route/scatter-add"),
        op(770, 40, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/attn/attn_core/short_attn_fwd", "custom-call"),  # not the text tower
        op(810, 20, "jit(step)/jvp(SigLIP)/textual/token_embed/take"),  # the tower, under none of the names
        op(830, 30, "jit(step)/optimizer/ssm_fusion"),  # a name inside a component is not the component
    ]
    ops = [Event(e.name, e.start_ns * scale, e.dur_ns * scale, e.stats) for e in ops]
    return ops, [Event("jit_step", 0, 1000 * scale, {})]


def context(ops, modules, count="flops_nemotron", pairs=16):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", "nemotron-3-nano-30b-text-b16-224.json")))
    cfg = types.SimpleNamespace(vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"]))
    return {
        "trace": {"plane": {"ops": ops, "modules": modules}}, "peaks": harness.peaks_for("TPU v5 lite"),
        "counters": {"count": harness.load_module(os.path.join(BENCH_DIR, count + ".py")), "cfg": cfg, "pairs_per_chip_per_step": pairs},
    }


def read(ctx, name):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read(ctx)


def test_the_readers_cut_a_step_by_the_six_names():
    got = scopes_nemotron.split_events(*unrolled_step())
    assert got.pop("steps") == 1
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "ssm": 50 + 20 + 60 + 20 + 10 + 30 + 80 + 140 + 30, "ssm_conv": 20 + 30, "ssm_core": 60 + 20 + 80 + 140,
        "attn_core": 40 + 90 + 10, "moe": 30 + 40 + 30 + 50, "moe_route": 30 + 50,
    }


def test_the_cores_rooflines_are_the_counts_least_times_over_their_times():
    import flops_nemotron

    ctx = context(*unrolled_step(scale=1e6))  # a step of 1 s: the state-space cores take 0.3 s, the attention core 0.14
    assert read(ctx, "ssm_ms") == pytest.approx(440.0) and read(ctx, "ssm_conv_ms") == pytest.approx(50.0)
    assert read(ctx, "ssm_core_ms") == pytest.approx(300.0) and read(ctx, "gqa_core_ms") == pytest.approx(140.0)
    assert read(ctx, "relu2_moe_ms") == pytest.approx(150.0) and read(ctx, "relu2_moe_route_ms") == pytest.approx(80.0)
    cfg, peaks = ctx["counters"]["cfg"], ctx["peaks"]
    assert read(ctx, "ssm_core_roofline_pct") == pytest.approx(100 * 1e3 * flops_nemotron.ssm_core_least_s(cfg, 16, peaks) / 300.0)
    assert read(ctx, "gqa_core_roofline_pct") == pytest.approx(100 * 1e3 * flops_nemotron.gqa_core_least_s(cfg, 16, peaks) / 140.0)
    assert round(read(ctx, "ssm_core_roofline_pct"), 2) == 3.4 and round(read(ctx, "gqa_core_roofline_pct"), 1) == 23.9


def test_every_reader_of_the_cell_reads_the_hand_made_step():
    ctx = context(*unrolled_step(scale=1e6))
    cell = harness.Cell(CELL, rehearse=False)
    listed = {m["name"] for m in cell.metrics("per_layer") if m.get("workloads") == [CELL]}
    assert listed == set(READERS)
    assert all(read(ctx, name) > 0 for name in READERS)
    of_the_cell = {m["name"] for m in cell.metrics("per_layer")}
    others = {m["name"] for m in cell.manifest["per_layer"] if "workloads" in m and CELL not in m["workloads"]}
    assert of_the_cell.isdisjoint(others) and {"mla_ms", "kda_ms", "moe_ms", "eva_ms", "attn_text_roofline_pct"} <= others
    for name in ("attn_text_fwd_ms", "attn_text_bwd_ms", "attn_text_roofline_pct", "mla_ms", "kda_core_ms", "eva_core_ms"):
        assert read(ctx, name) is None, name
    assert read(ctx, "attn_vision_fwd_ms") == pytest.approx(40.0)
    # the manifest's entries are the readers' META, letter for letter
    for m in cell.manifest["per_layer"]:
        if m["name"] in READERS:
            assert harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py")).META == m


def test_the_readers_are_silent_without_the_names_or_without_the_count():
    """On a trace recorded on the chip before the names existed (PR 22's, what the
    parent of this PR gives) and under a count module that has no least time for
    the cores: nothing, and no error."""
    trace = reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-1chip-scoped.xplane.pb"), n_devices=1)
    ctx = context(None, None)
    ctx["trace"] = trace
    assert [read(ctx, name) for name in READERS] == [None] * len(READERS)
    ctx = context(*unrolled_step(scale=1e6), count="flops")
    assert read(ctx, "ssm_core_ms") == pytest.approx(300.0) and read(ctx, "gqa_core_ms") == pytest.approx(140.0)
    assert read(ctx, "ssm_core_roofline_pct") is None and read(ctx, "gqa_core_roofline_pct") is None


@pytest.mark.parametrize("scale", [1e5, 1e6, 1e7, 1e8], ids=["0.1s", "1s", "10s", "100s"])
def test_no_share_of_a_roofline_or_a_peak_of_the_cell_leaves_0_100(scale):
    """Every reader of the new cell whose name holds `roofline` or `mfu`, on steps
    whose state-space cores take 0.03 to 30 s (the least time is 0.0102 s) and whose
    attention core takes 0.014 to 14 s (the least time 0.0335 s would pass 100 % at
    the shortest: a step that short the chip cannot make): nothing, or a share in (0, 100]."""
    ctx = context(*unrolled_step(scale=scale))
    cell = harness.Cell(CELL, rehearse=False)
    shares = [m["name"] for m in cell.metrics("per_layer") if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(shares) == ["attn_vision_roofline_pct", "gqa_core_roofline_pct", "ssm_core_roofline_pct"]
    got = {name: read(ctx, name) for name in shares}
    if scale == 1e5:  # shorter than the chip could be: the share says so, it is not clipped
        assert got["gqa_core_roofline_pct"] > 100
        return
    for name, value in got.items():
        assert value is None or 0 < value <= 100, (name, value)
    assert got["ssm_core_roofline_pct"] and got["gqa_core_roofline_pct"]
