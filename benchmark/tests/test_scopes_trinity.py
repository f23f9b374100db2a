"""scopes_trinity.py: a step cut by the window / full attention tower's seven
names on hand-made events, the two kinds of core's rooflines by hand, the
readers silent on a trace recorded before the names existed and where the count
has no least time, and every share of the new cell in (0, 100]."""

import json
import os
import types

import harness
import pytest
import reduce
import scopes_trinity

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
CELL = "trinity-b16-p8-s8192"
READERS = ("gattn_ms", "window_core_ms", "window_core_roofline_pct", "full_core_ms", "full_core_roofline_pct",
           "attn_gate_ms", "gattn_moe_ms", "gattn_moe_route_ms", "gattn_rope_ms")
Event = reduce.Event


def op(start, dur, path, category="non-fusion elementwise"):
    return Event("%x = f32[] x()", start, dur, {"tf_op": path, "hlo_category": category})


def unrolled_step(scale: float = 1.0):
    """One step of 1000 (x scale) ns of an unrolled text tower: forward and
    backward of a window layer with the dense MLP, the full layer with experts."""
    fwd = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/"
    bwd = "jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/textual/encoder/"
    ops = [
        op(0, 30, fwd + "block0/attn/q/dot_general", "convolution fusion"),
        op(30, 10, fwd + "block0/attn/attn_gate/q_norm/mul"),
        op(40, 10, fwd + "block0/attn/rope/mul"),
        op(50, 40, fwd + "block0/attn/window_attn_core/mla_attn_fwd", "custom-call"),
        op(90, 30, fwd + "block0/attn/gate/dot_general", "convolution fusion"),
        op(120, 10, fwd + "block0/attn/attn_gate/mul"),
        op(130, 20, fwd + "block0/attn/out/dot_general", "convolution fusion"),
        op(150, 10, fwd + "block0/ln1_post/mul"),  # a layer's norm: not the attention's
        op(160, 40, fwd + "block0/mlp/wi/dot_general", "convolution fusion"),
        op(200, 30, fwd + "block2/attn/q/dot_general", "convolution fusion"),
        op(230, 90, fwd + "block2/attn/attn_core/mla_attn_fwd", "custom-call"),
        op(320, 30, fwd + "block2/moe/moe_route/sort"),
        op(350, 40, fwd + "block2/moe/while/body/dot_general", "convolution fusion"),
        op(390, 30, fwd + "block2/moe/shared/wi/dot_general", "convolution fusion"),
        op(420, 10, fwd + "block2/ln2_post/mul"),
        op(430, 50, bwd + "block2/moe/while/body/moe_route/scatter-add"),
        op(480, 200, bwd + "block2/attn/attn_core/mla_attn_bwd", "custom-call"),
        op(680, 10, bwd + "block2/attn/attn_core/reduce_sum"),  # a group's dk and dv summed
        op(690, 90, bwd + "block0/attn/window_attn_core/mla_attn_bwd", "custom-call"),
        op(780, 10, bwd + "block0/attn/window_attn_core/reduce_sum"),
        op(790, 20, bwd + "block0/attn/attn_gate/mul"),
        op(810, 40, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/attn/attn_core/short_attn_fwd", "custom-call"),  # not the text tower
        op(850, 20, "jit(step)/jvp(SigLIP)/textual/token_embed/take"),  # the tower, under none of the names
        op(870, 30, "jit(step)/optimizer/attn_gate_fusion"),  # a name inside a component is not the component
    ]
    ops = [Event(e.name, e.start_ns * scale, e.dur_ns * scale, e.stats) for e in ops]
    return ops, [Event("jit_step", 0, 1000 * scale, {})]


def context(ops, modules, count="flops_trinity", pairs=8):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", "trinity-mini-text-b16-224.json")))
    text = types.SimpleNamespace(**{"mixers": (), "sublayers": "pair", **raw["text"]})
    cfg = types.SimpleNamespace(vision=types.SimpleNamespace(**raw["vision"]), text=text)
    return {
        "trace": {"plane": {"ops": ops, "modules": modules}}, "peaks": harness.peaks_for("TPU v5 lite"),
        "counters": {"count": harness.load_module(os.path.join(BENCH_DIR, count + ".py")), "cfg": cfg, "pairs_per_chip_per_step": pairs},
    }


def read(ctx, name):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read(ctx)


def test_the_readers_cut_a_step_by_the_seven_names():
    got = scopes_trinity.split_events(*unrolled_step())
    assert got.pop("steps") == 1
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "attn": 30 + 10 + 10 + 40 + 30 + 10 + 20 + 30 + 90 + 200 + 10 + 90 + 10 + 20,
        "window_attn_core": 40 + 90 + 10, "attn_core": 90 + 200 + 10, "attn_gate": 10 + 10 + 20, "rope": 10,
        "moe": 30 + 40 + 30 + 50, "moe_route": 30 + 50,
    }


def test_the_cores_rooflines_are_the_counts_least_times_over_their_times():
    import flops_trinity

    ctx = context(*unrolled_step(scale=1e6))  # a step of 1 s: the window cores take 0.14 s, the full core 0.3
    assert read(ctx, "gattn_ms") == pytest.approx(600.0) and read(ctx, "attn_gate_ms") == pytest.approx(40.0)
    assert read(ctx, "gattn_rope_ms") == pytest.approx(10.0)
    assert read(ctx, "window_core_ms") == pytest.approx(140.0) and read(ctx, "full_core_ms") == pytest.approx(300.0)
    assert read(ctx, "gattn_moe_ms") == pytest.approx(150.0) and read(ctx, "gattn_moe_route_ms") == pytest.approx(80.0)
    cfg, peaks = ctx["counters"]["cfg"], ctx["peaks"]
    assert read(ctx, "window_core_roofline_pct") == pytest.approx(100 * 1e3 * flops_trinity.window_core_least_s(cfg, 8, peaks) / 140.0)
    assert read(ctx, "full_core_roofline_pct") == pytest.approx(100 * 1e3 * flops_trinity.full_core_least_s(cfg, 8, peaks) / 300.0)
    assert round(read(ctx, "window_core_roofline_pct"), 1) == 83.7 and round(read(ctx, "full_core_roofline_pct"), 1) == 22.3


def test_every_reader_of_the_cell_reads_the_hand_made_step():
    ctx = context(*unrolled_step(scale=1e6))
    cell = harness.Cell(CELL, rehearse=False)
    listed = {m["name"] for m in cell.metrics("per_layer") if m.get("workloads") == [CELL]}
    assert listed == set(READERS)
    assert all(read(ctx, name) > 0 for name in READERS)
    of_the_cell = {m["name"] for m in cell.metrics("per_layer")}
    others = {m["name"] for m in cell.manifest["per_layer"] if "workloads" in m and CELL not in m["workloads"]}
    assert of_the_cell.isdisjoint(others) and {"mla_ms", "kda_ms", "moe_ms", "eva_ms", "gqa_core_ms", "attn_text_roofline_pct"} <= others
    for name in ("attn_text_fwd_ms", "attn_text_bwd_ms", "attn_text_roofline_pct", "mla_ms", "kda_core_ms", "eva_core_ms", "ssm_ms"):
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
    assert read(ctx, "window_core_ms") == pytest.approx(140.0) and read(ctx, "full_core_ms") == pytest.approx(300.0)
    assert read(ctx, "window_core_roofline_pct") is None and read(ctx, "full_core_roofline_pct") is None


def test_a_stack_of_one_kind_of_layer_reads_one_kind_of_core():
    """Nemotron's one attention layer is a full layer by its scope: no window core."""
    ops, modules = unrolled_step(scale=1e6)
    ctx = context([e for e in ops if "window_attn_core" not in e.stats["tf_op"]], modules)
    assert read(ctx, "window_core_ms") is None and read(ctx, "window_core_roofline_pct") is None
    assert read(ctx, "full_core_ms") == pytest.approx(300.0)


@pytest.mark.parametrize("scale", [1e5, 1e6, 1e7, 1e8], ids=["0.1s", "1s", "10s", "100s"])
def test_no_share_of_a_roofline_or_a_peak_of_the_cell_leaves_0_100(scale):
    """Every reader of the new cell whose name holds `roofline` or `mfu`, on steps
    whose window cores take 0.014 to 14 s (the least time is 0.117 s) and whose full
    core takes 0.03 to 30 s (0.067 s): a step shorter than the chip could make reads
    over 100 % and is not clipped; every other nothing, or a share in (0, 100]."""
    ctx = context(*unrolled_step(scale=scale))
    cell = harness.Cell(CELL, rehearse=False)
    shares = [m["name"] for m in cell.metrics("per_layer") if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(shares) == ["attn_vision_roofline_pct", "full_core_roofline_pct", "window_core_roofline_pct"]
    got = {name: read(ctx, name) for name in shares}
    if scale == 1e5:
        assert got["window_core_roofline_pct"] > 100 and got["full_core_roofline_pct"] > 100
        return
    for name, value in got.items():
        assert value is None or 0 < value <= 100, (name, value)
    assert got["window_core_roofline_pct"] and got["full_core_roofline_pct"]
