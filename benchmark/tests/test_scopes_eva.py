"""scopes_eva.py: a step cut by the windowed-chunk-attention tower's three names
on hand-made events, the core's roofline by hand, the readers silent on a trace
recorded before the names existed and where the count has no least time, and
every share of the new cell in (0, 100]."""

import json
import os
import types

import harness
import pytest
import reduce
import scopes_eva

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
CELL = "evabyte-b16-p8-s8192"
READERS = ("eva_ms", "eva_summary_ms", "eva_core_ms", "eva_core_roofline_pct")
Event = reduce.Event


def op(start, dur, path, category="non-fusion elementwise"):
    return Event("%x = f32[] x()", start, dur, {"tf_op": path, "hlo_category": category})


def scanned_step(scale: float = 1.0):
    """One step of 1000 (x scale) ns of a scanned text tower of windowed chunk
    attention: forward, remat's forward and backward inside the layer loops."""
    fwd = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/blocks/while/body/checkpoint/block/"
    bwd = "jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/textual/encoder/blocks/while/body/checkpoint/block/"
    ops = [
        op(0, 40, fwd + "eva/q/dot_general", "convolution fusion"),
        op(40, 10, fwd + "eva/rope/mul"),
        op(50, 15, fwd + "eva/eva_summary/dot_general", "convolution fusion"),
        op(65, 10, fwd + "eva/eva_summary/reduce_sum"),
        op(75, 100, fwd + "eva/eva_core/eva_attn_fwd", "custom-call"),
        op(175, 35, fwd + "eva/out/dot_general", "convolution fusion"),
        op(210, 90, fwd + "mlp/wi/dot_general", "convolution fusion"),
        op(300, 100, bwd + "eva/eva_core/eva_attn_fwd", "custom-call"),  # remat's forward
        op(400, 250, bwd + "eva/eva_core/eva_attn_bwd", "custom-call"),
        op(650, 30, bwd + "eva/eva_summary/mul"),
        op(680, 60, bwd + "eva/k/dot_general", "convolution fusion"),
        op(740, 20, bwd + "accum/add"),  # the accumulator in the layer loop: not the mixer's
        op(760, 40, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/attn/short_attn_fwd", "custom-call"),
        op(800, 20, "jit(step)/jvp(SigLIP)/textual/token_embed/take"),  # the tower, under none of the names
        op(820, 30, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/eva_core/mul"),  # not the text tower
        op(850, 30, "jit(step)/optimizer/eva_fusion"),  # a name inside a component is not the component
    ]
    ops = [Event(e.name, e.start_ns * scale, e.dur_ns * scale, e.stats) for e in ops]
    return ops, [Event("jit_step", 0, 1000 * scale, {})]


def context(ops, modules, count="flops_eva", pairs=8):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", "evabyte-text-b16-224.json")))
    cfg = types.SimpleNamespace(vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"]))
    return {
        "trace": {"plane": {"ops": ops, "modules": modules}}, "peaks": harness.peaks_for("TPU v5 lite"),
        "counters": {"count": harness.load_module(os.path.join(BENCH_DIR, count + ".py")), "cfg": cfg, "pairs_per_chip_per_step": pairs},
    }


def read(ctx, name):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read(ctx)


def test_the_readers_cut_a_step_by_the_three_names():
    got = scopes_eva.split_events(*scanned_step())
    assert got.pop("steps") == 1
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "eva": 40 + 10 + 15 + 10 + 100 + 35 + 100 + 250 + 30 + 60, "eva_summary": 15 + 10 + 30, "eva_core": 100 + 100 + 250,
    }


def test_the_cores_roofline_is_the_counts_least_time_over_its_time():
    import flops_eva

    ctx = context(*scanned_step(scale=1e7))  # a step of 10 s: the cores take 4.5 s
    assert read(ctx, "eva_ms") == pytest.approx(6500.0) and read(ctx, "eva_summary_ms") == pytest.approx(550.0)
    assert read(ctx, "eva_core_ms") == pytest.approx(4500.0)
    least = flops_eva.eva_core_least_s(ctx["counters"]["cfg"], 8, ctx["peaks"])
    assert read(ctx, "eva_core_roofline_pct") == pytest.approx(100 * 1e3 * least / 4500.0)
    assert round(read(ctx, "eva_core_roofline_pct"), 2) == 1.77


def test_every_reader_of_the_cell_reads_the_hand_made_step():
    ctx = context(*scanned_step(scale=1e6))
    cell = harness.Cell(CELL, rehearse=False)
    listed = {m["name"] for m in cell.metrics("per_layer") if m.get("workloads") == [CELL]}
    assert listed == set(READERS)
    assert all(read(ctx, name) > 0 for name in READERS)
    of_the_cell = {m["name"] for m in cell.metrics("per_layer")}
    others = {m["name"] for m in cell.manifest["per_layer"] if "workloads" in m and CELL not in m["workloads"]}
    assert of_the_cell.isdisjoint(others) and {"mla_ms", "kda_ms", "rope_ms", "attn_text_roofline_pct"} <= others
    for name in ("attn_text_fwd_ms", "attn_text_bwd_ms", "attn_text_roofline_pct", "mla_ms", "mla_rope_core_ms"):
        assert read(ctx, name) is None, name
    assert read(ctx, "attn_vision_fwd_ms") == pytest.approx(40.0)
    # the manifest's entries are the readers' META, letter for letter
    for m in cell.manifest["per_layer"]:
        if m["name"] in READERS:
            assert harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py")).META == m


def test_the_readers_are_silent_without_the_names_or_without_the_count():
    """On a trace recorded on the chip before the names existed (PR 22's, what the
    parent of this PR gives) and under a count module that has no least time for
    the core: nothing, and no error."""
    trace = reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-1chip-scoped.xplane.pb"), n_devices=1)
    ctx = context(None, None)
    ctx["trace"] = trace
    assert [read(ctx, name) for name in READERS] == [None] * len(READERS)
    ctx = context(*scanned_step(scale=1e6), count="flops")
    assert read(ctx, "eva_core_ms") == pytest.approx(450.0)
    assert read(ctx, "eva_core_roofline_pct") is None


@pytest.mark.parametrize("scale", [1e6, 1e7, 1e8], ids=["1s", "10s", "100s"])
def test_no_share_of_a_roofline_or_a_peak_of_the_cell_leaves_0_100(scale):
    """Every reader of the new cell whose name holds `roofline` or `mfu`, on steps
    whose cores take 0.45 to 45 s (the least time is 0.080 s): nothing, or a share
    in (0, 100]."""
    ctx = context(*scanned_step(scale=scale))
    cell = harness.Cell(CELL, rehearse=False)
    shares = [m["name"] for m in cell.metrics("per_layer") if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(shares) == ["attn_vision_roofline_pct", "eva_core_roofline_pct"]
    got = {name: read(ctx, name) for name in shares}
    for name, value in got.items():
        assert value is None or 0 < value <= 100, (name, value)
    assert got["eva_core_roofline_pct"]
