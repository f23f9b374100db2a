"""scopes_glm.py: a step cut by the latent-attention tower's two names on
hand-made events, the core's roofline by hand, the readers silent where the
program has none of the names, and every share of the new cell in (0, 100]."""

import json
import os
import types

import harness
import pytest
import reduce
import scopes_glm
import scopes_kimi

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
CELL = "glm-b16-p16-s4096"
READERS = ("mla_ms", "mla_rope_ms", "mla_rope_core_ms", "mla_rope_core_roofline_pct")
Event = reduce.Event


def op(start, dur, path, category="non-fusion elementwise"):
    return Event("%x = f32[] x()", start, dur, {"tf_op": path, "hlo_category": category})


def rotated_step(scale: float = 1.0):
    """One step of 1000 (x scale) ns of a text tower with latent attention in
    every layer, forward and backward."""
    fwd = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/checkpoint/block1/"
    bwd = "jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/textual/encoder/checkpoint/block1/"
    ops = [
        op(0, 30, fwd + "mla/q_a/dot_general", "convolution fusion"),
        op(30, 10, fwd + "mla/q_norm/mul"),
        op(40, 40, fwd + "mla/q_b/dot_general", "convolution fusion"),
        op(80, 20, fwd + "mla/mla_rope/rope/dot_general", "convolution fusion"),
        op(100, 15, fwd + "mla/mla_rope/concatenate"),
        op(115, 200, fwd + "mla/mla_core/shard_map/pallas_call", "custom-call"),
        op(315, 35, fwd + "mla/out/dot_general", "convolution fusion"),
        op(350, 10, fwd + "moe/moe_route/sort"),
        op(360, 60, fwd + "moe/while", "while"),  # an expert's loop: self time 60 - 40
        op(365, 40, fwd + "moe/while/body/closed_call/dot_general", "convolution fusion"),
        op(420, 300, bwd + "mla/mla_core/shard_map/pallas_call", "custom-call"),
        op(720, 25, bwd + "mla/mla_rope/rope/mul"),
        op(745, 55, bwd + "mla/kv_b/dot_general", "convolution fusion"),
        op(800, 50, bwd + "moe/custom_vjp_call/while", "while"),
        op(850, 40, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/attn/short_attn_fwd", "custom-call"),
        op(890, 20, "jit(step)/jvp(SigLIP)/textual/token_embed/take"),  # the tower, under neither name
        op(910, 30, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/mla_rope/mul"),  # not the text tower
        op(940, 30, "jit(step)/optimizer/mla_fusion"),  # a name inside a component is not the component
    ]
    ops = [Event(e.name, e.start_ns * scale, e.dur_ns * scale, e.stats) for e in ops]
    return ops, [Event("jit_step", 0, 1000 * scale, {})]


def context(ops, modules, count="flops_glm", pairs=16):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", "glm-4.7-flash-text-b16-224.json")))
    cfg = types.SimpleNamespace(vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"]))
    return {
        "trace": {"plane": {"ops": ops, "modules": modules}}, "peaks": harness.peaks_for("TPU v5 lite"),
        "counters": {"count": harness.load_module(os.path.join(BENCH_DIR, count + ".py")), "cfg": cfg, "pairs_per_chip_per_step": pairs},
    }


def read(ctx, name):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read(ctx)


def test_the_readers_cut_a_step_by_the_two_names():
    got = scopes_glm.split_events(*rotated_step())
    assert got.pop("steps") == 1
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "mla": 30 + 10 + 40 + 20 + 15 + 200 + 35 + 300 + 25 + 55, "mla_rope": 20 + 15 + 25,
    }
    # scopes_kimi.py's cut of the same step: the core, and the routed layers read by hand for PERF.md
    kimi = scopes_kimi.split_events(*rotated_step())
    assert {k: round(kimi[k] * 1e9, 6) for k in ("mla_core", "moe", "moe_route", "kda")} == {
        "mla_core": 500, "moe": 10 + 20 + 40 + 50, "moe_route": 10, "kda": 0,
    }


def test_the_cores_roofline_is_the_counts_least_time_over_its_time():
    import flops_glm

    ctx = context(*rotated_step(scale=1e7))  # a step of 10 s: the cores take 5 s
    assert read(ctx, "mla_ms") == pytest.approx(7300.0) and read(ctx, "mla_rope_ms") == pytest.approx(600.0)
    assert read(ctx, "mla_rope_core_ms") == pytest.approx(5000.0)
    least = flops_glm.mla_least_s(ctx["counters"]["cfg"], 16, ctx["peaks"])
    assert read(ctx, "mla_rope_core_roofline_pct") == pytest.approx(100 * 1e3 * least / 5000.0)
    assert round(read(ctx, "mla_rope_core_roofline_pct"), 2) == 4.19


def test_every_reader_of_the_cell_reads_the_hand_made_step():
    ctx = context(*rotated_step(scale=1e6))
    cell = harness.Cell(CELL, rehearse=False)
    listed = {m["name"] for m in cell.metrics("per_layer") if m.get("workloads") == [CELL]}
    assert listed == set(READERS)
    assert all(read(ctx, name) > 0 for name in READERS)
    # 16 accepted metrics without a list, the four above, and no reader of another cell's names
    of_the_cell = {m["name"] for m in cell.metrics("per_layer")}
    assert len(of_the_cell) == 20
    for name in ("attn_text_fwd_ms", "attn_text_bwd_ms", "attn_text_roofline_pct"):
        assert read(ctx, name) is None and name not in of_the_cell, name
    for name in ("mla_core_ms", "mla_core_roofline_pct", "moe_ms", "moe_route_ms", "kda_ms", "rope_ms", "accum_ms"):
        assert name not in of_the_cell, name
    assert read(ctx, "attn_vision_fwd_ms") == pytest.approx(40.0)


def test_the_readers_are_silent_without_the_names_or_without_the_count():
    """On a trace recorded before the names existed and under a count module
    that has no least time for the core: nothing, and no error."""
    trace = reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-1chip-scoped.xplane.pb"), n_devices=1)
    ctx = context(None, None)
    ctx["trace"] = trace
    assert [read(ctx, name) for name in READERS] == [None] * len(READERS)
    ctx = context(*rotated_step(scale=1e6), count="flops")
    assert read(ctx, "mla_rope_core_ms") == pytest.approx(500.0)
    assert read(ctx, "mla_rope_core_roofline_pct") is None


@pytest.mark.parametrize("scale", [1e6, 1e7, 1e8], ids=["1s", "10s", "100s"])
def test_no_share_of_a_roofline_or_a_peak_of_the_cell_leaves_0_100(scale):
    """Every reader of the new cell whose name holds `roofline` or `mfu`, on steps
    whose cores take 0.5 to 50 s (the least time is 0.209 s): nothing, or a share
    in (0, 100]."""
    ctx = context(*rotated_step(scale=scale))
    cell = harness.Cell(CELL, rehearse=False)
    shares = [m["name"] for m in cell.metrics("per_layer") if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(shares) == ["attn_vision_roofline_pct", "mla_rope_core_roofline_pct"]
    got = {name: read(ctx, name) for name in shares}
    for name, value in got.items():
        assert value is None or 0 < value <= 100, (name, value)
    assert got["mla_rope_core_roofline_pct"]
