"""scopes_kimi.py: a step cut by the hybrid text tower's five names on hand-made
events, the two rooflines by hand, the readers silent where the program or the
count has nothing for them, and every share of the new cell in (0, 100]."""

import json
import os
import types

import harness
import pytest
import reduce
import scopes
import scopes_kimi

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures")
CELL = "kimi-b16-p64-s1024"
READERS = ("kda_ms", "kda_core_ms", "kda_core_roofline_pct", "mla_core_ms", "mla_core_roofline_pct", "moe_ms", "moe_route_ms")
Event = reduce.Event


def op(start, dur, path, category="non-fusion elementwise"):
    return Event("%x = f32[] x()", start, dur, {"tf_op": path, "hlo_category": category})


def hybrid_step(scale: float = 1.0):
    """One step of 1000 (x scale) ns of a hybrid text tower, forward and backward."""
    fwd = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/checkpoint/block1/"
    bwd = "jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/textual/encoder/checkpoint/block1/"
    mla = fwd.replace("block1", "block3")
    ops = [
        op(0, 50, fwd + "kda/q/dot_general", "convolution fusion"),
        op(50, 20, fwd + "kda/mul"),  # convolution, norms, gates
        op(70, 200, fwd + "kda/kda_core/while", "while"),  # the core: self time 200 - 120
        op(80, 120, fwd + "kda/kda_core/while/body/closed_call/checkpoint/dot_general", "convolution fusion"),
        op(270, 10, fwd + "moe/moe_route/sort"),
        op(280, 60, fwd + "moe/while", "while"),  # an expert's loop: self time 60 - 5 - 40
        op(285, 5, fwd + "moe/while/body/closed_call/moe_route/gather"),
        op(290, 40, fwd + "moe/while/body/closed_call/dot_general", "convolution fusion"),
        op(340, 30, fwd + "moe/shared/wi/dot_general", "convolution fusion"),
        op(370, 20, mla + "mla/q/dot_general", "convolution fusion"),
        op(390, 120, mla + "mla/mla_core/shard_map/pallas_call", "custom-call"),
        op(510, 300, bwd + "kda/kda_core/while", "while"),
        op(810, 50, bwd + "moe/custom_vjp_call/while", "while"),
        op(860, 20, bwd + "moe/moe_route/scatter-add"),
        op(880, 40, "jit(step)/jvp(SigLIP)/visual/encoder/blocks/block/attn/short_attn_fwd", "custom-call"),
        op(920, 20, "jit(step)/jvp(SigLIP)/textual/token_embed/take"),  # the tower, under none of the names
        op(940, 30, "jit(step)/optimizer/kda_moe_fusion"),  # a name inside a component is not the component
    ]
    ops = [Event(e.name, e.start_ns * scale, e.dur_ns * scale, e.stats) for e in ops]
    return ops, [Event("jit_step", 0, 1000 * scale, {})]


def context(ops, modules, count="flops_kimi", pairs=64):
    raw = json.load(open(os.path.join(BENCH_DIR, "configs", "kimi-linear-48b-text-b16-224.json")))
    cfg = types.SimpleNamespace(vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"]))
    return {
        "trace": {"plane": {"ops": ops, "modules": modules}}, "peaks": harness.peaks_for("TPU v5 lite"),
        "counters": {"count": harness.load_module(os.path.join(BENCH_DIR, count + ".py")), "cfg": cfg, "pairs_per_chip_per_step": pairs},
    }


def test_the_readers_cut_a_step_by_the_five_names():
    got = scopes_kimi.split_events(*hybrid_step())
    assert got.pop("steps") == 1
    assert {k: round(v * 1e9, 6) for k, v in got.items()} == {
        "kda": 50 + 20 + 200 + 300, "kda_core": 200 + 300, "mla_core": 120,
        "moe": 10 + 60 + 30 + 50 + 20, "moe_route": 10 + 5 + 20,
    }


def test_the_rooflines_are_the_counts_least_time_over_the_cores_time():
    import flops_kimi

    ctx = context(*hybrid_step(scale=1e6))  # a step of 1 s: the cores take 500 and 120 ms
    cfg, peaks = ctx["counters"]["cfg"], ctx["peaks"]
    assert scopes_kimi.per_step_ms(ctx, scopes_kimi.KDA_CORE) == pytest.approx(500.0)
    assert scopes_kimi.roofline_pct(ctx, scopes_kimi.KDA_CORE, "kda_least_s") == pytest.approx(
        100 * 1e3 * flops_kimi.kda_least_s(cfg, 64, peaks) / 500.0)
    assert scopes_kimi.roofline_pct(ctx, scopes_kimi.MLA_CORE, "mla_least_s") == pytest.approx(
        100 * 1e3 * flops_kimi.mla_least_s(cfg, 64, peaks) / 120.0)
    assert round(scopes_kimi.roofline_pct(ctx, scopes_kimi.KDA_CORE, "kda_least_s"), 2) == 8.94
    assert round(scopes_kimi.roofline_pct(ctx, scopes_kimi.MLA_CORE, "mla_least_s"), 2) == 8.73


def read(ctx, name):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read(ctx)


def test_every_reader_of_the_cell_reads_the_hand_made_step():
    ctx = context(*hybrid_step(scale=1e6))
    cell = harness.Cell(CELL, rehearse=False)
    listed = {m["name"] for m in cell.metrics("per_layer") if m.get("workloads") == [CELL]}
    assert listed == set(READERS)
    assert all(read(ctx, name) > 0 for name in READERS)
    # The text tower's attention readers say nothing here (no short_attn_* under textual, and the count
    # says 0), so the manifest lists them for the five cells that had them and not for this one; the
    # accepted readers without a list read this cell too: the elementwise rest is the tower's.
    of_the_cell = {m["name"] for m in cell.metrics("per_layer")}
    for name in ("attn_text_fwd_ms", "attn_text_bwd_ms", "attn_text_roofline_pct"):
        assert read(ctx, name) is None and name not in of_the_cell, name
    assert read(ctx, "attn_vision_fwd_ms") == pytest.approx(40.0)
    # the text tower's rest outside matmuls and kernels: the loops' own time counts with it
    assert read(ctx, "tower_elementwise_ms") == pytest.approx(20 + 80 + 10 + 15 + 5 + 300 + 50 + 20 + 20)


def test_the_readers_are_silent_without_the_names_or_without_the_count():
    """On a trace recorded before the names existed (the parent of the PR that
    adds them runs these files too) and under a count module that has no least
    time for these cores: nothing, and no error."""
    trace = reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-1chip-scoped.xplane.pb"), n_devices=1)
    ctx = context(None, None)
    ctx["trace"] = trace
    assert [read(ctx, name) for name in READERS] == [None] * len(READERS)
    ctx = context(*hybrid_step(scale=1e6), count="flops")
    assert read(ctx, "kda_core_ms") == pytest.approx(500.0)
    assert read(ctx, "kda_core_roofline_pct") is None and read(ctx, "mla_core_roofline_pct") is None


@pytest.mark.parametrize("scale", [1e5, 1e6, 1e7], ids=["0.1s", "1s", "10s"])
def test_no_share_of_a_roofline_or_a_peak_of_the_cell_leaves_0_100(scale):
    """Every reader of the new cell whose name holds `roofline` or `mfu`, on steps
    whose cores take from 50 and 12 to 5000 and 1200 ms (the least times are 44.7 and 10.5):
    nothing, or a share in (0, 100]."""
    ctx = context(*hybrid_step(scale=scale))
    cell = harness.Cell(CELL, rehearse=False)
    shares = [m["name"] for m in cell.metrics("per_layer") if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(shares) == ["attn_vision_roofline_pct", "kda_core_roofline_pct", "mla_core_roofline_pct"]
    got = {name: read(ctx, name) for name in shares}
    for name, value in got.items():
        assert value is None or 0 < value <= 100, (name, value)
    assert got["kda_core_roofline_pct"] and got["mla_core_roofline_pct"]
