"""scopes.py: path-to-part matching on hand-written paths, the parts of a synthetic
trace adding up to its busy time, every new reader silent on PR 22's scope-less
fixtures, every reader of a cell reading fixtures recorded on the TPU v5e
with the program's scopes and kernel names in (PR 23), and the attention
rooflines reading the configuration's own count (PR 31):

    chiprun --chips 1 -- python benchmark/tests/record_fixture.py fixture-1chip
    chiprun --chips 4 -- python benchmark/tests/record_fixture.py fixture-4chip
    python -c "import sys; sys.path[:0] = ['benchmark', 'benchmark/tests']; import trim_xplane as t; \\
        t.KEEP_STATS += ('tf_op',); t.trim('chiprun_out/fixture-1chip.xplane.pb', \\
        'benchmark/tests/fixtures/fixture-1chip-scoped.xplane.pb', 2)"      # 4chip: 1 step

``trim_xplane.py`` as PR 22 left it drops the jax path (``tf_op``); the one-liner
keeps it and changes nothing else.
"""

import json
import os
import types

import harness
import pytest
import reduce
import scopes
from reduce import Event

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
NEW_METRICS = {
    "update_and_metrics_ms", "accum_ms", "loss_island_ms", "tower_elementwise_ms",
    "attn_vision_fwd_ms", "attn_vision_bwd_ms", "attn_text_fwd_ms", "attn_text_bwd_ms",
    "attn_vision_roofline_pct", "attn_text_roofline_pct", "unscoped_pct",
}


@pytest.mark.parametrize("path, group, part", [
    ("jit(step)/optimizer/mul", "other", "optimizer"),
    ("jit(<lambda>)/optimizer/reduce_sum", "other", "optimizer"),  # update_sharding="full"
    ("jit(step)/transpose(jvp(loss_island))/shard_map/dot_general", "matmul", "loss_island"),
    ("jit(step)/jvp(loss_island)/shard_map/ppermute", "collective", "loss_island"),  # dp4: the ring
    ("jit(step)/loss_island/transpose(loss_island)/jvp()/shard_map", "other", "loss_island"),  # gradcache
    ("jit(step)/while/body/closed_call/accum/convert_element_type", "other", "accum"),
    ("jit(step)/accum/div", "other", "accum"),
    ("jit(step)/step_metrics/reduce_sum", "other", "step_metrics"),
    ("jit(step)/while/body/closed_call/jvp(SigLIP)/visual/encoder/block0/ln1/reduce_sum", "other", "visual"),
    ("jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/textual/encoder/jvp(SigLIP)/textual/encoder/"
     "checkpoint/block1/mlp/wo/dot_general", "matmul", "textual"),
    ("jit(step)/transpose(jvp(SigLIP))/visual/encoder/block3/attn/shard_map/short_attn_bwd/pallas_call",
     "custom_call", "visual"),  # dp4: the kernel sits in a shard_map
    ("jit(step)/transpose(jvp(SigLIP))/visual/encoder/block3/mlp/wi/dot_general", "collective", "collective"),
    ("", "collective", "collective"),  # the partitioner's all-reduce may carry no path
    ("", "other", "unscoped"),
    ("jit(step)/accumulate/optimizers/visually", "other", "unscoped"),  # whole components only
])
def test_part_of_a_path(path, group, part):
    assert scopes.part_of(path, group) == part


def test_kernel_of_a_path():
    fwd = "jit(step)/while/body/closed_call/jvp(SigLIP)/textual/encoder/block0/attn/short_attn_fwd/pallas_call"
    recomputed = ("jit(step)/while/body/closed_call/transpose(jvp(SigLIP))/visual/encoder/jvp(SigLIP)/visual/"
                  "encoder/checkpoint/block1/attn/short_attn_fwd/pallas_call")
    bwd_dp4 = "jit(step)/transpose(jvp(SigLIP))/visual/encoder/block3/attn/shard_map/short_attn_bwd/pallas_call"
    assert scopes.kernel_of(fwd) == ("textual", "short_attn_fwd")
    assert scopes.kernel_of(recomputed) == ("visual", "short_attn_fwd")
    assert scopes.kernel_of(bwd_dp4) == ("visual", "short_attn_bwd")
    assert scopes.kernel_of("jit(step)/jvp(SigLIP)/visual/encoder/block0/attn/pallas_call") is None  # PR 22: no name
    assert scopes.kernel_of("jit(step)/jvp(loss_island)/shard_map/loss_fwd/pallas_call") is None  # no tower


def synthetic_step(t: float) -> list:
    """One step of 1000 ns from ``t``: a ``while`` 0-600 holding a vision matmul
    0-300, a vision layer norm 300-350, a text attention kernel 350-450 and an
    accumulator add 450-500 (the loop's own 100 ns carry no path); then the loss
    island's matmul 600-650 and permute 650-660, a gradient all-reduce 660-760
    with an optimizer fusion under it 700-740, the optimizer 760-860, the step
    metrics 860-900; idle to 1000."""
    body = "jit(step)/while/body/closed_call/"
    return [
        Event("while.1", t, 600.0, {"hlo_category": "while", "tf_op": "jit(step)/while"}),
        Event("fusion.1", t, 300.0, {"hlo_category": "convolution fusion",
                                     "tf_op": body + "transpose(jvp(SigLIP))/visual/encoder/block0/mlp/wi/dot_general"}),
        Event("fusion.2", t + 300.0, 50.0, {"hlo_category": "loop fusion",
                                            "tf_op": body + "jvp(SigLIP)/visual/encoder/block0/ln1/reduce_sum"}),
        Event("short_attn_bwd.3", t + 350.0, 100.0, {
            "hlo_category": "custom-call",
            "tf_op": body + "transpose(jvp(SigLIP))/textual/encoder/block0/attn/short_attn_bwd/pallas_call"}),
        Event("fusion.4", t + 450.0, 50.0, {"hlo_category": "loop fusion", "tf_op": body + "accum/convert_element_type"}),
        Event("fusion.5", t + 600.0, 50.0, {"hlo_category": "convolution fusion",
                                            "tf_op": "jit(step)/jvp(loss_island)/shard_map/dot_general"}),
        Event("collective-permute.6", t + 650.0, 10.0, {"hlo_category": "collective-permute",
                                                        "tf_op": "jit(step)/jvp(loss_island)/shard_map/ppermute"}),
        Event("all-reduce.7", t + 660.0, 100.0, {"hlo_category": "all-reduce", "tf_op": ""}),
        Event("fusion.8", t + 700.0, 40.0, {"hlo_category": "loop fusion", "tf_op": "jit(step)/optimizer/mul"}),
        Event("fusion.9", t + 760.0, 100.0, {"hlo_category": "loop fusion", "tf_op": "jit(step)/optimizer/add"}),
        Event("fusion.10", t + 860.0, 40.0, {"hlo_category": "reduce", "tf_op": "jit(step)/step_metrics/reduce_sum"}),
    ]


def test_parts_of_a_synthetic_trace_add_up_to_its_busy_time():
    ops = synthetic_step(0.0) + synthetic_step(1100.0)
    modules = [Event("jit_step(1)", 0.0, 1000.0, {}), Event("jit_step(1)", 1100.0, 1000.0, {}),
               Event("jit_convert(2)", 2200.0, 10.0, {})]
    ops.append(Event("fusion.11", 2200.0, 10.0, {"hlo_category": "loop fusion", "tf_op": "jit(convert)/mul"}))
    s = scopes.split_events(ops, modules)
    assert s["steps"] == 2
    want = {  # ns per step
        "unscoped": 100.0, "visual": 350.0, "textual": 100.0, "accum": 50.0, "loss_island": 60.0,
        "collective": 60.0, "optimizer": 140.0, "step_metrics": 40.0,
    }
    assert s["part_s"] == {k: pytest.approx(2 * v * 1e-9, rel=1e-12) for k, v in want.items()}
    assert s["other_s"]["visual"] == pytest.approx(100e-9) and "textual" not in s["other_s"]
    assert s["kernel_s"] == {("textual", "short_attn_bwd"): pytest.approx(200e-9)}
    busy = reduce.reduce_events(ops, modules, [])["busy_s"]
    assert sum(s["part_s"].values()) == pytest.approx(busy, rel=1e-9) == pytest.approx(1800e-9)
    assert set(s["part_s"]) <= set(scopes.PARTS)


# -- through the readers, on recorded traces --------------------------------------


def count_module(name):
    """A count module by file, as jobs/train_step.py loads the configuration's:
    benchmark/<name>.py, or a synthetic one under tests/counts/."""
    base = harness.BENCH_DIR if os.sep not in name else os.path.dirname(os.path.abspath(__file__))
    return harness.load_module(os.path.join(base, name + ".py"))


def read_cell(cell_name, fixture, n_devices, pairs, count="flops", **text):
    """The per-layer metrics of ``cell_name`` as run.py reads them with --trace 1:
    the reduction of ``fixture`` and counters like the job's; ``text`` overrides
    fields of the fixture configuration's text tower."""
    cell = harness.Cell(cell_name, rehearse=False)
    widths = json.load(open(os.path.join(harness.REHEARSAL_DIR, "configs", "b16-depth2.json")))
    cfg = types.SimpleNamespace(
        vision=types.SimpleNamespace(**widths["vision"]),
        text=types.SimpleNamespace(**{**widths["text"], **text}))
    trace = reduce.reduce_xplane(os.path.join(FIXTURES, fixture), n_devices)
    values = harness.read_layer_metrics(cell, {
        "trace": trace,
        "counters": {"recompiles": 0, "trace_lower_s": 7.37, "pairs_per_chip_per_step": pairs, "cfg": cfg,
                     "count": count_module(count),
                     "memory_analysis": types.SimpleNamespace(peak_memory_in_bytes=1077646848)},
        "peaks": harness.peaks_for("TPU v5 lite"), "devices": [], "memory_peak_bytes": 1171126272, "cell": cell,
    })
    return cell, trace, {name: m["value"] for name, m in values.items()}


def test_new_readers_are_silent_without_the_names():
    """PR 22's fixture, recorded before the program named anything and trimmed of
    its jax paths: nothing to read, nothing raised, and PR 22's readers read what
    they read before."""
    cell, _, got = read_cell("b16-mb128x8", "fixture-1chip.xplane.pb", 1, 32)
    assert not NEW_METRICS & set(got)
    assert set(got) == {m["name"] for m in cell.metrics("per_layer")} - NEW_METRICS


def test_every_reader_reads_the_scoped_one_chip_trace():
    """PR 22's test_every_reader_reads_the_recorded_trace over the full list, on
    the fixture cell recorded with the scopes in (2 x 16 pairs accumulated, B/16
    widths, two blocks, one chip)."""
    cell, trace, got = read_cell("b16-mb128x8", "fixture-1chip-scoped.xplane.pb", 1, 32)
    assert set(got) == {m["name"] for m in cell.metrics("per_layer")} >= NEW_METRICS
    d = trace["device"]
    busy_ms = 1e3 * trace["busy_s"] / d["steps"]
    s = scopes.split({"cell": cell, "trace": trace})
    # The parts are the reduction's groups cut another way: same operations, same self times.
    assert sum(s["part_s"].values()) == pytest.approx(sum(d["group_s"].values()), rel=1e-9)
    assert sum(s["part_s"].values()) == pytest.approx(trace["busy_s"], rel=1e-6)
    assert "collective" not in s["part_s"]
    kernels_ms = sum(got[f"attn_{tower}_{way}_ms"] for tower in ("vision", "text") for way in ("fwd", "bwd"))
    # All but 0.02 %: the compiler's own AllocateBuffer / ConcatBitcast custom calls carry no name.
    assert kernels_ms == pytest.approx(got["custom_call_share_pct"] / 100 * busy_ms, rel=1e-3)
    for name in ("update_and_metrics_ms", "accum_ms", "loss_island_ms", "tower_elementwise_ms"):
        assert 0 < got[name] < busy_ms
    # XLA fuses each leaf's AdamW update with the norms that read its result into one fusion,
    # which carries the reduction's path: the update's time reads under `step_metrics`, the
    # clip's norm alone under `optimizer`, and the metric is the two parts together.
    update_ms, metrics_ms = (1e3 * s["part_s"][p] / s["steps"] for p in ("optimizer", "step_metrics"))
    assert metrics_ms > update_ms > got["loss_island_ms"]
    assert got["update_and_metrics_ms"] == pytest.approx(update_ms + metrics_ms, rel=1e-12)
    assert 0 < got["attn_text_roofline_pct"] < got["attn_vision_roofline_pct"] < 100
    # Each roofline is its tower's least time (flops.py: 24 s w bytes a layer a sequence at 819 GB/s) over its kernels'.
    for tower, s_w in (("vision", 196 * 768), ("text", 64 * 768)):
        tower_ms = got[f"attn_{tower}_fwd_ms"] + got[f"attn_{tower}_bwd_ms"]
        assert got[f"attn_{tower}_roofline_pct"] == pytest.approx(100 * 1e3 * 24.0 * s_w * 2 * 32 / 819e9 / tower_ms)
    # copy-done / async-done carry no path; at this toy size (an 11 ms step) they weigh 10 %, at a cell's 2 %.
    assert got["unscoped_pct"] == pytest.approx(9.9, abs=0.2)


def test_every_reader_reads_the_scoped_four_chip_trace():
    """The dp=4 fixture: the kernels sit in a shard_map, the ring's permutes belong
    to the loss island, the gradient all-reduce to ``collective``."""
    cell, trace, got = read_cell("b16-bs256-dp4", "fixture-4chip-scoped.xplane.pb", 4, 32)
    assert set(got) == {m["name"] for m in cell.metrics("per_layer")} >= NEW_METRICS - {"accum_ms"}
    d = trace["device"]
    s = scopes.split({"cell": cell, "trace": trace})
    assert sum(s["part_s"].values()) == pytest.approx(sum(d["group_s"].values()), rel=1e-9)
    # Every collective is the loss island's (its permutes and the all-reduce of its pmean, where
    # at this toy batch the chips wait for each other) or the gradient sync's.
    assert s["part_s"]["collective"] < d["group_s"]["collective"]
    assert s["part_s"]["collective"] + s["part_s"]["loss_island"] > d["group_s"]["collective"]
    assert got["loss_island_ms"] == pytest.approx(3.413447734) and got["collective_ms"] == pytest.approx(4.652596094)
    kernels_ms = sum(got[f"attn_{tower}_{way}_ms"] for tower in ("vision", "text") for way in ("fwd", "bwd"))
    assert kernels_ms == pytest.approx(1e3 * d["group_s"]["custom_call"] / d["steps"], rel=1e-3)
    assert got["unscoped_pct"] < 15


# -- the attention rooflines read the configuration's count (PR 31) -----------------

SCOPED = [("b16-mb128x8", "fixture-1chip-scoped.xplane.pb", 1), ("b16-bs256-dp4", "fixture-4chip-scoped.xplane.pb", 4)]
COUNTS = ["flops", "flops_looped", "counts/no_text_attention", "counts/one_text_layer_in_five"]


def test_a_text_tower_without_attention_layers_reads_no_roofline():
    """The wire ISSUE 31 found: a text tower that runs no softmax attention through
    the fused kernels was counted as `depth` attention layers over whatever custom
    calls the step had. Its count says 0, the reader says nothing, and the image
    tower's reading and the kernel times stand."""
    _, trace, base = read_cell("b16-mb128x8", SCOPED[0][1], 1, 32, depth=5)
    cell, _, got = read_cell("b16-mb128x8", SCOPED[0][1], 1, 32, "counts/no_text_attention", depth=5)
    assert "attn_text_roofline_pct" in base and "attn_text_roofline_pct" not in got
    assert set(got) == set(base) - {"attn_text_roofline_pct"}
    assert all(got[name] == base[name] for name in got)
    ctx = {"trace": trace, "peaks": harness.peaks_for("TPU v5 lite"), "cell": cell, "counters": {
        "count": count_module("counts/no_text_attention"), "cfg": None, "pairs_per_chip_per_step": 32}}
    assert scopes.tower_roofline_pct(ctx, "textual") is None and scopes.kernel_ms(ctx, "textual") > 0


def test_one_attention_layer_in_five_reads_a_fifth_of_all_layers():
    """A hybrid stack counts the applications it does: five layers of which one is
    attention read a fifth of what five attention layers read over the same kernel
    time; the image tower does not move."""
    _, _, every = read_cell("b16-mb128x8", SCOPED[0][1], 1, 32, "flops", depth=5)
    _, _, fifth = read_cell("b16-mb128x8", SCOPED[0][1], 1, 32, "counts/one_text_layer_in_five", depth=5)
    assert fifth["attn_text_roofline_pct"] == pytest.approx(every["attn_text_roofline_pct"] / 5, rel=1e-12)
    assert fifth["attn_vision_roofline_pct"] == every["attn_vision_roofline_pct"]


def test_the_looped_count_reads_loops_times_the_unlooped_one():
    """`ouro-b16-mb32x2`'s readers with the looped count: the text tower's roofline
    is `loops` x what flops.py reads (what `looped_attn_roofline_pct` read beside
    `attn_text_roofline_pct` before PR 31), and nothing else moves."""
    got = {}
    for count, loops in (("flops", 1), ("flops_looped", 1), ("flops_looped", 4)):
        cell, _, got[count, loops] = read_cell("ouro-b16-mb32x2", SCOPED[0][1], 1, 32, count, loops=loops)
    assert set(got["flops", 1]) == {m["name"] for m in cell.metrics("per_layer")} - {"text_loops_ms", "rope_ms"}
    assert got["flops_looped", 1] == got["flops", 1]
    assert got["flops_looped", 4].pop("attn_text_roofline_pct") == 4 * got["flops", 1].pop("attn_text_roofline_pct")
    assert got["flops_looped", 4] == got["flops", 1]


@pytest.mark.parametrize("count", COUNTS)
@pytest.mark.parametrize("cell_name, fixture, n_devices", SCOPED, ids=["one-chip", "four-chips"])
def test_no_share_of_a_roofline_or_a_peak_leaves_0_100(cell_name, fixture, n_devices, count):
    """Every reader whose name holds `roofline` or `mfu`, on both scoped fixtures,
    under every count module there is: nothing, or a share in (0, 100]. Never 0
    for a tower without the work, never the 110 % to 596 % a miscount gives."""
    cell, _, got = read_cell(cell_name, fixture, n_devices, 32, count, depth=5)
    shares = [m["name"] for m in cell.metrics("per_layer") if "roofline" in m["name"] or "mfu" in m["name"]]
    assert sorted(shares) == ["attn_text_roofline_pct", "attn_vision_roofline_pct"]
    for name in shares:
        assert name not in got or 0 < got[name] <= 100, (name, got[name])
    assert "attn_vision_roofline_pct" in got
    assert ("attn_text_roofline_pct" in got) == (count != "counts/no_text_attention")
