"""The trace reduction: arithmetic on hand-checkable synthetic traces, and the
whole path from ``.xplane.pb`` to numbers pinned to traces recorded on the TPU
v5e in PR 22 (``fixtures/``; see record_fixture.py and trim_xplane.py)."""

import os

import pytest
import reduce
import trim_xplane
from reduce import Event

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def test_interval_arithmetic():
    assert reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert reduce.length([(0, 3), (5, 8)]) == 6
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert reduce.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert reduce.subtract([(0, 2)], []) == [(0, 2)]


def test_self_time_counts_nested_operations_once():
    ops = [
        Event("while.1", 0.0, 100.0, {"hlo_category": "while"}),
        Event("fusion.1", 10.0, 30.0, {"hlo_category": "convolution fusion"}),
        Event("attn.1", 50.0, 40.0, {"hlo_category": "custom-call"}),
        Event("fusion.2", 100.0, 20.0, {"hlo_category": "loop fusion"}),
    ]
    assert reduce.self_times(ops) == [30.0, 30.0, 40.0, 20.0]


def synthetic_device(shift=0.0, allreduce_ns=300.0):
    """Two steps of 1000 ns. Each: a matmul fusion 0-400 (8e5 operations), a Mosaic
    call 400-600, an all-reduce 600-(600 + allreduce_ns) under which a loop fusion
    runs 700-800, then idle to 1000. Step two starts at 1100."""
    ops, modules = [], []
    for t in (shift, shift + 1100.0):
        modules.append(Event("jit_step(1)", t, 1000.0, {}))
        ops += [
            Event("fusion.1", t, 400.0, {"hlo_category": "convolution fusion", "flops": 800000}),
            Event("attn.2", t + 400.0, 200.0, {"hlo_category": "custom-call"}),
            Event("all-reduce.3", t + 600.0, allreduce_ns, {"hlo_category": "all-reduce"}),
            Event("fusion.4", t + 700.0, 100.0, {"hlo_category": "loop fusion"}),
        ]
    modules.append(Event("jit_convert(2)", shift + 2200.0, 10.0, {}))
    return ops, modules


def test_reduce_events_on_a_hand_checkable_step():
    ops, modules = synthetic_device()
    host = [Event("dispatch", -50.0, 100.0, {}), Event("sync", 50.0, 2500.0, {}),
            Event("dispatch", 950.0, 100.0, {})]
    r = reduce.reduce_events(ops, modules, host)
    assert r["program"] == "jit_step(1)" and r["steps"] == 2
    assert r["window_s"] == pytest.approx(2100e-9)  # first step's start to the last one's end
    assert r["busy_s"] == pytest.approx(1800e-9)  # 2 x 900: the fusion lies under the all-reduce
    assert r["step_ms"] == [pytest.approx(1e-3)] * 2
    assert r["group_s"]["matmul"] == pytest.approx(800e-9)
    assert r["group_s"]["custom_call"] == pytest.approx(400e-9)
    # Self time: the loop fusion lies inside the all-reduce's interval and is taken out of it.
    assert r["group_s"]["collective"] == pytest.approx(400e-9)
    assert r["group_s"]["other"] == pytest.approx(200e-9)
    assert r["matmul_flops"] == 1600000
    assert r["collective_s_per_step"] == pytest.approx(300e-9)
    assert r["collective_exposed_s_per_step"] == pytest.approx(200e-9)  # 300 less the 100 hidden
    # Idle: 900-1100 is one gap, named by the innermost span over its middle (the
    # second `dispatch`, inside `sync`); 2000-2100 lies in `sync` alone.
    assert dict(r["idle_gaps"]) == {"dispatch": pytest.approx(200e-9), "sync": pytest.approx(100e-9)}
    assert r["top_ops"][0] == ["fusion.1 [convolution fusion]", pytest.approx(800e-9)]


def write_xplane(path, devices, host):
    """Synthetic events through the same text-proto writer as the trim tool."""
    from jax.profiler import ProfileData

    planes = []
    for i, (ops, modules) in enumerate(devices):
        plane = trim_xplane._Plane(i + 1, f"/device:TPU:{i}")
        plane.add_line(1, reduce.MODULES_LINE, modules)
        plane.add_line(2, reduce.OPS_LINE, ops)
        planes.append(plane)
    host_plane = trim_xplane._Plane(len(planes) + 1, "/host:CPU")
    host_plane.add_line(1, "python", host)
    text = "\n".join(p.text() for p in planes + [host_plane])
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


def test_four_devices_report_the_slowest_and_average_the_busy_time(tmp_path):
    """Device 2's all-reduce lasts 350 ns, the others' 300: its steps are not
    longer (the program's span is 1000 either way) but its busy time is, and the
    file round-trip keeps every number."""
    devices = [synthetic_device(shift=1000.0, allreduce_ns=350.0 if i == 2 else 300.0) for i in range(4)]
    for i, (ops, modules) in enumerate(devices):
        if i == 2:  # the slowest device: its step program runs 1050 ns
            devices[i] = (ops, [m._replace(dur_ns=1050.0) if m.name == "jit_step(1)" else m for m in modules])
    path = str(tmp_path / "four.xplane.pb")
    write_xplane(path, devices, [Event("sync", 0.0, 5000.0, {})])
    r = reduce.reduce_xplane(path, n_devices=4)
    assert sorted(r["per_device"]) == [f"/device:TPU:{i}" for i in range(4)]
    assert r["device"] is r["per_device"]["/device:TPU:2"]
    assert r["device"]["collective_s_per_step"] == pytest.approx(350e-9)
    assert r["device"]["collective_exposed_s_per_step"] == pytest.approx(250e-9)
    assert r["busy_s"] == pytest.approx((3 * 1800e-9 + 1900e-9) / 4)
    assert r["window_s"] == pytest.approx(2150e-9)
    assert r["breakdown"]["idle_gaps"] == [["sync", pytest.approx(250e-9)]]
    with pytest.raises(RuntimeError):
        reduce.reduce_xplane(path, n_devices=8)


def test_recorded_one_chip_trace():
    """Two steps of the fixture cell (B/16 widths, two blocks, 2 x 16 pairs
    accumulated in a while loop) on one TPU v5e chip, PR 22."""
    r = reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-1chip.xplane.pb"), n_devices=1)
    d = r["device"]
    assert d["program"].startswith("jit_step(") and d["steps"] == 2
    assert d["step_ms"] == [pytest.approx(11.20597625), pytest.approx(11.20720875)]
    assert r["window_s"] == pytest.approx(0.02241831625)
    assert r["busy_s"] == pytest.approx(0.022111646306)
    # Self times add up to the busy union: the while loop's body counts once.
    assert sum(d["group_s"].values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert d["group_s"]["matmul"] == pytest.approx(0.009969768198)
    assert d["group_s"]["custom_call"] == pytest.approx(0.00277014727)
    assert d["group_s"]["collective"] == 0.0
    assert d["matmul_flops"] == 1702870844916
    assert dict(d["idle_gaps"]) == {
        "sync": pytest.approx(2.966621e-4, rel=1e-5), "dispatch": pytest.approx(1.0007812e-5, rel=1e-5)}
    assert r["breakdown"]["device_ops"][1][0] == "attn.102 [custom-call]"
    assert len(r["breakdown"]["device_ops"]) == 10


def test_recorded_four_chip_trace():
    """One step of the fixture cell on the four chips of a v5e host (dp=4, 32 pairs
    a chip), PR 22: four device planes, the ring's collective-permutes and the
    gradient all-reduce, which at this toy batch is 29 % of the step."""
    r = reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-4chip.xplane.pb"), n_devices=4)
    assert sorted(r["per_device"]) == [f"/device:TPU:{i}" for i in range(4)]
    assert r["device"] is r["per_device"]["/device:TPU:1"]  # the longest step
    d = r["device"]
    assert d["step_ms"] == [pytest.approx(16.119917578)]
    assert r["window_s"] == pytest.approx(0.016119917578)
    assert r["busy_s"] == pytest.approx(0.0158364962275)  # the mean of four
    assert d["collective_s_per_step"] == pytest.approx(4.649003516e-3)
    assert d["collective_exposed_s_per_step"] == pytest.approx(d["collective_s_per_step"])
    assert d["group_s"]["collective"] == pytest.approx(4.649003516e-3, rel=1e-6)
    assert d["group_s"]["matmul"] == pytest.approx(5.21262e-3, rel=1e-5)
    assert r["breakdown"]["device_ops"][0][0] == "all-reduce.186 [all-reduce]"
    for other in r["per_device"].values():  # every chip runs the same program
        assert other["group_s"]["custom_call"] == pytest.approx(1.3882e-3, rel=2e-4)


# What PR 22's fixture can feed: it was recorded before the program named its scopes
# and kernels, and trimmed of its jax paths, so the readers of reduce.py's categories
# and of the job's counters read it and scopes.py's stay silent (tests/test_scopes.py
# has those, on the fixtures recorded with the names in).
PR22_READERS = {
    "recompiles", "trace_lower_s", "device_step_ms", "peak_hbm_gb", "matmul_share_pct", "matmul_tflops",
    "custom_call_share_pct", "device_idle_pct", "hbm_live_peak_gb",
}


def test_every_reader_reads_the_recorded_trace():
    """The readers of layer_metrics/ over the recorded trace and counters like
    the job's: the path run.py takes with --trace 1, which no CPU rehearsal does."""
    import json
    import types

    import harness

    cell = harness.Cell("b16-bs256", rehearse=False)
    fixture = json.load(open(os.path.join(harness.REHEARSAL_DIR, "configs", "b16-depth2.json")))
    cfg = types.SimpleNamespace(
        vision=types.SimpleNamespace(**fixture["vision"]), text=types.SimpleNamespace(**fixture["text"]))
    memory = types.SimpleNamespace(peak_memory_in_bytes=1077646848)
    memory_watch = harness.MemoryWatch([types.SimpleNamespace(
        memory_stats=lambda: {"bytes_in_use": 821737472, "bytes_reserved": 349388800,
                              "peak_bytes_in_use": 985215488})])
    memory_watch.sample()
    values = harness.read_layer_metrics(cell, {
        "trace": reduce.reduce_xplane(os.path.join(FIXTURES, "fixture-1chip.xplane.pb"), 1),
        "counters": {"recompiles": 0, "trace_lower_s": 7.37, "memory_analysis": memory,
                     "pairs_per_chip_per_step": 32, "cfg": cfg,
                     "count": harness.load_module(os.path.join(harness.BENCH_DIR, "flops.py"))},
        "peaks": harness.peaks_for("TPU v5 lite"), "devices": memory_watch.devices,
        "memory_peak_bytes": memory_watch.peak_bytes, "cell": cell,
    })
    got = {name: m["value"] for name, m in values.items()}
    assert set(got) == PR22_READERS < {m["name"] for m in cell.metrics("per_layer")}  # no collectives: one chip
    assert got["device_step_ms"] == pytest.approx((11.20597625 + 11.20720875) / 2)
    assert got["matmul_share_pct"] == pytest.approx(100 * 0.009969768198 / 0.022111646306)
    assert got["matmul_tflops"] == pytest.approx(1702870844916 / 0.009969768198 / 1e12)
    assert got["custom_call_share_pct"] == pytest.approx(100 * 0.00277014727 / 0.022111646306)
    assert got["device_idle_pct"] == pytest.approx(100 * (1 - 0.022111646306 / 0.02241831625))
    assert got["peak_hbm_gb"] == pytest.approx(1.077646848)
    assert got["hbm_live_peak_gb"] == pytest.approx(1.171126272)  # in use + reserved > peak in use
    assert got["recompiles"] == 0 and got["trace_lower_s"] == 7.37
