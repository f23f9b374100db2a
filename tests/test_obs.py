"""graftscope (obs/): spans, static attribution, health watchdog, metrics
schema — plus the LatencyWindow nearest-rank fix.

Contracts pinned here:

- **Span safety**: the disabled-spans hot path is allocation-free (identity +
  tracemalloc bound), the ring buffer never grows past capacity, recording is
  thread-safe, and the export is valid Chrome-trace JSON.
- **Flight recorder**: dumps fire on a REAL SIGTERM through the
  train_resilient preemption path, on the divergence raise, and on a crash —
  the resilience harness of tests/test_resilience.py re-run with the black
  box attached.
- **Attribution correctness**: collective wire bytes and matmul FLOPs for
  the fused all-gather and ring loss configs asserted against CLOSED-FORM
  counts (b, W, d known), chunked == fused flops (the scan-trip-count
  multiplier), ring_overlap == ring comm (overlap must not change traffic),
  all six step configs attribute with the expected comm structure, and the
  chunked-vs-fused peak-temp ratio re-derives PR 3's memory regression
  through ``attribution_of_compiled``.
- **Metrics schema**: emit-time validation warns without losing the line,
  and the real step metrics validate.

Standard tier: the heaviest piece is the compiled peak-temp pair (same cost
class as test_streamed_loss's existing memory regression); everything else
is pure host python or trace-only.
"""

import json
import math
import os
import signal
import threading
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributed_sigmoid_loss_tpu as dsl  # noqa: F401  (compat shims first)
from distributed_sigmoid_loss_tpu.obs import (
    FlightRecorder,
    HealthWatchdog,
    SpanRecorder,
    summarize_spans,
    validate_metrics,
)
from distributed_sigmoid_loss_tpu.obs.attribution import (
    attribution_of_compiled,
    jaxpr_costs,
    metrics_line_fields,
    roofline_estimate,
    static_attribution,
)
from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
    HEALTH_EVENT_FIELDS,
    SERVE_STATS_FIELDS,
    TRAIN_METRICS_FIELDS,
)
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import (
    init_loss_params,
    l2_normalize,
)
from distributed_sigmoid_loss_tpu.parallel import make_mesh, make_sharded_loss_fn
from distributed_sigmoid_loss_tpu.utils.logging import LatencyWindow, MetricsLogger


# ---------------------------------------------------------------------------
# spans: disabled-path overhead, ring bound, threads, export
# ---------------------------------------------------------------------------


def test_disabled_spans_are_allocation_free():
    """The disabled hot path returns ONE shared no-op object — identity, no
    per-call allocation (tracemalloc bound far below one object per call),
    and nothing recorded."""
    rec = SpanRecorder(enabled=False)
    assert rec.span("a") is rec.span("b") is rec.span("a")
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    for _ in range(2000):
        with rec.span("hot"):
            pass
        rec.record("cross", 0.0, 1.0)
    now, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # 2000 live-span objects would be >100 KB; the no-op path must stay
    # within interpreter noise.
    assert now - base < 16_384, f"disabled spans allocated {now - base} bytes"
    assert rec.spans() == []


def test_disabled_spans_touch_no_jax():
    """obs/ imports without initializing jax: spans.py names nothing of jax at
    module level, a disabled recorder never reaches the profiler, and a fresh
    process that imports obs/ and runs the disabled path has no backend."""
    import subprocess
    import sys

    import distributed_sigmoid_loss_tpu.obs.spans as spans_module

    assert not any(
        getattr(v, "__name__", "").split(".")[0] == "jax"
        or getattr(v, "__module__", "").split(".")[0] == "jax"
        for v in vars(spans_module).values()
    )
    code = (
        "from distributed_sigmoid_loss_tpu.obs import SpanRecorder\n"
        "import jax.profiler\n"
        "def boom(*a, **k): raise AssertionError('the disabled path opened an annotation')\n"
        "jax.profiler.TraceAnnotation = boom\n"
        "rec = SpanRecorder(enabled=False)\n"
        "with rec.span('hot'): pass\n"
        "rec.record('cross', 0.0, 1.0)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'a backend was initialized'\n"
        "rec.enable()\n"
        "try:\n"
        "    with rec.span('hot'): pass\n"
        "except AssertionError: print('enabled-path-reached-the-profiler')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert "enabled-path-reached-the-profiler" in out.stdout


def test_enabled_spans_record_and_nest():
    rec = SpanRecorder()
    with rec.span("outer"):
        with rec.span("inner"):
            pass
    names = [s.name for s in rec.spans()]
    assert names == ["inner", "outer"]  # inner exits (and records) first
    assert all(s.t1 >= s.t0 for s in rec.spans())


def test_ring_buffer_never_grows_unbounded():
    rec = SpanRecorder(capacity=64)
    for i in range(64 + 100):
        rec.record(f"s{i}", 0.0, 1.0)
    spans = rec.spans()
    assert len(spans) == 64
    assert rec.dropped == 100
    assert spans[0].name == "s100"  # newest capacity spans win


def test_spans_thread_safe():
    rec = SpanRecorder(capacity=256)

    def worker(k):
        for i in range(200):
            with rec.span(f"t{k}"):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec.spans()) == 256  # 800 recorded, ring holds capacity


def test_chrome_trace_export_and_summarize(tmp_path):
    rec = SpanRecorder()
    with rec.span("step"):
        pass
    with rec.span("step"):
        pass
    with rec.span("fetch"):
        pass
    path = str(tmp_path / "host_spans.trace.json")
    rec.export(path)
    with open(path) as f:
        trace = json.load(f)
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 3
    assert all("ts" in e and "dur" in e for e in xs)
    assert any(
        e.get("name") == "process_name" for e in trace["traceEvents"]
    )
    summary = summarize_spans(rec.spans())
    assert summary["step"]["count"] == 2
    assert summary["fetch"]["count"] == 1
    assert summary["step"]["total_ms"] >= 0.0


def host_plane_events(logdir, names):
    """Events called one of ``names`` in the host plane of the one profiler
    capture under ``logdir``: (name, start_ns, duration_ns)."""
    import glob

    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    return [
        (ev.name, ev.start_ns, ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name in names
    ]


def test_obs_summarize_merges_host_and_device(tmp_path, capsys):
    """The acceptance surface: one `obs summarize DIR` over a dir holding
    BOTH a host-span export and a device capture (the gzipped Perfetto JSON
    jax.profiler.trace writes) prints the host table AND the device
    hlo_category table. The one file that holds both halves on one clock is
    the profiler's own: while a capture runs, an enabled recorder's span is
    in its host plane, nested as it was on the host and as long as the
    recorder says."""
    import gzip

    from distributed_sigmoid_loss_tpu.cli import main

    rec = SpanRecorder()
    capture = tmp_path / "capture"
    jax.profiler.start_trace(str(capture))
    try:
        with rec.span("step"):
            with rec.span("h2d_commit"):
                jnp.ones((64, 64)).sum().block_until_ready()
        rec.record("queue_wait", 0.0, 1.0)  # cross-thread: perf_counter only
    finally:
        jax.profiler.stop_trace()
    seen = {n: (t0, dur) for n, t0, dur in host_plane_events(
        str(capture), {"step", "h2d_commit", "queue_wait"})}
    assert set(seen) == {"step", "h2d_commit"}
    (outer0, outer_dur), (inner0, inner_dur) = seen["step"], seen["h2d_commit"]
    assert outer0 <= inner0 and inner0 + inner_dur <= outer0 + outer_dur
    by_name = {s.name: s for s in rec.spans()}
    assert outer_dur / 1e9 == pytest.approx(by_name["step"].duration_s, abs=2e-3)
    rec.export(str(tmp_path / "host_spans.trace.json"))
    device_events = [
        {"ph": "M", "name": "thread_name", "pid": 7, "tid": 1,
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "name": "fusion.1", "pid": 7, "tid": 1,
         "ts": 0, "dur": 1500,
         "args": {"hlo_category": "convolution fusion",
                  "model_flops": 3.0e9, "bytes_accessed": 1.0e6}},
        {"ph": "X", "name": "all-reduce.2", "pid": 7, "tid": 1,
         "ts": 1500, "dur": 500,
         "args": {"hlo_category": "all-reduce"}},
    ]
    with gzip.open(tmp_path / "dev.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": device_events}, f)
    assert main(["obs", "summarize", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "host spans" in out and "step" in out
    assert "hlo_category" in out and "convolution fusion" in out


def test_obs_summarize_cli(tmp_path, capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    rec = SpanRecorder()
    with rec.span("step"):
        pass
    rec.export(str(tmp_path / "host_spans.trace.json"))
    assert main(["obs", "summarize", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "host spans" in out and "step" in out
    # --merged-out is gone (the merged file never related the two clocks);
    # a disabled recorder puts nothing into a capture that is running.
    with pytest.raises(SystemExit):
        main(["obs", "summarize", str(tmp_path), "--merged-out", "m.json"])
    capsys.readouterr()
    quiet = SpanRecorder(enabled=False)
    jax.profiler.start_trace(str(tmp_path / "capture"))
    try:
        with rec.span("eval"), quiet.span("checkpoint"):
            pass
    finally:
        jax.profiler.stop_trace()
    seen = host_plane_events(str(tmp_path / "capture"), {"eval", "checkpoint"})
    assert [name for name, _, _ in seen] == ["eval"]
    # empty dir is a usage error, not a crash
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["obs", "summarize", str(empty)]) == 2
    assert "no host_spans" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# LatencyWindow nearest-rank fix + p99
# ---------------------------------------------------------------------------


def test_latency_window_nearest_rank_small_windows():
    """N=2: p50 must be the MIN (the old int(N·p/100) indexing returned the
    max — the overshoot this pins)."""
    w = LatencyWindow()
    w.record(0.010)
    w.record(0.020)
    ps = w.percentiles_ms((50, 95, 99))
    assert ps["p50_ms"] == 10.0
    assert ps["p95_ms"] == 20.0
    assert ps["p99_ms"] == 20.0


def test_latency_window_nearest_rank_exact():
    w = LatencyWindow()
    for v in (1, 2, 3, 4):
        w.record(v / 1000.0)
    ps = w.percentiles_ms((25, 50, 75, 95, 99))
    # nearest-rank over [1,2,3,4] ms: ceil(p/100*4)-1
    assert ps["p25_ms"] == 1.0
    assert ps["p50_ms"] == 2.0
    assert ps["p75_ms"] == 3.0
    assert ps["p95_ms"] == 4.0
    assert ps["p99_ms"] == 4.0
    one = LatencyWindow()
    one.record(0.005)
    assert one.percentiles_ms((50, 99)) == {"p50_ms": 5.0, "p99_ms": 5.0}
    # 1..100 ms: p99 is the 99th sample, not the 100th
    big = LatencyWindow()
    for v in range(1, 101):
        big.record(v / 1000.0)
    ps = big.percentiles_ms((50, 99))
    assert ps["p50_ms"] == 50.0
    assert ps["p99_ms"] == 99.0
    assert LatencyWindow().percentiles_ms((50,)) == {"p50_ms": 0.0}


# ---------------------------------------------------------------------------
# health watchdog + flight recorder
# ---------------------------------------------------------------------------


def test_watchdog_non_finite_and_policy():
    dog = HealthWatchdog(policy="warn")
    evs = dog.observe(3, {"loss": float("nan"), "grad_norm": 1.0})
    assert [e.event for e in evs] == ["non_finite"]
    assert not dog.should_skip(evs)  # warn never skips
    skipdog = HealthWatchdog(policy="skip")
    evs = skipdog.observe(3, {"loss": float("inf")})
    assert skipdog.should_skip(evs)
    rec = evs[0].record()
    assert rec["metric"] == "health_event"
    assert validate_metrics(rec, fields=HEALTH_EVENT_FIELDS, prefixes=()) == []


def test_watchdog_loss_spike_detection():
    dog = HealthWatchdog(min_history=8, spike_factor=4.0)
    for i in range(10):
        assert dog.observe(i, {"loss": 1.0 + 0.01 * i}) == []
    evs = dog.observe(10, {"loss": 40.0})
    assert [e.event for e in evs] == ["loss_spike"]
    # before min_history nothing fires, however wild the values
    young = HealthWatchdog(min_history=8)
    assert young.observe(0, {"loss": 1.0}) == []
    assert young.observe(1, {"loss": 500.0}) == []


def test_watchdog_rejects_bad_config():
    with pytest.raises(ValueError, match="policy"):
        HealthWatchdog(policy="panic")
    with pytest.raises(ValueError, match="spike_factor"):
        HealthWatchdog(spike_factor=0.5)


def test_flight_recorder_bounded_and_dumps(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.note_metrics(i, {"loss": float(i)})
    snap = fr.snapshot("drill")
    assert len(snap["flight_recorder"]["metrics"]) == 4
    assert snap["flight_recorder"]["metrics"][0]["step"] == 6
    path = str(tmp_path / "flight.json")
    fr.dump("drill", path=path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["flight_recorder"]["reason"] == "drill"
    assert fr.dumps == 1


# -- the resilience harness with the black box attached ----------------------


def _make_step():
    tx = optax.adam(1e-2)

    @jax.jit
    def step(state, batch):
        params, opt_state = state

        def loss_fn(p):
            return dsl.sigmoid_loss(
                batch["zimg"], batch["ztxt"], p["t_prime"], p["bias"]
            )

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), {"loss": loss}

    params = init_loss_params()
    return step, (params, tx.init(params))


def _batches(n, poison_at=None):
    rng = np.random.default_rng(7)
    out = []
    for i in range(n):
        zi = rng.standard_normal((8, 16)).astype(np.float32)
        zt = rng.standard_normal((8, 16)).astype(np.float32)
        zi /= np.linalg.norm(zi, axis=-1, keepdims=True)
        zt /= np.linalg.norm(zt, axis=-1, keepdims=True)
        if poison_at is not None and i == poison_at:
            zi = zi * np.nan
        out.append({"zimg": jnp.asarray(zi), "ztxt": jnp.asarray(zt)})
    return out


def test_flight_recorder_dumps_on_sigterm(tmp_path):
    """A real SIGTERM through PreemptionGuard: the loop checkpoints, stops,
    and the flight recorder dumps the retained trajectory to its path."""
    from distributed_sigmoid_loss_tpu.train import (
        PreemptionGuard,
        train_resilient,
    )

    step_fn, state = _make_step()
    flight = FlightRecorder(capacity=16,
                            path=str(tmp_path / "flight.json"))
    spans = SpanRecorder()
    sent = []

    def on_metrics(step, metrics):
        flight.note_metrics(step, metrics)
        if step == 3 and not sent:
            sent.append(True)
            os.kill(os.getpid(), signal.SIGTERM)

    guard = PreemptionGuard(signals=(signal.SIGTERM,))
    with guard:
        _, report = train_resilient(
            state, step_fn, _batches(20), total_steps=20,
            ckpt_dir=str(tmp_path / "ck"), ckpt_every=100, guard=guard,
            on_metrics=on_metrics, spans=spans, flight=flight,
        )
    assert report.preempted
    assert flight.dumps == 1
    with open(flight.path) as f:
        doc = json.load(f)["flight_recorder"]
    assert "preemption" in doc["reason"]
    assert [m["step"] for m in doc["metrics"]] == [1, 2, 3]
    # ... and the loop's stages landed on the span timeline
    names = {s.name for s in spans.spans()}
    assert {"fetch", "step", "checkpoint"} <= names


def test_flight_recorder_dumps_on_divergence(tmp_path):
    from distributed_sigmoid_loss_tpu.train import (
        TrainingDiverged,
        train_resilient,
    )

    step_fn, state = _make_step()
    flight = FlightRecorder(capacity=16,
                            path=str(tmp_path / "flight.json"))
    with pytest.raises(TrainingDiverged):
        train_resilient(
            state, step_fn, _batches(10, poison_at=5), total_steps=10,
            ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, flight=flight,
        )
    assert flight.dumps == 1
    with open(flight.path) as f:
        assert "divergence" in json.load(f)["flight_recorder"]["reason"]


def test_flight_recorder_dumps_on_crash(tmp_path):
    from distributed_sigmoid_loss_tpu.train import train_resilient

    step_fn, state = _make_step()
    flight = FlightRecorder(capacity=16,
                            path=str(tmp_path / "flight.json"))

    def crashing():
        yield from _batches(2)
        raise RuntimeError("simulated crash")

    with pytest.raises(RuntimeError, match="simulated crash"):
        train_resilient(
            state, step_fn, crashing(), total_steps=10,
            ckpt_dir=str(tmp_path / "ck"), ckpt_every=100, flight=flight,
        )
    assert flight.dumps == 1
    with open(flight.path) as f:
        assert "crash" in json.load(f)["flight_recorder"]["reason"]


def test_resilient_loop_without_obs_unchanged(tmp_path):
    """spans/flight default to None: the loop behaves exactly as before (the
    no-overhead-when-off contract at the API level)."""
    from distributed_sigmoid_loss_tpu.train import train_resilient

    step_fn, state = _make_step()
    _, report = train_resilient(
        state, step_fn, _batches(4), total_steps=4,
        ckpt_dir=str(tmp_path), ckpt_every=2,
    )
    assert report.final_step == 4


# ---------------------------------------------------------------------------
# static attribution: closed-form counts (b, W, d known)
# ---------------------------------------------------------------------------

W, LOCAL_B, D = 8, 4, 16
F32 = 4  # bytes


def _loss_inputs(dtype=jnp.float32):
    rng = np.random.default_rng(0)
    zi = l2_normalize(jnp.asarray(
        rng.standard_normal((W * LOCAL_B, D)), jnp.float32))
    zt = l2_normalize(jnp.asarray(
        rng.standard_normal((W * LOCAL_B, D)), jnp.float32))
    return init_loss_params(), zi.astype(dtype), zt.astype(dtype)


def test_fused_allgather_attribution_closed_form():
    """Forward fused all-gather loss: the gather moves (W-1)·local_b·d·4
    bytes per device, and the one fused logits matmul is
    2·local_b·(W·local_b)·d FLOPs per device. Exact equality."""
    mesh = make_mesh(W)
    fn = make_sharded_loss_fn(mesh, variant="all_gather")
    params, zi, zt = _loss_inputs()
    att = static_attribution(fn, params, zi, zt)
    assert att["comm_bytes_all_gather"] == (W - 1) * LOCAL_B * D * F32
    assert att["comm_bytes_ppermute"] == 0.0
    assert att["flops_est"] == 2 * LOCAL_B * (W * LOCAL_B) * D


def test_ring_attribution_closed_form():
    """Ring loss: W-1 hops each moving local_b·d·4 bytes per device (bidir
    pairs included — same total), and W block matmuls of 2·local_b²·d."""
    mesh = make_mesh(W)
    fn = make_sharded_loss_fn(mesh, variant="ring")
    params, zi, zt = _loss_inputs()
    att = static_attribution(fn, params, zi, zt)
    assert att["comm_bytes_ppermute"] == (W - 1) * LOCAL_B * D * F32
    assert att["comm_bytes_all_gather"] == 0.0
    assert att["flops_est"] == W * 2 * LOCAL_B * LOCAL_B * D


def test_ring_overlap_attribution_matches_serial_ring():
    """The overlapped ring reorders comm/compute — it must not change ONE
    byte of traffic or one FLOP (bitwise-equal loss, PR 3 contract)."""
    mesh = make_mesh(W)
    params, zi, zt = _loss_inputs()
    serial = static_attribution(
        make_sharded_loss_fn(mesh, variant="ring"), params, zi, zt
    )
    overlap = static_attribution(
        make_sharded_loss_fn(mesh, variant="ring", ring_overlap=True),
        params, zi, zt,
    )
    assert overlap == serial


def test_chunked_attribution_scan_multiplier():
    """The chunked scan computes the SAME logits flops as the fused matmul
    (W scan trips × per-chunk block), and gathers the same bytes — the scan
    trip-count multiplier at work."""
    mesh = make_mesh(W)
    params, zi, zt = _loss_inputs()
    fused = static_attribution(
        make_sharded_loss_fn(mesh, variant="all_gather"), params, zi, zt
    )
    chunked = static_attribution(
        make_sharded_loss_fn(mesh, variant="all_gather", loss_impl="chunked"),
        params, zi, zt,
    )
    assert chunked["flops_est"] == fused["flops_est"]
    assert chunked["comm_bytes_all_gather"] == fused["comm_bytes_all_gather"]


def test_backward_attribution_sees_transpose_collectives():
    """grad through the all-gather loss: the gather's VJP is a
    reduce-scatter — the backward program's psum_scatter traffic must be
    visible to the static walk."""
    mesh = make_mesh(W)
    fn = make_sharded_loss_fn(mesh, variant="all_gather")
    params, zi, zt = _loss_inputs()

    def value_and_grads(p, a, b):
        return jax.value_and_grad(fn, argnums=(0, 1, 2))(p, a, b)

    att = static_attribution(value_and_grads, params, zi, zt)
    assert att["comm_bytes_all_gather"] >= (W - 1) * LOCAL_B * D * F32
    assert att["comm_bytes_psum_scatter"] > 0.0
    assert att["flops_est"] > 2 * LOCAL_B * (W * LOCAL_B) * D  # fwd + bwd


def test_six_step_configs_attribute_with_expected_structure():
    """Static attribution over the SAME step-config enumeration graftlint
    audits (the solver-drawn tier-1 sample — a superset of the legacy
    labels): every config counts flops and comm, the ring pair's traffic is
    identical, the all-gather pair's gather bytes agree, and the roofline
    estimate is a valid MFU bound everywhere."""
    from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
        DEFAULT_STEP_CONFIGS,
    )
    from distributed_sigmoid_loss_tpu.obs.attribution import (
        step_config_attribution,
    )

    att = step_config_attribution()
    assert set(att) >= set(DEFAULT_STEP_CONFIGS)
    for label, costs in att.items():
        assert costs["flops_est"] > 0, label
        assert costs["comm_bytes_total"] > 0, label
        assert 0.0 < costs["mfu_est"] <= 1.0, (label, costs)
    assert att["ring"]["comm_bytes_ppermute"] > 0
    assert (
        att["ring"]["comm_bytes_ppermute"]
        == att["ring_overlap"]["comm_bytes_ppermute"]
    )
    assert att["fused"]["comm_bytes_all_gather"] > 0
    assert (
        att["fused"]["comm_bytes_all_gather"]
        == att["chunked"]["comm_bytes_all_gather"]
    )
    # the compressed (dcn, dp) step reduces over BOTH axes
    assert att["compressed_dcn"]["comm_bytes_psum"] > 0


def test_chunked_vs_fused_peak_temp_through_attribution():
    """PR 3's memory contract re-derived through obs/attribution.py: the
    chunked loss's compiled peak-temp bytes are a fraction of the fused
    path's at W=8 (same shapes/threshold as the test_streamed_loss
    regression — one shared truth, two surfaces)."""
    mesh = make_mesh(8)
    local_b, d = 128, 32
    rng = np.random.default_rng(1)
    zi = l2_normalize(jnp.asarray(
        rng.standard_normal((8 * local_b, d)), jnp.float32))
    zt = l2_normalize(jnp.asarray(
        rng.standard_normal((8 * local_b, d)), jnp.float32))
    params = init_loss_params()

    def compiled_attr(impl):
        fn = make_sharded_loss_fn(
            mesh, variant="all_gather", loss_impl=impl, jit=False
        )
        jfn = jax.jit(fn)

        def value_and_grads(p, a, b):
            return jax.value_and_grad(jfn, argnums=(0, 1, 2))(p, a, b)

        compiled = jax.jit(value_and_grads).lower(params, zi, zt).compile()
        att = attribution_of_compiled(compiled)
        assert att["peak_temp_bytes"] is not None, (
            "memory_analysis unavailable on this backend"
        )
        return att

    fused, chunked = compiled_attr("fused"), compiled_attr("chunked")
    assert fused["peak_temp_bytes"] > 0
    ratio = chunked["peak_temp_bytes"] / fused["peak_temp_bytes"]
    assert ratio < 0.5, f"peak-temp ratio regressed: {ratio:.3f}"


def test_roofline_estimate_contract():
    # pure compute: mfu_est 1.0
    est = roofline_estimate(1e12, 0.0, device_kind="TPU v5 lite")
    assert est["mfu_est"] == 1.0 and est["bound"] == "compute"
    # comm-dominated: mfu_est collapses toward zero, bound names comm
    est = roofline_estimate(1e9, 1e12, device_kind="TPU v5 lite")
    assert est["bound"] == "comm" and est["mfu_est"] < 0.01
    # memory term participates when bytes are known
    est = roofline_estimate(1e9, 0.0, bytes_accessed=1e12,
                            device_kind="TPU v5 lite")
    assert est["bound"] == "memory"
    # an actual device the peaks table does not list is an error, never a
    # silent v5e; None is the what-if against the target chip, by name
    with pytest.raises(ValueError, match="cpu"):
        roofline_estimate(1e12, 0.0, device_kind="cpu")
    assert roofline_estimate(1e12, 0.0)["roofline_chip"] == "TPU v5 lite"
    costs = {"flops_est": 1e12, "comm_bytes_total": 5.0}
    fields = metrics_line_fields(costs)
    assert set(fields) == {"mfu_est", "comm_bytes_total"}
    assert fields["comm_bytes_total"] == 5.0
    # a run on an unlisted device keeps the count, drops the utilization
    assert metrics_line_fields(costs, device_kind="cpu") == {
        "comm_bytes_total": 5.0
    }


# ---------------------------------------------------------------------------
# metrics schema + MetricsLogger emit-time validation
# ---------------------------------------------------------------------------


def test_validate_metrics_contract():
    assert validate_metrics({"loss": 1.0, "grad_norm": 2.0}) == []
    assert validate_metrics({"eval/i2t_recall@1": 0.5}) == []
    # graftshard fields cli.py stamps when update sharding is on
    assert validate_metrics(
        {"loss": 1.0, "update_sharding": "full",
         "opt_mem_bytes_per_replica": 90872}
    ) == []
    assert validate_metrics({"opt_mem_bytes_per_rep1ica": 1}) != []
    bad = validate_metrics({"loss": 1.0, "bogus_metric": 2.0})
    assert len(bad) == 1 and "bogus_metric" in bad[0]
    assert validate_metrics([1]) != []
    # serve + health registries cover their emitters' fields
    assert "stage_latency_ms" in SERVE_STATS_FIELDS
    assert {"metric", "step", "event", "detail"} <= HEALTH_EVENT_FIELDS


def test_step_metrics_fields_are_registered():
    """The real step builders' metric keys (incl. the new health scalars)
    are all declared — the contract repo-metrics-schema enforces statically."""
    assert {
        "loss", "t", "bias", "grad_norm", "param_norm", "update_ratio",
        "moe_aux", "ef_norm", "input_wait_frac", "mfu_est",
        "comm_bytes_total",
    } <= TRAIN_METRICS_FIELDS


def test_metrics_logger_validates_without_losing_lines(capsys):
    import io

    buf = io.StringIO()
    logger = MetricsLogger(stream=buf, schema=TRAIN_METRICS_FIELDS,
                           schema_prefixes=("eval/",))
    logger.log(1, {"loss": 1.0, "bogus_metric": 2.0})
    err = capsys.readouterr().err
    assert "schema violation" in err and "bogus_metric" in err
    line = json.loads(buf.getvalue().strip())
    assert line["bogus_metric"] == 2.0  # never lost to its own validator
    # clean line: no warning; the string-valued graftshard mode field
    # survives _jsonable as-is (float("full") raised before PR 17's fix)
    logger.log(2, {"loss": 1.0, "eval/i2t_recall@1": 0.3,
                   "update_sharding": "full"})
    assert "schema violation" not in capsys.readouterr().err
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert line["update_sharding"] == "full"
    # write() with an override schema (health events)
    logger.write({"metric": "health_event", "step": 1, "event": "x",
                  "detail": "d"}, schema=HEALTH_EVENT_FIELDS)
    assert "schema violation" not in capsys.readouterr().err


def test_update_ratio_and_param_norm_on_real_step():
    """One real tiny train step emits finite health scalars with the right
    relationships (update_ratio ≈ ‖Δparams‖/‖params‖ > 0 once LR > 0)."""
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TrainConfig,
    )

    mesh = make_mesh(4)
    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    tx = make_optimizer(TrainConfig(warmup_steps=1, total_steps=10))
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(rng.standard_normal(
            (8, cfg.vision.image_size, cfg.vision.image_size, 3)),
            jnp.float32),
        "tokens": jnp.asarray(rng.integers(
            0, cfg.text.vocab_size, (8, cfg.text.context_length)), jnp.int32),
    }
    state = create_train_state(jax.random.key(0), model, tx, batch, mesh)
    step, sh = make_train_step(model, mesh, LossConfig(variant="ring"))
    state, m1 = step(state, jax.device_put(batch, sh))
    state, m2 = step(state, jax.device_put(batch, sh))
    for m in (m1, m2):
        for key in ("grad_norm", "param_norm", "update_ratio"):
            assert math.isfinite(float(m[key])), (key, m)
        assert float(m["param_norm"]) > 0
    # step 2 runs at a warmed-up LR: the update must actually move params
    assert float(m2["update_ratio"]) > 0
