"""Profiling & timing utilities (SURVEY.md §5: absent in the reference; TPU-native plan
is ``jax.profiler`` traces + a ``block_until_ready`` throughput harness).

``summarize_trace`` turns a captured trace directory into the op-level
where-the-time-goes table PERF.md wants, offline — no TensorBoard needed:
``python -m distributed_sigmoid_loss_tpu.utils.profiling /tmp/trace_dir``.
"""

from __future__ import annotations

import glob as _glob
import gzip
import json
import os
import re
import time
from collections import defaultdict
from typing import Callable

import jax

__all__ = [
    "time_step",
    "throughput",
    "compiled_memory_stats",
    "memory_stats_of_compiled",
    "summarize_trace",
    "summarize_device_ops",
]


def time_step(fn: Callable, *args, warmup: int = 3, iters: int = 10) -> float:
    """Mean wall-clock of ``fn(*args)`` per call, in seconds, with compile and
    warmup excluded and the window closed by ``jax.block_until_ready`` (dispatch
    is asynchronous: without it the clock measures the enqueue). Three warmup
    calls by default: the first dispatches of a fresh executable can run far
    slower than steady state."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def throughput(fn: Callable, *args, items_per_call: int, **kw) -> float:
    """Items/sec of a jitted callable (e.g. image-text pairs/sec of a train step)."""
    return items_per_call / time_step(fn, *args, **kw)


# -- compiled peak-memory introspection ----------------------------------------

_MEM_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "generated_code_size_in_bytes",
    "alias_size_in_bytes",
)


def memory_stats_of_compiled(compiled) -> dict | None:
    """XLA's static memory accounting of an already-compiled executable.

    Returns the ``memory_analysis()`` figures as a plain dict — the
    ``_MEM_FIELDS`` byte counts plus ``peak_bytes`` (arguments + outputs +
    temps + generated code − aliased) — or None when the backend doesn't expose the analysis.
    ``temp_size_in_bytes`` is the number a memory OPTIMIZATION should be
    judged by: arguments/outputs are fixed by the program's signature, temps
    are what the implementation choice actually changes.

    Static-analysis caveat: the sum can exceed
    physical HBM because the allocator reuses buffers the analysis counts
    separately — comparisons between two programs are meaningful, the
    absolute number is an upper bound.
    """
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    out = {}
    for field in _MEM_FIELDS:
        value = getattr(mem, field, None)
        if value is None:
            return None
        out[field] = int(value)
    out["peak_bytes"] = (
        out["argument_size_in_bytes"]
        + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"]
        + out["generated_code_size_in_bytes"]
        - out["alias_size_in_bytes"]
    )
    return out


def compiled_memory_stats(fn, *args) -> dict | None:
    """Compile ``jit(fn)`` for ``args`` and return its memory accounting.

    ``jax.jit(fn).lower(*args).compile().memory_analysis()`` as one call,
    normalized by :func:`memory_stats_of_compiled`. Works on CPU (the analysis
    is backend-generic), which is what makes peak-memory claims REGRESSION-
    TESTABLE: the chunked-vs-fused loss test asserts the streamed path's
    compiled temp bytes are a fraction of the fused path's without touching a
    chip. Double-jitting an already-jitted ``fn`` is fine (jit composes).
    """
    return memory_stats_of_compiled(jax.jit(fn).lower(*args).compile())


# -- offline trace summarization ----------------------------------------------

# "%fusion.123", "copy.4", "all-reduce.1" -> their op family; XLA appends
# numeric ids and jax sometimes a "%" prefix.
_OP_ID_RE = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:[._]\d+)*$")


def _op_family(name: str) -> str:
    m = _OP_ID_RE.match(name)
    return m.group(1) if m else name


def _read_trace_files(logdir: str):
    """Yield each ``*.trace.json.gz`` file's parsed events, ONE file at a time
    (captures are hundreds of MB of Perfetto JSON — holding every parsed file
    simultaneously would be multi-GB resident; consumers accumulate and drop)."""
    paths = sorted(
        _glob.glob(os.path.join(logdir, "**", "*.trace.json.gz"), recursive=True)
    )
    if not paths:
        raise FileNotFoundError(f"no *.trace.json.gz under {logdir!r}")
    for path in paths:
        with gzip.open(path, "rt") as f:
            yield json.load(f).get("traceEvents", [])


def summarize_trace(logdir: str, top: int = 15) -> dict:
    """Aggregate a :func:`trace` capture into per-THREAD op-family time totals.

    Reads every ``*.trace.json.gz`` under ``logdir`` (the Perfetto JSON the
    profiler writes alongside the XPlane protos — parseable with the stdlib,
    unlike the protos). Returns ``{"process/thread": [(op_family, total_ms,
    share), ...]}`` with up to ``top`` rows per track, shares of that TRACK's
    total.

    Grouping is per (pid, tid), never per process: a device process carries an
    "XLA Ops" thread (the per-op spans you want) alongside "XLA Modules" /
    "Steps" threads whose enclosing spans cover the same wall time again —
    summing them per-process would double/triple-count and bury the op rows
    under one giant module span. Read the device's "XLA Ops" track for the
    where-the-time-goes table; host Python tracks still nest internally, so
    treat their totals as upper bounds for dispatch-gap debugging only.
    """
    acc = _TrackAccum()
    for events in _read_trace_files(logdir):
        acc.add(events)
    return acc.finalize(top)


class _TrackAccum:
    """Streaming accumulator behind :func:`summarize_trace` — ``add`` one
    file's events at a time (so only one parsed file is resident), then
    ``finalize``."""

    def __init__(self):
        self.pid_names: dict = {}
        self.tid_names: dict = {}
        self.totals: dict = defaultdict(lambda: defaultdict(float))

    def add(self, events) -> None:
        for ev in events:
            if ev.get("ph") == "M" and ev.get("name") == "process_name":
                self.pid_names[ev.get("pid")] = ev.get("args", {}).get("name", "?")
            elif ev.get("ph") == "M" and ev.get("name") == "thread_name":
                self.tid_names[(ev.get("pid"), ev.get("tid"))] = ev.get(
                    "args", {}
                ).get("name", "?")
        for ev in events:
            if ev.get("ph") == "X" and "dur" in ev and ev.get("name"):
                key = (ev.get("pid"), ev.get("tid"))
                track = (
                    f"{self.pid_names.get(ev.get('pid'), ev.get('pid'))}/"
                    f"{self.tid_names.get(key, ev.get('tid'))}"
                )
                self.totals[track][_op_family(ev["name"])] += ev["dur"] / 1000.0

    def finalize(self, top: int) -> dict:
        out = {}
        for track, fams in self.totals.items():
            track_total = sum(fams.values())
            rows = sorted(fams.items(), key=lambda kv: -kv[1])[:top]
            out[track] = [
                (fam, round(ms, 3),
                 round(ms / track_total, 3) if track_total else 0.0)
                for fam, ms in rows
            ]
        return out


def summarize_device_ops(logdir: str, top: int = 12) -> dict:
    """Roofline-grade attribution of device time from a :func:`trace` capture.

    The profiler annotates each device op span with ``hlo_category`` (XLA's own
    taxonomy), ``model_flops`` and ``bytes_accessed`` — which is the honest
    attribution axis. Op NAMES mislead on TPU: a ``convolution_add_fusion``
    there is usually a MATMUL+bias fusion ("convolution" is how XLA:TPU frames
    dots in fusion names), so name-based tables make matmul time look like conv
    waste (it did, in an early reading of the patch embedding).

    Returns ``{"categories": [(category, ms, share, tflops, gbps), ...],
    "top_ops": [(dedup_name, ms, count, tflops, gbps), ...]}`` where ``tflops``
    / ``gbps`` are achieved rates over that row's summed span time — compare
    against peak to see whether a row is MXU-bound, HBM-bound, or neither
    (kernel overhead).
    """
    acc = _DeviceOpAccum()
    for events in _read_trace_files(logdir):
        acc.add(events)
    return acc.finalize(top)


class _DeviceOpAccum:
    """Streaming accumulator behind :func:`summarize_device_ops` (same one-
    file-resident contract as :class:`_TrackAccum`)."""

    def __init__(self):
        self.cat = defaultdict(lambda: [0.0, 0.0, 0.0])  # ms, flops, bytes
        self.ops = defaultdict(lambda: [0.0, 0, 0.0, 0.0])  # ms, n, flops, bytes
        # Persisted across add() calls: chunked captures may carry the "M"
        # metadata events only in the first file (same contract as _TrackAccum).
        self.tid_names: dict = {}

    def add(self, events) -> None:
        tid_names = self.tid_names
        for ev in events:
            if ev.get("ph") == "M" and ev.get("name") == "thread_name":
                tid_names[(ev.get("pid"), ev.get("tid"))] = ev.get(
                    "args", {}
                ).get("name", "")
        for ev in events:
            if not (
                ev.get("ph") == "X"
                and "dur" in ev
                and tid_names.get((ev.get("pid"), ev.get("tid"))) == "XLA Ops"
            ):
                continue
            a = ev.get("args", {})
            ms = ev["dur"] / 1000.0
            fl = float(a.get("model_flops", 0) or 0)
            by = float(a.get("bytes_accessed", 0) or 0)
            c = self.cat[a.get("hlo_category", _op_family(ev["name"]))]
            c[0] += ms
            c[1] += fl
            c[2] += by
            o = self.ops[a.get("deduplicated_name", ev["name"])]
            o[0] += ms
            o[1] += 1
            o[2] += fl
            o[3] += by

    def finalize(self, top: int) -> dict:
        def rates(ms, fl, by):
            s = ms / 1000.0
            return (
                round(fl / s / 1e12, 1) if s else 0.0,
                round(by / s / 2**30, 0) if s else 0.0,
            )

        total = sum(v[0] for v in self.cat.values())
        categories = [
            (name, round(ms, 1), round(ms / total, 3) if total else 0.0,
             *rates(ms, fl, by))
            for name, (ms, fl, by) in sorted(
                self.cat.items(), key=lambda kv: -kv[1][0]
            )
        ]
        top_ops = [
            (name, round(ms, 1), n, *rates(ms, fl, by))
            for name, (ms, n, fl, by) in sorted(
                self.ops.items(), key=lambda kv: -kv[1][0]
            )[:top]
        ]
        return {"categories": categories, "top_ops": top_ops}


def _main() -> int:
    import sys

    if len(sys.argv) < 2:
        print("usage: python -m distributed_sigmoid_loss_tpu.utils.profiling "
              "TRACE_DIR [TOP_N]", file=sys.stderr)
        return 2
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 15
    # ONE streaming pass: each file is parsed once and fed to both
    # accumulators, so peak memory is a single file's parsed events.
    tracks, device = _TrackAccum(), _DeviceOpAccum()
    for events in _read_trace_files(sys.argv[1]):
        tracks.add(events)
        device.add(events)
    for track, rows in tracks.finalize(top).items():
        print(f"\n== {track}")
        for fam, ms, share in rows:
            print(f"  {fam:<40} {ms:>10.3f} ms  {share:>6.1%}")
    dev = device.finalize(top)
    if dev["categories"]:
        print("\n== device ops by hlo_category (achieved rates over span time)")
        print(f"  {'category':<28}{'ms':>10}{'share':>8}{'TFLOP/s':>9}{'GB/s':>8}")
        for name, ms, share, tf, gb in dev["categories"]:
            print(f"  {name:<28}{ms:>10.1f}{share:>8.1%}{tf:>9.1f}{gb:>8.0f}")
        print("\n== top device ops")
        for name, ms, n, tf, gb in dev["top_ops"]:
            print(f"  {name:<42}{ms:>9.1f} ms  n={n:<5}{tf:>7.1f} TF/s{gb:>7.0f} GB/s")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
