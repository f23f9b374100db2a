"""Host-side tracing spans: the host half of graftscope's unified timeline.

``utils.profiling.trace`` captures what the DEVICE did (XLA op spans with
``hlo_category`` / ``model_flops`` annotations); nothing captured what the
HOST did around it — where a step interval went between fetch, h2d commit,
dispatch, eval and checkpoint, or where a serve request sat between queue,
batch assembly and the engine call. :class:`SpanRecorder` fills that half:

- **Thread-safe, ring-buffered**: producers append under a lock into a
  ``deque(maxlen=capacity)`` — a long-lived trainer or service never grows its
  tracing state, the newest ``capacity`` spans win (the flight-recorder
  convention, not the profiler's grow-forever one).
- **Near-zero overhead when disabled**: ``span()`` on a disabled recorder
  returns one preallocated no-op context manager — no object allocation, no
  clock read, no lock. The hot train/serve loops stay instrumented
  unconditionally and pay only an attribute check until someone turns
  recording on (pinned by the bounded-overhead test in tests/test_obs.py).
- **On the profiler's clock while a capture runs**: an enabled ``span()``
  also enters a ``jax.profiler.TraceAnnotation`` of the same name, so any
  capture that is running (``utils.profiling.trace``, ``bench --profile``,
  the benchmark's traced run) carries ``fetch``, ``h2d_commit``, ``step``,
  ``eval``, ``checkpoint`` and the serve stages in its host plane, on the
  clock of the device events: an idle gap of the device can be put down to
  what the host was doing. With no capture running the annotation is a flag
  check. ``record()`` — a span whose two ends were seen on different threads —
  keeps its ``perf_counter`` times only: an annotation opens and closes on
  one thread.
- **Chrome-trace JSON export**: ``chrome_trace()`` writes the recorder's own
  ring in the ``traceEvents`` format, timestamps in ``perf_counter``
  microseconds. That file (``host_spans.trace.json``) stands alone on its own
  clock: it opens in ui.perfetto.dev and feeds ``obs summarize``'s host table,
  but it does not line up with a device capture — the profiler's file is the
  one that holds both halves.

Nesting needs no explicit tracking: spans carry (tid, ts, dur) and the
Chrome trace model nests same-thread spans by containment, exactly like the
device capture's own tracks.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Iterable

from distributed_sigmoid_loss_tpu.obs.lockwatch import named_lock

__all__ = [
    "Span",
    "SpanRecorder",
    "summarize_spans",
]

# One pid for every host span so perfetto groups them as a single "process".
HOST_PID = 1_000_001


@dataclass(frozen=True)
class Span:
    """One completed host span. Times are ``time.perf_counter()`` seconds."""

    name: str
    t0: float
    t1: float
    tid: int

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0


class _NoopSpan:
    """Reusable disabled-path context manager: no state, so one instance
    serves every call site and thread concurrently — the disabled hot path
    allocates nothing (the property tests/test_obs.py asserts by identity)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _LiveSpan:
    """Enabled-path context manager: records into its recorder on exit, and
    brackets the block with a profiler annotation so a running capture sees
    the span on its own clock."""

    __slots__ = ("_rec", "_name", "_t0", "_annotation")

    def __init__(self, rec: "SpanRecorder", name: str):
        self._rec = rec
        self._name = name

    def __enter__(self):
        # Imported here: obs/ imports without initializing jax, and the
        # disabled path never gets this far.
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._rec.record(self._name, self._t0, t1)
        return False


class SpanRecorder:
    """Ring-buffered recorder of nested host spans.

    ``with rec.span("step"): ...`` on the caller's thread (also a profiler
    annotation, see the module docstring); ``record(name, t0, t1)`` for spans
    whose start and end are observed on different control paths (the serve
    batcher's queue-wait: enqueue happens on the client thread, the batch
    flush on the worker) — ``perf_counter`` times only, never in a profiler
    capture. ``enabled=False`` (or ``disable()``) turns every ``span()`` into
    the shared no-op.
    """

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.enabled = enabled
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._lock = named_lock("obs.spans.SpanRecorder._lock")
        self.dropped = 0  # spans evicted by the ring (total ever)

    # -- recording -----------------------------------------------------------

    def span(self, name: str):
        """Context manager timing the enclosed block (no-op when disabled)."""
        if not self.enabled:
            return _NOOP
        return _LiveSpan(self, name)

    def record(self, name: str, t0: float, t1: float, tid: int | None = None) -> None:
        """Record one completed span (cross-thread span API)."""
        if not self.enabled:
            return
        s = Span(name, t0, t1, threading.get_ident() if tid is None else tid)
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)

    def disable(self) -> None:
        self.enabled = False

    def enable(self) -> None:
        self.enabled = True

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    # -- export --------------------------------------------------------------

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def chrome_trace(self, label: str = "host") -> dict:
        """``{"traceEvents": [...]}`` — the Perfetto/Chrome format.
        Timestamps are perf_counter microseconds (a shared monotonic base
        across every recorder in the process, not the profiler's clock)."""
        events: list[dict] = [
            {
                "ph": "M",
                "name": "process_name",
                "pid": HOST_PID,
                "args": {"name": f"python-{label}"},
            }
        ]
        tids = {}
        for s in self.spans():
            if s.tid not in tids:
                tids[s.tid] = len(tids)
                events.append({
                    "ph": "M",
                    "name": "thread_name",
                    "pid": HOST_PID,
                    "tid": tids[s.tid],
                    "args": {"name": f"{label}-thread-{tids[s.tid]}"},
                })
            events.append({
                "ph": "X",
                "name": s.name,
                "pid": HOST_PID,
                "tid": tids[s.tid],
                "ts": s.t0 * 1e6,
                "dur": (s.t1 - s.t0) * 1e6,
            })
        return {"traceEvents": events}

    def export(self, path: str, label: str = "host") -> None:
        """Write :meth:`chrome_trace` as JSON to ``path``."""
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(label), f)


def summarize_spans(spans: Iterable[Span]) -> dict[str, dict]:
    """Per-name aggregation: ``{name: {count, total_ms, mean_ms, p50_ms,
    p95_ms, max_ms}}`` sorted by total time descending. The host half of the
    ``obs summarize`` report."""
    by_name: dict[str, list[float]] = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s.duration_s * 1000.0)
    out = {}
    for name, ds in sorted(
        by_name.items(), key=lambda kv: -sum(kv[1])
    ):
        ds.sort()
        n = len(ds)

        def rank(p):  # nearest-rank (the LatencyWindow convention)
            import math

            return ds[max(0, math.ceil(p / 100.0 * n) - 1)]

        out[name] = {
            "count": n,
            "total_ms": round(sum(ds), 3),
            "mean_ms": round(sum(ds) / n, 3),
            "p50_ms": round(rank(50), 3),
            "p95_ms": round(rank(95), 3),
            "max_ms": round(ds[-1], 3),
        }
    return out
