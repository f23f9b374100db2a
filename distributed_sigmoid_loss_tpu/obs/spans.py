"""Host-side tracing spans: the host half of graftscope's unified timeline.

``jax.profiler.trace`` captures what the DEVICE did (XLA op spans with
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
  capture that is running (``jax.profiler.trace``, ``chip_smoke.py --time-attention``,
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
device capture's own tracks. :func:`self_times` reads the same containment:
a span's self time is its duration less what its children cover.

**The process's record of start-up.** :data:`RECORDER` is the one recorder of
the process, on from import: the program's start-up boundaries write into it
where the work happens (``startup.import`` in the package's ``__init__``,
``startup.compile_cache``, ``startup.mesh``, ``init_state`` > ``init_params``,
``startup.step_builder``, through :func:`startup_span` / :func:`spanned`), and
``utils/compile_cache.py`` adds one span for each of jax's traces, lowerings
and backend compiles (``jax.trace`` / ``jax.lower`` / ``jax.backend_compile``,
``attrs``: the program's name, ``cache``: ``hit`` / ``miss`` / ``off``) with
the persistent cache's counters. Start-up spans enter no profiler annotation:
no capture runs during set-up, and the first is open before jax is imported.
jax reports its spans on ``time.time()``; :func:`from_wall_clock` moves them
onto ``perf_counter`` with one offset taken when this module is imported.
:func:`process_start` is the OS's start time of the process on that clock, so
the record also sees the interpreter's own start. ``train --obs-dir`` and the
benchmark's set-up readers (``benchmark/setup_record.py``) both read this one
record; ``train`` without ``--obs-dir`` disables it before its loop.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Iterable

from distributed_sigmoid_loss_tpu.obs.lockwatch import named_lock

__all__ = [
    "RECORDER",
    "Span",
    "SpanRecorder",
    "from_wall_clock",
    "process_start",
    "self_times",
    "spanned",
    "startup_line",
    "startup_span",
    "startup_split",
    "summarize_spans",
]

# One pid for every host span so perfetto groups them as a single "process".
HOST_PID = 1_000_001

# ``perf_counter`` less ``time.time()``, taken once: spans that jax reports on the
# wall clock land on the recorder's clock by this one offset, so they keep their
# order and their containment among themselves.
_WALL_TO_PERF = time.perf_counter() - time.time()


def from_wall_clock(t: float) -> float:
    """A ``time.time()`` reading on the recorder's ``perf_counter`` clock."""
    return t + _WALL_TO_PERF


@functools.cache  # one reading a process: two clocks are read, and a second pair differs by microseconds
def process_start() -> float | None:
    """When the OS started this process, on the ``perf_counter`` clock (earlier
    than any reading this process took), or None where ``/proc`` does not say:
    the 22nd field of ``/proc/self/stat`` is the start in clock ticks since boot."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # The command's name (field 2) may hold spaces: count from its ")".
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age


@dataclass(frozen=True)
class Span:
    """One completed host span. Times are ``time.perf_counter()`` seconds."""

    name: str
    t0: float
    t1: float
    tid: int
    attrs: dict | None = None  # e.g. a compile's ``fun_name`` and ``cache``: hit / miss

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
    (``annotate``) brackets the block with a profiler annotation so a running
    capture sees the span on its own clock."""

    __slots__ = ("_rec", "_name", "_attrs", "_annotate", "_t0", "_annotation")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: dict | None, annotate: bool):
        self._rec = rec
        self._name = name
        self._attrs = attrs
        self._annotate = annotate

    def __enter__(self):
        if self._annotate:
            # Imported here: obs/ imports without initializing jax, and the
            # disabled path never gets this far.
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation(self._name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annotate:
            self._annotation.__exit__(*exc)
        self._rec.record(self._name, self._t0, t1, attrs=self._attrs)
        return False


class SpanRecorder:
    """Ring-buffered recorder of nested host spans.

    ``with rec.span("step"): ...`` on the caller's thread (also a profiler
    annotation, see the module docstring); ``record(name, t0, t1)`` for spans
    whose start and end are observed on different control paths (the serve
    batcher's queue-wait: enqueue happens on the client thread, the batch
    flush on the worker) — ``perf_counter`` times only, never in a profiler
    capture. ``count(name)`` adds to a named counter at the same boundaries.
    ``enabled=False`` (or ``disable()``) turns every ``span()`` into the shared
    no-op and ``record`` / ``count`` into an early return.
    """

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.enabled = enabled
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._lock = named_lock("obs.spans.SpanRecorder._lock")
        self.dropped = 0  # spans evicted by the ring (total ever)
        self._counters: dict[str, int] = {}

    # -- recording -----------------------------------------------------------

    def span(self, name: str, attrs: dict | None = None, annotate: bool = True):
        """Context manager timing the enclosed block (no-op when disabled).
        ``annotate=False`` keeps the profiler out of it: the start-up spans'
        form, which must work before jax is imported."""
        if not self.enabled:
            return _NOOP
        return _LiveSpan(self, name, attrs, annotate)

    def record(
        self, name: str, t0: float, t1: float, tid: int | None = None,
        attrs: dict | None = None,
    ) -> None:
        """Record one completed span (cross-thread span API)."""
        if not self.enabled:
            return
        s = Span(name, t0, t1, threading.get_ident() if tid is None else tid, attrs)
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name``."""
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def disable(self) -> None:
        self.enabled = False

    def enable(self) -> None:
        self.enabled = True

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._counters.clear()

    # -- export --------------------------------------------------------------

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def counters(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def chrome_trace(self, label: str = "host") -> dict:
        """``{"traceEvents": [...]}`` — the Perfetto/Chrome format.
        Timestamps are perf_counter microseconds (a shared monotonic base
        across every recorder in the process, not the profiler's clock). A
        span's ``attrs`` go as the event's ``args``; the counters as a key of
        their own beside ``traceEvents``."""
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
            event = {
                "ph": "X",
                "name": s.name,
                "pid": HOST_PID,
                "tid": tids[s.tid],
                "ts": s.t0 * 1e6,
                "dur": (s.t1 - s.t0) * 1e6,
            }
            if s.attrs:
                event["args"] = s.attrs
            events.append(event)
        return {"traceEvents": events, "counters": self.counters()}

    def export(self, path: str, label: str = "host") -> None:
        """Write :meth:`chrome_trace` as JSON to ``path``."""
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.chrome_trace(label), f)


def self_times(spans: Iterable[Span]) -> list[tuple[Span, float]]:
    """Each span beside its self time in seconds: its duration less the part of
    it that its children cover. A span's children are the spans of its thread
    that start inside it; one that ends a little after its parent (two clocks,
    one offset) counts up to the parent's end. In start order per thread."""
    out: list[tuple[Span, float]] = []
    by_tid: dict[int, list[Span]] = {}
    for s in spans:
        by_tid.setdefault(s.tid, []).append(s)
    for thread in by_tid.values():
        thread.sort(key=lambda s: (s.t0, -s.t1))
        # [span, seconds its direct children cover, where the last of them ended]
        stack: list[list] = []

        def close(upto: float) -> None:
            while stack and stack[-1][0].t1 <= upto:
                span, covered, _ = stack.pop()
                out.append((span, max(0.0, span.duration_s - covered)))

        for s in thread:
            close(s.t0)
            if stack:
                parent = stack[-1]
                start = max(s.t0, parent[2])  # siblings that overlap count once
                end = min(s.t1, parent[0].t1)
                if end > start:
                    parent[1] += end - start
                    parent[2] = end
            stack.append([s, 0.0, s.t0])
        close(float("inf"))
    return out


def summarize_spans(spans: Iterable[Span]) -> dict[str, dict]:
    """Per-name aggregation: ``{name: {count, total_ms, self_ms, mean_ms,
    p50_ms, p95_ms, max_ms}}`` sorted by total time descending; ``self_ms`` is
    the name's :func:`self_times` summed. The host half of the ``obs
    summarize`` report."""
    by_name: dict[str, list[float]] = {}
    self_ms: dict[str, float] = {}
    for s, own in self_times(spans):
        by_name.setdefault(s.name, []).append(s.duration_s * 1000.0)
        self_ms[s.name] = self_ms.get(s.name, 0.0) + own * 1000.0
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
            "self_ms": round(self_ms[name], 3),
            "mean_ms": round(sum(ds) / n, 3),
            "p50_ms": round(rank(50), 3),
            "p95_ms": round(rank(95), 3),
            "max_ms": round(ds[-1], 3),
        }
    return out


# -- the process's record of start-up -----------------------------------------

RECORDER = SpanRecorder()


def startup_span(name: str, attrs: dict | None = None):
    """A span of the process's recorder with no profiler annotation: the form
    of the start-up boundaries, usable before jax is imported."""
    return RECORDER.span(name, attrs, annotate=False)


def spanned(name: str):
    """Decorator: each call of the function runs inside ``startup_span(name)``.
    Apply it at the END of a file whose functions jax traces (``f =
    spanned("x")(f)``): the compile cache's key holds the traced path's source
    lines (utils/compile_cache.py), so nothing above a traced function may
    move. ``configure_compile_cache`` keeps this file's frames out of the
    programs' locations, so the wrapper itself changes no key."""

    def wrap(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with startup_span(name):
                return fn(*args, **kwargs)

        return wrapper

    return wrap


def startup_split(spans: Iterable[Span], start: float, end: float, tid: int | None = None) -> dict[str, float]:
    """Where ``[start, end]`` went on one thread (default: the caller's):
    seconds under each name of the outermost spans there, in order of first
    appearance, and ``unnamed`` for what no span covers. The line ``train``
    prints at its first step."""
    tid = threading.get_ident() if tid is None else tid
    out: dict[str, float] = {}
    covered_to = start
    for s in sorted((s for s in spans if s.tid == tid), key=lambda s: (s.t0, -s.t1)):
        t0, t1 = max(s.t0, covered_to), min(s.t1, end)
        if t1 > t0:  # an outermost span, or the part of one past its neighbour
            out[s.name] = out.get(s.name, 0.0) + t1 - t0
            covered_to = t1
    out["unnamed"] = max(0.0, end - start - sum(out.values()))
    return out


def startup_line(spans: Iterable[Span], loop_start: float) -> str:
    """One line for an operator when the first step has ended: process start
    (the OS's; else the first span's) to now, the loop's share of it, and
    :func:`startup_split` of the whole on the calling thread."""
    spans = list(spans)
    now = time.perf_counter()
    start = process_start()
    if start is None:
        start = min((s.t0 for s in spans), default=loop_start)
    split = startup_split(spans, start, now)
    return (
        f"startup: {now - start:.1f} s from process start to the end of the first step "
        f"(the loop took {now - loop_start:.1f} of them): "
        + ", ".join(f"{name} {seconds:.1f}" for name, seconds in split.items())
    )
