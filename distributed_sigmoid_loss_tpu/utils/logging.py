"""Minimal metrics logging for the train loop (SURVEY.md §5: the reference has only
commented-out grad prints; the plan is scalar loss/t/bias + pairs/sec logging while
keeping the loss function pure) — plus the latency-window aggregation the serving
stack's ``stats()`` snapshots are built on."""

from __future__ import annotations

import json
import math
import sys
import threading
import time
from collections import deque
from typing import IO, Mapping

from distributed_sigmoid_loss_tpu.obs.lockwatch import named_lock

__all__ = ["MetricsLogger", "LatencyWindow"]


class LatencyWindow:
    """Rolling window of request durations → p50/p95 percentiles.

    Bounded (``maxlen`` most recent samples) so a long-lived service never
    grows its metrics state; thread-safe because producers are the serving
    stack's client threads. Percentiles use the nearest-rank method on the
    retained window — an honest tail estimate without per-request history.
    """

    def __init__(self, maxlen: int = 8192):
        self._samples: deque[float] = deque(maxlen=maxlen)
        self._lock = named_lock("utils.logging.LatencyWindow._lock")
        self.count = 0  # total ever recorded (not just retained)

    def record(self, seconds: float) -> None:
        with self._lock:
            self._samples.append(float(seconds))
            self.count += 1

    def percentiles_ms(self, ps: tuple[int, ...] = (50, 95)) -> dict[str, float]:
        """{"p50_ms": ..., "p95_ms": ...} over the retained window (zeros when
        nothing has been recorded yet — a snapshot must never raise).

        Nearest-rank: the p-th percentile of N sorted samples is the one at
        1-based rank ``ceil(p/100 · N)``, i.e. index ``ceil(p/100·N) − 1``.
        The previous ``int(N·p/100)`` overshot by one rank — at N=2 the "p50"
        was the MAX, and small serve windows systematically over-reported
        their tails (pinned by tests/test_obs.py).
        """
        with self._lock:
            samples = sorted(self._samples)
        if not samples:
            return {f"p{p}_ms": 0.0 for p in ps}
        n = len(samples)
        out = {}
        for p in ps:
            idx = min(n - 1, max(0, math.ceil(p / 100.0 * n) - 1))
            out[f"p{p}_ms"] = round(samples[idx] * 1000.0, 3)
        return out


class MetricsLogger:
    """JSON-lines metrics logger with steps/sec tracking.

    Keeps host-side state only; call with already-materialized scalars so it never
    forces an early device sync inside the step.

    ``schema`` (a field set from ``obs/metrics_schema.py``, with
    ``schema_prefixes`` for dynamic families like ``eval/``) turns on
    emit-time validation: an undeclared field warns on stderr but the line
    still prints — a metric must never be lost to its own validator
    (graftlint's ``repo-metrics-schema`` rule is the static tier-1
    enforcement of the same registry).
    """

    def __init__(self, stream: IO | None = None, every: int = 1,
                 schema: frozenset | None = None,
                 schema_prefixes: tuple = ()):
        self.stream = stream or sys.stdout
        self.every = every
        self.schema = schema
        self.schema_prefixes = tuple(schema_prefixes)
        self._last_time: float | None = None
        self._last_step: int | None = None

    def _validate(self, record: Mapping) -> None:
        if self.schema is None:
            return
        from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
            validate_metrics,
        )

        problems = validate_metrics(
            dict(record), fields=self.schema, prefixes=self.schema_prefixes
        )
        if problems:
            print(
                "WARNING: metrics schema violation: " + "; ".join(problems),
                file=sys.stderr,
            )

    @staticmethod
    def _jsonable(v):
        # Scalars (device or host) as float; strings (graftshard's
        # update_sharding mode) as-is; small count vectors (the adaptive
        # path's compression_scheme_hist) as a list of floats so the JSONL
        # line stays one self-describing record.
        if isinstance(v, str):
            return v
        try:
            return float(v)
        except TypeError:
            return [float(x) for x in v]

    def log(self, step: int, metrics: Mapping[str, float], *,
            force: bool = False) -> None:
        """``force=True`` (out-of-band records, e.g. in-training eval) bypasses
        the ``every`` filter AND leaves the steps/sec clock untouched — the
        eval's wall time then lands in the next train interval, so logged
        throughput honestly includes the eval overhead instead of hiding it."""
        if step % self.every and not force:
            return
        now = time.perf_counter()
        record = {"step": step}
        record.update({k: self._jsonable(v) for k, v in metrics.items()})
        if not force:
            if self._last_time is not None and step > self._last_step:
                record["steps_per_sec"] = (
                    (step - self._last_step) / (now - self._last_time)
                )
            self._last_time, self._last_step = now, step
        self._validate(record)
        self.stream.write(json.dumps(record) + "\n")
        self.stream.flush()

    def write(self, record: Mapping, schema: frozenset | None = None,
              schema_prefixes: tuple = ()) -> None:
        """Emit a raw JSON-lines record with no step bookkeeping — for
        structured snapshots (the serving stack's ``stats()``: nested cache /
        histogram dicts) that the scalar ``log`` contract can't carry. The
        steps/sec clock is untouched, same as ``force=True``. ``schema``
        overrides the constructor's (out-of-band records — health events,
        serve stats — validate against their own registries)."""
        record = dict(record)
        if schema is not None:
            from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
                validate_metrics,
            )

            problems = validate_metrics(
                record, fields=schema, prefixes=schema_prefixes
            )
            if problems:
                print(
                    "WARNING: metrics schema violation: "
                    + "; ".join(problems),
                    file=sys.stderr,
                )
        else:
            self._validate(record)
        self.stream.write(json.dumps(record) + "\n")
        self.stream.flush()
