"""graftscope: the unified observability layer — host tracing spans and the
process's record of start-up, static step attribution, a training health
watchdog, the metrics schema, live telemetry and the lock witness.

Six parts, one goal — every perf or robustness claim arrives with its
evidence attached, chip or no chip:

- :mod:`.spans` — thread-safe ring-buffered host spans (train loop stages,
  serve per-request stages) with optional ``attrs``, self time by containment
  and named counters; while a profiler capture runs they ride in its host
  plane on the device events' clock; the ring's own Chrome-trace export feeds
  the ``obs summarize`` CLI subcommand. ``spans.RECORDER`` is the one recorder
  of the process, on from import: the program's start-up boundaries
  (``startup.import``, ``startup.compile_cache``, ``startup.mesh``,
  ``init_state`` > ``init_params``, ``startup.step_builder``) and jax's own
  trace / lower / backend-compile spans (``utils/compile_cache.py``) write into
  it; ``train --obs-dir`` and the benchmark's set-up readers read it.
- :mod:`.attribution` — static per-step FLOPs, bytes, and per-kind
  collective wire bytes from the traced jaxpr (no compile), plus compiled-
  executable cost/memory readout, and the chip-free roofline ``mfu_est``
  stamped on every train metrics line.
- :mod:`.health` — host-side NaN/Inf + loss-spike watchdog emitting
  structured events, and the flight recorder that dumps the last N metrics
  lines on crash/SIGTERM through the resilience path.
- :mod:`.metrics_schema` — the declared registry of every train-metrics and
  serve-stats field, validated at emit by ``MetricsLogger`` and enforced
  statically by graftlint's ``repo-metrics-schema`` rule.
- :mod:`.telemetry` — live pull-based metrics: the OpenMetrics-style
  ``/metrics`` exporter the serving stack mounts, plus the atomic-rename
  telemetry file the train loop writes under ``--obs-dir`` and the
  environment fingerprint it stamps there.
- :mod:`.lockwatch` — graftguard's runtime half: the ``named_lock`` factory
  every host-stack lock routes through, a Goodlock-style potential-deadlock
  witness recording the runtime lock-acquisition graph when
  ``DSL_LOCKWATCH=1`` (raw ``threading.Lock`` otherwise — proven dead in
  prod by the ``repo-lockwatch-gate`` lint), and the ``WATCHED_LOCKS``
  inventory docs/SERVING.md's threading model is sourced from.

Import discipline: this package must stay importable without initializing
jax (the linter and the CLI's argparse layer import the schema); anything
jax-touching lives behind function-level imports in :mod:`.attribution`.
"""

from distributed_sigmoid_loss_tpu.obs.health import (  # noqa: F401
    FlightRecorder,
    HealthEvent,
    HealthWatchdog,
)
from distributed_sigmoid_loss_tpu.obs.metrics_schema import (  # noqa: F401
    HEALTH_EVENT_FIELDS,
    SERVE_STATS_FIELDS,
    TRAIN_METRICS_FIELDS,
    TRAIN_METRICS_PREFIXES,
    validate_metrics,
)
from distributed_sigmoid_loss_tpu.obs.lockwatch import (  # noqa: F401
    WATCHED_LOCKS,
    WitnessGraph,
    lockwatch_enabled,
    named_condition,
    named_lock,
    named_rlock,
    watched_lock,
    witness,
)
from distributed_sigmoid_loss_tpu.obs.spans import (  # noqa: F401
    RECORDER,
    Span,
    SpanRecorder,
    self_times,
    summarize_spans,
)
from distributed_sigmoid_loss_tpu.obs.telemetry import (  # noqa: F401
    TelemetryExporter,
    environment_fingerprint,
    render_openmetrics,
    write_telemetry_file,
)

__all__ = [
    "RECORDER",
    "Span",
    "SpanRecorder",
    "self_times",
    "summarize_spans",
    "HealthWatchdog",
    "HealthEvent",
    "FlightRecorder",
    "TRAIN_METRICS_FIELDS",
    "TRAIN_METRICS_PREFIXES",
    "SERVE_STATS_FIELDS",
    "HEALTH_EVENT_FIELDS",
    "validate_metrics",
    "environment_fingerprint",
    "TelemetryExporter",
    "render_openmetrics",
    "write_telemetry_file",
    "WATCHED_LOCKS",
    "WitnessGraph",
    "lockwatch_enabled",
    "named_lock",
    "named_rlock",
    "named_condition",
    "watched_lock",
    "witness",
]
