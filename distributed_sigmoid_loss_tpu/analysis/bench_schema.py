"""THE declared schema for the host-side benches' JSON record fields.

``data-bench`` (data/data_bench.py), ``serve-bench`` and its siege and fleet
scenarios (cli.py ``cmd_serve_bench``, serve/siege.py, serve/fleet/scenarios.py)
each print one-line JSON records. One registry for all of them, so a field
added on one emit path cannot drift from the others unseen: every emitter
routes its record through :func:`validate_record` (stderr warning on
violation; the record still prints — a measurement must never be lost to its
own validator), and tests/test_data_pipeline.py, tests/test_siege.py and
tests/test_fleet.py hold the emitted records to it.

A train step's rate or utilisation is not recorded here: ``benchmark/run.py``
measures those, and ``PERF_LEDGER.jsonl`` holds them.

Stdlib-only module: the emitters import it before jax is initialized.
"""

from __future__ import annotations

__all__ = [
    "REQUIRED_RECORD_FIELDS",
    "BENCH_RECORD_FIELDS",
    "validate_record",
]

# Present in EVERY record: streams are keyed by `metric` and plot
# `value`/`unit`.
REQUIRED_RECORD_FIELDS = ("metric", "value", "unit")

# The full registered field set, grouped by the emit path that owns them.
BENCH_RECORD_FIELDS = frozenset(
    REQUIRED_RECORD_FIELDS
    + (
        # data-bench (stage + composed-pipeline records, data/data_bench.py);
        # `model` is serve-bench's too
        "model", "steps", "global_batch", "n_devices", "device_kind",
        "stage", "data_workers", "native_decode", "worker_scaling",
        "synthetic_pairs_per_sec", "synthetic_ratio", "input_wait_frac",
        "pipelined", "read_ahead", "zero_copy", "bound_stage",
        # serve-bench record (cli.py cmd_serve_bench: invocation fields +
        # the serve stats() snapshot spread in — the snapshot's own field
        # set is declared in obs/metrics_schema.py SERVE_STATS_FIELDS and
        # mirrored here so the one-JSON-line record validates end to end;
        # stage_latency_ms carries the per-stage p50/p95/p99 percentiles)
        "clients", "requests_sent", "batch_buckets", "max_wait_ms",
        "sharded", "warmup_s", "uptime_s", "requests", "items", "qps",
        "items_per_sec", "latency_ms", "batch_size_hist", "stage_latency_ms",
        "rejected", "timeouts", "compile_count", "bucket_space", "index_size",
        "cache",
        # serve/distindex (RetrievalRouter through cmd_serve_bench): the
        # retrieval tier + churn-mode invocation fields and the router's
        # stats fields the snapshot spread carries (mirrored from
        # obs/metrics_schema.py SERVE_STATS_FIELDS).
        "index_tier", "swap_every", "index_version", "shard_count",
        "swap_count", "swap_latency_ms", "recall_at_k", "rerank_k",
        "search_stage_latency_ms",
        # graftsiege (serve/siege.py run_scenario through cmd_serve_bench
        # --scenario): the degradation record — scenario identity + offered
        # load, the trailing shed rate, per-tenant outcome rows (sent / ok /
        # shed / typed_errors / p99 vs slo), host-loss recovery time, and
        # the zero-silent-drops counter the acceptance drill asserts on;
        # plus the admission/swap fields the stats() snapshot spread carries
        # (mirrored from obs/metrics_schema.py SERVE_STATS_FIELDS).
        "scenario", "offered_load", "duration_s", "tenants", "per_tenant",
        "shed_rate", "recovery_time_s", "silent_drops", "restarts",
        "shed", "admission", "swap_in_flight",
        # graftfleet (serve/fleet/scenarios.py run_fleet_scenario through
        # cmd_serve_bench --fleet-scenario): the fleet_siege record — the
        # router/wave/lease stats snaps (mirrored from SERVE_STATS_FIELDS)
        # plus the invocation fields and the over-admission evidence: the
        # global rate ceiling, the peak admitted rate any sliding window
        # saw, and the count of windows that exceeded ceiling + burst
        # (asserted zero — the bounded-staleness lease proof).
        "replica_count", "healthy_replicas", "reroutes", "affinity_hits",
        "lease_epoch", "lease_reclaims", "wave_id", "fleet_replicas",
        "lease_ttl_s", "ceiling_rate", "peak_admitted_rate",
        "over_ceiling_samples",
    )
)


def validate_record(record) -> list[str]:
    """Validate one host-side bench JSON record against the declared schema.

    Returns a list of problem strings (empty = valid). Field VALUES are not
    typed here — the schema pins the field NAMESPACE, which is what drifts.
    """
    if not isinstance(record, dict):
        return [f"record must be a dict, got {type(record).__name__}"]
    problems = []
    for field in REQUIRED_RECORD_FIELDS:
        if field not in record:
            problems.append(f"missing required field {field!r}")
    unknown = sorted(set(record) - BENCH_RECORD_FIELDS)
    if unknown:
        problems.append(
            "unregistered field(s) "
            + ", ".join(repr(u) for u in unknown)
            + " — register in analysis/bench_schema.py BENCH_RECORD_FIELDS"
        )
    return problems
