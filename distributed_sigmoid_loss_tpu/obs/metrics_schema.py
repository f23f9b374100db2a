"""THE declared schema for train metrics lines and serve ``stats()`` fields.

``analysis/bench_schema.py`` fixed per-emit-path drift for the host-side
benches' JSON records; this module is the same registry for the OTHER two record streams —
the train loop's metrics lines (``MetricsLogger.log``) and the serving
stack's ``stats()`` snapshots / health events (``MetricsLogger.write``).
Before it, a metric field added in ``train_step.py`` but not
``compressed_step.py`` (or vice versa — ``ef_norm`` already only exists on
one path, correctly, but nothing DECLARED that) drifted silently, and
downstream per-metric parsers learned field names from whatever happened to
be emitted.

One registry, three consumers:

- ``utils.logging.MetricsLogger`` validates at emit time when constructed
  with ``schema=...`` (stderr warning; the line still prints — a metric must
  never be lost to its own validator, the bench ``_emit`` convention).
- ``tests/test_obs.py`` asserts real emit paths validate.
- ``analysis/repo_lint.py`` rule ``repo-metrics-schema`` statically
  cross-checks every metric-field string literal in the emitting modules
  against this registry, so an undeclared field fails tier-1 before it ever
  reaches a log parser.

Stdlib-only module (imported by the linter and bench paths that must not
initialize jax).
"""

from __future__ import annotations

__all__ = [
    "TRAIN_METRICS_FIELDS",
    "TRAIN_METRICS_PREFIXES",
    "SERVE_STATS_FIELDS",
    "HEALTH_EVENT_FIELDS",
    "STACK_RECORD_FIELDS",
    "STACK_RECORD_MLA_FIELDS",
    "STACK_RECORD_EVA_FIELDS",
    "validate_metrics",
]

# Every field a train metrics line may carry, grouped by the layer that owns
# it. Adding a field to a step's metrics dict (or cli.py's log_metrics merge)
# without registering it here fails the repo-metrics-schema lint rule.
TRAIN_METRICS_FIELDS = frozenset({
    # MetricsLogger bookkeeping
    "step", "steps_per_sec",
    # train/train_step.py + train/compressed_step.py step metrics
    "loss", "t", "bias", "grad_norm", "param_norm", "update_ratio",
    "moe_aux", "ef_norm",
    # train/train_step.py, a dropless routed text tower (models/moe.py
    # SharedExpertMoe): assignments to the experts held here, the fullest and
    # the mean held expert's tokens, assignments that did not run (always 0)
    "moe_local_assignments", "moe_max_expert_tokens", "moe_mean_expert_tokens",
    "moe_dropped_tokens",
    # train/compressed_step.py DCN wire accounting: per-device egress bytes
    # per sync round, payload bits per parameter, the residual-carry norm
    # (ef_norm's registered successor — both emitted), and the adaptive
    # path's per-scheme tensor-count histogram (a small list, not a scalar).
    "dcn_wire_bytes", "bits_per_param", "ef_residual_norm",
    "compression_scheme_hist",
    # parallel/adaptive_compression.py BitController bandwidth EWMA
    # (cli.py's adaptive step wrapper merges it into the line)
    "dcn_bw_est_mbps",
    # data/loader.py prefetch starvation (cli.py log_metrics)
    "input_wait_frac",
    # obs/attribution.py static attribution (cli.py log_metrics)
    "mfu_est", "comm_bytes_total",
    # parallel/update_shard.py (graftshard): the resolved update-sharding
    # mode and the compiler-measured at-rest optimizer bytes per replica
    # (cli.py stamps both on every metrics line when the mode is on).
    "update_sharding", "opt_mem_bytes_per_replica",
    # graftcodec: the learned rung's relative reconstruction error
    # (train/compressed_step.py, compression='learned'), the budgeted
    # controller's spent loss-impact budget + active policy (cli.py adaptive
    # wrapper), and the emulated-DCN measurements — bandwidth from MEASURED
    # transfer time over the throttled pipe (parallel/dcn_emu.py) and the
    # wall-clock step-time ratio vs the fixed-bf16 reference transfer.
    "codec_recon_err", "error_budget", "controller_mode",
    "dcn_measured_mbps", "wire_savings_wallclock_ratio",
})

# Prefix-namespaced families (dynamic keys): the in-training eval hook logs
# eval/i2t_recall@K etc. — any key under a registered prefix validates.
TRAIN_METRICS_PREFIXES = ("eval/",)

# serve/service.py stats() snapshot + the serve_stats/serve-bench records
# built from it (cli.py cmd_serve_bench spreads the snapshot into its
# record, so these are also registered in analysis/bench_schema.py).
SERVE_STATS_FIELDS = frozenset({
    "metric", "uptime_s", "requests", "items", "qps", "items_per_sec",
    "latency_ms", "batch_size_hist", "stage_latency_ms", "rejected",
    "timeouts", "compile_count", "bucket_space", "index_size", "cache",
    # serve/distindex (RetrievalRouter.stats): retrieval tier, versioned
    # hot-swap bookkeeping, measured ann recall, and the per-search-stage
    # (fanout/merge/coarse/rerank/exact) latency percentiles.
    "index_tier", "index_version", "shard_count", "swap_count",
    "swap_latency_ms", "recall_at_k", "rerank_k", "search_stage_latency_ms",
    # serve/admission.py (graftsiege): typed-shed counters distinct from the
    # queue-full "rejected" stream, the trailing-window shed rate that also
    # drives /healthz degraded, the nested AdmissionController.stats() row
    # (capacity/inflight/per_tenant), and the router's mid-swap flag.
    "shed", "shed_rate", "admission", "swap_in_flight",
    "capacity", "inflight", "per_tenant",
    # serve/fleet (graftfleet): the router's replica-health + routing
    # counters, the lease coordinator's epoch/reclaim bookkeeping, and the
    # wave controller's wave counter — every fleet stats() snap emits only
    # these, so the fleet_siege record stays schema-valid end to end.
    "replica_count", "healthy_replicas", "reroutes", "affinity_hits",
    "lease_epoch", "lease_reclaims", "wave_id",
})

# obs/health.py HealthEvent.record() — the structured watchdog events the
# train loop writes through the same logger.
HEALTH_EVENT_FIELDS = frozenset({"metric", "step", "event", "detail"})

# train/train_step.py stack_record_of: the trace-time record of a step whose
# text tower is a stack given layer by layer with dropless routed experts
# (``step.stack_record``, read by obs/attribution.py mixed_stack). No metrics
# line: what the step is made of, from shapes alone, beside the counters above
# that say what the routing did. Field -> meaning.
STACK_RECORD_FIELDS = {
    "layer_kinds": "each layer as mixer+mlp or mixer+moe, in order; a one-sub-layer layer as its one kind (ssm, attn, moe); "
                   "an attention layer with a window shorter than the sequence as window_attn",
    "ssm": "per state-space layer: the core it took (ops/ssm.py ssm_core: kernel or chunked), chunk, chunks a sequence, "
           "rows_per_pass, heads, groups, head_dim, state, and the bytes a differentiated call keeps (kept_bytes)",
    "attn": "per attention layer with head sizes or options of its own: the core it took (dense / short / flash / kernel), "
            "block, heads, kv_heads, head_dim, whether grouped keys and values were repeated for it (kv_repeated), its "
            "window (None: a full layer), the block pairs the kernel pair visits and the pairs the softmax admits for a "
            "head (block_pairs, admitted_pairs: counts from shapes), and whether it rotates q and k (rotated), norms "
            "their heads (qk_norm) and gates its output (gated)",
    "experts_held": "routed experts this chip holds",
    "experts_total": "routed experts the router scores",
    "experts_per_token": "experts a token chooses",
    "expected_local_assignments_per_token": "assignments to held experts a token makes under uniform routing",
    "tokens_per_microbatch": "text tokens one microbatch routes",
    "dispatch_rows_bound": "the sort's rows: every token choosing held experts only",
    "kda_core": "per delta-rule layer: the core it took (kernel / chunked), where its per-head norms ran "
                "(qk_norm, o_norm: kernel / xla), rows, heads and chunks of a call, and the bytes a differentiated "
                "call keeps from its forward to its backward (kept_bytes)",
    "short_conv": "per delta-rule and state-space layer, one entry a width its short convolution runs at: the form it took "
                  "(ops/gated_delta_rule.py short_conv_core: kernel, the Pallas pair short_conv_fwd / short_conv_bwd, or "
                  "xla), the channels and a program's tile of (tokens, channels), None in XLA",
    "mla": "per latent-attention layer: what it is made of (the fields below)",
    "eva": "per windowed-chunk-attention layer: what it is made of (STACK_RECORD_EVA_FIELDS)",
    "scanned": "whether the text stack's like layers are one scanned stack (the accumulator then rides the layer loop)",
}
# One windowed-chunk-attention layer's entry of ``eva``.
STACK_RECORD_EVA_FIELDS = {
    "window": "tokens a window: exact causal attention inside it",
    "chunk": "tokens a chunk: one summary each",
    "rope_theta": "the rotation's base (whole heads of q and k)",
    "core": "kernel (the Pallas pair eva_attn_fwd / eva_attn_bwd on (b, s, h x d)) or dense (XLA), as the dispatcher chose",
    "block": "tokens a block of the kernel; None for dense",
    "windows": "windows a sequence",
    "summaries": "summaries a sequence",
}
# One latent-attention layer's entry of ``mla``.
STACK_RECORD_MLA_FIELDS = {
    "q_rank": "width of the queries' normalised latent; 0 = one projection",
    "kv_rank": "width of the keys' and values' normalised latent",
    "rotated_dim": "width of the rotated parts (each head's query part, the one shared key part); 0 = none",
    "rope_theta": "the rotation's base; None where nothing is rotated",
    "core": "kernel (the repo's Pallas pair on (b, s, h x d)), flash (the library's blocked kernel) or dense (XLA), "
            "as the dispatcher chose",
    "qk_dim": "query and key head size",
    "v_dim": "value head size",
    "core_head_dim": "the query and key head size the core ran at",
    "core_v_dim": "the value head size the core ran at",
    "padded": "whether any head was zero-padded to core_head_dim / core_v_dim",
    "block": "tokens a block of a fused core; None for dense",
    "core_tokens": "the sequence the core ran, with the zero rows that fill its last block",
}


def validate_metrics(
    record,
    fields=TRAIN_METRICS_FIELDS,
    prefixes: tuple = TRAIN_METRICS_PREFIXES,
) -> list[str]:
    """Validate one record's field NAMESPACE against a declared field set.

    Returns problem strings (empty = valid). Values are not typed here —
    the namespace is what drifts (the bench_schema convention).
    """
    if not isinstance(record, dict):
        return [f"record must be a dict, got {type(record).__name__}"]
    problems = []
    for key in record:
        if key in fields:
            continue
        if any(key.startswith(p) for p in prefixes):
            continue
        problems.append(
            f"unregistered metric field {key!r} — register it in "
            "obs/metrics_schema.py"
        )
    return problems
