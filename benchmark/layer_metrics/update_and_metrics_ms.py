"""Device self time per step under the program's `optimizer` and `step_metrics` scopes together: the optax update, the zero1/full constraints, the publish, the EMA (parallel/update_shard.py apply_sharded_update) and the three global norms of the metrics line (train/train_step.py health_metrics). One metric over both scopes because XLA fuses each leaf's AdamW update with the norms that read its result into one pass, which carries one scope's path: either scope alone reads what the fuser left it. Slowest device; benchmark/scopes.py."""

META = {
    "name": "update_and_metrics_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "optimizer and update", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.part_ms(ctx, "optimizer", "step_metrics")
