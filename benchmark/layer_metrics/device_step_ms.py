"""Median duration of the step program's executions on the device (line XLA Modules), on the slowest device of the cell."""

META = {
    "name": "device_step_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "step builder", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import statistics

    steps = ctx["trace"]["device"]["step_ms"]
    return statistics.median(steps) if steps else None
