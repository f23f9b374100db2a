"""Share of the device's busy time in operations the profiler puts in its matmul / convolution categories (self time): the towers' projections and MLPs, the loss matmul included."""

META = {
    "name": "matmul_share_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "towers", "moves": "mfu_pct", "workloads": None,
}


def read(ctx):
    d = ctx["trace"]["device"]
    return 100.0 * d["group_s"]["matmul"] / d["busy_s"] if d["busy_s"] else None
