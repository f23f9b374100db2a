"""The profiler's own operation count for those matmul operations over their time: how fast the MXU runs while it runs."""

META = {
    "name": "matmul_tflops", "unit": "TFLOP/s", "better": "higher", "source": "device_trace",
    "layer": "towers", "moves": "mfu_pct", "workloads": None,
}


def read(ctx):
    d = ctx["trace"]["device"]
    seconds = d["group_s"]["matmul"]
    if not seconds or not d["matmul_flops"]:
        return None
    return d["matmul_flops"] / seconds / 1e12
