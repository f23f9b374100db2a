"""Share of the device's busy time in custom calls, which in these cells are the Mosaic attention kernels alone (the loss kernel is off): forward, recomputed forward and backward together."""

META = {
    "name": "custom_call_share_pct", "unit": "%", "better": "lower", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    d = ctx["trace"]["device"]
    return 100.0 * d["group_s"]["custom_call"] / d["busy_s"] if d["busy_s"] else None
