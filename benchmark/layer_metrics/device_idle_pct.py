"""1 - union of the device operations' intervals over the traced window (first traced step's start to the last one's end), slowest device."""

META = {
    "name": "device_idle_pct", "unit": "%", "better": "lower", "source": "device_trace",
    "layer": "device", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    d = ctx["trace"]["device"]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"]) if d["window_s"] else None
