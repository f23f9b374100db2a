"""Device time per step in collective operations (collective-permute of the ring loss, the gradient all-reduce, any all-gather), union of their intervals, slowest device."""

META = {
    "name": "collective_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "loss island and gradient sync", "moves": "pairs_per_s_per_chip", "workloads": ['b16-bs256-dp4'],
}


def read(ctx):
    d = ctx["trace"]["device"]
    return 1e3 * d["collective_s_per_step"] if d["steps"] else None
