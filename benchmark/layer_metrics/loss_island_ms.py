"""Device self time per step under the program's `loss_island` scope, forward and backward, any category: the pairwise matmul, the elementwise sigmoid loss and, across chips, the ring's permutes. Slowest device; benchmark/scopes.py."""

META = {
    "name": "loss_island_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "loss island and gradient sync", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.part_ms(ctx, "loss_island")
