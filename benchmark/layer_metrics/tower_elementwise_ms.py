"""Device self time per step of the towers' operations (flax paths `visual/`, `textual/`) outside matmuls, kernels and collectives: layer norms, gelu, residuals, casts, data formatting. Slowest device; benchmark/scopes.py."""

META = {
    "name": "tower_elementwise_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "towers", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.tower_elementwise_ms(ctx)
