"""Device self time per step under the program's `mla_core` scope in a cell whose latent attention is rotated (models/mixers.py LatentAttention): scores, softmax and values at 256-wide heads, which reach the blocked kernel unpadded, forward, remat's forward and backward. `mla_core_ms` is the same reading in the cell of the unrotated layer; a benchmark PR may fold the two. benchmark/scopes_kimi.py."""

META = {
    "name": "mla_rope_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "latent attention", "moves": "pairs_per_s_per_chip", "workloads": ['glm-b16-p16-s4096'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.per_step_ms(ctx, scopes_kimi.MLA_CORE)
