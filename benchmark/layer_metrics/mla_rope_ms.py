"""Device self time per step under the program's `mla_rope` scope (models/mixers.py LatentAttention): the rotation of each head's 64-wide query part and of the one shared key part, with the cuts and the joins that put the parts back, forward, remat's forward and backward. benchmark/scopes_glm.py."""

META = {
    "name": "mla_rope_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "latent attention", "moves": "pairs_per_s_per_chip", "workloads": ['glm-b16-p16-s4096'],
}


def read(ctx):
    import scopes_glm  # benchmark/ is on sys.path

    return scopes_glm.per_step_ms(ctx, scopes_glm.MLA_ROPE)
