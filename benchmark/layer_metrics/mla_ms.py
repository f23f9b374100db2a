"""Device self time per step of every operation under a latent-attention layer's mixer (flax path `textual/encoder/block<i>/mla`, models/mixers.py LatentAttention): the queries' two projections and the keys' and values' two, both latents' norms, the rotation, the core and the output projection, forward, remat's forward and backward. benchmark/scopes_glm.py."""

META = {
    "name": "mla_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "latent attention", "moves": "pairs_per_s_per_chip", "workloads": ['glm-b16-p16-s4096'],
}


def read(ctx):
    import scopes_glm  # benchmark/ is on sys.path

    return scopes_glm.per_step_ms(ctx, scopes_glm.MLA)
