"""Device self time per step under the program's `mla_core` scope (models/mixers.py LatentAttention): scores, softmax and values of the latent-attention layer, with the padding of the value head to the kernel's one head size and the cut back, forward, remat's forward and backward. benchmark/scopes_kimi.py."""

META = {
    "name": "mla_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "latent attention", "moves": "pairs_per_s_per_chip", "workloads": ['kimi-b16-p64-s1024'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.per_step_ms(ctx, scopes_kimi.MLA_CORE)
