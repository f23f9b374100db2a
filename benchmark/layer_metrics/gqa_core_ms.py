"""Device self time per step under the program's `attn_core` scope inside the text tower (models/transformer.py Attention): a grouped-head attention layer's scores, causal softmax and values (the kernels `mla_attn_fwd` / `mla_attn_bwd` where grouped heads take them, and the sum of a group's dk and dv), forward, remat's forward and backward. benchmark/scopes_nemotron.py."""

META = {
    "name": "gqa_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.per_step_ms(ctx, scopes_nemotron.ATTN_CORE)
