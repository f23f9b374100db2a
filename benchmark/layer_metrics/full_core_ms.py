"""Device self time per step under the program's `attn_core` scope inside the text tower (models/transformer.py Attention without a window) in a stack that mixes window and full layers: a full layer's scores, causal softmax and values (the kernels `mla_attn_fwd` / `mla_attn_bwd` over the whole lower triangle), forward, remat's forward and backward. benchmark/scopes_trinity.py."""

META = {
    "name": "full_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.per_step_ms(ctx, scopes_trinity.FULL_CORE)
