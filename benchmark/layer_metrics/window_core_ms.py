"""Device self time per step under the program's `window_attn_core` scope inside the text tower (models/transformer.py Attention with a window): a window layer's scores, banded softmax and values (the kernels `mla_attn_fwd` / `mla_attn_bwd` over the band's block pairs where the layer takes them, and the sum of a group's dk and dv), all window layers together, forward, remat's forward and backward. benchmark/scopes_trinity.py."""

META = {
    "name": "window_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.per_step_ms(ctx, scopes_trinity.WINDOW_CORE)
