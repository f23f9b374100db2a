"""The least time the chip could take for the window layers' cores of a step (the count module's `window_core_least_s`: per layer and caption the larger of 3 x 2 (d + d) h x the band's admitted pairs over the bf16 peak and the bytes of q, k, v, o, o's cotangent and the three gradients, k and v at their own fewer heads, over the HBM peak; recomputation not counted) over the time under `window_attn_core` in the text tower. benchmark/scopes_trinity.py."""

META = {
    "name": "window_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.roofline_pct(ctx, scopes_trinity.WINDOW_CORE, 'window_core_least_s')
