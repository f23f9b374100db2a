"""The least time the chip could take for the full layers' cores of a step (the count module's `full_core_least_s`: per layer and caption the larger of 3 x 2 (d + d) h s (s + 1) / 2 operations over the bf16 peak and the bytes of q, k, v, o, o's cotangent and the three gradients over the HBM peak; recomputation not counted) over the time under `attn_core` in the text tower. benchmark/scopes_trinity.py."""

META = {
    "name": "full_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.roofline_pct(ctx, scopes_trinity.FULL_CORE, 'full_core_least_s')
