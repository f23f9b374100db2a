"""The least time the chip could take for the windowed-chunk-attention cores of a step (the count module's `eva_core_least_s`: per caption and layer the larger of 3 x 2 (d + d) h x the pairs the masks admit, (s / W) W (W + 1) / 2 exact and W^2 / C (s / W)(s / W - 1) / 2 summarised, over the bf16 peak and the bytes of q, k, v, the summaries, o, o's cotangent and the gradients, once, over the HBM peak; recomputation not counted) over the time under `eva_core`. benchmark/scopes_eva.py."""

META = {
    "name": "eva_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "windowed chunk attention", "moves": "pairs_per_s_per_chip", "workloads": ['evabyte-b16-p8-s8192'],
}


def read(ctx):
    import scopes_eva  # benchmark/ is on sys.path

    return scopes_eva.core_roofline_pct(ctx)
