"""The least time the chip could take for the delta-rule cores of a step (the count module's `kda_least_s`: per layer and caption the larger of 3 x 4 dk dv h s operations over the bf16 peak and the bytes of q, k, v, g, beta in and o out, forward + backward, over the HBM peak; recomputation not counted; bound by memory) over the time under `kda_core`. benchmark/scopes_kimi.py."""

META = {
    "name": "kda_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "delta-rule mixer", "moves": "pairs_per_s_per_chip", "workloads": ['kimi-b16-p64-s1024'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.roofline_pct(ctx, scopes_kimi.KDA_CORE, 'kda_least_s')
