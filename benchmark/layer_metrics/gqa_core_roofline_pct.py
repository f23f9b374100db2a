"""The least time the chip could take for the grouped attention cores of a step (the count module's `gqa_core_least_s`: per layer and caption the larger of 3 x 2 (d + d) h s (s + 1) / 2 operations over the bf16 peak and the bytes of q, k, v, o and their cotangents, k and v at their own fewer heads, over the HBM peak; recomputation not counted) over the time under `attn_core` in the text tower. benchmark/scopes_nemotron.py."""

META = {
    "name": "gqa_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.roofline_pct(ctx, scopes_nemotron.ATTN_CORE, 'gqa_core_least_s')
