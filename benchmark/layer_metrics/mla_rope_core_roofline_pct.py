"""The least time the chip could take for the latent-attention cores of a step (the count module's `mla_least_s`: per caption and layer the larger of 3 x 2 (dqk + dv) h s (s + 1) / 2 operations, the causal lower triangle, over the bf16 peak and the bytes of q, k, v in and o out, forward + backward, over the HBM peak; recomputation not counted) over the time under `mla_core`, in a cell whose latent attention is rotated. `mla_core_roofline_pct` is the same reading in the cell of the unrotated layer. benchmark/scopes_kimi.py."""

META = {
    "name": "mla_rope_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "latent attention", "moves": "pairs_per_s_per_chip", "workloads": ['glm-b16-p16-s4096'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.roofline_pct(ctx, scopes_kimi.MLA_CORE, 'mla_least_s')
