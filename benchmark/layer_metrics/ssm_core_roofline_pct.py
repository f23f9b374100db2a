"""The least time the chip could take for the state-space cores of a step (the count module's `ssm_core_least_s`: per layer and caption the larger of 3 x the core's products (a group's scores, each head's intra-chunk values, the two products with the state) over the bf16 peak and the bytes of x, B, C, dt in and y out and their cotangents, once, over the HBM peak; recomputation not counted; from the shapes alone, whichever form runs) over the time under `ssm_core`. benchmark/scopes_nemotron.py."""

META = {
    "name": "ssm_core_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "state-space mixer", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.roofline_pct(ctx, scopes_nemotron.SSM_CORE, 'ssm_core_least_s')
