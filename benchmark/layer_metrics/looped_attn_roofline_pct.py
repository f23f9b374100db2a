"""attn_text_roofline_pct with every layer application counted: the least time for the text tower's block attention in a step (loops x depth x pairs applications, each the larger of 12 s^2 w operations over the bf16 peak and 24 s w bytes over the HBM peak; memory-bound at s = 256, 128 operations a byte against 240) over the time of its `short_attn_fwd` / `short_attn_bwd` kernels. benchmark/scopes_looped.py, benchmark/flops_looped.py."""

META = {
    "name": "looped_attn_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": ['ouro-b16-mb32x2'],
}


def read(ctx):
    import scopes_looped  # benchmark/ is on sys.path

    return scopes_looped.attn_roofline_pct(ctx)
