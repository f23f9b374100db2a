"""attn_roofline_pct for the text tower alone: the least time for its block attention in a step (12 s^2 w operations, 24 s w bytes per layer per sequence, forward + backward, recomputation not counted; memory-bound at s = 64) over the time of its two kernels. benchmark/scopes.py."""

META = {
    "name": "attn_text_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.tower_roofline_pct(ctx, "textual")
