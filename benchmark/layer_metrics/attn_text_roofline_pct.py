"""The least time the chip could take for the softmax attention the text tower runs through the fused kernels in a step, as the configuration's count module has it (`attention_least_s`: per layer application the larger of 12 s^2 w operations over the bf16 peak and 24 s w bytes over the HBM peak, forward + backward, recomputation not counted, every application the tower does; memory-bound at s = 64 and 256), over the time of its `short_attn_fwd` / `short_attn_bwd` kernels. Nothing where the tower has no such kernel or the count says it runs no such layer. benchmark/scopes.py."""

META = {
    "name": "attn_text_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.tower_roofline_pct(ctx, "textual")
