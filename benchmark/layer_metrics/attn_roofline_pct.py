"""The least time the chip could take for the attention a step needs (the larger of 12 s^2 w operations per layer per sequence over the bf16 peak and 24 s w bytes over the HBM peak, forward + backward, both towers, from flops.py; memory-bound at these lengths: s/2 operations per byte, 98 and 32, against the chip's 240) over the custom-call time per step. An aggregate over the attention kernels: a split per kernel needs kernel names the program does not give yet."""

META = {
    "name": "attn_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import flops  # benchmark/ is on sys.path

    d, c, peaks = ctx["trace"]["device"], ctx["counters"], ctx["peaks"]
    if not d["group_s"]["custom_call"] or not d["steps"]:
        return None
    pairs = c["pairs_per_chip_per_step"]
    least_s = max(
        flops.attention_flops_per_pair(c["cfg"]) * pairs / (peaks["bf16_tflops"] * 1e12),
        flops.attention_bytes_per_pair(c["cfg"]) * pairs / (peaks["hbm_gb_per_s"] * 1e9),
    )
    return 100.0 * least_s / (d["group_s"]["custom_call"] / d["steps"])
