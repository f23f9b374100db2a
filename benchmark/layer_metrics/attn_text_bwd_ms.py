"""Device time per step of the custom calls named `short_attn_bwd` under `textual/`: the text tower's attention backward. benchmark/scopes.py."""

META = {
    "name": "attn_text_bwd_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.kernel_ms(ctx, "textual", "short_attn_bwd")
