"""Device time per step of the custom calls named `short_attn_fwd` under `visual/`: the vision tower's attention forward, and the forward that remat runs again in the backward pass. benchmark/scopes.py."""

META = {
    "name": "attn_vision_fwd_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "attention kernels", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.kernel_ms(ctx, "visual", "short_attn_fwd")
