"""Device self time per step of every operation under the looped stack's flax path (`textual/encoder/loop`): its matmuls, kernels, norms, rotary products and the gradient sums of its weights, forward, remat's forward and backward. benchmark/scopes_looped.py."""

META = {
    "name": "text_loops_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "towers", "moves": "pairs_per_s_per_chip", "workloads": ['ouro-b16-mb32x2'],
}


def read(ctx):
    import scopes_looped  # benchmark/ is on sys.path

    return scopes_looped.per_step_ms(ctx, scopes_looped.LOOP)
