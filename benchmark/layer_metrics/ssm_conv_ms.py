"""Device self time per step under the program's `ssm_conv` scope (models/mixers.py SsmMixer): the causal depthwise convolution of 4 taps over the convolved channels (x, B, C), its bias and the silu, forward, remat's forward and backward. benchmark/scopes_nemotron.py."""

META = {
    "name": "ssm_conv_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "state-space mixer", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.per_step_ms(ctx, scopes_nemotron.SSM_CONV)
