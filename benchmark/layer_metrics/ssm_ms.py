"""Device self time per step under the flax path of a state-space layer's mixer (`textual/encoder/block<i>/ssm/...`, models/mixers.py SsmMixer): the fused input projection, the convolution, the recurrence, the gated group norm and the output projection, forward, remat's forward and backward. benchmark/scopes_nemotron.py."""

META = {
    "name": "ssm_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "state-space mixer", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.per_step_ms(ctx, scopes_nemotron.SSM)
