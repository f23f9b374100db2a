"""Device self time per step under the flax path of a windowed-chunk-attention layer's mixer (`textual/encoder/blocks/block/eva/...`, models/mixers.py EvaAttention): the q, k, v projections, the rotation, the pooling, the core and the output projection, forward, remat's forward and backward. benchmark/scopes_eva.py."""

META = {
    "name": "eva_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "windowed chunk attention", "moves": "pairs_per_s_per_chip", "workloads": ['evabyte-b16-p8-s8192'],
}


def read(ctx):
    import scopes_eva  # benchmark/ is on sys.path

    return scopes_eva.per_step_ms(ctx, scopes_eva.EVA)
