"""Device self time per step under the program's `eva_summary` scope (models/mixers.py EvaAttention): the pooling of the rotated keys and the values into one summary a chunk of 16 tokens (the chunks' softmax weights, the two weighted sums, the offset mu), forward, remat's forward and backward. benchmark/scopes_eva.py."""

META = {
    "name": "eva_summary_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "windowed chunk attention", "moves": "pairs_per_s_per_chip", "workloads": ['evabyte-b16-p8-s8192'],
}


def read(ctx):
    import scopes_eva  # benchmark/ is on sys.path

    return scopes_eva.per_step_ms(ctx, scopes_eva.EVA_SUMMARY)
