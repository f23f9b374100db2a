"""Device self time per step under the program's `eva_core` scope (models/mixers.py EvaAttention): the exact scores inside a window, the scores against the earlier windows' summaries, the one softmax over both and the values (the kernels `eva_attn_fwd` / `eva_attn_bwd` where the program takes them), forward, remat's forward and backward. benchmark/scopes_eva.py."""

META = {
    "name": "eva_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "windowed chunk attention", "moves": "pairs_per_s_per_chip", "workloads": ['evabyte-b16-p8-s8192'],
}


def read(ctx):
    import scopes_eva  # benchmark/ is on sys.path

    return scopes_eva.per_step_ms(ctx, scopes_eva.EVA_CORE)
