"""Device self time per step under the program's `kda_core` scope (models/mixers.py around ops/gated_delta_rule.py chunk_gated_delta_rule): the recurrence alone, from q, k, v, g, beta to o, in whatever form the program runs it, forward, remat's forward and backward. benchmark/scopes_kimi.py."""

META = {
    "name": "kda_core_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "delta-rule mixer", "moves": "pairs_per_s_per_chip", "workloads": ['kimi-b16-p64-s1024'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.per_step_ms(ctx, scopes_kimi.KDA_CORE)
