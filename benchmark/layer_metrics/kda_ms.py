"""Device self time per step of every operation under a gated delta-rule layer's mixer (flax path `textual/encoder/block<i>/kda`, models/mixers.py): the q, k, v, gate, beta and output projections, the short convolutions, the l2 and head norms, the gates and the recurrence, forward, remat's forward and backward. benchmark/scopes_kimi.py."""

META = {
    "name": "kda_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "delta-rule mixer", "moves": "pairs_per_s_per_chip", "workloads": ['kimi-b16-p64-s1024'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.per_step_ms(ctx, scopes_kimi.KDA)
