"""Device self time per step of every operation under a routed layer (flax path `textual/encoder/block<i>/moe`, models/moe.py SharedExpertMoe): the router, the selection and the sort, the held experts' loops of gathered blocks, the shared expert, forward, remat's forward and backward. benchmark/scopes_kimi.py."""

META = {
    "name": "moe_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts", "moves": "pairs_per_s_per_chip", "workloads": ['kimi-b16-p64-s1024'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.per_step_ms(ctx, scopes_kimi.MOE)
