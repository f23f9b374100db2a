"""Device self time per step under the program's `moe_route` scope (models/moe.py): everything of a routed layer but the expert and shared-expert products: the float32 scores, top-k by score + bias, the renormalised weights, the sort of the held experts' assignments, the gathers of token rows and the scatter-adds of expert outputs. benchmark/scopes_kimi.py."""

META = {
    "name": "moe_route_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts", "moves": "pairs_per_s_per_chip", "workloads": ['kimi-b16-p64-s1024'],
}


def read(ctx):
    import scopes_kimi  # benchmark/ is on sys.path

    return scopes_kimi.per_step_ms(ctx, scopes_kimi.MOE_ROUTE)
