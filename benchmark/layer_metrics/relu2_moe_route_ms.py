"""Device self time per step under the program's `moe_route` scope (models/moe.py) in a routed layer of relu2 experts: everything but the expert and shared-expert products: the float32 scores, top-k by score + bias, the renormalised weights, the sort of the held experts' assignments, the gathers of token rows and the scatter-adds of expert outputs. `moe_route_ms` under a name of this cell's own. benchmark/scopes_nemotron.py."""

META = {
    "name": "relu2_moe_route_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.per_step_ms(ctx, scopes_nemotron.MOE_ROUTE)
