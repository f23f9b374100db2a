"""Device self time per step under the program's `moe_route` scope (models/moe.py) in a routed layer beside a gated attention: everything but the expert and shared-expert products: the float32 scores, top-k by score + bias, the renormalised weights, the sort of the held experts' assignments, the gathers of token rows and the scatter-adds of expert outputs. `moe_route_ms` under a name of this cell's own. benchmark/scopes_trinity.py."""

META = {
    "name": "gattn_moe_route_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.per_step_ms(ctx, scopes_trinity.MOE_ROUTE)
