"""Device self time per step of every operation under a routed layer beside a gated attention (flax path `textual/encoder/block<i>/moe`, models/moe.py SharedExpertMoe): the router, the selection and the sort, the held experts' loops of gathered blocks, the shared expert, forward, remat's forward and backward. `moe_ms` under a name of this cell's own: that reader's accepted `workloads` list cannot be edited here. benchmark/scopes_trinity.py."""

META = {
    "name": "gattn_moe_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.per_step_ms(ctx, scopes_trinity.MOE)
