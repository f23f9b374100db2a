"""Device self time per step of every operation under a routed layer of ungated relu2 experts (flax path `textual/encoder/block<i>/moe`, models/moe.py SharedExpertMoe): the router, the selection and the sort, the held experts' loops of gathered blocks, the shared expert, forward, remat's forward and backward. `moe_ms` under a name of this cell's own: that reader's accepted `workloads` list cannot be edited here. benchmark/scopes_nemotron.py."""

META = {
    "name": "relu2_moe_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "routed experts", "moves": "pairs_per_s_per_chip", "workloads": ['nemotron-b16-p16-s4096'],
}


def read(ctx):
    import scopes_nemotron  # benchmark/ is on sys.path

    return scopes_nemotron.per_step_ms(ctx, scopes_nemotron.MOE)
