"""Device self time per step under the program's `attn_gate` scope (models/transformer.py Attention): the RMSNorm over each head of q and of k and the product of the heads' outputs with the gate's sigmoid, forward, remat's forward and backward. The gate's projection is a plain product outside the scope. benchmark/scopes_trinity.py."""

META = {
    "name": "attn_gate_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.per_step_ms(ctx, scopes_trinity.ATTN_GATE)
