"""Device self time per step under the program's `rope` scope (models/transformer.py rope) in a tower of gated window / full attention layers: the rotary product on q and k of the window layers, which alone rotate (`rope_layers="window"`), forward, remat's forward and backward. Part of `gattn_ms`. `rope_ms` under a name of this cell's. benchmark/scopes_trinity.py."""

META = {
    "name": "gattn_rope_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "gated window / full attention", "moves": "pairs_per_s_per_chip", "workloads": ['trinity-b16-p8-s8192'],
}


def read(ctx):
    import scopes_trinity  # benchmark/ is on sys.path

    return scopes_trinity.per_step_ms(ctx, scopes_trinity.ROPE)
