"""Device self time per step under the program's `rope` scope (models/transformer.py rope): the rotary product on q and k of every layer application, forward, the forward that remat runs again, and backward. benchmark/scopes_looped.py."""

META = {
    "name": "rope_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "towers", "moves": "pairs_per_s_per_chip", "workloads": ['ouro-b16-mb32x2'],
}


def read(ctx):
    import scopes_looped  # benchmark/ is on sys.path

    return scopes_looped.per_step_ms(ctx, scopes_looped.ROPE)
