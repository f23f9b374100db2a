"""Share of the device's busy self time in none of the program's named parts (optimizer, loss_island, accum, step_metrics, visual, textual) and in no collective: what the names still miss. benchmark/scopes.py."""

META = {
    "name": "unscoped_pct", "unit": "%", "better": "lower", "source": "device_trace",
    "layer": "device", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.unscoped_pct(ctx)
