"""Device self time per step under the program's `accum` scope (train/train_step.py accum_zeros, accum_add, accum_finish): the gradient accumulator's traffic in the microbatch scan, where no tower path encloses it; the cells that accumulate. benchmark/scopes.py."""

META = {
    "name": "accum_ms", "unit": "ms", "better": "lower", "source": "device_trace",
    "layer": "step builder", "moves": "pairs_per_s_per_chip", "workloads": ['b16-mb128x8', 'so400m-mb32x4', 'ouro-b16-mb32x2'],
}


def read(ctx):
    import scopes  # benchmark/ is on sys.path

    return scopes.part_ms(ctx, "accum")
