"""Backend compilations jax reported inside the window, plus the growth of the step's jit cache: the window must compile nothing."""

META = {
    "name": "recompiles", "unit": "count", "better": "lower", "source": "program_counter",
    "layer": "runtime boundary", "moves": "pairs_per_s_per_chip", "workloads": None,
}


def read(ctx):
    return ctx["counters"]["recompiles"]
