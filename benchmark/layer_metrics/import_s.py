"""Host seconds in the program's span ``startup.import``: the package's eager imports, jax's included where the package is the first to import it. ``startup_s`` less this is the TPU's start, late imports and glue."""

import setup_record

META = {
    "name": "import_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    return setup_record.covered_s(setup_record.named(rec, "startup.import"))
