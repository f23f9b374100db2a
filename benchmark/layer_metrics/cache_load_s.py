"""Host seconds under the backend-compile spans marked ``hit``: programs read from the persistent cache and loaded onto the device. 0.0 where the cache is off or cold."""

import setup_record

META = {
    "name": "cache_load_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    return setup_record.covered_s(setup_record.named(rec, "jax.backend_compile", cache=("hit",)))
