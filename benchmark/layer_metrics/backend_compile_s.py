"""Host seconds under the backend-compile spans that the persistent cache did not serve (``jax.backend_compile`` marked ``miss``, or ``off`` where the cache was not asked): real compiles. Near 0 on a run that found its programs, but for those under a second, which jax never stores."""

import setup_record

META = {
    "name": "backend_compile_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    return setup_record.covered_s(setup_record.named(rec, "jax.backend_compile", cache=("miss", "off")))
