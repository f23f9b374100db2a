"""Host seconds under every trace and lowering span of the process up to the reading (``jax.trace``, ``jax.lower``; nested ones once): the step's (``trace_lower_s`` is the harness's clock around that one), init's, the harness's check's and the reference's. No cache removes it. 0.0 where nothing was traced."""

import setup_record

META = {
    "name": "trace_lower_total_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    return setup_record.covered_s(
        setup_record.named(rec, "jax.trace") + setup_record.named(rec, "jax.lower")
    )
