"""Host seconds in ``step.lower(...)``: tracing the step and lowering it to StableHLO, the part of set-up that the compile cache cannot remove."""

META = {
    "name": "trace_lower_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    return ctx["counters"]["trace_lower_s"]
