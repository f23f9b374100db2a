"""Host seconds from the process's start (the OS's) to the start of the first trace span of the process: the interpreter, every import, the TPU's start, the mesh and the step builder; the harness's ``import`` phase seen from inside the program. 0.0 where nothing was traced."""

import setup_record

META = {
    "name": "startup_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "runtime boundary", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    traces = setup_record.named(rec, "jax.trace")
    return min(s.t0 for s in traces) - rec.start if traces else 0.0
