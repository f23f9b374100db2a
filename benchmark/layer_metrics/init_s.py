"""Host seconds in the program's span ``init_state`` (``create_train_state``), children included: the two init programs traced, lowered, compiled or loaded, run, and the state placed on the mesh."""

import setup_record

META = {
    "name": "init_s", "unit": "s", "better": "lower", "source": "host_clock",
    "layer": "step builder", "moves": "setup_s", "workloads": None,
}


def read(ctx):
    rec = setup_record.record(ctx)
    if rec is None:
        return None
    return setup_record.covered_s(setup_record.named(rec, "init_state"))
