"""The job ``train_step_check_first``: ``train_step.py``'s job with the comparison
against the plain reference made BEFORE the optimizer state exists, for a cell
whose training state leaves the comparison no room.

``train_step.py`` makes the whole training state, compiles the step and then runs
the system's forward and backward on the ``check_rows`` sample beside it. The
compiler sizes that check for an empty chip: it cannot know what the process
already holds. In ``evabyte-b16-p8-s8192`` the state is 9.07 GB (907 M parameters
at 10 bytes: float32 weight, bf16 first and float32 second moment) and the check
on three captions of 8192 bytes asks for 11.9 GB beside its 3.63 GB of parameters
(compiled for a described v5e, PR 39: 15.52 GB in all; one caption alone 7.73 GB,
where 7.57 are free beside the state), so on the chip it would wait for memory
that never comes. The check reads the parameters only. So here the seed's
parameters are made first (``init_params``: what ``create_train_state`` calls with
the same key, so the same numbers), the batch, then checks (1) and (3) and the
reference's loss for (2) through ``train_step.check_against_reference`` itself;
the parameters are dropped, and ``train_step.run`` does everything else as it does
it, handed the readings where it would have computed them (a leaf of its state is
held to the parameters that were checked). The set-up's time is counted as before:
the phases carry the same names.
"""

from __future__ import annotations

import os
import types

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness

# A private instance of the job this one re-orders: the name patched below is its own.
_base = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", "train_step.py"))

# What the tools under tests/ call on a job module (compile_for_chip.py, the controls).
load_modules = _base.load_modules
build_config = _base.build_config
build_step = _base.build_step
make_batch = _base.make_batch
make_system_check = _base.make_system_check
check_against_reference = _base.check_against_reference


def _fingerprint(params):
    import jax

    return jax.device_get(params["textual"]["proj"]["kernel"][:8, :8]).tolist()


def run(ctx) -> dict:
    import jax
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    cell, phases = ctx.cell, ctx.phases
    reference, _ = load_modules(cell.config)
    built = build_step(cell, ctx.devices)
    phases.done("import")
    k_init, k_batch = jax.random.split(jax.random.key(ctx.seed))  # as train_step.run splits it
    params = init_params(k_init, built.model, built.batch_shapes, built.mesh)
    batch = make_batch(built, k_batch)
    jax.block_until_ready((params, batch))
    phases.done("init")
    checks = check_against_reference(built, cell.traffic, types.SimpleNamespace(params=params), batch, reference)
    checked = _fingerprint(params)
    del params, batch
    phases.done("reference")

    def already_made(built, mix, state, batch, reference):
        if _fingerprint(state.params) != checked:
            raise RuntimeError("the state's parameters are not the ones that were checked")
        return checks

    _base.check_against_reference = already_made
    try:
        return _base.run(ctx)
    finally:
        _base.check_against_reference = check_against_reference
