"""The job ``train_step_balanced_on_batch``: ``train_step_check_first.py``'s job
with the seed's batch itself handed to the program's initialisation, where the
two jobs it is made of hand it the batch's shapes.

For a configuration with ``text.moe_balanced_init`` the program ends its
initialisation by setting every router's selection bias so that the experts are
chosen evenly on the sample batch it is given (train/train_step.py
``balance_routers``): on the batch's own token ids where the sample carries them,
as ``cli.py train`` hands it its first batch, and on uniform ids of its own where
the sample is shapes. ``train_step.py`` hands it shapes, and the bias found on
other ids is then tried on the seed's batch. That holds where every token's
router input is its own (``nemotron-b16-p16-s4096``: 16 blocks a layer on every
seed). It does not in a stack with sandwich norms at random weights: the norm
after each attention hands a sequence's tokens a common part, a sequence then
loads single experts several times over, a step has 8 sequences, and the chip's
sixteen held experts see 0.90 to 1.02 of their expected rows by seed; the step
follows them, and six seeds spread by 0.94 % where a cell is admitted under 0.5
(PERF.md section 6, PR 47). A window repeats ONE batch, so what the recipe's
balancing update would hold a router at over such a stream is the balance on
that batch: here the program finds it, by the same pass, on the ids the window
then repeats, and every held expert is given tokens x k / E of them a step on
every seed.

Nothing else differs: the same keys give the same weights and the same batch,
the comparison with the reference runs on the parameters the step then trains
(``train_step_check_first.py`` holds a leaf of the state to them), the phases
carry the same names. The batch takes the shapes' place in what ``build_step``
returns (``batch_shapes``: an array has the shape and the type the readers of
that field ask for), for ``run`` alone: the tools under tests/ that call a job's
``build_step`` describe a chip and get shapes.
"""

from __future__ import annotations

import os

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness

# A private instance of the job this one hands a batch, and of the one that job re-orders.
_first = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", "train_step_check_first.py"))
_base = _first._base

# What the tools under tests/ call on a job module (compile_for_chip.py, the controls).
load_modules = _base.load_modules
build_config = _base.build_config
build_step = _base.build_step
make_batch = _base.make_batch
make_system_check = _base.make_system_check
check_against_reference = _base.check_against_reference


def run(ctx) -> dict:
    import jax

    def with_the_batch(cell, devices):
        built = build_step(cell, devices)
        _, k_batch = jax.random.split(jax.random.key(ctx.seed))  # as both jobs split it
        built.batch_shapes = make_batch(built, k_batch)
        return built

    _first.build_step = _base.build_step = with_the_batch
    try:
        return _first.run(ctx)
    finally:
        _first.build_step = _base.build_step = build_step
