#!/usr/bin/env python3
"""Sound readings and planted faults of the rotated latent-attention cell's
``correct``: ``controls_kimi.py``'s tool (its ``--run`` / ``--read`` / ``--forced``,
its kept programs, its planted selection bias and lost expert) with the faults
that change the traced program planted here, where the reference keeps the
equations:

    python3 benchmark/tests/controls_glm.py --workload glm-b16-p16-s4096 \
        --run no_rope:3500000001 --read sound:3500000002 ...

    no_rope     the rotation dropped: ``models/transformer.py rope`` hands back what it was
                given, so the queries' 64-wide parts and the shared key part go unrotated
    no_q_norm   the queries' latent goes on unnormalised: the module named ``q_norm`` hands
                back its input (its scale keeps its place in the tree and gets no gradient)
    fp8         every flax Dense output of the program rounded through float8_e4m3fn on the
                way forward: the nearest precision below the configuration's bfloat16.
                ``controls_kimi.py`` casts there and straight back, a pair the chip's compiler
                removes as excess precision (my chip run, PR 35: two seeds read as sound,
                0.0184 / 0.0189 against 0.0178 / 0.0179); here an ``optimization_barrier``
                stands between the two casts

``--bias-std X`` draws the selection bias of ``biased`` / ``select_by_score`` from
N(0, X^2) in place of ``controls_kimi.BIAS_STD`` (0.05). These change the traced
program, so nothing built is kept across them: the step, the check and the
state's static fields are dropped when such a fault comes or goes.
``controls_kimi.py`` says what else holds (on the chip one ``--run`` a process).
Nothing here is read by the benchmark: it is how ``reference_glm.py``'s control
readings are reproduced.
"""

from __future__ import annotations

import gc
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402

kimi = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_kimi.py"))

TRACED_FAULTS = ("no_rope", "no_q_norm", "fp8")
kimi.VARIANTS = kimi.VARIANTS + TRACED_FAULTS[:2]

_planted = {"fault": None, "undo": []}


def plant_in_trace(fault: str | None) -> None:
    """``fault`` in place of the one planted before (None: the program as it is)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from distributed_sigmoid_loss_tpu.models import transformer

    for obj, name, was in reversed(_planted["undo"]):
        setattr(obj, name, was)
    _planted.update(fault=fault, undo=[])

    def swap(obj, name, value):
        _planted["undo"].append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "no_rope":
        swap(transformer, "rope", lambda x, theta: x)
    elif fault == "no_q_norm":
        normed = nn.RMSNorm.__call__

        def unnormed(self, x, *args, **kwargs):
            y = normed(self, x, *args, **kwargs)  # the scale keeps its place in the tree
            return x if self.name == "q_norm" else y

        swap(nn.RMSNorm, "__call__", unnormed)
    elif fault == "fp8":
        exact = nn.Dense.__call__

        def rounded(self, x):  # forwards; the cotangent passes as it is (fp8 would flush it to zero)
            y = exact(self, x)
            low = jax.lax.optimization_barrier(y.astype(jnp.float8_e4m3fn))  # else the compiler drops the pair of casts
            return y + jax.lax.stop_gradient(low.astype(y.dtype) - y)

        swap(nn.Dense, "__call__", rounded)


def program_of_the_variant(on: bool) -> None:
    """Stands in for ``controls_kimi.round_dense_outputs``, which both of its
    loops call once the variant in hand is set: a fault of the trace is planted
    or lifted, with everything built under the other program dropped."""
    variant = kimi._now["variant"]
    fault = variant if variant in TRACED_FAULTS else None
    if fault != _planted["fault"]:
        for key in [k for k in kimi._kept if isinstance(k, tuple) and k[0] in ("built", "check", "static")]:
            del kimi._kept[key]
        gc.collect()
        plant_in_trace(fault)


kimi.round_dense_outputs = program_of_the_variant


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    bias_std = kimi.BIAS_STD
    if "--bias-std" in argv:
        at = argv.index("--bias-std")
        kimi.BIAS_STD = float(argv[at + 1])
        del argv[at : at + 2]
    try:
        return kimi.main(argv)
    finally:
        kimi.BIAS_STD = bias_std
        plant_in_trace(None)


if __name__ == "__main__":
    raise SystemExit(main())
