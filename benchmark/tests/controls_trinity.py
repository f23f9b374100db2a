#!/usr/bin/env python3
"""Sound readings and planted faults of the window / full attention cell's
``correct``, several in one process, so that the reference's programs compile
once: ``controls_eva.py``'s tool (its ``--run`` / ``--read``, ``--rehearse`` and
``--any-device``) with this tower's faults:

    python3 benchmark/tests/controls_trinity.py --workload trinity-b16-p8-s8192 \\
        --read sound:4700000001 --read no_window:4700000001 --read fp8:4700000001 ...

The faults are planted in the PROGRAM's trace, the reference keeps the equations;
each must fail at least one of ``reference_trinity.py``'s bounds:

    sound          nothing planted
    no_window      the window layers run as full layers: ``models/transformer.py attention_core`` is
                   asked with no window, so the core the layer takes reads every key up to the query's
    rope_all       rotation on the full layers too: every layer's ``Attention`` is handed the window
                   layers' ``rope_theta`` (``models/text.py layer_specs``)
    no_gate        the gate left out: the projection named ``gate`` hands back 40 everywhere, whose
                   sigmoid is 1 (its kernel keeps its place in the tree and gets no gradient)
    no_qk_norm     the heads of q and k go on unnormalised: the modules named ``q_norm`` and
                   ``k_norm`` hand back their input (their scales get no gradient)
    fp8            the text tower's attention cores' operands (q, k, v) and their output rounded to
                   float8_e4m3's precision by ``lax.reduce_precision`` on the way forward: the nearest
                   precision below the configuration's bfloat16

On the chip ``--read``s of every variant share one process; give a ``--run`` a
process of its own (``controls_kimi.py`` says why). Nothing here is read by the
benchmark: it is how ``reference_trinity.py``'s control readings are reproduced.
"""

from __future__ import annotations

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402

eva = harness.load_module(os.path.join(BENCH_DIR, "tests", "controls_eva.py"))

VARIANTS = ("sound", "no_window", "rope_all", "no_gate", "no_qk_norm", "fp8")
GATE_OF_ONE = 40.0  # sigmoid(40) is 1 in bfloat16 and in float32


def plant(variant: str) -> None:
    """``variant``'s fault in place of whatever was planted before."""
    import dataclasses

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from distributed_sigmoid_loss_tpu.models import text, transformer
    from distributed_sigmoid_loss_tpu.ops import pallas_latent_attention
    from distributed_sigmoid_loss_tpu.parallel import ring_attention

    eva.lift()

    def low(x):  # forwards; the cotangent passes as it is
        return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) - x)

    if variant == "no_window":
        core = transformer.attention_core
        eva._swap(transformer, "attention_core", lambda *a, **kw: core(*a, **{**kw, "window": 0}))
    elif variant == "rope_all":
        specs = text.layer_specs

        def every_layer_rotates(cfg):
            def rotated(spec):
                fields = tuple((k, cfg.rope_theta if k == "rope_theta" else v) for k, v in spec.mixer_fields)
                return dataclasses.replace(spec, mixer_fields=fields)

            return tuple(rotated(spec) if spec.mixer == "attn" else spec for spec in specs(cfg))

        eva._swap(text, "layer_specs", every_layer_rotates)
    elif variant == "no_gate":
        exact = nn.Dense.__call__

        def open_gate(self, x):
            y = exact(self, x)
            return jnp.full_like(y, GATE_OF_ONE) if self.name == "gate" and "textual" in self.path else y

        eva._swap(nn.Dense, "__call__", open_gate)
    elif variant == "no_qk_norm":
        normed = nn.RMSNorm.__call__

        def unnormed(self, x, *args, **kwargs):
            y = normed(self, x, *args, **kwargs)  # the scale keeps its place in the tree
            return x if self.name in ("q_norm", "k_norm") else y

        eva._swap(nn.RMSNorm, "__call__", unnormed)
    elif variant == "fp8":
        # ``Attention`` looks both cores up when it is called; the image tower's fused kernels
        # (``short_attn_*``) are neither, and on the CPU, where it too runs ``dense_attention``,
        # an operand that is not the text tower's width of heads passes as it is.
        def rounded(core):
            return lambda q, k, v, **kw: low(core(low(q), low(k), low(v), **kw))

        eva._swap(pallas_latent_attention, "latent_attention_kernel", rounded(pallas_latent_attention.latent_attention_kernel))
        eva._swap(ring_attention, "dense_attention", rounded(ring_attention.dense_attention))
    elif variant != "sound":
        raise SystemExit(f"unknown variant {variant!r}: want one of {VARIANTS}")


def main(argv=None) -> int:
    """``controls_eva.py``'s ``main`` with this tower's faults."""
    eva.VARIANTS, eva.plant = VARIANTS, plant
    return eva.main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
