#!/usr/bin/env python3
"""Sound readings and planted faults of the windowed-chunk-attention cell's
``correct``, several in one process, so that the reference's programs compile once:

    python3 benchmark/tests/controls_eva.py --workload evabyte-b16-p8-s8192 \\
        --read sound:3900000001 --read no_summaries:3900000001 --read fp8:3900000001 ...

``--read <variant>:<seed>`` makes the seed's weights and its ``check_rows`` sample
only and prints the three readings of the comparison (embeddings, the sample's
loss, the compared gradients with the worst leaf and every leaf), without the
step. ``--run <variant>:<seed>`` is ``run.py``'s own ``main`` on the cell (its job,
its ``correct``, its result line) with the variant's fault planted first. The
faults are planted in the PROGRAM's trace (``models/mixers.py``), the reference
keeps the equations; each must fail at least one of ``reference_eva.py``'s bounds:

    sound            nothing planted
    no_summaries     the summaries left out: every window attends to itself alone
                     (the core is called with the windows as rows, so none is admitted)
    no_mu            the summaries' key offset mu_h left out (kc = sum a k): mu gets no gradient
    shifted_mask     the summaries' mask shifted by one window: a query of window w meets
                     the summaries of windows 1..w (its own among them) where it should
                     meet those of windows 0..w-1
    uniform_pooling  the chunks' weights uniform (phi read as 0): a mean of the chunk; phi gets no gradient
    fp8              the cores' operands (q, k, v, the summaries) and their output rounded to
                     float8_e4m3's precision by ``lax.reduce_precision`` on the way forward:
                     the nearest precision below the configuration's bfloat16
    fp8_dense        every flax Dense output of the program rounded so too

On the chip ``--read``s of every variant share one process (each variant compiles
its own check, a minute or so); give a ``--run`` a process of its own
(``controls_kimi.py`` says why: a step that has run keeps its temporaries).
``--rehearse --rehearsal-dir`` takes a toy cell on the CPU, ``--any-device``
``--read``s on whatever jax finds. Nothing here is read by the benchmark: it is how
``reference_eva.py``'s control readings are reproduced.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402
import run  # noqa: E402

VARIANTS = ("sound", "no_summaries", "no_mu", "shifted_mask", "uniform_pooling", "fp8", "fp8_dense")

_undo: list = []  # (object, attribute, what it was)


def _swap(obj, name: str, value) -> None:
    _undo.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def lift() -> None:
    for obj, name, was in reversed(_undo):
        setattr(obj, name, was)
    _undo.clear()


def plant(variant: str) -> None:
    """``variant``'s fault in place of whatever was planted before."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from distributed_sigmoid_loss_tpu.models import mixers
    from distributed_sigmoid_loss_tpu.ops import pallas_eva_attention

    lift()
    cores = ((pallas_eva_attention, "eva_attention_kernel"), (mixers, "eva_core_dense"))
    summaries = mixers.eva_summaries

    def low(x):  # forwards; the cotangent passes as it is
        return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3) - x)

    def around_cores(wrap):
        for module, name in cores:
            _swap(module, name, wrap(getattr(module, name)))

    if variant == "no_summaries":
        def windows_alone(core):
            def alone(q, k, v, kc, vc, **kw):
                b, s, width = q.shape
                n = s // kw["window"]
                rows = [x.reshape(b * n, x.shape[1] // n, width) for x in (q, k, v, kc, vc)]
                return core(*rows, **kw).reshape(b, s, width)
            return alone
        around_cores(windows_alone)
    elif variant == "shifted_mask":
        def one_window_on(core):
            def shifted(q, k, v, kc, vc, **kw):
                per_window = kc.shape[1] * kw["window"] // q.shape[1]
                return core(q, k, v, jnp.roll(kc, -per_window, 1), jnp.roll(vc, -per_window, 1), **kw)
            return shifted
        around_cores(one_window_on)
    elif variant == "no_mu":
        _swap(mixers, "eva_summaries", lambda k, v, phi, mu, *a: summaries(k, v, phi, jnp.zeros_like(mu), *a))
    elif variant == "uniform_pooling":
        _swap(mixers, "eva_summaries", lambda k, v, phi, mu, *a: summaries(k, v, jnp.zeros_like(phi), mu, *a))
    elif variant == "fp8":
        around_cores(lambda core: lambda *xs, **kw: low(core(*(low(x) for x in xs), **kw)))
    elif variant == "fp8_dense":
        exact = nn.Dense.__call__
        _swap(nn.Dense, "__call__", lambda self, x: low(exact(self, x)))
    elif variant != "sound":
        raise SystemExit(f"unknown variant {variant!r}: want one of {VARIANTS}")


def say(tag: str, **fields) -> None:
    harness.info_line("controls." + tag, **fields)


def read(job, cell, devices, kept: dict, variant: str, seed: int) -> None:
    """One seed's weights and sample through the system's check and the
    reference, without the step."""
    import jax
    import numpy as np
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    plant(variant)
    reference, _ = kept.setdefault("modules", job.load_modules(cell.config))
    built = job.build_step(cell, devices)  # the model of this variant's trace
    n = cell.traffic["check_rows"]
    k_init, k_batch = jax.random.split(jax.random.key(seed))
    params = init_params(k_init, built.model, built.batch_shapes, built.mesh)
    sample = jax.tree.map(lambda x: x[:n], job.make_batch(built, k_batch))
    loss, zimg, ztxt, grads = jax.device_get(job.make_system_check(built, reference)(params, sample))
    if "sample_grads" not in kept:
        kept["sample_grads"] = reference.make_sample_grads(built.cfg)
        kept["batch_loss"] = reference.make_batch_loss(built.cfg, 1, np.arange(n)[None])
    ref_loss, ref_grads = jax.device_get(kept["sample_grads"](params, sample["images"], sample["tokens"]))
    _, ref_zimg, ref_ztxt = jax.device_get(kept["batch_loss"](params, sample["images"], sample["tokens"]))
    errs = reference.tree_max_rel_err(grads, ref_grads)
    say("read", variant=variant, seed=seed,
        embed_err=max(reference.max_rel_err(zimg, ref_zimg), reference.max_rel_err(ztxt, ref_ztxt)),
        embed_err_text=reference.max_rel_err(ztxt, ref_ztxt),
        sample_loss_err=abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
        grad_err=max(errs.values()), grad_err_worst_leaf=max(errs, key=errs.get), grad_errs=errs,
        bounds={"embed_err": reference.EMBED_BOUND, "loss_err": reference.LOSS_BOUND, "grad_err": reference.GRAD_BOUND})
    del params, sample, built
    gc.collect()


def pairs(values):
    out = []
    for v in values:
        variant, _, seed = v.partition(":")
        if variant not in VARIANTS or not seed.isdigit():
            raise SystemExit(f"want <variant>:<seed> with a variant of {VARIANTS}, got {v!r}")
        out.append((variant, int(seed)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--run", action="append", default=[], metavar="VARIANT:SEED")
    ap.add_argument("--read", action="append", default=[], metavar="VARIANT:SEED")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true", help="run.py's --rehearse: a tiny cell on the CPU")
    ap.add_argument("--rehearsal-dir", help="where --rehearse finds its tiny cell (default: benchmark/tests/rehearsal)")
    ap.add_argument("--any-device", action="store_true", help="--read on whatever jax finds")
    args = ap.parse_args(argv)
    runs, reads = pairs(args.run), pairs(args.read)
    if not (args.rehearse or args.any_device) and runs and len(runs) + len(reads) > 1:
        raise SystemExit("on the chip: one --run a process and no --read beside it (the docstring says why)")

    was_dir, was_line = harness.REHEARSAL_DIR, harness.result_line
    verdicts: list = []

    def keep_result_line(result):
        verdicts[-1].update(correct=result["correct"], failed=result["failed"], compared=result["compared"])
        was_line(result)

    try:
        if args.rehearsal_dir:
            harness.REHEARSAL_DIR = args.rehearsal_dir
        harness.result_line = keep_result_line
        cell = harness.Cell(args.workload, args.rehearse)
        harness.prepare_environment(cell, args.rehearse)
        for variant, seed in runs:
            plant(variant)
            say("run", variant=variant, seed=seed)
            verdicts.append({"variant": variant, "seed": seed})
            verdicts[-1]["exit"] = run.main(
                ["--workload", args.workload, "--seed", str(seed), "--seconds", str(args.seconds), "--trace", "0"]
                + ["--rehearse"] * args.rehearse
            )
            gc.collect()
        if reads:
            import jax

            job = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", cell.traffic["job"] + ".py"))
            devices = jax.devices()[: cell.chips] if args.any_device else harness.require_devices(cell, args.rehearse)[0]
            kept: dict = {}
            for variant, seed in reads:
                read(job, cell, devices, kept, variant, seed)
        print(json.dumps({"info": "controls.done", "runs": verdicts, "reads": len(reads)}), flush=True)
        return 0
    finally:
        lift()
        harness.REHEARSAL_DIR, harness.result_line = was_dir, was_line


if __name__ == "__main__":
    raise SystemExit(main())
