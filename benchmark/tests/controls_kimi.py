#!/usr/bin/env python3
"""Sound readings and planted faults of a hybrid cell's ``correct``, several in
one process, so that the programs compile once:

    python3 benchmark/tests/controls_kimi.py --workload kimi-b16-p64-s1024 \\
        --run sound:3200000001 --run lost_expert:3200000001 --read sound:3200002001 ...

``--run <variant>:<seed>`` is ``run.py``'s own ``main`` on the cell (its job, its
``correct``, its result line), with the variant's fault planted first. ``--read
<variant>:<seed>`` makes the seed's weights and its ``check_rows`` sample only and
prints every reading of the comparison by row and by leaf, without the step. The
variants (what ``reference_kimi.py``'s bounds were set between):

    sound            nothing planted
    biased           the selection bias b drawn N(0, 0.05^2) in every routed layer, in the
                     program and in the reference alike: sound, and what makes the next one show
    select_by_score  the program keeps b = 0 (it selects by s) where the reference reads the
                     biased b (s + b): the fault "selection by s instead of s + b"
    lost_expert      held expert LOST's down projection zero in the program's tree, in the
                     routed layer whose gradients are compared (the latent-attention one),
                     where the reference reads the tree as initialised: that expert's term
                     left out of y, and no gradient to its gate and up stacks
    fp8              every flax Dense output of the program rounded through float8_e4m3fn
                     on the way forward: the nearest precision below the configuration's bfloat16

``--forced <seed>`` adds, to that seed's ``--read``, the reference run on the program's own
selection of experts (taken from the program's normalised stream at each router):
what is left then is arithmetic, what went is the near-ties.

The reference's programs, the step and the system's check are kept between
variants (``fp8`` builds its own step and check). **On the chip one ``--run`` a
process, and no ``--read`` after it**: a step that has run keeps its temporaries
reserved while it stays loaded, and the next run's check then waits for memory
for ever (PR 32's fix session lost its whole chip call to that). ``--read``s
alone share a process there; ``--rehearse`` and ``--any-device`` take what they
are given. Nothing here is read by the benchmark: it is how the bounds' control
readings are reproduced.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import harness  # noqa: E402
import run  # noqa: E402

VARIANTS = ("sound", "biased", "select_by_score", "lost_expert", "fp8")
LOST = 3  # the held expert that lost_expert loses
BIAS_STD = 0.05  # a quarter of the scores' spread at initialisation: other sets, no held expert idle

_kept: dict = {}  # what is compiled once: built steps, checks, the reference's programs
_now = {"variant": "sound", "reference_reads": {}}  # the run in hand


_patched: list = []  # (object, attribute, what it was): undone when main returns


def patch(obj, name: str, value) -> None:
    _patched.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def say(tag: str, **fields) -> None:
    harness.info_line("controls." + tag, variant=_now["variant"], **fields)


# -- planting -----------------------------------------------------------------


def routed_layers(params) -> list[str]:
    encoder = params["textual"]["encoder"]
    return sorted(name for name in encoder if name.startswith("block") and "moe" in encoder[name])


def with_leaves(params, leaves: dict):
    """``params`` with ``{(layer, leaf): array}`` in place of its routed layers' own."""
    if not leaves:
        return params
    encoder = dict(params["textual"]["encoder"])
    for (name, leaf), value in leaves.items():
        encoder[name] = {**encoder[name], "moe": {**encoder[name]["moe"], leaf: value}}
    return {**params, "textual": {**params["textual"], "encoder": encoder}}


def plant(variant: str, params):
    """``(the program's tree, what the reference reads in its place)``."""
    import jax
    import jax.numpy as jnp

    names = routed_layers(params)
    moe = {name: params["textual"]["encoder"][name]["moe"] for name in names}
    drawn = {
        (name, "select_bias"): BIAS_STD * jax.random.normal(jax.random.key(i), moe[name]["select_bias"].shape)
        for i, name in enumerate(names)
    }
    if variant == "biased":
        return with_leaves(params, drawn), {}
    if variant == "select_by_score":
        return params, drawn
    if variant == "lost_expert":
        # One layer, the routed one whose gradients are compared: two copies of a
        # stack (75 MB at the cell's size) beside a training state that fills the chip.
        name = next(n for n in names if "mla" in params["textual"]["encoder"][n])
        kept = {(name, "wo"): moe[name]["wo"]}
        return with_leaves(params, {(name, "wo"): jnp.asarray(moe[name]["wo"]).at[LOST].set(0.0)}), kept
    return params, {}


def round_dense_outputs(on: bool) -> None:
    """fp8: every flax Dense output through float8_e4m3fn while ``on``. The
    other program's step and check go first: two loaded steps do not fit a chip."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    other = "plain" if on else "fp8"
    _kept.pop(("check", id(_kept.pop(("built", other), None))), None)
    _kept.pop(("static", other), None)
    gc.collect()
    if "dense_call" not in _kept:
        _kept["dense_call"] = nn.Dense.__call__
        _patched.append((nn.Dense, "__call__", nn.Dense.__call__))
    plain = _kept["dense_call"]

    def rounded(self, x):  # forwards; the cotangent passes as it is (fp8 would flush it to zero)
        y = plain(self, x)
        return y + jax.lax.stop_gradient(y.astype(jnp.float8_e4m3fn).astype(y.dtype) - y)

    nn.Dense.__call__ = rounded if on else plain


# -- keeping what compiles ----------------------------------------------------


class KeptStep:
    """The jitted step, lowered and compiled once."""

    def __init__(self, step):
        self._step, self._lowered = step, None

    def lower(self, *args):
        if self._lowered is None:
            self._lowered = KeptLowered(self._step.lower(*args))
        return self._lowered

    def _cache_size(self):
        return self._step._cache_size()

    def __getattr__(self, name):  # accum_record, stack_record and the like
        return getattr(self._step, name)


class KeptLowered:
    def __init__(self, lowered):
        self._lowered, self._compiled = lowered, None

    def compile(self):
        if self._compiled is None:
            self._compiled = self._lowered.compile()
        return self._compiled


def kept_reference(module):
    """The reference module with its two makers returning one program a
    configuration, which reads the planted tree's stand-ins, and its two
    measures saying what they saw."""

    def keep(make):
        @functools.wraps(make)
        def made(cfg, *args):
            key = (make.__name__, repr(cfg), *(repr(getattr(a, "tolist", lambda: a)()) for a in args))
            if key not in _kept:
                _kept[key] = make(cfg, *args)
            return lambda params, *rest: _kept[key](with_leaves(params, _now["reference_reads"]), *rest)

        return made

    module.make_batch_loss = keep(module.make_batch_loss)
    module.make_sample_grads = keep(module.make_sample_grads)
    measure_tree, measure = module.tree_max_rel_err, module.max_rel_err

    def tree_max_rel_err(got, want):
        errs = measure_tree(got, want)
        say("grad_errs", by_leaf=errs, **leaf_readings(got, want))
        return errs

    def max_rel_err(got, want):
        say("embed_rows", **row_readings(got, want))
        return measure(got, want)

    module.tree_max_rel_err, module.max_rel_err = tree_max_rel_err, max_rel_err
    return module


def patch_job(job):
    """The job module ``run.main`` has just loaded, with its builders kept."""
    build, make_check, load = job.build_step, job.make_system_check, job.load_modules

    def build_step(cell, devices):
        kind = "fp8" if _now["variant"] == "fp8" else "plain"
        if ("built", kind) not in _kept:
            built = build(cell, devices)
            built.step = KeptStep(built.step)
            _kept["built", kind] = built
        return _kept["built", kind]

    def make_system_check(built, reference):
        if ("check", id(built)) not in _kept:
            _kept["check", id(built)] = make_check(built, reference)
        return _kept["check", id(built)]

    def load_modules(config):
        if "modules" not in _kept:
            reference, count = load(config)
            _kept["modules"] = (kept_reference(reference), count)
        return _kept["modules"]

    job.build_step, job.make_system_check, job.load_modules = build_step, make_system_check, load_modules
    return job


def patch_program() -> None:
    """``create_train_state`` hands the job the planted tree."""
    import distributed_sigmoid_loss_tpu.train as train

    create = train.create_train_state

    def create_train_state(*args, **kwargs):
        state = create(*args, **kwargs)
        params, _now["reference_reads"] = plant(_now["variant"], state.params)
        # The kept step was compiled for the first state's static fields (each
        # call makes its own optax.masked): every later state carries those.
        kind = "fp8" if _now["variant"] == "fp8" else "plain"
        apply_fn, tx = _kept.setdefault(("static", kind), (state.apply_fn, state.tx))
        return state.replace(params=params, apply_fn=apply_fn, tx=tx)

    patch(train, "create_train_state", create_train_state)


# -- the readings, by row and by leaf ------------------------------------------


def row_readings(got, want) -> dict:
    """Embeddings (rows, d): each row's max-norm and l2 relative error."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = got - want
    return {
        "max_norm": (np.abs(diff).max(-1) / np.abs(want).max(-1)).tolist(),
        "l2": (np.linalg.norm(diff, axis=-1) / np.linalg.norm(want, axis=-1)).tolist(),
        "whole_max_norm": float(np.abs(diff).max() / np.abs(want).max()),
    }


def leaf_readings(got, want) -> dict:
    """Gradients: each leaf's max-norm and Frobenius relative error, and an
    expert stack's by expert with each expert's norm on both sides."""
    import jax
    import numpy as np

    flat_g = dict(jax.tree_util.tree_leaves_with_path(got))
    out = {"max_norm": {}, "frobenius": {}, "frobenius_by_expert": {}, "norm_by_expert": {}}
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        name = jax.tree_util.keystr(path)
        g, w = np.asarray(flat_g[path], np.float64), np.asarray(w, np.float64)
        if not np.abs(w).max():  # the selection bias: no gradient on either side
            continue
        out["max_norm"][name] = float(np.abs(g - w).max() / np.abs(w).max())
        out["frobenius"][name] = float(np.linalg.norm(g - w) / np.linalg.norm(w))
        if "'moe'" in name and w.ndim == 3:
            per = np.linalg.norm((g - w).reshape(len(w), -1), axis=1) / np.maximum(
                np.linalg.norm(w.reshape(len(w), -1), axis=1), 1e-30
            )
            out["frobenius_by_expert"][name] = per.tolist()
            out["norm_by_expert"][name] = [  # (the system's, the reference's)
                np.linalg.norm(x.reshape(len(x), -1), axis=1).tolist() for x in (g, w)
            ]
    return out


def read(job, cell, devices, variant: str, seed: int, forced: bool) -> None:
    """One seed's weights and sample through the system's check and the
    reference, without the step."""
    import jax
    import numpy as np
    from distributed_sigmoid_loss_tpu.train.train_step import init_params

    _now["variant"] = variant
    round_dense_outputs(variant == "fp8")
    reference, _ = job.load_modules(cell.config)
    built = job.build_step(cell, devices)
    n = cell.traffic["check_rows"]
    k_init, k_batch = jax.random.split(jax.random.key(seed))
    params, reads = plant(variant, init_params(k_init, built.model, built.batch_shapes, built.mesh))
    _now["reference_reads"] = reads
    sample = jax.tree.map(lambda x: x[:n], job.make_batch(built, k_batch))
    loss, zimg, ztxt, grads = jax.device_get(job.make_system_check(built, reference)(params, sample))
    ref_loss, ref_grads = jax.device_get(
        reference.make_sample_grads(built.cfg)(params, sample["images"], sample["tokens"])
    )
    _, ref_zimg, ref_ztxt = jax.device_get(
        reference.make_batch_loss(built.cfg, n, np.arange(n)[None])(params, sample["images"], sample["tokens"])
    )
    out = {
        "seed": seed,
        "embed_err": max(reference.max_rel_err(zimg, ref_zimg), reference.max_rel_err(ztxt, ref_ztxt)),
        "sample_loss_err": abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
    }
    errs = reference.tree_max_rel_err(grads, ref_grads)
    out["grad_err"], out["grad_err_worst_leaf"] = max(errs.values()), max(errs, key=errs.get)
    if forced:
        out["on_the_programs_selection"] = on_the_programs_selection(
            built, reference, with_leaves(params, reads), sample, ztxt, grads
        )
    say("read", **out)


def on_the_programs_selection(built, reference, params, sample, ztxt, grads) -> dict:
    """The text rows and the compared gradients against the reference run on the
    experts the program chose: the program's normalised stream at each router
    (flax's intermediates), through the program's own routing function."""
    import jax
    from distributed_sigmoid_loss_tpu.models.moe import sigmoid_route
    from distributed_sigmoid_loss_tpu.parallel.mesh import trace_on

    t = built.cfg.text
    names = routed_layers(params)

    @jax.jit
    def chosen_by_program(params, sample):
        with trace_on(built.mesh):
            _, state = built.model.apply(
                {"params": params}, sample["images"], sample["tokens"], mutable=["intermediates"],
                capture_intermediates=lambda module, _: module.name == "ln2" and "textual" in module.path,
            )
        streams = state["intermediates"]["textual"]["encoder"]
        out = {}
        for name in names:
            x = streams[name]["ln2"]["__call__"][0]
            moe = params["textual"]["encoder"][name]["moe"]
            idx, _ = sigmoid_route(
                x.reshape(-1, x.shape[-1]), moe["router"], moe["select_bias"], t.moe_num_selected, t.moe_route_scale
            )
            out[name] = idx.reshape(*x.shape[:-1], -1)
        return out

    @jax.jit
    @reference._base._highest
    def text_rows(params, tokens, chosen):
        z = reference.text_tower(t, params["textual"], tokens, chosen=chosen)
        return reference._base.l2_normalize(z)

    base = reference._base

    @jax.jit
    @base._highest
    def sample_grads(params, images, tokens, chosen):
        def loss_of(sel):
            zimg = base.l2_normalize(base.vision_tower(built.cfg.vision, params["visual"], images, sel["visual"]))
            ztxt = base.l2_normalize(reference.text_tower(t, params["textual"], tokens, sel["textual"], chosen))
            return base.sigmoid_loss(zimg, ztxt, sel["loss_scalars"][0], sel["loss_scalars"][1])

        return jax.grad(loss_of)(reference.first_blocks(params))

    chosen = chosen_by_program(params, sample)
    rows = row_readings(ztxt, jax.device_get(text_rows(params, sample["tokens"], chosen)))
    leaves = leaf_readings(grads, jax.device_get(sample_grads(params, sample["images"], sample["tokens"], chosen)))
    return {"text_rows": rows, "gradients": {k: leaves[k] for k in ("frobenius", "frobenius_by_expert")}}


# -- main ----------------------------------------------------------------------


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
    ap.add_argument("--forced", action="append", type=int, default=[], metavar="SEED")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true", help="run.py's --rehearse: a tiny cell on the CPU")
    ap.add_argument("--rehearsal-dir", help="where --rehearse finds its tiny cell (default: benchmark/tests/rehearsal)")
    ap.add_argument("--any-device", action="store_true", help="--read on whatever jax finds (the CPU at full width)")
    args = ap.parse_args(argv)
    runs, reads = pairs(args.run), pairs(args.read)
    if not (args.rehearse or args.any_device) and runs and len(runs) + len(reads) > 1:
        raise SystemExit("on the chip: one --run a process and no --read after it (the docstring says why)")

    verdicts: list = []
    try:
        return controls(args, runs, reads, verdicts)
    finally:
        for obj, name, was in reversed(_patched):
            setattr(obj, name, was)
        _patched.clear()
        _kept.clear()
        _now.update(variant="sound", reference_reads={})
        gc.collect()


def controls(args, runs, reads, verdicts) -> int:
    load_module, result_line = harness.load_module, harness.result_line
    jobs_dir = os.path.join(harness.BENCH_DIR, "jobs") + os.sep
    patch(harness, "load_module", lambda path: patch_job(load_module(path)) if path.startswith(jobs_dir) else load_module(path))
    if args.rehearsal_dir:
        patch(harness, "REHEARSAL_DIR", args.rehearsal_dir)

    def keep_result_line(result):
        verdicts[-1].update(correct=result["correct"], failed=result["failed"], compared=result["compared"])
        result_line(result)

    patch(harness, "result_line", keep_result_line)
    cell = harness.Cell(args.workload, args.rehearse)
    harness.prepare_environment(cell, args.rehearse)
    patch_program()

    def through_run_py(variant, seed):
        _now["variant"] = variant
        round_dense_outputs(variant == "fp8")
        say("run", seed=seed)
        verdicts.append({"variant": variant, "seed": seed})
        verdicts[-1]["exit"] = run.main(
            ["--workload", args.workload, "--seed", str(seed), "--seconds", str(args.seconds), "--trace", "0"]
            + ["--rehearse"] * args.rehearse
        )
        gc.collect()  # the state and the batch of the run before go before the next one's come

    def fp8_last(items):  # fp8 unloads the other program's step and check: it goes last
        return sorted(items, key=lambda item: item[0] == "fp8")

    for variant, seed in (r for r in runs if r[0] != "fp8"):
        through_run_py(variant, seed)
    if reads:
        import jax

        job = harness.load_module(jobs_dir + cell.traffic["job"] + ".py")
        devices = jax.devices()[: cell.chips] if args.any_device else harness.require_devices(cell, args.rehearse)[0]
        for variant, seed in fp8_last(reads):
            read(job, cell, devices, variant, seed, seed in args.forced)
            gc.collect()
    for variant, seed in (r for r in runs if r[0] == "fp8"):
        through_run_py(variant, seed)
    print(json.dumps({"info": "controls.done", "runs": verdicts, "reads": len(reads)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
