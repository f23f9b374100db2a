"""The job ``train_step``: full optimizer steps of the SigLIP trainer on a
synthetic batch that lives on the device, for a fixed window.

The step is built through the program's own entry points (``SigLIP``,
``make_mesh``, ``make_optimizer``, ``create_train_state``, ``make_train_step``)
from the cell's configuration file and traffic mix; the window, the counters and
the comparison with the plain reference are the benchmark's.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import statistics
import time
import types

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness


def load_modules(config: dict):
    """The plain reference and the operation count of a configuration, the two
    files under benchmark/ its ``modules`` names (benchmark/README.md has what
    each must offer): the model's mathematics and its count belong to the
    configuration, not to the job."""
    names = config.get("modules", {})
    if set(names) != {"reference", "count"}:
        raise harness.Refused('the configuration file needs "modules": {"reference": ..., "count": ...}')
    return tuple(
        harness.load_module(os.path.join(harness.BENCH_DIR, names[kind] + ".py"))
        for kind in ("reference", "count")
    )


def build_config(config: dict, traffic: dict):
    """``SigLIPConfig`` from the configuration file's sections, field by field,
    with the mix's tower and loss settings on top."""
    from distributed_sigmoid_loss_tpu.utils import config as program_config

    def section(cls, *layers):
        fields = {f.name for f in dataclasses.fields(cls)}
        merged = {}
        for layer in layers:
            unknown = set(layer) - fields
            if unknown:
                raise harness.Refused(f"{cls.__name__} has no field(s) {sorted(unknown)}")
            merged.update(layer)
        return cls(**merged)

    towers = traffic.get("towers", {})
    return program_config.SigLIPConfig(
        vision=section(program_config.ViTConfig, config["vision"], towers),
        text=section(program_config.TextConfig, config["text"], towers),
        loss=section(program_config.LossConfig, config.get("loss", {}), traffic.get("loss", {})),
    )


def build_step(cell: harness.Cell, devices):
    """Model, mesh, optimizer, batch shapes and the jitted step of a cell. Reads
    shapes only, so it also serves a compile for a described chip."""
    import jax
    import jax.numpy as jnp
    from distributed_sigmoid_loss_tpu.models.siglip import SigLIP
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

    mix = cell.traffic
    axes = mix["mesh"]
    if set(axes) != {"dp"} or axes["dp"] != cell.chips:
        raise harness.Refused(f"job train_step builds a dp mesh over the cell's chips, the mix asks for {axes}")
    accum = mix["step"]["accum_steps"]
    if mix["microbatch"] * accum != mix["pairs_per_chip_per_step"]:
        raise harness.Refused("microbatch x accum_steps must equal pairs_per_chip_per_step")
    cfg = build_config(cell.config, mix)
    mesh = make_mesh(cell.chips, devices=list(devices))
    global_pairs = mix["pairs_per_chip_per_step"] * cell.chips
    batch_shapes = {
        "images": jax.ShapeDtypeStruct(
            (global_pairs, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32
        ),
        "tokens": jax.ShapeDtypeStruct((global_pairs, cfg.text.context_length), jnp.int32),
    }
    model = SigLIP(cfg)
    step, batch_shardings = make_train_step(model, mesh, cfg.loss, **mix["step"])
    return types.SimpleNamespace(
        cfg=cfg, model=model, mesh=mesh, tx=make_optimizer(TrainConfig(**mix["optimizer"])),
        batch_shapes=batch_shapes, batch_shardings=batch_shardings, step=step,
        global_pairs=global_pairs, accum_steps=accum,
    )


def make_batch(built, key):
    """The synthetic batch, generated on the devices under the step's own batch
    sharding: each chip makes its rows."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        ki, kt = jax.random.split(key)
        return {
            "images": jax.random.normal(ki, built.batch_shapes["images"].shape, jnp.float32),
            "tokens": jax.random.randint(
                kt, built.batch_shapes["tokens"].shape, 0, built.cfg.text.vocab_size, jnp.int32
            ),
        }

    return jax.jit(gen, out_shardings=built.batch_shardings)(key)


def make_system_check(built, reference):
    """``f(params, sample) -> (loss, zimg, ztxt, grads)``: the model's forward as
    the step calls it (the cell's configuration, attention path, dtype and mesh)
    and the program's sharded loss, differentiated through."""
    import jax
    from distributed_sigmoid_loss_tpu.parallel.api import make_sharded_loss_fn
    from distributed_sigmoid_loss_tpu.parallel.mesh import trace_on

    lc = built.cfg.loss
    loss_fn = make_sharded_loss_fn(
        built.mesh, variant=lc.variant, family=lc.family, axis_name=lc.axis_name,
        bidir=lc.bidir, precision=getattr(jax.lax.Precision, lc.precision.upper()),
        use_pallas=lc.use_pallas,
        loss_impl=lc.loss_impl, ring_overlap=lc.ring_overlap, jit=False,
    )

    def system_loss(params, sample):
        with trace_on(built.mesh):
            zimg, ztxt, lp = built.model.apply({"params": params}, sample["images"], sample["tokens"])
        return loss_fn(lp, zimg, ztxt), (zimg, ztxt)

    @jax.jit
    def check(params, sample):
        (loss, (zimg, ztxt)), grads = jax.value_and_grad(system_loss, has_aux=True)(params, sample)
        return loss, zimg, ztxt, reference.first_blocks(grads)

    return check


def check_against_reference(built, mix, state, batch, reference) -> dict:
    """Checks (1) and (3) of ``correct``, and the reference's loss on the whole
    batch for (2). Runs before the first step, which donates the state."""
    import jax
    import numpy as np

    n = mix["check_rows"]
    sample = jax.jit(
        lambda b: jax.tree.map(lambda x: x[:n], b), out_shardings=built.batch_shardings
    )(batch)
    sys_loss, sys_zimg, sys_ztxt, sys_grads = jax.device_get(
        make_system_check(built, reference)(state.params, sample)
    )

    # The reference runs on one device, on plain unsharded arrays: a replicated
    # leaf is read where it lies (no copy beside a full chip), the batch is gathered.
    dev = built.mesh.devices.flat[0]

    def on_one_device(x):
        if x.sharding.is_fully_replicated:
            return next(s.data for s in x.addressable_shards if s.device == dev)
        return jax.device_put(x, dev)

    params, whole, sample = jax.tree.map(on_one_device, (state.params, batch, sample))
    groups = reference.microbatch_rows(built.global_pairs, built.mesh.size, built.accum_steps)
    ref_loss, ref_zimg, ref_ztxt = jax.device_get(
        reference.make_batch_loss(built.cfg, mix["reference_slice_rows"], groups)(
            params, whole["images"], whole["tokens"]
        )
    )
    ref_sample_loss, ref_grads = jax.device_get(
        reference.make_sample_grads(built.cfg)(params, sample["images"], sample["tokens"])
    )
    grad_errs = reference.tree_max_rel_err(sys_grads, ref_grads)
    return {
        "embed_err": max(
            reference.max_rel_err(sys_zimg, ref_zimg[:n]),
            reference.max_rel_err(sys_ztxt, ref_ztxt[:n]),
        ),
        "sample_loss_err": abs(float(sys_loss) - float(ref_sample_loss)) / abs(float(ref_sample_loss)),
        "grad_err": max(grad_errs.values()),
        "grad_err_worst_leaf": max(grad_errs, key=grad_errs.get),
        "grad_err_loss_scalars": grad_errs["['loss_scalars']"],
        "ref_grad_loss_scalars": [float(g) for g in ref_grads["loss_scalars"]],
        "ref_loss": float(ref_loss),
        "embed_spread": float(np.abs(ref_zimg - ref_zimg.mean(0)).max()),
    }


def run(ctx) -> dict:
    import jax
    import numpy as np
    from distributed_sigmoid_loss_tpu.train import create_train_state

    cell, mix, phases = ctx.cell, ctx.cell.traffic, ctx.phases
    reference, count = load_modules(cell.config)
    built = build_step(cell, ctx.devices)
    counter = harness.CompileCounter()
    phases.done("import")

    k_init, k_batch = jax.random.split(jax.random.key(ctx.seed))
    state = create_train_state(k_init, built.model, built.tx, built.batch_shapes, built.mesh)
    batch = make_batch(built, k_batch)
    jax.block_until_ready((state, batch))
    phases.done("init")

    lowered = built.step.lower(state, batch)
    trace_lower_s = phases.done("trace_lower")
    compiled = lowered.compile()
    phases.done("compile_or_load")
    memory = compiled.memory_analysis()

    checks = check_against_reference(built, mix, state, batch, reference)
    phases.done("reference")

    # Warm-up: three steps, each synced; the first is the correctness step.
    warm_losses, warm_times = [], []
    for _ in range(3):
        t = time.perf_counter()
        state, metrics = compiled(state, batch)
        warm_losses.append(float(metrics["loss"]))
        warm_times.append(time.perf_counter() - t)
    checks["loss_err"] = abs(warm_losses[0] - checks["ref_loss"]) / abs(checks["ref_loss"])
    step_s = min(warm_times[1:])
    k = mix["sync_every"]
    trace_steps = mix["trace_steps"]
    trace_from = k  # the profiler starts after the first synced interval
    tracer = harness.TraceWindow(cell.name, ctx.trace and not ctx.rehearse)
    n_steps = max(k, int(round(ctx.seconds / step_s / k)) * k)
    if tracer.enabled:
        n_steps = max(n_steps, trace_from + trace_steps)
    cache_before = built.step._cache_size()
    ctx.memory.sample()
    phases.done("warmup")
    setup_s = phases.total()

    # -- the window: nothing below compiles, the host syncs every k steps -------
    # No garbage collection inside it: a full collection of jax's objects can
    # stall the host for 0.1 s, which is 1 % of a 10 s window.
    gc.collect()
    gc.disable()
    counter.armed = True
    losses, marks = [], []
    i = 0
    t0 = time.perf_counter()
    with tracer.span("window"):
        while i < n_steps:
            tracing = tracer.enabled and i == trace_from
            if tracing:
                tracer.start()
            burst = trace_steps if tracing else min(k, n_steps - i)
            with tracer.span("dispatch"):
                for _ in range(burst):
                    state, metrics = compiled(state, batch)
                    losses.append(metrics["loss"])
            i += burst
            with tracer.span("sync"):
                float(metrics["loss"])
            marks.append((i, time.perf_counter() - t0))
            if tracing:
                tracer.stop()
    elapsed = marks[-1][1]
    counter.armed = False
    gc.enable()
    ctx.memory.sample()  # while the state and the loaded step are held
    recompiles = counter.in_window + built.step._cache_size() - cache_before

    losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int((~np.isfinite(losses)).sum())
    bounds = {
        "embed_err": reference.EMBED_BOUND, "grad_err": reference.GRAD_BOUND,
        "loss_err": reference.LOSS_BOUND, "sample_loss_err": reference.LOSS_BOUND,
    }
    verdicts = {name: bool(checks[name] <= bound) for name, bound in bounds.items()}
    nonfinite = failed + sum(not math.isfinite(x) for x in warm_losses)
    verdicts["losses_finite"] = nonfinite == 0
    verdicts["no_recompile"] = recompiles == 0
    harness.info_line("correct", verdicts=verdicts, bounds=bounds, **checks)
    # Each number compared beside its limit: the result line's last key, and the
    # last lines of standard error.
    # (a number that is not finite goes as its name: the line stays JSON)
    compared = {
        name: [float(checks[name]) if math.isfinite(checks[name]) else repr(float(checks[name])), bound]
        for name, bound in bounds.items()
    }
    compared.update(nonfinite_losses=[nonfinite, 0], recompiles=[recompiles, 0])

    steps_done = marks[-1][0]
    pairs_per_s_per_chip = built.global_pairs * steps_done / elapsed / cell.chips
    interval_s = [
        (t1 - t0_) / (i1 - i0) for (i0, t0_), (i1, t1) in zip([(0, 0.0)] + marks[:-1], marks)
    ]
    if not ctx.rehearse:  # a rehearsal prints no time, rate or size: the CPU's mean nothing
        harness.info_line(
            "window", steps=steps_done, elapsed_s=elapsed, warm_step_s=warm_times,
            step_s_per_interval=interval_s, step_s_median=statistics.median(interval_s),
            first_loss=warm_losses[0], last_loss=float(losses[-1]),
        )
        harness.info_line(
            "setup", setup_s=setup_s, **phases.seconds,
            compile_cache_requests=counter.requests, compile_cache_hits=counter.hits,
        )
        harness.info_line(
            "memory",
            allocator={d.id: d.memory_stats() for d in ctx.devices},
            step_program={f: getattr(memory, f, None) for f in harness.MEMORY_FIELDS},
        )

    end_to_end = {"setup_s": setup_s}
    if ctx.peaks is not None:
        end_to_end["pairs_per_s_per_chip"] = pairs_per_s_per_chip
        end_to_end["mfu_pct"] = (
            100.0 * count.train_flops_per_pair(built.cfg) * pairs_per_s_per_chip
            / (ctx.peaks["bf16_tflops"] * 1e12)
        )
    return {
        "correct": all(verdicts.values()),
        "attempted": steps_done,
        "failed": failed,
        "end_to_end": end_to_end,
        # What the per-layer readers see beside the reduced trace.
        "counters": {
            "recompiles": recompiles,
            "trace_lower_s": trace_lower_s,
            "memory_analysis": memory,
            "pairs_per_chip_per_step": mix["pairs_per_chip_per_step"],
            "cfg": built.cfg,
            "count": count,
        },
        "xplane": tracer.xplane,
        "compared": compared,
    }
