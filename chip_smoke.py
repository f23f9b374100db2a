#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the B/16 train path starts on the chip.

One process, no child that needs the chip. On a TPU whose ``device_kind`` the
peaks table lists it:

1. compiles every Pallas kernel the package ships at B/16's real shapes (the
   fused attention also at so400m's heads, 16 x 72) with ``interpret=False``
   and compares each with the repo's plain ``jax.numpy`` reference;
2. checks the ring and all-gather sharded losses (loss and grads) against the
   single-device ``sigmoid_loss`` on the same global batch;
3. builds the B/16 train step (``SigLIPConfig.b16()``: width 768, depth 12,
   bf16, package defaults) through ``make_train_step`` and checks what the CLI
   cannot show: the fused attention traced, no recompile after warm-up, one
   shard per device for batch, params and optimizer state;
4. trains the same model through the normal entry point,
   ``cli.main(["train", ...])``, and checks a finite, falling loss. The CLI's
   step is the program phase 3 compiled, so phase 3's first call is the cold
   compile and this one shows the persistent cache working in-process.

Anywhere else (no TPU, an unlisted device, a directory without the package) it
exits non-zero before any compile and prints no result. Compile and step
seconds are printed as set-up information under no metric name. On success the
last stdout line is ``{"ok": true, "device": {...}}`` with the device as jax
reports it.

    python chip_smoke.py
    python chip_smoke.py --time-attention   # only: the attention kernels alone,
                                            # us per program and per head
"""

from __future__ import annotations

import contextlib
import functools
import io
import json
import math
import sys
import time
import traceback

PER_CHIP_BATCH = 128
# The CLI's default 1e-3 makes B/16's first steps on random pairs jump about
# (10.2, 10.2, 8.9, 12.3, 11.8, 7.4 on the chip: a sixth step decides whether
# "last below first" holds). At 1e-4 over ten steps the curve still spikes once
# on four chips (13.8 at step 6) but ends far below the start: 5.9 on one chip,
# 7.4 on four, from 10.2. Data and init are seeded, so the curve repeats.
LEARNING_RATE = 1e-4
CLI_STEPS = 10
BUILDER_STEPS = 9  # after one warm-up step; same schedule length as the CLI
# The repo's envelope for bf16 matmul operands against the f32 loss
# (tests/test_bf16_numerics.py LOSS_RTOL / GRAD_RTOL: the t' gradient alone
# measures 3.1e-2 there) — what a kernel whose MXU passes are bf16 is held to.
BF16_LOSS_RTOL = 1e-4
BF16_GRAD_RTOL = 6e-2


def check(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def rel_err(got, want) -> float:
    """max|got - want| / max|want| in f32 — one number per comparison, robust
    to the near-zero entries an elementwise rtol trips on."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def parity(name: str, labels: str, got, want, fwd_tol, grad_tol) -> list[str]:
    """Record and print the leaf-by-leaf error of ``got`` against ``want``
    (both ``(forward, gradients...)`` trees) and return what is out of bounds.
    Returned, not raised: a phase measures all its kernels, then judges."""
    import jax

    errs = jax.tree.leaves(jax.tree.map(rel_err, got, want))
    REPORT["parity"][name] = [float(f"{e:.3g}") for e in errs]
    print(f"  parity {name}: {labels} rel err {REPORT['parity'][name]}")
    problems = []
    if errs[0] > fwd_tol:
        problems.append(f"{name}: forward off by {errs[0]:.3g} (> {fwd_tol})")
    if max(errs[1:]) > grad_tol:
        problems.append(
            f"{name}: gradients off by {max(errs[1:]):.3g} (> {grad_tol})"
        )
    return problems


def unit_rows(seed: int, n: int, d: int):
    """(n, d) f32 embeddings, L2-normalized as the towers hand them to the loss."""
    import jax.numpy as jnp
    import numpy as np

    from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import l2_normalize

    rows = np.random.default_rng(seed).standard_normal((n, d))
    return l2_normalize(jnp.asarray(rows, jnp.float32))


def compile_and_run(name: str, fn, *args):
    """AOT-compile ``fn`` (timed: the cold/warm compile seconds the report
    carries), run it once, return its outputs."""
    import jax

    lowered = jax.jit(fn).lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    print(f"  compile {name}: {dt:.2f}s")
    REPORT["compile_s"][name] = round(dt, 2)
    return out


REPORT: dict = {"compile_s": {}, "parity": {}}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


# (name, batch, seq, heads, head size, causal): what one microbatch of a benchmark
# cell hands each tower's attention (b16-bs256; so400m-mb32x4; ouro-b16-mb32x2,
# whose image tower is B/16's).
TOWER_SHAPES = [
    ("b16_vision", 256, 196, 12, 64, False),
    ("b16_text", 256, 64, 12, 64, False),
    ("so400m_vision", 32, 256, 16, 72, False),
    ("so400m_text", 32, 64, 16, 72, False),
    ("ouro_text", 32, 256, 16, 128, True),
]


def phase_attention_kernels() -> None:
    """short_self_attention at the tower shapes and flash_self_attention
    past the short kernel's envelope, fwd+bwd in bf16, against dense_attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sigmoid_loss_tpu.ops.flash_attention import (
        flash_self_attention,
    )
    from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
        short_attention_fits,
        short_self_attention,
    )
    from distributed_sigmoid_loss_tpu.parallel.ring_attention import (
        dense_attention,
    )

    def short(q, k, v, causal=False):
        return short_self_attention(q, k, v, causal, None, False)  # interpret=False

    # (name, kernel, batch, seq, heads, head size, causal): the tower shapes at
    # a small batch, and one sequence past the short kernel's envelope.
    cases = [
        (f"short_attn_{name}", short, 8, s, h, dh, causal)
        for name, _, s, h, dh, causal in TOWER_SHAPES
    ] + [("flash_attn_s2048", flash_self_attention, 2, 2048, 12, 64, False)]
    rng = np.random.default_rng(0)
    problems = []
    for name, kernel, b, s, h, dh, causal in cases:
        check(
            short_attention_fits(s, h * dh, 2) == (kernel is short),
            f"{name}: dispatch envelope disagrees with the smoke's case table",
        )
        q, k, v, w = (
            jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
            for _ in range(4)
        )

        def fwd_bwd(fn):
            def loss(q, k, v):
                return jnp.sum((fn(q, k, v) * w).astype(jnp.float32))

            return lambda q, k, v: (
                fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            )

        got = compile_and_run(name, fwd_bwd(functools.partial(kernel, causal=causal)), q, k, v)
        want = jax.jit(fwd_bwd(functools.partial(dense_attention, causal=causal)))(q, k, v)
        # bf16 operands, f32 softmax on both sides: the repo's on-chip bound
        # (tests/test_flash_attention.py) is 2e-2 forward, 5e-2 gradients.
        problems += parity(name, "out/dq/dk/dv", got, want, 2e-2, 5e-2)
    check(not problems, "; ".join(problems))


def kernel_device_us(trace_dir: str, kernels) -> dict:
    """Median device duration in us of each named Pallas kernel in the newest
    trace under ``trace_dir``: the custom call's events on the device planes'
    "XLA Ops" line, found by the kernel's ``name=`` as benchmark/scopes.py does."""
    import os
    import statistics

    from jax.profiler import ProfileData

    found = [os.path.join(base, f) for base, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    check(found, f"the profiler wrote no .xplane.pb under {trace_dir}")
    durations = {k: [] for k in kernels}
    for plane in ProfileData.from_file(max(found, key=os.path.getmtime)).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for event in line.events:
                # An event is named by its HLO instruction, on this libtpu by the
                # whole line "%name = ...": the name alone decides (a copy next
                # to the kernel carries the kernel's name in its metadata). Under
                # a transformation the name is wrapped: transpose_jvp_<kernel>__.
                instruction = event.name.split(" = ")[0]
                kernel = next((k for k in kernels if k in instruction), None)
                if kernel:
                    durations[kernel].append(event.duration_ns / 1e3)
    for kernel, found_us in durations.items():
        check(found_us, f"no event of {kernel} on a device plane's XLA Ops line")
    return {k: statistics.median(v) for k, v in durations.items()}


def phase_attention_timing() -> None:
    """``python chip_smoke.py --time-attention``: short_attn_fwd and
    short_attn_bwd alone at the tower shapes, bf16, device time from the
    profiler, as us per call, per grid program (one batch row) and per head.
    Where a kernel PR starts: the per-head cost that does not follow the
    sequence length is read off the text / vision pairs (PERF.md section 5)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
        short_self_attention,
    )

    rng = np.random.default_rng(0)
    REPORT["attention_us"] = {}
    print("  shape            kernel           us/call  us/program  us/head")
    for name, b, s, h, dh, causal in TOWER_SHAPES:
        q, k, v, g = (
            jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
            for _ in range(4)
        )
        attend = functools.partial(short_self_attention, causal=causal)
        fwd = jax.jit(attend)
        bwd = jax.jit(lambda q, k, v, g, attend=attend: jax.vjp(attend, q, k, v)[1](g))
        jax.block_until_ready((fwd(q, k, v), bwd(q, k, v, g)))
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(8):  # the median of eight calls of each kernel
                    out = (fwd(q, k, v), bwd(q, k, v, g))
                jax.block_until_ready(out)
            us = kernel_device_us(trace_dir, ("short_attn_fwd", "short_attn_bwd"))
        for kernel, t in us.items():
            row = {"us_per_call": t, "us_per_program": t / b,
                   "us_per_head": t / b / h}
            REPORT["attention_us"][f"{name}/{kernel}"] = row
            print(f"  {name:16s} {kernel:15s} {t:8.1f}  {t / b:10.3f}  "
                  f"{t / b / h:7.4f}")


def phase_loss_kernel() -> None:
    """streaming_block_loss_sum fwd+bwd at B/16's embedding width on the
    all-gather block of four chips (128 x 512), f32 and int8, against
    sigmoid_loss_block."""
    import jax
    import jax.numpy as jnp

    from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
        DEFAULT_TILE_B,
        DEFAULT_TILE_N,
        streaming_block_loss_sum,
    )
    from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import (
        init_loss_params,
        sigmoid_loss_block,
    )

    b, n, d = 128, 512, 512
    p = init_loss_params()
    args = (unit_rows(1, b, d), unit_rows(2, n, d), p["t_prime"], p["bias"])

    def value_and_grads(loss):
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))

    want = jax.jit(value_and_grads(
        lambda zi, zt, tp, bi: sigmoid_loss_block(zi, zt, tp, bi) * b
    ))(*args)

    # (loss tolerance, gradient tolerance) against the HIGHEST-precision XLA
    # reference. f32: Mosaic runs the kernel's f32 dots on bf16 MXU passes, the
    # grade of LossConfig(precision="default"), so the bound is the repo's
    # bf16-operand envelope (BF16_LOSS_RTOL / BF16_GRAD_RTOL). Measured on the
    # v5e: loss 1.8e-5, grads <= 5e-3. int8 quantizes the forward product only
    # (STE backward, full-precision dots): measured 2e-5 / 5.6e-3.
    problems = []
    for quant, loss_tol in (("", BF16_LOSS_RTOL), ("int8", 10 * BF16_LOSS_RTOL)):
        name = f"streaming_loss_{quant or 'f32'}"

        def kernel_loss(zi, zt, tp, bi):
            return streaming_block_loss_sum(
                zi, zt, tp, bi, jnp.float32(0.0), quant,
                min(DEFAULT_TILE_B, b), min(DEFAULT_TILE_N, n), False,
            )

        got = compile_and_run(name, value_and_grads(kernel_loss), *args)
        problems += parity(name, "loss/dzimg/dztxt/dt/dbias", got, want,
                           loss_tol, BF16_GRAD_RTOL)
    check(not problems, "; ".join(problems))


def phase_sharded_loss(mesh) -> None:
    """Ring and all-gather losses over every chip (loss and grads), and the
    ring with the streaming kernel as its block body (what ``train
    --use-pallas`` runs), against the single-device sigmoid_loss on the same
    global batch."""
    import jax

    from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
        reset_traced_loss_kernels,
        traced_loss_kernels,
    )
    from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import (
        init_loss_params,
        sigmoid_loss,
    )
    from distributed_sigmoid_loss_tpu.parallel import make_sharded_loss_fn

    n_dev = mesh.size
    b, d = PER_CHIP_BATCH * n_dev, 512
    zimg, ztxt = unit_rows(3, b, d), unit_rows(4, b, d)
    p = init_loss_params()

    def value_and_grads(loss_fn):
        return jax.value_and_grad(loss_fn, argnums=(0, 1, 2))

    want = jax.jit(value_and_grads(
        lambda pp, zi, zt: sigmoid_loss(zi, zt, pp["t_prime"], pp["bias"])
    ))(p, zimg, ztxt)
    reset_traced_loss_kernels()
    problems = []
    # XLA blocks at HIGHEST precision: f32 grade. The kernel's blocks run on
    # bf16 MXU passes (see phase_loss_kernel): gradients at bf16 grade
    # (measured over four chips: dt' 2.5e-2, embeddings 2.4e-3).
    for variant, use_pallas, grad_tol in (
        ("ring", False, 1e-4), ("all_gather", False, 1e-4),
        ("ring", True, BF16_GRAD_RTOL),
    ):
        name = (f"sharded_loss_{variant}{'_pallas' if use_pallas else ''}"
                f"_dp{n_dev}")
        got = compile_and_run(
            name,
            value_and_grads(make_sharded_loss_fn(
                mesh, variant=variant, use_pallas=use_pallas
            )),
            p, zimg, ztxt,
        )
        problems += parity(name, "loss/dbias/dt/dzimg/dztxt", got, want,
                           BF16_LOSS_RTOL, grad_tol)
    check(not problems, "; ".join(problems))
    check(traced_loss_kernels() == ("streaming",),
          f"use_pallas blocks traced {traced_loss_kernels()}, not the kernel")


def phase_cli_train(n_dev: int) -> None:
    """The normal entry point, in-process: full-width, full-depth B/16 on
    synthetic data through data.prefetch; the loss must be finite and fall."""
    from distributed_sigmoid_loss_tpu import cli

    argv = ["train", "--model", "b16", "--steps", str(CLI_STEPS),
            "--batch", str(PER_CHIP_BATCH * n_dev), "--lr", str(LEARNING_RATE),
            "--log-every", "1"]
    print(f"  python -m distributed_sigmoid_loss_tpu {' '.join(argv)}")
    captured = io.StringIO()
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(captured):
            rc = cli.main(argv)
    finally:
        sys.stdout.write(captured.getvalue())
    REPORT["cli_train_wall_s"] = round(time.perf_counter() - t0, 1)
    check(rc == 0, f"cli train exited {rc}")
    lines = [json.loads(ln) for ln in captured.getvalue().splitlines()
             if ln.startswith("{")]
    losses = [ln["loss"] for ln in lines if "loss" in ln]
    REPORT["cli_train_losses"] = [round(x, 4) for x in losses]
    check(len(losses) == CLI_STEPS, f"expected {CLI_STEPS} loss lines: {losses}")
    check(all(map(math.isfinite, losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check("mfu_est" in lines[-1], "metrics line carries no mfu_est on a listed chip")
    print(f"  cli train: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"in {REPORT['cli_train_wall_s']}s wall (compile included)")


def _pallas_names(jaxpr) -> list:
    """``name`` of every pallas_call in a jaxpr, nested jaxprs included."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_names(sub)
    return found


def phase_train_step(mesh) -> None:
    """The CLI's step through the builders directly, for what the CLI cannot
    show: fused attention traced, zero recompiles, per-device placement. Its
    first call is the run's cold compile of the B/16 step."""
    import jax

    from distributed_sigmoid_loss_tpu.data import SyntheticImageText, prefetch
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TrainConfig,
    )

    n_dev = mesh.size
    cfg = SigLIPConfig.b16()
    check((cfg.vision.width, cfg.vision.depth, cfg.text.width, cfg.text.depth)
          == (768, 12, 768, 12), "SigLIPConfig.b16() is not width 768 / depth 12")
    model = SigLIP(cfg)
    # The CLI's optimizer at the smoke's flags (cli.py cmd_train).
    tx = make_optimizer(TrainConfig(
        learning_rate=LEARNING_RATE, warmup_steps=5,
        total_steps=max(CLI_STEPS, 10),
    ))
    source = iter(SyntheticImageText(cfg, PER_CHIP_BATCH * n_dev))
    first = next(source)

    t0 = time.perf_counter()
    state = jax.block_until_ready(
        create_train_state(jax.random.key(0), model, tx, first, mesh)
    )
    REPORT["compile_s"]["create_train_state"] = round(time.perf_counter() - t0, 2)
    step, _ = make_train_step(
        model, mesh, LossConfig(variant="ring", precision="default")
    )

    def one_shard_per_device(tree, what, batch_dim_sharded):
        for leaf in jax.tree.leaves(tree):
            shards = leaf.addressable_shards
            check(
                sorted(s.device.id for s in shards)
                == sorted(d.id for d in mesh.devices.flat),
                f"{what}: shards on {[s.device.id for s in shards]}, "
                f"not one per mesh device",
            )
            want = list(leaf.shape)
            if batch_dim_sharded:
                want[0] //= n_dev
            check(all(list(s.data.shape) == want for s in shards),
                  f"{what}: shard shapes {[s.data.shape for s in shards]} "
                  f"!= {want}")

    batches = prefetch(source, mesh, size=2)
    losses = []
    try:
        batch = next(batches)
        one_shard_per_device(batch, "batch", True)
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))  # warm-up: trace + compile + run
        REPORT["compile_s"]["train_step_first_call"] = round(
            time.perf_counter() - t0, 2
        )
        # Stage the timed window's batches first: the host generator (19M
        # normals per 128 images) must not be what the step time measures.
        staged = jax.block_until_ready(
            [b for _, b in zip(range(BUILDER_STEPS), batches)]
        )
        t0 = time.perf_counter()
        for batch in staged:
            state, metrics = step(state, batch)
            losses.append(metrics["loss"])
        jax.block_until_ready(state)
        step_s = (time.perf_counter() - t0) / BUILDER_STEPS
    finally:
        batches.close()
    losses = [float(x) for x in losses]
    REPORT["train_step_losses"] = [round(x, 4) for x in losses]
    REPORT["steady_step_s"] = round(step_s, 4)
    print(f"  make_train_step: first call "
          f"{REPORT['compile_s']['train_step_first_call']}s, then "
          f"{step_s:.4f}s/step at global batch {PER_CHIP_BATCH * n_dev}; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")

    check(all(map(math.isfinite, losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    # The trace is cached by the calls above: this reads it, it does not retrace.
    kernels = set(_pallas_names(step.trace(state, staged[0]).jaxpr.jaxpr))
    check({"short_attn_fwd", "short_attn_bwd"} <= kernels,
          f"the step's jaxpr holds kernels {sorted(kernels)}, not the fused "
          "short attention: attn_impl='auto' fell to dense_attention on the chip")
    print(f"  fused short attention in the step's jaxpr: {sorted(kernels)}")
    check(step._cache_size() == 1,
          f"step recompiled after warm-up: cache size {step._cache_size()}")
    one_shard_per_device(state.params, "params", False)
    one_shard_per_device(state.opt_state, "opt_state", False)
    print(f"  one shard per device on {n_dev} device(s): batch, params, "
          "opt_state; no recompile after warm-up")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    time_attention = sys.argv[1:] == ["--time-attention"]
    if sys.argv[1:] and not time_attention:
        print("usage: python chip_smoke.py [--time-attention]", file=sys.stderr)
        return 2
    # ImportError here = a directory without the package: non-zero, no result.
    from distributed_sigmoid_loss_tpu.obs.attribution import CHIP_SPECS
    from distributed_sigmoid_loss_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string for the banner only
        libtpu = "not installed"
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"cache_dir={cache_dir!r}")
    if device["platform"] != "tpu" or device["kind"] not in CHIP_SPECS:
        print(f"chip_smoke: refusing to run: need a TPU whose device_kind is "
              f"in the peaks table {sorted(CHIP_SPECS)}", file=sys.stderr)
        return 1
    print(f"chip_smoke: jax.devices() = {devices}")

    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    phases = [("attention timing", phase_attention_timing)] if time_attention else [
        ("attention kernels", phase_attention_kernels),
        ("loss kernel", phase_loss_kernel),
        ("sharded loss", lambda: phase_sharded_loss(mesh)),
        ("train step", lambda: phase_train_step(mesh)),
        ("cli train", lambda: phase_cli_train(len(devices))),
    ]
    failed = []
    for name, phase in phases:
        print(f"== {name}")
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # noqa: BLE001 — recorded; the run then exits 1
            traceback.print_exc()
            failed.append(name)
        print(f"== {name}: {'FAILED' if name in failed else 'ok'} "
              f"({time.perf_counter() - t0:.1f}s)")
    REPORT["total_s"] = round(time.perf_counter() - t_start, 1)
    print("chip_smoke report: " + json.dumps(REPORT))
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
