#!/usr/bin/env python
"""Headline benchmark: SigLIP ViT-B/16 train-step throughput (image-text pairs/sec/chip).

Runs the full flagship train step — ViT-B/16 + text transformer + ring sigmoid loss +
adamw update — on the TPU chip(s) jax reports and prints ONE JSON line with
throughput, achieved TFLOP/s, and MFU. With no arguments it runs the headline recipe
(2048 pairs per optimizer step as 16 accumulated microbatches of 128, save_hot remat,
unrolled layers, bf16 accumulator + adam first moment).

One process: bench.py starts no subprocess, so nothing else ever holds the chip. It
measures on the chip only — where jax finds no TPU listed in the peaks table it exits
non-zero and prints no metric row (CPU smoke runs belong to
`python -m distributed_sigmoid_loss_tpu train --tiny --cpu-devices N`).

The reference publishes no benchmark numbers (BASELINE.md); the ``vs_baseline`` ratio is
measured throughput vs the A100 ballpark for open_clip-style ViT-B/16 contrastive
training (~1100 pairs/sec/GPU, bf16).

Usage: bench.py [batch [steps [model]]] [--use-pallas] [--accum N] [--variant V]
Positional args keep the historical invocation; config is echoed in the JSON so runs
across revisions are comparable.
"""

import argparse
import json
import sys
import time

A100_REF_PAIRS_PER_SEC = 1100.0  # open_clip ViT-B/16 A100 bf16 ballpark (no published ref)


# The no-args invocation: the measured single-chip sweet spot. 16 accumulated
# microbatches of 128 with save_hot remat beat every no-accum shape in the
# builder's sweep (docs/PERF.md) — the optimizer update amortizes over
# microsteps. Explicit invocations keep plain argparse defaults (288, no accum).
HEADLINE_ARGV = ["2048", "5", "b16", "--accum", "16", "--accum-bf16",
                 "--mu-bf16", "--remat-policy", "save_hot"]


def require_chip():
    """Configure the compile cache, then return ``jax.devices()`` — or exit
    non-zero, with no metric row, unless they are TPUs the peaks table lists.
    In-process: the check IS the backend initialisation the bench would do
    next, so no second process ever touches the chip."""
    from distributed_sigmoid_loss_tpu.obs.attribution import CHIP_SPECS
    from distributed_sigmoid_loss_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"bench: platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devices)} cache_dir={cache_dir!r}", file=sys.stderr)
    if d0.platform != "tpu" or d0.device_kind not in CHIP_SPECS:
        print("bench.py measures on the chip only: need a TPU whose "
              f"device_kind is in the peaks table {sorted(CHIP_SPECS)}; "
              "no metric row written", file=sys.stderr)
        raise SystemExit(1)
    return devices


def peak_bf16_tflops(device_kind: str) -> float:
    """Peak dense bf16 TFLOP/s from the one peaks table (obs/attribution.py
    CHIP_SPECS) — require_chip already refused unlisted devices."""
    from distributed_sigmoid_loss_tpu.obs.attribution import CHIP_SPECS

    return CHIP_SPECS[device_kind][0]


def peak_hbm_live_gb() -> float | None:
    """High-water mark the device allocator actually held, max over local
    devices (chip 0 alone hides an imbalance on a multi-chip host)."""
    import jax

    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    )
    return round(peak / 2**30, 2) if peak else None


def _emit(record: dict, flush: bool = False) -> None:
    """Print ONE JSON record line, validated against the declared schema
    (analysis/bench_schema.py) — every emit path goes through here so record
    fields cannot drift per path. A violation warns on stderr but still
    prints: a measurement must never be lost to its own validator (the
    repo-bench-record lint rule catches the drift statically in tier-1).

    Every record ALSO lands in the append-only run ledger (obs/ledger.py:
    record + environment fingerprint + explicit ok/no-backend/deferred
    status) — the longitudinal half the one-shot stdout contract never had.
    The graftlint rule ``repo-ledger-emit`` enforces statically that record
    prints happen only here, so no emit path can bypass the ledger.
    """
    try:
        # Function-level import: bench.py's TOP-LEVEL imports stay stdlib-only
        # (tests import it without initializing jax); by emit time the heavy
        # imports have long happened.
        from distributed_sigmoid_loss_tpu.analysis.bench_schema import (
            validate_record,
        )

        problems = validate_record(record)
    except Exception:
        problems = []
    if problems:
        print(
            "WARNING: bench record schema violation: " + "; ".join(problems),
            file=sys.stderr,
        )
    print(json.dumps(record), flush=flush)
    try:
        from distributed_sigmoid_loss_tpu.obs.ledger import append_record

        append_record(record, problems=problems)
    except Exception as e:  # noqa: BLE001 — the ledger never kills a record
        print(f"WARNING: ledger append failed ({type(e).__name__}: {e})",
              file=sys.stderr)


def _pallas_record_fields(args) -> dict:
    """Pallas-loss record fields from the kernel choice ACTUALLY resolved at
    trace time, cross-checked against argv.

    ``pallas_compatible`` falls back to the XLA block silently at trace time,
    so before round 10 a record could claim ``use_pallas: true`` while every
    block ran the XLA path. The streaming kernel records every dispatch
    resolution process-wide (ops/pallas_sigmoid_loss.traced_loss_kernels); the record
    carries that truth as ``pallas_engaged``, with ``pallas_mismatch`` set
    (and a stderr warning) whenever any block fell back — so the datapoint
    never silently enters a per-metric stream under the wrong tag.
    """
    if not args.use_pallas:
        return {}
    from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
        traced_loss_kernels,
    )

    traced = traced_loss_kernels()
    kinds = [t for t in traced if t != "xla"]
    fell_back = "xla" in traced or not traced
    if not kinds:
        engaged = "none"
    elif len(kinds) == 1 and not fell_back:
        engaged = kinds[0]
    else:
        engaged = "mixed"
    fields = {"pallas_engaged": engaged}
    if fell_back:
        print(
            f"WARNING: --use-pallas requested but the traced loss blocks "
            f"resolved to {traced or ('none',)!r} — at least one block ran "
            "the XLA fallback; tagging the record pallas_mismatch",
            file=sys.stderr,
        )
        fields["pallas_mismatch"] = True
    return fields


def transformer_forward_flops(s: int, width: int, depth: int, mlp_ratio: int) -> float:
    """Analytic forward FLOPs for one sequence through a standard pre-LN transformer:
    per layer 24·s·w² (qkv/out/mlp matmul MACs×2 at mlp_ratio 4) + 4·s²·w (attention
    scores + values). Elementwise/LN omitted (<1%)."""
    per_layer = (4 + 4 + 4 * mlp_ratio) * s * width * width + 4 * s * s * width
    return float(depth * per_layer)


def model_forward_flops_per_pair(cfg) -> float:
    """Forward FLOPs for ONE image-text pair through the SigLIP towers (loss matmul
    excluded — it depends on the negative-set size and is <1% at bench shapes)."""
    v, t = cfg.vision, cfg.text
    s_img = (v.image_size // v.patch_size) ** 2
    vit = transformer_forward_flops(s_img, v.width, v.depth, v.mlp_ratio)
    # Patch embedding: s · (p²·3·w) MACs ×2; MAP pool ≈ k/v projections over s tokens.
    vit += 2.0 * s_img * v.patch_size * v.patch_size * 3 * v.width
    if v.pool == "map":
        vit += 4.0 * s_img * v.width * v.width
    if v.use_proj:
        vit += 2.0 * v.width * v.embed_dim
    txt = transformer_forward_flops(t.context_length, t.width, t.depth, t.mlp_ratio)
    if t.pool == "map":
        txt += 4.0 * t.context_length * t.width * t.width
    txt += 2.0 * t.width * t.embed_dim
    # MoE: each token runs k expert MLPs of the dense hidden size, so the MLP
    # term scales by k (router/dispatch einsums are <1% at bench shapes).
    def moe_extra(tower, s):
        extra_k = tower.moe_num_selected - 1
        if not tower.moe_experts or extra_k <= 0:
            return 0.0
        return extra_k * 4.0 * tower.mlp_ratio * s * tower.width**2 * tower.depth

    return vit + txt + moe_extra(v, s_img) + moe_extra(t, t.context_length)


def _base_model_config(model_name: str):
    """Base SigLIPConfig for a bench model name — ONE dispatch shared by the
    train bench and the breakdown modes, so a record's "model" field always
    names the config that was actually measured."""
    from distributed_sigmoid_loss_tpu.utils.config import (
        SigLIPConfig,
        TextConfig,
        ViTConfig,
    )

    if model_name == "l14":
        # L/14 needs full remat at useful batch sizes (save_hot exceeds v5e HBM).
        return SigLIPConfig.l14()
    if model_name == "so400m":
        # ~878M params: adam state alone is ~10.5G of the 16G HBM; small batch,
        # full remat.
        return SigLIPConfig.so400m()
    if model_name == "tiny":
        return SigLIPConfig.tiny_test()  # harness smoke config (CPU-runnable)
    return SigLIPConfig(
        vision=ViTConfig(remat_policy="save_hot"),
        text=TextConfig(remat_policy="save_hot"),
    )


def _timeit_ms(fn, args_, steps: int) -> float:
    """Mean ms/call of ``jax.jit(fn)(*args_)``.

    ``fn`` must RETURN every array whose computation is being measured —
    returned outputs cannot be dead-code-eliminated, where returning a slice
    (e.g. ``state.step``) lets XLA drop the very work under test. Sync is a
    device->host transfer of a reduction over the first output leaf.
    """
    import jax
    import jax.numpy as jnp

    f = jax.jit(fn)

    def drain(out):
        leaf = jax.tree.leaves(out)[0]
        float(jnp.sum(leaf).astype(jnp.float32))

    out = f(*args_)
    drain(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = f(*args_)
    drain(out)
    return (time.perf_counter() - t0) / steps * 1000.0


def run_eval_throughput(args) -> int:
    """Forward-only embedding throughput (the retrieval/zero-shot serving
    metric): jit of ``model.apply`` producing both towers' embeddings, timed at
    ``batch`` pairs/call. ``--quant int8`` runs the block projection matmuls in
    dynamic int8 (ops/quant.py) — the v5e's 394-TOPS int8 MXU gear (2x bf16
    peak) — so the bf16-vs-int8 pair of runs prices PTQ serving on real
    hardware. One JSON line; MFU on the 1x-forward FLOPs basis.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_sigmoid_loss_tpu.models import SigLIP

    cfg = _base_model_config(args.model)
    # Inference: no backward, so remat buys nothing; unrolled stacks measured
    # fastest (docs/PERF.md).
    tower_kw = dict(remat=False, scan_layers=bool(args.scan_layers))
    if args.quant:
        tower_kw["quant"] = args.quant
    if args.attn_impl != "auto":
        tower_kw["attn_impl"] = args.attn_impl
    if args.moe:
        tower_kw["moe_experts"] = args.moe
        tower_kw["moe_num_selected"] = args.moe_k
        if args.moe_group_size:
            tower_kw["moe_group_size"] = args.moe_group_size
        if args.moe_cf is not None:
            tower_kw["moe_capacity_factor"] = args.moe_cf
    cfg = dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, **tower_kw),
        text=dataclasses.replace(cfg.text, **tower_kw),
    )
    if args.text_attn_impl:
        cfg = dataclasses.replace(
            cfg, text=dataclasses.replace(cfg.text, attn_impl=args.text_attn_impl)
        )
    model = SigLIP(cfg)
    key = jax.random.key(0)
    images = jax.random.normal(
        key, (args.batch, cfg.vision.image_size, cfg.vision.image_size, 3),
        jnp.float32,
    )
    tokens = jax.random.randint(
        key, (args.batch, cfg.text.context_length), 0, cfg.text.vocab_size,
        jnp.int32,
    )
    params = model.init(key, images[:2], tokens[:2])["params"]

    from distributed_sigmoid_loss_tpu.utils.profiling import time_step

    fwd = jax.jit(lambda p, im, tk: model.apply({"params": p}, im, tk)[:2])
    # time_step's 3 warmup calls matter here: the first dispatches of a fresh
    # executable were observed to run far slower than steady state (the int8
    # path: 733 pairs/s at --steps 10 vs 2996 at --steps 30 with a single
    # warmup — builder session, docs/PERF.md round-3 serving section).
    dt = time_step(fwd, params, images, tokens, warmup=3, iters=args.steps)

    pairs_per_sec = args.batch / dt
    device_kind = jax.devices()[0].device_kind
    fwd_flops = model_forward_flops_per_pair(cfg)
    tflops = fwd_flops * pairs_per_sec / 1e12
    peak = peak_bf16_tflops(device_kind)
    record = {
        "metric": f"siglip_vit{args.model}_eval_pairs_per_sec_per_chip",
        "value": round(pairs_per_sec, 2),
        "unit": "pairs/s/chip",
        # Serving has no A100 ballpark in BASELINE.md; the comparison that
        # matters is bf16-vs-int8 at the same shapes, so vs_baseline pins 1.0.
        "vs_baseline": 1.0,
        "model": args.model,
        "batch": args.batch,
        "steps": args.steps,
        "quant": args.quant or "bf16",
        "scan_layers": bool(args.scan_layers),
        "device_kind": device_kind,
        "fwd_tflops_per_sec_per_chip": round(tflops, 1),
    }
    if args.attn_impl != "auto":
        record["attn_impl"] = args.attn_impl
    if args.text_attn_impl:
        record["text_attn_impl"] = args.text_attn_impl
    if args.moe:
        record["moe_experts"] = args.moe
        record["moe_num_selected"] = args.moe_k
        if args.moe_group_size:
            record["moe_group_size"] = args.moe_group_size
        if args.moe_cf is not None:
            record["moe_capacity_factor"] = args.moe_cf
    record["mfu_bf16_basis"] = round(tflops / peak, 3)
    _emit(record)
    return 0


def run_context_bench(args) -> int:
    """Long-context attention bench: one ViT-B-width transformer block, fwd+bwd,
    at ``--context`` tokens — the regime the >1024 flash-kernel dispatch
    envelope (ops/flash_attention.py) was built for but round 2 never executed
    on hardware. Times each available impl and reports ms/layer + peak HBM:

    - dense: XLA einsum-softmax core (the s² baseline)
    - flash: blockwise Pallas kernel (TPU only; the long-seq path)
    - ring@1: the sequence-parallel ring-attention code path at W=1 (a 1-chip
      host can't scale sp, but its per-hop machinery still executes — this
      prices the sp overhead against dense at the same shapes)

    Emits ONE JSON line (same contract shape as the train bench; value = best
    impl's ms/layer, vs_baseline = dense_ms / best_ms, i.e. speedup over dense).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import flax.linen as nn

    from distributed_sigmoid_loss_tpu.models.transformer import Block
    from distributed_sigmoid_loss_tpu.ops.flash_attention import (
        flash_attention_available,
    )

    seq, width, heads = args.context, 768, 12
    b = max(1, min(args.batch, 4096 // max(seq // 512, 1)))  # keep b*s bounded
    on_tpu = jax.default_backend() == "tpu"

    def bench_impl(impl, sp_axis=None):
        if sp_axis is not None:
            from jax.sharding import Mesh

            # The sp shard_map needs the ambient mesh at EVERY trace,
            # including init — and under an ambient mesh flax applies the
            # kernels' (None, "tp") partitioning at param creation, so the
            # mesh must carry a (size-1) tp axis as well.
            grid = np.asarray(jax.devices()[:1]).reshape(1, 1)
            ctx = jax.set_mesh(Mesh(grid, (sp_axis, "tp")))
        else:
            import contextlib

            ctx = contextlib.nullcontext()
        block = Block(width, heads, 4, jnp.bfloat16, attn_impl=impl,
                      sp_axis=sp_axis)
        x = jax.random.normal(jax.random.key(0), (b, seq, width), jnp.bfloat16)

        def loss(p, xx):
            return jnp.sum(block.apply({"params": p}, xx).astype(jnp.float32) ** 2)

        step = jax.jit(jax.value_and_grad(loss))

        def strip(tree):
            # nn.meta.unbox under an ambient mesh applies an eager sharding
            # constraint whose tp axis this 1-device sp mesh doesn't have.
            return jax.tree.map(
                lambda v: v.value if isinstance(v, nn.meta.AxisMetadata) else v,
                tree, is_leaf=lambda v: isinstance(v, nn.meta.AxisMetadata),
            )

        with ctx:
            params = strip(block.init(jax.random.key(1), x)["params"])
            v, _ = step(params, x)
            float(v)  # device->host transfer: the step has finished
            n_steps = args.steps
            t0 = time.perf_counter()
            for _ in range(n_steps):
                v, _ = step(params, x)
            float(v)
            dt = time.perf_counter() - t0
        live = peak_hbm_live_gb()
        stats = {"peak_hbm_gb": live} if live is not None else {}
        return dt / n_steps * 1000.0, stats

    results = {}
    dense_ms, dense_stats = bench_impl("dense")
    results["dense"] = {"ms_per_layer": round(dense_ms, 3), **dense_stats}
    if on_tpu and flash_attention_available():
        flash_ms, flash_stats = bench_impl("flash")
        results["flash"] = {"ms_per_layer": round(flash_ms, 3), **flash_stats}
    ring_ms, ring_stats = bench_impl("dense", sp_axis="sp")
    results["ring_sp1"] = {"ms_per_layer": round(ring_ms, 3), **ring_stats}

    best = min(results.values(), key=lambda r: r["ms_per_layer"])
    record = {
        "metric": f"attn_block_ms_per_layer_s{seq}",
        "value": best["ms_per_layer"],
        "unit": "ms/layer",
        "vs_baseline": round(dense_ms / best["ms_per_layer"], 3),
        "context": seq,
        "batch": b,
        "width": width,
        "num_heads": heads,
        "steps": args.steps,
        "device_kind": jax.devices()[0].device_kind,
        "impls": results,
    }
    _emit(record)
    return 0


def run_step_breakdown(args) -> int:
    """Where does the train step's time go? Times independently-jitted pieces
    of the HEADLINE configuration (same model/batch/remat flags as the train
    bench) so PERF.md's attribution table comes from measurements, not guesses:

    - full_step: the complete jitted (state, batch) -> (state, metrics) step
    - towers_fwd: model.apply only (no grads, no loss comm)
    - grads: grad of the full loss (towers fwd+bwd+loss, no update)
    - optimizer: apply_gradients on precomputed grads
    - loss_island: the shard_map'd loss fwd+bwd on precomputed embeddings
    - attn_stack / mlp_stack: depth x Attention-only / Mlp-only towers at the
      vision shapes, fwd+bwd (the two compute families inside a block)

    Every timed program RETURNS its full outputs (see _timeit_ms: anything not
    returned is dead-code-eliminable, which would time a hollowed-out program).
    Sub-timings need not sum to full_step (XLA fuses differently per program,
    remat recompute lands in `grads`); the value is the RATIO structure. One
    JSON line; value = full_step ms, vs_baseline = 1.0 by construction.
    `--profile` is not consumed here — capture traces with a separate
    train-bench run.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    import flax.linen as nn

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.models.transformer import (
        Attention,
        Mlp,
        _remat_policy,
    )
    from distributed_sigmoid_loss_tpu.parallel.api import make_sharded_loss_fn
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig, TrainConfig

    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)
    cfg = _base_model_config(args.model)
    if args.loss_family != "sigmoid":
        cfg = dataclasses.replace(cfg, loss=LossConfig(family=args.loss_family))
    if args.model != "tiny" and not args.scan_layers:
        # Unrolled stacks: the measured-fastest headline config (docs/PERF.md).
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, scan_layers=False),
            text=dataclasses.replace(cfg.text, scan_layers=False),
        )
    model = SigLIP(cfg)
    tx = make_optimizer(TrainConfig(
        warmup_steps=100, total_steps=100_000,
        adam_mu_dtype="bfloat16" if args.mu_bf16 else None,
    ))
    global_b = args.batch * n_dev  # same convention as the train bench
    key = jax.random.key(0)
    batch = {
        "images": jax.random.normal(
            key, (global_b, cfg.vision.image_size, cfg.vision.image_size, 3),
            jnp.float32,
        ),
        "tokens": jax.random.randint(
            key, (global_b, cfg.text.context_length), 0, cfg.text.vocab_size,
            jnp.int32,
        ),
    }
    loss_cfg = LossConfig(
        variant=args.variant, family=args.loss_family,
        precision=args.precision, use_pallas=args.use_pallas,
        loss_impl=args.loss_impl, ring_overlap=args.ring_overlap,
    )
    state = create_train_state(key, model, tx, batch, mesh)
    step, shardings = make_train_step(model, mesh, loss_cfg)
    batch = jax.device_put(batch, shardings)
    n_steps = args.steps

    parts = {}
    parts["towers_fwd_ms"] = _timeit_ms(
        lambda p, bt: model.apply({"params": p}, bt["images"], bt["tokens"]),
        (state.params, batch), n_steps,
    )

    loss_fn = make_sharded_loss_fn(
        mesh, variant=args.variant, family=args.loss_family,
        precision=args.precision, use_pallas=args.use_pallas,
        loss_impl=args.loss_impl, ring_overlap=args.ring_overlap, jit=False,
    )

    def full_loss(p, bt):
        zimg, ztxt, lp = model.apply({"params": p}, bt["images"], bt["tokens"])
        return loss_fn({"t_prime": lp["t_prime"], "bias": lp["bias"]}, zimg, ztxt)

    grads = jax.jit(jax.grad(full_loss))(state.params, batch)
    # Full grads tree returned -> the whole tower backward is live.
    parts["grads_ms"] = _timeit_ms(
        lambda p, bt: jax.grad(full_loss)(p, bt), (state.params, batch), n_steps
    )

    # Full new state returned -> the adam/clip update is live.
    parts["optimizer_ms"] = _timeit_ms(
        lambda s_, g: s_.apply_gradients(grads=g), (state, grads), n_steps
    )

    zimg, ztxt, lp = jax.jit(model.apply)(
        {"params": state.params}, batch["images"], batch["tokens"]
    )
    parts["loss_island_ms"] = _timeit_ms(
        lambda zi, zt: jax.value_and_grad(
            lambda z: loss_fn(
                {"t_prime": lp["t_prime"], "bias": lp["bias"]}, z, zt
            )
        )(zi),
        (zimg, ztxt), n_steps,
    )

    # The two compute families inside a block, isolated: depth x Attention and
    # depth x Mlp at the vision shapes, fwd+bwd, same remat policy. Inputs are
    # dp-sharded like every other piece — unsharded arrays would run the whole
    # GLOBAL batch per device, inflating these numbers n_dev-fold.
    from jax.sharding import NamedSharding, PartitionSpec as P

    v = cfg.vision
    s_img = (v.image_size // v.patch_size) ** 2
    x_tokens = jax.device_put(
        jax.random.normal(key, (global_b, s_img, v.width), jnp.bfloat16),
        NamedSharding(mesh, P("dp")),
    )

    def stack_time(module):
        xp = nn.meta.unbox(module.init(jax.random.key(1), x_tokens)["params"])
        apply_one = lambda p, xx: module.apply({"params": p}, xx)
        if v.remat:
            apply_one = jax.checkpoint(
                apply_one, policy=_remat_policy(v.remat_policy),
                prevent_cse=False,
            )

        def loss(p, xx):
            for _ in range(v.depth):
                xx = apply_one(p, xx)
            return jnp.sum(xx.astype(jnp.float32) ** 2)

        return _timeit_ms(
            lambda p: jax.grad(loss)(p, x_tokens), (xp,), n_steps
        )

    parts["attn_stack_ms"] = stack_time(
        Attention(v.width, v.num_heads, jnp.bfloat16, attn_impl=v.attn_impl)
    )
    parts["mlp_stack_ms"] = stack_time(Mlp(v.width, v.mlp_ratio, jnp.bfloat16))

    # Full step LAST (it consumes `state`): timed through make_train_step's own
    # jit so donate_argnums=(0,) stays live — re-wrapping in jax.jit would drop
    # donation and time a step that pays an extra params+opt_state copy the
    # real train bench never does. State threads through like the train loop.
    st = state
    st, metrics = step(st, batch)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        st, metrics = step(st, batch)
    float(metrics["loss"])
    parts["full_step_ms"] = (time.perf_counter() - t0) / n_steps * 1000.0

    record = {
        "metric": "train_step_breakdown_ms",
        "value": round(parts["full_step_ms"], 2),
        "unit": "ms",
        "vs_baseline": 1.0,
        "parts": {k: round(vl, 2) for k, vl in parts.items()},
        "model": args.model,
        "per_chip_batch": args.batch,
        "global_batch": global_b,
        "n_devices": n_dev,
        "variant": args.variant,
        "loss_family": args.loss_family,
        "precision": args.precision,
        "use_pallas": args.use_pallas,
        "remat_policy": cfg.vision.remat_policy,
        "scan_layers": cfg.vision.scan_layers,
        "steps": n_steps,
        "device_kind": jax.devices()[0].device_kind,
    }
    if args.loss_impl != "fused":
        record["loss_impl"] = args.loss_impl
    if args.ring_overlap:
        record["ring_overlap"] = True
    if args.mu_bf16:
        record["adam_mu_dtype"] = "bfloat16"
    record.update(_pallas_record_fields(args))
    _emit(record)
    return 0


def run_moe_breakdown(args) -> int:
    """Attribute the MoE routing tax (builder sessions: MFU 0.30-0.36 vs 0.54 dense)
    across the layer's stages. Times the EXACT factored functions the layer
    executes (models/moe.py: router_topk / build_dispatch / expert_apply),
    fwd+bwd each, at the headline token count (batch x 196 ViT-B/16 patches),
    plus the dense Mlp baseline at the same shapes. One JSON line; value =
    full-MoE ms, vs_baseline = dense_ms / moe_ms.
    """
    import jax
    import jax.numpy as jnp

    from distributed_sigmoid_loss_tpu.models.moe import (
        build_dispatch,
        expert_apply,
        moe_capacity,
        router_topk,
    )

    d, hidden = 768, 3072
    e, k = (args.moe or 4), args.moe_k
    tokens = args.batch * 196  # ViT-B/16: (224/16)^2 patches per image
    group_target = args.moe_group_size or 512
    group = max(g for g in range(1, min(group_target, tokens) + 1)
                if tokens % g == 0)
    n_groups = tokens // group
    capacity = moe_capacity(group, e, k, 1.25)

    key = jax.random.key(0)
    kx, kr, ki, ko = jax.random.split(key, 4)
    xg = jax.random.normal(kx, (n_groups, group, d), jnp.bfloat16)
    wr = jax.random.normal(kr, (d, e), jnp.float32) * 0.02
    wi = jax.random.normal(ki, (e, d, hidden), jnp.float32) * 0.02
    wo = jax.random.normal(ko, (e, hidden, d), jnp.float32) * 0.02

    probs, gates, idx = jax.jit(lambda x, w: router_topk(x, w, k))(xg, wr)
    # dtype=bf16: the dtype MoeMlp passes for bf16 towers (round-4
    # model-dtype dispatch build) — the breakdown times the module's code.
    dispatch, combine = jax.jit(
        lambda g, i: build_dispatch(g, i, e, capacity, dtype=jnp.bfloat16)
    )(gates, idx)

    def timeit(fn, *a):
        return _timeit_ms(fn, a, args.steps)

    # Every operand is a jit ARGUMENT, never a closure: closed-over arrays are
    # embedded in the HLO as literal constants — ~83MB of activations at bench
    # token counts, which bloats the module and its compile.
    stages = {}
    # Each stage fwd+bwd (grad wrt its weights/inputs), matching training cost.
    stages["router_ms"] = timeit(
        jax.grad(lambda w, x: jnp.sum(router_topk(x, w, k)[1])), wr, xg
    )
    stages["dispatch_build_ms"] = timeit(
        jax.grad(lambda g, i: jnp.sum(
            build_dispatch(g, i, e, capacity, dtype=jnp.bfloat16)[1]
            .astype(jnp.float32)
        )),
        gates, idx,
    )
    stages["expert_einsums_ms"] = timeit(
        jax.grad(
            lambda ws, x, disp, comb: jnp.sum(
                expert_apply(x, disp, comb, ws[0], ws[1],
                             jnp.bfloat16).astype(jnp.float32) ** 2
            )
        ),
        (wi, wo), xg, dispatch, combine,
    )

    def full_moe(ws, x):
        w_r, w_i, w_o = ws
        _, g, i = router_topk(x, w_r, k)
        disp, comb = build_dispatch(g, i, e, capacity, dtype=jnp.bfloat16)
        y = expert_apply(x, disp, comb, w_i, w_o, jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    moe_ms = timeit(jax.grad(full_moe), (wr, wi, wo), xg)

    def dense_mlp(ws, x):
        w_i, w_o = ws
        h = jax.nn.gelu(
            jnp.einsum("ntd,dh->nth", x, w_i.astype(jnp.bfloat16)),
            approximate=True,
        )
        y = jnp.einsum("nth,hd->ntd", h, w_o.astype(jnp.bfloat16))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    dense_ms = timeit(
        jax.grad(dense_mlp), (wi[0], wo[0]), xg
    )

    record = {
        "metric": "moe_mlp_fwdbwd_ms",
        "value": round(moe_ms, 3),
        "unit": "ms",
        "vs_baseline": round(dense_ms / moe_ms, 3),
        "dense_mlp_ms": round(dense_ms, 3),
        "stages": {k_: round(v_, 3) for k_, v_ in stages.items()},
        "tokens": tokens,
        "experts": e,
        "num_selected": k,
        "group": group,
        "capacity": capacity,
        "steps": args.steps,
        "device_kind": jax.devices()[0].device_kind,
    }
    _emit(record)
    return 0


def run_data_bench_mode(args) -> int:
    """--data-bench: delegate to the package's stage-level input-pipeline
    runner (data/data_bench.py — the same code path as the CPU-runnable
    `python -m distributed_sigmoid_loss_tpu data-bench`), mapping the bench
    positionals onto its surface: batch → global batch, steps → timed
    batches, model → tower shape. Records are schema-validated by the runner
    itself; generated-shard defaults keep the run self-contained on the chip
    host."""
    from distributed_sigmoid_loss_tpu.data.data_bench import run_data_bench

    ns = argparse.Namespace(
        batch=args.batch, batches=args.steps, model=args.model,
        data_shards="", data_workers=args.data_workers, image_hw="240x320",
        shards=4, pil_decode=False, no_read_ahead=False, no_pipelined=False,
        no_zero_copy=False, seed=0,
    )
    return run_data_bench(ns)


def run_serve_bench_mode(args) -> int:
    """--serve-bench: delegate to the cli serve-bench runner (the same code
    path as the CPU-runnable `python -m distributed_sigmoid_loss_tpu
    serve-bench`), mapping the bench positionals onto its surface: batch x
    steps → total client requests, model → tower config. The runner emits
    the schema-validated serve_bench record itself and exits non-zero if any
    request escapes the warmed bucket grid (the zero-recompile gate, which
    --swap-every churn must also hold)."""
    from distributed_sigmoid_loss_tpu.cli import cmd_serve_bench

    ns = argparse.Namespace(
        requests=max(args.batch * args.steps, 1), clients=8,
        model=args.model, batch_buckets="1,8,32", max_wait_ms=5.0,
        max_queue=1024, cache_size=4096, pool=64,
        index_size=256, topk=10, seed=0, mesh=False, cpu_devices=0,
        index_tier=args.index_tier, swap_every=args.swap_every, rerank_k=0,
        metrics_port=-1, scenario=args.serve_scenario,
        tenants="gold:prio=2,quota=24,slo=500;free:prio=1,rate=80,quota=8",
        duration_s=4.0, offered_load=200.0, capacity=64,
    )
    if args.index_tier == "sharded":
        import jax

        # The sharded tier partitions the corpus over the dp mesh; on a
        # 1-chip host the mesh is a single shard, which measures nothing.
        n_dev = len(jax.devices())
        if n_dev > 1:
            ns.mesh = True
            # The sharded engine needs every bucket to divide the dp axis.
            ns.batch_buckets = f"{n_dev},{4 * n_dev}"
        else:
            print(
                "WARNING: --index-tier sharded on a 1-device host falls "
                "back to the exact tier (nothing to shard over)",
                file=sys.stderr,
            )
            ns.index_tier = "exact"
    return cmd_serve_bench(ns)


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    # 288/chip, save_hot remat, unrolled layers is the measured single-chip sweet
    # spot (760 pairs/s; sweep in docs/PERF.md): selective checkpointing cuts
    # backward recompute to ~25% of forward, and unrolling the block stack lets
    # XLA schedule across layer boundaries (+3% over lax.scan).
    ap.add_argument("batch", nargs="?", type=int, default=288,
                    help="per-chip pairs per optimizer step (before accumulation)")
    ap.add_argument("steps", nargs="?", type=int, default=10)
    ap.add_argument("model", nargs="?", default="b16",
                    choices=["b16", "l14", "so400m", "tiny"])
    ap.add_argument("--use-pallas", action="store_true",
                    help="fused Pallas loss kernel instead of the XLA-fused path")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microsteps (scan over microbatches); "
                         "batch is the TOTAL per-chip pairs per optimizer step")
    ap.add_argument("--accum-negatives", default="local",
                    choices=["local", "global"],
                    help="with --accum: 'global' prices the GradCache-style "
                         "exact-full-negatives accumulation (extra embed pass "
                         "per microbatch) vs plain 'local'")
    ap.add_argument("--variant", default="ring", choices=["ring", "all_gather"])
    ap.add_argument("--loss-impl", default="fused", choices=["fused", "chunked"],
                    help="with --variant all_gather: 'chunked' streams the "
                         "gathered negatives through a scan over W "
                         "chunk-blocks instead of one fused "
                         "(local_b, W*local_b) matmul — never materializes "
                         "the full logits (~W* lower peak loss HBM)")
    ap.add_argument("--ring-overlap", action="store_true",
                    help="with --variant ring: double-buffer the hop loop "
                         "(hop k+1's ppermute issued before hop k's block "
                         "matmuls) so XLA hides ICI latency behind the MXU; "
                         "bitwise-same accumulation order as the serial ring")
    ap.add_argument("--loss-family", default="sigmoid",
                    choices=["sigmoid", "softmax"],
                    help="sigmoid = SigLIP (headline); softmax = CLIP/InfoNCE "
                         "over the same comm variant")
    ap.add_argument("--steps-per-call", type=int, default=1, metavar="K",
                    help="fuse K optimizer steps into ONE compiled call "
                         "(lax.fori_loop over the train step) so the host "
                         "dispatches once per K steps — isolates host dispatch "
                         "overhead from device compute; steps must be a multiple "
                         "of K")
    ap.add_argument("--precision", default="default", choices=["default", "highest"])
    # Perf-experiment knobs (sweep results recorded in docs/PERF.md):
    ap.add_argument("--no-text-remat", action="store_true",
                    help="save ALL text-tower activations (measured: OOMs at the "
                         "bench config — the layer-scan stacks every saved tensor; "
                         "kept for sweeps at smaller batches)")
    ap.add_argument("--update-sharding", choices=["off", "zero1", "full"],
                    default="",
                    help="cross-replica update sharding (graftshard): 'zero1' "
                         "re-pins optimizer state over dp; 'full' "
                         "reduce-scatters grads into a 1/W shard, runs the "
                         "optimizer on the shard (~W x less optimizer HBM, "
                         "recorded as opt_mem_bytes_per_replica) and "
                         "all-gathers params once — with --grad-compression "
                         "the dcn wire carries the shard (~W x fewer bytes); "
                         "needs > 1 device")
    ap.add_argument("--zero1", action="store_true",
                    help="deprecated alias for --update-sharding zero1; "
                         "no-op on 1 chip")
    ap.add_argument("--mu-bf16", action="store_true",
                    help="bf16 Adam first moment (halves that buffer; the cheap "
                         "end of the optimizer-memory ladder before ZeRO-1)")
    ap.add_argument("--accum-bf16", action="store_true",
                    help="bf16 gradient accumulator under --accum (adds stay "
                         "f32; halves the accumulator's per-microstep HBM "
                         "read+write and its resident footprint)")
    ap.add_argument("--gradcache-bf16", action="store_true",
                    help="with --accum-negatives global: store the GradCache "
                         "embedding stash in bf16 (island matmuls read bf16 "
                         "operands, stash HBM halves) — the round-5 lever on "
                         "the exact-negatives path's 21%% tax")
    ap.add_argument("--metric-suffix", default="",
                    help="appended to the JSON metric name (e.g. _32k_equiv "
                         "for the 4096/chip recipe: 4096 5 b16 --accum 32 "
                         "--accum-bf16 --mu-bf16 --remat-policy save_hot)")
    ap.add_argument("--remat-policy", default="",
                    choices=["", "nothing", "save_hot", "save_all_hot",
                             "save_mlp"],
                    help="override both towers' remat policy (default: the "
                         "per-model measured best — save_hot for b16, full "
                         "remat for l14/so400m)")
    ap.add_argument("--moe", type=int, default=0, metavar="E",
                    help="mixture-of-experts towers with E experts per block "
                         "(replicated on 1 chip; shard over ep on a pod)")
    ap.add_argument("--moe-k", type=int, default=1, choices=[1, 2],
                    help="experts per token (with --moe)")
    ap.add_argument("--moe-group-size", type=int, default=0, metavar="G",
                    help="GShard routing group size (with --moe; default 512): "
                         "capacity is per-group, so smaller groups shrink the "
                         "dispatch tensors for tight HBM budgets")
    ap.add_argument("--moe-cf", type=float, default=None, metavar="F",
                    help="MoE capacity factor (with --moe; default 1.25): "
                         "per-expert buffer slack — smaller cuts the padded "
                         "expert FLOPs, at higher token-drop rates")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "dense", "flash"],
                    help="tower attention core: auto = fused Pallas kernel for "
                         "bf16 self-attention (VMEM-resident at tower seqs, "
                         "blockwise flash beyond), dense = plain XLA einsums")
    ap.add_argument("--text-attn-impl", default="",
                    choices=["", "auto", "dense", "flash"],
                    help="override the TEXT tower's attention impl only (A/B: "
                         "at s=64 the s2-HBM-traffic rationale for the fused "
                         "kernel is weakest — the trace shows its backward at "
                         "5.3 TFLOP/s; empty = follow --attn-impl)")
    ap.add_argument("--scan-layers", action="store_true",
                    help="lax.scan over tower depth instead of the unrolled "
                         "default (O(1) compile time in depth, ~1.3%% slower)")
    ap.add_argument("--profile", metavar="DIR", default="",
                    help="capture a jax.profiler trace of the timed steps into DIR "
                         "(view with TensorBoard or ui.perfetto.dev)")
    ap.add_argument("--step-breakdown", action="store_true",
                    help="train-step time attribution INSTEAD of the train "
                         "bench: time the full step, towers-forward, "
                         "grads-only, optimizer-only, the loss island, and "
                         "per-layer attention/MLP stacks at the same shapes — "
                         "the where-the-time-goes table for PERF.md")
    ap.add_argument("--moe-breakdown", action="store_true",
                    help="MoE routing-tax breakdown INSTEAD of the train "
                         "bench: time router / dispatch-build / expert-einsum "
                         "stages separately (the factored fns the layer runs, "
                         "models/moe.py) plus the dense-MLP baseline, at the "
                         "headline token count")
    ap.add_argument("--eval-throughput", action="store_true",
                    help="forward-only embedding throughput INSTEAD of the "
                         "train bench (the retrieval/zero-shot serving "
                         "metric); pair with --quant int8 for the PTQ run")
    ap.add_argument("--quant", default="", choices=["", "int8"],
                    help="with --eval-throughput: dynamic int8 projection "
                         "matmuls (v5e int8 MXU = 2x bf16 peak)")
    ap.add_argument("--quant-train", default="", choices=["", "int8"],
                    help="TRAIN bench with STE-quantized towers: int8 "
                         "projection matmuls forward (the 2x-bf16 MXU gear), "
                         "full-precision VJP backward — the int8 training "
                         "track's headline lever (docs/PERF.md roofline "
                         "rationale); recipes tag records via --metric-suffix")
    ap.add_argument("--grad-compression", default="",
                    choices=["", "int8", "topk", "adaptive", "learned"],
                    help="TRAIN bench with the compressed cross-slice grad "
                         "sync (train/compressed_step.py): hybrid (dcn, dp) "
                         "mesh of --dcn-slices x rest, f32 psum inside each "
                         "slice + this wire format over dcn ('learned' = "
                         "the adaptive ladder plus graftcodec's autoencoder "
                         "rung, trained during warmup); the record gains "
                         "the wire accounting (dcn_wire_bytes, "
                         "bits_per_param, ...) for the adaptive-vs-fixed "
                         "A/Bs")
    ap.add_argument("--dcn-slices", type=int, default=0, metavar="N",
                    help="with --grad-compression: size of the mesh's dcn "
                         "axis (>= 2; must divide the device count). On "
                         "single-slice hardware the axis is EMULATED over "
                         "ICI neighbors — wire-byte accounting stays exact, "
                         "sync timings are optimistic")
    ap.add_argument("--dcn-budget-mbps", type=float, default=None,
                    metavar="MBPS",
                    help="with --grad-compression adaptive: bandwidth cap "
                         "fed to the BitController; the scheme table is "
                         "decided during warmup and staged STATICALLY for "
                         "the timed loop, so the measurement has no "
                         "per-step host round-trip")
    ap.add_argument("--controller", default=None,
                    choices=["greedy", "budgeted"],
                    help="with --grad-compression adaptive/learned: bit-"
                         "controller policy (default greedy) — budgeted "
                         "allocates a global loss-impact budget via "
                         "error-per-byte knapsack descent over "
                         "ef_ratio/gvar/gnorm (docs/PERF.md graftcodec)")
    ap.add_argument("--emu-dcn-mbps", type=float, default=None,
                    metavar="MBPS",
                    help="with --grad-compression: honest DCN emulation "
                         "(parallel/dcn_emu.py) — each timed call's actual "
                         "dcn payload crosses a throttled two-process "
                         "localhost pipe at this bandwidth, the measured "
                         "transfer time lands in the wall clock, and the "
                         "record gains dcn_measured_mbps + "
                         "wire_savings_wallclock_ratio vs the fixed-bf16 "
                         "reference transfer")
    ap.add_argument("--topk-frac", type=float, default=0.01, metavar="F",
                    help="with --grad-compression topk/adaptive: kept "
                         "fraction of entries per tensor for the top-k wire "
                         "format (adaptive also uses F/4 as its narrowest "
                         "rung)")
    ap.add_argument("--data-bench", action="store_true",
                    help="input-pipeline stage bench INSTEAD of the train "
                         "bench: shard read / decode / tokenize / augment / "
                         "h2d commit in isolation + the composed real-data "
                         "pipeline vs the synthetic loader (generated JPEG "
                         "shards; batch/steps/model map to global batch, "
                         "timed batches, tower shape) — the host-side proof "
                         "the headline rate can be FED (docs/PERF.md "
                         "'Feeding the headline')")
    ap.add_argument("--data-workers", type=int, default=0, metavar="N",
                    help="with --data-bench: host decode/generation worker "
                         "threads (0 = auto: cpu_count minus the "
                         "prefetch/main threads; resolved value recorded)")
    ap.add_argument("--serve-bench", action="store_true",
                    help="online-serving bench INSTEAD of the train bench: "
                         "the cli serve-bench runner on the chip host "
                         "(requests = batch x steps, 8 client threads; "
                         "engine warmup compiles one program per shape "
                         "bucket) — tier A/Bs via --index-tier, hot-swap "
                         "churn via --swap-every (docs/SERVING.md)")
    ap.add_argument("--index-tier", default="exact",
                    choices=["exact", "sharded", "ann"],
                    help="with --serve-bench: retrieval tier answering the "
                         "search traffic (sharded needs a multi-chip mesh; "
                         "ann records measured recall@k)")
    ap.add_argument("--swap-every", type=int, default=0, metavar="N",
                    help="with --serve-bench: hot-swap weights + index "
                         "segments after every N client ops (0 = off); "
                         "swap latency percentiles land in the record")
    ap.add_argument("--serve-scenario", default="",
                    choices=["", "burst", "skew", "slowloris", "hostloss",
                             "swapstorm"],
                    help="with --serve-bench: run a graftsiege overload "
                         "scenario soak instead of the fixed-request loop "
                         "(multi-tenant admission, shaped offered load; the "
                         "degradation record lands in LEDGER.jsonl — "
                         "docs/SERVING.md 'Overload & SLO semantics')")
    ap.add_argument("--context", type=int, default=0, metavar="SEQ",
                    help="long-context attention bench INSTEAD of the train "
                         "bench: time one transformer block fwd+bwd at this "
                         "sequence length for each attention impl (dense, "
                         "flash kernel when seq qualifies, sp ring at W=1), "
                         "reporting ms/layer and peak HBM")
    args = ap.parse_args(argv)
    if args.moe == 1 or args.moe < 0:
        ap.error(f"--moe must be >= 2 experts (or 0 for dense), got {args.moe}")
    if args.moe_k != 1 and not args.moe:
        ap.error("--moe-k without --moe would be a silent no-op")
    if args.moe_cf is not None and not args.moe:
        ap.error("--moe-cf without --moe would be a silent no-op")
    if args.moe_cf is not None and args.moe_cf <= 0:
        ap.error(f"--moe-cf must be > 0, got {args.moe_cf}")
    if args.quant and not args.eval_throughput:
        ap.error("--quant without --eval-throughput would be a silent no-op "
                 "(the train bench never quantizes: training through round() "
                 "has zero gradients; --quant-train int8 is the trainable "
                 "STE path)")
    if args.quant and args.quant_train:
        ap.error("--quant (inference PTQ, --eval-throughput) and "
                 "--quant-train (STE train bench) are mutually exclusive")
    if args.quant_train and (args.context or args.moe_breakdown):
        ap.error("--quant-train applies to the train bench only (the "
                 "context/MoE breakdowns build their own block programs)")
    if args.loss_impl != "fused" and args.variant != "all_gather":
        # Refuse, don't auto-switch: bench's --variant default is an explicit
        # recorded field — silently flipping it would contaminate the
        # per-variant record streams.
        ap.error("--loss-impl chunked requires --variant all_gather (the "
                 "ring already streams negatives one chunk per hop)")
    if args.ring_overlap and args.variant != "ring":
        ap.error("--ring-overlap requires --variant ring (the all-gather "
                 "loss has no hop loop to overlap)")
    if args.loss_family != "sigmoid" and (
        args.loss_impl != "fused" or args.ring_overlap
    ):
        ap.error("--loss-impl chunked / --ring-overlap apply to the sigmoid "
                 "family only (the softmax ring already streams its "
                 "logsumexp)")
    modes = {
        "--eval-throughput": args.eval_throughput,
        "--context": bool(args.context),
        "--moe-breakdown": args.moe_breakdown,
        "--step-breakdown": args.step_breakdown,
        "--data-bench": args.data_bench,
        "--serve-bench": args.serve_bench,
    }
    picked_modes = [k for k, v in modes.items() if v]
    if len(picked_modes) > 1:
        ap.error(f"{' '.join(picked_modes)} are mutually exclusive bench modes")
    if args.eval_throughput:
        # Same anti-silent-no-op rule as --step-breakdown: flags the forward
        # bench cannot honor are refused, not dropped (a record measuring a
        # different program than the flags claim poisons comparisons). The
        # honored set: model/batch/steps, --quant, --attn-impl,
        # --text-attn-impl, --scan-layers, --moe/--moe-k/--moe-group-size.
        unsupported = {
            "--accum": args.accum != 1, "--zero1": args.zero1,
            "--update-sharding": bool(args.update_sharding),
            "--mu-bf16": args.mu_bf16, "--accum-bf16": args.accum_bf16,
            "--remat-policy": bool(args.remat_policy),
            "--metric-suffix": bool(args.metric_suffix),
            "--no-text-remat": args.no_text_remat,
            "--steps-per-call": args.steps_per_call != 1,
            "--use-pallas": args.use_pallas,
            "--variant": args.variant != "ring",
            "--loss-family": args.loss_family != "sigmoid",
            "--precision": args.precision != "default",
            "--accum-negatives": args.accum_negatives != "local",
            "--gradcache-bf16": args.gradcache_bf16,
            "--quant-train": bool(args.quant_train),
            "--loss-impl": args.loss_impl != "fused",
            "--ring-overlap": args.ring_overlap,
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            ap.error(f"--eval-throughput does not support {' '.join(bad)} "
                     "(forward-only: no loss, no optimizer; PTQ serving is "
                     "--quant int8)")
    if args.data_bench:
        # The host-pipeline bench never builds the train step: every flag
        # that would change that program is refused, not dropped (same
        # honest-records rule as --eval-throughput/--step-breakdown). The
        # honored set: batch/steps/model positionals + --data-workers.
        unsupported = {
            "--accum": args.accum != 1, "--zero1": args.zero1,
            "--update-sharding": bool(args.update_sharding),
            "--mu-bf16": args.mu_bf16, "--accum-bf16": args.accum_bf16,
            "--remat-policy": bool(args.remat_policy),
            "--metric-suffix": bool(args.metric_suffix),
            "--no-text-remat": args.no_text_remat,
            "--steps-per-call": args.steps_per_call != 1,
            "--use-pallas": args.use_pallas,
            "--variant": args.variant != "ring",
            "--loss-family": args.loss_family != "sigmoid",
            "--precision": args.precision != "default",
            "--accum-negatives": args.accum_negatives != "local",
            "--gradcache-bf16": args.gradcache_bf16,
            "--attn-impl": args.attn_impl != "auto",
            "--text-attn-impl": bool(args.text_attn_impl),
            "--scan-layers": args.scan_layers,
            "--moe": bool(args.moe),
            "--quant": bool(args.quant),
            "--quant-train": bool(args.quant_train),
            "--loss-impl": args.loss_impl != "fused",
            "--ring-overlap": args.ring_overlap,
            "--profile": bool(args.profile),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            ap.error(f"--data-bench does not support {' '.join(bad)} "
                     "(it measures the input pipeline, not the train step)")
    elif args.data_workers:
        ap.error("--data-workers applies to --data-bench only (the train "
                 "bench generates batches on-device; the CLI train "
                 "subcommand has its own --data-workers)")
    if args.serve_bench:
        # The serving bench never builds the train step: refuse, don't drop,
        # every flag that would claim to change it (the honest-records rule
        # of every other mode). Honored: batch/steps/model positionals +
        # --index-tier / --swap-every.
        unsupported = {
            "--accum": args.accum != 1, "--zero1": args.zero1,
            "--update-sharding": bool(args.update_sharding),
            "--mu-bf16": args.mu_bf16, "--accum-bf16": args.accum_bf16,
            "--remat-policy": bool(args.remat_policy),
            "--metric-suffix": bool(args.metric_suffix),
            "--no-text-remat": args.no_text_remat,
            "--steps-per-call": args.steps_per_call != 1,
            "--use-pallas": args.use_pallas,
            "--variant": args.variant != "ring",
            "--loss-family": args.loss_family != "sigmoid",
            "--precision": args.precision != "default",
            "--accum-negatives": args.accum_negatives != "local",
            "--gradcache-bf16": args.gradcache_bf16,
            "--attn-impl": args.attn_impl != "auto",
            "--text-attn-impl": bool(args.text_attn_impl),
            "--scan-layers": args.scan_layers,
            "--moe": bool(args.moe),
            "--quant": bool(args.quant),
            "--quant-train": bool(args.quant_train),
            "--loss-impl": args.loss_impl != "fused",
            "--ring-overlap": args.ring_overlap,
            "--profile": bool(args.profile),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            ap.error(f"--serve-bench does not support {' '.join(bad)} "
                     "(it measures the online serving stack, not the train "
                     "step)")
    else:
        if args.index_tier != "exact":
            ap.error("--index-tier without --serve-bench would be a silent "
                     "no-op")
        if args.swap_every:
            ap.error("--swap-every without --serve-bench would be a silent "
                     "no-op")
        if args.serve_scenario:
            ap.error("--serve-scenario without --serve-bench would be a "
                     "silent no-op")
    if args.grad_compression:
        if picked_modes:
            ap.error(f"--grad-compression applies to the train bench only "
                     f"(got {' '.join(picked_modes)}); the other modes never "
                     "build the compressed step")
        if args.dcn_slices < 2:
            ap.error("--grad-compression requires --dcn-slices >= 2 "
                     "(the dcn axis being compressed)")
        if args.variant != "all_gather":
            # Refuse, don't auto-switch — the --loss-impl rule above: variant
            # is a recorded field and the ring ppermute has no joint-(dcn,
            # dp) axis form (train/compressed_step.py's own refusal).
            ap.error("--grad-compression requires --variant all_gather "
                     "(the ring ppermute has no joint-(dcn, dp) axis form)")
        if not (0.0 < args.topk_frac <= 1.0):
            ap.error(f"--topk-frac must be in (0, 1], got {args.topk_frac}")
        if (args.dcn_budget_mbps is not None
                and args.grad_compression not in ("adaptive", "learned")):
            ap.error("--dcn-budget-mbps applies to --grad-compression "
                     "adaptive/learned only (fixed schemes have no "
                     "controller)")
        if args.dcn_budget_mbps is not None and args.dcn_budget_mbps <= 0:
            ap.error(f"--dcn-budget-mbps must be > 0, "
                     f"got {args.dcn_budget_mbps}")
        if (args.controller
                and args.grad_compression not in ("adaptive", "learned")):
            ap.error("--controller applies to --grad-compression "
                     "adaptive/learned only (fixed schemes have no per-round "
                     "policy to select)")
        if args.emu_dcn_mbps is not None and args.emu_dcn_mbps <= 0:
            ap.error(f"--emu-dcn-mbps must be > 0, got {args.emu_dcn_mbps}")
    else:
        # Same anti-silent-no-op rule as the cli train subcommand: a knob
        # that cannot reach the measured program is refused, not dropped.
        if args.dcn_slices:
            ap.error("--dcn-slices without --grad-compression would be a "
                     "silent no-op (the plain bench mesh has no dcn axis)")
        if args.dcn_budget_mbps is not None:
            ap.error("--dcn-budget-mbps without --grad-compression adaptive "
                     "would be a silent no-op")
        if args.controller:
            ap.error("--controller without --grad-compression "
                     "adaptive/learned would be a silent no-op")
        if args.emu_dcn_mbps is not None:
            ap.error("--emu-dcn-mbps without --grad-compression would be a "
                     "silent no-op (there is no dcn mesh axis whose payload "
                     "the pipe could carry)")
        if args.topk_frac != 0.01:
            ap.error("--topk-frac without --grad-compression would be a "
                     "silent no-op")
    if args.steps_per_call < 1 or args.steps % args.steps_per_call:
        ap.error(f"steps={args.steps} must be a positive multiple of "
                 f"--steps-per-call={args.steps_per_call}")
    if args.accum_bf16 and args.accum == 1:
        ap.error("--accum-bf16 requires --accum > 1 "
                 "(the unaccumulated step has no accumulator)")
    if args.gradcache_bf16 and (
        args.accum == 1 or args.accum_negatives != "global"
    ):
        ap.error("--gradcache-bf16 requires --accum > 1 with "
                 "--accum-negatives global (only the GradCache path "
                 "stashes embedding tables)")
    if args.zero1 and args.update_sharding not in ("", "zero1"):
        ap.error(f"--zero1 is the deprecated alias for --update-sharding "
                 f"zero1 and contradicts --update-sharding "
                 f"{args.update_sharding}; drop one of them")
    if args.step_breakdown:
        # Flags the breakdown mode cannot honor are refused up front; a
        # silently different program would poison the attribution table.
        # The flags that change the compiled step
        # (family/precision/pallas/scan/mu-bf16) are threaded through instead.
        unsupported = {
            "--accum": args.accum != 1, "--zero1": args.zero1,
            "--update-sharding": bool(args.update_sharding),
            "--accum-bf16": args.accum_bf16,
            "--remat-policy": bool(args.remat_policy),
            "--metric-suffix": bool(args.metric_suffix),
            "--moe": bool(args.moe), "--no-text-remat": args.no_text_remat,
            "--steps-per-call": args.steps_per_call != 1,
            "--accum-negatives": args.accum_negatives != "local",
            "--gradcache-bf16": args.gradcache_bf16,
            "--quant-train": bool(args.quant_train),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            ap.error(f"--step-breakdown does not support {' '.join(bad)}; "
                     "run the train bench for those configurations")

    devices = require_chip()

    if args.data_bench:
        return run_data_bench_mode(args)
    if args.serve_bench:
        return run_serve_bench_mode(args)
    if args.eval_throughput:
        return run_eval_throughput(args)
    if args.context:
        return run_context_bench(args)
    if args.moe_breakdown:
        return run_moe_breakdown(args)
    if args.step_breakdown:
        return run_step_breakdown(args)

    import jax
    import jax.numpy as jnp

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
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

    n_dev = len(devices)
    if args.grad_compression:
        # Hybrid (dcn, dp) mesh, dcn outermost and grouped by real slice on
        # multi-slice hardware (the cli train path's arrangement, via the
        # same helper); on one slice the axis maps onto ICI neighbors — wire
        # accounting exact, sync timing optimistic (the --dcn-slices help
        # text caveat).
        import numpy as np
        from jax.sharding import Mesh

        from distributed_sigmoid_loss_tpu.parallel.multihost import (
            _hybrid_device_array,
        )

        if n_dev % args.dcn_slices:
            print(f"--dcn-slices {args.dcn_slices} must divide the device "
                  f"count {n_dev}", file=sys.stderr)
            return 2
        slice_ids = {getattr(d, "slice_index", 0) for d in devices}
        if len(slice_ids) > 1:
            if len(slice_ids) != args.dcn_slices:
                print(f"--dcn-slices {args.dcn_slices} != actual slice "
                      f"count {len(slice_ids)} — the dcn axis must follow "
                      "real slice boundaries", file=sys.stderr)
                return 2
            arr = _hybrid_device_array(
                args.dcn_slices, n_dev // args.dcn_slices, 1, devices
            )
        else:
            # A single slice carries no slice metadata: plain
            # enumeration-order reshape (the cli train path's fallback). The
            # bench skips the cli's --force-dcn-emulation gate — emulated
            # A/Bs of wire formats are what this mode is for.
            arr = np.array(devices)
        mesh = Mesh(
            arr.reshape(args.dcn_slices, n_dev // args.dcn_slices),
            ("dcn", "dp"),
        )
    else:
        mesh = make_mesh(n_dev)

    update_mode = args.update_sharding or ("zero1" if args.zero1 else "off")
    if update_mode == "full" and dict(mesh.shape).get("dp", 1) < 2:
        # Environment refusal (same as the builders'): nothing to
        # reduce-scatter over on a 1-wide dp axis.
        print("--update-sharding full requires a dp axis of size > 1, got "
              f"mesh {dict(mesh.shape)}", file=sys.stderr)
        return 2

    cfg = _base_model_config(args.model)
    import dataclasses

    if args.moe:
        moe_kw = {"moe_experts": args.moe, "moe_num_selected": args.moe_k}
        if args.moe_group_size:
            moe_kw["moe_group_size"] = args.moe_group_size
        if args.moe_cf is not None:
            moe_kw["moe_capacity_factor"] = args.moe_cf
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, **moe_kw),
            text=dataclasses.replace(cfg.text, **moe_kw),
        )
    if args.loss_family != "sigmoid":
        from distributed_sigmoid_loss_tpu.utils.config import LossConfig as _LC

        # The model's t_prime init is family-dependent (CLIP: log(1/0.07)) —
        # keep bench loss trajectories identical to `train --loss-family`.
        cfg = dataclasses.replace(cfg, loss=_LC(family=args.loss_family))
    if args.no_text_remat:
        cfg = dataclasses.replace(cfg, text=dataclasses.replace(cfg.text, remat=False))
    if args.attn_impl != "auto":
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, attn_impl=args.attn_impl),
            text=dataclasses.replace(cfg.text, attn_impl=args.attn_impl),
        )
    if args.text_attn_impl:
        cfg = dataclasses.replace(
            cfg, text=dataclasses.replace(cfg.text, attn_impl=args.text_attn_impl)
        )
    if not args.scan_layers:
        # Unrolled block stacks are the measured-fastest config (docs/PERF.md);
        # the package default stays scan_layers=True (constant compile time for
        # dev/test loops) — the bench optimizes for steady-state throughput.
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, scan_layers=False),
            text=dataclasses.replace(cfg.text, scan_layers=False),
        )
    if args.remat_policy:
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, remat_policy=args.remat_policy),
            text=dataclasses.replace(cfg.text, remat_policy=args.remat_policy),
        )
    if args.quant_train:
        # STE-quantized towers: int8 forward on the MXU, full-precision VJP
        # (make_train_step accepts quant_train; inference `quant` it rejects).
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, quant_train=args.quant_train),
            text=dataclasses.replace(cfg.text, quant_train=args.quant_train),
        )
    model = SigLIP(cfg)
    tx = make_optimizer(
        TrainConfig(
            warmup_steps=100,
            total_steps=100_000,
            adam_mu_dtype="bfloat16" if args.mu_bf16 else None,
        )
    )

    global_b = args.batch * n_dev

    batch_shapes = {
        "images": jax.ShapeDtypeStruct(
            (global_b, cfg.vision.image_size, cfg.vision.image_size, 3),
            jnp.float32,
        ),
        "tokens": jax.ShapeDtypeStruct(
            (global_b, cfg.text.context_length), jnp.int32
        ),
    }
    state = create_train_state(
        jax.random.key(0), model, tx, batch_shapes, mesh,
        update_sharding=update_mode,
    )
    loss_cfg = LossConfig(
        variant=args.variant, family=args.loss_family,
        precision=args.precision, use_pallas=args.use_pallas,
        loss_impl=args.loss_impl, ring_overlap=args.ring_overlap,
    )
    if args.grad_compression:
        from distributed_sigmoid_loss_tpu.train import (
            make_compressed_train_step,
            with_adaptive_compression,
            with_error_feedback,
        )

        # EF (and the adaptive carry) ride the live state only — the
        # checkpointless bench never sees the strip/restore cycle.
        if args.grad_compression in ("adaptive", "learned"):
            state = with_adaptive_compression(
                state, mesh, update_sharding=update_mode,
                learned=args.grad_compression == "learned",
            )
        else:
            state = with_error_feedback(
                state, mesh, update_sharding=update_mode
            )
        step, shardings = make_compressed_train_step(
            model, mesh, loss_cfg,
            compression=args.grad_compression,
            topk_frac=args.topk_frac,
            accum_steps=args.accum, update_sharding=update_mode,
            moe_aux_weight=0.01 if args.moe else None,
            accum_negatives=args.accum_negatives,
            accum_dtype="bfloat16" if args.accum_bf16 else None,
            gradcache_embed_dtype="bfloat16" if args.gradcache_bf16 else None,
        )
    else:
        step, shardings = make_train_step(
            model, mesh, loss_cfg, accum_steps=args.accum,
            update_sharding=update_mode,
            moe_aux_weight=0.01 if args.moe else None,
            accum_negatives=args.accum_negatives,
            accum_dtype="bfloat16" if args.accum_bf16 else None,
            gradcache_embed_dtype="bfloat16" if args.gradcache_bf16 else None,
        )

    # Generate the batch ON the devices (the metric is step compute, not host
    # IO), born with the step's own batch sharding: each chip makes its rows.
    # Unsharded, the whole global batch lands on device 0 first — 9.9 GB at
    # the 32k-equivalent shape on four chips, on a 16 GB chip.
    def make_batch(key):
        ki, kt = jax.random.split(key)
        return {
            "images": jax.random.normal(
                ki, batch_shapes["images"].shape, jnp.float32
            ),
            "tokens": jax.random.randint(
                kt, batch_shapes["tokens"].shape, 0, cfg.text.vocab_size,
                jnp.int32,
            ),
        }

    batch = jax.jit(make_batch, out_shardings=shardings)(jax.random.key(0))

    spc = args.steps_per_call
    if spc > 1:
        # One compiled call = K full optimizer steps. The jitted inner step
        # inlines into the fori_loop trace; state keeps its shardings through the
        # loop carry, and the whole K-step chain is a single device program —
        # the host dispatches once per K steps.
        inner = step

        def step_fused(state, batch):
            st = jax.lax.fori_loop(
                0, spc - 1, lambda _, s: inner(s, batch)[0], state
            )
            return inner(st, batch)

        step = jax.jit(step_fused, donate_argnums=(0,))

    # AOT-compile once and reuse the executable for warmup + the timed loop (a
    # second trace-and-compile via the jit cache would double the multi-minute
    # XLA compile). cost_analysis() reports the FLOPs of the
    # post-SPMD-partitioning PER-DEVICE module (includes remat recompute); it may
    # be unavailable on some PJRT backends.
    compiled = step.lower(state, batch).compile()
    # Peak device memory of the compiled step (XLA's own accounting):
    # arguments+outputs+temps+generated code — via the shared introspection
    # helper (utils/profiling.py), the same figures the CPU peak-HBM
    # regression test asserts on. The number that tells you how far the
    # config sits from the HBM wall before you hit it mid-run.
    from distributed_sigmoid_loss_tpu.utils.profiling import (
        memory_stats_of_compiled,
    )

    mem_stats = memory_stats_of_compiled(compiled)
    # GiB, matching the --context bench's peak_hbm_gb.
    peak_hbm_gb = (
        round(mem_stats["peak_bytes"] / 2**30, 2) if mem_stats else None
    )
    hw_flops_per_step_per_dev = None
    if spc == 1:
        # Only meaningful unfused: HloCostAnalysis counts a while-loop body
        # ONCE regardless of trip count, so the fused program's "flops" is
        # neither K steps' worth nor 1 — skip rather than publish a bogus
        # hw_util.
        try:
            cost = compiled.cost_analysis()
            if cost and cost.get("flops", 0) > 0:
                hw_flops_per_step_per_dev = float(cost["flops"])
        except Exception:
            pass

    # Warmup (compile + first steps). Sync via device->host transfer: float()
    # of a step output cannot return before the step has run.
    controller = None
    codec_trainer = None
    emulator = None
    controller_sizes = None
    if (args.grad_compression in ("adaptive", "learned")
            or args.emu_dcn_mbps is not None):
        from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
            leaf_sizes,
        )

        if update_mode == "full":
            # The compressor sees the reduce-scattered 1/W shard, so the
            # controller's payload table must be shard-sized — full-tensor
            # sizes would overestimate wire bytes W× and starve the rungs.
            from distributed_sigmoid_loss_tpu.parallel.mesh import data_axis
            from distributed_sigmoid_loss_tpu.parallel.update_shard import (
                shard_leaf_sizes,
            )

            controller_sizes = shard_leaf_sizes(
                state.params, dict(mesh.shape)[data_axis]
            )
        else:
            controller_sizes = leaf_sizes(state.params)
    if args.grad_compression in ("adaptive", "learned"):
        # Warmup doubles as the controller's observation window: each warmup
        # step is wall-timed (the wire-bytes float() waits for the step, like
        # the loss sync below), then ONE
        # decision is staged for the timed loop — the measured steady state
        # has no per-step host round-trip, so adaptive-vs-fixed A/Bs compare
        # wire formats, not host-sync overhead. The learned rung's codec
        # trains during the same window (host PCA of the step's block
        # moments) and is staged alongside the scheme — both are value
        # changes of replicated donated operands, never recompiles.
        import numpy as np

        from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
            BitController,
            CodecTrainer,
        )
        from distributed_sigmoid_loss_tpu.train import stage_codec, stage_scheme

        controller = BitController(
            controller_sizes,
            n_dcn=args.dcn_slices,
            topk_frac=args.topk_frac,
            dcn_budget_mbps=args.dcn_budget_mbps,
            controller=args.controller or "greedy",
            learned=args.grad_compression == "learned",
        )
        if args.grad_compression == "learned":
            codec_trainer = CodecTrainer()
    if args.emu_dcn_mbps is not None:
        # Honest DCN emulation: the throttled two-process pipe the timed
        # loop ships each call's actual payload through (parallel/dcn_emu.py).
        from distributed_sigmoid_loss_tpu.parallel.dcn_emu import DCNEmulator

        emulator = DCNEmulator(args.emu_dcn_mbps).start()
        # The fixed-bf16 reference payload per sync round — the same
        # (n_dcn-1)-hop egress at 2 bytes/param, measured through the SAME
        # pipe so wire_savings_wallclock_ratio compares wire time with wire
        # time at this bandwidth.
        bf16_ref_bytes = (args.dcn_slices - 1) * 2 * int(sum(controller_sizes))
    for _ in range(3):
        tw = time.perf_counter()
        state, metrics = compiled(state, batch)
        if controller is not None or emulator is not None:
            wire = float(metrics["dcn_wire_bytes"])  # drains the queue
            step_dt = time.perf_counter() - tw
            if emulator is not None:
                # Observe MEASURED transfer time, not compute-bounded step
                # time — the controller's bandwidth EWMA reacts to the pipe.
                transfer_dt = emulator.transfer(wire)
                if controller is not None:
                    controller.observe(transfer_dt, wire)
            elif controller is not None:
                controller.observe(step_dt, wire)
        if codec_trainer is not None:
            codec_trainer.update(np.asarray(state.comp["blockmoment"]))
    float(metrics["loss"])
    if codec_trainer is not None:
        state = stage_codec(state, codec_trainer.codec(), mesh)
    if controller is not None:
        controller.decide(
            np.asarray(state.comp["ef_ratio"]),
            gnorm=np.asarray(state.comp["gnorm"]),
            gvar=np.asarray(state.comp["gvar"]),
        )
        state = stage_scheme(state, controller.scheme, mesh)
    ref_dt_per_call = 0.0
    if emulator is not None:
        # One settle step AFTER staging so the timed loop starts from the
        # decided scheme/codec, then calibrate the bf16 reference transfer
        # through the same pipe (median-free mean of 3 — the pipe's pacing
        # makes repeats tight).
        state, metrics = compiled(state, batch)
        float(metrics["dcn_wire_bytes"])
        ref_times = [
            emulator.transfer(bf16_ref_bytes * spc) for _ in range(3)
        ]
        ref_dt_per_call = sum(ref_times) / len(ref_times)

    import contextlib

    from distributed_sigmoid_loss_tpu.utils.profiling import trace

    profile_ctx = trace(args.profile) if args.profile else contextlib.nullcontext()
    transfer_total = 0.0
    with profile_ctx:
        t0 = time.perf_counter()
        for _ in range(args.steps // spc):
            state, metrics = compiled(state, batch)
            if emulator is not None:
                # The call's ACTUAL payload crosses the throttled pipe; the
                # float() drains the queue first so transfer time serializes
                # after compute, exactly as a blocking DCN sync would.
                wire = float(metrics["dcn_wire_bytes"])
                transfer_total += emulator.transfer(wire * spc)
        final_loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
    assert jnp.isfinite(final_loss), f"non-finite loss in bench: {final_loss}"

    pairs_per_sec_per_chip = global_b * args.steps / dt / n_dev

    # MFU on the standard model-FLOPs basis (3x forward: fwd + 2x bwd, remat
    # recompute excluded); hw_util additionally counts executed recompute FLOPs.
    device_kind = jax.devices()[0].device_kind
    peak = peak_bf16_tflops(device_kind)
    model_flops_per_pair = 3.0 * model_forward_flops_per_pair(cfg)
    achieved_model_tflops = model_flops_per_pair * pairs_per_sec_per_chip / 1e12
    # The published A100 ballpark is a ViT-B/16 number; for other models the
    # comparable reference is the same-MFU A100 rate, i.e. scaled by the FLOPs
    # ratio — otherwise vs_baseline for l14/so400m compares throughput of
    # different-sized models.
    flops_b16 = model_forward_flops_per_pair(SigLIPConfig.b16())
    a100_ref = A100_REF_PAIRS_PER_SEC * flops_b16 / model_forward_flops_per_pair(cfg)
    record = {
        "metric": f"siglip_vit{args.model}_train_pairs_per_sec_per_chip"
                  f"{args.metric_suffix}",
        "value": round(pairs_per_sec_per_chip, 2),
        "unit": "pairs/s/chip",
        "vs_baseline": round(pairs_per_sec_per_chip / a100_ref, 3),
        "a100_ref_pairs_per_sec": round(a100_ref, 1),
        "model": args.model,
        "per_chip_batch": args.batch,
        "global_batch": global_b,
        "accum_steps": args.accum,
        "accum_negatives": args.accum_negatives,
        "steps": args.steps,
        "steps_per_call": spc,
        "variant": args.variant,
        "loss_family": args.loss_family,
        "precision": args.precision,
        "use_pallas": args.use_pallas,
        "remat_policy": cfg.vision.remat_policy,
        "n_devices": n_dev,
        "device_kind": device_kind,
        "final_loss": round(final_loss, 4),
        "model_tflops_per_sec_per_chip": round(achieved_model_tflops, 1),
    }
    if peak_hbm_gb is not None:
        record["peak_hbm_gb"] = peak_hbm_gb
    # Real occupancy next to XLA's static memory_analysis sum: the static
    # figure can exceed physical HBM (16.89 "GB" reported on the 16 GB chip,
    # docs/PERF.md round-3 caveat) because the allocator reuses buffers the
    # analysis counts separately. peak_bytes_in_use is what the device
    # allocator actually held at its high-water mark.
    live_gb = peak_hbm_live_gb()
    if live_gb is not None:
        record["peak_hbm_live_gb"] = live_gb
    # Executed-FLOPs utilization from XLA's cost model — only when self-consistent:
    # executed FLOPs include remat recompute, so they can never be below the model
    # FLOPs. A backend was observed to report a module "flops" an order of
    # magnitude low; publishing a 0.06 "hw_util" next to a 0.51 MFU would be noise.
    hw_tflops = None
    record["scan_layers"] = args.scan_layers
    if args.attn_impl != "auto":
        record["attn_impl"] = args.attn_impl
    if args.text_attn_impl:
        record["text_attn_impl"] = args.text_attn_impl
    record.update(_pallas_record_fields(args))
    if args.moe:
        record["moe_experts"] = args.moe
        record["moe_num_selected"] = args.moe_k
        if args.moe_group_size:
            record["moe_group_size"] = args.moe_group_size
        if args.moe_cf is not None:
            record["moe_capacity_factor"] = args.moe_cf
    if args.quant_train:
        record["quant_train"] = args.quant_train
    if args.loss_impl != "fused":
        record["loss_impl"] = args.loss_impl
    if args.ring_overlap:
        record["ring_overlap"] = True
    if update_mode != "off":
        record["update_sharding"] = update_mode
        if update_mode == "zero1":
            record["zero1"] = True  # legacy field, kept for LEDGER continuity
        # Measured at-rest optimizer bytes per replica AFTER the run — under
        # full sharding the post-step opt_state carries its shard placement,
        # which is the figure the ≥0.6·W× regression pin asserts on.
        from distributed_sigmoid_loss_tpu.parallel.update_shard import (
            opt_mem_bytes_per_replica,
        )

        opt_mem = opt_mem_bytes_per_replica(state.opt_state)
        if opt_mem is not None:
            record["opt_mem_bytes_per_replica"] = opt_mem
    if args.mu_bf16:
        record["adam_mu_dtype"] = "bfloat16"
    if args.accum_bf16:
        record["accum_dtype"] = "bfloat16"
    if args.gradcache_bf16:
        record["gradcache_embed_dtype"] = "bfloat16"
    if args.no_text_remat:
        record["no_text_remat"] = True
    if args.grad_compression:
        record["grad_compression"] = args.grad_compression
        record["dcn_slices"] = args.dcn_slices
        if args.grad_compression in ("topk", "adaptive", "learned"):
            record["topk_frac"] = args.topk_frac
        # The step's own wire accounting (obs/metrics_schema.py fields):
        # per-device DCN egress bytes per sync round and payload bits/param.
        record["dcn_wire_bytes"] = round(float(metrics["dcn_wire_bytes"]), 1)
        record["bits_per_param"] = round(float(metrics["bits_per_param"]), 4)
        record["ef_residual_norm"] = round(
            float(metrics["ef_residual_norm"]), 6
        )
        if args.grad_compression in ("adaptive", "learned"):
            record["compression_scheme_hist"] = [
                int(x) for x in metrics["compression_scheme_hist"]
            ]
            record["dcn_bw_est_mbps"] = round(
                controller.bw_est_mbps or 0.0, 1
            )
            if args.dcn_budget_mbps is not None:
                record["dcn_budget_mbps"] = args.dcn_budget_mbps
            record["controller_mode"] = controller.mode
            record["error_budget"] = round(
                float(controller.last_error_budget), 6
            )
        if args.grad_compression == "learned":
            record["codec_recon_err"] = round(
                float(metrics["codec_recon_err"]), 6
            )
        if emulator is not None:
            # graftcodec's emulated-DCN measurements: the throttle setting,
            # the bandwidth MEASURED through the pipe, and the wall-clock
            # step-time ratio vs the fixed-bf16 reference transfer (> 1 =
            # the compressed wire saves wall clock at this bandwidth).
            record["emu_dcn_mbps"] = args.emu_dcn_mbps
            record["dcn_measured_mbps"] = round(
                emulator.measured_mbps or 0.0, 2
            )
            compute_dt = dt - transfer_total
            n_calls = args.steps // spc
            record["wire_savings_wallclock_ratio"] = round(
                (compute_dt + n_calls * ref_dt_per_call) / dt, 4
            )
            emulator.close()
    if hw_flops_per_step_per_dev is not None:
        hw_tflops = hw_flops_per_step_per_dev * args.steps / dt / 1e12
        if hw_tflops >= achieved_model_tflops:
            record["hw_tflops_per_sec_per_chip"] = round(hw_tflops, 1)
        else:
            hw_tflops = None
    record["mfu"] = round(achieved_model_tflops / peak, 3)
    if hw_tflops is not None:
        record["hw_util"] = round(hw_tflops / peak, 3)
    # graftscope static attribution (obs/attribution.py): per-kind collective
    # wire bytes + the roofline mfu_est for THIS chip ride every headline
    # record, so the number's attribution is pinned even when only the record
    # (not a trace) survives. Trace-only: seconds next to the minutes of compile.
    from distributed_sigmoid_loss_tpu.obs.attribution import (
        COLLECTIVE_KINDS,
        jaxpr_costs,
        roofline_estimate,
    )

    costs = jaxpr_costs(jax.make_jaxpr(step)(state, batch))
    est = roofline_estimate(
        costs["flops_est"], costs["comm_bytes_total"],
        bytes_accessed=None, device_kind=device_kind,
    )
    record["mfu_est"] = est["mfu_est"]
    record["roofline_bound"] = est["bound"]
    record["comm_bytes_total"] = round(costs["comm_bytes_total"], 1)
    for kind in COLLECTIVE_KINDS:
        record[f"comm_bytes_{kind}"] = round(costs[f"comm_bytes_{kind}"], 1)
    _emit(record)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or HEADLINE_ARGV))
