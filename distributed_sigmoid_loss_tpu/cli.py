"""Command-line entry point: ``python -m distributed_sigmoid_loss_tpu <cmd>``.

The reference has no CLI (its entry points are test-file ``__main__`` blocks,
/root/reference/test_distributed_sigmoid_loss.py:144-148); a framework needs one.
The subcommands tie the subsystems together:

- ``train`` — end-to-end SigLIP training on synthetic data: mesh, towers,
  distributed sigmoid loss (all-gather or ring), optax, metrics logging,
  preemption-safe checkpointing (``--ckpt-dir``).
- ``eval``  — zero-shot retrieval + classification of a (random-init or
  checkpointed) model on held-out synthetic data.
- ``export`` — AOT-export a lowered train/forward step to a StableHLO artifact
  (``jax.export``): deployable without model code, replayable on a matching
  topology.
- ``serve-bench`` — online-serving micro-bench: concurrent client threads
  through the batched/cached/bucketed ``serve/`` stack (engine + micro-batcher
  + LRU cache + retrieval index) on synthetic data; prints the ``stats()``
  snapshot (qps, latency percentiles, batch histogram, cache hit rate, compile
  count) as one JSON record. CPU-runnable — docs/SERVING.md.
- ``data-bench`` — input-pipeline stage bench: shard read / decode / tokenize
  / augment / host→device commit in isolation, plus the composed real-data
  pipeline (read-ahead + fused batcher + prefetch) vs the synthetic loader,
  as schema-validated JSON records with the ``synthetic_ratio`` acceptance
  figure and a decode worker-scaling curve. CPU-runnable.
- ``lint`` — graftlint: the repo-invariant AST linter, the graftprove
  config-space drift check (declarative solver vs the real imperative
  refusals), and the jaxpr collective/dtype/dataflow auditor traced over the
  sampled step-config product on an emulated CPU mesh (exit 1 on findings,
  ``--json``, per-rule ``--disable``, ``--full-product``, ``--baseline``).
  The same analyzers run in tier-1 (tests/test_analysis.py,
  tests/test_config_space.py) and the dryrun — docs/ANALYSIS.md.
- ``obs`` — graftscope offline reports: ``obs summarize DIR`` prints the
  host spans a ``train --obs-dir`` run recorded and the op table of any
  device trace capture under DIR as one where-the-time-goes report —
  docs/OBSERVABILITY.md.

``train`` and ``eval`` accept ``--cpu-devices N`` to emulate an N-chip mesh on
CPU — the TPU-native analogue of the reference's ``mp.spawn`` + Gloo localhost
harness. A rate or a utilisation comes from ``benchmark/run.py`` on the chip,
never from this CLI.
"""

from __future__ import annotations

import argparse
import os
import sys

__all__ = ["main"]


def _bootstrap_devices(args) -> None:
    """Force an emulated N-device CPU platform BEFORE jax initializes."""
    if args.cpu_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_devices}"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")


def _config_from_file(path: str):
    """``SigLIPConfig`` from a configuration file's ``vision`` / ``text`` /
    ``loss`` sections, by dataclass field name (benchmark/configs/*.json have
    them; their other keys say where the numbers come from and are not read)."""
    import dataclasses
    import json

    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TextConfig,
        ViTConfig,
    )

    with open(path) as f:
        body = json.load(f)

    def section(cls, name):
        given = body.get(name, {})
        unknown = set(given) - {f.name for f in dataclasses.fields(cls)}
        if unknown:
            raise SystemExit(f"{path}: {cls.__name__} has no field(s) {sorted(unknown)}")
        return cls(**given)

    return SigLIPConfig(
        vision=section(ViTConfig, "vision"), text=section(TextConfig, "text"),
        loss=section(LossConfig, "loss"),
    )


def _model_config(args):
    from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig

    if getattr(args, "tiny", False) and args.model != "b16":
        # --tiny is an alias for --model tiny; silently overriding an explicit
        # non-default --model would run a different config than the user asked for.
        raise SystemExit(
            f"--tiny conflicts with --model {args.model}; pass one or the other"
        )
    name = "tiny" if getattr(args, "tiny", False) else args.model
    if getattr(args, "model_config", ""):
        if name != "b16":
            raise SystemExit(f"--model-config conflicts with --model {name}; pass one or the other")
        cfg = _config_from_file(args.model_config)
    else:
        cfg = {
            "tiny": SigLIPConfig.tiny_test,
            "l14": SigLIPConfig.l14,
            "so400m": SigLIPConfig.so400m,
            "b16": SigLIPConfig.b16,
        }[name]()
    moe = getattr(args, "moe_experts", 0)
    if moe:
        # Shared by train AND eval: a checkpoint trained with --moe-experts can
        # only be restored into an identically-shaped (MoE) model.
        if moe < 2:
            raise SystemExit(f"--moe-experts must be >= 2, got {moe}")
        import dataclasses

        group = getattr(args, "moe_group_size", 0)
        tower_kw = {"moe_experts": moe}
        if group:
            if group < 1:
                raise SystemExit(f"--moe-group-size must be >= 1, got {group}")
            tower_kw["moe_group_size"] = group
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, **tower_kw),
            text=dataclasses.replace(cfg.text, **tower_kw),
        )
    elif getattr(args, "moe_group_size", 0):
        raise SystemExit("--moe-group-size without --moe-experts is a no-op")
    if getattr(args, "quant", ""):
        # Eval/export-only (make_train_step rejects quantized configs): dynamic
        # int8 projection matmuls — the v5e's 2x-bf16 inference gear.
        import dataclasses

        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, quant=args.quant),
            text=dataclasses.replace(cfg.text, quant=args.quant),
        )
    if getattr(args, "quant_train", ""):
        # Trainable int8 (train subcommand): same dynamic int8 forward through
        # the straight-through estimator — backward stays full-precision
        # (ops/quant.py int8_dot_general_ste), so the step trains normally.
        import dataclasses

        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(
                cfg.vision, quant_train=args.quant_train
            ),
            text=dataclasses.replace(cfg.text, quant_train=args.quant_train),
        )
    if getattr(args, "remat_policy", ""):
        # The best policy differs by model AND by batch, so the train CLI
        # exposes the knob rather than hard-coding one winner.
        if not (cfg.vision.remat or cfg.text.remat):
            # tiny_test() disables remat entirely — the policy would be
            # silently ignored (Encoder applies it only under remat=True).
            raise SystemExit(
                f"--remat-policy {args.remat_policy} is a no-op for "
                f"{name!r}: its towers run without rematerialization"
            )
        import dataclasses

        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(
                cfg.vision, remat_policy=args.remat_policy
            ),
            text=dataclasses.replace(cfg.text, remat_policy=args.remat_policy),
        )
    return cfg


def _make_training_mesh(args):
    """The (dp[, ep|pp]) mesh for ``--ep`` / ``--pp`` topologies — ONE set of
    rules shared by train and export (an artifact validated under different
    rules than the job it deploys to is exactly the drift this helper prevents).

    Returns ``(mesh, None)`` or ``(None, error_message)``.
    """
    import jax

    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh

    dcn = getattr(args, "dcn_slices", 1)
    if dcn > 1:
        import numpy as np
        from jax.sharding import Mesh

        from distributed_sigmoid_loss_tpu.parallel.mesh import data_axis
        from distributed_sigmoid_loss_tpu.parallel.multihost import (
            _hybrid_device_array,
        )

        devices = jax.devices()
        n_dev = len(devices)
        pp = getattr(args, "pp", 1)
        if args.ep > 1:
            return None, "--dcn-slices composes with dp/pp only (no --ep)"
        if n_dev % (dcn * pp):
            return None, (
                f"--dcn-slices {dcn} x --pp {pp} must divide device count "
                f"{n_dev}"
            )
        # dcn outermost, and GROUPED BY REAL SLICE on multi-slice hardware
        # (mesh_utils.create_hybrid_device_mesh via _hybrid_device_array) —
        # a raw enumeration-order reshape could put devices of different
        # slices in one "dp" row, sending the f32 psum over DCN and the int8
        # hop over ICI: the exact inversion of the feature. CPU emulation and
        # single-slice devices carry no slice metadata; plain reshape there.
        slice_ids = {getattr(d, "slice_index", 0) for d in devices}
        if len(slice_ids) > 1:
            if len(slice_ids) != dcn:
                return None, (
                    f"--dcn-slices {dcn} != actual slice count "
                    f"{len(slice_ids)} — the dcn axis must follow real "
                    f"slice boundaries for the compression split to match "
                    f"the link topology"
                )
            # pp rides the innermost ICI factor (stage hops are ppermute
            # neighbor traffic); _hybrid_device_array groups by real slice.
            arr = _hybrid_device_array(dcn, n_dev // (dcn * pp), pp, devices)
        else:
            if devices and devices[0].platform == "tpu":
                # On real single-slice TPU hardware the 'dcn' axis lands on
                # ICI neighbors: the int8/top-k hop pays quantization loss on
                # a fast link with zero bandwidth win. A stderr warning is
                # easy to lose in multi-host logs (advisor, round 4), so a
                # production run REFUSES unless the override flag makes the
                # emulation intent explicit. The silent plain-reshape path
                # exists for CPU emulation, where virtual devices carry no
                # slice metadata.
                if not getattr(args, "force_dcn_emulation", False):
                    return None, (
                        f"--dcn-slices {dcn} on single-slice TPU hardware: "
                        "the 'dcn' axis maps onto ICI neighbors, so "
                        "compressed gradient sync pays quantization loss on "
                        "a fast link with no bandwidth win; pass "
                        "--force-dcn-emulation to run it anyway (perf "
                        "experiments emulating a multi-slice topology)"
                    )
                print(
                    f"WARNING: --dcn-slices {dcn} on single-slice TPU "
                    "hardware (--force-dcn-emulation) — compressed sync "
                    "pays quantization loss on ICI with no bandwidth win",
                    file=sys.stderr,
                )
            arr = np.array(devices)
        if pp > 1:
            from distributed_sigmoid_loss_tpu.parallel.pipeline import (
                pipeline_axis,
            )

            return (
                Mesh(
                    arr.reshape(dcn, n_dev // (dcn * pp), pp),
                    ("dcn", data_axis, pipeline_axis),
                ),
                None,
            )
        return (
            Mesh(arr.reshape(dcn, n_dev // dcn), ("dcn", data_axis)),
            None,
        )
    pp = getattr(args, "pp", 1)
    if pp > 1:
        from distributed_sigmoid_loss_tpu.parallel.mesh import (
            data_axis,
            make_2d_mesh,
        )
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis

        n_dev = len(jax.devices())
        if args.ep > 1:
            return None, "--pp with --ep is not supported (pp towers are dense)"
        if n_dev % pp:
            return None, f"--pp {pp} must divide device count {n_dev}"
        return (
            make_2d_mesh(n_dev // pp, pp, axis_names=(data_axis, pipeline_axis)),
            None,
        )
    if args.ep <= 1:
        return make_mesh(), None
    from distributed_sigmoid_loss_tpu.models.moe import EP_AXIS
    from distributed_sigmoid_loss_tpu.parallel.mesh import data_axis, make_2d_mesh

    n_dev = len(jax.devices())
    if not args.moe_experts:
        return None, (
            "--ep > 1 without --moe-experts would only shrink data "
            "parallelism (a dense model has no ep-sharded params)"
        )
    if n_dev % args.ep:
        return None, f"--ep {args.ep} must divide device count {n_dev}"
    if args.moe_experts % args.ep:
        return None, (
            f"--ep {args.ep} must divide --moe-experts {args.moe_experts} "
            f"(expert kernels are stacked (E, ...) and sharded over ep)"
        )
    return make_2d_mesh(n_dev // args.ep, args.ep, axis_names=(data_axis, EP_AXIS)), None


def _byte_tokenize_for(cfg, vocab_path: str = ""):
    """Tokenizer folded into the config's vocab when it's smaller (tiny test
    configs): modulo keeps distinct texts distinct, where clamping would
    collapse them onto the max id. Shared by train (real-data loaders) and eval
    (zero-shot prompts). ``vocab_path``: a trained BPE vocab (``tokenizer``
    subcommand) instead of the byte-level default."""
    from distributed_sigmoid_loss_tpu.data import BpeTokenizer, ByteTokenizer

    tok = BpeTokenizer.load(vocab_path) if vocab_path else ByteTokenizer()

    def tokenize(texts, length):
        import numpy as np

        ids = np.asarray(tok(texts, length))
        if cfg.text.vocab_size < tok.vocab_size:
            ids = ids % cfg.text.vocab_size
        return ids

    return tokenize


def _resolve_eval_data(path: str):
    """Resolve --eval-data to ("dir", path) / ("shards", [tars]) / (None, error).

    ONE resolution helper shared by cmd_train's early usage check and the
    source build, so the two can never disagree on what a valid path is.
    """
    import glob as globmod
    import os

    if os.path.isdir(path):
        return "dir", path
    shards = globmod.glob(path)
    if shards:
        return "shards", shards
    return None, f"--eval-data matched nothing: {path!r}"


def _eval_holdout_source(args, cfg, tokenize, native_decode: bool):
    """Build the --eval-data holdout source (directory or tar-shard glob).

    Yields GLOBAL batches of ``args.batch`` rows on every host (place_global
    slices process-wise) — the eval batch is one fixed batch, so the striped
    multi-host read path is deliberately not used here. ``native_decode``
    must match the training stream's decoder: PIL and the native libjpeg
    engine produce numerically different pixels, and a decode-skewed eval
    batch would measure the wrong distribution.
    """
    from distributed_sigmoid_loss_tpu.data import ImageTextFolder, ImageTextShards

    kind, resolved = _resolve_eval_data(args.eval_data)
    if kind == "dir":
        return ImageTextFolder(
            resolved, cfg, args.batch, tokenize, native_decode=native_decode,
        )
    if kind == "shards":
        return ImageTextShards(
            resolved, cfg, args.batch, tokenize, native_decode=native_decode,
        )
    # Same exit-2 usage-error channel as '--data-shards matched nothing'
    # (cmd_train pre-validates; this is the non-train-caller backstop).
    print(resolved, file=sys.stderr)
    raise SystemExit(2)


def _train_config_conflicts(args) -> str | None:
    """The ``train`` command's config-compatibility refusals, as a pure
    predicate: the first conflict message, or None when the flag set is
    coherent.

    Extracted from cmd_train so graftprove (analysis/config_space.py) can
    probe the CLI layer with a synthesized namespace: every refusal here is
    config-space (flag compatibility) and must agree with the declarative
    constraint table — a disagreement is a ``config-space-drift`` finding.
    Environment checks (paths, coordinators, device counts) stay in
    cmd_train.
    """
    if args.ep < 1:
        return f"--ep must be >= 1, got {args.ep}"
    if args.moe_aux_weight is not None and not args.moe_experts:
        return ("--moe-aux-weight without --moe-experts would be a silent "
                "no-op (a dense model has no routers to balance)")
    if args.pp > 1 and args.moe_experts:
        return "--pp with --moe-experts is not supported (pp towers are dense)"
    # graftshard mode resolution: --update-sharding supersedes --zero1 (the
    # deprecated alias). Mirrors parallel/update_shard.resolve_update_sharding
    # without the jax import this predicate must stay free of.
    update_mode = getattr(args, "update_sharding", "") or ""
    if args.zero1 and update_mode not in ("", "zero1"):
        return (f"--zero1 is the deprecated alias for --update-sharding "
                f"zero1 and contradicts --update-sharding {update_mode}; "
                "drop one of them")
    if args.zero1 and not update_mode:
        update_mode = "zero1"
    if update_mode == "off":
        update_mode = ""
    if args.pp > 1 and update_mode:
        return (f"--pp with --update-sharding {update_mode} is not supported "
                "(the sharded update — zero1's constrain and full's "
                "reduce-scatter alike — would re-shard the stage-local "
                "moments dp-wise every step)")
    if args.pp_microbatches and args.pp <= 1:
        return "--pp-microbatches without --pp > 1 would be a silent no-op"
    if args.pp_microbatches < 0:
        return f"--pp-microbatches must be >= 1, got {args.pp_microbatches}"
    if args.accum_bf16 and args.accum == 1:
        # Same check exists in make_train_step; exit-2 here beats a deep raise.
        return ("--accum-bf16 requires --accum > 1 (the unaccumulated step "
                "has no accumulator)")
    if args.pp > 1 and args.accum > 1 and args.accum_negatives == "global":
        # Same check exists in make_train_step; repeat it HERE so the exit-2
        # message lands before the minutes-long create_train_state.
        return ("--accum-negatives global with --pp is not supported (the pp "
                "forward is already whole-batch per accumulation step)")
    if args.gradcache_bf16 and (
        args.accum == 1 or args.accum_negatives != "global"
    ):
        return ("--gradcache-bf16 requires --accum > 1 with "
                "--accum-negatives global (only the GradCache path stashes "
                "embedding tables)")
    if args.loss_impl == "chunked":
        # Refuse, don't drop: a run claiming the streamed-negatives memory
        # shape while silently running the ring would invalidate any HBM A/B.
        if args.variant == "ring":
            return ("--loss-impl chunked applies to the all_gather variant "
                    "only (the ring already streams negatives one chunk per "
                    "hop); drop --variant ring or pass --variant all_gather")
        if args.ring_overlap:
            return ("--loss-impl chunked (all_gather) and --ring-overlap "
                    "(ring) select different comm variants; pick one")
    if args.ring_overlap and args.variant == "all_gather":
        return ("--ring-overlap applies to the ring variant only (the "
                "all-gather loss has no hop loop to overlap)")
    if args.loss_family == "softmax" and (
        args.loss_impl != "fused" or args.ring_overlap
    ):
        return ("--loss-impl chunked / --ring-overlap apply to the sigmoid "
                "family only (the softmax ring already streams its logsumexp)")
    if args.use_pallas and args.loss_family != "sigmoid":
        # The streaming kernel computes the sigmoid family's block math; a
        # softmax run claiming --use-pallas would silently run plain XLA.
        return "--use-pallas applies to the sigmoid family only"
    if args.watchdog == "skip" and not args.ckpt_dir:
        # The jitted step DONATES its input state, so a poisoned update can
        # only be undone by restoring a checkpoint — skip without --ckpt-dir
        # would silently train on from the poisoned params.
        return ("--watchdog skip requires --ckpt-dir (skipping rolls back to "
                "the last good checkpoint; without one there is nothing to "
                "roll back to)")
    if args.dcn_slices > 1 and not args.grad_compression:
        return ("--dcn-slices without --grad-compression is a silent no-op: "
                "the regular step already spans slices when the dp axis is "
                "built dcn-outermost (parallel/multihost.py make_hybrid_mesh); "
                "the separate dcn axis exists to compress its gradient hop")
    if args.grad_compression:
        reasons = []
        if args.dcn_slices < 2:
            reasons.append("--dcn-slices >= 2 (the dcn axis being compressed)")
        if args.variant == "ring":
            reasons.append("--variant all_gather or unset (ring ppermute has "
                           "no joint-(dcn,dp) axis form)")
        if args.ep > 1:
            # --pp and --moe-experts (experts replicated, ep == 1) compose
            # since round 5; expert PARALLELISM stays with the regular step
            # (no GSPMD all-to-alls inside the manual region).
            reasons.append("no --ep (expert parallelism needs the regular step)")
        if args.ring_overlap:
            reasons.append("no --ring-overlap (compressed sync is "
                           "all_gather-only; there is no ring hop loop)")
        if args.ema_decay is not None:
            reasons.append("no --ema-decay")
        if args.grad_compression in ("topk", "adaptive", "learned") and not (
            0 < args.topk_frac <= 1
        ):
            reasons.append(
                f"--topk-frac in (0, 1], got {args.topk_frac} (it is the "
                f"fraction of gradient entries kept per tensor)"
            )
        if args.grad_compression in ("adaptive", "learned") and args.pp > 1:
            reasons.append(
                "no --pp (the adaptive controller's scheme table is per "
                "GLOBAL tensor; pp shards block grads stage-locally — use "
                "int8/topk under pp)"
            )
        if reasons:
            return "--grad-compression requires: " + "; ".join(reasons)
    if args.topk_frac != 0.01 and args.grad_compression not in (
        "topk", "adaptive", "learned"
    ):
        return "--topk-frac without --grad-compression topk is a silent no-op"
    if args.topk_exact and args.grad_compression not in (
        "topk", "adaptive", "learned"
    ):
        return "--topk-exact without --grad-compression topk is a silent no-op"
    if args.dcn_budget_mbps is not None and args.grad_compression not in (
        "adaptive", "learned"
    ):
        return ("--dcn-budget-mbps without --grad-compression adaptive is a "
                "silent no-op: only the adaptive bit controller consumes the "
                "bandwidth budget")
    if getattr(args, "controller", None) and args.grad_compression not in (
        "adaptive", "learned"
    ):
        return ("--controller without --grad-compression adaptive/learned is "
                "a silent no-op: the bit controller only exists inside the "
                "adaptive step wrapper (a fixed scheme has no per-round "
                "policy to select)")
    if getattr(args, "emu_dcn_mbps", None) is not None and args.dcn_slices < 2:
        return ("--emu-dcn-mbps without --dcn-slices >= 2 is a silent no-op: "
                "the emulated pipe carries the dcn hop's payload, and there "
                "is no dcn mesh axis (or compressed sync round) to emulate")
    return None


def cmd_train(args) -> int:
    _bootstrap_devices(args)
    import jax

    if args.async_checkpoint and not args.ckpt_dir:
        print("--async-checkpoint without --ckpt-dir would be a silent no-op "
              "(there is nothing to save)", file=sys.stderr)
        return 2
    if args.eval_data and not args.eval_every:
        print("--eval-data without --eval-every would be a silent no-op "
              "(nothing ever evaluates it)", file=sys.stderr)
        return 2
    if args.eval_data:
        # Validate the path NOW — the eval hook is built after the
        # minutes-long state init, far too late for a typo'd glob.
        kind, resolved = _resolve_eval_data(args.eval_data)
        if kind is None:
            print(resolved, file=sys.stderr)
            return 2
    if args.coordinator:
        if args.num_processes < 1 or args.process_id < 0:
            print(
                "--coordinator requires --num-processes >= 1 and --process-id >= 0 "
                "(every process runs the same command with its own --process-id)",
                file=sys.stderr,
            )
            return 2
        if args.batch % args.num_processes:
            print(
                f"--batch {args.batch} must be divisible by --num-processes "
                f"{args.num_processes} (batch is GLOBAL; each process contributes "
                f"batch/num_processes rows)",
                file=sys.stderr,
            )
            return 2
        # Multi-process run: rendezvous BEFORE any other jax use so every host
        # sees the same global device list (the pjit single-controller model).
        from distributed_sigmoid_loss_tpu.parallel.multihost import (
            initialize_multihost,
        )

        try:
            initialize_multihost(
                coordinator_address=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
            )
        except Exception as e:
            # Environmental (ports/sandbox): a distinct exit code lets harnesses
            # skip rather than fail — same contract as tests/_multihost_worker.py.
            print(f"INIT_FAILED: {type(e).__name__}: {e}", file=sys.stderr)
            return 3

    from distributed_sigmoid_loss_tpu.data import (
        SyntheticImageText,
        global_batch_from_local,
    )
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        PreemptionGuard,
        RestoreRequiredError,
        create_train_state,
        latest_step,
        make_optimizer,
        make_train_step,
        train_resilient,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig, TrainConfig
    from distributed_sigmoid_loss_tpu.utils.logging import MetricsLogger

    cfg = _model_config(args)
    conflict = _train_config_conflicts(args)
    if conflict is not None:
        print(conflict, file=sys.stderr)
        return 2
    mesh, mesh_err = _make_training_mesh(args)
    if mesh_err:
        print(mesh_err, file=sys.stderr)
        return 2
    pidx, pcnt = jax.process_index(), jax.process_count()
    dev0 = jax.devices()[0]
    print(
        f"mesh: {dict(mesh.shape)} devices={len(jax.devices())} "
        f"platform={dev0.platform} device_kind={dev0.device_kind!r}"
        + (f" process {pidx}/{pcnt}" if pcnt > 1 else ""),
        file=sys.stderr,
    )
    if pcnt > 1 and args.batch % pcnt:
        # --coordinator runs checked this already; a pre-initialized runtime
        # (TPU pod auto-init) reaches here without that gate. batch is GLOBAL;
        # an indivisible value would silently train at batch//pcnt*pcnt.
        print(
            f"--batch {args.batch} must be divisible by process count {pcnt}",
            file=sys.stderr,
        )
        return 2
    # Resolved graftshard mode ("off" | "zero1" | "full") — the conflict
    # predicate above already refused contradictory flag pairs.
    update_mode = args.update_sharding or ("zero1" if args.zero1 else "off")
    if update_mode == "full":
        from distributed_sigmoid_loss_tpu.parallel.mesh import data_axis as _dax

        if dict(mesh.shape).get(_dax, 1) < 2:
            # Environment refusal (a mesh-instance property, not flag
            # compatibility — same split as the builders'): nothing to
            # reduce-scatter over on a 1-wide data axis.
            print(
                "--update-sharding full requires a data-parallel axis of "
                f"size > 1, got mesh {dict(mesh.shape)}",
                file=sys.stderr,
            )
            return 2

    if args.loss_family != "sigmoid":
        import dataclasses

        # The model's t_prime init is family-dependent (CLIP: log(1/0.07));
        # the loss config lives on the model config so init sees it.
        cfg = dataclasses.replace(cfg, loss=LossConfig(family=args.loss_family))
    if args.pp > 1:
        import dataclasses

        # pp stages are the nn.scan-stacked block params; force scanned towers
        # (the production configs already are — this covers --tiny, whose
        # test default is unrolled).
        cfg = dataclasses.replace(
            cfg,
            vision=dataclasses.replace(cfg.vision, scan_layers=True),
            text=dataclasses.replace(cfg.text, scan_layers=True),
        )
        # Validate BEFORE create_train_state: a full b16-class param init costs
        # minutes, and every other bad flag combination exits 2 with a message.
        from distributed_sigmoid_loss_tpu.parallel.pp_towers import (
            validate_pp_tower,
        )

        try:
            validate_pp_tower(cfg.vision, args.pp, "vision")
            validate_pp_tower(cfg.text, args.pp, "text")
        except ValueError as e:
            print(f"--pp {args.pp}: {e}", file=sys.stderr)
            return 2
    model = SigLIP(cfg)
    tx = make_optimizer(
        TrainConfig(
            learning_rate=args.lr, warmup_steps=5, total_steps=max(args.steps, 10),
            optimizer=args.optimizer,
        )
    )
    source = None
    if sum(map(bool, (args.data_dir, args.data_shards, args.native_data))) > 1:
        print(
            "--data-dir, --data-shards and --native-data are mutually "
            "exclusive data sources",
            file=sys.stderr,
        )
        return 2
    if args.data_dir and pcnt > 1:
        # A plain folder has no shard structure to stripe across hosts; the
        # multi-host real-data path is --data-shards (tar shards stripe
        # process-wise, the reference's per-rank slicing scaled to files —
        # test_distributed_sigmoid_loss.py:57-68).
        print(
            "--data-dir is a single-process flag; for multi-host real-data "
            "training pack the data as tar shards and use --data-shards "
            "(shards stripe across processes)",
            file=sys.stderr,
        )
        return 2
    if args.shuffle_buffer and not args.data_shards:
        print("--shuffle-buffer applies to --data-shards streams only "
              "(--data-dir already shuffles whole epochs)", file=sys.stderr)
        return 2
    if args.native_decode and not (args.data_dir or args.data_shards):
        print("--native-decode without --data-dir/--data-shards would be a "
              "silent no-op (synthetic data is not decoded)", file=sys.stderr)
        return 2
    # 0 = auto (cpu_count minus the prefetch/main threads); the host worker
    # pool for decode (file sources) / generation (native engine).
    from distributed_sigmoid_loss_tpu.data.workers import resolve_data_workers

    try:
        data_workers = resolve_data_workers(args.data_workers)
    except ValueError as e:
        print(f"--data-workers: {e}", file=sys.stderr)
        return 2
    # Resolved by the file-stream branch; read by the --eval-data holdout so
    # eval decode/tokenization matches training exactly.
    native_decode = False
    tokenize = None
    if args.data_dir or args.data_shards:
        from distributed_sigmoid_loss_tpu.data import (
            ImageTextFolder,
            ImageTextShards,
        )

        tokenize = _byte_tokenize_for(cfg, args.tokenizer)
        if args.native_decode:
            from distributed_sigmoid_loss_tpu.data.native_decode import (
                native_decode_available,
            )

            native_decode = native_decode_available()
            if not native_decode:
                print("--native-decode: libjpeg engine unavailable, "
                      "falling back to PIL decode", file=sys.stderr)
        if args.data_dir:
            source = ImageTextFolder(
                args.data_dir, cfg, args.batch, tokenize,
                native_decode=native_decode,
                data_workers=data_workers,
            )
        else:
            import glob as globmod

            shards = globmod.glob(args.data_shards)
            if not shards:
                print(f"--data-shards matched nothing: {args.data_shards!r}",
                      file=sys.stderr)
                return 2
            if pcnt > 1 and len(shards) < pcnt:
                print(
                    f"--data-shards matched {len(shards)} tar(s) for {pcnt} "
                    "processes; every process needs at least one shard in its "
                    "stripe",
                    file=sys.stderr,
                )
                return 2
            # Multi-process: each host reads its own shard stripe (i, i+N, ...)
            # and contributes batch/num_processes LOCAL rows per step; place()
            # assembles them into the global array with zero cross-host data
            # movement (global_batch_from_local).
            source = ImageTextShards(
                shards, cfg, args.batch // pcnt, tokenize,
                shard_index=pidx, num_shards=pcnt,
                native_decode=native_decode,
                shuffle_buffer=args.shuffle_buffer,
                data_workers=data_workers,
            )
    elif args.native_data:
        from distributed_sigmoid_loss_tpu.data import (
            NativeSyntheticImageText,
            native_available,
        )

        reason = "no C++ toolchain or prebuilt library"
        if native_available():
            try:
                source = NativeSyntheticImageText(
                    cfg, args.batch, num_threads=data_workers
                )
            except (RuntimeError, OSError) as e:
                # available() can't foresee every build failure (old compiler,
                # read-only install dir); the flag promises a fallback either way.
                reason = f"engine unusable: {e}"
        if source is None:
            print(
                f"--native-data: {reason}; falling back to the numpy pipeline",
                file=sys.stderr,
            )
    if source is None:
        source = SyntheticImageText(cfg, args.batch)
    data = iter(source)
    first = next(data)

    # When resuming, the freshly-created state is only train_resilient's
    # restore target — zeros=True skips the (minutes-long on b16-class towers)
    # random init that the checkpoint would immediately overwrite.
    resuming = bool(args.ckpt_dir) and latest_step(args.ckpt_dir) is not None
    pp_micro = 0
    if args.pp > 1:
        # Default microbatch count 2x stages: enough to keep the bubble
        # fraction (S-1)/(S+M-1) under a third without shrinking per-call work.
        pp_micro = args.pp_microbatches or 2 * args.pp
    if args.grad_compression and pp_micro:
        # Fail the batch-split arithmetic HERE (exit 2), not as a traceback
        # inside the first step trace after the minutes-long state init: the
        # compressed+pp step needs global batch = (dcn*dp) x accum x
        # pp-microbatch rows.
        from distributed_sigmoid_loss_tpu.parallel.mesh import data_axis as _dax

        groups = mesh.shape["dcn"] * mesh.shape[_dax]
        ok = args.batch % groups == 0
        local = args.batch // groups if ok else 0
        ok = ok and local % args.accum == 0
        micro_rows = local // args.accum if ok else 0
        if not ok or micro_rows % pp_micro:
            print(
                f"--grad-compression with --pp: global batch {args.batch} "
                f"must divide as (dcn*dp = {groups}) x accum = {args.accum} "
                f"x pp-microbatches = {pp_micro}; "
                f"need batch % {groups * args.accum * pp_micro} == 0",
                file=sys.stderr,
            )
            return 2
    state = create_train_state(
        jax.random.key(0), model, tx, first, mesh,
        update_sharding=update_mode,
        ema=args.ema_decay is not None, zeros=resuming,
        pp_axis="pp" if args.pp > 1 else None,
    )
    # ONE resolution of the step kwargs shared by the compressed and regular
    # branches — a default (e.g. the 0.01 router-aux weight) edited in only
    # one branch would silently train a different objective per mode.
    moe_aux_w = (
        (0.01 if args.moe_aux_weight is None else args.moe_aux_weight)
        if args.moe_experts
        else None
    )
    gradcache_dt = "bfloat16" if args.gradcache_bf16 else None
    if args.grad_compression:
        from distributed_sigmoid_loss_tpu.train import (
            make_compressed_train_step,
            with_adaptive_compression,
            with_error_feedback,
        )

        # ef (and the adaptive carry) ride the live state only; checkpoints never include them (checkpoint._strip_ef), so compressed and plain runs share one checkpoint structure.
        if args.grad_compression in ("adaptive", "learned"):
            state = with_adaptive_compression(
                state, mesh, update_sharding=update_mode,
                learned=args.grad_compression == "learned",
            )
        else:
            state = with_error_feedback(
                state, mesh, pp_axis="pp" if args.pp > 1 else None,
                update_sharding=update_mode,
            )
        try:
            step_fn, shardings = make_compressed_train_step(
                model,
                mesh,
                LossConfig(variant="all_gather", family=args.loss_family,
                           precision="default", loss_impl=args.loss_impl,
                           use_pallas=args.use_pallas),
                update_sharding=update_mode,
                compression=args.grad_compression,
                topk_frac=args.topk_frac,
                topk_approximate=not args.topk_exact,
                accum_steps=args.accum,
                accum_dtype="bfloat16" if args.accum_bf16 else None,
                accum_negatives=args.accum_negatives,
                gradcache_embed_dtype=gradcache_dt,
                pp_microbatches=pp_micro,
                moe_aux_weight=moe_aux_w,
            )
        except ValueError as e:
            # Tower/pp constraints (scan_layers, depth % stages, ...) surface
            # as exit-2 config errors, not tracebacks — same contract as the
            # regular --pp path's validate_pp_tower handling.
            print(f"--grad-compression with --pp {args.pp}: {e}",
                  file=sys.stderr)
            return 2
        jit_step = step_fn  # what static attribution traces (no host wrapper)
        if args.grad_compression in ("adaptive", "learned"):
            # Host-side bit controller around the jitted step: stage the
            # scheme table (a value change of a donated replicated operand —
            # never a recompile), time the step, fold (duration, reported
            # wire bytes) into the bandwidth EWMA, and re-decide from the
            # step's per-tensor stats. Without emulation the step duration
            # upper-bounds the sync duration, so the EWMA UNDER-estimates
            # bandwidth — conservative narrowing, never optimistic widening;
            # under --emu-dcn-mbps the payload actually crosses the throttled
            # pipe and the EWMA tracks MEASURED transfer time. Wrapping
            # step_fn keeps one wiring for both the resilient and plain
            # loops below.
            import atexit as _atexit
            import time as _time

            import numpy as _np

            from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
                BitController,
                CodecTrainer,
                leaf_sizes,
            )
            from distributed_sigmoid_loss_tpu.train import (
                stage_codec,
                stage_scheme,
            )

            if update_mode == "full":
                # The wire carries the dp reduce-scattered 1/W shard per
                # tensor, so the controller's payload tables (its bandwidth
                # arithmetic) must be sized to the shard, not the tensor.
                from distributed_sigmoid_loss_tpu.parallel.mesh import (
                    data_axis as _dax,
                )
                from distributed_sigmoid_loss_tpu.parallel.update_shard import (
                    shard_leaf_sizes,
                )

                controller_sizes = shard_leaf_sizes(
                    state.params, dict(mesh.shape)[_dax]
                )
            else:
                controller_sizes = leaf_sizes(state.params)
            learned_mode = args.grad_compression == "learned"
            n_dcn = dict(mesh.shape)["dcn"]
            controller = BitController(
                controller_sizes,
                n_dcn=n_dcn,
                topk_frac=args.topk_frac,
                dcn_budget_mbps=args.dcn_budget_mbps,
                controller=args.controller or "greedy",
                learned=learned_mode,
            )
            codec_trainer = CodecTrainer() if learned_mode else None
            emulator = None
            bf16_ref_dt = None
            if args.emu_dcn_mbps is not None:
                from distributed_sigmoid_loss_tpu.parallel.dcn_emu import (
                    DCNEmulator,
                )

                emulator = DCNEmulator(args.emu_dcn_mbps).start()
                _atexit.register(emulator.close)
                # The fixed-bf16 reference payload the wall-clock ratio
                # compares against: the same (n_dcn-1)-hop egress at 2
                # bytes/param, measured through the SAME pipe so the ratio is
                # wire time vs wire time, not model vs measurement.
                bf16_ref_bytes = (n_dcn - 1) * 2 * int(sum(controller_sizes))
            def step_fn(st, batch):
                nonlocal bf16_ref_dt
                st = stage_scheme(st, controller.scheme, mesh)
                t0 = _time.perf_counter()
                st, metrics = jit_step(st, batch)
                wire = float(metrics["dcn_wire_bytes"])  # blocks on the step
                step_dt = _time.perf_counter() - t0
                metrics = dict(metrics)
                if emulator is None:
                    controller.observe(step_dt, wire)
                else:
                    transfer_dt = emulator.transfer(wire)
                    controller.observe(transfer_dt, wire)
                    # Re-measure the bf16 reference occasionally (every
                    # transfer for the first few, then EWMA holds) so the
                    # ratio tracks the live pipe, not a stale calibration.
                    if bf16_ref_dt is None or emulator.transfers <= 8:
                        ref = emulator.transfer(bf16_ref_bytes)
                        bf16_ref_dt = ref if bf16_ref_dt is None else (
                            0.5 * ref + 0.5 * bf16_ref_dt
                        )
                    metrics["dcn_measured_mbps"] = (
                        emulator.measured_mbps or 0.0
                    )
                    metrics["wire_savings_wallclock_ratio"] = (
                        (step_dt + bf16_ref_dt) / (step_dt + transfer_dt)
                    )
                controller.decide(
                    _np.asarray(st.comp["ef_ratio"]),
                    gnorm=_np.asarray(st.comp["gnorm"]),
                    gvar=_np.asarray(st.comp["gvar"]),
                )
                if codec_trainer is not None:
                    # Host-side codec training from the step's block second
                    # moments; staging new codec weights is a value change of
                    # a replicated operand — never a recompile.
                    new_codec = codec_trainer.update(
                        _np.asarray(st.comp["blockmoment"])
                    )
                    if codec_trainer.rounds >= codec_trainer.warmup_rounds:
                        st = stage_codec(st, new_codec, mesh)
                metrics["dcn_bw_est_mbps"] = controller.bw_est_mbps or 0.0
                metrics["controller_mode"] = controller.mode
                metrics["error_budget"] = float(controller.last_error_budget)
                return st, metrics
    else:
        # --loss-impl chunked is an all_gather memory shape; an unset --variant
        # follows it (same convention as --grad-compression selecting
        # all_gather) — an EXPLICIT ring was already refused above.
        variant = args.variant or (
            "all_gather" if args.loss_impl == "chunked" else "ring"
        )
        step_fn, shardings = make_train_step(
            model,
            mesh,
            LossConfig(variant=variant,
                       family=args.loss_family, precision="default",
                       loss_impl=args.loss_impl,
                       ring_overlap=args.ring_overlap,
                       use_pallas=args.use_pallas),
            accum_steps=args.accum,
            accum_negatives=args.accum_negatives,
            accum_dtype="bfloat16" if args.accum_bf16 else None,
            gradcache_embed_dtype=gradcache_dt,
            update_sharding=update_mode,
            ema_decay=args.ema_decay,
            moe_aux_weight=moe_aux_w,
            pp_microbatches=pp_micro,
        )
        jit_step = step_fn

    # graftscope wiring: schema-validated metrics lines, host spans (the
    # process's one recorder, which has the start-up spans already; without
    # --obs-dir it is disabled before the loop — disabled spans are the
    # allocation-free no-op), the health watchdog, and the always-on flight
    # recorder.
    from distributed_sigmoid_loss_tpu.obs import (
        FlightRecorder,
        HealthWatchdog,
    )
    from distributed_sigmoid_loss_tpu.obs.spans import RECORDER as spans
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        HEALTH_EVENT_FIELDS,
        TRAIN_METRICS_FIELDS,
        TRAIN_METRICS_PREFIXES,
    )

    logger = MetricsLogger(
        every=args.log_every,
        schema=TRAIN_METRICS_FIELDS,
        schema_prefixes=TRAIN_METRICS_PREFIXES,
    )
    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
    flight = FlightRecorder(
        path=os.path.join(args.obs_dir, "flight.json") if args.obs_dir
        else None
    )
    watchdog = (
        None if args.watchdog == "off"
        else HealthWatchdog(policy="warn" if args.watchdog == "warn" else "skip")
    )

    # Static attribution of THE step that will run (obs/attribution.py):
    # trace-only — seconds, no compile — so every metrics line carries the
    # step's comm_bytes_total, plus mfu_est when the run's device is a chip
    # the peaks table lists (a CPU run carries no utilization figure).
    from distributed_sigmoid_loss_tpu.obs.attribution import (
        metrics_line_fields,
        static_attribution,
    )

    abstract_batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), first
    )
    att_fields = metrics_line_fields(
        static_attribution(jit_step, state, abstract_batch),
        device_kind=dev0.device_kind,
    )
    print(
        "obs attribution: "
        + " ".join(f"{k}={v}" for k, v in sorted(att_fields.items())),
        file=sys.stderr,
    )

    # graftshard placement fields on every metrics line: the mode plus the
    # measured at-rest optimizer bytes per replica (compiler accounting) —
    # so a training-run JSONL alone shows the W× shard saving.
    upd_fields = {}
    if update_mode != "off":
        from distributed_sigmoid_loss_tpu.parallel.update_shard import (
            opt_mem_bytes_per_replica,
        )

        upd_fields["update_sharding"] = update_mode
        _opt_mem = opt_mem_bytes_per_replica(state.opt_state)
        if _opt_mem is not None:
            upd_fields["opt_mem_bytes_per_replica"] = _opt_mem

    # Striped-shard sources already yield this host's LOCAL rows (batch/pcnt
    # each); synthetic sources yield the same deterministic GLOBAL batch on
    # every host, which place() slices process-wise.
    rows_are_local = pcnt > 1 and bool(args.data_shards)

    # The batch dim's mesh axes: ("dcn", dp) under --dcn-slices (the
    # compressed step shards rows over BOTH; P("dp") alone would declare the
    # dp blocks replicated over dcn and mis-assemble multi-host stripes).
    from distributed_sigmoid_loss_tpu.parallel.mesh import data_axis as _da

    batch_axes = ("dcn", _da) if args.dcn_slices > 1 else _da

    def place_global(b):
        # Reference-style full-batch-then-slice (test_distributed_sigmoid_loss.py:
        # 57-68): every host holds the same global batch and contributes the
        # process-order slice its own devices hold.
        if pcnt == 1:
            return jax.device_put(b, shardings)
        import numpy as np

        local = jax.tree.map(
            lambda x: np.asarray(x).reshape(
                pcnt, x.shape[0] // pcnt, *x.shape[1:]
            )[pidx],
            b,
        )
        return global_batch_from_local(local, mesh, axis_name=batch_axes)

    def place(b):
        if pcnt > 1 and rows_are_local:
            return global_batch_from_local(b, mesh, axis_name=batch_axes)
        return place_global(b)

    def host_batches(skip: int = 0):
        # The synthetic pipeline is deterministic per position: on resume, skip
        # the batches the checkpointed steps already consumed so the resumed run
        # sees the same stream an uninterrupted run would.
        if skip == 0:
            yield first
        for i, b in enumerate(data, start=1):
            if i >= skip:
                yield b

    # Device feeding goes through data.prefetch: a worker thread keeps host
    # fetch + decode + host->device commit one batch ahead of the step, and
    # the stats object turns device starvation into a NUMBER — every train
    # log line carries input_wait_frac (~0 = the host keeps up; positive =
    # the fraction of wall time the device sat waiting on input).
    from distributed_sigmoid_loss_tpu.data import PrefetchStats, prefetch as _prefetch

    input_stats = PrefetchStats()

    def place_spanned(b):
        # h2d-commit runs on the prefetch worker thread; the span lands on
        # its own track of the host timeline (SpanRecorder is thread-safe).
        with spans.span("h2d_commit"):
            return place(b)

    def device_batches(skip: int = 0):
        return _prefetch(
            host_batches(skip), mesh, size=2,
            put=lambda b, m, a: place_spanned(b), stats=input_stats,
        )

    # Soak-run telemetry: under --obs-dir the latest metrics
    # line is ALSO mirrored into DIR/telemetry.json via atomic rename each
    # log interval — tail the run's live state without parsing (or racing)
    # the metrics log stream.
    telemetry_env = None
    if args.obs_dir:
        from distributed_sigmoid_loss_tpu.obs.telemetry import (
            environment_fingerprint,
        )

        telemetry_env = environment_fingerprint()

    def write_telemetry(step_i, line):
        if not args.obs_dir or step_i % args.log_every:
            return
        import time as _time

        from distributed_sigmoid_loss_tpu.obs.telemetry import (
            write_telemetry_file,
        )

        try:
            write_telemetry_file(
                os.path.join(args.obs_dir, "telemetry.json"),
                {"step": step_i, "ts": round(_time.time(), 3),
                 "metrics": line, "env": telemetry_env},
            )
        except OSError as e:  # telemetry must never kill a training run
            print(f"WARNING: telemetry write failed: {e}", file=sys.stderr)

    def log_metrics(step_i, m):
        # Most metrics are device scalars; compression_scheme_hist is a small
        # per-scheme count vector — serialized as a list so the JSONL line
        # stays one self-describing record.
        def as_jsonable(v):
            try:
                return float(v)
            except TypeError:
                return [float(x) for x in v]

        line = {
            **{k: as_jsonable(v) for k, v in m.items()},
            "input_wait_frac": input_stats.input_wait_frac(),
            **att_fields,
            **upd_fields,
        }
        if watchdog is not None:
            for ev in watchdog.observe(step_i, line):
                flight.note_event(ev)
                logger.write(ev.record(), schema=HEALTH_EVENT_FIELDS)
        flight.note_metrics(step_i, line)
        logger.log(step_i, line)
        write_telemetry(step_i, line)
        if loop_t0:  # the first step has ended: where the time before it went
            from distributed_sigmoid_loss_tpu.obs.attribution import mixed_stack, mixed_stack_line
            from distributed_sigmoid_loss_tpu.obs.spans import startup_line

            print(startup_line(spans.spans(), loop_t0.pop()), file=sys.stderr)
            if (stack := mixed_stack_line(mixed_stack(jit_step))) is not None:
                print(stack, file=sys.stderr)  # a mixed stack: which cores the trace took

    from distributed_sigmoid_loss_tpu.parallel.mesh import trace_on

    def embed(params, images, tokens):
        # Traced on the mesh: on several chips the towers' fused attention
        # kernels must know how their operands are sharded.
        with trace_on(mesh):
            return model.apply({"params": params}, images, tokens)[:2]

    eval_hook = None
    if args.eval_every:
        from distributed_sigmoid_loss_tpu.eval import retrieval_metrics as _rm

        # ONE fixed batch for every in-training eval: the curve then measures
        # the model, not data drift. It must NOT be drawn from the live
        # training iterator: that would shift every subsequent stream
        # position, so a resume with a different --eval-every would silently
        # train on a different stream than the original run (breaking
        # device_batches' skip arithmetic). Synthetic runs get a genuinely
        # held-out source (shifted seeds); file/native streams use the
        # --eval-data holdout when given and otherwise fall back to the
        # already-drawn position-0 batch (disclosed: that curve partially
        # measures train-set fit).
        if args.eval_data:
            try:
                # A too-small holdout surfaces as a loader ValueError — at
                # construction for the directory source, at first draw for
                # shards: usage error, not a traceback. place_global stays
                # OUTSIDE the try — its sharding errors are batch/topology
                # mistakes, not --eval-data's fault.
                holdout = _eval_holdout_source(
                    args, cfg,
                    tokenize or _byte_tokenize_for(cfg, args.tokenizer),
                    native_decode=native_decode,
                )
                eval_first = next(iter(holdout))
            except ValueError as e:
                print(f"--eval-data: {e}", file=sys.stderr)
                return 2
            eval_batch = place_global(eval_first)
        elif isinstance(source, SyntheticImageText):
            eval_batch = place(
                next(iter(SyntheticImageText(
                    cfg, args.batch, image_seed=43, text_seed=41
                )))
            )
        else:
            print(
                "--eval-every without --eval-data on a file/native stream: "
                "the fixed eval batch is the position-0 TRAINING batch, so "
                "the curve partially measures train-set fit — pass "
                "--eval-data with held-out shards or a directory for a true "
                "validation curve",
                file=sys.stderr,
            )
            eval_batch = place(first)
        # Jitted once: the hook runs repeatedly inside the train loop, where
        # an eager per-op forward would dominate wall time on real models.
        eval_fwd = jax.jit(embed)

        def eval_hook(step_i, st):
            zi, zt = eval_fwd(
                st.params, eval_batch["images"], eval_batch["tokens"]
            )
            rm = _rm(zi, zt, mesh=mesh, ks=(1, 5))
            # force: eval steps are out-of-band of --log-every (and must not
            # touch the steps/sec clock).
            logger.log(
                step_i, {f"eval/{k}": float(v) for k, v in rm.items()},
                force=True,
            )

    if args.ckpt_dir and args.tokenizer:
        # Stash the vocab with the checkpoints: eval auto-loads it, so restored
        # models never silently tokenize with a different vocab than training.
        import shutil

        os.makedirs(args.ckpt_dir, exist_ok=True)
        stash = os.path.join(args.ckpt_dir, "tokenizer.json")
        if os.path.abspath(args.tokenizer) != os.path.abspath(stash):
            shutil.copyfile(args.tokenizer, stash)
    # The loop's span sites (`step`, `eval`, `h2d_commit`, the resilient loop's)
    # record only under --obs-dir; the start-up spans stay in the ring either way.
    spans.enabled = bool(args.obs_dir)
    import time as _time

    loop_t0 = [_time.perf_counter()]  # taken by the first metrics line
    if args.ckpt_dir:
        # Preemption-safe resilient loop: resumes from the newest checkpoint in
        # --ckpt-dir, saves every --ckpt-every steps and on SIGTERM, rolls back
        # on a non-finite loss.
        skip = latest_step(args.ckpt_dir) or 0
        import contextlib

        from distributed_sigmoid_loss_tpu.train import AsyncSaver

        saver_ctx = AsyncSaver() if args.async_checkpoint else contextlib.nullcontext()
        stream = device_batches(skip)
        with PreemptionGuard() as guard, saver_ctx as saver:
            try:
                state, report = train_resilient(
                    state,
                    step_fn,
                    stream,
                    total_steps=args.steps,
                    ckpt_dir=args.ckpt_dir,
                    ckpt_every=args.ckpt_every,
                    guard=guard,
                    saver=saver,
                    # The state was built with zeros=True on the promise that
                    # train_resilient's restore overwrites it; if the
                    # checkpoint vanished between latest_step() and restore,
                    # refuse (BEFORE any step runs) to train from all-zero
                    # params and overwrite --ckpt-dir with garbage.
                    require_restore=resuming,
                    on_metrics=log_metrics,
                    eval_every=args.eval_every,
                    on_eval=eval_hook,
                    # --watchdog skip routes a non-finite loss into the
                    # rollback-and-skip path instead of the halting raise;
                    # either way the flight recorder dumps the trajectory.
                    on_divergence="skip" if args.watchdog == "skip" else "halt",
                    spans=spans,
                    flight=flight,
                )
            except RestoreRequiredError as e:
                print(f"--ckpt-dir {args.ckpt_dir}: {e}", file=sys.stderr)
                return 1
            finally:
                # Join the prefetch worker BEFORE anything else reads `data`:
                # after close the source iterator has no concurrent reader.
                stream.close()
        print(
            f"resilient loop: steps {report.start_step}->{report.final_step}, "
            f"checkpoints at {report.checkpoints}"
            + (" (preempted)" if report.preempted else ""),
            file=sys.stderr,
        )
    else:
        # 1-based step numbers, matching train_resilient's on_metrics contract.
        stream = device_batches()
        i = 0  # the crash dump below must name a step even if fetch 1 dies
        try:
            for i, batch in zip(range(1, args.steps + 1), stream):
                with spans.span("step"):
                    state, metrics = step_fn(state, batch)
                log_metrics(i, metrics)
                if eval_hook is not None and i % args.eval_every == 0:
                    with spans.span("eval"):
                        eval_hook(i, state)
        except BaseException as e:
            # Same black-box contract as the resilient loop: a crash leaves
            # the last-N trajectory behind, not just a traceback.
            flight.dump(f"crash at step {i}: {type(e).__name__}: {e}")
            raise
        finally:
            stream.close()  # joins the worker; `data` is single-reader again

    if args.obs_dir:
        spans_path = os.path.join(args.obs_dir, "host_spans.trace.json")
        spans.export(spans_path)
        print(f"obs: host spans -> {spans_path} "
              f"({len(spans.spans())} spans retained; summarize with "
              f"`python -m distributed_sigmoid_loss_tpu obs summarize "
              f"{args.obs_dir}`)", file=sys.stderr)

    # Zero-shot retrieval on a held-out synthetic batch (the model normalizes
    # its embeddings already).
    from distributed_sigmoid_loss_tpu.eval import retrieval_metrics

    held_out = place(next(iter(data)))
    # Jitted: an eager forward is one tiny compile per op of both full-width
    # towers — minutes on a cold machine for a closing sanity print.
    zimg, ztxt = jax.jit(embed)(
        state.params, held_out["images"], held_out["tokens"]
    )
    rm = retrieval_metrics(zimg, ztxt, mesh=mesh, ks=(1, 5))
    print({k: round(float(v), 4) for k, v in rm.items()}, file=sys.stderr)
    return 0


def cmd_eval(args) -> int:
    _bootstrap_devices(args)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sigmoid_loss_tpu.data import SyntheticImageText, put_batch
    from distributed_sigmoid_loss_tpu.eval import (
        retrieval_metrics,
        zeroshot_metrics,
    )
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import init_params

    if args.ema and not args.ckpt_dir:
        print(
            "--ema requires --ckpt-dir (EMA weights live in a train checkpoint; "
            "a fresh model has none)",
            file=sys.stderr,
        )
        return 2
    cfg = _model_config(args)
    if args.ckpt_dir:
        # Use the vocab stashed by `train --tokenizer` unless the user overrode
        # it — silently tokenizing with a different vocab than training makes
        # the metrics garbage with no error.
        stashed = os.path.join(args.ckpt_dir, "tokenizer.json")
        if os.path.exists(stashed):
            if not args.tokenizer:
                args.tokenizer = stashed
                print(f"using checkpoint tokenizer {stashed}", file=sys.stderr)
            elif os.path.abspath(args.tokenizer) != os.path.abspath(stashed):
                import json as jsonmod

                with open(args.tokenizer) as f1, open(stashed) as f2:
                    if jsonmod.load(f1) != jsonmod.load(f2):
                        print(
                            f"WARNING: --tokenizer {args.tokenizer} differs "
                            f"from the checkpoint's stashed vocab {stashed}; "
                            "token ids will not match training",
                            file=sys.stderr,
                        )
    mesh = make_mesh()
    model = SigLIP(cfg)

    captions = None
    if args.data_dir and args.data_shards:
        print("--data-dir and --data-shards are mutually exclusive",
              file=sys.stderr)
        return 2
    if args.data_dir or args.data_shards:
        # Real pairs through the SAME loaders train uses; captions ride along
        # as the zero-shot class names (see below).
        from distributed_sigmoid_loss_tpu.data import (
            ImageTextFolder,
            ImageTextShards,
        )

        tokenize = _byte_tokenize_for(cfg, args.tokenizer)
        if args.data_dir:
            source = ImageTextFolder(
                args.data_dir, cfg, args.batch, tokenize, keep_captions=True
            )
        else:
            import glob as globmod

            shards = globmod.glob(args.data_shards)
            if not shards:
                print(f"--data-shards matched nothing: {args.data_shards!r}",
                      file=sys.stderr)
                return 2
            source = ImageTextShards(
                shards, cfg, args.batch, tokenize, keep_captions=True
            )
        batch = next(iter(source))
        captions = batch.pop("captions")
    else:
        batch = next(
            iter(SyntheticImageText(cfg, args.batch, image_seed=7, text_seed=9))
        )
    if args.ckpt_dir:
        # Train writes step-numbered checkpoints of the FULL train state; restore
        # the newest one into a matching structure (optimizer slots are needed
        # only as the restore target) and keep the params. Checkpoints written
        # with --ema-decay carry an extra `ema` subtree — the restore target must
        # match, so retry with an EMA-shaped state when the bare one mismatches.
        from distributed_sigmoid_loss_tpu.train import (
            create_train_state,
            make_optimizer,
            restore_latest,
        )
        from distributed_sigmoid_loss_tpu.utils.config import TrainConfig

        # The restore target's opt_state tree must match the checkpoint's
        # optimizer family — lion has one momentum slot, adafactor factored
        # moments (orbax restore is structure-strict).
        tx = make_optimizer(TrainConfig(optimizer=args.optimizer))
        # zeros=True: the state is only a restore TARGET (structure + shapes +
        # shardings); running the real random init here costs minutes of host
        # RNG on b16-class towers before the checkpoint overwrites every leaf.
        state = create_train_state(
            jax.random.key(0), model, tx, batch, mesh, ema=args.ema, zeros=True
        )
        try:
            restored = restore_latest(args.ckpt_dir, state)
        except Exception as first_err:
            # The checkpoint's EMA-shapedness may differ from the request; retry
            # with the other target shape. If that fails too, the problem is NOT
            # EMA (wrong --model, corrupt checkpoint, ...) — surface the
            # ORIGINAL error rather than guessing from message text.
            try:
                alt = create_train_state(
                    jax.random.key(0), model, tx, batch, mesh,
                    ema=not args.ema, zeros=True,
                )
                restored = restore_latest(args.ckpt_dir, alt)
            except Exception:
                raise first_err
            if args.ema:
                # The bare-shaped retry succeeded: the checkpoint has no EMA.
                print(
                    f"--ema requested but the checkpoint at {args.ckpt_dir} has "
                    f"no EMA weights (train with --ema-decay)",
                    file=sys.stderr,
                )
                return 2
        if restored is None:
            print(f"no checkpoint found under {args.ckpt_dir}", file=sys.stderr)
            return 2
        state, step = restored
        which = "ema" if args.ema else "params"
        print(f"restored step {step} ({which}) from {args.ckpt_dir}", file=sys.stderr)
        params = state.ema if args.ema else state.params
    else:
        # Forward-only eval of a fresh model: params only, no optimizer slots.
        params = init_params(jax.random.key(0), model, batch, mesh)

    batch = put_batch(batch, mesh)
    zimg, ztxt, _ = model.apply({"params": params}, batch["images"], batch["tokens"])
    out = {
        k: round(float(v), 4)
        for k, v in retrieval_metrics(zimg, ztxt, mesh=mesh, ks=(1, 5)).items()
    }

    # Zero-shot classification demo: class prompts through the byte tokenizer and
    # text tower -> prompt-ensembled classifier; synthetic integer labels.
    from functools import partial

    from distributed_sigmoid_loss_tpu.eval import build_classifier

    tokenize = _byte_tokenize_for(cfg, args.tokenizer)
    if captions is not None:
        # Real data: the batch's distinct captions ARE the label space — each
        # image's true class is its own caption (caption-matching zero-shot, the
        # standard retrieval-as-classification eval when no label set exists).
        class_names = sorted(set(captions))
        n_classes = len(class_names)
        class_index = {c: i for i, c in enumerate(class_names)}
        label_values = np.asarray([class_index[c] for c in captions], np.int32)
    else:
        n_classes = args.classes
        class_names = [f"c{c}" for c in range(n_classes)]
        # Class name first: short context lengths (tiny config: 8 tokens) would
        # truncate a trailing class name out of every prompt, collapsing all
        # classes onto identical token rows.
        rng = np.random.default_rng(0)
        label_values = rng.integers(0, n_classes, zimg.shape[0]).astype(np.int32)

    classifier = build_classifier(
        partial(model.apply, {"params": params}, method=SigLIP.encode_text),
        class_names,
        tokenize,
        cfg.text.context_length,
        templates=("{} photo.", "{} image."),
    )
    labels = put_batch(jnp.asarray(label_values), mesh)
    ks = tuple(k for k in (1, 5) if k <= n_classes)
    zs = zeroshot_metrics(zimg, classifier, labels, mesh=mesh, ks=ks)
    out.update({f"zeroshot_{k}": round(float(v), 4) for k, v in zs.items()})
    print(out)
    return 0


def cmd_export(args) -> int:
    """AOT-export a lowered step (train or forward) to a StableHLO artifact.

    The artifact replays with ``jax.export.deserialize(...).call(...)`` on a
    matching device topology — no model code needed at load time. ``--check``
    reloads the written file and replays one step against the live jitted step.
    """
    _bootstrap_devices(args)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_sigmoid_loss_tpu.data import SyntheticImageText
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        export_step,
        load_exported,
        make_optimizer,
        make_train_step,
        save_exported,
    )
    from distributed_sigmoid_loss_tpu.utils.config import LossConfig, TrainConfig

    if args.quant and args.what == "train_step":
        print(
            "--quant is inference-only (zero gradients through round); "
            "use it with --what forward",
            file=sys.stderr,
        )
        return 2
    cfg = _model_config(args)
    if args.loss_family != "sigmoid":
        import dataclasses

        # Same family wiring as train: the model's t_prime init follows it.
        cfg = dataclasses.replace(cfg, loss=LossConfig(family=args.loss_family))
    model = SigLIP(cfg)
    n_dev = len(jax.devices())
    if args.what == "forward" and args.ep > 1:
        # The forward export takes freshly-init'd (unsharded) params and never
        # touches the mesh; silently accepting --ep would emit a 1-device
        # program while the flags promise an expert-parallel one.
        print("--ep applies to --what train_step only (the forward export is "
              "a single-device inference program)", file=sys.stderr)
        return 2
    mesh, mesh_err = _make_training_mesh(args)  # same topology rules as train
    if mesh_err:
        print(mesh_err, file=sys.stderr)
        return 2

    b = args.batch
    batch = next(iter(SyntheticImageText(cfg, b)))

    if args.what == "train_step":
        # The schedule + aux weight are baked into the artifact — export the
        # values the deployed job will actually train with (--lr etc.).
        tx = make_optimizer(
            TrainConfig(
                learning_rate=args.lr,
                warmup_steps=args.warmup_steps,
                total_steps=args.total_steps,
            )
        )
        state = create_train_state(jax.random.key(0), model, tx, batch, mesh)
        moe_aux = args.moe_aux_weight if args.moe_experts else None
        step, shardings = make_train_step(
            model, mesh,
            LossConfig(variant=args.variant, family=args.loss_family),
            moe_aux_weight=moe_aux,
        )
        batch = jax.device_put(batch, shardings)
        example = (state, batch)
        fn = step
    else:  # forward
        from flax import linen as nn

        params = nn.meta.unbox(
            model.init(jax.random.key(0), batch["images"], batch["tokens"])[
                "params"
            ]
        )

        def fn(params, images, tokens):
            zimg, ztxt, _ = model.apply({"params": params}, images, tokens)
            return zimg, ztxt

        example = (params, batch["images"], batch["tokens"])

    platforms = (args.platform,) if args.platform else None
    exported = export_step(fn, example, platforms=platforms)
    save_exported(args.out, exported)
    size = os.path.getsize(args.out)
    model_name = "tiny" if args.tiny else args.model
    print(
        f"exported {args.what} ({model_name}, batch {b}, {n_dev} device(s)) "
        f"-> {args.out} ({size} bytes)"
    )

    if args.check:
        if args.platform and args.platform != jax.default_backend():
            print(
                f"--check skipped: artifact targets {args.platform!r}, current "
                f"backend is {jax.default_backend()!r}",
                file=sys.stderr,
            )
            return 0
        loaded = load_exported(args.out)
        # Flat calling convention (see train/export.py); the live train step
        # donates its state argument, so replay the artifact on copies first.
        got = loaded.call(*jax.tree.leaves(jax.tree.map(jnp.copy, example)))
        want = fn(*example)
        want_leaves = jax.tree.leaves(want)
        assert len(want_leaves) == len(got)
        for w, g in zip(want_leaves, got):
            np.testing.assert_allclose(
                np.asarray(w), np.asarray(g), rtol=1e-5, atol=1e-6
            )
        print("check ok: reloaded artifact replays identically")
    return 0


def _emit_serve_record(record: dict, *, strict_zero_drops: bool = False) -> int:
    """The serve-bench emit contract (shared by the snapshot and scenario
    paths): validate against the declared record schema, warn on stderr,
    never lose the measurement. With
    ``strict_zero_drops`` a non-zero ``silent_drops`` count fails the run —
    the chaos scenarios' every-outcome-is-typed acceptance gate."""
    import json

    from distributed_sigmoid_loss_tpu.analysis.bench_schema import (
        validate_record,
    )

    problems = validate_record(record)
    if problems:
        print("WARNING: serve-bench record schema violation: "
              + "; ".join(problems), file=sys.stderr)
    print(json.dumps(record))
    if strict_zero_drops and record.get("silent_drops"):
        print(
            f"WARNING: {record['silent_drops']} silent drop(s) — a request "
            "ended with neither a result nor a typed rejection; the "
            "degradation contract is broken",
            file=sys.stderr,
        )
        return 1
    return 0


def cmd_serve_bench(args) -> int:
    """Drive the serve/ stack on synthetic data with concurrent clients and
    print the ``stats()`` snapshot as one JSON record.

    The operational proof of the serving layer: with warmed buckets the
    printed ``compile_count`` equals ``bucket_space`` (the number of shape
    buckets) — NOT the request count — while concurrent clients coalesce into
    batched engine calls (see ``batch_size_hist``) and repeated content hits
    the cache (``cache.hit_rate``).
    """
    _bootstrap_devices(args)
    import concurrent.futures
    import threading
    import time

    import numpy as np

    from distributed_sigmoid_loss_tpu.data import SyntheticImageText
    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.serve import (
        EmbeddingCache,
        EmbeddingService,
        InferenceEngine,
        QueueFullError,
        RequestTimeoutError,
        RetrievalRouter,
        SwapController,
    )
    from distributed_sigmoid_loss_tpu.utils.logging import MetricsLogger

    if args.requests < 1 or args.clients < 1:
        print("--requests and --clients must be >= 1", file=sys.stderr)
        return 2
    if args.swap_every < 0 or args.rerank_k < 0:
        print("--swap-every and --rerank-k must be >= 0", file=sys.stderr)
        return 2
    if args.index_tier == "sharded" and not args.mesh:
        print(
            "--index-tier sharded needs --mesh (the dp axis the corpus "
            "partitions over; pair with --cpu-devices N off-chip)",
            file=sys.stderr,
        )
        return 2
    try:
        buckets = tuple(int(b) for b in args.batch_buckets.split(","))
    except ValueError:
        print(f"--batch-buckets must be comma-separated ints, got "
              f"{args.batch_buckets!r}", file=sys.stderr)
        return 2

    if args.fleet_scenario and args.scenario:
        print("--fleet-scenario and --scenario are mutually exclusive (one "
              "drill per run)", file=sys.stderr)
        return 2
    if not args.fleet_scenario and (args.fleet_replicas or args.lease_ttl_s):
        print("--fleet-replicas/--lease-ttl-s only make sense with "
              "--fleet-scenario", file=sys.stderr)
        return 2
    if args.fleet_scenario and args.fleet_replicas and args.fleet_replicas < 2:
        print("--fleet-replicas must be >= 2 (with one replica there is no "
              "sibling to reroute to and no wave to order)", file=sys.stderr)
        return 2

    scenario_tenants = None
    if args.scenario or args.fleet_scenario:
        from distributed_sigmoid_loss_tpu.serve import parse_tenant_spec

        if args.duration_s <= 0 or args.offered_load <= 0 or args.capacity < 1:
            print("--duration-s/--offered-load must be > 0 and --capacity "
                  ">= 1", file=sys.stderr)
            return 2
        try:
            scenario_tenants = parse_tenant_spec(args.tenants)
        except ValueError as e:
            print(f"--tenants: {e}", file=sys.stderr)
            return 2

    if args.fleet_scenario:
        # Like the hostloss drill below: the fleet drill runs the leased
        # admission → router → EngineProcess stack with stdlib surrogate
        # workers, so it exercises the fleet-tier failure semantics (lease
        # reclaim, typed reroute, swap waves) without spinning up the
        # jitted stack. Over-admission is a hard failure: the split-brain
        # ceiling proof is only as good as its enforcement.
        from distributed_sigmoid_loss_tpu.serve import run_fleet_scenario

        record = run_fleet_scenario(
            args.fleet_scenario,
            replicas=args.fleet_replicas or 3,
            tenants=scenario_tenants,
            duration_s=args.duration_s,
            offered_load=args.offered_load,
            lease_ttl_s=args.lease_ttl_s or 0.5,
            seed=args.seed,
        )
        rc = _emit_serve_record(record, strict_zero_drops=True)
        if record.get("over_ceiling_samples"):
            print(
                f"WARNING: {record['over_ceiling_samples']} window sample(s) "
                "exceeded the global admission ceiling — the bounded-"
                "staleness lease invariant is broken",
                file=sys.stderr,
            )
            return 1
        return rc

    if args.scenario == "hostloss":
        # The host-loss drill runs the admission → batcher → EngineProcess
        # stack with the stdlib surrogate worker: it drills the SERVING
        # failure semantics (kill -9 mid-traffic, typed HostLostError to
        # every in-flight caller, measured recovery), not the model forward
        # — so it runs before the jitted stack spins up and the drill's
        # child process never imports jax.
        from distributed_sigmoid_loss_tpu.serve import hostloss_drill

        record = hostloss_drill(
            tenants=scenario_tenants,
            duration_s=args.duration_s,
            offered_load=args.offered_load,
            capacity=args.capacity,
            seed=args.seed,
        )
        return _emit_serve_record(record, strict_zero_drops=True)

    import jax
    from flax import linen as nn

    cfg = _model_config(args)
    model = SigLIP(cfg)
    mesh = None
    if args.mesh:
        from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()
        n_dev = len(jax.devices())
        if any(b % n_dev for b in buckets):
            print(
                f"--mesh: batch buckets {buckets} must all divide the device "
                f"count {n_dev} (every device holds whole rows)",
                file=sys.stderr,
            )
            return 2

    pool = max(args.pool, 1)
    source = iter(SyntheticImageText(cfg, pool, image_seed=args.seed + 1,
                                     text_seed=args.seed + 2))
    batch = next(source)
    pool_tokens = np.asarray(batch["tokens"])
    pool_images = np.asarray(batch["images"])

    params = nn.meta.unbox(
        model.init(jax.random.key(args.seed), pool_images[:1],
                   pool_tokens[:1])["params"]
    )
    engine = InferenceEngine.from_model(
        model, params, batch_buckets=buckets, mesh=mesh
    )
    t0 = time.perf_counter()
    warmed = engine.warmup()
    warmup_s = time.perf_counter() - t0
    print(
        f"warmed {warmed} shape buckets in {warmup_s:.1f}s "
        f"({args.model} model, {len(buckets)} batch buckets)",
        file=sys.stderr,
    )

    # Corpus embeddings straight through the engine (the service clock should
    # measure client traffic, not index build); chunked to the largest bucket.
    step = buckets[-1]
    corpus_rows = [
        engine.encode_image(pool_images[i : i + step])
        for i in range(0, min(args.index_size, pool), step)
    ]
    corpus_emb = np.concatenate(corpus_rows)
    router = RetrievalRouter(
        tier=args.index_tier,
        mesh=mesh if args.index_tier == "sharded" else None,
        rerank_k=args.rerank_k or None,
    )
    router.publish(corpus_emb)
    if args.index_tier == "sharded":
        # Warm the fan-out program off the clock — same discipline as the
        # engine's bucket warmup (the shard_map compiles once per query
        # bucket; client searches are single-query).
        router.search(corpus_emb[:1], k=args.topk)

    admission = None
    if args.scenario:
        from distributed_sigmoid_loss_tpu.serve import AdmissionController

        admission = AdmissionController(
            scenario_tenants, capacity=args.capacity
        )
    service = EmbeddingService(
        engine,
        cache=EmbeddingCache(args.cache_size),
        index=router,
        max_wait_ms=args.max_wait_ms,
        max_queue=args.max_queue,
        default_timeout=60.0,
        logger=MetricsLogger(),
        admission=admission,
    )
    if args.metrics_port >= 0:
        # Live pull-based telemetry DURING the bench: the OpenMetrics-style
        # /metrics endpoint (obs/telemetry.py) on a stdlib HTTP thread —
        # scrape it mid-run instead of waiting for the final JSON record.
        exporter = service.start_metrics_server(port=args.metrics_port)
        print(f"serve-bench: live /metrics at {exporter.url}",
              file=sys.stderr)

    if args.scenario:
        # Scenario soak: graftsiege's generator replaces the fixed-request
        # client loop — open-loop offered load shaped per scenario, real
        # engine underneath, admission at the front door. The degradation
        # record (p99 vs offered load, per-tenant shed_rate, recovery_time_s,
        # silent_drops) merges with the stats() snapshot; any silent drop
        # fails the run.
        from distributed_sigmoid_loss_tpu.serve import run_scenario

        swap_fn = None
        if args.scenario == "swapstorm":
            storm_controller = SwapController(engine, router)

            def swap_fn() -> None:
                storm_controller.swap(params=params, embeddings=corpus_emb)

        def submit(tenant: str, i: int, *, items: int = 1,
                   fresh: bool = False) -> None:
            if fresh:
                # Deterministic per-i cache-hostile row: always misses the
                # cache, so every admit reaches the batcher/engine.
                rng = np.random.default_rng(args.seed * 100003 + i)
                row = rng.integers(0, cfg.text.vocab_size,
                                   cfg.text.context_length, dtype=np.int32)
                service.encode_text(row, tenant=tenant, timeout=5.0)
            elif items > 1:
                rows = np.stack(
                    [pool_tokens[(i + j) % pool] for j in range(items)]
                )
                service.encode_text(rows, tenant=tenant, timeout=5.0)
            else:
                service.encode_text(pool_tokens[i % pool], tenant=tenant,
                                    timeout=5.0)

        scen = run_scenario(
            args.scenario,
            submit=submit,
            tenants=scenario_tenants,
            admission=admission,
            duration_s=args.duration_s,
            offered_load=args.offered_load,
            clients_per_tenant=args.clients,
            swap_fn=swap_fn,
            seed=args.seed,
        )
        snap = service.stats()
        service.close()
        record = {
            "model": args.model,
            "clients": args.clients,
            "batch_buckets": list(buckets),
            "max_wait_ms": args.max_wait_ms,
            "sharded": bool(mesh),
            "index_tier": args.index_tier,
            "swap_every": args.swap_every,
            "warmup_s": round(warmup_s, 2),
            **snap,
            **scen,
        }
        rc = _emit_serve_record(record, strict_zero_drops=True)
        # The steady-state compile gate holds under chaos too: shedding and
        # swap churn must not push any request off the warmed bucket grid.
        if snap["compile_count"] != warmed:
            print(
                f"WARNING: compile_count {snap['compile_count']} != warmed "
                f"buckets {warmed} — a request triggered a fresh compile",
                file=sys.stderr,
            )
            return 1
        return rc

    # --swap-every N churn: a swapper thread republishes the weights and
    # freshly built index segments after every N completed client ops —
    # the zero-downtime/zero-recompile contract exercised UNDER the same
    # traffic the bench measures (swap_count / swap_latency_ms land in the
    # record; the compile_count gate below still applies).
    ops_done = [0]
    swap_done = threading.Event()
    swap_thread = None
    if args.swap_every:
        controller = SwapController(engine, router)

        def swapper():
            next_at = args.swap_every
            while not swap_done.is_set():
                if ops_done[0] >= next_at:
                    controller.swap(params=params, embeddings=corpus_emb)
                    next_at += args.swap_every
                else:
                    swap_done.wait(0.002)

        swap_thread = threading.Thread(
            target=swapper, name="serve-bench-swapper", daemon=True
        )
        swap_thread.start()

    def client(cid: int, n_ops: int) -> None:
        rng = np.random.default_rng(args.seed * 1000 + cid)
        for _ in range(n_ops):
            op = rng.random()
            try:
                if op < 0.2:  # image encode from the shared pool (cacheable)
                    service.encode_image(pool_images[rng.integers(pool)])
                elif op < 0.4:  # retrieval query
                    service.search(pool_tokens[rng.integers(pool)], k=args.topk)
                elif op < 0.7:  # repeated text from the pool (cacheable)
                    service.encode_text(pool_tokens[rng.integers(pool)])
                else:  # fresh text (guaranteed cache miss → batcher/engine)
                    row = rng.integers(
                        0, cfg.text.vocab_size,
                        cfg.text.context_length, dtype=np.int32,
                    )
                    service.encode_text(row)
            except (QueueFullError, RequestTimeoutError):
                pass  # shed/missed requests are counted in service.stats()
            ops_done[0] += 1

    per_client = [args.requests // args.clients] * args.clients
    for i in range(args.requests % args.clients):
        per_client[i] += 1
    with concurrent.futures.ThreadPoolExecutor(args.clients) as pool_ex:
        list(pool_ex.map(client, range(args.clients), per_client))
    if swap_thread is not None:
        swap_done.set()
        swap_thread.join(timeout=60)

    snap = service.stats()
    service.close()
    record = {
        "metric": "serve_bench",
        "value": snap["qps"],
        "unit": "req/s",
        "model": args.model,
        "clients": args.clients,
        "requests_sent": args.requests,
        "batch_buckets": list(buckets),
        "max_wait_ms": args.max_wait_ms,
        "sharded": bool(mesh),
        "index_tier": args.index_tier,
        "swap_every": args.swap_every,
        "warmup_s": round(warmup_s, 2),
        **snap,
    }
    rc = _emit_serve_record(record)
    # Steady-state contract: every compile happened at warmup — one per shape
    # bucket. A violation means a request escaped the bucket grid.
    if snap["compile_count"] != warmed:
        print(
            f"WARNING: compile_count {snap['compile_count']} != warmed "
            f"buckets {warmed} — a request triggered a fresh compile",
            file=sys.stderr,
        )
        return 1
    return rc


def cmd_data_bench(args) -> int:
    """Run the input-pipeline stage bench (data/data_bench.py)."""
    _bootstrap_devices(args)
    from distributed_sigmoid_loss_tpu.data.data_bench import run_data_bench

    return run_data_bench(args)


def _load_host_spans(root: str):
    """(paths, spans, counters) of every host_spans.trace.json under ``root``
    — shared by `obs summarize` and the span half of `obs diff`."""
    import glob as globmod
    import json as jsonmod

    from distributed_sigmoid_loss_tpu.obs.spans import Span

    host_paths = sorted(
        globmod.glob(os.path.join(root, "**", "host_spans.trace.json"),
                     recursive=True)
    )
    spans: list = []
    counters: dict = {}
    for path in host_paths:
        with open(path, encoding="utf-8") as f:
            trace = jsonmod.load(f)
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") == "X" and "dur" in ev:
                t0 = ev["ts"] / 1e6
                spans.append(Span(ev["name"], t0, t0 + ev["dur"] / 1e6,
                                  ev.get("tid", 0), ev.get("args")))
        for name, n in trace.get("counters", {}).items():
            counters[name] = counters.get(name, 0) + n
    return host_paths, spans, counters


def _add_obs_args(p) -> None:
    """Register the `obs` arguments on ``p`` — used for both the subparser in
    ``main`` (so `obs` shows up in --help) and the standalone intermixed
    parser the obs short-circuit builds, keeping the two in lockstep."""
    p.add_argument("action",
                   choices=["summarize", "diff"],
                   help="summarize: aggregate host spans + device op time "
                        "under DIR; diff: two run dirs' span summaries "
                        "side by side")
    p.add_argument("paths", nargs="*",
                   help="summarize: DIR; diff: two run dirs")
    p.add_argument("--top", type=int, default=12,
                   help="rows per device-op table (obs summarize)")


def cmd_obs(args) -> int:
    """The graftscope offline surface:

    - ``obs summarize DIR`` — merged host-span + device-trace report.
    - ``obs diff DIR_A DIR_B`` — two run directories' span summaries, side
      by side with the change in each span's mean.
    """
    if args.action == "diff":
        return _obs_diff(args)
    return _obs_summarize(args)


def _obs_diff(args) -> int:
    from distributed_sigmoid_loss_tpu.obs.spans import summarize_spans

    if len(args.paths) != 2 or not all(os.path.isdir(p) for p in args.paths):
        print("obs diff needs exactly two run directories (train with "
              "--obs-dir)", file=sys.stderr)
        return 2
    a, b = args.paths
    rows_a = summarize_spans(_load_host_spans(a)[1])
    rows_b = summarize_spans(_load_host_spans(b)[1])
    if not rows_a or not rows_b:
        print("obs diff: one of the run dirs has no host spans "
              "(train with --obs-dir)", file=sys.stderr)
        return 2
    print(f"== span summary diff (A={a} B={b})")
    print(f"  {'span':<28}{'A mean ms':>11}{'B mean ms':>11}{'delta':>9}")
    for name in sorted(set(rows_a) | set(rows_b)):
        ma = rows_a.get(name, {}).get("mean_ms")
        mb = rows_b.get(name, {}).get("mean_ms")
        if ma is None or mb is None:
            only = "A" if mb is None else "B"
            print(f"  {name:<28}{'(only in ' + only + ')':>31}")
            continue
        print(f"  {name:<28}{ma:>11.2f}{mb:>11.2f}{mb - ma:>+9.2f}")
    return 0


def _obs_summarize(args) -> int:
    """``obs summarize DIR``: one offline report of a run's host spans
    (``host_spans.trace.json`` written by ``train --obs-dir``) and any device
    trace capture (``*.trace.json.gz`` from ``jax.profiler.trace``) found
    under DIR — two tables, no TensorBoard needed.
    For both on one time axis open the profiler's own capture: while it runs,
    every enabled span is also a ``TraceAnnotation`` in its host plane
    (obs/spans.py).
    """
    import glob as globmod

    if len(args.paths) != 1:
        print("obs summarize needs exactly one DIR operand", file=sys.stderr)
        return 2
    root = args.paths[0]
    from distributed_sigmoid_loss_tpu.obs.spans import summarize_spans

    host_paths, spans, counters = _load_host_spans(root)

    device_files = globmod.glob(
        os.path.join(root, "**", "*.trace.json.gz"), recursive=True
    )

    if not spans and not device_files:
        print(f"no host_spans.trace.json or *.trace.json.gz under "
              f"{root!r} (train with --obs-dir and/or capture a device "
              "trace with jax.profiler.trace)",
              file=sys.stderr)
        return 2

    if spans:
        print(f"== host spans ({len(spans)} retained, "
              f"{len(host_paths)} file(s))")
        # self ms: a name's time less what the spans inside its spans cover
        # (`init_state` less its programs' trace, lowering and compile).
        print(f"  {'span':<28}{'count':>7}{'total ms':>11}{'self ms':>11}"
              f"{'mean ms':>9}{'p50':>8}{'p95':>8}{'max':>9}")
        for name, row in summarize_spans(spans).items():
            print(f"  {name:<28}{row['count']:>7}{row['total_ms']:>11.1f}"
                  f"{row['self_ms']:>11.1f}"
                  f"{row['mean_ms']:>9.2f}{row['p50_ms']:>8.2f}"
                  f"{row['p95_ms']:>8.2f}{row['max_ms']:>9.2f}")
        if counters:
            print("  counters: " + " ".join(
                f"{k}={v}" for k, v in sorted(counters.items())))

    if device_files:
        from distributed_sigmoid_loss_tpu.utils.profiling import (
            summarize_device_ops,
        )

        dev = summarize_device_ops(root, top=args.top)
        if dev["categories"]:
            print("\n== device ops by hlo_category "
                  "(achieved rates over span time)")
            print(f"  {'category':<28}{'ms':>10}{'share':>8}{'TFLOP/s':>9}"
                  f"{'GB/s':>8}")
            for name, ms, share, tf, gb in dev["categories"]:
                print(f"  {name:<28}{ms:>10.1f}{share:>8.1%}{tf:>9.1f}"
                      f"{gb:>8.0f}")
            print("\n== top device ops")
            for name, ms, n, tf, gb in dev["top_ops"]:
                print(f"  {name:<42}{ms:>9.1f} ms  n={n:<5}"
                      f"{tf:>7.1f} TF/s{gb:>7.0f} GB/s")
        else:
            print("\n(device trace files found but no 'XLA Ops' track — "
                  "host-only capture?)")

    return 0


def cmd_lint(args) -> int:
    """Run graftlint: the repo-invariant AST linter, the graftguard
    lock-discipline analyzer (guarded-by + lock-order + lockwatch gate),
    plus (default) the config-space drift check and the jaxpr
    collective/dtype/dataflow auditor over the sampled step-config product
    on an emulated CPU mesh. Exit 0 = clean, 1 = findings, 2 = usage error.

    Rule catalog + allowlist policy: docs/ANALYSIS.md. The same entry points
    run inside tests/test_analysis.py and the __graft_entry__ dryrun, so a
    finding here is a tier-1 failure — `lint` is the local preview.
    """
    # The auditor traces shard_map'd steps, which needs a multi-device mesh;
    # default to the 8-virtual-device CPU bootstrap the tests use.
    if not args.no_jaxpr and not args.cpu_devices:
        args.cpu_devices = 8
    _bootstrap_devices(args)
    import json as jsonmod

    from distributed_sigmoid_loss_tpu.analysis import (
        ALL_RULES,
        apply_lint_baseline,
        load_lint_baseline,
        run_lint,
    )

    unknown = [r for r in args.disable if r not in ALL_RULES]
    if unknown:
        print(
            f"--disable: unknown rule(s) {unknown}; known rules: "
            + ", ".join(ALL_RULES),
            file=sys.stderr,
        )
        return 2
    baseline_keys = None
    if args.baseline:
        try:
            baseline_keys = load_lint_baseline(args.baseline)
        except (OSError, ValueError) as e:
            print(f"--baseline: {e}", file=sys.stderr)
            return 2
    findings = run_lint(
        disabled=set(args.disable),
        jaxpr=not args.no_jaxpr,
        full_product=args.full_product,
    )
    if baseline_keys is not None:
        findings = apply_lint_baseline(findings, baseline_keys)
    checked = [r for r in ALL_RULES if r not in args.disable]
    if args.no_jaxpr:
        checked = [
            r for r in checked
            if not r.startswith("jaxpr-") and r != "config-space-drift"
        ]
    if baseline_keys is None:
        checked = [r for r in checked if r != "lint-stale-suppression"]
    if args.json:
        print(jsonmod.dumps({
            "rules_checked": checked,
            "disabled": sorted(args.disable),
            "findings": [f.as_dict() for f in findings],
        }, indent=2))
    else:
        for f in findings:
            print(f)
    print(
        f"graftlint: {len(checked)} rules checked, {len(findings)} "
        f"finding(s)" + (f", {len(args.disable)} disabled" if args.disable
                         else ""),
        file=sys.stderr,
    )
    return 1 if findings else 0


def cmd_tokenizer(args) -> int:
    """Train a BPE vocab from captions and write it as json."""
    import glob as globmod

    from distributed_sigmoid_loss_tpu.data import BpeTokenizer

    if bool(args.data_dir) == bool(args.text_file):
        print("pass exactly one of --data-dir or --text-file", file=sys.stderr)
        return 2
    if args.data_dir:
        paths = sorted(globmod.glob(os.path.join(args.data_dir, "*.txt")))
        if not paths:
            print(f"no *.txt captions under {args.data_dir!r}", file=sys.stderr)
            return 2
        texts = []
        for path in paths:
            with open(path, encoding="utf-8") as f:
                texts.append(f.read().strip())
    else:
        with open(args.text_file, encoding="utf-8") as f:
            texts = [line.strip() for line in f if line.strip()]
    if not texts:
        print("corpus is empty (no non-blank captions)", file=sys.stderr)
        return 2
    tok = BpeTokenizer.train(texts, args.vocab_size)
    tok.save(args.out)
    n_merges = len(tok.merges)
    sample = texts[0][:60]
    ratio = len(sample.encode("utf-8")) / max(1, len(tok.encode(sample)) - 2)
    print(
        f"trained {n_merges} merges (vocab {tok.vocab_size}) from "
        f"{len(texts)} captions -> {args.out}; "
        f"~{ratio:.2f} bytes/token on a sample"
    )
    return 0


def main(argv=None) -> int:
    from distributed_sigmoid_loss_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    ap = argparse.ArgumentParser(
        prog="distributed_sigmoid_loss_tpu", description=__doc__
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    tr = sub.add_parser("train", help="end-to-end SigLIP training (synthetic data)")
    tr.add_argument("--steps", type=int, default=20)
    tr.add_argument("--tokenizer", default="",
                    help="trained BPE vocab json (see the `tokenizer` "
                         "subcommand); default = byte-level tokenizer")

    tr.add_argument("--batch", type=int, default=64, help="global batch size")
    tr.add_argument("--variant", choices=["all_gather", "ring"], default=None,
                    help="loss comm pattern (default ring; --grad-compression "
                         "and --loss-impl chunked select all_gather)")
    tr.add_argument("--loss-impl", choices=["fused", "chunked"],
                    default="fused",
                    help="all_gather loss memory shape: 'fused' computes the "
                         "whole (local_b, W*local_b) logits in one matmul; "
                         "'chunked' streams the gathered negatives through a "
                         "scan over W chunk-blocks — the full logits matrix "
                         "is never materialized (~W* lower peak loss HBM, "
                         "unlocking larger per-chip batches)")
    tr.add_argument("--ring-overlap", action="store_true",
                    help="double-buffer the ring loss's hop loop: hop k+1's "
                         "ppermute is issued before hop k's block matmuls so "
                         "XLA hides ICI latency behind the MXU (ring variant "
                         "only; bitwise-same accumulation order)")
    tr.add_argument("--use-pallas", action="store_true",
                    help="streaming 2-D Pallas loss kernel: every logits "
                         "block (fused gather, chunked scan body, ring hop) "
                         "computes tile-by-tile in VMEM with a fused-backward "
                         "recompute VJP — composes with --loss-impl chunked "
                         "and --ring-overlap; with --quant-train int8 the "
                         "block products run the int8 MXU path (STE "
                         "semantics); falls back to XLA per block for "
                         "non-tileable shapes (recorded, never silent)")
    tr.add_argument("--loss-family", choices=["sigmoid", "softmax"],
                    default="sigmoid",
                    help="sigmoid = SigLIP (reference); softmax = CLIP/InfoNCE "
                         "over the same comm variants")
    tr.add_argument("--lr", type=float, default=1e-3)
    tr.add_argument("--optimizer", choices=["adamw", "lion", "adafactor"],
                    default="adamw",
                    help="optimizer family: adamw (default), lion (half the "
                         "optimizer state; use ~3-10x smaller --lr), adafactor "
                         "(factored second moments, biggest-model memory)")
    tr.add_argument("--model", choices=["b16", "l14", "so400m", "tiny"], default="b16")
    tr.add_argument("--tiny", action="store_true", help="alias for --model tiny")
    tr.add_argument("--model-config", default="", metavar="FILE",
                    help="a configuration file in place of --model: JSON with "
                         "vision / text / loss sections by SigLIPConfig field "
                         "name (benchmark/configs/*.json), e.g. a text tower of "
                         "several layer kinds with routed experts")
    tr.add_argument("--accum", type=int, default=1, help="grad-accumulation microsteps")
    tr.add_argument("--accum-bf16", action="store_true",
                    help="bf16 gradient accumulator under --accum (adds stay "
                         "f32; halves the accumulator's HBM footprint and "
                         "per-microstep read+write traffic)")
    tr.add_argument("--remat-policy", default="",
                    choices=["", "nothing", "save_hot", "save_all_hot",
                             "save_mlp"],
                    help="override both towers' remat policy (default: the "
                         "model config's own; benchmark/traffic/*.json "
                         "name the one each cell runs)")
    tr.add_argument("--quant-train", choices=["", "int8"], default="",
                    help="trainable int8: block projection matmuls run the "
                         "dynamic symmetric int8 recipe FORWARD (v5e int8 "
                         "MXU = 2x bf16 peak) with the full-precision VJP "
                         "BACKWARD (straight-through estimator) — the int8 "
                         "training track")
    tr.add_argument("--accum-negatives", choices=["local", "global"],
                    default="local",
                    help="with --accum > 1: 'local' contrasts each microbatch "
                         "against its own texts only (cheap, smaller negative "
                         "set); 'global' computes the EXACT full-batch loss "
                         "GradCache-style (embed pass + loss island + "
                         "surrogate re-forward; ~30%% slower, bitwise-faithful "
                         "negatives)")
    tr.add_argument("--gradcache-bf16", action="store_true",
                    help="with --accum-negatives global: store the GradCache "
                         "embedding stash in bf16 (island matmuls read bf16 "
                         "operands, stash HBM halves; ~2^-9 rounding on the "
                         "island loss/cotangents)")
    tr.add_argument("--moe-experts", type=int, default=0,
                    help="swap tower MLPs for this many experts per block "
                         "(mixture-of-experts; shards over an ep mesh axis)")
    tr.add_argument("--moe-aux-weight", type=float, default=None,
                    help="router load-balancing loss weight (requires "
                         "--moe-experts; default 0.01 when MoE is on)")
    tr.add_argument("--moe-group-size", type=int, default=0,
                    help="GShard routing group size (with --moe-experts): "
                         "capacity is per-group, so smaller groups shrink the "
                         "dispatch tensors for tight HBM budgets (default 512)")
    tr.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages: split each tower's block "
                         "stack into this many gpipe stages over a pp mesh "
                         "axis (device count must divide; towers must be "
                         "scanned + dense)")
    tr.add_argument("--pp-microbatches", type=int, default=0,
                    help="microbatches per pipelined step (default 2*pp); "
                         "global batch must divide by dp*pp_microbatches")
    tr.add_argument("--ep", type=int, default=1,
                    help="expert-parallel mesh factor (with --moe-experts): mesh "
                         "becomes (dp = devices/ep, ep); 1 = replicated experts")
    tr.add_argument("--data-dir", default="",
                    help="train on a directory of name.jpg + name.txt pairs "
                         "(real data; single-process)")
    tr.add_argument("--data-shards", default="",
                    help="train on webdataset-style tar shards matching this "
                         "glob (real data; single-process)")
    tr.add_argument("--shuffle-buffer", type=int, default=0,
                    help="sample-shuffle reservoir size for --data-shards "
                         "(webdataset-style; 0 = stream in tar order)")
    tr.add_argument("--native-decode", action="store_true",
                    help="decode real-data images with the native libjpeg "
                         "engine (threaded, off-GIL; with --data-dir or "
                         "--data-shards); falls back to PIL with a notice")
    tr.add_argument("--native-data", action="store_true",
                    help="use the C++ input-pipeline engine (native/dataloader.cc) "
                         "instead of the numpy pipeline; falls back with a notice "
                         "when no toolchain is available")
    tr.add_argument("--data-workers", type=int, default=0, metavar="N",
                    help="host worker threads for image decode / native "
                         "generation (0 = auto: cpu_count minus the "
                         "prefetch/main threads)")
    tr.add_argument("--update-sharding", choices=["off", "zero1", "full"],
                    default="",
                    help="cross-replica update sharding (graftshard, "
                         "parallel/update_shard.py): 'zero1' re-pins "
                         "optimizer state over dp (the classic layout); "
                         "'full' reduce-scatters gradients into a 1/W shard, "
                         "runs the optax update + state on the shard, and "
                         "all-gathers params once per step — ~W x less "
                         "optimizer HBM, and with --grad-compression the "
                         "dcn wire compresses the shard (another ~W x fewer "
                         "bytes); requires a dp axis > 1, excludes --pp")
    tr.add_argument("--zero1", action="store_true",
                    help="deprecated alias for --update-sharding zero1 — "
                         "shard optimizer state over dp (ZeRO-1); fits "
                         "so400m-class towers in v5e HBM")
    tr.add_argument("--dcn-slices", type=int, default=1, metavar="N",
                    help="multi-slice topology: a separate dcn mesh axis of "
                         "size N outermost (cross-slice DCN links), dp inside "
                         "(ICI) — pair with --grad-compression")
    tr.add_argument("--force-dcn-emulation", action="store_true",
                    help="allow --dcn-slices on single-slice TPU hardware "
                         "(quantization loss on ICI, no bandwidth win — for "
                         "perf experiments emulating a multi-slice topology)")
    tr.add_argument("--grad-compression", "--compression",
                    choices=["int8", "topk", "adaptive", "learned"],
                    default="",
                    help="compress the gradient sync over the dcn axis: f32 "
                         "psum on ICI; on DCN either int8 all-gather (~4x "
                         "fewer bytes), top-k sparsification (~50x at the "
                         "default 1%%), adaptive — a per-tensor "
                         "int8/int4/sign1/top-k scheme chosen each round by "
                         "the bandwidth-aware bit controller "
                         "(parallel/adaptive_compression.py) — or learned: "
                         "the adaptive ladder plus graftcodec's rung 6, a "
                         "per-tensor-group linear autoencoder (~0.26 "
                         "bytes/param) trained online on the host from the "
                         "step's block moments; all with error feedback "
                         "(train/compressed_step.py)")
    tr.add_argument("--dcn-budget-mbps", type=float, default=None,
                    metavar="MBPS",
                    help="per-device DCN egress budget for --grad-compression "
                         "adaptive: the bit controller narrows per-tensor "
                         "schemes until min(measured-bandwidth EWMA, this "
                         "budget) fits the sync round (unset: measured "
                         "bandwidth alone)")
    tr.add_argument("--controller", choices=["greedy", "budgeted"],
                    default=None,
                    help="bit-controller policy for --grad-compression "
                         "adaptive/learned (default greedy): greedy narrows "
                         "the lowest-EF-ratio tensors first; budgeted "
                         "allocates a global loss-impact budget — per-rung "
                         "error-per-byte-saved knapsack descent over "
                         "ef_ratio/gvar/gnorm")
    tr.add_argument("--emu-dcn-mbps", type=float, default=None,
                    metavar="MBPS",
                    help="honest DCN emulation (parallel/dcn_emu.py): ship "
                         "each round's dcn payload across a throttled "
                         "two-process localhost pipe at this bandwidth, so "
                         "dcn_bw_est_mbps reacts to MEASURED transfer time "
                         "and metrics carry dcn_measured_mbps + "
                         "wire_savings_wallclock_ratio vs the fixed-bf16 "
                         "reference; requires --dcn-slices >= 2")
    tr.add_argument("--topk-frac", type=float, default=0.01, metavar="F",
                    help="fraction of entries kept per tensor under "
                         "--grad-compression topk (adaptive: its top-k "
                         "rung; the narrow rung keeps F/4)")
    tr.add_argument("--topk-exact", action="store_true",
                    help="exact lax.top_k selection instead of the default "
                         "approx_max_k (4x slower on TPU at gradient scale; "
                         "use for bit-reproducibility)")
    tr.add_argument("--ema-decay", type=float, default=None,
                    help="maintain an EMA of the params in the train state "
                         "(e.g. 0.9999, warmed up)")
    tr.add_argument("--cpu-devices", type=int, default=0, help="emulate N CPU devices")
    tr.add_argument("--ckpt-dir", default="",
                    help="checkpoint/resume directory: resumes from the newest "
                         "step-numbered checkpoint, saves every --ckpt-every steps "
                         "and on SIGTERM (preemption)")
    tr.add_argument("--async-checkpoint", action="store_true",
                    help="non-blocking checkpoint writes (orbax async): the "
                         "step loop overlaps the save IO instead of stalling "
                         "for it (seconds per save at so400m scale)")
    tr.add_argument("--ckpt-every", type=int, default=50)
    tr.add_argument("--eval-every", type=int, default=0, metavar="N",
                    help="every N steps, log zero-shot retrieval metrics "
                         "(eval/i2t_recall@K ...) on one fixed batch — the "
                         "in-training validation curve. Synthetic runs use a "
                         "genuinely held-out batch (shifted seeds); file/"
                         "native streams use --eval-data when given, else "
                         "fall back (with a warning) to the first training "
                         "batch, so the curve there includes train-set fit")
    tr.add_argument("--eval-data", default="", metavar="PATH_OR_GLOB",
                    help="held-out eval source for --eval-every: a directory "
                         "(ImageTextFolder layout) or a tar-shard glob kept "
                         "OUT of --data-dir/--data-shards — makes the "
                         "in-training curve a true validation curve")
    tr.add_argument("--log-every", type=int, default=1)
    tr.add_argument("--obs-dir", default="", metavar="DIR",
                    help="enable graftscope host-span recording: the train "
                         "loop's fetch/h2d-commit/step/eval/checkpoint spans "
                         "are written to DIR/host_spans.trace.json "
                         "(Chrome-trace JSON — overlays a device capture in "
                         "ui.perfetto.dev; merge offline with `obs summarize "
                         "DIR`), and the flight recorder dumps to "
                         "DIR/flight.json on crash/SIGTERM instead of stderr")
    tr.add_argument("--watchdog", choices=["off", "warn", "skip"],
                    default="warn",
                    help="training health watchdog (obs/health.py): 'warn' "
                         "(default) emits structured health_event records on "
                         "NaN/Inf metrics and loss spikes vs the rolling "
                         "median; 'skip' additionally routes a non-finite "
                         "loss into the resilient loop's rollback-and-skip "
                         "path (requires --ckpt-dir); 'off' disables "
                         "detection (the grad_norm/param_norm/update_ratio "
                         "scalars stay on every metrics line regardless)")
    tr.add_argument("--coordinator", default="",
                    help="multi-process rendezvous address host:port — every "
                         "process runs this same command with its own --process-id; "
                         "--batch stays GLOBAL and must be divisible by "
                         "--num-processes")
    tr.add_argument("--num-processes", type=int, default=0,
                    help="total process count (required with --coordinator)")
    tr.add_argument("--process-id", type=int, default=-1,
                    help="this process's 0-based rank (required with --coordinator)")

    ev = sub.add_parser("eval", help="zero-shot retrieval + classification")
    ev.add_argument("--tokenizer", default="",
                    help="trained BPE vocab json (see the `tokenizer` "
                         "subcommand); default = byte-level tokenizer")
    ev.add_argument("--batch", type=int, default=64)
    ev.add_argument("--classes", type=int, default=10)
    ev.add_argument("--model", choices=["b16", "l14", "so400m", "tiny"], default="b16")
    ev.add_argument("--tiny", action="store_true", help="alias for --model tiny")
    ev.add_argument("--moe-experts", type=int, default=0,
                    help="match a checkpoint trained with --moe-experts")
    ev.add_argument("--optimizer", choices=["adamw", "lion", "adafactor"],
                    default="adamw",
                    help="optimizer family the checkpoint was trained with "
                         "(shapes the restore target's optimizer state)")
    ev.add_argument("--data-dir", default="",
                    help="directory of name.jpg + name.txt pairs: score REAL "
                         "pairs (retrieval + caption-matching zero-shot) "
                         "instead of synthetic data")
    ev.add_argument("--data-shards", default="",
                    help="glob of webdataset-style tar shards (same loaders as "
                         "train); mutually exclusive with --data-dir")
    ev.add_argument("--cpu-devices", type=int, default=0)
    ev.add_argument("--ckpt-dir", default="", help="restore params from this checkpoint")
    ev.add_argument("--quant", choices=["", "int8"], default="",
                    help="run the towers' projection matmuls in dynamic int8 "
                         "(v5e int8 MXU = 2x bf16 peak; inference-only)")
    ev.add_argument("--ema", action="store_true",
                    help="evaluate the checkpoint's EMA weights (train --ema-decay)")

    tk = sub.add_parser(
        "tokenizer",
        help="train a byte-level BPE vocab on a caption corpus (data/tokenizer.py)",
    )
    tk.add_argument("out", help="output vocab json path")
    tk.add_argument("--vocab-size", type=int, default=4096)
    tk.add_argument("--data-dir", default="",
                    help="directory of name.txt caption files (the "
                         "ImageTextFolder layout)")
    tk.add_argument("--text-file", default="",
                    help="plain text file, one caption per line")

    ex = sub.add_parser(
        "export",
        help="AOT-export a lowered step to a StableHLO artifact (jax.export)",
    )
    ex.add_argument("out", help="output artifact path")
    ex.add_argument("--quant", choices=["", "int8"], default="",
                    help="quantize the towers for --what forward artifacts "
                         "(int8 projection matmuls; rejected for train_step)")
    ex.add_argument("--what", choices=["train_step", "forward"],
                    default="train_step")
    ex.add_argument("--model", choices=["b16", "l14", "so400m", "tiny"],
                    default="b16")
    ex.add_argument("--tiny", action="store_true", help="alias for --model tiny")
    ex.add_argument("--moe-experts", type=int, default=0,
                    help="export the MoE variant (matches train --moe-experts)")
    ex.add_argument("--ep", type=int, default=1,
                    help="expert-parallel mesh factor (with --moe-experts): the "
                         "artifact is lowered for a (dp = devices/ep, ep) mesh, "
                         "matching train --ep (train_step only)")
    ex.add_argument("--moe-aux-weight", type=float, default=0.01,
                    help="router load-balancing loss weight baked into the "
                         "train_step artifact (match the train job's value)")
    ex.add_argument("--moe-group-size", type=int, default=0,
                    help="GShard routing group size baked into the artifact "
                         "(match the train job's value; default 512)")
    ex.add_argument("--batch", type=int, default=64,
                    help="global batch the artifact is shaped for")
    ex.add_argument("--variant", choices=["all_gather", "ring"], default="ring")
    ex.add_argument("--loss-family", choices=["sigmoid", "softmax"],
                    default="sigmoid",
                    help="loss family baked into the train_step artifact "
                         "(match the train job's --loss-family)")
    ex.add_argument("--lr", type=float, default=1e-3,
                    help="learning rate baked into the train_step artifact")
    ex.add_argument("--warmup-steps", type=int, default=2000,
                    help="LR warmup steps baked into the train_step artifact")
    ex.add_argument("--total-steps", type=int, default=100_000,
                    help="LR schedule horizon baked into the train_step artifact")
    ex.add_argument("--platform", default="",
                    help="lowering target (e.g. tpu) when exporting from a "
                         "different host backend; default: current backend")
    ex.add_argument("--check", action="store_true",
                    help="reload the written artifact and replay one step "
                         "against the live jitted step")
    ex.add_argument("--cpu-devices", type=int, default=0,
                    help="emulate N CPU devices (export for an N-device mesh)")

    sb = sub.add_parser(
        "serve-bench",
        help="online serving micro-bench: concurrent clients through the "
             "batched/cached/bucketed serve/ stack; prints the stats "
             "snapshot as JSON (CPU-runnable)",
    )
    sb.add_argument("--requests", type=int, default=512,
                    help="total client requests across all clients")
    sb.add_argument("--clients", type=int, default=8,
                    help="concurrent client threads")
    sb.add_argument("--model", choices=["b16", "l14", "so400m", "tiny"],
                    default="tiny",
                    help="tower config (default tiny: the CPU-runnable "
                         "smoke/bench shape; big models need a real chip)")
    sb.add_argument("--batch-buckets", default="1,8,32", metavar="N,N,...",
                    help="padded batch-size buckets the engine compiles "
                         "(steady state never compiles outside the grid)")
    sb.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="micro-batcher deadline: max ms a queued request "
                         "waits for coalescing before a partial flush")
    sb.add_argument("--max-queue", type=int, default=1024,
                    help="bounded request queue per modality (full queue "
                         "rejects with backpressure)")
    sb.add_argument("--cache-size", type=int, default=4096,
                    help="LRU embedding cache capacity (entries)")
    sb.add_argument("--pool", type=int, default=64,
                    help="distinct synthetic items clients draw from "
                         "(repeats exercise the cache)")
    sb.add_argument("--index-size", type=int, default=64,
                    help="corpus rows indexed for the search requests")
    sb.add_argument("--index-tier", choices=["exact", "sharded", "ann"],
                    default="exact",
                    help="retrieval tier answering search requests: exact = "
                         "single-host chunked scan (the oracle), sharded = "
                         "dp-mesh per-shard top-k + merged candidates "
                         "(requires --mesh), ann = int8 quantize-then-rerank "
                         "with measured recall@k in the record "
                         "(docs/SERVING.md)")
    sb.add_argument("--swap-every", type=int, default=0, metavar="N",
                    help="churn mode: hot-swap the weights + freshly built "
                         "index segments after every N completed client ops "
                         "(0 = off); swap_count / swap_latency_ms land in "
                         "the record and the zero-recompile gate still "
                         "applies")
    sb.add_argument("--rerank-k", type=int, default=0, metavar="K",
                    help="ann tier: coarse candidates kept for the exact "
                         "re-rank (0 = auto: max(8·topk, 64)) — the "
                         "recall/latency knob")
    sb.add_argument("--topk", type=int, default=5)
    sb.add_argument("--metrics-port", type=int, default=-1, metavar="PORT",
                    help="expose the live OpenMetrics-style /metrics "
                         "endpoint during the bench on this port (0 = an "
                         "ephemeral port, printed on stderr; -1 = off) — "
                         "scrape qps/latency/compile_count mid-run "
                         "(docs/OBSERVABILITY.md 'Live telemetry')")
    sb.add_argument("--scenario", default="",
                    choices=["", "burst", "skew", "slowloris", "hostloss",
                             "swapstorm"],
                    help="graftsiege soak: replace the fixed-request client "
                         "loop with a shaped overload scenario (open-loop "
                         "offered load, multi-tenant admission at the front "
                         "door) and emit the degradation record — p99 vs "
                         "offered load, per-tenant shed_rate, "
                         "recovery_time_s, silent_drops "
                         "(docs/SERVING.md 'Overload & SLO semantics')")
    sb.add_argument("--tenants",
                    default="gold:prio=2,quota=24,slo=500;"
                            "free:prio=1,rate=80,quota=8",
                    metavar="SPEC",
                    help="scenario tenant policies, ';'-separated "
                         "name:key=value[,key=value...] rows (keys: prio, "
                         "rate req/s, burst, quota in-flight items, slo ms)")
    sb.add_argument("--duration-s", type=float, default=4.0,
                    help="scenario soak duration (wall seconds of offered "
                         "load; recovery measurement may extend past it)")
    sb.add_argument("--offered-load", type=float, default=200.0,
                    help="aggregate offered load across tenants (req/s) the "
                         "scenario shapes — set ≥2x sustained capacity for "
                         "the overload drill")
    sb.add_argument("--capacity", type=int, default=64,
                    help="AdmissionController global in-flight item budget "
                         "(priority tiers partition it under overload)")
    sb.add_argument("--fleet-scenario", default="",
                    choices=["", "fleet-rolling-swap", "fleet-hostloss",
                             "fleet-splitbrain"],
                    help="graftfleet drill: N EngineProcess-backed replicas "
                         "behind the fleet router with token-lease "
                         "distributed admission — rolling swap wave under "
                         "burst, replica kill -9 with lease reclaim, or "
                         "coordinator split-brain (must under-admit, never "
                         "over-admit); emits the fleet_siege degradation "
                         "record (docs/SERVING.md 'Fleet tier')")
    sb.add_argument("--fleet-replicas", type=int, default=0, metavar="N",
                    help="replica count for --fleet-scenario (>= 2; 0 = "
                         "unset, defaults to 3 when a fleet scenario runs)")
    sb.add_argument("--lease-ttl-s", type=float, default=0.0, metavar="S",
                    help="fleet lease TTL: a dead host's quota slices "
                         "expire and redistribute within this bound (0 = "
                         "unset, defaults to 0.5 when a fleet scenario "
                         "runs)")
    sb.add_argument("--seed", type=int, default=0)
    sb.add_argument("--mesh", action="store_true",
                    help="shard engine batches over the dp mesh (batch "
                         "buckets must divide the device count)")
    sb.add_argument("--cpu-devices", type=int, default=0,
                    help="emulate N CPU devices (pair with --mesh)")

    db = sub.add_parser(
        "data-bench",
        help="input-pipeline stage bench: shard read / decode / tokenize / "
             "augment / h2d commit in isolation + the composed real-data "
             "pipeline vs the synthetic loader (schema-validated JSON "
             "records; CPU-runnable)",
    )
    from distributed_sigmoid_loss_tpu.data.data_bench import (
        add_data_bench_args,
    )

    add_data_bench_args(db)
    db.add_argument("--cpu-devices", type=int, default=0,
                    help="emulate N CPU devices (the h2d/composed stages "
                         "commit onto this mesh)")

    ob = sub.add_parser(
        "obs",
        help="graftscope reports: `obs summarize DIR` (merged host+device "
             "timeline), `obs diff DIR_A DIR_B` (two runs' span summaries) "
             "— docs/OBSERVABILITY.md",
    )
    _add_obs_args(ob)

    ln = sub.add_parser(
        "lint",
        help="graftlint: repo-invariant linter + config-space drift check + "
             "jaxpr collective/dtype/dataflow auditor over the sampled "
             "step-config product (exit 1 on findings); rule catalog in "
             "docs/ANALYSIS.md",
    )
    ln.add_argument("--json", action="store_true",
                    help="machine-readable report (rules checked + findings, "
                         "each with a stable rule_id + location) instead of "
                         "one text line per finding")
    ln.add_argument("--disable", action="append", default=[], metavar="RULE",
                    help="skip this rule id (repeatable); see docs/ANALYSIS.md "
                         "for the catalog — prefer fixing or allowlisting "
                         "with a rationale over disabling")
    ln.add_argument("--no-jaxpr", action="store_true",
                    help="AST rules only (skip the config-space probe and "
                         "the step-config traces; sub-second, for "
                         "pre-commit-style hooks)")
    ln.add_argument("--full-product", action="store_true",
                    help="audit the pairwise-covering sample of the FULL "
                         "legal config product from the solver, not just "
                         "the tier-1 sample (~30 s of extra traces; what "
                         "the dryrun's graftprove token runs)")
    ln.add_argument("--baseline", default="", metavar="FILE",
                    help="ratchet mode: suppress findings recorded in FILE "
                         "(a saved `lint --json` report or a JSON list of "
                         "{rule, subject}); entries that no longer fire "
                         "become lint-stale-suppression findings")
    ln.add_argument("--cpu-devices", type=int, default=0,
                    help="virtual CPU mesh size for the jaxpr auditor "
                         "(default 8 — the same emulated mesh the tests use)")

    argv = sys.argv[1:] if argv is None else list(argv)
    # obs mixes nargs="*" positionals (diff's two operands) with options;
    # plain parse_args consumes positionals greedily, so flags were only
    # accepted trailing (`obs summarize DIR --top 5` worked, `obs summarize
    # --top 5 DIR` errored). parse_intermixed_args fixes that but cannot
    # traverse subparsers, so obs is routed through a standalone parser
    # built from the same _add_obs_args. The subparser stays registered for
    # --help and as a fallback.
    if argv[:1] == ["obs"]:
        obs_ap = argparse.ArgumentParser(
            prog="distributed_sigmoid_loss_tpu obs"
        )
        _add_obs_args(obs_ap)
        return cmd_obs(obs_ap.parse_intermixed_args(argv[1:]))
    args = ap.parse_args(argv)
    dispatch = {
        "train": cmd_train,
        "eval": cmd_eval,
        "export": cmd_export,
        "tokenizer": cmd_tokenizer,
        "serve-bench": cmd_serve_bench,
        "data-bench": cmd_data_bench,
        "lint": cmd_lint,
        "obs": cmd_obs,
    }
    return dispatch[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
