"""Stage-level input-pipeline benchmark — the ``data-bench`` subcommand.

The benchmark's cells feed the chip synthetic batches generated on-device;
SigLIP-scale pretraining needs the HOST to sustain the same rate through the
real path: tar shard read → JPEG decode → tokenize → (on-device) augment →
host→device commit. Until this bench existed, none of those stages had a
measured number, so a host-bound headline would have been invisible.

What it measures (one JSON record per line, validated against
``analysis/bench_schema.py``):

- each stage in ISOLATION (``data_bench_stage`` records: shard_read, decode,
  tokenize, augment, h2d_commit — items/s each), plus a decode
  worker-scaling curve;
- the COMPOSED real-data pipeline (read-ahead shards + fused decode/tokenize
  batcher + ``prefetch`` overlap) vs the synthetic loader on the same host
  (``data_bench_pipeline_pairs_per_sec``), with the starvation ratio
  (``input_wait_frac``) and the ``synthetic_ratio`` acceptance figure: the
  real path must reach >= 95% of synthetic throughput, or the record
  attributes the bound stage.

CPU-runnable end to end (shards are generated when ``--data-shards`` is not
given). jax is imported inside the runner so the module stays importable (e.g.
by argparse plumbing) without initializing a backend.
"""

from __future__ import annotations

import io
import json
import os
import sys
import tarfile
import tempfile
import time

import numpy as np

__all__ = ["add_data_bench_args", "run_data_bench", "make_synthetic_shards"]


def add_data_bench_args(ap) -> None:
    """The data-bench argument surface of the CLI subcommand."""
    ap.add_argument("--batch", type=int, default=64,
                    help="global batch size (pairs per composed-pipeline "
                         "batch)")
    ap.add_argument("--batches", type=int, default=8,
                    help="timed batches per stage measurement")
    ap.add_argument("--model", choices=["b16", "l14", "so400m", "tiny"],
                    default="tiny",
                    help="tower config supplying image_size / "
                         "context_length (tiny = the CPU-runnable shape)")
    ap.add_argument("--data-shards", default="",
                    help="measure THESE webdataset-style tar shards (glob) "
                         "instead of generating a synthetic JPEG shard set")
    ap.add_argument("--data-workers", type=int, default=0,
                    help="host worker threads for decode/generation "
                         "(0 = auto: cpu_count minus the prefetch/main "
                         "threads; the resolved value lands in every record)")
    ap.add_argument("--image-hw", default="240x320", metavar="HxW",
                    help="source resolution of the GENERATED shard images "
                         "(decode cost scales with it; ignored with "
                         "--data-shards)")
    ap.add_argument("--shards", type=int, default=4,
                    help="generated shard count (read-ahead needs >= 2)")
    ap.add_argument("--pil-decode", action="store_true",
                    help="force the PIL decode path (A/B vs the native "
                         "libjpeg engine; default: native when available)")
    ap.add_argument("--no-read-ahead", action="store_true",
                    help="disable shard read-ahead in the composed pipeline "
                         "(A/B the overlap)")
    ap.add_argument("--no-pipelined", action="store_true",
                    help="disable the fused decode+tokenize worker overlap "
                         "in the composed pipeline (A/B)")
    ap.add_argument("--no-zero-copy", action="store_true",
                    help="synthetic reference: copy C++ ring batches into "
                         "numpy instead of the zero-copy device_put handoff "
                         "(A/B)")
    ap.add_argument("--seed", type=int, default=0)


def make_synthetic_shards(
    out_dir: str, num_shards: int, pairs_per_shard: int, hw: tuple[int, int],
    seed: int = 0, quality: int = 90,
) -> list[str]:
    """Write webdataset-style tar shards of synthetic JPEG + caption pairs.

    Images are smooth random sinusoid mixes — they JPEG-compress (and
    therefore decode) like photographic content, unlike uint8 noise, whose
    pathological entropy makes decode ~3x slower than any real photo.
    """
    from PIL import Image

    h, w = hw
    rng = np.random.default_rng(seed)
    yy = np.linspace(0.0, 1.0, h, dtype=np.float32)[:, None, None]
    xx = np.linspace(0.0, 1.0, w, dtype=np.float32)[None, :, None]
    paths = []
    for s in range(num_shards):
        path = os.path.join(out_dir, f"bench-{s:05d}.tar")
        with tarfile.open(path, "w") as tf:
            for i in range(pairs_per_shard):
                f = rng.uniform(1.0, 6.0, (2, 3)).astype(np.float32)
                ph = rng.uniform(0.0, 6.28, (2, 3)).astype(np.float32)
                img = 63.75 * (
                    2.0
                    + np.sin(6.28 * f[0] * yy + ph[0])
                    + np.sin(6.28 * f[1] * xx + ph[1])
                )
                arr = np.clip(img, 0, 255).astype(np.uint8)
                buf = io.BytesIO()
                Image.fromarray(arr).save(buf, "JPEG", quality=quality)
                blob = buf.getvalue()
                name = f"pair-{s:05d}-{i:05d}"
                info = tarfile.TarInfo(f"{name}.jpg")
                info.size = len(blob)
                tf.addfile(info, io.BytesIO(blob))
                cap = f"synthetic scene {s}-{i} hue {i % 11}".encode()
                info = tarfile.TarInfo(f"{name}.txt")
                info.size = len(cap)
                tf.addfile(info, io.BytesIO(cap))
        paths.append(path)
    return paths


def _emit_record(record: dict, collected: list) -> None:
    """One JSON line per record, schema-validated (warn, never drop)."""
    from distributed_sigmoid_loss_tpu.analysis.bench_schema import (
        validate_record,
    )

    problems = validate_record(record)
    if problems:
        print(
            "WARNING: data-bench record schema violation: "
            + "; ".join(problems),
            file=sys.stderr,
        )
    collected.append(record)
    print(json.dumps(record), flush=True)


def _timed(fn, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return time.perf_counter() - t0


def run_data_bench(args, collected: list | None = None) -> int:
    """Run every stage + the composed comparison; returns the exit code.

    ``collected`` (a list) receives every emitted record dict — the
    introspection channel the tests use.
    """
    import glob as globmod

    import jax

    from distributed_sigmoid_loss_tpu.data.files import ImageTextShards
    from distributed_sigmoid_loss_tpu.data.loader import (
        PrefetchStats,
        prefetch,
        put_batch,
    )
    from distributed_sigmoid_loss_tpu.data.workers import resolve_data_workers
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig

    cfg = {
        "tiny": SigLIPConfig.tiny_test,
        "b16": SigLIPConfig.b16,
        "l14": SigLIPConfig.l14,
        "so400m": SigLIPConfig.so400m,
    }[args.model]()
    size = cfg.vision.image_size
    workers = resolve_data_workers(args.data_workers)
    batch, n_batches = args.batch, args.batches
    need_pairs = batch * (n_batches + 1)  # +1 warmup batch

    tmp = None
    if args.data_shards:
        shard_paths = sorted(globmod.glob(args.data_shards))
        if not shard_paths:
            print(f"--data-shards matched nothing: {args.data_shards!r}",
                  file=sys.stderr)
            return 2
    else:
        try:
            h, w = (int(x) for x in args.image_hw.lower().split("x"))
        except ValueError:
            print(f"--image-hw must be HxW (e.g. 240x320), got "
                  f"{args.image_hw!r}", file=sys.stderr)
            return 2
        if args.shards < 1:
            print(f"--shards must be >= 1, got {args.shards}", file=sys.stderr)
            return 2
        tmp = tempfile.TemporaryDirectory(prefix="dsl_data_bench_")
        per_shard = -(-need_pairs // args.shards)
        t0 = time.perf_counter()
        shard_paths = make_synthetic_shards(
            tmp.name, args.shards, per_shard, (h, w), seed=args.seed,
        )
        print(
            f"generated {args.shards} shard(s) x {per_shard} pairs "
            f"({h}x{w} JPEG) in {time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )

    from distributed_sigmoid_loss_tpu.cli import _byte_tokenize_for

    tokenize = _byte_tokenize_for(cfg)

    native = False
    if not args.pil_decode:
        from distributed_sigmoid_loss_tpu.data.native_decode import (
            native_decode_available,
        )

        native = native_decode_available()
        if not native:
            print("native libjpeg engine unavailable; decode stage runs PIL",
                  file=sys.stderr)

    mesh = make_mesh()
    records: list[dict] = collected if collected is not None else []
    base = {
        "unit": "items/s",
        "model": args.model,
        "global_batch": batch,
        "steps": n_batches,
        "data_workers": workers,
        "native_decode": native,
        "n_devices": len(jax.devices()),
        "device_kind": jax.devices()[0].device_kind,
    }

    def stage(name: str, value: float, **extra) -> None:
        _emit_record(
            {"metric": "data_bench_stage", "stage": name,
             "value": round(value, 1), **base, **extra},
            records,
        )

    probe = ImageTextShards(
        shard_paths, cfg, batch, tokenize, native_decode=native,
        data_workers=workers, read_ahead=False, pipelined=False,
    )

    # --- shard_read: raw pair streaming (tar IO + member pairing only).
    order = np.arange(len(probe.shards))
    t0 = time.perf_counter()
    pairs: list[tuple[bytes, str]] = []
    for p in probe._pairs(order):
        pairs.append(p)
        if len(pairs) >= need_pairs:
            break
    read_s = time.perf_counter() - t0
    if len(pairs) < batch:
        print(f"shards hold {len(pairs)} pairs; need at least one batch of "
              f"{batch}", file=sys.stderr)
        return 2
    read_ips = len(pairs) / read_s
    stage("shard_read", read_ips)

    blobs = [b for b, _ in pairs[:need_pairs]]
    texts = [t for _, t in pairs[:need_pairs]]

    # --- decode (native fans over threads / PIL serial), + scaling curve.
    def decode_ips(threads: int, reps: int = n_batches) -> float:
        if native:
            from distributed_sigmoid_loss_tpu.data.native_decode import (
                decode_batch,
            )

            def one(i):
                decode_batch(
                    blobs[i * batch:(i + 1) * batch], size, threads=threads
                )
        else:
            from distributed_sigmoid_loss_tpu.data.files import (
                decode_and_resize,
            )

            def one(i):
                for b in blobs[i * batch:(i + 1) * batch]:
                    decode_and_resize(b, size)

        reps = min(reps, len(blobs) // batch)
        one(0)  # touch the library/build path outside the clock
        t0 = time.perf_counter()
        for i in range(reps):
            one(i)
        return reps * batch / (time.perf_counter() - t0)

    curve = {}
    w_points = sorted({1, *(2 ** k for k in range(1, 6) if 2 ** k < workers),
                       workers})
    for w_ in w_points:
        curve[str(w_)] = round(decode_ips(w_, reps=max(2, n_batches // 2)), 1)
    dec_ips = decode_ips(workers)
    stage("decode", dec_ips, worker_scaling=curve)

    # --- tokenize.
    tok_reps = min(n_batches, len(texts) // batch)
    tok_s = _timed(
        lambda: [
            tokenize(texts[i * batch:(i + 1) * batch],
                     cfg.text.context_length)
            for i in range(tok_reps)
        ],
        1,
    )
    tok_ips = tok_reps * batch / tok_s
    stage("tokenize", tok_ips)

    # --- augment (on-device, jitted — overlaps the step in production; its
    # stage number shows whether it could ever become the bound).
    from distributed_sigmoid_loss_tpu.data.augment import augment_batch

    host_batch = {
        "images": np.zeros((batch, size, size, 3), np.float32),
        "tokens": np.asarray(
            tokenize(texts[:batch], cfg.text.context_length), np.int32
        ),
    }
    aug = jax.jit(lambda k, im: augment_batch(k, im, size))
    dev_images = jax.device_put(host_batch["images"])
    key = jax.random.key(args.seed)
    jax.block_until_ready(aug(key, dev_images))  # compile outside the clock
    aug_s = _timed(
        lambda: jax.block_until_ready(aug(key, dev_images)), n_batches
    )
    stage("augment", n_batches * batch / aug_s)

    # --- host->device commit (put_batch onto the dp mesh).
    def commit():
        jax.block_until_ready(put_batch(host_batch, mesh))

    commit()  # compile/placement warmup
    h2d_s = _timed(commit, n_batches)
    stage("h2d_commit", n_batches * batch / h2d_s)

    # --- composed real-data pipeline: read-ahead shards -> fused batcher ->
    # prefetch -> device. Warm one batch (thread/pool spin-up), time the rest.
    def run_pipeline(it) -> tuple[float, PrefetchStats]:
        stats = PrefetchStats()
        stream = prefetch(it, mesh, size=2, stats=stats)
        try:
            jax.block_until_ready(next(stream))
            t0 = time.perf_counter()
            for _ in range(n_batches):
                jax.block_until_ready(next(stream))
            dt = time.perf_counter() - t0
        finally:
            stream.close()
        return n_batches * batch / dt, stats

    real_src = ImageTextShards(
        shard_paths, cfg, batch, tokenize, native_decode=native,
        data_workers=workers, read_ahead=not args.no_read_ahead,
        pipelined=not args.no_pipelined, seed=args.seed,
    )
    real_pps, real_stats = run_pipeline(iter(real_src))

    # --- synthetic reference on the same host + mesh (the feeding rate the
    # headline bench implicitly assumes). Native C++ ring with the zero-copy
    # device_put handoff when available; numpy stream otherwise.
    from distributed_sigmoid_loss_tpu.data.native_loader import (
        native_available,
    )

    zero_copy = False
    if native_available():
        from distributed_sigmoid_loss_tpu.data.native_loader import (
            NativeSyntheticImageText,
        )

        ds = NativeSyntheticImageText(cfg, batch, num_threads=workers)
        zero_copy = not args.no_zero_copy and hasattr(
            ds._lib, "dsl_pipeline_acquire"
        )
        with ds:
            syn_pps, _ = run_pipeline(ds.batches(zero_copy=zero_copy))
    else:
        from distributed_sigmoid_loss_tpu.data.synthetic import (
            SyntheticImageText,
        )

        syn_pps, _ = run_pipeline(iter(SyntheticImageText(cfg, batch)))

    ratio = real_pps / syn_pps if syn_pps > 0 else 0.0
    # Host stages that serialize with each other on the real path; the
    # slowest is the bound the composed number inherits (augment/h2d ride the
    # device queue and overlap the step in production).
    host_stages = {
        "shard_read": read_ips, "decode": dec_ips, "tokenize": tok_ips,
    }
    bound = min(host_stages, key=host_stages.get)
    composed = {
        "metric": "data_bench_pipeline_pairs_per_sec",
        "value": round(real_pps, 1),
        **base,
        "unit": "pairs/s",
        "synthetic_pairs_per_sec": round(syn_pps, 1),
        "synthetic_ratio": round(ratio, 3),
        "input_wait_frac": round(real_stats.input_wait_frac(), 4),
        "pipelined": not args.no_pipelined,
        "read_ahead": not args.no_read_ahead,
        "zero_copy": zero_copy,
    }
    if ratio < 0.95:
        # The acceptance contract: either >= 95% of synthetic, or the record
        # names the bound stage and how decode scales with workers.
        composed["bound_stage"] = bound
        composed["worker_scaling"] = curve
    _emit_record(composed, records)
    if tmp is not None:
        tmp.cleanup()
    return 0
