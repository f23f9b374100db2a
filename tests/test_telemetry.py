"""Live telemetry export, and the two-run span diff.

Contract families:

- **Telemetry** (`obs/telemetry.py` + `serve/service.py`): the ``/metrics``
  endpoint serves a schema-complete OpenMetrics snapshot under concurrent
  scrape+request load ACROSS a live ``swap_params`` hot swap — zero request
  errors, compile_count flat, endpoint latency bounded, snapshot reuse
  actually bounding the render rate; the atomic telemetry file is never torn,
  and the environment fingerprint stamped into it reads an initialized jax
  without initializing one.
- **CLI**: ``obs diff DIR_A DIR_B`` renders two runs' span summaries side by
  side; anything else is a usage error (exit 2), and no ``ledger`` action or
  ``bench`` sub-command is left behind the names.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.obs import telemetry as telemetry_mod


def test_fingerprint_reads_initialized_jax():
    import jax

    jax.devices()  # conftest already initialized the CPU platform
    env = telemetry_mod.environment_fingerprint()
    assert env["jax"] == jax.__version__
    assert env["device_count"] == len(jax.devices())
    assert "cpu" in env["device_kind"].lower()


def test_fingerprint_lives_with_the_telemetry_writer():
    """Its one caller (`train --obs-dir`) writes it through
    write_telemetry_file: both come from obs.telemetry (and the package's
    obs namespace), carry host and git sha without jax's help, and no
    obs.ledger module is left to import it from."""
    import importlib

    import distributed_sigmoid_loss_tpu.obs as obs

    assert obs.environment_fingerprint is telemetry_mod.environment_fingerprint
    env = telemetry_mod.environment_fingerprint()
    assert env["host"] and "git_sha" in env
    assert telemetry_mod.environment_fingerprint()["git_sha"] == env["git_sha"]
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("distributed_sigmoid_loss_tpu.obs.ledger")
    assert not hasattr(obs, "append_record")


# ---------------------------------------------------------------------------
# obs diff CLI: two run directories' span summaries
# ---------------------------------------------------------------------------


def _run_dir(root, name, spans):
    from distributed_sigmoid_loss_tpu.obs import SpanRecorder

    rec = SpanRecorder()
    for span_name, t0, t1 in spans:
        rec.record(span_name, t0, t1)
    d = root / name
    d.mkdir()
    rec.export(str(d / "host_spans.trace.json"))
    return str(d)


def test_cli_obs_diff_two_run_dirs_and_errors(tmp_path, capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    a = _run_dir(tmp_path, "a", [("step", 0.0, 0.010), ("fetch", 0.010, 0.012)])
    b = _run_dir(tmp_path, "b", [("step", 0.0, 0.015), ("eval", 0.015, 0.020)])
    assert main(["obs", "diff", a, b]) == 0
    out, _ = capsys.readouterr()
    step = next(ln for ln in out.splitlines() if ln.split()[:1] == ["step"])
    assert step.split()[1:] == ["10.00", "15.00", "+5.00"]
    assert "(only in A)" in next(ln for ln in out.splitlines() if "fetch" in ln)
    assert "(only in B)" in next(ln for ln in out.splitlines() if "eval" in ln)
    # one operand, a file where a run dir belongs, a run dir with no spans
    empty = tmp_path / "empty"
    empty.mkdir()
    for argv in (
        ["obs", "diff", a],
        ["obs", "diff", a, os.path.join(b, "host_spans.trace.json")],
        ["obs", "diff", a, str(empty)],
    ):
        assert main(argv) == 2, argv
        assert "obs diff" in capsys.readouterr().err
    # nothing is left behind the retired names: no ledger action, no record
    # selectors, no --ledger / --metric / --backfill
    for argv in (
        ["obs", "ledger"],
        ["obs", "diff", a, b, "--ledger", "x.jsonl"],
        ["obs", "summarize", a, "--metric", "m"],
        ["obs", "summarize", a, "--backfill"],
    ):
        with pytest.raises(SystemExit) as e:
            main(argv)
        assert e.value.code == 2, argv
        capsys.readouterr()


def test_cli_bench_is_not_a_subcommand(capsys):
    """No passthrough left: argparse refuses the name like any other."""
    from distributed_sigmoid_loss_tpu.cli import main

    for argv in (["bench"], ["bench", "288", "10", "b16"], ["bench", "--cpu-devices", "8"]):
        with pytest.raises(SystemExit) as e:
            main(argv)
        assert e.value.code == 2
        assert "invalid choice: 'bench'" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# telemetry: render, exporter, /metrics under load + hot swap
# ---------------------------------------------------------------------------

_SNAPSHOT = {
    "uptime_s": 12.5,
    "requests": 100,
    "items": 140,
    "qps": 8.0,
    "items_per_sec": 11.2,
    "latency_ms": {"p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0},
    "batch_size_hist": {"text": {1: 5, 8: 2}, "image": {1: 1}},
    "stage_latency_ms": {"text": {"device": {"p50_ms": 0.5, "p95_ms": 0.9,
                                             "p99_ms": 1.0}}},
    "rejected": 0,
    "timeouts": 1,
    "compile_count": 4,
    "bucket_space": 4,
    "index_size": 64,
    "cache": {"hits": 10, "misses": 3, "hit_rate": 0.77},
    "index_tier": "ann",
    "index_version": 3,
    "shard_count": 1,
    "swap_count": 2,
    "swap_latency_ms": {"p50_ms": 4.0, "p95_ms": 6.0, "p99_ms": 7.0},
    "recall_at_k": 1.0,
    "rerank_k": 40,
    "search_stage_latency_ms": {},
}


def test_render_openmetrics_is_schema_complete():
    """Every snapshot key must be recoverable from the exposition text —
    numerics as gauges, strings on the _info series; tenant-style labels
    stamp EVERY series."""
    text = telemetry_mod.render_openmetrics(
        _SNAPSHOT, labels={"tenant": "t0"}
    )
    for key in _SNAPSHOT:
        assert key in text, f"snapshot field {key} missing from /metrics"
    assert 'dsl_serve_latency_ms{quantile="99",tenant="t0"} 3' in text
    assert 'dsl_serve_qps{tenant="t0"} 8' in text
    assert 'index_tier="ann"' in text
    assert 'stage="text"' in text and 'modality="text"' in text
    assert text.rstrip().endswith("# EOF")
    # every sample line carries the tenant label
    for line in text.splitlines():
        if line.startswith("dsl_serve_") and not line.startswith("# "):
            assert 'tenant="t0"' in line, line


def test_exporter_serves_and_reuses_snapshots():
    calls = [0]

    def snap():
        calls[0] += 1
        return _SNAPSHOT

    with telemetry_mod.TelemetryExporter(snap, refresh_s=5.0) as ex:
        bodies = [
            urllib.request.urlopen(ex.url, timeout=10).read()
            for _ in range(6)
        ]
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/healthz", timeout=10).read())
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{ex.port}/nope", timeout=10)
    assert health == {"ok": True}
    assert calls[0] == 1  # 6 scrapes, ONE snapshot: the reuse contract
    assert len(set(bodies)) == 1
    assert b"dsl_serve_qps" in bodies[0]


def test_write_telemetry_file_atomic(tmp_path):
    path = str(tmp_path / "telemetry.json")
    telemetry_mod.write_telemetry_file(path, {"step": 1})
    telemetry_mod.write_telemetry_file(path, {"step": 2})
    assert json.load(open(path)) == {"step": 2}
    assert os.listdir(tmp_path) == ["telemetry.json"]  # no tmp droppings


@pytest.fixture(scope="module")
def serve_engine():
    import jax
    from flax import linen as nn

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.serve import InferenceEngine
    from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig

    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    imgs = np.zeros((1, 16, 16, 3), np.float32)
    toks = np.zeros((1, 8), np.int32)
    params = nn.meta.unbox(
        model.init(jax.random.key(0), imgs, toks)["params"]
    )
    eng = InferenceEngine.from_model(model, params, batch_buckets=(1, 4))
    eng.warmup()
    return eng


def test_metrics_endpoint_under_concurrent_load_and_hot_swap(serve_engine):
    """The satellite drill: concurrent clients + concurrent scrapers ACROSS
    a live swap_params hot swap — schema-complete /metrics the whole time,
    zero request errors, compile_count flat, bounded endpoint latency."""
    import jax

    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        SERVE_STATS_FIELDS,
    )
    from distributed_sigmoid_loss_tpu.serve import (
        EmbeddingService,
        RetrievalRouter,
        SwapController,
    )

    engine = serve_engine
    rng = np.random.default_rng(3)
    corpus_toks = rng.integers(0, 64, (16, 8), dtype=np.int32)
    corpus = np.concatenate(
        [engine.encode_text(corpus_toks[i: i + 4]) for i in range(0, 16, 4)]
    )
    router = RetrievalRouter(tier="ann", measure_every=4)
    router.publish(corpus)
    old_params = engine.params
    warmed = engine.compile_count
    ctl = SwapController(engine, router)

    def perturbed(seed):
        leaves, tree = jax.tree.flatten(old_params)
        prng = np.random.default_rng(seed)
        return jax.tree.unflatten(tree, [
            np.asarray(l) + 0.02 * prng.standard_normal(
                np.shape(l)).astype(np.asarray(l).dtype)
            for l in leaves
        ])

    errors: list = []
    scrape_latencies: list = []
    scraped_texts: list = []
    stop = threading.Event()
    try:
        with EmbeddingService(engine, index=router, max_wait_ms=2.0) as svc:
            exporter = svc.start_metrics_server(
                labels={"tenant": "t0"}, refresh_s=0.05
            )

            def client(cid):
                crng = np.random.default_rng(50 + cid)
                try:
                    for _ in range(20):
                        q = crng.integers(0, 64, 8, dtype=np.int32)
                        _, ids = svc.search(q, k=3)
                        assert ids.shape[-1] == 3
                except Exception as e:  # noqa: BLE001 — the drill counts them
                    errors.append(e)

            def scraper():
                try:
                    while not stop.is_set():
                        t0 = time.monotonic()
                        body = urllib.request.urlopen(
                            exporter.url, timeout=10).read().decode()
                        scrape_latencies.append(time.monotonic() - t0)
                        scraped_texts.append(body)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(3)]
            threads += [threading.Thread(target=scraper) for _ in range(2)]
            for t in threads:
                t.start()
            for j in range(2):  # live hot swaps mid-traffic, mid-scrape
                ctl.swap(params=perturbed(60 + j), embeddings=corpus)
            for t in threads[:3]:
                t.join(timeout=120)
            stop.set()
            for t in threads[3:]:
                t.join(timeout=30)
            time.sleep(0.1)  # age the cache past refresh_s: a FRESH snapshot
            final = urllib.request.urlopen(
                exporter.url, timeout=10).read().decode()
    finally:
        engine.swap_params(old_params)

    assert errors == [], errors
    assert engine.compile_count == warmed  # flat across swaps AND scrapes
    assert scraped_texts, "scrapers never completed a scrape"
    # schema-complete: the declared serve stats fields appear in the text
    for field in ("qps", "latency_ms", "compile_count", "swap_count",
                  "index_version", "index_tier", "rejected", "timeouts"):
        assert field in SERVE_STATS_FIELDS
        assert field in final, f"{field} missing from final /metrics"
    assert 'tenant="t0"' in final
    assert 'dsl_serve_swap_count{tenant="t0"} 2' in final
    # bounded endpoint latency: generous bound, but a wedged endpoint fails
    assert max(scrape_latencies) < 5.0, max(scrape_latencies)


@pytest.mark.slow
def test_cli_train_writes_atomic_telemetry_file(tmp_path, capsys):
    """`train --obs-dir` mirrors the latest metrics line into telemetry.json
    via atomic rename — step, metrics, and env fingerprint all present.
    Slow tier (a full CLI train run, ~15 s; the atomic-write contract itself
    is pinned standard-tier by test_write_telemetry_file_atomic, per the
    --durations=15 budget rule)."""
    from distributed_sigmoid_loss_tpu.cli import main

    obs = str(tmp_path / "obs")
    rc = main(["train", "--tiny", "--steps", "3", "--batch", "8",
               "--obs-dir", obs, "--log-every", "1"])
    capsys.readouterr()
    assert rc == 0
    tele = json.load(open(os.path.join(obs, "telemetry.json")))
    assert tele["step"] == 3
    assert "loss" in tele["metrics"]
    assert tele["env"]["host"]
    assert not [f for f in os.listdir(obs) if f.startswith(".telemetry")]
