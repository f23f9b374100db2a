"""graftledger: the perf-trajectory ledger and live telemetry export.

Three contract families:

- **Ledger** (`obs/ledger.py`): append/read round trips with torn-line
  tolerance, status classification (a dead backend is ``no-backend``, never
  a 0.0 measurement), backfill from driver-format BENCH_r*/MULTICHIP_r*
  round files (761.74 @ r3 must surface as the last verified headline, with
  the r04/r05 outages excluded from baseline stats), and the bench.py
  ``_emit`` integration.
- **Telemetry** (`obs/telemetry.py` + `serve/service.py`): the ``/metrics``
  endpoint serves a schema-complete OpenMetrics snapshot under concurrent
  scrape+request load ACROSS a live ``swap_params`` hot swap — zero request
  errors, compile_count flat, endpoint latency bounded, snapshot reuse
  actually bounding the render rate; the atomic telemetry file is never torn.
- **CLI**: ``obs ledger`` / ``obs diff`` exit codes and
  rendering.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from distributed_sigmoid_loss_tpu.obs import ledger as ledger_mod
from distributed_sigmoid_loss_tpu.obs import telemetry as telemetry_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# ledger core
# ---------------------------------------------------------------------------


def test_append_read_roundtrip_and_torn_line_tolerance(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    e = ledger_mod.append_record(
        {"metric": "m", "value": 1.5, "unit": "x"}, path=path,
        source="drill", round_hint=7,
    )
    assert e["status"] == "ok" and e["round"] == 7
    assert e["env"]["host"]  # fingerprint always carries the host
    # a process killed mid-append leaves a truncated line — tolerated
    with open(path, "a") as f:
        f.write('{"schema": 1, "record": {"metr')
    ledger_mod.append_record(
        {"metric": "m2", "value": 2.0, "unit": "x"}, path=path
    )
    entries = ledger_mod.read_ledger(path)
    assert [en["record"]["metric"] for en in entries] == ["m", "m2"]


def test_status_classification():
    ok = {"metric": "m", "value": 1.0, "unit": "x"}
    assert ledger_mod.record_status(ok) == "ok"
    assert ledger_mod.record_status(
        {**ok, "value": 0.0, "error": "backend unavailable: hung"}
    ) == "no-backend"
    assert ledger_mod.record_status(
        {**ok, "deferred": True, "error": "signal during a fresh-compile "
         "bench"}
    ) == "deferred"
    assert ledger_mod.record_status(
        {**ok, "error": "child exited rc=1"}
    ) == "error"


def test_fingerprint_reads_initialized_jax():
    import jax

    jax.devices()  # conftest already initialized the CPU platform
    env = ledger_mod.environment_fingerprint()
    assert env["jax"] == jax.__version__
    assert env["device_count"] == len(jax.devices())
    assert "cpu" in env["device_kind"].lower()


def test_disabled_ledger_is_a_noop(tmp_path, monkeypatch):
    monkeypatch.setenv("DSL_LEDGER_PATH", "")
    assert ledger_mod.ledger_path() is None
    assert ledger_mod.append_record(
        {"metric": "m", "value": 1.0, "unit": "x"}
    ) is None


def test_append_never_raises_on_unwritable_path(capsys):
    out = ledger_mod.append_record(
        {"metric": "m", "value": 1.0, "unit": "x"},
        path="/proc/definitely/not/writable/ledger.jsonl",
    )
    assert out is None
    assert "ledger append failed" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# backfill from driver-format round files (the r01-r05 trajectory; the
# committed LEDGER.jsonl rows 0-10 hold the real ones' numbers)
# ---------------------------------------------------------------------------

HEADLINE = "siglip_vitb16_train_pairs_per_sec_per_chip"


def _write_round_files(root) -> str:
    """The driver's round-file formats, with the r01-r05 trajectory: two
    measured headlines, two outage rounds (headline + 32k-equiv records both
    dead), one failed and one passed multichip dryrun."""
    def bench(n, records, rc=0):
        tail = "some stderr noise\n" + "".join(
            json.dumps(r) + "\n" for r in records
        )
        (root / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": rc, "tail": tail,
             "parsed": records[-1]}
        ))

    def ok(value, **extra):
        return {"metric": HEADLINE, "value": value, "unit": "pairs/s/chip",
                **extra}

    def dead(metric):
        return {"metric": metric, "value": 0.0, "unit": "pairs/s/chip",
                "vs_baseline": 0.0,
                "error": "backend unavailable: backend init/compute hung "
                         "past 240s (after 3 attempts)"}

    bench(1, [ok(718.23, vs_baseline=0.653)])
    bench(3, [ok(761.74, vs_baseline=0.692, device_kind="TPU v5 lite",
                 mfu=0.54)])
    for n in (4, 5):
        bench(n, [dead(HEADLINE + "_32k_equiv"), dead(HEADLINE)], rc=1)
    for n, passed in ((1, False), (2, True)):
        (root / f"MULTICHIP_r{n:02d}.json").write_text(json.dumps(
            {"n_devices": 8, "rc": 0 if passed else 1, "ok": passed,
             "skipped": False, "tail": "dryrun output\n"}
        ))
    return str(root)


def test_backfill_true_trajectory_and_idempotence(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    root = _write_round_files(tmp_path)
    added = ledger_mod.backfill_round_files(repo_root=root, path=path)
    assert len(added) == 8  # 2 measured + 2x2 outage records + 2 multichip
    assert ledger_mod.backfill_round_files(repo_root=root, path=path) \
        == []  # idempotent

    traj = ledger_mod.trajectory(ledger_mod.read_ledger(path))
    headline = traj["siglip_vitb16_train_pairs_per_sec_per_chip"]
    by_round = {p["round"]: p for p in headline}
    assert by_round[3]["value"] == 761.74 and by_round[3]["status"] == "ok"
    assert by_round[4]["status"] == "no-backend"
    assert by_round[5]["status"] == "no-backend"

    s = ledger_mod.trajectory_summary(headline)
    # THE acceptance contract: outage rounds never drag the baseline to 0.0.
    assert s["last"]["value"] == 761.74
    assert s["best"] == 761.74
    assert s["excluded"] == 2
    # the 32k stream is ALL outages so far: no baseline, not a 0.0 one
    s32 = ledger_mod.trajectory_summary(
        traj["siglip_vitb16_train_pairs_per_sec_per_chip_32k_equiv"]
    )
    assert s32["n"] == 0 and s32["last"] is None
    # multichip outcomes ride the same stream
    assert {p["round"]: p["value"] for p in traj["multichip_dryrun"]}[2] == 1.0


def test_committed_ledger_holds_the_backfilled_trajectory():
    """The repo ships LEDGER.jsonl pre-backfilled (satellite): the committed
    file itself must already render the true r01-r05 trajectory."""
    entries = ledger_mod.read_ledger(os.path.join(REPO_ROOT, "LEDGER.jsonl"))
    traj = ledger_mod.trajectory(
        entries, metric="siglip_vitb16_train_pairs_per_sec_per_chip"
    )
    pts = traj["siglip_vitb16_train_pairs_per_sec_per_chip"]
    s = ledger_mod.trajectory_summary(pts)
    assert s["last"]["value"] == 761.74  # r3: the last verified headline
    assert s["excluded"] >= 2  # r04/r05 outages excluded from baselines


def test_trajectory_field_fallback_renders_graftcodec_fields(tmp_path):
    """graftcodec's emulation figures (wire_savings_wallclock_ratio,
    dcn_measured_mbps, ...) are FIELDS on other streams' records, not metric
    streams of their own — `--metric <field>` must still render them, with
    the host stream named in the unit column for provenance."""
    path = str(tmp_path / "ledger.jsonl")
    ledger_mod.append_record(
        {"metric": "siglip_vittiny_train_pairs_per_sec_per_chip",
         "value": 900.0, "unit": "pairs/s/chip", "emu_dcn_mbps": 200.0,
         "dcn_measured_mbps": 184.2, "wire_savings_wallclock_ratio": 1.31},
        path=path,
    )
    ledger_mod.append_record(
        {"metric": "siglip_vittiny_train_pairs_per_sec_per_chip",
         "value": 880.0, "unit": "pairs/s/chip", "emu_dcn_mbps": 20.0,
         "dcn_measured_mbps": 18.7, "wire_savings_wallclock_ratio": 2.05},
        path=path,
    )
    entries = ledger_mod.read_ledger(path)
    traj = ledger_mod.trajectory(
        entries, metric="wire_savings_wallclock_ratio"
    )
    pts = traj["wire_savings_wallclock_ratio"]
    assert [p["value"] for p in pts] == [1.31, 2.05]
    assert all(
        p["unit"] == "on siglip_vittiny_train_pairs_per_sec_per_chip"
        for p in pts
    )
    assert all(p["status"] == "ok" for p in pts)
    # a real stream by that name still wins over the fallback
    assert "wire_savings_wallclock_ratio" not in ledger_mod.trajectory(entries)

    from distributed_sigmoid_loss_tpu.cli import main

    assert main(["obs", "ledger", "--ledger", path,
                 "--metric", "wire_savings_wallclock_ratio"]) == 0
    assert main(["obs", "ledger", "--ledger", path,
                 "--metric", "dcn_measured_mbps"]) == 0


def test_diff_records_fields_and_deltas():
    a = {"metric": "m", "value": 100.0, "unit": "x", "gone": 1}
    b = {"metric": "m", "value": 110.0, "unit": "x", "new": 2}
    d = ledger_mod.diff_records(a, b)
    assert d["changed"]["value"]["delta"] == 10.0
    assert d["changed"]["value"]["rel"] == 0.1
    assert d["added"] == ["new"] and d["removed"] == ["gone"]


def test_bench_emit_appends_to_ledger(tmp_path, monkeypatch, capsys):
    """bench.py's _emit (the single emitter repo-ledger-emit pins) appends
    every record — including schema violations — to the ledger."""
    import bench

    path = str(tmp_path / "bench_ledger.jsonl")
    monkeypatch.setenv("DSL_LEDGER_PATH", path)
    bench._emit({"metric": "m", "value": 0.0, "unit": "x",
                 "error": "backend unavailable: drill"})
    bench._emit({"metric": "m2", "value": 1.0, "unit": "x", "bogus": 1})
    capsys.readouterr()
    entries = ledger_mod.read_ledger(path)
    assert [e["status"] for e in entries] == ["no-backend", "ok"]
    assert entries[1]["schema_violations"]  # the violation is recorded too
    assert entries[1]["record"]["bogus"] == 1  # and the record never lost


# ---------------------------------------------------------------------------
# obs ledger / obs diff CLI
# ---------------------------------------------------------------------------


def test_cli_obs_ledger_backfill_and_render(tmp_path, capsys, monkeypatch):
    from distributed_sigmoid_loss_tpu.cli import main

    path = str(tmp_path / "ledger.jsonl")
    # `--backfill` reads the round files beside the package: point it here
    monkeypatch.setattr(ledger_mod, "_REPO_ROOT", _write_round_files(tmp_path))
    assert main(["obs", "ledger", "--ledger", path, "--backfill"]) == 0
    out, err = capsys.readouterr()
    assert "761.74" in out and "no-backend" in out
    assert "last 761.74" in out
    assert "backfilled" in err
    # metric filter + unknown metric
    assert main(["obs", "ledger", "--ledger", path,
                 "--metric", "multichip_dryrun"]) == 0
    out, _ = capsys.readouterr()
    assert "multichip_dryrun" in out and "761.74" not in out
    assert main(["obs", "ledger", "--ledger", path,
                 "--metric", "nope"]) == 2
    capsys.readouterr()
    # empty ledger is a usage error, not a crash
    assert main(["obs", "ledger", "--ledger",
                 str(tmp_path / "void.jsonl")]) == 2
    capsys.readouterr()


def test_cli_obs_diff_selectors_and_errors(tmp_path, capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    path = str(tmp_path / "ledger.jsonl")
    root = _write_round_files(tmp_path)
    ledger_mod.backfill_round_files(repo_root=root, path=path)
    metric = HEADLINE
    assert main(["obs", "diff", f"{metric}@0", f"{metric}@1",
                 "--ledger", path]) == 0
    out, _ = capsys.readouterr()
    assert "718.23" in out and "761.74" in out and "+6.1%" in out
    # flags in ANY position: obs routes through parse_intermixed_args, so
    # the ledger flag may precede or split the two operands (this was the
    # PR 9 argparse-greediness bug — positionals used to swallow the flag)
    for argv in (
        ["obs", "diff", "--ledger", path, f"{metric}@0", f"{metric}@1"],
        ["obs", "diff", f"{metric}@0", "--ledger", path, f"{metric}@1"],
        ["obs", "--ledger", path, "diff", f"{metric}@0", f"{metric}@1"],
    ):
        assert main(argv) == 0, argv
        out, _ = capsys.readouterr()
        assert "+6.1%" in out, argv
    # a round file is a valid operand (its tail's last record)
    assert main(["obs", "diff", f"{metric}@0",
                 os.path.join(root, "BENCH_r03.json"),
                 "--ledger", path]) == 0
    capsys.readouterr()
    assert main(["obs", "diff", f"{metric}@0", "--ledger", path]) == 2
    assert main(["obs", "diff", "bogus@0", f"{metric}@0",
                 "--ledger", path]) == 2
    assert main(["obs", "diff", f"{metric}@99", f"{metric}@0",
                 "--ledger", path]) == 2
    capsys.readouterr()


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
