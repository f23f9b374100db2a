"""The benchmark's own chip-free tests, run by the gate the driver runs: the
manifest against the contract and the files it names, the count modules by hand,
the readers' cuts, the (0, 100] sweep of every reader with ``roofline`` or
``mfu`` in its name, and the set-up readers on a recorder filled by hand. ``benchmark/tests`` holds the files (``python -m pytest
benchmark/tests -q`` runs them with the rehearsals, which start processes and stay
there); each is loaded by path and its cases are collected here under its name."""

import importlib.util
import os
import sys
import types

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
# As benchmark/tests/conftest.py: the benchmark's files find each other by name.
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

FILES = (
    "test_manifest", "test_flops", "test_flops_looped", "test_scopes", "test_flops_kimi", "test_scopes_kimi",
    "test_flops_glm", "test_scopes_glm", "test_setup_record", "test_flops_eva", "test_scopes_eva",
    "test_flops_nemotron", "test_scopes_nemotron", "test_flops_trinity", "test_scopes_trinity",
)


def _collect(stem: str) -> dict:
    """The cases of benchmark/tests/<stem>.py, loaded under a name of its own
    (tests/ has a test_scopes.py too)."""
    spec = importlib.util.spec_from_file_location("benchmark_" + stem, os.path.join(BENCH_DIR, "tests", stem + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {
        f"test_{stem[5:]}__{name[5:]}": case
        for name, case in vars(module).items() if name.startswith("test_") and callable(case)
    }


for _stem in FILES:
    globals().update(_collect(_stem))


# The three readers of the text tower's `short_attn_*` kernels say `"workloads": None` in
# their META, in files of the accepted benchmark. A text tower with no such layer reads
# nothing there, and the driver's contract then has the manifest list the accepted cells
# for them, which `test_manifest.py` holds against META letter for letter. Until a
# `benchmark` PR writes the list into the three files, these cases hold the list to what
# it must be: every cell whose count module gives the text tower a least time for those
# kernels. Everything else the case asks stands.
LISTED_BEFORE_THEIR_READERS = ("attn_text_fwd_ms", "attn_text_bwd_ms", "attn_text_roofline_pct")
_reader_case = globals()["test_manifest__per_layer_metric_has_its_reader"]


def _cells_with_text_attention() -> list[str]:
    import harness  # benchmark/ is on sys.path

    peaks = harness.peaks_for("TPU v5 lite")
    names = []
    for entry in harness.load_json(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))["workloads"]:
        raw = harness.Cell(entry["name"], rehearse=False).config
        count = harness.load_module(os.path.join(BENCH_DIR, raw["modules"]["count"] + ".py"))
        cfg = types.SimpleNamespace(vision=types.SimpleNamespace(**raw["vision"]), text=types.SimpleNamespace(**raw["text"]))
        if count.attention_least_s(cfg, "textual", 1, peaks) > 0:
            names.append(entry["name"])
    return names


def test_manifest__per_layer_metric_has_its_reader(metric):
    if metric["name"] in LISTED_BEFORE_THEIR_READERS:
        assert metric["workloads"] == _cells_with_text_attention()
        metric = {k: v for k, v in metric.items() if k != "workloads"}
    _reader_case(metric)


test_manifest__per_layer_metric_has_its_reader.pytestmark = _reader_case.pytestmark


# `test_scopes_glm.py`, a file of the accepted benchmark, counts the per-layer metrics of its
# cell: 20 at PR 35. PR 37 adds seven set-up readers with no `workloads` list (every cell has a
# set-up), and may not edit that file. Until a `benchmark` PR writes the new count into it, the
# case runs here with those seven left out of the cell's list, and this file holds that the cell
# reports exactly them besides.
SETUP_READERS = (
    "startup_s", "import_s", "init_s", "trace_lower_total_s", "backend_compile_s", "cache_load_s",
    "compile_cache_hit_pct",
)
_glm_reader_case = globals()["test_scopes_glm__every_reader_of_the_cell_reads_the_hand_made_step"]


def test_scopes_glm__every_reader_of_the_cell_reads_the_hand_made_step(monkeypatch):
    import harness

    metrics = harness.Cell.metrics
    of_the_cell = {m["name"] for m in metrics(harness.Cell("glm-b16-p16-s4096", rehearse=False), "per_layer")}
    assert set(SETUP_READERS) <= of_the_cell and len(of_the_cell) == 20 + len(SETUP_READERS)
    monkeypatch.setattr(
        harness.Cell, "metrics",
        lambda self, kind: [m for m in metrics(self, kind) if m["name"] not in SETUP_READERS],
    )
    _glm_reader_case()
