"""BENCHMARK.json against the contract's limits and against the files it names."""

import importlib.util
import json
import os
import re

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
MANIFEST = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("width", "hidden", "intermediate", "latent", "state", "proj", "head", "_dim", "_rank", "mlp_ratio")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def load(path):
    spec = importlib.util.spec_from_file_location("m", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert 2 <= len(MANIFEST["workloads"]) <= 24 and 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16 and 1 <= len(MANIFEST["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 4)


def test_names_units_and_lines():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in MANIFEST[group]}) == len(MANIFEST[group])
    for x in MANIFEST["configs"] + MANIFEST["workloads"] + METRICS:
        assert NAME.match(x["name"]), x["name"]
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"], w["name"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert not any(word in key for key in c["reduced"] for word in WIDTH_WORDS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics():
    by_name = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in by_name and by_name["setup_s"]["bound"] <= 0.1
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


# What jobs/train_step.py and the readers ask of the two files a configuration
# names under "modules" (benchmark/README.md, "What a configuration brings").
REFERENCE_INTERFACE = (
    "first_blocks", "microbatch_rows", "make_batch_loss", "make_sample_grads", "tree_max_rel_err", "max_rel_err",
    "EMBED_BOUND", "LOSS_BOUND", "GRAD_BOUND",
)
COUNT_INTERFACE = ("train_flops_per_pair", "attention_least_s")


def assert_modules_offer_the_interface(config_body):
    """conftest.py has put benchmark/ on sys.path: the modules import each other by name."""
    assert set(config_body["modules"]) == {"reference", "count"}
    for kind, interface in (("reference", REFERENCE_INTERFACE), ("count", COUNT_INTERFACE)):
        path = os.path.join(BENCH_DIR, config_body["modules"][kind] + ".py")
        assert os.path.isfile(path), path
        module = load(path)
        assert not [name for name in interface if not hasattr(module, name)], path
        assert all(callable(getattr(module, name)) for name in interface if name.islower())
        assert all(0 < getattr(module, name) < 1 for name in interface if name.isupper())


def test_the_rehearsal_configurations_name_their_modules_too():
    rehearsal = os.path.join(BENCH_DIR, "tests", "rehearsal")
    for config in json.load(open(os.path.join(rehearsal, "BENCHMARK.json")))["configs"]:
        assert_modules_offer_the_interface(json.load(open(os.path.join(rehearsal, config["file"]))))


@pytest.mark.parametrize("cell", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_every_cell_has_its_files(cell):
    config = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    assert config["file"].startswith("benchmark/")
    body = json.load(open(os.path.join(CHECKOUT, config["file"])))
    assert body["source"] == config["source"] and body["reduced"] == config["reduced"]
    for section in ("vision", "text", "loss", "assumed", "deployment"):
        assert section in body
    mix = json.load(open(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")))
    assert mix["chips"] == cell["chips"]
    assert mix["microbatch"] * mix["step"]["accum_steps"] == mix["pairs_per_chip_per_step"]
    assert os.path.isfile(os.path.join(BENCH_DIR, "jobs", mix["job"] + ".py"))
    assert_modules_offer_the_interface(body)
    for kind in ("end_to_end", "per_layer"):
        assert any("workloads" not in m or cell["name"] in m["workloads"] for m in MANIFEST[kind])


def test_every_config_is_used():
    assert {c["name"] for c in MANIFEST["configs"]} == {w["config"] for w in MANIFEST["workloads"]}


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_reader(metric):
    """One file per metric, describing itself as the manifest does."""
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    assert 1 <= len(metric["layer"]) <= 200
    reader = load(os.path.join(BENCH_DIR, "layer_metrics", metric["name"] + ".py"))
    assert callable(reader.read)
    meta = dict(reader.META)
    workloads = meta.pop("workloads")
    assert metric.get("workloads") == workloads
    assert {k: metric[k] for k in meta} == meta


def test_no_reader_without_an_entry():
    listed = {m["name"] for m in MANIFEST["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert files == listed


def test_layers_are_the_ones_perf_md_lists():
    perf = open(os.path.join(CHECKOUT, "PERF.md")).read()
    for layer in {m["layer"] for m in MANIFEST["per_layer"]}:
        assert f"**{layer}**" in perf, layer


def test_file_names_under_paths():
    for base, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)
