"""The seven set-up readers on a recorder filled by hand: what each cuts from the
program's record of start-up (benchmark/setup_record.py), 0.0 and never None where
nothing compiled or the cache is off, and None only where the program keeps no record."""

import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH_DIR, os.path.dirname(BENCH_DIR)):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402
import setup_record  # noqa: E402
from distributed_sigmoid_loss_tpu.obs.spans import SpanRecorder  # noqa: E402

READERS = (
    "startup_s", "import_s", "init_s", "trace_lower_total_s", "backend_compile_s", "cache_load_s",
    "compile_cache_hit_pct",
)


def read(name, ctx):
    return harness.load_module(os.path.join(BENCH_DIR, "layer_metrics", name + ".py")).read(ctx)


def a_warm_run() -> dict:
    """A process started at 100.0 whose cache served two programs of five."""
    rec = SpanRecorder()
    rec.record("startup.import", 100.5, 104.5, tid=1)
    rec.record("startup.compile_cache", 104.5, 104.6, tid=1, attrs={"dir": "/c", "bytes": 7, "entries": 1})
    rec.record("startup.mesh", 110.0, 110.25, tid=1)
    rec.record("startup.step_builder", 110.25, 110.5, tid=1)
    # init: two programs inside `init_state`, the first inside `init_params`
    rec.record("jax.trace", 120.0, 122.0, tid=1, attrs={"fun_name": "init_fn"})
    rec.record("jax.trace", 120.5, 121.0, tid=1, attrs={"fun_name": "inner"})  # nested: counts once
    rec.record("jax.lower", 122.0, 123.0, tid=1, attrs={"fun_name": "jit(init_fn)"})
    rec.record("jax.backend_compile", 123.0, 126.0, tid=1, attrs={"fun_name": "jit(init_fn)", "cache": "hit", "retrieval_s": 2.5})
    rec.record("init_params", 119.5, 127.0, tid=1)
    rec.record("jax.backend_compile", 127.0, 127.5, tid=1, attrs={"fun_name": "jit(create)", "cache": "miss"})
    rec.record("init_state", 119.0, 131.0, tid=1)
    # the step
    rec.record("jax.trace", 140.0, 150.0, tid=1, attrs={"fun_name": "step"})
    rec.record("jax.lower", 150.0, 155.0, tid=1, attrs={"fun_name": "jit(step)"})
    rec.record("jax.backend_compile", 155.0, 162.0, tid=1, attrs={"fun_name": "jit(step)", "cache": "hit"})
    # a program the cache was not asked about, and a small one compiled on another thread meanwhile
    rec.record("jax.backend_compile", 170.0, 171.0, tid=1, attrs={"fun_name": "jit(f)", "cache": "off"})
    rec.record("jax.backend_compile", 170.5, 171.25, tid=2, attrs={"fun_name": "jit(g)", "cache": "miss"})
    rec.count("compile_cache.requests", 5)
    rec.count("compile_cache.hits", 2)
    rec.count("compile_cache.misses", 3)
    return {"recorder": rec, "process_start": 100.0}


def test_each_reader_cuts_what_it_says():
    ctx = a_warm_run()
    assert read("startup_s", ctx) == 20.0  # to the first trace span, not to the first span
    assert read("import_s", ctx) == 4.0
    assert read("init_s", ctx) == 12.0  # children included
    assert read("trace_lower_total_s", ctx) == 2.0 + 1.0 + 10.0 + 5.0  # the nested trace once
    assert read("backend_compile_s", ctx) == 0.5 + 1.25  # miss and off; the two that overlap once
    assert read("cache_load_s", ctx) == 3.0 + 7.0
    assert read("compile_cache_hit_pct", ctx) == 40.0


def test_without_proc_the_start_is_the_import_spans():
    ctx = a_warm_run()
    ctx["process_start"] = None
    assert read("startup_s", ctx) == 19.5


@pytest.mark.parametrize("name", READERS)
def test_an_empty_record_reads_zero_and_never_none(name):
    value = read(name, {"recorder": SpanRecorder(), "process_start": 100.0})
    assert value == 0.0 and isinstance(value, float)


@pytest.mark.parametrize("name", READERS)
def test_with_the_cache_off_every_reader_still_reads_a_number(name):
    """No request, no hit: the compiles are real ones and the hit share is 0.0."""
    rec = SpanRecorder()
    rec.record("startup.import", 100.5, 101.5, tid=1)
    rec.record("jax.trace", 103.0, 104.0, tid=1, attrs={"fun_name": "f"})
    rec.record("jax.backend_compile", 104.0, 106.0, tid=1, attrs={"fun_name": "jit(f)", "cache": "off"})
    value = read(name, {"recorder": rec, "process_start": 100.0})
    assert isinstance(value, float)
    expected = {"startup_s": 3.0, "import_s": 1.0, "trace_lower_total_s": 1.0, "backend_compile_s": 2.0}
    assert value == expected.get(name, 0.0)


@pytest.mark.parametrize("name", READERS)
def test_the_process_s_own_record_is_read_where_none_is_given(name):
    """As run.py calls it: no recorder in the context, the program's is alive in the process."""
    value = read(name, {})
    assert isinstance(value, float) and value >= 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_record_reads_nothing_and_does_not_raise(name, monkeypatch):
    """The parent of PR 37 has obs/spans.py and no RECORDER in it."""
    from distributed_sigmoid_loss_tpu.obs import spans

    monkeypatch.delattr(spans, "RECORDER")
    assert read(name, {}) is None
