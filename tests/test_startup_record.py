"""The process's record of start-up (PR 37): ``obs/spans.py``'s recorder (self
time by containment, attrs, counters, the one offset between the two clocks, the
OS's process start, the ring), the compile log of ``utils/compile_cache.py`` (one
span a trace, lowering and backend compile, marked hit or miss; one set of
listeners however often it is configured), the spans at the program's start-up
boundaries, and what ``train --obs-dir`` and ``obs summarize`` make of them. The
benchmark's seven readers of the record are tested in
``benchmark/tests/test_setup_record.py`` (collected by test_benchmark_contract.py).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from distributed_sigmoid_loss_tpu.obs import spans as spans_module
from distributed_sigmoid_loss_tpu.obs.spans import (
    RECORDER,
    Span,
    SpanRecorder,
    from_wall_clock,
    process_start,
    self_times,
    spanned,
    startup_line,
    startup_span,
    startup_split,
    summarize_spans,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def recorder():
    """The process's recorder, on: an earlier test's `train` may have left it as it likes."""
    was = RECORDER.enabled
    RECORDER.enable()
    yield RECORDER
    RECORDER.enabled = was


# -- the recorder --------------------------------------------------------------


def test_self_time_is_the_duration_less_what_the_children_cover():
    own = {
        s.name: t for s, t in self_times([
            Span("init_state", 0.0, 10.0, 1),
            Span("init_params", 1.0, 7.0, 1),
            Span("jax.trace", 2.0, 3.0, 1),
            Span("jax.backend_compile", 3.0, 6.5, 1),
            Span("late", 8.0, 10.5, 1),  # ends after its parent (two clocks): counts to the parent's end
            Span("alone", 20.0, 21.0, 1),
            Span("other_thread", 0.0, 10.0, 2),  # another thread's span is nobody's child
        ])
    }
    assert own == {
        "init_state": 10.0 - 6.0 - 2.0, "init_params": 6.0 - 1.0 - 3.5, "jax.trace": 1.0,
        "jax.backend_compile": 3.5, "late": 2.5, "alone": 1.0, "other_thread": 10.0,
    }


def test_children_that_overlap_count_once_and_grandchildren_not_at_all():
    own = {
        s.name: t for s, t in self_times([
            Span("a", 0.0, 10.0, 1), Span("b", 1.0, 5.0, 1), Span("c", 2.0, 3.0, 1), Span("d", 5.0, 6.0, 1),
        ])
    }
    assert own == {"a": 5.0, "b": 3.0, "c": 1.0, "d": 1.0}


def test_summarize_spans_reports_self_time_beside_the_totals():
    rows = summarize_spans([
        Span("init_state", 0.0, 4.0, 1), Span("jax.trace", 1.0, 2.0, 1), Span("jax.trace", 2.0, 2.5, 1),
    ])
    assert rows["init_state"]["total_ms"] == 4000.0 and rows["init_state"]["self_ms"] == 2500.0
    assert rows["jax.trace"]["count"] == 2 and rows["jax.trace"]["self_ms"] == rows["jax.trace"]["total_ms"] == 1500.0


def test_attrs_and_counters_are_recorded_and_exported(tmp_path):
    from distributed_sigmoid_loss_tpu.cli import _load_host_spans

    rec = SpanRecorder()
    with rec.span("jax.backend_compile", {"fun_name": "jit(step)", "cache": "hit"}, annotate=False):
        pass
    rec.record("plain", 1.0, 2.0)
    rec.count("compile_cache.requests")
    rec.count("compile_cache.requests", 2)
    first, plain = rec.spans()
    assert first.attrs == {"fun_name": "jit(step)", "cache": "hit"} and plain.attrs is None
    assert rec.counters() == {"compile_cache.requests": 3}
    events = [e for e in rec.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert events[0]["args"] == first.attrs and "args" not in events[1]
    rec.export(str(tmp_path / "host_spans.trace.json"))
    _, loaded, counters = _load_host_spans(str(tmp_path))
    assert [s.attrs for s in loaded] == [first.attrs, None] and counters == {"compile_cache.requests": 3}
    rec.clear()
    assert rec.spans() == [] and rec.counters() == {}
    rec.disable()
    rec.count("compile_cache.requests")
    assert rec.counters() == {}


def test_one_offset_moves_the_wall_clock_onto_the_recorders():
    a, b = 1_700_000_000.0, 1_700_000_012.5
    assert from_wall_clock(b) - from_wall_clock(a) == pytest.approx(12.5, abs=1e-6)  # one offset: order and lengths stay
    assert from_wall_clock(time.time()) == pytest.approx(time.perf_counter(), abs=0.05)


def test_process_start_is_the_os_s_and_earlier_than_any_span(monkeypatch):
    start = process_start()
    assert start is not None  # this sandbox and the chip's machine have /proc
    first = min(s.t0 for s in RECORDER.spans() if s.name == "startup.import") if RECORDER.dropped == 0 else None
    assert start < time.perf_counter() and (first is None or start < first)
    assert process_start() == start  # one reading a process
    assert process_start.__wrapped__() == pytest.approx(start, abs=0.05)  # the same instant whenever it is taken

    def no_proc(*args, **kwargs):
        raise OSError("no /proc here")

    monkeypatch.setattr("builtins.open", no_proc)
    assert process_start.__wrapped__() is None


def test_the_ring_is_still_bounded():
    assert RECORDER.capacity == 8192 and RECORDER._spans.maxlen == 8192
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", float(i), i + 0.5, attrs={"i": i})
    assert [s.name for s in rec.spans()] == [f"s{i}" for i in range(12, 20)] and rec.dropped == 12


def test_obs_imports_and_opens_a_start_up_span_without_jax_s_backend_or_profiler():
    code = (
        "import distributed_sigmoid_loss_tpu.obs\n"
        "from distributed_sigmoid_loss_tpu.obs import spans\n"
        "import jax.profiler\n"
        "def boom(*a, **k): raise AssertionError('a start-up span opened an annotation')\n"
        "jax.profiler.TraceAnnotation = boom\n"
        "with spans.startup_span('startup.x', {'k': 1}): pass\n"
        "names = [s.name for s in spans.RECORDER.spans()]\n"
        "assert names == ['startup.import', 'startup.x'], names\n"
        "assert spans.RECORDER.spans()[0].t0 > spans.process_start()\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'a backend was initialized'\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr


def test_spanned_wraps_a_call_in_a_start_up_span(recorder):
    @spanned("startup.test_boundary")
    def boundary(x, fail=False):
        """doc"""
        if fail:
            raise ValueError("inside")
        return x + 1

    assert boundary.__name__ == "boundary" and boundary.__doc__ == "doc"
    before = len([s for s in recorder.spans() if s.name == "startup.test_boundary"])
    assert boundary(1) == 2
    with pytest.raises(ValueError):
        boundary(1, fail=True)
    assert len([s for s in recorder.spans() if s.name == "startup.test_boundary"]) == before + 2
    recorder.disable()
    assert boundary(2) == 3 and startup_span("startup.off") is recorder.span("anything")  # the shared no-op
    assert len([s for s in recorder.spans() if s.name == "startup.test_boundary"]) == before + 2


def test_startup_split_names_the_outermost_spans_and_the_rest():
    spans = [
        Span("startup.import", 1.0, 3.0, 1), Span("init_state", 4.0, 8.0, 1), Span("jax.trace", 5.0, 6.0, 1),
        Span("jax.trace", 8.5, 9.0, 1), Span("h2d_commit", 0.0, 10.0, 2),
    ]
    assert startup_split(spans, 0.0, 10.0, tid=1) == {
        "startup.import": 2.0, "init_state": 4.0, "jax.trace": 0.5, "unnamed": 3.5,
    }
    assert startup_split(spans, 0.0, 5.0, tid=1) == {"startup.import": 2.0, "init_state": 1.0, "unnamed": 2.0}
    line = startup_line(
        [Span("startup.import", time.perf_counter() - 1.0, time.perf_counter(), threading.get_ident())],
        time.perf_counter(),
    )
    assert line.startswith("startup: ") and "startup.import 1.0" in line and "unnamed" in line


# -- the compile log -----------------------------------------------------------

COMPILE_LOG_SCRIPT = """
import json
from distributed_sigmoid_loss_tpu.utils.compile_cache import cache_dir_stats, configure_compile_cache
path = configure_compile_cache()
assert configure_compile_cache() == configure_compile_cache() == path
import jax
from jax._src import monitoring
from distributed_sigmoid_loss_tpu.obs.spans import RECORDER

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def a_function_of_the_test(x):
    return jax.numpy.tanh(x) * 2.0

x = jax.numpy.arange(8.0)
for _ in range(2):  # from one line: the key holds the call's source line (utils/compile_cache.py)
    a_function_of_the_test(x).block_until_ready()
    jax.clear_caches()
mine = [s for s in RECORDER.spans() if "a_function_of_the_test" in (s.attrs or {}).get("fun_name", "")]
print(json.dumps({
    "listeners": [len(monitoring.get_event_listeners()), len(monitoring.get_event_duration_listeners()),
                  len(monitoring.get_event_time_span_listeners()), len(monitoring.get_scalar_listeners())],
    "mine": [[s.name, s.attrs, s.t0, s.t1] for s in mine],
    "counters": RECORDER.counters(),
    "cache_spans": [s.attrs for s in RECORDER.spans() if s.name == "startup.compile_cache"],
    "dir_now": cache_dir_stats(path), "path": path,
    "marks": [s.attrs.get("cache") for s in RECORDER.spans() if s.name == "jax.backend_compile"],
}))
"""


def test_the_compile_log_marks_a_miss_and_then_a_hit_under_the_function_s_name(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", COMPILE_LOG_SCRIPT], capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["listeners"] == [1, 1, 1, 1]  # configured three times, one set of listeners
    assert got["path"] == str(tmp_path / "cache")
    compiles = [(attrs["cache"], attrs) for name, attrs, _, _ in got["mine"] if name == "jax.backend_compile"]
    assert [mark for mark, _ in compiles] == ["miss", "hit"]
    assert compiles[1][1]["retrieval_s"] > 0 and "retrieval_s" not in compiles[0][1]
    assert all(attrs["fun_name"] == "jit(a_function_of_the_test)" for _, attrs in compiles)
    # each compile has its trace and its lowering before it, on the recorder's clock
    assert [name for name, _, _, _ in got["mine"]] == ["jax.trace", "jax.lower", "jax.backend_compile"] * 2
    assert all(t1 >= t0 for _, _, t0, t1 in got["mine"])
    assert [t0 for _, _, t0, _ in got["mine"]] == sorted(t0 for _, _, t0, _ in got["mine"])
    counters = got["counters"]
    assert counters["compile_cache.requests"] == counters["compile_cache.hits"] + counters["compile_cache.misses"]
    assert counters["compile_cache.hits"] == got["marks"].count("hit") >= 1
    assert counters["compile_cache.misses"] == got["marks"].count("miss") >= 1
    # the first call found the directory empty and said so on its span; the same reading later sees what the run added
    assert got["cache_spans"] == [{"dir": got["path"], "bytes": 0, "entries": 0}] * 3
    assert got["dir_now"]["entries"] >= 1 and got["dir_now"]["bytes"] > 0


def test_with_the_cache_off_a_compile_is_marked_off_and_nothing_is_counted(tmp_path):
    script = COMPILE_LOG_SCRIPT.replace('jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)',
                                        'jax.config.update("jax_enable_compilation_cache", False)')
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR="", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["path"] == "" and set(got["marks"]) == {"off"} and got["counters"] == {}
    assert got["dir_now"] == {"bytes": 0, "entries": 0}


def test_nested_traces_leave_one_span_the_outermost(recorder):
    """Tracing a function traces every jitted function it calls; the record keeps the outer one."""
    import jax
    import jax.numpy as jnp

    inner = jax.jit(lambda x: jnp.sin(x) + 1.0)

    def outer_of_the_nesting_test(x):
        return inner(x) * inner(x + 1.0)

    x = jnp.arange(4.0)  # an eager op traces too: before the mark
    mark = time.perf_counter()  # not an index: the ring may be full and turning over
    jax.jit(outer_of_the_nesting_test).lower(x)
    new = [s for s in recorder.spans() if s.t0 >= mark]
    traces = [s for s in new if s.name == "jax.trace"]
    assert [s.attrs["fun_name"] for s in traces] == ["outer_of_the_nesting_test"]
    assert [s.attrs["fun_name"] for s in new if s.name == "jax.lower"] == ["jit(outer_of_the_nesting_test)"]
    assert traces[0].tid == threading.get_ident()


# -- the program's start-up boundaries -----------------------------------------


def test_the_boundaries_record_their_spans_where_the_work_happens(recorder):
    import jax
    import jax.numpy as jnp

    from distributed_sigmoid_loss_tpu.models.siglip import SigLIP
    from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
    from distributed_sigmoid_loss_tpu.train import create_train_state, make_optimizer, make_train_step
    from distributed_sigmoid_loss_tpu.utils.config import SigLIPConfig, TrainConfig

    mark = time.perf_counter()  # not an index: the ring may be full and turning over
    cfg = SigLIPConfig.tiny_test()
    mesh = make_mesh(2)
    model = SigLIP(cfg)
    step, _ = make_train_step(model, mesh, cfg.loss)
    shapes = {
        "images": jax.ShapeDtypeStruct((4, cfg.vision.image_size, cfg.vision.image_size, 3), jnp.float32),
        "tokens": jax.ShapeDtypeStruct((4, cfg.text.context_length), jnp.int32),
    }
    state = create_train_state(jax.random.key(0), model, make_optimizer(TrainConfig()), shapes, mesh)
    assert int(state.step) == 0 and callable(step)
    new = [s for s in recorder.spans() if s.t0 >= mark]
    names = [s.name for s in new]
    for name in ("startup.mesh", "startup.step_builder", "init_params", "init_state"):
        assert names.count(name) == 1, (name, names)
    by_name = {s.name: s for s in new if not s.name.startswith("jax.")}
    assert by_name["init_state"].t0 <= by_name["init_params"].t0 <= by_name["init_params"].t1 <= by_name["init_state"].t1
    # jax's spans of the two init programs fall inside `init_state` by containment: its self time is less than its length
    inside = [s for s in new if s.name.startswith("jax.") and by_name["init_state"].t0 <= s.t0 <= by_name["init_state"].t1]
    assert {"jax.trace", "jax.backend_compile"} <= {s.name for s in inside}
    own = {s.name: t for s, t in self_times(new) if s.name == "init_state"}["init_state"]
    assert own < by_name["init_state"].duration_s - sum(s.duration_s for s in inside if s.name == "jax.backend_compile") + 1e-6
    # the package's import is the record's first span (unless the ring has turned over since)
    if recorder.dropped == 0:
        assert recorder.spans()[0].name == "startup.import"
    # the wrappers sit at the files' ends and their frames stay out of the programs' locations
    from jax._src import source_info_util

    assert not source_info_util.is_user_filename(spans_module.__file__)


# -- train --obs-dir and obs summarize -----------------------------------------


def test_train_obs_dir_writes_the_start_up_spans_and_prints_the_split(tmp_path, capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    obs = str(tmp_path / "obs")
    RECORDER.enable()
    assert main(["train", "--tiny", "--steps", "2", "--batch", "8", "--obs-dir", obs, "--log-every", "1"]) == 0
    err = capsys.readouterr().err
    lines = [line for line in err.splitlines() if line.startswith("startup: ")]
    assert len(lines) == 1, err  # once, at the first step
    assert "from process start to the end of the first step" in lines[0]
    assert "init_state" in lines[0] and "step" in lines[0] and "unnamed" in lines[0]
    trace = json.load(open(os.path.join(obs, "host_spans.trace.json")))
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in events}
    assert {"init_state", "init_params", "startup.mesh", "startup.step_builder", "step", "h2d_commit",
            "jax.trace", "jax.lower", "jax.backend_compile"} <= names
    compiles = [e["args"] for e in events if e["name"] == "jax.backend_compile"]
    assert all(a["cache"] in ("hit", "miss", "off") and a["fun_name"] for a in compiles)
    counters = trace["counters"]
    assert counters["compile_cache.requests"] == counters.get("compile_cache.hits", 0) + counters.get("compile_cache.misses", 0)
    assert main(["obs", "summarize", obs]) == 0
    out = capsys.readouterr().out
    assert "self ms" in out and "init_state" in out and "counters: compile_cache." in out


def test_train_without_obs_dir_keeps_the_loop_s_sites_the_no_op(capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    RECORDER.enable()
    mark = time.perf_counter()
    assert main(["train", "--tiny", "--steps", "1", "--batch", "8"]) == 0
    err = capsys.readouterr().err
    assert len([line for line in err.splitlines() if line.startswith("startup: ")]) == 1
    assert not [s for s in RECORDER.spans() if s.name in ("step", "h2d_commit") and s.t0 >= mark]
    assert [s for s in RECORDER.spans() if s.name == "init_state" and s.t0 >= mark]  # set-up is recorded either way
    assert not RECORDER.enabled  # disabled before the loop
    RECORDER.enable()
