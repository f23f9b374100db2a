"""What every job of the benchmark shares: finding a cell's files by name, the
device check, the compile cache, set-up timing, the profiler window, the counters
and the one JSON line. Nothing here belongs to one cell: a configuration, a
traffic mix, a job and a per-layer metric are files of their own, found by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
REHEARSAL_DIR = os.path.join(BENCH_DIR, "tests", "rehearsal")
TRACE_DIR = os.path.join(CHECKOUT, ".bench_trace")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The fields of ``compiled.memory_analysis()`` the benchmark prints.
MEMORY_FIELDS = (
    "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
    "temp_size_in_bytes", "generated_code_size_in_bytes", "peak_memory_in_bytes",
)


class Refused(SystemExit):
    """The run cannot measure here: exit non-zero, print no result."""

    def __init__(self, why: str):
        print(f"benchmark: refused: {why}", file=sys.stderr)
        super().__init__(1)


# -- files found by name ------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> types.ModuleType:
    if not os.path.isfile(path):
        raise Refused(f"no such file: {os.path.relpath(path, CHECKOUT)}")
    name = "bench_" + os.path.relpath(path, BENCH_DIR)[:-3].replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files."""

    def __init__(self, name: str, rehearse: bool):
        # A rehearsal reads the same three kinds of file from benchmark/tests/rehearsal.
        root, data_dir = (REHEARSAL_DIR, REHEARSAL_DIR) if rehearse else (CHECKOUT, BENCH_DIR)
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in self.manifest["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in self.manifest["workloads"])
            raise Refused(f"no workload {name!r}; the manifest has: {known}")
        self.name = name
        self.chips = entry["chips"]
        config = next(c for c in self.manifest["configs"] if c["name"] == entry["config"])
        self.config = load_json(os.path.join(root, config["file"]))
        self.traffic = load_json(os.path.join(data_dir, "traffic", entry["traffic"] + ".json"))
        if self.traffic["chips"] != self.chips:
            raise Refused(f"mix {entry['traffic']!r} is for {self.traffic['chips']} chips, the cell says {self.chips}")

    def metrics(self, kind: str) -> list[dict]:
        """The manifest's ``end_to_end`` or ``per_layer`` metrics of this cell."""
        return [
            m for m in self.manifest[kind]
            if "workloads" not in m or self.name in m["workloads"]
        ]


def peaks_for(device_kind: str) -> dict | None:
    return load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"].get(device_kind)


# -- the device ---------------------------------------------------------------


def prepare_environment(cell: Cell, rehearse: bool) -> None:
    """Before jax is imported. A rehearsal gets the CPU with as many virtual
    devices as the cell has chips; a measurement takes what the machine has."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}"
        ).strip()
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)


def require_devices(cell: Cell, rehearse: bool):
    """The cell's devices and their peaks, or a refusal: a measurement needs
    TPUs that the peaks table lists, as many as the cell asks for."""
    try:
        from distributed_sigmoid_loss_tpu.utils.compile_cache import configure_compile_cache
    except ImportError as e:
        raise Refused(f"the program is not in this checkout ({e})")

    cache_dir = configure_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"jax found no backend: {e}")
    d0 = devices[0]
    print(f"benchmark: platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devices)} cache_dir={cache_dir!r}", file=sys.stderr)
    peaks = peaks_for(d0.device_kind)
    if not rehearse and (d0.platform != "tpu" or peaks is None):
        raise Refused(
            f"need a TPU listed in benchmark/peaks.json, found {d0.platform} "
            f"{d0.device_kind!r}; --rehearse runs the control flow on the CPU"
        )
    if len(devices) < cell.chips:
        raise Refused(f"the cell needs {cell.chips} chips, jax reports {len(devices)}")
    return devices[: cell.chips], peaks


class MemoryWatch:
    """The most memory held on the fullest chip, sampled by the job while it
    holds its state and its loaded programs. On this runtime the allocator's
    ``bytes_in_use`` counts live arrays only and leaves out what a loaded program
    needs for its temporaries, which the runtime sets aside as ``bytes_reserved``
    (b16-bs256, PR 22: 3.08 GB in use and 7.81 GB reserved after the window, where
    the compiler's peak for the step is 10.30 GB). A sample adds the two as they
    stand; the high-water mark of live arrays alone counts where it is larger (a
    set-up program that held more than the step). Both fall back once the job
    returns and its arrays and programs are freed, so the job samples, after its
    warm-up and after its window, and nothing is read afterwards."""

    def __init__(self, devices):
        self.devices = devices
        self.peak_bytes = 0
        self.samples = 0

    def sample(self) -> int:
        self.samples += 1
        for d in self.devices:
            s = d.memory_stats() or {}
            self.peak_bytes = max(
                self.peak_bytes,
                int(s.get("bytes_in_use", 0)) + int(s.get("bytes_reserved", 0)),
                int(s.get("peak_bytes_in_use", 0)),
            )
        return self.peak_bytes


def device_report(devices, memory: MemoryWatch) -> dict:
    """``device`` of the result line, as jax reports it."""
    import jax

    if not memory.samples:
        raise RuntimeError("the job never called ctx.memory.sample() while it held its state")
    d0 = devices[0]
    return {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": memory.peak_bytes,
    }


# -- set-up timing and counters -----------------------------------------------


class Phases:
    """Host-clock split of ``setup_s``: each phase runs from the end of the one
    before, so the phases add up to the set-up."""

    def __init__(self, process_start: float):
        self.start = process_start
        self.last = process_start
        self.seconds: dict[str, float] = {}

    def done(self, name: str) -> float:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - self.last
        self.last = now
        return self.seconds[name]

    def total(self) -> float:
        return self.last - self.start


class CompileCounter:
    """What jax reports of its compilations. ``in_window`` counts backend
    compilations (cache loads included) while ``armed``: the window must see
    none, whatever function they come from. ``requests`` and ``hits`` are the
    persistent cache's, over the whole run: a run after the first of a cell has
    to find every program there."""

    def __init__(self):
        import jax.monitoring

        self.in_window = self.requests = self.hits = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if self.armed and event == BACKEND_COMPILE_EVENT:
            self.in_window += 1

    def _on_event(self, event: str, **_):
        self.requests += event == "/jax/compilation_cache/compile_requests_use_cache"
        self.hits += event == "/jax/compilation_cache/cache_hits"


# -- the profiler window ------------------------------------------------------


class TraceWindow:
    """The jax profiler over a few steady steps, written under the checkout at a
    fixed path. Host spans go in through ``span`` on the same clock."""

    def __init__(self, cell_name: str, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(TRACE_DIR, cell_name)
        self.xplane: str | None = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans come from `span`, not from frames
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self) -> None:
        if not self.enabled:
            return
        import jax

        jax.profiler.stop_trace()
        found = []
        for base, _, files in os.walk(self.dir):
            found += [os.path.join(base, f) for f in files if f.endswith(".xplane.pb")]
        if not found:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.dir}")
        self.xplane = max(found, key=os.path.getmtime)

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)


# -- the result ---------------------------------------------------------------


def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own reader,
    ``layer_metrics/<name>.py``; a reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        reader = load_module(os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def info_line(tag: str, **fields) -> None:
    """An earlier line of standard output: for people, not for the driver."""
    print(json.dumps({"info": tag, **fields}), flush=True)


def result_line(result: dict) -> None:
    """The result as the last line of standard output, and what ``correct``
    compared, each number beside its limit, as the last lines of standard error."""
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result.get("compared", {}).items():
        print(f"benchmark: compared {name} = {value!r}, limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
