#!/usr/bin/env python3
"""Record the trace fixtures on the chip:

    chiprun --chips 1 -- python benchmark/tests/record_fixture.py fixture-1chip
    chiprun --chips 4 -- python benchmark/tests/record_fixture.py fixture-4chip

Runs a fixture cell of ``tests/rehearsal/BENCHMARK.json`` (real B/16 widths, two
blocks) through the benchmark's own job for two seconds with the profiler on, and
copies the ``.xplane.pb`` to ``chiprun_out/<cell>.xplane.pb``. ``trim_xplane.py``
then cuts it to the few hundred kB kept under ``tests/fixtures``.
"""

from __future__ import annotations

import os
import shutil
import sys
import time
import types

START = time.perf_counter()
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import harness  # noqa: E402


def main() -> int:
    cell = harness.Cell(sys.argv[1], rehearse=True)  # the files under tests/rehearsal
    harness.prepare_environment(cell, rehearse=False)  # ... on the real device
    devices, peaks = harness.require_devices(cell, rehearse=False)
    job = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", cell.traffic["job"] + ".py"))
    out = job.run(types.SimpleNamespace(
        cell=cell, seed=0, seconds=2.0, trace=True, rehearse=False,
        devices=devices, peaks=peaks, phases=harness.Phases(START),
        memory=harness.MemoryWatch(devices),
    ))
    dest = os.path.join(harness.CHECKOUT, "chiprun_out", cell.name + ".xplane.pb")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    shutil.copy(out["xplane"], dest)
    print(f"correct={out['correct']} wrote {dest} ({os.path.getsize(dest)} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
