#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, the cell's chips. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
traced ``breakdown``, and last ``compared`` (each number ``correct`` compared
beside its limit, which are also the last lines of standard error). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics. ``--rehearse`` runs the same control flow on the CPU at
the tiny cells under ``benchmark/tests/rehearsal`` and prints no metric: no
time, rate or utilization comes from a CPU.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU control-flow rehearsal of a tiny cell; prints no metric")
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload, args.rehearse)
    harness.prepare_environment(cell, args.rehearse)
    devices, peaks = harness.require_devices(cell, args.rehearse)
    job = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", cell.traffic["job"] + ".py"))
    ctx = types.SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, devices=devices, peaks=peaks,
        phases=harness.Phases(PROCESS_START), memory=harness.MemoryWatch(devices),
    )
    out = job.run(ctx)

    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}}
    device = harness.device_report(devices, ctx.memory)
    if args.rehearse:
        pass  # the control flow and `correct` only: no number from a CPU
    elif args.trace:
        import reduce

        # The file is parsed once: the readers find the reported device's events in the reduction.
        trace = reduce.reduce_xplane(out["xplane"], n_devices=cell.chips)
        result["metrics"] = harness.read_layer_metrics(cell, {
            "trace": trace, "counters": out["counters"], "peaks": peaks,
            "devices": devices, "memory_peak_bytes": ctx.memory.peak_bytes, "cell": cell,
        })
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": float(out["end_to_end"][m["name"]]), "unit": m["unit"]}
            for m in cell.metrics("end_to_end") if m["name"] in out["end_to_end"]
        }
    result["device"] = device
    result["compared"] = out["compared"]
    harness.result_line(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
