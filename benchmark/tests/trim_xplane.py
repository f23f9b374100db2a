#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to what the reduction reads:

    python benchmark/tests/trim_xplane.py <in.xplane.pb> <out.xplane.pb> [--steps N]

Keeps, of every TPU device plane, the lines "XLA Ops" and "XLA Modules" with the
statistics the reduction uses, and of the host planes the harness's own spans;
``--steps N`` keeps the first N executions of the step program and what lies
inside them. Times, names and values are copied, never changed. The output is
a real XSpace, written through jax's own text-proto converter, so the tests read
it through the same ``ProfileData`` path as a fresh trace.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import reduce  # noqa: E402

KEEP_STATS = ("hlo_category", "opcode", "calls", "flops", "bytes_accessed", "deduplicated_name")


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n") + '"'


class _Plane:
    """One XPlane as text proto. Events are interned by name and carry their
    statistics on the metadata, as the profiler writes them."""

    def __init__(self, plane_id: int, name: str):
        self.id, self.name = plane_id, name
        self.events: dict[str, tuple[int, str]] = {}
        self.stat_ids: dict[str, int] = {}
        self.lines: list[str] = []

    def _stat(self, key: str, value) -> str:
        sid = self.stat_ids.setdefault(key, len(self.stat_ids) + 1)
        if isinstance(value, str):
            field = f"str_value: {_quote(value)}"
        elif isinstance(value, float):
            field = f"double_value: {value!r}"
        else:
            field = f"int64_value: {int(value)}"
        return f"stats {{ metadata_id: {sid} {field} }}"

    def _event_id(self, e: reduce.Event) -> int:
        if e.name not in self.events:
            stats = " ".join(self._stat(k, e.stats[k]) for k in KEEP_STATS if k in e.stats)
            self.events[e.name] = (len(self.events) + 1, stats)
        return self.events[e.name][0]

    def add_line(self, line_id: int, name: str, events: list[reduce.Event]) -> None:
        if not events:
            return
        t0 = int(min(e.start_ns for e in events))
        body = [
            f"events {{ metadata_id: {self._event_id(e)} "
            f"offset_ps: {int(round((e.start_ns - t0) * 1000))} "
            f"duration_ps: {int(round(e.dur_ns * 1000))} }}"
            for e in sorted(events, key=lambda e: e.start_ns)
        ]
        self.lines.append(
            f"lines {{ id: {line_id} name: {_quote(name)} timestamp_ns: {t0} " + " ".join(body) + " }"
        )

    def text(self) -> str:
        meta = [
            f"event_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} {stats} }} }}"
            for n, (i, stats) in self.events.items()
        ] + [
            f"stat_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} }} }}"
            for n, i in self.stat_ids.items()
        ]
        return (f"planes {{ id: {self.id} name: {_quote(self.name)} "
                + " ".join(self.lines + meta) + " }")


def trim(src: str, dst: str, steps: int | None) -> None:
    from jax.profiler import ProfileData

    raw = reduce.read_xplane(src)
    planes = []
    lo, hi = float("inf"), float("-inf")
    for i, (name, d) in enumerate(sorted(raw["devices"].items())):
        ops, modules = d["ops"], d["modules"]
        if steps:
            program = reduce.step_program(modules)
            kept = sorted((e for e in modules if e.name == program), key=lambda e: e.start_ns)[:steps]
            w0, w1 = kept[0].start_ns, kept[-1].start_ns + kept[-1].dur_ns
            modules = [e for e in modules if w0 <= e.start_ns and e.start_ns + e.dur_ns <= w1]
            ops = [e for e in ops if w0 <= e.start_ns and e.start_ns + e.dur_ns <= w1]
        lo = min([lo] + [e.start_ns for e in ops])
        hi = max([hi] + [e.start_ns + e.dur_ns for e in ops])
        plane = _Plane(i + 1, name)
        plane.add_line(1, reduce.MODULES_LINE, modules)
        plane.add_line(2, reduce.OPS_LINE, ops)
        planes.append(plane)
    host = _Plane(len(planes) + 1, "/host:CPU")
    # Host spans that touch the kept device window (the window span covers it all).
    host.add_line(1, "python", [
        e for e in raw["host"] if e.start_ns < hi and e.start_ns + e.dur_ns > lo
    ])
    planes.append(host)
    text = "\n".join(p.text() for p in planes)
    with open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    print(f"{src}: {os.path.getsize(src)} bytes -> {dst}: {os.path.getsize(dst)} bytes")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--steps", type=int)
    a = ap.parse_args()
    trim(a.src, a.dst, a.steps)
