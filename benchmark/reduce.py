"""From the profiler's ``.xplane.pb`` to the numbers the per-layer metrics read.

Two stages. ``read_xplane`` turns the file into plain events: per device plane
the operations (line "XLA Ops") and the program executions (line "XLA Modules"),
each with the statistics the profiler attached, and the harness's own host spans.
``reduce_events`` is arithmetic on those events: the busy union, idle gaps, time
by category (self time, so an operation nested in a ``while`` counts once),
collective time and the part of it nothing else covers, step durations. The
reduction is pinned to a trace recorded on the chip (``tests/test_reduce.py``).

    python benchmark/reduce.py --dump <file.xplane.pb>     # look at a trace by hand
"""

from __future__ import annotations

import collections
import json
import re
import statistics
import struct
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("window", "dispatch", "sync")

# The profiler's hlo_category of an operation -> the benchmark's four groups.
MATMUL_CATEGORIES = ("convolution", "convolution fusion", "matmul", "dot")
COLLECTIVE_WORDS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all",
    "collective-broadcast",
)

Event = collections.namedtuple("Event", "name start_ns dur_ns stats")

# On this libtpu an operation's metadata name is its whole HLO line,
# "%all-reduce-start.3 = (f32[..]{..:T(8,128)}, ..) all-reduce-start(%x), ..":
# the opcode is the first lower-case word before a "(" after the " = ".
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _opcode_stats(hlo_line: str) -> dict:
    """``opcode`` (and, for an async wrapper, the computation it ``calls``) of an
    operation whose name is an HLO line; nothing for any other name."""
    _, eq, rest = hlo_line.partition(" = ")
    m = _OPCODE.search(rest) if eq and hlo_line.startswith("%") else None
    if not m:
        return {}
    out = {"opcode": m.group(1)}
    calls = _CALLS.search(rest) if m.group(1).startswith("async-") else None
    if calls:
        out["calls"] = calls.group(1)
    return out


# -- stage one: the file ------------------------------------------------------
# jax.profiler.ProfileData reads names and times but leaves out the statistics
# the profiler attaches to an operation's metadata (hlo_category, flops,
# bytes_accessed, tf_op), so the file is read here, in protobuf's wire format.
# Field numbers are those of tsl/profiler/protobuf/xplane.proto.


def _varint(buf, i: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews for
    length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf, stat_names: dict) -> tuple[str, object]:
    key, value = None, None
    for field, v in _fields(buf):
        if field == 1:
            key = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value = stat_names.get(v, str(v))
    return key, value


def _map_value(entry) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for field, v in _fields(entry):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf, want_line) -> dict:
    """One XPlane: its name and, of the lines ``want_line(name)`` accepts, the
    events with their own and their metadata's statistics."""
    name, lines, stat_names, event_meta = "", [], {}, {}
    for field, v in _fields(buf):
        if field == 2:
            name = bytes(v).decode()
        elif field == 3:
            lines.append(v)
        elif field == 5:
            key, value = _map_value(v)
            stat_names[key] = next((bytes(x).decode() for f, x in _fields(value) if f == 2), "")
    for field, v in _fields(buf):
        if field == 4:
            key, value = _map_value(v)
            meta = {"name": "", "display": "", "stats": {}}
            for f, x in _fields(value):
                if f == 2:
                    meta["name"] = bytes(x).decode("utf-8", "replace")
                    meta["stats"].update(_opcode_stats(meta["name"]))
                elif f == 4:
                    meta["display"] = bytes(x).decode("utf-8", "replace")
                elif f == 5:
                    k, val = _stat(x, stat_names)
                    meta["stats"][k] = val
            event_meta[key] = meta
    out = {}
    for line in lines:
        line_name, t0, events = "", 0, []
        for field, v in _fields(line):
            if field == 2:
                line_name = bytes(v).decode()
            elif field == 3:
                t0 = v
            elif field == 4:
                events.append(v)
        if not want_line(line_name):
            continue
        parsed = []
        for ev in events:
            meta_id = offset_ps = dur_ps = 0
            stats = {}
            for field, v in _fields(ev):
                if field == 1:
                    meta_id = v
                elif field == 2:
                    offset_ps = v
                elif field == 3:
                    dur_ps = v
                elif field == 4:
                    k, val = _stat(v, stat_names)
                    stats[k] = val
            meta = event_meta.get(meta_id, {"name": str(meta_id), "display": "", "stats": {}})
            parsed.append(Event(
                meta["display"] or meta["name"], t0 + offset_ps / 1e3, dur_ps / 1e3,
                {**meta["stats"], **stats},
            ))
        out.setdefault(line_name, []).extend(parsed)
    return {"name": name, "lines": out}


def read_xplane(path: str) -> dict:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, host = {}, []
    for field, v in _fields(space):
        if field != 1:
            continue
        name = next((bytes(x).decode() for f, x in _fields(v) if f == 2), "")
        if name.startswith("/device:TPU:"):
            plane = _plane(v, lambda n: n in (OPS_LINE, MODULES_LINE))
            if plane["lines"].get(OPS_LINE):
                devices[name] = {
                    "ops": plane["lines"][OPS_LINE],
                    "modules": plane["lines"].get(MODULES_LINE, []),
                }
        elif name.startswith("/host:"):
            for events in _plane(v, lambda n: True)["lines"].values():
                host += [e for e in events if e.name in HOST_SPANS]
    return {"devices": devices, "host": sorted(host, key=lambda e: e.start_ns)}


# -- stage two: arithmetic on events ------------------------------------------


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def length(disjoint) -> float:
    return sum(b - a for a, b in disjoint)


def subtract(a, b) -> list[tuple[float, float]]:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def group_of(event: Event) -> str:
    """matmul, custom_call, collective or other: from the profiler's category,
    the operation's opcode and, for an async wrapper, the computation it calls."""
    stats = event.stats
    category = str(stats.get("hlo_category", "")).lower()
    words = (category, str(stats.get("opcode", "")), str(stats.get("calls", "")).replace("_", "-"))
    if any(c in w for w in words for c in COLLECTIVE_WORDS):
        return "collective"
    if category == "custom-call" or stats.get("opcode") == "custom-call":
        return "custom_call"
    if category in MATMUL_CATEGORIES:
        return "matmul"
    return "other"


def self_times(ops: list[Event]) -> list[float]:
    """Each operation's duration less that of the operations nested in it (the
    body of a ``while`` lies inside the ``while`` on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    self_ns = [e.dur_ns for e in ops]
    stack: list[int] = []
    for i in order:
        start, end = ops[i].start_ns, ops[i].start_ns + ops[i].dur_ns
        while stack and ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns <= start:
            stack.pop()
        if stack and end <= ops[stack[-1]].start_ns + ops[stack[-1]].dur_ns:
            self_ns[stack[-1]] -= ops[i].dur_ns
        stack.append(i)
    return [max(s, 0.0) for s in self_ns]


def label_gap(start: float, end: float, host: list[Event]) -> str:
    """What the host was doing in an idle gap: the innermost of the harness's
    spans that covers the gap's middle."""
    mid = (start + end) / 2
    covering = [e for e in host if e.start_ns <= mid < e.start_ns + e.dur_ns]
    if not covering:
        return "no_span"
    return min(covering, key=lambda e: e.dur_ns).name


def step_program(modules: list[Event]) -> str | None:
    """The program that took most device time: the train step."""
    total = collections.Counter()
    for e in modules:
        total[e.name] += e.dur_ns
    return total.most_common(1)[0][0] if total else None


def reduce_events(ops: list[Event], modules: list[Event], host: list[Event]) -> dict:
    """One device's operations to its numbers; times in seconds. Collective time
    is the core's own: on the dp=4 cell the gradient all-reduce is synchronous
    (7.9 ms a step) and a collective-permute costs the core its -start and -done
    (0.04 ms). The line "Async XLA Ops" is not read: its spans run from where the
    compiler hoisted a -start to the -done (172 ms for one ring hop), which is
    scheduling distance, not time on the wire."""
    program = step_program(modules)
    steps = sorted((e for e in modules if e.name == program), key=lambda e: e.start_ns)
    if steps:
        # The traced window: from the first traced step's start to the last one's end.
        w0, w1 = steps[0].start_ns, steps[-1].start_ns + steps[-1].dur_ns
    else:
        w0 = min(e.start_ns for e in ops)
        w1 = max(e.start_ns + e.dur_ns for e in ops)
    ops = [e for e in ops if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]
    busy = merge((e.start_ns, e.start_ns + e.dur_ns) for e in ops)
    groups = [group_of(e) for e in ops]
    selfs = self_times(ops)

    by_group = collections.Counter()
    by_name = collections.Counter()
    where = {}  # the jax path of the first operation seen under each name
    matmul_flops = 0.0
    for e, g, s in zip(ops, groups, selfs):
        by_group[g] += s
        name = f"{e.stats.get('deduplicated_name') or e.name} [{e.stats.get('hlo_category', g)}]"
        by_name[name] += s
        where.setdefault(name, str(e.stats.get("tf_op", "")).removeprefix("jit(step)/")[:120])
        if g == "matmul":
            matmul_flops += float(e.stats.get("flops", e.stats.get("model_flops", 0.0)))

    coll = merge((e.start_ns, e.start_ns + e.dur_ns) for e, g in zip(ops, groups) if g == "collective")
    # Leaves only: a `while` that contains a collective is not compute that hides it.
    others = merge(
        (e.start_ns, e.start_ns + e.dur_ns)
        for e, g, s in zip(ops, groups, selfs) if g != "collective" and s >= e.dur_ns
    )
    gaps = collections.Counter()
    for a, b in subtract([(w0, w1)], busy):
        gaps[label_gap(a, b, host)] += b - a

    n_steps = max(len(steps), 1)
    return {
        "program": program,
        "steps": len(steps),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": length(busy) / 1e9,
        "step_ms": [e.dur_ns / 1e6 for e in steps],
        "group_s": {g: by_group[g] / 1e9 for g in ("matmul", "custom_call", "collective", "other")},
        "matmul_flops": matmul_flops,
        "collective_s_per_step": length(coll) / 1e9 / n_steps,
        "collective_exposed_s_per_step": length(subtract(coll, others)) / 1e9 / n_steps,
        "top_ops": [[f"{n} {where[n]}".rstrip(), s / 1e9] for n, s in by_name.most_common(10)],
        "idle_gaps": [[n, s / 1e9] for n, s in gaps.most_common(10)],
    }


def reduce_xplane(path: str, n_devices: int) -> dict:
    """The trace of a cell on ``n_devices`` chips: every device plane reduced,
    the slowest device (longest median step) reported, busy time averaged."""
    raw = read_xplane(path)
    if len(raw["devices"]) < n_devices:
        raise RuntimeError(
            f"the trace has {len(raw['devices'])} device planes with an "
            f"{OPS_LINE!r} line, the cell ran on {n_devices}"
        )
    per_device = {
        name: reduce_events(d["ops"], d["modules"], raw["host"])
        for name, d in sorted(raw["devices"].items())
    }
    active = {n: r for n, r in per_device.items() if r["busy_s"] > 0}
    if len(active) < n_devices:
        raise RuntimeError("a device of the cell ran no operation in the traced window")
    slowest_name = max(active, key=lambda n: statistics.median(active[n]["step_ms"] or [0.0]))
    slowest = active[slowest_name]
    return {
        "busy_s": statistics.fmean(r["busy_s"] for r in active.values()),
        "window_s": slowest["window_s"],
        "device": slowest,
        "per_device": per_device,
        # The reported device's parsed events (``ops``, ``modules``), for the readers that
        # cut them by the program's own names (scopes.py): the file is read once.
        "plane": raw["devices"][slowest_name],
        "breakdown": {"device_ops": slowest["top_ops"], "idle_gaps": slowest["idle_gaps"]},
    }


# -- a look by hand -------------------------------------------------------------


def dump(path: str, top: int = 30) -> None:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, v in _fields(space):
        if field != 1:
            continue
        plane = _plane(v, lambda n: True)
        print(f"PLANE {plane['name']!r}")
        for line_name, events in plane["lines"].items():
            print(f"  LINE {line_name!r}: {len(events)} events")
            by_name, by_cat, example = collections.Counter(), collections.Counter(), {}
            for e in events:
                by_name[e.name] += e.dur_ns
                by_cat[str(e.stats.get("hlo_category"))] += e.dur_ns
                example.setdefault(e.name, e)
            if line_name == OPS_LINE:
                print("    by hlo_category:", [(c, round(ns / 1e6, 3)) for c, ns in by_cat.most_common()])
            for name, ns in by_name.most_common(top):
                e = example[name]
                stats = {k: (v if len(str(v)) < 60 else str(v)[:60] + "...") for k, v in e.stats.items()}
                print(f"    {ns / 1e6:10.3f} ms  {name[:60]!r} start={e.start_ns:.0f} dur={e.dur_ns:.0f} {stats}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    elif len(sys.argv) == 3 and sys.argv[1] == "--reduce":
        out = reduce_xplane(sys.argv[2], 1)
        out.pop("per_device")
        out.pop("plane")
        print(json.dumps(out, indent=1))
    else:
        raise SystemExit(__doc__)
