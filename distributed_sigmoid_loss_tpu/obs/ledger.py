"""graftledger: the append-only perf-trajectory ledger.

The perf stream's records used to be one-shot stdout lines: the driver
captured whatever a round's `python bench.py` printed and the repo kept no
longitudinal memory of it. Rounds 4 and 5 then recorded 0.0 (chip backend
unavailable) and nothing distinguished "the config regressed" from "the chip
was down" — the trajectory itself was blind (ROADMAP item 3 calls landing
real trajectory numbers "part of this item, not an afterthought").

The ledger fixes the memory half: every record emit path (bench.py ``_emit``,
cli ``serve-bench``, ``data-bench``) ALSO appends one JSONL entry to
``LEDGER.jsonl`` at the repo root, carrying

- the schema-validated record itself (unmodified — the stdout contract is
  untouched),
- an environment fingerprint (jax version, device kind/count, host, git sha)
  so any number can be tied to the program AND the machine that produced it,
- an explicit ``status``: ``ok`` / ``no-backend`` / ``deferred`` / ``error``
  — a dead backend lands as ``no-backend`` instead of polluting the
  trajectory with a 0.0 that looks like a measurement.

``obs ledger`` summarizes the per-metric trajectory (no-backend/error rounds
excluded from the baseline stats), ``obs diff A B`` diffs two entries'
records. The graftlint rule ``repo-ledger-emit`` statically enforces that
bench.py record prints only happen inside the ledger-appending ``_emit``.

Stdlib-only module: bench.py imports it at emit time and must not initialize
jax; the fingerprint reads jax ONLY if something else already imported it.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

__all__ = [
    "DEFAULT_LEDGER_BASENAME",
    "ledger_path",
    "environment_fingerprint",
    "record_status",
    "append_record",
    "read_ledger",
    "backfill_round_files",
    "trajectory",
    "trajectory_summary",
    "diff_records",
]

DEFAULT_LEDGER_BASENAME = "LEDGER.jsonl"
LEDGER_SCHEMA_VERSION = 1

_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO_ROOT = os.path.dirname(_PACKAGE_DIR)

_FINGERPRINT_CACHE: dict = {}


def ledger_path(path: str | None = None) -> str | None:
    """Resolve the ledger file path: an explicit ``path`` wins, then the
    ``DSL_LEDGER_PATH`` env var (set to the empty string to DISABLE ledger
    appends — the test suites do this so CI runs never dirty the committed
    trajectory), then ``<repo_root>/LEDGER.jsonl``."""
    if path:
        return path
    env = os.environ.get("DSL_LEDGER_PATH")
    if env is not None:
        return env or None
    return os.path.join(_REPO_ROOT, DEFAULT_LEDGER_BASENAME)


def _git_sha() -> str:
    if "git_sha" not in _FINGERPRINT_CACHE:
        sha = ""
        try:
            r = subprocess.run(
                ["git", "-C", _REPO_ROOT, "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=5,
            )
            if r.returncode == 0:
                sha = r.stdout.strip()
        except Exception:
            pass
        _FINGERPRINT_CACHE["git_sha"] = sha
    return _FINGERPRINT_CACHE["git_sha"]


def environment_fingerprint() -> dict:
    """Who/what produced this entry: host, git sha, jax version and — only
    when a backend is ALREADY initialized — device kind/count.

    Deliberately passive about jax: importing it here would drag a multi-GB
    runtime into a stdlib emit path, and touching ``jax.devices()`` on an
    uninitialized process initialises the backend — it would claim the
    host's accelerator just to stamp a fingerprint. An already-imported,
    already-initialized jax is read; anything else is left alone.
    """
    env = {"host": socket.gethostname(), "git_sha": _git_sha()}
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        env["jax"] = getattr(jax_mod, "__version__", "?")
        try:
            from jax._src import xla_bridge  # noqa: PLC0415

            if getattr(xla_bridge, "_backends", None):
                devs = jax_mod.devices()
                env["device_kind"] = devs[0].device_kind
                env["device_count"] = len(devs)
        except Exception:
            pass
    return env


def record_status(record: dict) -> str:
    """Classify one bench record for the trajectory: ``deferred`` (compile
    shield handed off to a detached child), ``no-backend`` (the chip was
    dead — the 0.0 is an outage, not a measurement), ``error`` (the bench
    itself failed), else ``ok``."""
    if record.get("deferred"):
        return "deferred"
    err = str(record.get("error") or "")
    if "backend unavailable" in err or "backend init" in err:
        return "no-backend"
    if err:
        return "error"
    return "ok"


def append_record(
    record: dict,
    *,
    path: str | None = None,
    source: str = "bench",
    round_hint: int | None = None,
    problems=None,
) -> dict | None:
    """Append one record to the ledger; returns the written entry (None when
    the ledger is disabled). NEVER raises: a measurement must never be lost
    to its own ledger (the ``_emit`` convention) — failures warn on stderr.
    """
    try:
        target = ledger_path(path)
        if target is None:
            return None
        entry = {
            "schema": LEDGER_SCHEMA_VERSION,
            "ts": round(time.time(), 3),
            "source": source,
            "status": record_status(record),
            "env": environment_fingerprint(),
            "record": dict(record),
        }
        if round_hint is not None:
            entry["round"] = int(round_hint)
        if problems:
            entry["schema_violations"] = list(problems)
        line = json.dumps(entry)
        parent = os.path.dirname(os.path.abspath(target))
        os.makedirs(parent, exist_ok=True)
        # A writer killed mid-append leaves a torn final line with no
        # newline; appending straight after it would corrupt THIS entry too.
        # Start on a fresh line so one torn write costs one entry, not two.
        needs_newline = False
        try:
            with open(target, "rb") as rf:
                rf.seek(-1, os.SEEK_END)
                needs_newline = rf.read(1) != b"\n"
        except (OSError, ValueError):
            pass  # missing or empty file: no heal needed
        with open(target, "a", encoding="utf-8") as f:
            f.write(("\n" if needs_newline else "") + line + "\n")
        return entry
    except Exception as e:  # noqa: BLE001 — see docstring
        print(f"WARNING: ledger append failed ({type(e).__name__}: {e})",
              file=sys.stderr)
        return None


def read_ledger(path: str | None = None) -> list[dict]:
    """Parse the ledger into entries, tolerating torn lines (a process killed
    mid-append leaves a truncated final line — skipped, never fatal)."""
    target = ledger_path(path)
    if target is None or not os.path.exists(target):
        return []
    entries = []
    with open(target, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and isinstance(obj.get("record"), dict):
                entries.append(obj)
    return entries


def _records_in_tail(tail: str) -> list[dict]:
    """The JSON record lines embedded in a round file's captured ``tail``
    (same filter as bench.py's ``_emit_valid_json_lines``: dicts carrying
    ``metric``)."""
    out = []
    for line in tail.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            out.append(obj)
    return out


def backfill_round_files(
    repo_root: str | None = None, path: str | None = None,
) -> list[dict]:
    """Backfill ledger entries from the driver's committed round files
    (``BENCH_r*.json`` / ``MULTICHIP_r*.json``), so the trajectory starts at
    round 1 instead of at the ledger's introduction.

    - BENCH files: every JSON record line in the captured ``tail`` becomes an
      entry (rounds 4/5's "backend unavailable" records land as
      ``status="no-backend"`` automatically — the true trajectory then shows
      761.74 @ r3 as the last verified headline, not 0.0).
    - MULTICHIP files: one ``multichip_dryrun`` entry per round (value 1/0 =
      the dryrun's ok flag) so correctness-drill outcomes sit in the same
      stream.

    Idempotent: an entry whose (source, metric) pair already exists in the
    ledger is skipped. Returns the entries actually appended.
    """
    import glob
    import re

    root = repo_root or _REPO_ROOT
    existing = {
        (e.get("source"), e.get("record", {}).get("metric"))
        for e in read_ledger(path)
    }
    appended = []

    def backfill_one(record, source, rnd):
        if (source, record.get("metric")) in existing:
            return
        # Backfilled entries describe PAST runs: the backfilling host's
        # fingerprint would be a lie, so the `backfill:` source prefix marks
        # them and downstream readers trust the record's own device_kind.
        entry = append_record(
            record, path=path, source=source, round_hint=rnd,
        )
        if entry is not None:
            appended.append(entry)

    for kind in ("BENCH", "MULTICHIP"):
        for fp in sorted(glob.glob(os.path.join(root, f"{kind}_r*.json"))):
            m = re.search(r"_r(\d+)\.json$", fp)
            rnd = int(m.group(1)) if m else None
            try:
                with open(fp, encoding="utf-8") as f:
                    data = json.load(f)
            except (OSError, ValueError):
                continue
            source = f"backfill:{os.path.basename(fp)}"
            if kind == "BENCH":
                for record in _records_in_tail(data.get("tail", "")):
                    backfill_one(record, source, rnd)
            else:
                ok = bool(data.get("ok"))
                record = {
                    "metric": "multichip_dryrun",
                    "value": 1.0 if ok else 0.0,
                    "unit": "ok",
                    "n_devices": data.get("n_devices"),
                }
                if not ok:
                    record["error"] = (
                        f"dryrun rc={data.get('rc')} (see {os.path.basename(fp)})"
                    )
                backfill_one(record, source, rnd)
    return appended


# Statuses the trajectory summary treats as non-measurements: they appear in
# the listing (outages are information) but never in the baseline stats.
_EXCLUDED_FROM_BASELINE = ("no-backend", "deferred", "error")


def trajectory(
    entries: list[dict], metric: str | None = None,
) -> dict[str, list[dict]]:
    """metric -> ordered points ``{round?, ts?, value, status, source,
    device_kind?}``; ``metric`` filters to one stream."""
    out: dict[str, list[dict]] = {}
    for e in entries:
        rec = e.get("record", {})
        name = rec.get("metric")
        if not name or (metric and name != metric):
            continue
        point = {
            "value": rec.get("value"),
            "unit": rec.get("unit"),
            "status": e.get("status", record_status(rec)),
            "source": e.get("source", "?"),
        }
        if e.get("round") is not None:
            point["round"] = e["round"]
        if e.get("ts") is not None:
            point["ts"] = e["ts"]
        kind = rec.get("device_kind") or e.get("env", {}).get("device_kind")
        if kind:
            point["device_kind"] = kind
        out.setdefault(name, []).append(point)
    if metric and not out:
        # Field fallback: the graftcodec emulation figures
        # (wire_savings_wallclock_ratio, dcn_measured_mbps, error_budget,
        # ...) are FIELDS stamped on other streams' records, not streams of
        # their own — `obs ledger --metric wire_savings_wallclock_ratio`
        # should still render the emulated-A/B trajectory. When no stream
        # matches, build one from every record carrying the named field; the
        # unit column names the host stream so the provenance stays visible.
        for e in entries:
            rec = e.get("record", {})
            if metric not in rec or rec.get("metric") == metric:
                continue
            point = {
                "value": rec.get(metric),
                "unit": f"on {rec.get('metric')}",
                "status": e.get("status", record_status(rec)),
                "source": e.get("source", "?"),
            }
            if e.get("round") is not None:
                point["round"] = e["round"]
            if e.get("ts") is not None:
                point["ts"] = e["ts"]
            kind = (
                rec.get("device_kind") or e.get("env", {}).get("device_kind")
            )
            if kind:
                point["device_kind"] = kind
            out.setdefault(metric, []).append(point)
    return out


def trajectory_summary(points: list[dict]) -> dict:
    """Baseline stats over ONE metric's points with non-measurements
    (no-backend / deferred / error) excluded — the acceptance contract: an
    outage round must never drag the baseline to 0.0."""
    measured = [
        p for p in points
        if p["status"] not in _EXCLUDED_FROM_BASELINE
        and isinstance(p.get("value"), (int, float))
    ]
    excluded = len(points) - len(measured)
    if not measured:
        return {"n": 0, "excluded": excluded, "last": None, "best": None}
    values = [float(p["value"]) for p in measured]
    return {
        "n": len(measured),
        "excluded": excluded,
        "last": measured[-1],
        "best": max(values),
        "mean": sum(values) / len(values),
    }


def diff_records(a: dict, b: dict) -> dict:
    """Field-level diff of two records: ``added`` / ``removed`` field sets
    and ``changed`` with per-field (a, b) pairs plus a relative delta for
    numeric fields — what `obs diff` renders."""
    changed: dict = {}
    for k in sorted(set(a) & set(b)):
        va, vb = a[k], b[k]
        if va == vb:
            continue
        entry = {"a": va, "b": vb}
        if isinstance(va, (int, float)) and isinstance(vb, (int, float)) and (
            not isinstance(va, bool) and not isinstance(vb, bool)
        ):
            entry["delta"] = vb - va
            if va:
                entry["rel"] = round((vb - va) / abs(va), 4)
        changed[k] = entry
    return {
        "added": sorted(set(b) - set(a)),
        "removed": sorted(set(a) - set(b)),
        "changed": changed,
    }
