"""Not a job of its own any more: ``jobs/train_step.py`` under the name the
tier-1 cases of ``tests/test_looped_tower.py`` load (they name this path, copy
``traffic/mb32x2.json`` with its ``"job"``, and read two counters), which a
``benchmark`` PR may not edit. A configuration names its reference and its
count itself (``"modules"``), so nothing is rebound here. The next PR that may
touch ``tests/`` points those cases at ``jobs/train_step.py``, sets ``"job":
"train_step"`` in ``traffic/mb32x2.json`` and deletes this file (PERF.md
section 7).
"""

from __future__ import annotations

import os

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness

_base = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", "train_step.py"))

build_config = _base.build_config
build_step = _base.build_step  # tests/compile_for_chip.py asks the job for it


def run(ctx) -> dict:
    # The tier-1 case writes a configuration file without "modules".
    ctx.cell.config.setdefault("modules", {"reference": "reference_looped", "count": "flops_looped"})
    out = _base.run(ctx)
    text = out["counters"]["cfg"].text
    out["counters"]["loops"] = text.loops
    out["counters"]["layer_applications"] = out["counters"]["count"].layer_applications(text)
    return out
