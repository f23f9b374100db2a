"""The job ``train_step_looped``: ``jobs/train_step.py``'s run, for a cell whose
text tower is a looped stack. That job imports the plain reference and the
operation count by name, and both are another model's: this file loads a
private instance of it, binds its ``reference`` and ``flops`` to
``reference_looped.py`` and ``flops_looped.py``, and runs it. Nothing of its
window, counters or comparison is copied. (PERF.md section 7: once a
configuration can name its reference and its count, this file folds back.)
"""

from __future__ import annotations

import os

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops_looped
import harness
import reference_looped

_base = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", "train_step.py"))
_base.reference = reference_looped
_base.flops = flops_looped

build_config = _base.build_config
build_step = _base.build_step  # tests/compile_for_chip.py asks the job for it


def run(ctx) -> dict:
    out = _base.run(ctx)
    text = out["counters"]["cfg"].text
    out["counters"]["loops"] = text.loops
    out["counters"]["layer_applications"] = flops_looped.layer_applications(text)
    return out
