"""The program's own record of start-up, as the seven set-up readers cut it.

Since PR 37 the program keeps one record a process
(``distributed_sigmoid_loss_tpu/obs/spans.py RECORDER``): spans at its start-up
boundaries (``startup.import``, ``startup.compile_cache``, ``startup.mesh``,
``init_state`` > ``init_params``, ``startup.step_builder``), one span for each of
jax's traces, lowerings and backend compiles (``jax.trace``, ``jax.lower``,
``jax.backend_compile`` with ``attrs["cache"]``: ``hit`` / ``miss`` / ``off``), the
persistent cache's counters, and the OS's start time of the process. It is alive
in the process when ``run.py`` calls the readers, so they read it where it lies.
All seven move ``setup_s``; what each reads is in its own file. A program from
before PR 37 has no such record: :func:`record` then gives None, the readers
return None and the line leaves the metrics out.
"""

from __future__ import annotations

import types


def record(ctx: dict):
    """``spans`` (a list), ``counters`` (a dict) and ``start`` (the process's
    start on the spans' clock) of the recorder under ``ctx["recorder"]`` (the
    tests fill one by hand, with ``ctx["process_start"]``) or of the process's
    own; None where the program keeps none."""
    rec, start = ctx.get("recorder"), ctx.get("process_start")
    if rec is None:
        try:
            from distributed_sigmoid_loss_tpu.obs import spans as program
        except ImportError:
            return None
        rec = getattr(program, "RECORDER", None)
        if rec is None:
            return None
        start = program.process_start()
    spans = rec.spans()
    if start is None:  # /proc does not say: the first start-up span is the earliest reading
        start = min((s.t0 for s in spans if s.name == "startup.import"), default=0.0)
    return types.SimpleNamespace(spans=spans, counters=rec.counters(), start=start)


def named(rec, name: str, cache: tuple[str, ...] | None = None) -> list:
    """The record's spans of one name; ``cache`` keeps the backend compiles
    whose ``attrs["cache"]`` is one of the given."""
    return [
        s for s in rec.spans
        if s.name == name and (cache is None or (s.attrs or {}).get("cache") in cache)
    ]


def covered_s(spans) -> float:
    """Seconds that the spans cover together: nested or overlapping spans count
    once (a trace of a jitted function inside the step's trace is part of it)."""
    total, upto = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s.t0):
        if s.t1 > upto:
            total += s.t1 - max(s.t0, upto)
            upto = s.t1
    return total
