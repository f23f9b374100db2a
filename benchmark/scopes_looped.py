"""Device time of a looped text tower by the program's own names: what the
readers of ``text_loops_ms`` and ``rope_ms`` under ``layer_metrics/`` share.
``scopes.py`` cuts a traced step into its parts; this file cuts the text tower
once more, by two names the program gives (models/transformer.py):

- ``loop``: the flax path of the stack that runs several times a sequence
  (``textual/encoder/loop/...``): every operation under it, whatever its
  category, forward, the forward that remat runs again, and backward;
- ``rope``: the ``jax.named_scope`` around the rotary product on q and k.

It reads what ``scopes.split`` reads (the reported device's events, from the
reduction; the same window of whole steps, ``reduce``'s self times), once per
traced run. The text tower's attention roofline is ``attn_text_roofline_pct``:
the configuration's count module counts every pass. Where the program has
neither name (any other cell, the parent of the PR that added them) a reader
gets ``None`` and the metric is left out.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import reduce
import scopes

TEXT_TOWER, LOOP, ROPE = "textual", "loop", "rope"


def split_events(ops: list, modules: list) -> dict:
    """One device's operations, windowed to the traced steps as
    ``scopes.split_events`` windows them, to self seconds under ``loop`` and under
    ``rope``. Totals, not per step."""
    program = reduce.step_program(modules)
    steps = sorted((e for e in modules if e.name == program), key=lambda e: e.start_ns)
    if steps:
        w0, w1 = steps[0].start_ns, steps[-1].start_ns + steps[-1].dur_ns
        ops = [e for e in ops if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]
    out = {"steps": len(steps), LOOP: 0.0, ROPE: 0.0}
    for e, self_ns in zip(ops, reduce.self_times(ops)):
        names = scopes.components(str(e.stats.get("tf_op", "")))
        if TEXT_TOWER not in names:
            continue
        if LOOP in names:
            out[LOOP] += self_ns / 1e9
        if ROPE in names:
            out[ROPE] += self_ns / 1e9
    return out


def per_step_ms(ctx, name: str) -> float | None:
    """Self time per traced step under ``name``, in ms; None where no operation
    carried the name."""
    s = scopes.split_once(ctx["trace"], "scopes_looped", split_events)
    return 1e3 * s[name] / s["steps"] if s["steps"] and s[name] else None
