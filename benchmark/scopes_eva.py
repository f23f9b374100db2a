"""Device time of a text tower of windowed chunk attention by the program's own
names: what the readers of ``eva_ms``, ``eva_summary_ms``, ``eva_core_ms`` and
``eva_core_roofline_pct`` under ``layer_metrics/`` share. The text tower cut by

- ``eva``: the flax path of a layer's mixer (``textual/encoder/blocks/block/eva/...``,
  models/mixers.py EvaAttention): the q, k, v projections, the rotation, the
  pooling, the core and the output projection;
- ``eva_summary``: the ``jax.named_scope``, inside it, around the pooling of the
  rotated keys and the values into one summary a chunk;
- ``eva_core``: the scope around both score sets, the one softmax and the
  values: the kernels ``eva_attn_fwd`` / ``eva_attn_bwd`` where the program takes
  them, XLA's operations where it does not.

Every operation under a name counts, whatever its category, forward, the forward
that remat runs again, and backward; windowed and self-timed as
``scopes_kimi.split_events`` does it, once per traced run. Where the program has
none of the names (any other cell, the parent of the PR that added them) a reader
gets ``None`` and the metric is left out.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import reduce
import scopes

TEXT_TOWER = "textual"
EVA, EVA_SUMMARY, EVA_CORE = "eva", "eva_summary", "eva_core"
NAMES = (EVA, EVA_SUMMARY, EVA_CORE)


def split_events(ops: list, modules: list) -> dict:
    """One device's operations, windowed to the traced steps, to self seconds
    under each name. Totals, not per step."""
    program = reduce.step_program(modules)
    steps = sorted((e for e in modules if e.name == program), key=lambda e: e.start_ns)
    if steps:
        w0, w1 = steps[0].start_ns, steps[-1].start_ns + steps[-1].dur_ns
        ops = [e for e in ops if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]
    out = {"steps": len(steps), **{name: 0.0 for name in NAMES}}
    for e, self_ns in zip(ops, reduce.self_times(ops)):
        names = scopes.components(str(e.stats.get("tf_op", "")))
        if TEXT_TOWER not in names:
            continue
        for name in NAMES:
            if name in names:
                out[name] += self_ns / 1e9
    return out


def per_step_ms(ctx, name: str) -> float | None:
    """Self time per traced step under ``name``, in ms; None where no operation
    carried the name."""
    s = scopes.split_once(ctx["trace"], "scopes_eva", split_events)
    return 1e3 * s[name] / s["steps"] if s["steps"] and s[name] else None


def core_roofline_pct(ctx) -> float | None:
    """The count module's ``eva_core_least_s`` for the step's sequences over the
    time under ``eva_core``; None without either."""
    core_ms = per_step_ms(ctx, EVA_CORE)
    counters = ctx["counters"]
    least = getattr(counters["count"], "eva_core_least_s", None)
    if not core_ms or least is None:
        return None
    return 100.0 * 1e3 * least(counters["cfg"], counters["pairs_per_chip_per_step"], ctx["peaks"]) / core_ms
