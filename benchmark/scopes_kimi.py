"""Device time of a hybrid text tower by the program's own names: what the
readers of ``kda_ms``, ``kda_core_ms``, ``kda_core_roofline_pct``,
``mla_core_ms``, ``mla_core_roofline_pct``, ``moe_ms`` and ``moe_route_ms`` under
``layer_metrics/`` share. ``scopes.py`` cuts a traced step into its parts; this
file cuts the text tower once more, by five names the program gives
(models/mixers.py, models/moe.py):

- ``kda``: the flax path of a gated delta-rule layer's mixer
  (``textual/encoder/block<i>/kda/...``): projections, convolutions, norms,
  gates and the recurrence; ``kda_core``: the ``jax.named_scope`` around the
  recurrence alone, inside it;
- ``mla_core``: the scope around latent attention's scores, softmax and values;
- ``moe``: the flax path of a routed layer (router, experts, shared expert);
  ``moe_route``: the scope, inside it, around everything but the expert and
  shared-expert products: scores, selection, the sort, gathers and scatters.

Every operation under a name counts, whatever its category, forward, the
forward that remat runs again, and backward. It reads what ``scopes.split``
reads, once per traced run. The two rooflines divide the count module's
``kda_least_s`` / ``mla_least_s`` (the configuration's: ``flops_kimi.py``) by the
core's time. Where the program has none of the names (any other cell, the parent
of the PR that added them) or the count module has no such function, a reader
gets ``None`` and the metric is left out.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import reduce
import scopes

TEXT_TOWER = "textual"
KDA, KDA_CORE, MLA_CORE, MOE, MOE_ROUTE = "kda", "kda_core", "mla_core", "moe", "moe_route"
NAMES = (KDA, KDA_CORE, MLA_CORE, MOE, MOE_ROUTE)


def split_events(ops: list, modules: list) -> dict:
    """One device's operations, windowed to the traced steps as
    ``scopes.split_events`` windows them, to self seconds under each name.
    Totals, not per step."""
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
    s = scopes.split_once(ctx["trace"], "scopes_kimi", split_events)
    return 1e3 * s[name] / s["steps"] if s["steps"] and s[name] else None


def roofline_pct(ctx, name: str, least_s: str) -> float | None:
    """The count module's ``least_s`` for the step's sequences over the time
    under ``name``; None without either."""
    core_ms = per_step_ms(ctx, name)
    counters = ctx["counters"]
    least = getattr(counters["count"], least_s, None)
    if not core_ms or least is None:
        return None
    return 100.0 * 1e3 * least(counters["cfg"], counters["pairs_per_chip_per_step"], ctx["peaks"]) / core_ms
