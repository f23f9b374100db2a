"""Device time of a text tower of one-sub-layer layers by the program's own
names: what the readers of ``ssm_ms``, ``ssm_conv_ms``, ``ssm_core_ms``,
``ssm_core_roofline_pct``, ``gqa_core_ms``, ``gqa_core_roofline_pct``,
``relu2_moe_ms`` and ``relu2_moe_route_ms`` under ``layer_metrics/`` share. The
text tower cut by

- ``ssm``: the flax path of a state-space layer's mixer
  (``textual/encoder/block<i>/ssm/...``, models/mixers.py SsmMixer): the fused
  input projection, the convolution, the recurrence, the gated norm and the
  output projection;
- ``ssm_conv``: the ``jax.named_scope``, inside it, around the causal depthwise
  convolution, its bias and the silu;
- ``ssm_core``: the scope around the recurrence alone, from x, B, C, dt, A, D to
  y: the chunk's decays, the intra-chunk scores and values, the chunk states,
  their scan and the states' outputs;
- ``attn_core``: the scope around an attention layer's scores, softmax and values
  (models/transformer.py Attention): the kernels ``mla_attn_fwd`` /
  ``mla_attn_bwd`` where grouped heads take them, with the sum of a group's dk and dv;
- ``moe``: the flax path of a routed layer (router, experts, shared expert);
  ``moe_route``: the scope, inside it, around everything but the expert and
  shared-expert products.

Every operation under a name counts, whatever its category, forward, the forward
that remat runs again, and backward; windowed and self-timed as
``scopes_kimi.split_events`` does it, once per traced run. The two rooflines
divide the count module's ``ssm_core_least_s`` / ``gqa_core_least_s`` (the
configuration's: ``flops_nemotron.py``) by the core's time. Where the program has
none of the names (any other cell, the parent of the PR that added them) or the
count module has no such function, a reader gets ``None`` and the metric is left
out.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import reduce
import scopes

TEXT_TOWER = "textual"
SSM, SSM_CONV, SSM_CORE, ATTN_CORE, MOE, MOE_ROUTE = "ssm", "ssm_conv", "ssm_core", "attn_core", "moe", "moe_route"
NAMES = (SSM, SSM_CONV, SSM_CORE, ATTN_CORE, MOE, MOE_ROUTE)


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
    s = scopes.split_once(ctx["trace"], "scopes_nemotron", split_events)
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
