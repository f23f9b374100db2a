"""Device time of a latent-attention text tower by two more of the program's own
names: what the readers of ``mla_ms`` and ``mla_rope_ms`` under
``layer_metrics/`` share. ``scopes_kimi.py`` cuts the text tower by ``mla_core``,
``moe`` and ``moe_route`` (``mla_rope_core_ms`` and ``mla_rope_core_roofline_pct``
read ``mla_core`` through its functions); this file cuts it by

- ``mla``: the flax path of a latent-attention layer's mixer
  (``textual/encoder/block<i>/mla/...``, models/mixers.py LatentAttention): the
  queries' and the keys' and values' projections, both latents' norms, the
  rotation, the core and the output projection;
- ``mla_rope``: the ``jax.named_scope``, inside it, around the rotation of each
  head's 64-wide query part and of the one shared key part, with the cuts and the
  joins that put the parts back.

Every operation under a name counts, whatever its category, forward, the forward
that remat runs again, and backward; windowed and self-timed as
``scopes_kimi.split_events`` does it, once per traced run. Where the program has
neither name (any other cell, the parent of the PR that added them) a reader
gets ``None`` and the metric is left out.
"""

from __future__ import annotations

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import reduce
import scopes
import scopes_kimi

MLA, MLA_ROPE = "mla", "mla_rope"
NAMES = (MLA, MLA_ROPE)


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
        if scopes_kimi.TEXT_TOWER not in names:
            continue
        for name in NAMES:
            if name in names:
                out[name] += self_ns / 1e9
    return out


def per_step_ms(ctx, name: str) -> float | None:
    """Self time per traced step under ``name``, in ms; None where no operation
    carried the name."""
    s = scopes.split_once(ctx["trace"], "scopes_glm", split_events)
    return 1e3 * s[name] / s["steps"] if s["steps"] and s[name] else None
