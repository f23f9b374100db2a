"""Device time of a text tower that mixes window and full attention layers, by
the program's own names: what the readers of ``gattn_ms``, ``window_core_ms``,
``window_core_roofline_pct``, ``full_core_ms``, ``full_core_roofline_pct``,
``attn_gate_ms``, ``gattn_rope_ms``, ``gattn_moe_ms`` and ``gattn_moe_route_ms``
under ``layer_metrics/`` share. The text tower cut by

- ``attn``: the flax path of a layer's attention
  (``textual/encoder/block<i>/attn/...``, models/transformer.py Attention): the
  five projections (q, k, v, gate, out), the head norms, the rotation, the gate
  and the core;
- ``window_attn_core``: the ``jax.named_scope``, inside it, around a WINDOW
  layer's scores, banded softmax and values: the kernels ``mla_attn_fwd`` /
  ``mla_attn_bwd`` over the band's block pairs where the layer takes them, with
  the sum of a group's dk and dv;
- ``attn_core``: the same scope's name in a FULL layer (every key up to the
  query's own): the two kinds of layer of one stack are told apart by it;
- ``attn_gate``: the scope around the head norms of q and k and the product
  with the gate's sigmoid (the gate's projection is a plain product under
  ``attn`` and not under it);
- ``rope``: the scope around the rotation of q and of k (models/transformer.py
  rope), which the window layers alone run here;
- ``moe``: the flax path of a routed layer (router, experts, shared expert);
  ``moe_route``: the scope, inside it, around everything but the expert and
  shared-expert products.

Every operation under a name counts, whatever its category, forward, the forward
that remat runs again, and backward; windowed to the traced steps and self-timed
by ``scopes_kimi.split_events`` itself, in a private instance of that module given
these names, once per traced run. The two rooflines
divide the count module's ``window_core_least_s`` / ``full_core_least_s`` (the
configuration's: ``flops_trinity.py``) by the cores' time. Where the program has
none of the names (any other cell, the parent of the PR that added them) or the
count module has no such function, a reader gets ``None`` and the metric is left
out.
"""

from __future__ import annotations

import os

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness
import scopes

ATTN, WINDOW_CORE, FULL_CORE, ATTN_GATE, ROPE, MOE, MOE_ROUTE = "attn", "window_attn_core", "attn_core", "attn_gate", "rope", "moe", "moe_route"
NAMES = (ATTN, WINDOW_CORE, FULL_CORE, ATTN_GATE, ROPE, MOE, MOE_ROUTE)

# A private instance of the hybrid tower's cut, with this tower's names in place of its own.
_cut = harness.load_module(os.path.join(harness.BENCH_DIR, "scopes_kimi.py"))
_cut.NAMES = NAMES
split_events = _cut.split_events


def per_step_ms(ctx, name: str) -> float | None:
    """Self time per traced step under ``name``, in ms; None where no operation
    carried the name."""
    s = scopes.split_once(ctx["trace"], "scopes_trinity", split_events)
    return 1e3 * s[name] / s["steps"] if s["steps"] and s[name] else None


_cut.per_step_ms = per_step_ms  # what its roofline_pct divides by
roofline_pct = _cut.roofline_pct


def gated_attention_ms(ctx) -> float | None:
    """``attn`` per step where the layers are gated, normed ones (something ran
    under ``attn_gate``); None for any other tower's attention, which flax names
    ``attn`` too."""
    return per_step_ms(ctx, ATTN) if per_step_ms(ctx, ATTN_GATE) else None

