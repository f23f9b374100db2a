"""Device time by the program's own names: what the readers of
``update_and_metrics_ms``, ``accum_ms``, ``loss_island_ms``, ``tower_elementwise_ms``,
``attn_{vision,text}_{fwd,bwd}_ms``, ``attn_{vision,text}_roofline_pct`` and
``unscoped_pct`` under ``layer_metrics/`` share.

``reduce.py`` cuts a traced step by XLA's categories (matmul, custom call,
collective, other). The program now names its layers from inside: a
``jax.named_scope`` around the optimizer update, the loss island, the gradient
accumulator and the step's health metrics (train/train_step.py,
parallel/update_shard.py), a ``name=`` on every ``pallas_call``, and flax's own
``visual/...`` and ``textual/...`` for the towers. Every operation of the trace
carries its jax path in the statistic ``tf_op``, wrapped by the transformation it
went through: ``jit(step)/transpose(jvp(loss_island))/shard_map/...``,
``while/body/closed_call/jvp(SigLIP)/visual/encoder/block0/attn/short_attn_fwd``.
A path belongs to a name when one of its components, wrappers peeled, equals it;
the outermost such component decides.

This module takes the events of the device the reduction reported from the
reduction itself (``ctx["trace"]["plane"]``: run.py parses the file once), and
windows and self-times its operations with ``reduce``'s own functions, once per
traced run, so its parts add up to the reduction's busy time. Every operation
lands in exactly one part:

- ``optimizer``, ``loss_island``, ``accum``, ``step_metrics``: under that scope,
  whatever its category (the loss island's matmul, on four chips its permutes);
- ``collective``: a collective under none of those (the gradient all-reduce);
- ``visual``, ``textual``: the towers, by flax's path;
- ``unscoped``: the rest, what the names still miss.

XLA fuses across scope edges and a fusion carries one operation's path, so an
edge is soft by a fusion or two; ``unscoped_pct`` and the closure test
(tests/test_scopes.py) keep that honest. Between ``optimizer`` and
``step_metrics`` the edge is gone: each leaf's AdamW update and the norms that
read its result are one fusion, named after its reduction, so the two parts are
read as one metric (``update_and_metrics_ms``). Where no operation of the trace
carries a name (the parent of the PR that added the name, PR 22's fixtures), a
reader gets ``None`` and the metric is left out.

The attention rooflines divide a count by a kernel time. The count is the
configuration's: ``ctx["counters"]["count"]`` is the count module its file names
(``"modules"``), and ``attention_least_s`` there says what softmax attention
each tower runs through the fused kernels. A tower with none reads nothing.
"""

from __future__ import annotations

import collections
import re

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import reduce

SCOPES = ("optimizer", "loss_island", "accum", "step_metrics")
TOWERS = ("visual", "textual")
PARTS = SCOPES + ("collective",) + TOWERS + ("unscoped",)
KERNELS = ("short_attn_fwd", "short_attn_bwd")

_SEPARATORS = re.compile(r"[/()]")


def components(path: str) -> list[str]:
    """The components of a jax path with the transformations' wrappers peeled:
    ``transpose(jvp(loss_island))/shard_map`` -> transpose, jvp, loss_island,
    shard_map."""
    return [c for c in _SEPARATORS.split(path) if c]


def part_of(path: str, group: str) -> str:
    """The one part an operation belongs to, from its jax path and its group
    (``reduce.group_of``). Scopes before towers: the first of either in the
    path (the outermost) decides, and a scope holds its collectives."""
    named = next((c for c in components(path) if c in SCOPES or c in TOWERS), None)
    if named in SCOPES:
        return named
    if group == "collective":
        return "collective"
    return named or "unscoped"


def kernel_of(path: str) -> tuple[str, str] | None:
    """(tower, kernel) of an attention custom call, from the kernel's ``name=``
    and flax's tower path; None for any other operation."""
    parts = components(path)
    kernel = next((c for c in parts if c in KERNELS), None)
    tower = next((c for c in parts if c in TOWERS), None)
    return (tower, kernel) if kernel and tower else None


def split_events(ops: list, modules: list) -> dict:
    """One device's operations, windowed to the traced steps as
    ``reduce.reduce_events`` windows them, to self seconds by part, by part
    within group ``other``, and by attention kernel. Totals, not per step."""
    program = reduce.step_program(modules)
    steps = sorted((e for e in modules if e.name == program), key=lambda e: e.start_ns)
    if steps:
        w0, w1 = steps[0].start_ns, steps[-1].start_ns + steps[-1].dur_ns
        ops = [e for e in ops if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]
    part_s, other_s, kernel_s = (collections.Counter() for _ in range(3))
    for e, self_ns in zip(ops, reduce.self_times(ops)):
        path, group = str(e.stats.get("tf_op", "")), reduce.group_of(e)
        part = part_of(path, group)
        part_s[part] += self_ns / 1e9
        if group == "other":
            other_s[part] += self_ns / 1e9
        kernel = kernel_of(path) if group == "custom_call" else None
        if kernel:
            kernel_s[kernel] += self_ns / 1e9
    return {
        "steps": len(steps), "part_s": dict(part_s), "other_s": dict(other_s),
        "kernel_s": dict(kernel_s),
    }


def split_once(trace: dict, key: str, split_events) -> dict:
    """``split_events`` of the reported device's events, computed once per traced
    run and kept with the reduction under ``key``."""
    if key not in trace:
        trace[key] = split_events(trace["plane"]["ops"], trace["plane"]["modules"])
    return trace[key]


def split(ctx) -> dict:
    return split_once(ctx["trace"], "scopes", split_events)


# -- what the readers call ------------------------------------------------------


def _per_step_ms(ctx, seconds_of) -> float | None:
    """``seconds_of(split(ctx))`` per traced step, in ms. None where there is no
    time at all: no operation carried the name."""
    s = split(ctx)
    seconds = seconds_of(s) if s["steps"] else 0.0
    return 1e3 * seconds / s["steps"] if seconds else None


def part_ms(ctx, *parts: str) -> float | None:
    """Self time per step of the operations in ``parts``, together."""
    return _per_step_ms(ctx, lambda s: sum(s["part_s"].get(p, 0.0) for p in parts))


def tower_elementwise_ms(ctx) -> float | None:
    """Self time per step of the towers' operations outside matmuls and
    kernels: layer norms, gelu, residuals, casts, data formatting."""
    return _per_step_ms(ctx, lambda s: sum(s["other_s"].get(t, 0.0) for t in TOWERS))


def kernel_ms(ctx, tower: str, kernel: str | None = None) -> float | None:
    """Time per step of one tower's attention kernel, or of both (``kernel=None``)."""
    return _per_step_ms(ctx, lambda s: sum(
        v for (t, k), v in s["kernel_s"].items() if t == tower and kernel in (None, k)
    ))


def tower_roofline_pct(ctx, tower: str) -> float | None:
    """The least time the chip could take for the softmax attention ``tower``
    runs through the fused kernels in a step, as the configuration's count module
    has it (``attention_least_s``: forward + backward, recomputation not counted,
    every layer application the tower really does), over the time of that tower's
    attention kernels. None where the tower has no kernel time or the count says
    it runs no such layer."""
    kernels_ms = kernel_ms(ctx, tower)
    if not kernels_ms:
        return None
    counters = ctx["counters"]
    least_s = counters["count"].attention_least_s(
        counters["cfg"], tower, counters["pairs_per_chip_per_step"], ctx["peaks"]
    )
    return 100.0 * 1e3 * least_s / kernels_ms if least_s else None


def unscoped_pct(ctx) -> float | None:
    """Share of busy self time in no named part and in no collective. None where
    the program named nothing (no operation under any of its four scopes): the
    share then says nothing about the names."""
    s = split(ctx)
    if not any(s["part_s"].get(name) for name in SCOPES):
        return None
    return 100.0 * s["part_s"].get("unscoped", 0.0) / sum(s["part_s"].values())
