"""Device time of a looped text tower by the program's own names: what the
readers of ``text_loops_ms``, ``rope_ms`` and ``looped_attn_roofline_pct`` under
``layer_metrics/`` share. ``scopes.py`` cuts a traced step into its parts; this
file cuts the text tower once more, by two names the program gives
(models/transformer.py):

- ``loop``: the flax path of the stack that runs several times a sequence
  (``textual/encoder/loop/...``): every operation under it, whatever its
  category, forward, the forward that remat runs again, and backward;
- ``rope``: the ``jax.named_scope`` around the rotary product on q and k.

It reads the traced run's file as ``scopes.split`` does (the same file, the
same device, the same window of whole steps, ``reduce``'s self times), once more
per process. Where the program has neither name (any other cell, the parent of
the PR that added them) a reader gets ``None`` and the metric is left out.
"""

from __future__ import annotations

import functools

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import flops_looped
import reduce
import scopes

TEXT_TOWER, LOOP, ROPE = "textual", "loop", "rope"


def split_events(ops: list, modules: list) -> dict:
    """One device's operations, windowed to the traced steps as
    ``scopes.split_events`` windows them, to self seconds under ``loop``, under
    ``rope``, and in the text tower's attention kernels. Totals, not per step."""
    program = reduce.step_program(modules)
    steps = sorted((e for e in modules if e.name == program), key=lambda e: e.start_ns)
    if steps:
        w0, w1 = steps[0].start_ns, steps[-1].start_ns + steps[-1].dur_ns
        ops = [e for e in ops if e.start_ns >= w0 and e.start_ns + e.dur_ns <= w1]
    out = {"steps": len(steps), LOOP: 0.0, ROPE: 0.0, "kernels": 0.0}
    for e, self_ns in zip(ops, reduce.self_times(ops)):
        path = str(e.stats.get("tf_op", ""))
        names = scopes.components(path)
        if TEXT_TOWER not in names:
            continue
        if LOOP in names:
            out[LOOP] += self_ns / 1e9
        if ROPE in names:
            out[ROPE] += self_ns / 1e9
        if reduce.group_of(e) == "custom_call" and scopes.kernel_of(path):
            out["kernels"] += self_ns / 1e9
    return out


@functools.lru_cache(maxsize=1)
def _split_file(path: str, device: str) -> dict:
    plane = reduce.read_xplane(path)["devices"][device]
    return split_events(plane["ops"], plane["modules"])


def per_step_ms(ctx, name: str) -> float | None:
    """Self time per traced step under ``name``, in ms; None where there is no
    traced file or no operation carried the name."""
    path = scopes.newest_xplane(ctx["cell"].name)
    if path is None:
        return None
    trace = ctx["trace"]
    device = next(n for n, r in trace["per_device"].items() if r is trace["device"])
    s = _split_file(path, device)
    return 1e3 * s[name] / s["steps"] if s["steps"] and s[name] else None


def attn_roofline_pct(ctx) -> float | None:
    """The least time for the text tower's block attention in a step, every
    layer application of every pair counted (``flops_looped``), over the time
    of the text tower's attention kernels."""
    kernels_ms = per_step_ms(ctx, "kernels")
    if not kernels_ms:
        return None
    counters = ctx["counters"]
    least_s = flops_looped.text_attention_least_s(
        counters["cfg"].text, counters["pairs_per_chip_per_step"], ctx["peaks"]
    )
    return 100.0 * 1e3 * least_s / kernels_ms
