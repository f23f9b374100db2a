"""Static step attribution: FLOPs, bytes and collective traffic from the
PROGRAM, not the chip.

A measured number says how fast; this module says what the program asked the
chip to do, derived STATICALLY (no chip needed), two ways:

- :func:`jaxpr_costs` / :func:`static_attribution` walk a traced jaxpr (the
  same trace-only harness graftlint's auditor uses — seconds, no compile) and
  count (a) matmul/conv FLOPs closed-form per ``dot_general`` /
  ``conv_general_dilated`` (2·B·M·N·K, scan trip counts multiplied in), and
  (b) per-device collective bytes BY KIND with the standard wire conventions
  below. Bytes-moved, not FLOPs, is the lever for the memory-bound parts of
  this workload ("Dissecting Embedding Bag Performance in DLRM Inference",
  PAPERS.md) — so the comm traffic gets first-class, per-kind accounting.
- :func:`attribution_of_compiled` reads an already-compiled executable:
  XLA's own ``cost_analysis()`` (executed FLOPs / post-fusion bytes accessed)
  plus ``utils.profiling.memory_stats_of_compiled`` (peak temp HBM).

Per-device collective wire bytes, for a collective whose PER-SHARD operand is
``s`` bytes over a mesh axis (or axes) of total size ``W``:

==================  =======================  =================================
bucket              bytes per device         rationale
==================  =======================  =================================
all_gather          ``(W-1)·s``              each device receives W-1 shards
ppermute            ``s``                    one shard sent, one received
psum                ``2·s·(W-1)/W``          ring all-reduce (reduce-scatter
                                             + all-gather of 1/W chunks)
psum_scatter        ``s·(W-1)/W``            ring reduce-scatter
all_to_all          ``s·(W-1)/W``            every device keeps 1/W locally
==================  =======================  =================================

:func:`roofline_estimate` turns (flops, comm bytes, optionally bytes
accessed) into a chip-free roofline: per-term times against a target chip's
peak MXU rate / HBM bandwidth / ICI bandwidth, ``mfu_est`` = the MFU the
config cannot exceed on that chip, and ``bound`` naming the limiting
resource. ``device_kind=None`` asks for the repo's target chip (v5e) by name
— the chip-free what-if. An ACTUAL device
kind must be in :data:`CHIP_SPECS`: a device the table does not know raises,
so a CPU run never carries a v5e ``mfu_est`` (callers on an unlisted device
omit the field).

``bytes_est`` (trace-only) sums operand+result bytes per equation with scan
multipliers — a fusion-ignorant UPPER bound on HBM traffic, reported but
deliberately NOT fed into ``mfu_est`` (post-fusion truth is 5-20× lower;
use the compiled ``bytes_accessed`` when an executable is at hand).
"""

from __future__ import annotations

import math
from typing import Iterable

from distributed_sigmoid_loss_tpu.analysis.collective_prims import (
    BUCKETS,
    COLLECTIVES,
    collective_axes,
    manual_axis_sizes,
)
from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
    _jaxpr_of,
    _sub_jaxprs,
)

__all__ = [
    "CHIP_SPECS",
    "DEFAULT_CHIP",
    "COLLECTIVE_KINDS",
    "jaxpr_costs",
    "static_attribution",
    "accum_placement",
    "mixed_stack",
    "mixed_stack_line",
    "attribution_of_compiled",
    "roofline_estimate",
    "step_config_attribution",
    "metrics_line_fields",
]

# device_kind -> (peak dense bf16 TFLOP/s, HBM GB/s, aggregate ICI GB/s per
# chip). Public spec-sheet figures: a second peaks table beside
# benchmark/peaks.json, which is the one a reported MFU uses (ROADMAP D2b).
CHIP_SPECS = {
    "TPU v4": (275.0, 1228.0, 300.0),
    "TPU v5 lite": (197.0, 819.0, 200.0),
    "TPU v5e": (197.0, 819.0, 200.0),
    "TPU v5": (459.0, 2765.0, 400.0),
    "TPU v5p": (459.0, 2765.0, 400.0),
    "TPU v6 lite": (918.0, 1640.0, 400.0),
    "TPU v6e": (918.0, 1640.0, 400.0),
}

# The repo's roofline target: what ``device_kind=None`` (the what-if asked
# for by name) is computed against.
DEFAULT_CHIP = "TPU v5 lite"

# The reporting buckets; which primitive reports under which, and its wire
# factor, is analysis/collective_prims.COLLECTIVES (the one table).
COLLECTIVE_KINDS = BUCKETS


def _aval_bytes(v) -> float:
    aval = getattr(v, "aval", None)
    if aval is None:
        return 0.0
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0.0
    return float(size) * getattr(dtype, "itemsize", 4)


def _dot_general_flops(eqn) -> float:
    """2·B·M·N·K for one dot_general application."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = getattr(eqn.invars[0], "aval", None)
    rhs = getattr(eqn.invars[1], "aval", None)
    if lhs is None or rhs is None:
        return 0.0
    ls, rs = lhs.shape, rhs.shape
    batch = math.prod(ls[i] for i in lb) if lb else 1
    k = math.prod(ls[i] for i in lc) if lc else 1
    m = math.prod(
        d for i, d in enumerate(ls) if i not in lc and i not in lb
    )
    n = math.prod(
        d for i, d in enumerate(rs) if i not in rc and i not in rb
    )
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    """2 · |out| · (MACs per output element) for conv_general_dilated."""
    out = getattr(eqn.outvars[0], "aval", None)
    rhs = getattr(eqn.invars[1], "aval", None)
    if out is None or rhs is None:
        return 0.0
    dn = eqn.params.get("dimension_numbers")
    try:
        out_features = rhs.shape[dn.rhs_spec[0]]
    except Exception:
        out_features = rhs.shape[-1]
    macs_per_out = math.prod(rhs.shape) / max(1, out_features)
    return 2.0 * math.prod(out.shape) * macs_per_out


class _Costs:
    __slots__ = ("flops", "bytes_est", "comm")

    def __init__(self):
        self.flops = 0.0
        self.bytes_est = 0.0
        self.comm = {k: 0.0 for k in COLLECTIVE_KINDS}


def _walk(jaxpr, bound: dict, mult: float, acc: _Costs) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name

        if name == "shard_map":
            inner_bound = {**bound, **manual_axis_sizes(eqn)}
            _walk(eqn.params["jaxpr"], inner_bound, mult, acc)
            continue

        if name == "scan":
            body = _jaxpr_of(eqn.params.get("jaxpr"))
            length = float(eqn.params.get("length", 1) or 1)
            if body is not None:
                _walk(body, bound, mult * length, acc)
            continue

        if name == "pallas_call":
            # The kernel body runs once PER GRID STEP: walk its jaxpr (the
            # per-tile dots are ordinary dot_general eqns there) with the
            # grid product as multiplier — closed-form exact for the loss
            # kernels (grid · 2·tile_b·tile_n·d == 2·b·n·d), the same
            # trip-count treatment the scan case gives the chunked path.
            # Leaving it opaque is how mfu_est undercounted every
            # --use-pallas record before round 10.
            body = _jaxpr_of(eqn.params.get("jaxpr"))
            grid = getattr(eqn.params.get("grid_mapping"), "grid", ()) or ()
            steps = 1.0
            for g in grid:
                try:
                    steps *= float(int(g))
                except (TypeError, ValueError):
                    pass  # dynamic grid dim: count the body once (lower bound)
            if body is not None:
                _walk(body, bound, mult * max(steps, 1.0), acc)
            continue

        if name == "cond":
            # Branches are alternatives, not a sequence: charge the costliest
            # one (the conservative upper bound for a static estimate).
            best = None
            for br in eqn.params.get("branches", ()):
                inner = _jaxpr_of(br)
                if inner is None:
                    continue
                sub = _Costs()
                _walk(inner, bound, mult, sub)
                score = sub.flops + sub.bytes_est + sum(sub.comm.values())
                if best is None or score > (
                    best.flops + best.bytes_est + sum(best.comm.values())
                ):
                    best = sub
            if best is not None:
                acc.flops += best.flops
                acc.bytes_est += best.bytes_est
                for k, v in best.comm.items():
                    acc.comm[k] += v
            continue

        if name in COLLECTIVES:
            _role, bucket, wire = COLLECTIVES[name]
            w = 1
            for ax in collective_axes(eqn):
                w *= int(bound.get(ax, 1))
            if bucket is not None and w > 1:
                s = sum(_aval_bytes(v) for v in eqn.invars)
                acc.comm[bucket] += wire(w) * s * mult
            continue

        subs = _sub_jaxprs(eqn.params)
        if subs:
            # Call-like eqns (pjit / remat2 / custom_vjp / while bodies):
            # recurse only — counting the call's own operand bytes would
            # double what the body already counts. while trip counts are
            # unknowable statically; its body is charged once (documented).
            for _, inner in subs:
                _walk(inner, bound, mult, acc)
            continue

        if name == "dot_general":
            acc.flops += _dot_general_flops(eqn) * mult
        elif name == "conv_general_dilated":
            acc.flops += _conv_flops(eqn) * mult
        acc.bytes_est += (
            sum(_aval_bytes(v) for v in eqn.invars)
            + sum(_aval_bytes(v) for v in eqn.outvars)
        ) * mult


def jaxpr_costs(jaxpr_or_closed, bound_axes: dict | None = None) -> dict:
    """Walk one (closed) jaxpr into the static cost dict.

    Returns ``{"flops_est", "bytes_est", "comm_bytes_total",
    "comm_bytes_all_gather", "comm_bytes_ppermute", "comm_bytes_psum",
    "comm_bytes_psum_scatter", "comm_bytes_all_to_all"}`` — flops/bytes are
    PER DEVICE (shard_map bodies trace per-shard shapes; the GSPMD outer
    program is counted at its global shapes, which for the dp-replicated
    towers of this repo is the per-device program too).
    """
    j = _jaxpr_of(jaxpr_or_closed)
    if j is None:
        raise TypeError(f"not a jaxpr: {jaxpr_or_closed!r}")
    acc = _Costs()
    _walk(j, dict(bound_axes or {}), 1.0, acc)
    out = {
        "flops_est": acc.flops,
        "bytes_est": acc.bytes_est,
        "comm_bytes_total": sum(acc.comm.values()),
    }
    for kind in COLLECTIVE_KINDS:
        out[f"comm_bytes_{kind}"] = acc.comm[kind]
    return out


def static_attribution(fn, *args, bound_axes: dict | None = None) -> dict:
    """Trace ``fn(*args)`` (abstract — ShapeDtypeStructs work) and return its
    :func:`jaxpr_costs`. The trace-only path: seconds, no compile, CPU-safe —
    what cmd_train stamps onto every metrics line."""
    import jax

    return jaxpr_costs(jax.make_jaxpr(fn)(*args), bound_axes=bound_axes)


def accum_placement(step) -> dict | None:
    """Where a traced train step's microbatch accumulation adds a parameter's
    gradient, from the record ``make_train_step`` writes while it traces
    (train/train_step.py): parameter bytes added inside the backward layer loop
    (scanned stacks, in the weight-gradient matmul's epilogue), bytes
    ``accum_add`` carries as a pass of its own, and the first's share. None
    for a step that has not traced yet or does not accumulate."""
    record = getattr(step, "accum_record", None)
    if not record:
        return None
    total = record["layer_loop_bytes"] + record["accum_add_bytes"]
    return dict(record, layer_loop_share=record["layer_loop_bytes"] / total)


def mixed_stack(step) -> dict | None:
    """What a traced train step's text tower runs where it is a stack of
    several layer kinds with dropless routed experts, or a stack of windowed
    chunk attention (``eva``, by layer index: the core it took, ``"kernel"``:
    the Pallas pair ``eva_attn_fwd`` / ``eva_attn_bwd``, or ``"dense"``: XLA;
    the tokens a block of the kernel, the window, the chunk, the windows and
    the summaries a sequence; ``scanned``: whether the layers are one scanned
    stack; the experts' keys are then absent), from the record
    ``make_train_step`` writes while it traces (``step.stack_record``): the
    layers' kinds in order (``mixer+mlp`` / ``mixer+moe``), experts held / in
    all / per token, the assignments to held experts a token is expected to
    make under uniform routing, the tokens of a microbatch, the rows the
    dispatch is bounded by (every token choosing held experts only) and, per
    delta-rule layer (``kda_core``, by layer index), which core it took
    (``"kernel"``: the Pallas kernels; ``"chunked"``: XLA operations), where
    its per-head norms ran (``qk_norm``, the l2 norm of q and k, and ``o_norm``,
    the head RMS norm of o: ``"kernel"``, on the head's tile inside the
    kernels, or ``"xla"``, on a (b, s, h, d) view around the core) with the
    rows, heads and chunks of a call and the bytes a differentiated call keeps
    from its forward to its backward (``kept_bytes``: each chunk's incoming
    state, what the forward solved of it and 1 / rms on the kernel path, 0 on
    the chunked one), and per latent-attention layer (``mla``,
    by layer index) what it is made of: the queries' latent (``q_rank``, 0 = one
    projection) and the keys' and values' (``kv_rank``), the width of the
    rotated parts and their base (``rotated_dim`` 0 and ``rope_theta`` None
    where nothing is rotated), the core it took (``"kernel"``: the repo's
    Pallas pair on (b, s, h x d), ``ops/pallas_latent_attention.py``;
    ``"flash"``: the library's blocked kernel; ``"dense"``: XLA), the query/key
    and value head sizes, the sizes the core ran them at and whether any head
    was zero-padded to them, the tokens a block of a fused core and the sequence
    with the zero rows that fill its last block; per state-space layer (``ssm``,
    by layer index) the core ``ops/ssm.py ssm_core`` names with its chunk, chunks
    a sequence, rows a pass, heads, groups, head size, state and ``kept_bytes``;
    per attention layer with head sizes of its own (``attn``, by layer index)
    what ``models/transformer.py attention_core`` says: the core, the query and
    key / value heads, the head size, whether keys and values were repeated;
    per delta-rule and state-space layer (``short_conv``, by layer index) one
    entry for each width its short convolution runs at (a delta-rule layer's q,
    k and v share one; a state-space layer has x' and B, C), as
    ``ops/gated_delta_rule.py short_conv_core`` says: ``form`` (``"kernel"``:
    the Pallas pair ``short_conv_fwd`` / ``short_conv_bwd``; ``"xla"``), the
    ``channels`` and a program's ``tile`` of (tokens, channels), None in XLA. A
    stack of one-sub-layer layers (``TextConfig.sublayers="single"``) names
    each layer's one kind in ``layer_kinds`` ("ssm", "attn", "moe"). None for a
    step that has not traced yet or runs no such tower. :func:`mixed_stack_line` is the same on
    one line, which ``train`` prints once a run."""
    return dict(getattr(step, "stack_record", None) or {}) or None


def mixed_stack_line(record: dict | None) -> str | None:
    """What ``mixed_stack`` found, on one line, so that a run says without a
    trace which cores engaged: per latent-attention and per delta-rule layer the
    core taken with the blocks and head sizes it ran at. None for no record."""
    if not record:
        return None
    parts = [" ".join(record["layer_kinds"])]
    for i, m in sorted(record.get("mla", {}).items()):
        ran = f"heads {m['core_head_dim']}/{m['core_v_dim']}" + (" (zero-padded)" if m["padded"] else "")
        blocks = "" if m["block"] is None else f", {m['core_tokens'] // m['block']} blocks of {m['block']} tokens"
        parts.append(f"mla[{i}] core={m['core']} {ran}{blocks}")
    for i, e in sorted(record.get("eva", {}).items()):
        blocks = "" if e["block"] is None else f", blocks of {e['block']} tokens"
        parts.append(f"eva[{i}] core={e['core']} {e['windows']} windows of {e['window']}, {e['summaries']} summaries{blocks}")
    if "scanned" in record:
        parts.append("scanned" if record["scanned"] else "unrolled")
    for i, c in sorted(record.get("ssm", {}).items()):
        kept = f", {c['kept_bytes'] / 1e6:.0f} MB kept for the backward" if c["kept_bytes"] else ""
        parts.append(f"ssm[{i}] core={c['core']} {c['chunks']} chunks of {c['chunk']}, {c['heads']} heads of "
                     f"{c['head_dim']} in {c['groups']} groups, state {c['state']}, {c['rows_per_pass']} rows a pass{kept}")
    for i, a in sorted(record.get("attn", {}).items()):
        blocks = "" if a["block"] is None else f", blocks of {a['block']} tokens"
        repeated = " (keys and values repeated)" if a["kv_repeated"] else ""
        if a["block_pairs"] is not None:
            blocks += f", {a['block_pairs']} block pairs a head"
        # A layer with a window or options of its own says so; Nemotron's line stays what it was.
        window = f" window {a['window']}, {a['admitted_pairs']} pairs a head," if a["window"] else ""
        options = "".join(f" {name}" for name in ("rotated", "qk_norm", "gated") if a[name])
        parts.append(f"attn[{i}] core={a['core']}{window} {a['heads']}/{a['kv_heads']} heads of "
                     f"{a['head_dim']}{repeated}{options}{blocks}")
    for i, k in sorted(record.get("kda_core", {}).items()):
        kept = f", {k['kept_bytes'] / 1e6:.0f} MB kept for the backward" if k["kept_bytes"] else ""
        parts.append(f"kda[{i}] core={k['core']} qk_norm={k['qk_norm']} o_norm={k['o_norm']}, {k['chunks']} chunks{kept}")
    for i, convs in sorted(record.get("short_conv", {}).items()):
        said = ", ".join(c["form"] + (f" {c['tile'][0]}x{c['tile'][1]}" if c["tile"] else "") + f" of {c['channels']}"
                         for c in convs)
        parts.append(f"conv[{i}] {said}")
    return "stack: " + "; ".join(parts)


def attribution_of_compiled(compiled) -> dict:
    """What XLA says about an already-compiled executable: executed FLOPs and
    post-fusion bytes accessed (``cost_analysis``), plus the static memory
    accounting (``memory_stats_of_compiled`` — ``temp_size_in_bytes`` is the
    peak-temp figure memory optimizations are judged by). Fields are None
    when the backend withholds the analysis."""
    from distributed_sigmoid_loss_tpu.utils.profiling import (
        memory_stats_of_compiled,
    )

    out = {"flops_exec": None, "bytes_accessed": None}
    try:
        cost = compiled.cost_analysis()
        if cost:
            if cost.get("flops", 0) > 0:
                out["flops_exec"] = float(cost["flops"])
            ba = cost.get("bytes accessed", 0)
            if ba > 0:
                out["bytes_accessed"] = float(ba)
    except Exception:
        pass
    mem = memory_stats_of_compiled(compiled)
    out["peak_temp_bytes"] = mem["temp_size_in_bytes"] if mem else None
    out["peak_bytes"] = mem["peak_bytes"] if mem else None
    return out


def roofline_estimate(
    flops: float,
    comm_bytes_total: float,
    bytes_accessed: float | None = None,
    device_kind: str | None = None,
) -> dict:
    """Chip-free roofline: per-resource step-time lower bounds against the
    target chip, the limiting resource, and ``mfu_est`` — the MFU ceiling the
    program's arithmetic/traffic ratio permits there. ``mfu_est`` is an
    upper bound on achievable MFU, not a prediction of the measured one
    (overlap, dispatch and kernel overheads only lower it further).

    ``device_kind=None`` is the what-if against :data:`DEFAULT_CHIP`; a named
    device outside :data:`CHIP_SPECS` raises — an unknown device is an error,
    not a v5e."""
    kind = DEFAULT_CHIP if device_kind is None else device_kind
    if kind not in CHIP_SPECS:
        raise ValueError(
            f"no roofline peaks for device_kind {kind!r} (known: "
            f"{sorted(CHIP_SPECS)}); pass device_kind=None for the "
            f"{DEFAULT_CHIP} what-if"
        )
    tflops, hbm_gbps, ici_gbps = CHIP_SPECS[kind]
    compute_s = flops / (tflops * 1e12)
    comm_s = comm_bytes_total / (ici_gbps * 1e9)
    mem_s = (bytes_accessed or 0.0) / (hbm_gbps * 1e9)
    terms = {"compute": compute_s, "comm": comm_s, "memory": mem_s}
    t_bound = max(terms.values())
    bound = max(terms, key=terms.get) if t_bound > 0 else "compute"
    mfu_est = (compute_s / t_bound) if t_bound > 0 else 0.0
    return {
        "mfu_est": round(mfu_est, 3),
        "bound": bound,
        "est_step_ms_lower_bound": round(t_bound * 1e3, 3),
        "roofline_chip": kind,
    }


def step_config_attribution(
    n_devices: int | None = None,
    labels: Iterable[str] | None = None,
    device_kind: str | None = None,
) -> dict:
    """Static attribution for the step configs graftlint already enumerates.

    Reuses ``analysis/jaxpr_audit.step_config_jaxprs`` (the REAL step
    builders traced abstractly on the virtual CPU mesh) — label ->
    ``jaxpr_costs`` + ``roofline_estimate``. Trace-only; the compiled-side
    fields (peak temp) come from :func:`attribution_of_compiled` on whatever
    executable the caller actually compiles.
    """
    from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
        step_config_jaxprs,
    )

    jaxprs = step_config_jaxprs(n_devices)
    want = set(labels) if labels is not None else set(jaxprs)
    out = {}
    for label, (closed, _kwargs) in jaxprs.items():
        if label not in want:
            continue
        costs = jaxpr_costs(closed)
        costs.update(roofline_estimate(
            costs["flops_est"], costs["comm_bytes_total"],
            device_kind=device_kind,
        ))
        out[label] = costs
    return out


def metrics_line_fields(costs: dict, device_kind: str | None = None) -> dict:
    """The attribution scalars a train metrics line carries:
    ``comm_bytes_total`` (per-device wire bytes per step, a count from
    shapes) always, and ``mfu_est`` (roofline ceiling) only when
    ``device_kind`` is a listed chip or the ``None`` what-if — a run on an
    unlisted device (the CPU) carries no utilization figure."""
    fields = {"comm_bytes_total": float(costs["comm_bytes_total"])}
    if device_kind is None or device_kind in CHIP_SPECS:
        fields["mfu_est"] = roofline_estimate(
            costs["flops_est"], costs["comm_bytes_total"],
            device_kind=device_kind,
        )["mfu_est"]
    return fields
