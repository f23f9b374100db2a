"""graftprove half 2: sharding/state dataflow rules over the traced jaxprs.

Extends jaxpr_audit's ``_Auditor`` invariance walk (per-value ``(inv, red)``
frozenset pairs: axes a value is replicated over, and the subset it is
replicated over BECAUSE it was already reduced/gathered) with rules for bug
classes the base auditor's communication checks don't see:

- ``jaxpr-redundant-gather``: an ``all_gather`` whose operand is already
  known-invariant (replicated) over every gathered axis — W identical copies
  concatenated, pure wire + HBM waste. Scoped to gathers on purpose: a
  ``psum`` of a replicated-but-not-reduced value is jax's own sanctioned
  psum-self-transpose convention (the pmean backward, compensated by 1/S)
  and must stay silent, and a psum of an already-REDUCED value is already
  ``jaxpr-double-psum``. Unknown ⇒ varying ⇒ silent, the base walk's
  no-false-positive direction.
- ``jaxpr-state-drop``: a ``scan`` carry that the body READS and UPDATES
  with data from outside the carry, whose final value then never leaves the
  scan — state the program pretends to maintain but actually discards (the
  historical pp-silently-dropped-quant bug; the class the compression
  stream's error-feedback residual lives in). Pure carry rotations
  (``ppermute`` of the carry itself, counters ``c+1``) are exempt: their
  update depends on nothing outside the carry, so dropping the final value
  loses no information that entered the loop. GPipe's drained shift
  registers (parallel/pipeline.py) are updated WITH external microbatch data
  by design and legitimately drained — pp step configs opt out via
  ``check_state_drop=False``, same per-config-kwarg pattern as
  ``expect_chunk_checkpoint``.
- ``jaxpr-collective-order``: across ``cond`` branches, the per-axis
  sequence of collectives must match whenever the predicate is not
  known-invariant over that axis — shards disagreeing on the branch would
  enter different collective sequences and deadlock the mesh (the multihost
  hang class).
- ``jaxpr-ef-threaded``: for error-feedback step configs, each EF-residual
  OUTPUT leaf must transitively depend on non-EF step inputs (the gradient
  data) — a residual with no input dependence was dropped/re-zeroed, one
  depending only on the incoming EF leaves was passed through un-updated.
  Backward-dependence pass (``_outvar_deps``) that recurses positionally
  through pjit/remat/shard_map and goes conservative (all-inputs union)
  elsewhere, so it can only under-fire, never false-fire. Armed per config
  via ``ef_indices`` from ``jaxpr_audit.step_config_jaxprs``.
- ``jaxpr-codec-threaded``: for learned-rung step configs (graftcodec), the
  codec operands entering the step (``state.comp`` ``codec_enc``/
  ``codec_dec``, host-trained and replicated) must transitively reach the
  updated params — a step that takes the codec but never lets the decode
  touch the gradient path silently trains on the ENCODER-SIDE reconstruction
  while claiming the learned rung; and the per-round codec stats the host
  trainer consumes (``blockmoment``, ``codec_recon_err``) must depend on
  non-codec step inputs (this round's gradient data) — a constant or
  passed-through stat starves the trainer and freezes the codec at its DCT
  cold start with nothing ever reporting it. Same ``_outvar_deps`` backward
  pass as jaxpr-ef-threaded (conservative unions can only under-fire).
  Armed per config via ``codec_indices`` from
  ``jaxpr_audit.step_config_jaxprs``.
- ``jaxpr-gather-placement``: for ``update_sharding="full"`` step configs
  (graftshard), an ``all_gather`` over the update-shard axis whose operand
  was produced (transitively) by a ``psum_scatter``/``reduce_scatter`` over
  that same axis — the exact regression that silently re-replicates the
  1/W update the reduce-scatter just paid to shard, turning the single
  post-update param publish into a per-gradient gather storm. Forward taint
  pass: scatters over the axis taint their outputs, taint propagates
  through eqns (positionally through ``_POSITIONAL_CALLS``, coarsely
  elsewhere), and a gather of a tainted value over the same axis fires.
  Gathers of un-tainted values (the loss island's embedding all-gathers)
  stay silent — scatter-then-gather is the discriminator, not the gather
  itself. Armed per config via ``update_shard_axis`` from
  ``jaxpr_audit.step_config_jaxprs``.

Run alongside the base audit by ``audit_default_step_configs`` for every
config in the sampled product; rule catalog in docs/ANALYSIS.md.
"""

from __future__ import annotations

from distributed_sigmoid_loss_tpu.analysis.collective_prims import (
    COLLECTIVES,
    collective_axes,
    names_with_role,
)
from distributed_sigmoid_loss_tpu.analysis.findings import Finding
from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
    _Auditor,
    _is_literal,
    _jaxpr_of,
    _sub_jaxprs,
)

__all__ = ["SHARD_FLOW_RULES", "audit_shard_flow"]

SHARD_FLOW_RULES = (
    "jaxpr-redundant-gather",
    "jaxpr-state-drop",
    "jaxpr-collective-order",
    # The EF residual entering a compressed step must leave it UPDATED with
    # gradient data — never dropped (a constant output) and never passed
    # through as a pure function of the old residual (see
    # _check_ef_threading; ROADMAP item 2's named rule).
    "jaxpr-ef-threaded",
    # The learned rung's codec operands must reach the update path and its
    # host-trainer stats must draw on this round's gradients — never a
    # dropped decode or a frozen stat (see _check_codec_threading;
    # graftcodec's named rule).
    "jaxpr-codec-threaded",
    # Under update_sharding="full", a reduce-scattered value must never be
    # all-gathered back over the shard axis before the optimizer update
    # (see _check_gather_placement; graftshard's named rule).
    "jaxpr-gather-placement",
)

_GATHER_PRIMS = names_with_role("gather")
# Collectives that synchronize across shards of an axis — the ones whose
# cross-branch ordering matters for the deadlock check: everything that puts
# bytes on the wire (a mismatched ppermute leaves peers waiting too);
# axis_index and pvary communicate nothing.
_SYNC_COLLECTIVES = frozenset(COLLECTIVES) - names_with_role("retype", "index")


def _collective_sequence(jaxpr, out: list) -> None:
    """Flat (prim_name, axes) sequence of every named-axis collective under
    ``jaxpr``, in program order, recursing through call-like/scan/shard_map
    sub-jaxprs (a collective inside a scan body synchronizes every
    iteration; for cross-branch comparison its one-body order is what must
    agree)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _SYNC_COLLECTIVES:
            axes = collective_axes(eqn)
            if axes:
                out.append((name, axes))
        for _, inner in _sub_jaxprs(eqn.params):
            _collective_sequence(inner, out)


class _FlowAuditor(_Auditor):
    """The base invariance walk plus the redundant-gather and
    collective-order emissions (state-drop is a separate structural pass —
    it needs liveness, not invariance)."""

    def _walk_collective(self, eqn, env, bound, emit, get) -> None:
        name = eqn.primitive.name
        if name in _GATHER_PRIMS and emit:
            axes = collective_axes(eqn)
            v = eqn.invars[0]
            # Scalars exempt: a gathered scalar is bookkeeping wire (the
            # compressed hop's quant-scale exchange double-syncs the two
            # scalar params whose grads the loss island already psum'd over
            # dcn — 4 bytes, uniform-tree compression by design), not the
            # W-identical-HBM-blocks waste this rule exists for.
            if (
                axes
                and not _is_literal(v)
                and getattr(getattr(v, "aval", None), "size", 1) > 1
            ):
                inv = get(v)[0]
                covered = sorted(ax for ax in axes if ax in inv)
                if len(covered) == len(axes):
                    self.add(
                        "jaxpr-redundant-gather",
                        f"{name} over axis(es) {covered} of a value already "
                        "replicated over them — every shard contributes an "
                        "identical copy, so the gather is W identical "
                        "blocks of wire traffic and HBM for data each "
                        "shard already holds; drop the gather (or shard "
                        "the producer)",
                    )
        super()._walk_collective(eqn, env, bound, emit, get)

    def _walk_cond(self, eqn, env, bound, emit, get) -> None:
        if emit:
            branches = eqn.params.get("branches", ())
            seqs = []
            for br in branches:
                inner = _jaxpr_of(br)
                seq: list = []
                if inner is not None:
                    _collective_sequence(inner, seq)
                seqs.append(tuple(seq))
            pred_inv = get(eqn.invars[0])[0] if eqn.invars else frozenset()
            axes_seen = sorted(
                {ax for seq in seqs for _, axes in seq for ax in axes}
            )
            for ax in axes_seen:
                if ax in pred_inv:
                    # Every shard of ax agrees on the predicate, so they all
                    # take the same branch — differing sequences can't split
                    # the axis.
                    continue
                if ax not in bound:
                    continue  # foreign axis: jaxpr-collective-axis's beat
                per_branch = [
                    tuple((n, axes) for n, axes in seq if ax in axes)
                    for seq in seqs
                ]
                if len(set(per_branch)) > 1:
                    shapes = ", ".join(
                        "[" + " ".join(n for n, _ in pb) + "]"
                        for pb in per_branch
                    )
                    self.add(
                        "jaxpr-collective-order",
                        f"cond branches run different collective sequences "
                        f"over axis {ax!r} ({shapes}) and the predicate is "
                        "not known replicated over it — shards that "
                        "disagree on the branch enter mismatched "
                        "collectives and the mesh deadlocks (multihost "
                        "hang class); hoist the collectives out of the "
                        "cond or make the predicate axis-invariant",
                    )
        super()._walk_cond(eqn, env, bound, emit, get)


# ---------------------------------------------------------------------------
# jaxpr-state-drop: a structural liveness pass, independent of invariance.


def _external_deps(body, var, carry_invars: set) -> bool:
    """Does ``var``'s transitive definition inside ``body`` draw on anything
    beyond the carry invars (consts, xs slices, constvars)? False for pure
    carry rotations/counters — the exempt class."""
    produced_by: dict = {}
    for eqn in body.eqns:
        for ov in eqn.outvars:
            produced_by[ov] = eqn
    seen: set = set()
    stack = [var]
    while stack:
        v = stack.pop()
        if _is_literal(v) or v in seen:
            continue
        seen.add(v)
        eqn = produced_by.get(v)
        if eqn is None:
            # A leaf: a body invar or constvar. External unless it is one of
            # the carry's own invars.
            if v not in carry_invars:
                return True
            continue
        stack.extend(eqn.invars)
        # Sub-jaxpr closures (scan/cond/pjit bodies) see only their mapped
        # operands, which are already in eqn.invars; constvars of the OUTER
        # body reached through them are leaves handled above.
    return False


def _live_vars(jaxpr) -> set:
    live = set(v for v in jaxpr.outvars if not _is_literal(v))
    for eqn in jaxpr.eqns:
        live.update(v for v in eqn.invars if not _is_literal(v))
    return live


def _is_drop_var(v) -> bool:
    return type(v).__name__ == "DropVar"


def _check_state_drops(jaxpr, add) -> None:
    """Recursively flag scan carries that are read, updated with external
    data, and whose final value is dead at the scan's own level."""
    live = _live_vars(jaxpr)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            body = _jaxpr_of(eqn.params.get("jaxpr"))
            if body is not None and not any(
                beqn.primitive.name == "add_any" for beqn in body.eqns
            ):
                # add_any is a transpose-only primitive: a scan body holding
                # one is AD-generated cotangent accumulation (the reversed
                # scan legitimately drops the cotangent of a constant carry
                # init), not user state — only forward-authored scans are in
                # scope for the drop check.
                nc = eqn.params.get("num_consts", 0)
                ncar = eqn.params.get("num_carry", 0)
                carry_invars = set(body.invars[nc : nc + ncar])
                reads: set = set()
                for beqn in body.eqns:
                    reads.update(
                        v for v in beqn.invars
                        if not _is_literal(v) and v in carry_invars
                    )
                # A carry passed through to a ys output is also a read.
                for ov in body.outvars[ncar:]:
                    if not _is_literal(ov) and ov in carry_invars:
                        reads.add(ov)
                for i in range(min(ncar, len(eqn.outvars))):
                    ci = body.invars[nc + i]
                    co = body.outvars[i]
                    scan_out = eqn.outvars[i]
                    if ci not in reads:
                        continue  # write-only slot; not "read then dropped"
                    if co is ci or _is_literal(co):
                        continue  # passthrough / constant: nothing updated
                    if not (_is_drop_var(scan_out) or scan_out not in live):
                        continue  # the final value IS consumed
                    if not _external_deps(body, co, carry_invars):
                        # Pure rotation/counter (ring ppermute buffers,
                        # c + 1): dropping it loses nothing that entered
                        # the loop.
                        continue
                    aval = getattr(ci, "aval", None)
                    add(
                        "jaxpr-state-drop",
                        f"scan carry #{i} ({aval}) is read by the body and "
                        "updated with non-carry data, but the updated value "
                        "never leaves the scan — state the program "
                        "maintains and then silently discards (the "
                        "pp-dropped-quant / error-feedback-residual "
                        "class); thread the final carry to an output or "
                        "stop carrying it",
                    )
        for _, inner in _sub_jaxprs(eqn.params):
            _check_state_drops(inner, add)


# Call-like primitives whose inner jaxpr maps 1:1 positionally onto the
# eqn's invars/outvars — the cases _outvar_deps can recurse through exactly.
# Anything else (scan's consts+carry+xs layout, while, cond branches) falls
# back to the conservative all-inputs union, which can only make dependence
# sets LARGER — the rule's silent direction (it misses nothing on the
# shipped tree, and never false-fires).
_POSITIONAL_CALLS = frozenset({
    "pjit", "jit", "closed_call", "core_call", "remat", "remat2",
    "checkpoint", "custom_jvp_call", "custom_vjp_call", "shard_map", "smap",
})


def _positional_inner(eqn):
    if eqn.primitive.name not in _POSITIONAL_CALLS:
        return None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            inner = _jaxpr_of(eqn.params[key])
            if (
                inner is not None
                and len(inner.invars) == len(eqn.invars)
                and len(inner.outvars) == len(eqn.outvars)
            ):
                return inner
    return None


def _outvar_deps(jaxpr, memo: dict) -> list:
    """Per-outvar transitive dependence on the jaxpr's OWN invar positions.

    Forward pass over the (topologically ordered) eqns; recurses positionally
    through _POSITIONAL_CALLS eqns and unions all inputs otherwise. Returns
    ``[frozenset[int], ...]`` aligned with ``jaxpr.outvars``; literals and
    constvars contribute nothing (a constant has no input dependence).
    """
    key = id(jaxpr)
    if key in memo:
        return memo[key]
    memo[key] = [frozenset() for _ in jaxpr.outvars]  # cycle guard
    dep: dict = {v: frozenset([i]) for i, v in enumerate(jaxpr.invars)}

    def get(v):
        if _is_literal(v):
            return frozenset()
        return dep.get(v, frozenset())

    for eqn in jaxpr.eqns:
        inner = _positional_inner(eqn)
        if inner is not None:
            inner_deps = _outvar_deps(inner, memo)
            outsets = [
                frozenset().union(*(get(eqn.invars[i]) for i in ideps))
                if ideps else frozenset()
                for ideps in inner_deps
            ]
        else:
            u = (
                frozenset().union(*(get(iv) for iv in eqn.invars))
                if eqn.invars else frozenset()
            )
            outsets = [u] * len(eqn.outvars)
        for ov, s in zip(eqn.outvars, outsets):
            dep[ov] = s
    result = [get(v) for v in jaxpr.outvars]
    memo[key] = result
    return result


def _check_ef_threading(jaxpr, ef_indices, add) -> None:
    """jaxpr-ef-threaded: every EF-residual output must depend on non-EF
    inputs (gradient data). A residual that depends on NOTHING is a dropped/
    re-zeroed carry; one that depends ONLY on the EF inputs is passed through
    (or merely decayed) un-updated — both are the silent-drop bug class the
    pp/quant composition already taught us (compression runs, the claimed
    error feedback never happens, the quantization bias accumulates
    un-carried)."""
    ef_in, ef_out = ef_indices
    ef_in_set = frozenset(ef_in)
    dep_sets = _outvar_deps(jaxpr, {})
    for o in ef_out:
        if o >= len(dep_sets):
            add(
                "jaxpr-ef-threaded",
                f"ef output index {o} out of range for {len(dep_sets)} "
                "outputs — stale ef_indices plumbing",
            )
            continue
        deps = dep_sets[o]
        if not deps:
            add(
                "jaxpr-ef-threaded",
                f"EF residual output #{o} depends on NO step inputs — the "
                "carried residual is dropped or re-zeroed instead of "
                "accumulating this round's compression error",
            )
        elif deps <= ef_in_set:
            add(
                "jaxpr-ef-threaded",
                f"EF residual output #{o} depends only on the incoming EF "
                f"state (inputs {sorted(deps)}) — passed through un-updated; "
                "the compressed hop's error is silently discarded",
            )


def _check_codec_threading(jaxpr, codec_indices, add) -> None:
    """jaxpr-codec-threaded: the learned rung's two dataflow obligations.

    ``codec_indices`` is ``(codec_in, stat_out, update_out)`` — flattened
    positions of the codec operands among the step inputs, the codec stats
    (blockmoment / codec_recon_err) among the outputs, and the updated-param
    leaves among the outputs. (1) Every stat output must depend on non-codec
    step inputs: empty dependence is a constant stat, codec-only dependence
    is a stat computed from the codec itself — either way the host trainer
    EWMAs noise and the codec never leaves its DCT cold start. (2) At least
    one updated-param output must draw on the codec operands: the decode is
    what turns the wire latents back into a gradient, and a step that drops
    it applies rung-6 "compression" that never actually happened."""
    codec_in, stat_out, update_out = codec_indices
    codec_in_set = frozenset(codec_in)
    dep_sets = _outvar_deps(jaxpr, {})
    for o in stat_out:
        if o >= len(dep_sets):
            add(
                "jaxpr-codec-threaded",
                f"codec stat output index {o} out of range for "
                f"{len(dep_sets)} outputs — stale codec_indices plumbing",
            )
            continue
        deps = dep_sets[o]
        if not deps:
            add(
                "jaxpr-codec-threaded",
                f"codec stat output #{o} depends on NO step inputs — a "
                "constant stat; the host codec trainer would EWMA zeros and "
                "the learned rung freezes at its DCT cold start",
            )
        elif deps <= codec_in_set:
            add(
                "jaxpr-codec-threaded",
                f"codec stat output #{o} depends only on the codec operands "
                f"(inputs {sorted(deps)}) — not on this round's gradients; "
                "the trainer's moment stream carries no new information",
            )
    live_updates = [o for o in update_out if o < len(dep_sets)]
    if codec_in and live_updates and not any(
        dep_sets[o] & codec_in_set for o in live_updates
    ):
        add(
            "jaxpr-codec-threaded",
            "no updated-param output depends on the codec operands "
            "(codec_enc/codec_dec) — the learned rung's decode never reaches "
            "the optimizer update, so the step claims rung-6 compression "
            "while training on something else entirely",
        )


# ---------------------------------------------------------------------------
# jaxpr-gather-placement: the graftshard scatter-then-gather taint pass.

# The primitives that produce a shard-axis-partial value (lax.psum_scatter
# traces to reduce_scatter).
_SCATTER_PRIMS = names_with_role("scatter")


def _check_gather_placement(jaxpr, axis, add, taint_in=None) -> list:
    """Forward taint pass for one jaxpr level; returns per-outvar taint.

    A value is TAINTED once a psum_scatter/reduce_scatter over ``axis``
    produced it — it now holds a 1/W shard of a cross-replica sum, the thing
    graftshard's update path must carry through the optimizer un-gathered.
    An ``all_gather`` over the same axis of a tainted value fires: it
    re-replicates the update the scatter just sharded (param publish is the
    ONE sanctioned gather, and it happens on the post-update params — a
    fresh, never-scattered value — so it cannot taint-match). Propagation is
    positional through ``_POSITIONAL_CALLS`` (shard_map bodies included, so
    the compressed step's manual region is walked exactly) and coarse
    any-in-taints-all-out elsewhere; scan/cond/while interiors are scanned
    for self-contained scatter→gather pairs without seeding, the
    under-fire-never-false-fire direction the module promises.
    """
    taint: dict = {}
    if taint_in:
        for v, t in zip(jaxpr.invars, taint_in):
            if t:
                taint[v] = True

    def tainted(v):
        return not _is_literal(v) and taint.get(v, False)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _SCATTER_PRIMS or name in _GATHER_PRIMS:
            axes = collective_axes(eqn)
            if name in _SCATTER_PRIMS and axis in axes:
                for ov in eqn.outvars:
                    taint[ov] = True
                continue
            if (
                name in _GATHER_PRIMS
                and axis in axes
                and any(tainted(iv) for iv in eqn.invars)
            ):
                aval = getattr(eqn.invars[0], "aval", None)
                add(
                    "jaxpr-gather-placement",
                    f"{name} over axis {axis!r} of a value produced by a "
                    f"reduce-scatter over the same axis ({aval}) — the 1/W "
                    "update shard is re-replicated BEFORE the optimizer "
                    "update, undoing graftshard's sharding and paying a "
                    "per-gradient gather the single post-update param "
                    "publish exists to avoid; keep the optimizer on the "
                    "shard and gather only the updated params",
                )
                # The gathered output is whole again; redundant follow-on
                # gathers are jaxpr-redundant-gather's beat, not this rule's.
                continue
        inner = _positional_inner(eqn)
        if inner is not None:
            inner_taint = _check_gather_placement(
                inner, axis, add, [tainted(iv) for iv in eqn.invars]
            )
            for ov, t in zip(eqn.outvars, inner_taint):
                if t:
                    taint[ov] = True
            continue
        for _, sub in _sub_jaxprs(eqn.params):
            _check_gather_placement(sub, axis, add)
        if any(tainted(iv) for iv in eqn.invars):
            for ov in eqn.outvars:
                taint[ov] = True
    return [tainted(v) for v in jaxpr.outvars]


def audit_shard_flow(
    jaxpr_or_closed,
    *,
    label: str,
    bound_axes: dict | None = None,
    check_state_drop: bool = True,
    ef_indices: tuple | None = None,
    codec_indices: tuple | None = None,
    update_shard_axis: str | None = None,
) -> list[Finding]:
    """Run the shard-flow rules over one (closed) jaxpr.

    ``check_state_drop=False`` is the pp opt-out: GPipe's shift-register
    carries are drained by design (see module docstring). ``ef_indices``
    (``(in_positions, out_positions)`` of the flattened EF-residual leaves,
    computed by jaxpr_audit.step_config_jaxprs for error-feedback configs)
    arms the ``jaxpr-ef-threaded`` dataflow check; None skips it.
    ``codec_indices`` (``(codec_in, stat_out, update_out)`` positions, set
    by step_config_jaxprs for learned-rung configs) arms
    ``jaxpr-codec-threaded`` the same way. ``update_shard_axis`` (the dp axis name, set by step_config_jaxprs for
    ``update_sharding="full"`` configs) arms ``jaxpr-gather-placement``;
    None skips it.
    """
    j = _jaxpr_of(jaxpr_or_closed)
    if j is None:
        raise TypeError(f"not a jaxpr: {jaxpr_or_closed!r}")
    auditor = _FlowAuditor(label)
    bound = dict(bound_axes or {})
    env: dict = {}
    for iv in j.invars:
        env[iv] = (frozenset(), frozenset())
    for cv in getattr(j, "constvars", ()):
        env[cv] = (frozenset(bound), frozenset())
    auditor.walk(j, env, bound, True)
    if check_state_drop:
        _check_state_drops(j, auditor.add)
    if ef_indices is not None:
        _check_ef_threading(j, ef_indices, auditor.add)
    if codec_indices is not None:
        _check_codec_threading(j, codec_indices, auditor.add)
    if update_shard_axis is not None:
        _check_gather_placement(j, update_shard_axis, auditor.add)
    return [f for f in auditor.findings if f.rule in SHARD_FLOW_RULES]
