"""graftlint's jaxpr half: static audit of the distributed loss/train-step
programs' communication structure and dtype hygiene.

Every distributed-correctness bug this repo hit was statically visible in the
jaxpr before a single device cycle: a broken ring permutation silently
zero-fills the shards nobody sends to; a psum of an already-reduced
(axis-invariant) value overcounts S-fold in an unchecked shard_map transpose;
a python-scalar input leaks a weak-typed aval and recompiles per call-site
flavor; dropping the chunk scan's ``jax.checkpoint`` silently re-materializes
the full logits matrix in the backward. This auditor traces the REAL step
builders (make_train_step / make_compressed_train_step) on the virtual-device
CPU mesh — trace only, no compile, no execution — and walks the closed
jaxprs. The "verify the sharded program's communication structure, don't
trust the author" discipline of XLA's cross-replica sharding work (Xu et al.,
arXiv:2004.13336) applied to this repo's own programs.

Rules (ids used by ``lint --disable`` and the Finding records):

- ``jaxpr-ppermute-bijection``: every ppermute perm is a total bijection on a
  live mesh axis (shared check with parallel/collectives.validate_ring_perm).
- ``jaxpr-collective-axis``: every named-axis collective names axes actually
  bound by an enclosing shard_map.
- ``jaxpr-double-psum``: no value reduced TWICE over the same axis along one
  path (the S-fold overcount class). Two taints ride the dataflow: axes a
  value is *invariant* (replicated) over, and axes it was already
  *reduced/gathered* over. Only a psum/psum_scatter of a still-reduced value
  trips the rule: jax's own psum-self-transpose convention (the pmean
  backward psums a replicated cotangent, exactly compensated by the 1/S)
  consumes values that are replicated but NOT reduced, so it stays silent —
  as do psums of literals (the symbolic-zero transpose artifact and the
  ``psum(1)`` axis-size idiom). Mixing a reduced value with varying data
  clears the taint (a later psum is then a genuine new reduction);
  unknown ⇒ varying ⇒ silent, the no-false-positive direction.
- ``jaxpr-f64``: no float64/complex128 avals anywhere (silent x64 promotion).
- ``jaxpr-weak-type``: no weak-typed input avals (python-scalar leak — the
  recompile-per-callsite hazard).
- ``jaxpr-chunk-checkpoint``: the chunked loss's scan carries a
  ``jax.checkpoint``'d body (remat eqn inside a dot-bearing scan) — pins
  PR 3's memory contract structurally, complementing the byte-count
  regression test in tests/test_streamed_loss.py.
- ``jaxpr-bf16-upcast``: (opt-in, ``check_bf16_upcast=True``) no explicit
  bf16→f32 convert feeding a dot_general inside a declared-bf16 region — the
  silent half-MXU-rate upcast; f32 ACCUMULATION via
  ``preferred_element_type`` is the sanctioned pattern and does not trip it.
"""

from __future__ import annotations

from distributed_sigmoid_loss_tpu.analysis.collective_prims import (
    COLLECTIVES,
    collective_axes,
    manual_axis_sizes,
)
from distributed_sigmoid_loss_tpu.analysis.findings import Finding

__all__ = [
    "JAXPR_RULES",
    "audit_jaxpr",
    "step_config_jaxprs",
    "audit_default_step_configs",
    "DEFAULT_STEP_CONFIGS",
]

JAXPR_RULES = (
    "jaxpr-ppermute-bijection",
    "jaxpr-collective-axis",
    "jaxpr-double-psum",
    "jaxpr-f64",
    "jaxpr-weak-type",
    "jaxpr-chunk-checkpoint",
    "jaxpr-bf16-upcast",
)

# The fifteen step configs the acceptance gate requires coverage of (the
# round-4 six plus the round-10 streaming-pallas compositions); see
# step_config_jaxprs for how each is built. The pallas_* configs trace at
# kernel-compatible shapes (embed 128, local_b 8 f32 / 32 int8) so the
# pallas_call genuinely appears in the audited jaxpr — an incompatible shape
# would silently audit the XLA fallback instead.
DEFAULT_STEP_CONFIGS = (
    "fused",
    "chunked",
    "ring",
    "ring_overlap",
    "compressed_dcn",
    "quant_train_int8",
    "pallas_fused",
    "pallas_chunked",
    "pallas_ring",
    "pallas_ring_overlap",
    "pallas_int8_fused",
    "pallas_int8_chunked",
    "pallas_int8_ring",
    "pallas_int8_ring_overlap",
    "compressed_pallas_chunked",
)

_REMAT_PRIMS = {"remat2", "remat", "checkpoint"}

# (invariant-over, reduced-over) for a value we know nothing about.
_VARYING = (frozenset(), frozenset())


def _jaxpr_of(obj):
    """Open jaxpr of a Jaxpr/ClosedJaxpr, else None."""
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    return None


def _sub_jaxprs(params: dict):
    """Every (param_key, open_jaxpr) nested in an eqn's params."""
    out = []
    for k, v in params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for u in vals:
            j = _jaxpr_of(u)
            if j is not None:
                out.append((k, j))
    return out


def _is_literal(v) -> bool:
    # core.Literal has a `val`; Vars do not.
    return hasattr(v, "val") and not hasattr(v, "count")


class _Auditor:
    """One audit pass over a closed jaxpr; collects deduplicated Findings."""

    def __init__(self, label: str, check_bf16_upcast: bool = False):
        self.label = label
        self.check_bf16_upcast = check_bf16_upcast
        self.findings: list[Finding] = []
        self._seen: set = set()
        # Axes bound by an enclosing shard_map(check_vma=True): over these
        # jax's own type says which values are invariant (_walk_shard_map).
        self.typed: frozenset = frozenset()

    def add(self, rule: str, detail: str) -> None:
        key = (rule, detail)
        if key not in self._seen:
            self._seen.add(key)
            self.findings.append(Finding(rule, self.label, detail))

    # -- invariance/reduction-tracking walk ---------------------------------

    def walk(self, jaxpr, env: dict, bound: dict, emit: bool) -> dict:
        """Walk one open jaxpr.

        ``env``: var -> ``(inv, red)`` pair of frozensets: the mesh axes the
        value is known INVARIANT over (replicated; identical on every shard),
        and the subset of those it is invariant over BECAUSE it was already
        reduced/gathered over them (the double-psum taint). Unknown vars
        default to varying ``(∅, ∅)`` — the conservative direction: it can
        only suppress a finding, never fabricate one. Over the axes in
        ``self.typed`` the invariant set is not inferred but read from the
        value's type (``axis not in aval.vma``); the taint, which the type
        does not carry, stays this walk's own. Returns the env (callers map
        outvars through it).
        """

        def get(v):
            if _is_literal(v):
                return (frozenset(bound), frozenset())
            inv, red = env.get(v, _VARYING)
            if self.typed:
                vma = getattr(getattr(v, "aval", None), "vma", self.typed)
                inv = (inv - self.typed) | (self.typed - vma)
            return (inv, red)

        for eqn in jaxpr.eqns:
            name = eqn.primitive.name

            if emit:
                for ov in eqn.outvars:
                    aval = getattr(ov, "aval", None)
                    dt = getattr(aval, "dtype", None)
                    if dt is not None and str(dt) in ("float64", "complex128"):
                        self.add(
                            "jaxpr-f64",
                            f"{name} produces a {dt} value — silent f64 "
                            "promotion (x64 leak); TPU executes f64 in "
                            "software emulation and parity gates assume f32",
                        )

            if name == "shard_map":
                self._walk_shard_map(eqn, env, bound, emit, get)
                continue

            if name in COLLECTIVES:
                self._walk_collective(eqn, env, bound, emit, get)
                continue

            if name == "scan":
                self._walk_scan(eqn, env, bound, emit, get)
                continue

            if name == "cond":
                self._walk_cond(eqn, env, bound, emit, get)
                continue

            subs = _sub_jaxprs(eqn.params)
            if subs:
                if name == "while":
                    # Loop-carried invariance needs a fixpoint; assume varying
                    # everywhere inside (silent, never wrong).
                    for _, inner in subs:
                        self.walk(inner, {}, bound, emit)
                    for ov in eqn.outvars:
                        env[ov] = _VARYING
                else:
                    # Call-like eqns (pjit, remat2, custom_jvp/vjp, ...): map
                    # operands through positionally when the arity matches.
                    self._walk_call(eqn, subs, env, bound, emit, get)
                continue

            # Default: elementwise/structural op — invariance is preserved
            # only when EVERY operand is invariant over the axis; the
            # reduced taint survives only while the value stays invariant
            # (mixing with varying data makes a later psum a NEW reduction).
            inv, red = None, frozenset()
            for v in eqn.invars:
                ii, rr = get(v)
                inv = ii if inv is None else (inv & ii)
                red = red | rr
            if inv is None:
                inv = frozenset(bound)  # no operands (iota, rng seeds, ...)
            for ov in eqn.outvars:
                env[ov] = (inv, red & inv)

        if self.check_bf16_upcast and emit:
            self._check_bf16_upcasts(jaxpr)
        return env

    def _walk_shard_map(self, eqn, env, bound, emit, get) -> None:
        manual = eqn.params["manual_axes"]
        inner_bound = {**bound, **manual_axis_sizes(eqn)}
        inner = eqn.params["jaxpr"]
        inner_env: dict = {}
        for iv, spec in zip(inner.invars, eqn.params["in_specs"]):
            sharded_over: set = set()
            for part in spec:
                if part is not None:
                    sharded_over.update(
                        part if isinstance(part, tuple) else (part,)
                    )
            # A P()-replicated input is invariant over every bound axis; a
            # P("dp")-sharded one varies over dp. Neither is REDUCED yet.
            inner_env[iv] = (
                frozenset(ax for ax in inner_bound if ax not in sharded_over),
                frozenset(),
            )
        for cv in inner.constvars:
            inner_env[cv] = (frozenset(inner_bound), frozenset())
        outer_typed = self.typed
        self.typed = (outer_typed - manual) | (
            manual if eqn.params["check_vma"] else frozenset()
        )
        self.walk(inner, inner_env, inner_bound, emit)
        self.typed = outer_typed
        for ov in eqn.outvars:
            env[ov] = _VARYING

    def _walk_collective(self, eqn, env, bound, emit, get) -> None:
        name = eqn.primitive.name
        role = COLLECTIVES[name][0]
        axes = collective_axes(eqn)
        if emit:
            for ax in axes:
                if ax not in bound:
                    self.add(
                        "jaxpr-collective-axis",
                        f"{name} over axis {ax!r} which no enclosing "
                        f"shard_map binds (bound: {sorted(bound) or 'none'})"
                        " — the collective would resolve against a stale or "
                        "foreign axis environment",
                    )
        if role == "permute" and emit and axes:
            size = bound.get(axes[0])
            if size is not None:
                from distributed_sigmoid_loss_tpu.parallel.collectives import (
                    ring_perm_problems,
                )

                for problem in ring_perm_problems(
                    eqn.params.get("perm", ()), size
                ):
                    self.add(
                        "jaxpr-ppermute-bijection",
                        f"{name} over {axes[0]!r} (size {size}): {problem}",
                    )
        if role in ("sum", "scatter") and emit:
            # Both ADD over their axes: a second one over the same axis of a
            # value already reduced there is the S-fold overcount.
            for v in eqn.invars:
                if _is_literal(v):
                    # psum of a trace-time constant: either a symbolic-zero
                    # transpose artifact or the deliberate psum(1) axis-size
                    # idiom — never the overcount bug.
                    continue
                already = sorted(set(axes) & get(v)[1])
                if already:
                    self.add(
                        "jaxpr-double-psum",
                        f"{name} over axis(es) {already} of a value that was "
                        "already reduced/gathered over them — each shard "
                        "contributes the identical summed value, so the "
                        "result is S-fold the intended sum (the shard_map-"
                        "transpose overcount class)",
                    )
        # Output invariance + reduction taint:
        axset = frozenset(axes)
        if role in ("sum", "extremum", "gather"):
            # Every shard ends with the same value; a max/min adds nothing up,
            # so it leaves no taint.
            taint = frozenset() if role == "extremum" else axset
            for ov, v in zip(eqn.outvars, eqn.invars):
                inv, red = get(v)
                env[ov] = (inv | axset, (red | taint) & (inv | axset))
        elif role == "index":
            for ov in eqn.outvars:
                env[ov] = (frozenset(bound) - axset, frozenset())
        elif role == "permute":
            # permuting a replicated value is the identity; varying stays varying
            for ov, v in zip(eqn.outvars, eqn.invars):
                env[ov] = get(v)
        elif role == "retype":
            # Retyped as varying, the same bytes: a sum it already went
            # through still counts against the next one.
            for ov, v in zip(eqn.outvars, eqn.invars):
                inv, red = get(v)
                env[ov] = (inv - axset, red)
        else:  # scatter, all_to_all, ...: shards end up with distinct pieces
            for ov in eqn.outvars:
                env[ov] = _VARYING

    def _walk_scan(self, eqn, env, bound, emit, get) -> None:
        body = _jaxpr_of(eqn.params.get("jaxpr"))
        if body is None:
            for ov in eqn.outvars:
                env[ov] = _VARYING
            return
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        in_inv = [get(v) for v in eqn.invars]
        carry_inv = list(in_inv[nc : nc + ncar])

        def meet(a, b):
            inv = a[0] & b[0]
            return (inv, (a[1] | b[1]) & inv)

        def body_pass(carry, do_emit):
            ienv: dict = {}
            seq = list(in_inv[:nc]) + list(carry) + list(in_inv[nc + ncar :])
            for iv, inv in zip(body.invars, seq):
                ienv[iv] = inv
            for cv in getattr(body, "constvars", ()):
                ienv[cv] = (frozenset(bound), frozenset())
            self.walk(body, ienv, bound, do_emit)
            outs = []
            for ov in body.outvars:
                outs.append(
                    (frozenset(bound), frozenset()) if _is_literal(ov)
                    else ienv.get(ov, _VARYING)
                )
            return outs

        # Fixpoint on the carry's invariance (the invariant set only shrinks,
        # so this terminates fast); findings emit only on the settled pass.
        for _ in range(2 * len(bound) * max(ncar, 1) + 2):
            outs = body_pass(carry_inv, do_emit=False)
            new_carry = [meet(a, b) for a, b in zip(carry_inv, outs[:ncar])]
            if new_carry == carry_inv:
                break
            carry_inv = new_carry
        outs = body_pass(carry_inv, do_emit=emit)
        for i, ov in enumerate(eqn.outvars):
            if i < ncar:
                env[ov] = carry_inv[i] if i < len(carry_inv) else _VARYING
            else:
                env[ov] = outs[i] if i < len(outs) else _VARYING

    def _walk_cond(self, eqn, env, bound, emit, get) -> None:
        branches = eqn.params.get("branches", ())
        ops = eqn.invars[1:]
        out_inv = None
        for br in branches:
            inner = _jaxpr_of(br)
            if inner is None:
                continue
            ienv: dict = {}
            if len(inner.invars) == len(ops):
                for iv, v in zip(inner.invars, ops):
                    ienv[iv] = get(v)
            for cv in getattr(inner, "constvars", ()):
                ienv[cv] = (frozenset(bound), frozenset())
            self.walk(inner, ienv, bound, emit)
            outs = [
                (frozenset(bound), frozenset()) if _is_literal(ov)
                else ienv.get(ov, _VARYING)
                for ov in inner.outvars
            ]
            out_inv = outs if out_inv is None else [
                ((a[0] & b[0]), (a[1] | b[1]) & (a[0] & b[0]))
                for a, b in zip(out_inv, outs)
            ]
        for i, ov in enumerate(eqn.outvars):
            env[ov] = (
                out_inv[i] if out_inv is not None and i < len(out_inv)
                else _VARYING
            )

    def _walk_call(self, eqn, subs, env, bound, emit, get) -> None:
        """pjit / remat2 / custom_jvp / custom_vjp / closed_call: positional
        1:1 operand mapping when the arity matches, varying otherwise."""
        _, inner = subs[0]
        ienv: dict = {}
        if len(inner.invars) == len(eqn.invars):
            for iv, v in zip(inner.invars, eqn.invars):
                ienv[iv] = get(v)
        for cv in getattr(inner, "constvars", ()):
            ienv[cv] = (frozenset(bound), frozenset())
        self.walk(inner, ienv, bound, emit)
        # Extra sub-jaxprs (e.g. custom_vjp's fwd/bwd thunks are not Jaxprs;
        # anything that is gets a conservative varying walk for the
        # axis/bijection/f64 checks).
        for _, extra in subs[1:]:
            self.walk(extra, {}, bound, emit)
        if len(inner.outvars) == len(eqn.outvars):
            for ov, io in zip(eqn.outvars, inner.outvars):
                env[ov] = (
                    (frozenset(bound), frozenset()) if _is_literal(io)
                    else ienv.get(io, _VARYING)
                )
        else:
            for ov in eqn.outvars:
                env[ov] = _VARYING

    # -- bf16 upcast post-scan ----------------------------------------------

    def _check_bf16_upcasts(self, jaxpr) -> None:
        produced_by = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                produced_by[ov] = eqn
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "dot_general":
                continue
            for v in eqn.invars:
                src = produced_by.get(v)
                if src is None or src.primitive.name != "convert_element_type":
                    continue
                src_in = src.invars[0]
                in_aval = getattr(src_in, "aval", None)
                out_aval = getattr(v, "aval", None)
                if (
                    in_aval is not None
                    and out_aval is not None
                    and str(getattr(in_aval, "dtype", "")) == "bfloat16"
                    and str(getattr(out_aval, "dtype", "")) == "float32"
                    and getattr(out_aval, "size", 1) > 1
                ):
                    self.add(
                        "jaxpr-bf16-upcast",
                        "dot_general consumes an explicitly f32-upcast bf16 "
                        "array inside a declared-bf16 region — halves the "
                        "MXU rate silently; keep operands bf16 and use "
                        "preferred_element_type=f32 for the accumulation",
                    )


def _collect_scans(jaxpr, out: list) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            body = _jaxpr_of(eqn.params.get("jaxpr"))
            if body is not None:
                out.append(body)
        for _, inner in _sub_jaxprs(eqn.params):
            _collect_scans(inner, out)


def _contains_prim(jaxpr, names: set) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            return True
        for _, inner in _sub_jaxprs(eqn.params):
            if _contains_prim(inner, names):
                return True
    return False


def audit_jaxpr(
    jaxpr_or_closed,
    *,
    label: str,
    bound_axes: dict | None = None,
    expect_chunk_checkpoint: bool = False,
    check_bf16_upcast: bool = False,
) -> list[Finding]:
    """Audit one (closed) jaxpr; returns the Findings.

    ``bound_axes``: axis name -> size already bound OUTSIDE this jaxpr (for
    auditing a bare shard_map body); normally empty — the walk binds axes at
    the shard_map eqns it encounters.
    """
    auditor = _Auditor(label, check_bf16_upcast=check_bf16_upcast)
    j = _jaxpr_of(jaxpr_or_closed)
    if j is None:
        raise TypeError(f"not a jaxpr: {jaxpr_or_closed!r}")
    import numpy as np

    bound = dict(bound_axes or {})
    env: dict = {}
    for iv in j.invars:
        aval = getattr(iv, "aval", None)
        dt = getattr(aval, "dtype", None)
        # Float/complex only: a weak-typed float input is the classic python-
        # scalar leak (0.1 vs np.float32(0.1) recompiles). Weak INT scalars
        # are the flax convention (TrainState.step counts in a weak int32,
        # stable across the whole run) — flagging them would be pure noise.
        if (
            getattr(aval, "weak_type", False)
            and dt is not None
            and np.issubdtype(dt, np.inexact)
        ):
            auditor.add(
                "jaxpr-weak-type",
                f"input aval {aval} is weak-typed — a python-scalar leak; "
                "the compiled cache keys on weak_type, so passing a numpy "
                "or jax scalar later recompiles the whole program",
            )
        # Top-level inputs are assumed varying (per-shard) — conservative.
        env[iv] = _VARYING
    for cv in getattr(j, "constvars", ()):
        env[cv] = (frozenset(bound), frozenset())
    auditor.walk(j, env, bound, emit=True)

    if expect_chunk_checkpoint:
        scans: list = []
        _collect_scans(j, scans)
        ok = any(
            _contains_prim(body, _REMAT_PRIMS)
            and _contains_prim(body, {"dot_general"})
            for body in scans
        )
        if not ok:
            auditor.add(
                "jaxpr-chunk-checkpoint",
                "no scan with a jax.checkpoint'd (remat) dot-bearing body "
                "found — the chunked loss's backward would save every "
                "block's logits instead of recomputing them, silently "
                "re-materializing the full (local_b, W*local_b) matrix the "
                "chunked path exists to avoid (PR 3 memory contract)",
            )
    return auditor.findings


# ---------------------------------------------------------------------------
# The fifteen real step configs, traced abstractly (no compile, no execution).
# ---------------------------------------------------------------------------


def _abstract_batch(cfg, global_b: int):
    import jax
    import jax.numpy as jnp

    v, t = cfg.vision, cfg.text
    return {
        "images": jax.ShapeDtypeStruct(
            (global_b, v.image_size, v.image_size, 3), jnp.float32
        ),
        "tokens": jax.ShapeDtypeStruct(
            (global_b, t.context_length), jnp.int32
        ),
    }


def _abstract_params(model, batch):
    import jax

    import flax.linen as nn

    boxed = jax.eval_shape(
        lambda r, im, tk: model.init(r, im, tk)["params"],
        jax.random.key(0), batch["images"], batch["tokens"],
    )
    return jax.tree.map(
        lambda x: x.value if isinstance(x, nn.meta.AxisMetadata) else x,
        boxed,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )


def _abstract_state(
    model, tx, batch,
    ef_slices: int | None = None,
    comp_tensors: int | None = None,
    ef_full_w: int | None = None,
    learned: bool = False,
):
    import jax
    import jax.numpy as jnp

    from distributed_sigmoid_loss_tpu.train.train_step import TrainState

    params = _abstract_params(model, batch)
    state = jax.eval_shape(
        lambda p: TrainState.create(apply_fn=model.apply, params=p, tx=tx),
        params,
    )
    if ef_slices is not None:
        if ef_full_w:
            # update_sharding="full": the residual is shard-local, so the
            # abstract EF must carry with_error_feedback's padded
            # (n_dcn, padded_rows(d0, W), ...) layout or the traced step
            # would reject the carry's shapes.
            from distributed_sigmoid_loss_tpu.parallel.update_shard import (
                ef_slot_shape,
            )

            ef = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    ef_slot_shape(x.shape, ef_slices, ef_full_w, "full"),
                    x.dtype,
                ),
                params,
            )
        else:
            from distributed_sigmoid_loss_tpu.train.compressed_step import (
                init_error_feedback,
            )

            ef = jax.eval_shape(
                lambda p: init_error_feedback(p, ef_slices), params
            )
        state = state.replace(ef=ef)
    if comp_tensors is not None:
        # Abstract twin of with_adaptive_compression's carry: one scheme /
        # stat scalar per flattened param leaf, replicated on device.
        comp = {
            "scheme": jax.ShapeDtypeStruct((comp_tensors,), jnp.int32),
            "gnorm": jax.ShapeDtypeStruct((comp_tensors,), jnp.float32),
            "gvar": jax.ShapeDtypeStruct((comp_tensors,), jnp.float32),
            "ef_ratio": jax.ShapeDtypeStruct((comp_tensors,), jnp.float32),
        }
        if learned:
            # graftcodec's learned-rung extension of the carry: the host-
            # trained codec operands plus the step-written trainer stats
            # (with_adaptive_compression(..., learned=True) shapes).
            from distributed_sigmoid_loss_tpu.parallel import (
                adaptive_compression as ac,
            )

            g, b, l = ac.CODEC_GROUPS, ac.CODEC_BLOCK, ac.CODEC_LATENT
            comp.update({
                "codec_enc": jax.ShapeDtypeStruct((g, b, l), jnp.float32),
                "codec_dec": jax.ShapeDtypeStruct((g, l, b), jnp.float32),
                "blockmoment": jax.ShapeDtypeStruct((g, b, b), jnp.float32),
                "codec_recon_err": jax.ShapeDtypeStruct((), jnp.float32),
            })
        state = state.replace(comp=comp)
    return state


# Memo for step_config_jaxprs keyed by the RESOLVED mesh size: the traces
# are deterministic (tiny towers, abstract state, fixed mesh), and the
# auditor and obs/attribution enumerate the same sampled product. The memo
# is INCREMENTAL per label: the dryrun's --full-product pass reuses every
# trace the tier-1 sample already paid for and adds only the extra configs.
# Host-side only; never read inside traced code (allowlisted in repo_lint).
_STEP_CONFIG_CACHE: dict = {}


def _build_step_config(cfg, n_devices: int):
    """(abstract_state, abstract_batch, build_fn, audit_kwargs) for one
    declarative StepConfig (analysis/config_space.py) — the solver-driven
    generalization of the old hand-written fifteen-entry builds table.

    Shape discipline: the pallas_* configs trace at kernel-compatible shapes
    (embed 128 lane-aligned, per-microstep local_b % 8 for f32 / % 32 for
    the int8 sublane quantum) so the pallas_call genuinely appears in the
    audited jaxpr — an incompatible shape would silently audit the XLA
    fallback instead. Mesh axes are allocated (dcn?, dp, pp?) with dcn and
    pp fixed at 2 (tiny_test towers have depth 2, so 2 pp stages is the
    divisible choice) and dp taking the rest.
    """
    import dataclasses

    import jax
    import numpy as np

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        make_compressed_train_step,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TrainConfig,
    )
    from jax.sharding import Mesh

    axis_names, shape = ["dp"], [0]
    if cfg.compression:
        axis_names.insert(0, "dcn")
        shape.insert(0, 2)
    if cfg.pp:
        axis_names.append("pp")
        shape.append(2)
    fixed = int(np.prod([s for s in shape if s]))
    dp_size = max(n_devices // max(fixed, 1), 1)
    shape[axis_names.index("dp")] = dp_size
    n_used = int(np.prod(shape))
    mesh = Mesh(
        np.asarray(jax.devices()[:n_used]).reshape(shape), tuple(axis_names)
    )

    mcfg = SigLIPConfig.tiny_test()
    if cfg.use_pallas:
        mcfg = dataclasses.replace(
            mcfg,
            vision=dataclasses.replace(mcfg.vision, embed_dim=128),
            text=dataclasses.replace(mcfg.text, embed_dim=128),
        )
    if cfg.quant_train:
        mcfg = dataclasses.replace(
            mcfg,
            vision=dataclasses.replace(
                mcfg.vision, quant_train=cfg.quant_train
            ),
            text=dataclasses.replace(mcfg.text, quant_train=cfg.quant_train),
        )
    if cfg.moe:
        mcfg = dataclasses.replace(
            mcfg,
            vision=dataclasses.replace(mcfg.vision, moe_experts=4),
            text=dataclasses.replace(
                mcfg.text, moe_experts=4, moe_num_selected=2
            ),
        )
    if cfg.pp:
        # Stage params are the nn.scan-stacked block leaves; tiny_test's
        # depth-2 towers pipeline as 2 stages x 1 block.
        mcfg = dataclasses.replace(
            mcfg,
            vision=dataclasses.replace(mcfg.vision, scan_layers=True),
            text=dataclasses.replace(mcfg.text, scan_layers=True),
        )
    model = SigLIP(mcfg)

    accum_steps = 2 if cfg.accum else 1
    pp_microbatches = 2 if cfg.pp else 0
    # Per-microstep loss-island batch quantum (pallas sublane contract),
    # scaled back up by the microbatch splits that happen before the island.
    quantum = 32 if (cfg.use_pallas and cfg.quant_train) else (
        8 if cfg.use_pallas else 2
    )
    local_b = quantum * accum_steps * max(pp_microbatches, 1)
    # Batch rows shard over the data axes (dcn and dp; pp stages all see the
    # same rows) — for the legacy labels this reproduces the exact historic
    # global sizes (2n / 8n / 32n).
    batch_shards = dp_size * (2 if cfg.compression else 1)
    batch = _abstract_batch(mcfg, local_b * batch_shards)
    tx = make_optimizer(TrainConfig(warmup_steps=1, total_steps=10))
    comp_tensors = None
    if cfg.compression in ("adaptive", "learned"):
        comp_tensors = len(
            jax.tree_util.tree_leaves(_abstract_params(model, batch))
        )
    full_shard = cfg.update_sharding == "full"
    state = _abstract_state(
        model, tx, batch,
        ef_slices=2 if cfg.error_feedback else None,
        comp_tensors=comp_tensors,
        ef_full_w=dp_size if (full_shard and cfg.error_feedback) else None,
        learned=cfg.compression == "learned",
    )

    loss_cfg = LossConfig(
        variant=cfg.variant,
        family=cfg.family,
        loss_impl=cfg.loss_impl,
        ring_overlap=cfg.ring_overlap,
        use_pallas=cfg.use_pallas,
    )
    if cfg.compression:
        def build():
            return make_compressed_train_step(
                model, mesh, loss_cfg,
                compression=cfg.compression,
                error_feedback=cfg.error_feedback,
                update_sharding=cfg.update_sharding,
                accum_steps=accum_steps,
                accum_negatives=cfg.accum_negatives,
                pp_microbatches=pp_microbatches,
                moe_aux_weight=0.01 if cfg.moe else None,
            )[0]
    else:
        def build():
            return make_train_step(
                model, mesh, loss_cfg,
                accum_steps=accum_steps,
                update_sharding=cfg.update_sharding,
                moe_aux_weight=0.01 if cfg.moe else None,
                pp_microbatches=pp_microbatches,
                accum_negatives=cfg.accum_negatives,
            )[0]

    audit_kwargs: dict = {}
    if cfg.loss_impl == "chunked":
        audit_kwargs["expect_chunk_checkpoint"] = True
    if cfg.error_feedback:
        # Arms shard_flow's jaxpr-ef-threaded rule: step_config_jaxprs
        # resolves the flag into flattened (invar, outvar) index sets once
        # the trace's output structure is known.
        audit_kwargs["check_ef_threading"] = True
    if cfg.compression == "learned":
        # Arms shard_flow's jaxpr-codec-threaded rule the same way: resolved
        # into (codec_in, stat_out, update_out) positions post-trace.
        audit_kwargs["check_codec_threading"] = True
    if cfg.pp:
        # GPipe's shift-register carries are drained by design
        # (parallel/pipeline.py); see shard_flow's module docstring.
        audit_kwargs["check_state_drop"] = False
    if full_shard:
        # Arms shard_flow's jaxpr-gather-placement rule: an all_gather of a
        # reduce-scattered value over this axis before the update would
        # silently re-replicate what graftshard sharded.
        audit_kwargs["update_shard_axis"] = "dp"
    return state, batch, build, audit_kwargs


def step_config_jaxprs(
    n_devices: int | None = None, full_product: bool = False,
) -> dict:
    """label -> (closed_jaxpr, audit_kwargs) for the sampled step-config
    product (config_space.tier1_sample, or .full_product_sample when
    ``full_product=True``), traced on virtual CPU devices. Trace-only: tiny
    towers, abstract state/batch — seconds, not the minutes a compile would
    cost. Traces are memoized per (mesh size, label), so the full-product
    pass pays only for the configs tier-1 didn't already trace (a shallow
    copy is returned so callers can't disturb the memo)."""
    import jax

    from distributed_sigmoid_loss_tpu.analysis.config_space import (
        full_product_sample,
        tier1_sample,
    )

    devices = jax.devices()
    if n_devices is None:
        n_devices = min(8, len(devices))
    if n_devices < 4 or n_devices % 2:
        raise RuntimeError(
            f"the jaxpr audit needs an even mesh of >= 4 devices to cover "
            f"the sampled step configs (got {n_devices}; run under "
            f"--xla_force_host_platform_device_count or lint --cpu-devices)"
        )
    sample = full_product_sample() if full_product else tier1_sample()
    cache = _STEP_CONFIG_CACHE.setdefault(n_devices, {})
    for label, cfg in sample.items():
        if label in cache:
            continue
        state, batch, build, kwargs = _build_step_config(cfg, n_devices)
        step = build()
        want_ef = kwargs.pop("check_ef_threading", False)
        want_codec = kwargs.pop("check_codec_threading", False)
        if want_ef or want_codec:
            closed, out_shape = jax.make_jaxpr(step, return_shape=True)(
                state, batch
            )
            if want_ef:
                kwargs["ef_indices"] = (
                    _leaf_indices_named((state, batch), "ef"),
                    _leaf_indices_named(out_shape, "ef"),
                )
            if want_codec:
                # (codec_in, stat_out, update_out) for jaxpr-codec-threaded:
                # the codec operands among the inputs, the trainer stats
                # among the outputs, and the updated params the decode must
                # reach.
                kwargs["codec_indices"] = (
                    _leaf_indices_named((state, batch), "codec_enc")
                    + _leaf_indices_named((state, batch), "codec_dec"),
                    _leaf_indices_named(out_shape, "blockmoment")
                    + _leaf_indices_named(out_shape, "codec_recon_err"),
                    _leaf_indices_named(out_shape, "params"),
                )
            cache[label] = (closed, kwargs)
        else:
            cache[label] = (jax.make_jaxpr(step)(state, batch), kwargs)
    return {label: cache[label] for label in sample}


def _leaf_indices_named(tree, name: str) -> tuple:
    """Flattened leaf positions whose pytree path contains an entry exactly
    named ``name`` (dataclass field or dict key). Exact match — the state's
    ``ef`` residual leaves, not the metrics dict's ``ef_norm`` scalar. Used
    to locate the EF carry among a traced step's invars/outvars for
    shard_flow's jaxpr-ef-threaded rule."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    hits = []
    for i, (path, _leaf) in enumerate(leaves):
        for entry in path:
            key = getattr(entry, "name", None)
            if key is None:
                key = getattr(entry, "key", None)
            if key == name:
                hits.append(i)
                break
    return tuple(hits)


def audit_default_step_configs(
    n_devices: int | None = None, full_product: bool = False,
) -> list[Finding]:
    """Audit the sampled step-config product — base jaxpr rules plus the
    shard-flow dataflow rules — the tier-1/dryrun entry point."""
    from distributed_sigmoid_loss_tpu.analysis.shard_flow import (
        audit_shard_flow,
    )

    findings: list[Finding] = []
    jaxprs = step_config_jaxprs(n_devices, full_product=full_product)
    for label, (closed, kwargs) in jaxprs.items():
        flow_kwargs = {
            "check_state_drop": kwargs.get("check_state_drop", True)
        }
        if "ef_indices" in kwargs:
            flow_kwargs["ef_indices"] = kwargs["ef_indices"]
        if "codec_indices" in kwargs:
            flow_kwargs["codec_indices"] = kwargs["codec_indices"]
        if "update_shard_axis" in kwargs:
            flow_kwargs["update_shard_axis"] = kwargs["update_shard_axis"]
        base_kwargs = {
            k: v for k, v in kwargs.items()
            if k not in ("check_state_drop", "ef_indices", "codec_indices",
                         "update_shard_axis")
        }
        findings.extend(audit_jaxpr(closed, label=label, **base_kwargs))
        findings.extend(
            audit_shard_flow(closed, label=label, **flow_kwargs)
        )
    return findings
