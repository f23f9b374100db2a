"""graftlint self-enforcement: the analyzers run green on the shipped tree,
and every rule is falsified on a known-bad fixture (no rule ships untested —
a rule that cannot fire is a rule that silently stopped protecting anything).

Standard tier: the jaxpr audit is trace-only (no compile) — the sampled
step-config sweep (fifteen legacy + coverage extras) runs in ~45 s on this
host, memoized per label across the analysis/attribution consumers;
everything else is AST/pure-python.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import distributed_sigmoid_loss_tpu  # noqa: F401  (compat shims first)
from jax import shard_map

from distributed_sigmoid_loss_tpu.analysis import (
    ALL_RULES,
    JAXPR_RULES,
    Finding,
    run_lint,
)
from distributed_sigmoid_loss_tpu.analysis import (
    jaxpr_audit,
    repo_lint,
    shard_flow,
)
from distributed_sigmoid_loss_tpu.analysis.bench_schema import validate_record
from distributed_sigmoid_loss_tpu.parallel.collectives import (
    ring_perm_problems,
    validate_ring_perm,
)


def _mesh8():
    return Mesh(np.asarray(jax.devices()[:8]), ("dp",))


def _rules_of(findings):
    return sorted({f.rule for f in findings})


def _prim_names(closed, named_axis_only=False) -> set:
    """Primitive names in a closed jaxpr, nested jaxprs included; with
    ``named_axis_only`` only equations that run over a named mesh axis."""
    from distributed_sigmoid_loss_tpu.analysis.collective_prims import (
        collective_axes,
    )

    out = set()

    def rec(j):
        for e in j.eqns:
            if not named_axis_only or collective_axes(e):
                out.add(e.primitive.name)
            for _, inner in jaxpr_audit._sub_jaxprs(e.params):
                rec(inner)

    rec(closed.jaxpr)
    return out


def _audit_rules(fn, *args, **kwargs):
    return _rules_of(
        jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(*args), label="fixture",
                                **kwargs)
    )


# ---------------------------------------------------------------------------
# jaxpr rules: each known-bad fixture trips exactly its rule
# ---------------------------------------------------------------------------


def test_broken_ring_perm_trips_bijection_rule():
    """Everyone sends to shard 0: duplicate destinations, shards 1..7 receive
    zeros — the broken-ring class. Trips the bijection rule and nothing else."""
    mesh = _mesh8()
    bad = [(i, 0) for i in range(8)]
    fn = shard_map(
        lambda z: lax.ppermute(z, "dp", bad),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"), check_vma=False,
    )
    assert _audit_rules(fn, jnp.ones((8, 4))) == ["jaxpr-ppermute-bijection"]


def test_partial_ring_perm_trips_bijection_rule():
    mesh = _mesh8()
    partial = [(i, (i + 1) % 8) for i in range(4)]  # only half the ring sends
    fn = shard_map(
        lambda z: lax.ppermute(z, "dp", partial),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"), check_vma=False,
    )
    assert _audit_rules(fn, jnp.ones((8, 4))) == ["jaxpr-ppermute-bijection"]


def test_double_psum_trips_overcount_rule():
    """psum of a psum over the same axis: each shard re-contributes the
    identical global sum — the S-fold overcount class."""
    mesh = _mesh8()
    fn = shard_map(
        lambda z: lax.psum(lax.psum(z, "dp"), "dp"),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P(), check_vma=False,
    )
    assert _audit_rules(fn, jnp.ones((8, 4))) == ["jaxpr-double-psum"]


def test_pmean_backward_is_not_flagged():
    """jax's psum-self-transpose convention (pmean backward psums a replicated
    cotangent, compensated by the 1/S) must NOT trip the overcount rule."""
    mesh = _mesh8()
    fn = jax.grad(
        shard_map(
            lambda z: lax.pmean(jnp.sum(z**2), "dp"),
            mesh=mesh, in_specs=(P("dp"),), out_specs=P(), check_vma=False,
        )
    )
    assert _audit_rules(fn, jnp.ones((8, 4))) == []


def test_unbound_axis_trips_collective_axis_rule():
    """A shard_map BODY audited standalone (no axis bound): its psum names an
    axis nothing binds — the stale/foreign axis-environment class."""
    mesh = _mesh8()
    closed = jax.make_jaxpr(
        shard_map(
            lambda z: lax.psum(z, "dp"),
            mesh=mesh, in_specs=(P("dp"),), out_specs=P(), check_vma=False,
        )
    )(jnp.ones((8, 4)))
    inner = [
        e for e in closed.jaxpr.eqns if e.primitive.name == "shard_map"
    ][0].params["jaxpr"]
    findings = jaxpr_audit.audit_jaxpr(inner, label="fixture")
    assert _rules_of(findings) == ["jaxpr-collective-axis"]
    # ...and with the axis properly declared, the same body audits clean.
    assert jaxpr_audit.audit_jaxpr(
        inner, label="fixture", bound_axes={"dp": 8}
    ) == []


def test_missing_chunk_checkpoint_trips_and_checkpointed_passes():
    mesh = _mesh8()

    def chunk_loss(checkpointed):
        def raw_body(acc, c):
            return acc + (z_ref[0] @ c.T).sum(), None

        def fn(z):
            z_ref[0] = z
            body = jax.checkpoint(raw_body) if checkpointed else raw_body
            out, _ = lax.scan(body, 0.0, lax.all_gather(z, "dp"))
            return out

        z_ref = [None]
        return shard_map(
            fn, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
            check_vma=False,
        )

    x = jnp.ones((8, 4))
    assert _audit_rules(
        chunk_loss(False), x, expect_chunk_checkpoint=True
    ) == ["jaxpr-chunk-checkpoint"]
    assert _audit_rules(
        chunk_loss(True), x, expect_chunk_checkpoint=True
    ) == []


def test_weak_float_input_trips_and_int_counter_is_exempt():
    # python float scalar input -> weak f32 aval -> recompile hazard
    assert _audit_rules(lambda s: s * 2.0, 3.5) == ["jaxpr-weak-type"]
    # weak INT scalar (the flax TrainState.step convention) stays silent
    assert _audit_rules(lambda s: s + 1, 3) == []


def test_f64_aval_trips_dtype_rule():
    with jax.enable_x64():
        rules = _audit_rules(
            lambda z: z.astype("float64") * 2, jnp.ones((4,), jnp.float32)
        )
    assert rules == ["jaxpr-f64"]


def test_bf16_upcast_trips_and_preferred_element_type_passes():
    a = jnp.ones((4, 4), jnp.bfloat16)

    def upcast(x, y):
        return x.astype(jnp.float32) @ y.astype(jnp.float32).T

    def sanctioned(x, y):
        return lax.dot_general(
            x, y, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    assert _audit_rules(upcast, a, a, check_bf16_upcast=True) == [
        "jaxpr-bf16-upcast"
    ]
    assert _audit_rules(sanctioned, a, a, check_bf16_upcast=True) == []


# ---------------------------------------------------------------------------
# the real programs audit green, covering the sampled step-config product
# ---------------------------------------------------------------------------


def test_fifteen_step_configs_audit_green_and_cover_all_paths():
    jaxprs = jaxpr_audit.step_config_jaxprs()
    # The solver-drawn sample must remain a SUPERSET of the fifteen legacy
    # configs (the acceptance pin: the declarative lattice may only widen
    # coverage, never drop a config the auditor historically guarded).
    assert set(jaxprs) >= set(jaxpr_audit.DEFAULT_STEP_CONFIGS)
    assert set(jaxprs) >= {
        "fused", "chunked", "ring", "ring_overlap", "compressed_dcn",
        "quant_train_int8",
        "pallas_fused", "pallas_chunked", "pallas_ring",
        "pallas_ring_overlap", "pallas_int8_fused", "pallas_int8_chunked",
        "pallas_int8_ring", "pallas_int8_ring_overlap",
        "compressed_pallas_chunked",
    }
    all_findings = []
    for label, (closed, kwargs) in jaxprs.items():
        # check_state_drop, ef_indices, update_shard_axis and codec_indices
        # are shard_flow kwargs (the same split audit_default_step_configs
        # makes); audit_jaxpr takes none of them.
        audit_kwargs = {
            k: v for k, v in kwargs.items()
            if k not in ("check_state_drop", "ef_indices",
                         "update_shard_axis", "codec_indices")
        }
        all_findings += jaxpr_audit.audit_jaxpr(
            closed, label=label, **audit_kwargs
        )
    assert all_findings == [], [str(f) for f in all_findings]
    # The audit is load-bearing only if the programs actually contain the
    # comm structure it checks: the ring configs must carry ppermutes, the
    # all-gather ones all_gathers, chunked a remat'd scan — and every
    # pallas_* config a REAL pallas_call (an incompatible trace shape would
    # silently audit the XLA fallback instead of the new composition).
    prims = _prim_names
    assert "ppermute" in prims(jaxprs["ring"][0])
    assert "ppermute" in prims(jaxprs["ring_overlap"][0])
    assert "all_gather" in prims(jaxprs["fused"][0])
    assert "all_gather" in prims(jaxprs["chunked"][0])
    assert "psum" in prims(jaxprs["compressed_dcn"][0])
    for label in jaxpr_audit.DEFAULT_STEP_CONFIGS:
        if "pallas" not in label:
            continue
        p = prims(jaxprs[label][0])
        assert "pallas_call" in p, f"{label} traced without the kernel"
        if "ring" in label:
            assert "ppermute" in p
        else:
            assert "all_gather" in p


def test_pallas_chunk_scan_without_checkpoint_trips():
    """Known-bad fixture for the NEW composition (ANALYSIS.md falsification
    policy): a chunk scan whose body is the streaming Pallas kernel but NOT
    jax.checkpoint'd must trip jaxpr-chunk-checkpoint — the dot the rule
    hunts for lives inside the pallas_call's kernel jaxpr, so this pins that
    the detection recurses into kernels rather than only spotting top-level
    dot_generals."""
    from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
        streaming_block_loss_sum,
    )

    mesh = _mesh8()

    def chunk_loss(checkpointed):
        def raw_body(carry, c):
            acc, z = carry
            s = streaming_block_loss_sum(
                z, c, jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
                "", 8, 8, True,
            )
            return (acc + s, z), None

        def fn(z):
            body = jax.checkpoint(raw_body) if checkpointed else raw_body
            (out, _), _ = lax.scan(body, (0.0, z), lax.all_gather(z, "dp"))
            return out

        return shard_map(
            fn, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
            check_vma=False,
        )

    x = jnp.ones((64, 128))  # local (8, 128): kernel-compatible tiles
    assert _audit_rules(
        jax.jit(chunk_loss(False)), x, expect_chunk_checkpoint=True
    ) == ["jaxpr-chunk-checkpoint"]
    assert _audit_rules(
        jax.jit(chunk_loss(True)), x, expect_chunk_checkpoint=True
    ) == []


# ---------------------------------------------------------------------------
# what the auditor reads from the installed jax (PR 28): in_specs, aval.vma,
# psum_invariant / pvary, and the one table of collective primitives
# ---------------------------------------------------------------------------


def test_shard_map_operands_are_seeded_from_in_specs():
    """A P("dp") operand enters the body varying over dp, a P() one replicated
    over every bound axis: read from the equation's in_specs and manual_axes."""
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("dcn", "dp"))
    fn = shard_map(
        lambda a, b, c, d: a.sum() + b.sum() + c.sum() + d.sum(),
        mesh=mesh,
        in_specs=(P("dp"), P(), P(("dcn", "dp")), P(None, "dcn")),
        out_specs=P(), check_vma=False,
    )
    x = jnp.ones((8, 4))
    closed = jax.make_jaxpr(fn)(x, x, x, x)
    body = closed.jaxpr.eqns[0].params["jaxpr"]
    seeded = {}

    class Spy(jaxpr_audit._Auditor):
        def walk(self, jaxpr, env, bound, emit):
            if jaxpr is body:
                seeded.update(env=dict(env), bound=dict(bound))
            return super().walk(jaxpr, env, bound, emit)

    Spy("fixture").walk(closed.jaxpr, {}, {}, True)
    assert seeded["bound"] == {"dcn": 2, "dp": 4}
    assert [seeded["env"][v][0] for v in body.invars] == [
        frozenset({"dcn"}), frozenset({"dcn", "dp"}), frozenset(),
        frozenset({"dp"}),
    ]
    assert all(seeded["env"][v][1] == frozenset() for v in body.invars)


def test_psum_invariant_of_a_reduced_value_trips_double_psum():
    """Under check_vma=True jax spells the loss island's sums psum_invariant
    and retypes an invariant value with pvary. A sum of a value already
    summed over the axis is the S-fold overcount whatever its type says; a
    sum of a varying value is the reduction itself."""
    from distributed_sigmoid_loss_tpu.parallel.collectives import pvary

    mesh = _mesh8()

    def island(body):
        return shard_map(
            body, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
        )

    twice = island(lambda z: lax.psum(pvary(lax.psum(z, "dp"), "dp"), "dp"))
    once = island(lambda z: lax.psum(z * 2.0, "dp"))
    x = jnp.ones((8, 4))
    names = [e.primitive.name for e in
             jax.make_jaxpr(twice)(x).jaxpr.eqns[0].params["jaxpr"].eqns]
    assert names == ["psum_invariant", "pvary", "psum_invariant"]
    assert _audit_rules(twice, x) == ["jaxpr-double-psum"]
    assert _audit_rules(once, x) == []
    # jax's own transpose of a checked island (pvary <-> psum_invariant) is
    # not the overcount.
    assert _audit_rules(jax.grad(lambda z: once(z).sum()), x) == []


def test_pvary_ends_replication_in_a_checked_island():
    """Inside check_vma=True "replicated over dp" is the value's type
    (dp not in aval.vma), and pvary ends it: the fused loss island, forward
    and backward, holds no redundant gather, and a gather jax itself typed
    as of a varying value is taken at its word."""
    from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import init_loss_params
    from distributed_sigmoid_loss_tpu.parallel import make_sharded_loss_fn

    mesh = _mesh8()
    loss = make_sharded_loss_fn(mesh, variant="all_gather", jit=False)
    z = jnp.ones((16, 32))
    closed = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        init_loss_params(), z, z
    )
    assert {"all_gather", "pvary", "psum_invariant"} <= _prim_names(closed)
    assert shard_flow.audit_shard_flow(closed, label="fused-island") == []
    assert jaxpr_audit.audit_jaxpr(closed, label="fused-island") == []
    # check_vma=False has no types: there the walk's own dataflow still sees
    # the gather of a P() operand (test_redundant_gather_trips_...).
    typed = shard_map(
        lambda w: lax.all_gather(w, "dp"),
        mesh=mesh, in_specs=(P(),), out_specs=P("dp"),
    )
    assert _flow_rules(typed, jnp.ones((8, 4))) == []


def test_one_table_covers_the_step_configs_collectives():
    """Every named-axis primitive in the sampled step configurations' jaxprs
    is a row of analysis/collective_prims.COLLECTIVES (this list is what jax
    0.9.0 emits for them), and obs/attribution reads the same table: the
    checked loss island's psum_invariant is wire traffic under comm_bytes_psum."""
    from distributed_sigmoid_loss_tpu.analysis.collective_prims import (
        BUCKETS,
        COLLECTIVES,
    )
    from distributed_sigmoid_loss_tpu.obs import attribution
    from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import init_loss_params
    from distributed_sigmoid_loss_tpu.parallel import make_sharded_loss_fn

    seen = set()
    for closed, _kwargs in jaxpr_audit.step_config_jaxprs().values():
        seen |= _prim_names(closed, named_axis_only=True)
    assert seen == {
        "all_gather", "axis_index", "ppermute", "psum", "psum_invariant",
        "pvary", "reduce_scatter",
    }
    assert seen <= set(COLLECTIVES)
    assert {b for _, b, _ in COLLECTIVES.values() if b} == set(BUCKETS)
    assert attribution.COLLECTIVE_KINDS == BUCKETS

    loss = make_sharded_loss_fn(_mesh8(), variant="all_gather", jit=False)
    z = jnp.ones((16, 32))
    costs = attribution.jaxpr_costs(
        jax.make_jaxpr(loss)(init_loss_params(), z, z)
    )
    # One f32 scalar all-reduced over 8 shards: 2 * 4 * 7/8 bytes a device.
    assert costs["comm_bytes_psum"] == 2 * 4 * 7 / 8
    assert costs["comm_bytes_all_gather"] > 0


# ---------------------------------------------------------------------------
# shard-flow (graftprove) rules: known-bad fixture + green twin each
# ---------------------------------------------------------------------------


def _flow_rules(fn, *args, **kwargs):
    return _rules_of(
        shard_flow.audit_shard_flow(
            jax.make_jaxpr(fn)(*args), label="fixture", **kwargs
        )
    )


def test_redundant_gather_trips_on_replicated_and_sharded_passes():
    """all_gather of a value every shard already holds in full (P() spec):
    W identical blocks of wire + HBM. The sharded twin is the gather's whole
    point and must stay silent."""
    mesh = _mesh8()

    def gather(spec):
        return shard_map(
            lambda z: lax.all_gather(z, "dp"),
            mesh=mesh, in_specs=(spec,), out_specs=P(None, None, None),
            check_vma=False,
        )

    assert _flow_rules(gather(P()), jnp.ones((8, 4))) == [
        "jaxpr-redundant-gather"
    ]
    fn = shard_map(
        lambda z: lax.all_gather(z, "dp"),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P(None, None),
        check_vma=False,
    )
    assert _flow_rules(fn, jnp.ones((8, 4))) == []


def test_redundant_gather_scalar_is_exempt():
    """A gathered scalar is bookkeeping wire (the compressed hop's
    quant-scale exchange), not the HBM-blocks waste the rule hunts."""
    mesh = _mesh8()
    fn = shard_map(
        lambda z: lax.all_gather(z.sum() * 0 + 1.0, "dp"),
        mesh=mesh, in_specs=(P(),), out_specs=P(None), check_vma=False,
    )
    assert _flow_rules(fn, jnp.ones((8, 4))) == []


def test_state_drop_trips_on_dropped_quant_carry_and_threaded_passes():
    """Reconstruction of the pp-dropped-quant class: a scan carry (think
    'running quant scale') read each microbatch, updated from the incoming
    slice, and then never emitted — the program maintains state it silently
    discards. Threading the final carry to an output is the fix and the
    green twin."""

    def step(drop):
        def body(scale, x):
            new_scale = 0.9 * scale + 0.1 * jnp.max(jnp.abs(x))
            return new_scale, x * scale
        def fn(xs):
            final, ys = lax.scan(body, jnp.float32(1.0), xs)
            return ys if drop else (final, ys)
        return fn

    xs = jnp.ones((4, 8))
    assert _flow_rules(step(True), xs) == ["jaxpr-state-drop"]
    assert _flow_rules(step(False), xs) == []


def test_state_drop_rotation_carry_is_exempt():
    """A dropped carry whose update is a pure rotation of the carry itself
    (the ring's ppermute shift buffer) loses nothing that entered the loop —
    exempt by the external-deps test."""
    mesh = _mesh8()
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def fn(z):
        def body(carry, x):
            return lax.ppermute(carry, "dp", perm), (x * carry).sum()
        _, ys = lax.scan(body, z[0], z)
        return ys

    wrapped = shard_map(
        fn, mesh=mesh, in_specs=(P(None, "dp"),), out_specs=P(None),
        check_vma=False,
    )
    assert _flow_rules(wrapped, jnp.ones((4, 8))) == []


def test_collective_order_trips_on_varying_pred_and_replicated_passes():
    """cond branches with mismatched collective sequences over dp: shards
    disagreeing on a VARYING predicate enter different collectives and the
    mesh deadlocks. With the predicate replicated every shard agrees, so the
    same program is fine."""
    mesh = _mesh8()

    def branchy(pred_spec):
        def fn(z, p):
            return lax.cond(
                p[0] > 0,
                lambda v: lax.psum(v, "dp"),
                lambda v: v * 2.0,
                z,
            )
        return shard_map(
            fn, mesh=mesh, in_specs=(P("dp"), pred_spec), out_specs=P("dp"),
            check_vma=False,
        )

    z = jnp.ones((8, 4))
    p_sharded = jnp.ones((8,))
    p_repl = jnp.ones((1,))
    assert _flow_rules(branchy(P("dp")), z, p_sharded) == [
        "jaxpr-collective-order"
    ]
    assert _flow_rules(branchy(P()), z, p_repl) == []


def test_gather_placement_trips_on_pre_update_gather_and_publish_passes():
    """graftshard's ordering invariant: once grads are reduce-scattered over
    the update axis, gathering a value derived from the shard re-materializes
    the full tensor on every replica BEFORE the publish — the W× optimizer
    saving silently evaporates. The green twin holds the legitimate pair:
    an embedding all-gather (un-scattered operand) next to a grad
    reduce-scatter whose shard is returned for a shard-local update."""
    mesh = _mesh8()

    def bad(g):
        shard = lax.psum_scatter(g, "dp", scatter_dimension=0, tiled=True)
        upd = shard * 0.1  # the "optimizer update" on the shard
        return lax.all_gather(upd, "dp", tiled=True)

    bad_fn = shard_map(
        bad, mesh=mesh, in_specs=(P(),), out_specs=P(None, None),
        check_vma=False,
    )
    g = jnp.ones((8, 4))
    assert _flow_rules(bad_fn, g, update_shard_axis="dp") == [
        "jaxpr-gather-placement"
    ]
    # Un-armed (no update sharding in the config): same program, silent.
    assert _flow_rules(bad_fn, g) == []

    def good(z, gr):
        emb = lax.all_gather(z, "dp", tiled=True)
        shard = lax.psum_scatter(gr, "dp", scatter_dimension=0, tiled=True)
        return emb, shard

    good_fn = shard_map(
        good, mesh=mesh, in_specs=(P("dp"), P()),
        out_specs=(P(None, None), P("dp")), check_vma=False,
    )
    assert _flow_rules(good_fn, jnp.ones((8, 4)), g,
                       update_shard_axis="dp") == []


def _codec_findings(fn, args, codec_indices):
    closed = jax.make_jaxpr(fn)(*args)
    return [
        f
        for f in shard_flow.audit_shard_flow(
            closed, label="fixture", codec_indices=codec_indices
        )
        if f.rule == "jaxpr-codec-threaded"
    ]


def test_codec_threaded_trips_on_broken_fixtures_and_threaded_passes():
    """graftcodec's dataflow rule, falsified both ways: (1) a codec stat
    output that is constant (the host trainer would EWMA zeros — DCT
    freeze) or computed only FROM the codec operands (no new information),
    and (2) an update output that never touches the codec (the decode
    dropped — rung-6 compression that never happened). The green twin
    threads both: stats from the gradients, params through decode."""
    g = jnp.ones((4, 64))
    enc = jnp.full((64, 16), 0.1)
    dec = jnp.full((16, 64), 0.1)
    # Positional layout shared by all fixtures: inputs (grad, enc, dec),
    # outputs (params, stat) -> codec_in=(1, 2), stat_out=(1,), update=(0,).
    idx = ((1, 2), (1,), (0,))

    @jax.jit
    def good(grad, e, d):
        params = (grad @ e) @ d                      # decode reaches update
        stat = grad.T @ grad                         # moment of the grads
        return params, stat

    assert _codec_findings(good, (g, enc, dec), idx) == []

    @jax.jit
    def bad_const_stat(grad, e, d):
        return (grad @ e) @ d, jnp.zeros((64, 64))

    found = _codec_findings(bad_const_stat, (g, enc, dec), idx)
    assert len(found) == 1 and "constant stat" in found[0].detail

    @jax.jit
    def bad_codec_only_stat(grad, e, d):
        return (grad @ e) @ d, d.T @ d               # moment of the codec

    found = _codec_findings(bad_codec_only_stat, (g, enc, dec), idx)
    assert len(found) == 1 and "only on the codec operands" in found[0].detail

    @jax.jit
    def bad_decode_dropped(grad, e, d):
        return grad * 2.0, grad.T @ grad             # codec never consulted

    found = _codec_findings(bad_decode_dropped, (g, enc, dec), idx)
    assert len(found) == 1 and "never reaches" in found[0].detail
    # Un-armed (no codec_indices): the same broken program is silent — the
    # rule only exists for configs that claim the learned rung.
    closed = jax.make_jaxpr(bad_decode_dropped)(g, enc, dec)
    assert [
        f for f in shard_flow.audit_shard_flow(closed, label="fixture")
        if f.rule == "jaxpr-codec-threaded"
    ] == []


def test_codec_threaded_sees_through_shard_map():
    """The decode-dropped fixture hidden inside a jitted shard_map body —
    the positional recursion must follow it rather than go conservative
    (conservative would union ALL inputs and the rule could never fire)."""
    mesh = _mesh8()

    def make(fix):
        def body(grad, e, d):
            stat = lax.pmean(grad.T @ grad, "dp")
            if fix == "dropped":
                return grad * 2.0, stat
            return (grad @ e) @ d, stat

        return jax.jit(
            shard_map(
                body, mesh=mesh, in_specs=(P("dp"), P(), P()),
                out_specs=(P("dp"), P()), check_vma=False,
            )
        )

    g = jnp.ones((8, 64))
    enc = jnp.full((64, 16), 0.1)
    dec = jnp.full((16, 64), 0.1)
    idx = ((1, 2), (1,), (0,))
    found = _codec_findings(make("dropped"), (g, enc, dec), idx)
    assert len(found) == 1 and "never reaches" in found[0].detail
    assert _codec_findings(make("good"), (g, enc, dec), idx) == []


@pytest.mark.slow
def test_learned_step_config_arms_codec_indices():
    """The shipped learned-step configs trace with resolved codec_indices
    (codec operands in, blockmoment/codec_recon_err + params out) and run
    the rule green — the self-enforcement half of the graftcodec tentpole."""
    from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
        step_config_jaxprs,
    )

    jaxprs = step_config_jaxprs(8)
    label = "compression=learned+error_feedback"
    assert label in jaxprs
    closed, kw = jaxprs[label]
    codec_in, stat_out, update_out = kw["codec_indices"]
    assert codec_in and stat_out and update_out
    found = [
        f
        for f in shard_flow.audit_shard_flow(
            closed, label=label, codec_indices=kw["codec_indices"]
        )
        if f.rule == "jaxpr-codec-threaded"
    ]
    assert found == [], [str(f) for f in found]
    # The adaptive (non-learned) config must NOT arm the rule: there is no
    # codec operand to thread.
    assert "codec_indices" not in jaxprs[
        "compression=adaptive+error_feedback"
    ][1]


def test_rule_catalogs_agree():
    from distributed_sigmoid_loss_tpu.analysis import (
        CONFIG_RULES,
        LOCK_RULES,
        META_RULES,
        shard_flow,
    )
    from distributed_sigmoid_loss_tpu.analysis.config_space import (
        CONFIG_SPACE_RULES,
    )
    from distributed_sigmoid_loss_tpu.analysis.lock_flow import (
        LOCK_RULES as LOCK_FLOW_RULES,
    )

    assert tuple(JAXPR_RULES) == (
        tuple(jaxpr_audit.JAXPR_RULES) + tuple(shard_flow.SHARD_FLOW_RULES)
    )
    assert tuple(CONFIG_RULES) == tuple(CONFIG_SPACE_RULES)
    assert tuple(LOCK_RULES) == tuple(LOCK_FLOW_RULES)
    assert (
        set(repo_lint.REPO_RULES) | set(LOCK_RULES) | set(JAXPR_RULES)
        | set(CONFIG_RULES) | set(META_RULES)
    ) == set(ALL_RULES)


# ---------------------------------------------------------------------------
# runtime twin of the bijection rule (parallel/collectives.py)
# ---------------------------------------------------------------------------


def test_validate_ring_perm_raises_naming_axis_and_size():
    with pytest.raises(ValueError) as e:
        validate_ring_perm([(0, 1), (1, 1)], 2, "dp")
    msg = str(e.value)
    assert "'dp'" in msg and "size 2" in msg and "destination" in msg
    # the shared problem list is what the jaxpr auditor consumes
    assert ring_perm_problems([(i, (i + 1) % 8) for i in range(8)], 8) == []
    assert ring_perm_problems([(0, 1)], 8)  # partial
    assert ring_perm_problems([(0, 9)], 8)  # out of range


def test_ring_helpers_still_trace_clean():
    from distributed_sigmoid_loss_tpu.parallel.collectives import (
        ring_shift_left,
        ring_shift_right,
    )

    mesh = _mesh8()
    fn = shard_map(
        lambda z: ring_shift_left(ring_shift_right(z, "dp"), "dp"),
        mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"), check_vma=False,
    )
    assert _audit_rules(fn, jnp.ones((8, 4))) == []


# ---------------------------------------------------------------------------
# repo-lint rules: green tree + one known-bad fixture each
# ---------------------------------------------------------------------------


def test_repo_lint_green_on_shipped_tree():
    findings = repo_lint.run_repo_lint()
    assert findings == [], [str(f) for f in findings]


def test_unregistered_mutable_global_trips():
    src = (
        "_CACHE = {}\n"
        "_MODE = False\n"
        "def set_mode(v):\n"
        "    global _MODE\n"
        "    _MODE = v\n"
        "def put(k, v):\n"
        "    _CACHE[k] = v\n"
    )
    findings = repo_lint.check_mutable_globals(
        sources={"fake/mod.py": src}, allowlist={}
    )
    assert _rules_of(findings) == ["repo-mutable-global"]
    assert {f.subject for f in findings} == {
        "fake/mod.py::_CACHE", "fake/mod.py::_MODE"
    }
    # allowlisted -> green; stale allowlist entry -> finding again
    assert repo_lint.check_mutable_globals(
        sources={"fake/mod.py": src},
        allowlist={"fake/mod.py::_CACHE": "r", "fake/mod.py::_MODE": "r"},
    ) == []
    stale = repo_lint.check_mutable_globals(
        sources={"fake/mod.py": "X = 1\n"},
        allowlist={"fake/mod.py::_GONE": "r"},
    )
    assert _rules_of(stale) == ["repo-mutable-global"]
    assert "stale" in stale[0].detail


def test_undocumented_cli_flag_trips_doc_rule():
    cli_src = (
        "import argparse\n"
        "ap = argparse.ArgumentParser()\n"
        'ap.add_argument("--frobnicate")\n'
    )
    cfg_src = "class LossConfig:\n    variant: str = 'ring'\n"
    findings = repo_lint.check_doc_staleness(
        cli_source=cli_src, config_source=cfg_src,
        docs_text="docs mention variant but not the flag",
    )
    assert _rules_of(findings) == ["repo-doc-stale"]
    assert findings[0].subject == "cli.py::--frobnicate"
    assert repo_lint.check_doc_staleness(
        cli_source=cli_src, config_source=cfg_src,
        docs_text="--frobnicate and variant are documented",
    ) == []


def test_slow_suite_without_marker_trips():
    findings = repo_lint.check_slow_markers(
        sources={"test_cli.py": "def test_x():\n    pass\n"},
        required=("test_cli.py",),
    )
    assert _rules_of(findings) == ["repo-slow-marker"]
    assert repo_lint.check_slow_markers(
        sources={
            "test_cli.py": "import pytest\npytestmark = pytest.mark.slow\n"
        },
        required=("test_cli.py",),
    ) == []
    missing = repo_lint.check_slow_markers(
        sources={"test_cli.py": None}, required=("test_cli.py",)
    )
    assert _rules_of(missing) == ["repo-slow-marker"]


def test_unregistered_metric_field_trips_metrics_schema_rule():
    """repo-metrics-schema: an undeclared metric field in any registered
    emitting module trips the rule; declared-only sources stay green — for
    all three schemas (train line, serve stats, health events)."""
    bad_train = repo_lint.check_metrics_schema(
        sources={"train/train_step.py":
                 'metrics = {"loss": 1, "bogus_metric": 2}\n'}
    )
    assert _rules_of(bad_train) == ["repo-metrics-schema"]
    assert bad_train[0].subject == "train/train_step.py::bogus_metric"
    assert repo_lint.check_metrics_schema(
        sources={"train/train_step.py":
                 'metrics = {"loss": 1, "grad_norm": 2}\n'
                 'metrics["update_ratio"] = 3\n'}
    ) == []
    # logger.log / logger.write dict literals are scanned too
    assert repo_lint.check_metrics_schema(
        sources={"cli.py": 'logger.log(1, {"loss": 1, "sneaky": 2})\n'}
    )[0].subject == "cli.py::sneaky"
    # serve stats dict (the `snap` convention) validates against SERVE fields
    bad_serve = repo_lint.check_metrics_schema(
        sources={"serve/service.py": 'snap = {"qps": 1, "bogus_stat": 2}\n'}
    )
    assert [f.subject for f in bad_serve] == ["serve/service.py::bogus_stat"]
    # the distindex router-stats record type: its registered fields stay
    # green, and an UNregistered swap/tier field trips the rule — the drift
    # guard for the serve/distindex record shape.
    bad_router = repo_lint.check_metrics_schema(
        sources={"serve/service.py":
                 'snap = {"index_tier": "ann", "index_version": 3,\n'
                 '        "swap_count": 1, "swap_latency_ms": {},\n'
                 '        "recall_at_k": 0.99, "rerank_k": 64,\n'
                 '        "search_stage_latency_ms": {},\n'
                 '        "swap_epoch": 2}\n'}
    )
    assert [f.subject for f in bad_router] == ["serve/service.py::swap_epoch"]
    # health events: the dict a function named `record` returns
    bad_health = repo_lint.check_metrics_schema(
        sources={"obs/health.py":
                 'def record(self):\n'
                 '    return {"metric": "health_event", "bogus_ev": 1}\n'}
    )
    assert [f.subject for f in bad_health] == ["obs/health.py::bogus_ev"]
    # eval/ prefix family never trips the train schema
    assert repo_lint.check_metrics_schema(
        sources={"cli.py": 'logger.log(1, {"eval/i2t_recall@1": 0.5})\n'}
    ) == []


def test_fleet_stats_fields_registered_both_sides():
    """graftfleet schema, both sides: the fleet stats snaps (router /
    coordinator / wave controller) validate against the SERVE registry,
    an unregistered fleet field trips the rule, and the fleet record
    fields ride the bench-record schema the same way."""
    good = (
        'snap = {"replica_count": 3, "healthy_replicas": 2,\n'
        '        "reroutes": 1, "affinity_hits": 9}\n'
        'snap = {"lease_epoch": 4, "lease_reclaims": 2}\n'
        'snap = {"wave_id": 7}\n'
    )
    assert repo_lint.check_metrics_schema(
        sources={"serve/fleet/router.py": good}
    ) == []
    bad = repo_lint.check_metrics_schema(
        sources={"serve/fleet/router.py":
                 'snap = {"replica_count": 3, "bogus_fleet_stat": 1}\n'}
    )
    assert [f.subject for f in bad] == [
        "serve/fleet/router.py::bogus_fleet_stat"
    ]
    # bench-record side: the fleet_siege record fields are registered...
    rec = {
        "metric": "fleet_siege", "value": 0.0, "unit": "shed_rate",
        "fleet_replicas": 3, "lease_ttl_s": 0.5, "ceiling_rate": 120.0,
        "peak_admitted_rate": 90.0, "over_ceiling_samples": 0,
        "reroutes": 1, "lease_reclaims": 2, "wave_id": 7,
    }
    assert validate_record(rec) == []
    # ...and an invented one trips (the falsification half).
    assert any(
        "bogus_fleet_field" in p
        for p in validate_record({**rec, "bogus_fleet_field": 1})
    )


def test_graftcodec_fields_registered_both_sides():
    """graftcodec schema: the five fields (codec_recon_err / error_budget /
    controller_mode / dcn_measured_mbps / wire_savings_wallclock_ratio) ride
    the train metrics line, with an invented neighbor tripping the registry
    (the falsification half — a typo'd stamp must not validate)."""
    good_line = (
        'metrics = {"loss": 1, "codec_recon_err": 0.03,\n'
        '           "error_budget": 0.12, "controller_mode": "budgeted",\n'
        '           "dcn_measured_mbps": 184.2,\n'
        '           "wire_savings_wallclock_ratio": 1.31}\n'
    )
    assert repo_lint.check_metrics_schema(
        sources={"train/compressed_step.py": good_line}
    ) == []
    bad_line = repo_lint.check_metrics_schema(
        sources={"cli.py": 'metrics = {"codec_recon_errz": 0.03}\n'}
    )
    assert [f.subject for f in bad_line] == ["cli.py::codec_recon_errz"]
    # Direct validator fixtures (what the CLI stamps each step under
    # --grad-compression learned --emu-dcn-mbps).
    from distributed_sigmoid_loss_tpu.obs.metrics_schema import (
        validate_metrics,
    )

    assert validate_metrics({
        "codec_recon_err": 0.03, "error_budget": 0.12,
        "controller_mode": "budgeted", "dcn_measured_mbps": 184.2,
        "wire_savings_wallclock_ratio": 1.31,
    }) == []
    assert validate_metrics({"wire_savings_wallclock_ration": 1.3}) != []


def test_metrics_schema_green_on_shipped_tree():
    assert repo_lint.check_metrics_schema() == []


# ---------------------------------------------------------------------------
# host-side bench record schema (data-bench, serve-bench, siege, fleet)
# ---------------------------------------------------------------------------


def test_validate_record_contract():
    assert validate_record(
        {"metric": "m", "value": 1.0, "unit": "pairs/s/chip"}
    ) == []
    missing = validate_record({"value": 1.0})
    assert any("metric" in p for p in missing)
    unknown = validate_record(
        {"metric": "m", "value": 0.0, "unit": "x", "bogus": 1}
    )
    assert any("bogus" in p for p in unknown)
    assert validate_record([1, 2]) != []


# ---------------------------------------------------------------------------
# the `lint` CLI subcommand
# ---------------------------------------------------------------------------


def test_cli_lint_ast_only_green(capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    assert main(["lint", "--no-jaxpr"]) == 0
    out, err = capsys.readouterr()
    assert "0 finding(s)" in err


def test_cli_lint_json_report(capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    assert main(["lint", "--no-jaxpr", "--json",
                 "--disable", "repo-doc-stale"]) == 0
    out, _ = capsys.readouterr()
    report = json.loads(out)
    assert report["findings"] == []
    assert "repo-doc-stale" in report["disabled"]
    assert "repo-doc-code" in report["rules_checked"]
    assert "repo-doc-stale" not in report["rules_checked"]


def test_cli_lint_unknown_rule_is_usage_error(capsys):
    from distributed_sigmoid_loss_tpu.cli import main

    assert main(["lint", "--no-jaxpr", "--disable", "bogus-rule"]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_cli_lint_exits_1_on_findings(capsys, monkeypatch):
    import distributed_sigmoid_loss_tpu.analysis as analysis
    from distributed_sigmoid_loss_tpu.cli import main

    monkeypatch.setattr(
        analysis, "run_lint",
        lambda **kw: [Finding("repo-doc-stale", "x", "drill finding")],
    )
    assert main(["lint", "--no-jaxpr"]) == 1
    out, err = capsys.readouterr()
    assert "drill finding" in out
    assert "1 finding(s)" in err


def test_run_lint_full_green():
    """The exact call tier-1/dryrun makes: AST rules + config-space drift
    probe + both jaxpr rule sets over the tier-1 sample."""
    findings = run_lint()
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# repo-chaos-gate (graftsiege): fault injection provably dead in production
# ---------------------------------------------------------------------------

_GOOD_SIEGE_FIXTURE = '''
import os

CHAOS_POINTS = {"engine.latency": "slow accelerator step degradation drill"}

def chaos_enabled():
    return os.environ.get("DSL_CHAOS", "") == "1"

def maybe_inject(point):
    if point not in CHAOS_POINTS:
        raise KeyError(point)
    if not chaos_enabled():
        return
'''

_GOOD_SERVE_FIXTURE = {
    "serve/engine.py": 'maybe_inject("engine.latency")\n',
}


def test_chaos_gate_green_on_minimal_fixture_and_shipped_tree():
    assert repo_lint.check_chaos_gate(
        siege_source=_GOOD_SIEGE_FIXTURE, serve_sources=_GOOD_SERVE_FIXTURE
    ) == []
    findings = repo_lint.check_chaos_gate()
    assert findings == [], [str(f) for f in findings]


def test_chaos_gate_trips_on_ungated_maybe_inject():
    """The load-bearing half: a maybe_inject that fires without checking
    chaos_enabled() is an injection point live in production."""
    ungated = _GOOD_SIEGE_FIXTURE.replace(
        "    if not chaos_enabled():\n        return\n", "    pass\n"
    )
    findings = repo_lint.check_chaos_gate(
        siege_source=ungated, serve_sources=_GOOD_SERVE_FIXTURE
    )
    assert _rules_of(findings) == ["repo-chaos-gate"]
    assert findings[0].subject == "serve/siege.py::maybe_inject"


def test_chaos_gate_trips_when_gate_ignores_dsl_chaos_hook():
    wrong_hook = _GOOD_SIEGE_FIXTURE.replace('"DSL_CHAOS"', '"OTHER_VAR"')
    findings = repo_lint.check_chaos_gate(
        siege_source=wrong_hook, serve_sources=_GOOD_SERVE_FIXTURE
    )
    assert [f.subject for f in findings] == ["serve/siege.py::chaos_enabled"]


def test_chaos_gate_trips_on_empty_rationale():
    no_why = _GOOD_SIEGE_FIXTURE.replace(
        '"slow accelerator step degradation drill"', '""'
    )
    findings = repo_lint.check_chaos_gate(
        siege_source=no_why, serve_sources=_GOOD_SERVE_FIXTURE
    )
    assert [f.subject for f in findings] == ["serve/siege.py::engine.latency"]


def test_chaos_gate_trips_on_unregistered_and_computed_call_sites():
    bad_sites = {
        "serve/engine.py": 'maybe_inject("engine.latency")\n'
                           'maybe_inject("engine.unregistered")\n',
        "serve/swap.py": 'maybe_inject(point_var)\n',
    }
    findings = repo_lint.check_chaos_gate(
        siege_source=_GOOD_SIEGE_FIXTURE, serve_sources=bad_sites
    )
    subjects = sorted(f.subject for f in findings)
    assert subjects == [
        "serve/engine.py::engine.unregistered",
        "serve/swap.py::maybe_inject",
    ]
    assert set(_rules_of(findings)) == {"repo-chaos-gate"}


def test_chaos_gate_trips_on_stale_registry_row():
    """A registered point nobody calls is a drill that silently stopped
    existing — the registry must mirror the real call sites."""
    findings = repo_lint.check_chaos_gate(
        siege_source=_GOOD_SIEGE_FIXTURE,
        serve_sources={"serve/engine.py": "x = 1\n"},
    )
    assert [f.subject for f in findings] == ["serve/siege.py::engine.latency"]
    assert "stale" in findings[0].detail


# ---------------------------------------------------------------------------
# graftguard (analysis/lock_flow.py): each lock-* rule falsified on a
# known-bad fixture, green on the shipped tree
# ---------------------------------------------------------------------------

from distributed_sigmoid_loss_tpu.analysis import lock_flow  # noqa: E402


def test_lock_flow_green_on_shipped_tree():
    findings = lock_flow.run_lock_flow()
    assert findings == [], [str(f) for f in findings]


def test_unguarded_write_trips_and_init_and_reads_exempt():
    src = (
        "import threading\n"
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"           # construction: exempt
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"      # defines the guarded set
        "    def reset(self):\n"
        "        self._n = 0\n"           # unguarded write: trips
        "    def peek(self):\n"
        "        return self._n\n"        # plain read: NOT flagged
    )
    findings = lock_flow.analyze_lock_flow(sources={"fake/mod.py": src})
    assert _rules_of(findings) == ["lock-unguarded-write"]
    assert [f.subject for f in findings] == ["fake/mod.py::Counter._n"]
    fixed = src.replace(
        "    def reset(self):\n        self._n = 0\n",
        "    def reset(self):\n        with self._lock:\n"
        "            self._n = 0\n",
    )
    assert lock_flow.analyze_lock_flow(sources={"fake/mod.py": fixed}) == []


def test_unguarded_mutating_method_call_trips():
    """Compound RMW through a mutating method (append/pop/...) outside the
    lock is the same torn-update class as a bare assignment."""
    src = (
        "import threading\n"
        "class Log:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._rows = []\n"
        "    def add(self, r):\n"
        "        with self._lock:\n"
        "            self._rows.append(r)\n"
        "    def drop(self):\n"
        "        self._rows.pop()\n"
    )
    findings = lock_flow.analyze_lock_flow(sources={"fake/mod.py": src})
    assert [(f.rule, f.subject) for f in findings] == [
        ("lock-unguarded-write", "fake/mod.py::Log._rows")
    ]


def test_wait_no_loop_trips_and_while_wrapped_is_clean():
    src = (
        "import threading\n"
        "class Waiter:\n"
        "    def __init__(self):\n"
        "        self._cv = threading.Condition()\n"
        "        self.ready = False\n"
        "    def bad(self):\n"
        "        with self._cv:\n"
        "            if not self.ready:\n"
        "                self._cv.wait()\n"
        "    def good(self):\n"
        "        with self._cv:\n"
        "            while not self.ready:\n"
        "                self._cv.wait()\n"
    )
    findings = lock_flow.analyze_lock_flow(sources={"fake/mod.py": src})
    assert [(f.rule, f.subject) for f in findings] == [
        ("lock-wait-no-loop", "fake/mod.py::Waiter.bad")
    ]


def test_blocking_hold_trips_and_str_join_dict_get_exempt():
    src = (
        "import threading\n"
        "class Holder:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._futs = []\n"
        "        self.cfg = {}\n"
        "    def flush(self):\n"
        "        with self._lock:\n"
        "            for f in self._futs:\n"
        "                f.result()\n"          # blocking under lock
        "    def ok(self):\n"
        "        with self._lock:\n"
        "            s = ','.join(['a'])\n"     # str.join: exempt
        "            v = self.cfg.get('k')\n"   # dict.get: exempt
        "            return s, v\n"
    )
    findings = lock_flow.analyze_lock_flow(sources={"fake/mod.py": src})
    assert [(f.rule, f.subject) for f in findings] == [
        ("lock-blocking-hold", "fake/mod.py::Holder.flush")
    ]
    # queue-ish receivers DO trip: the q.get() convoy class.
    qsrc = src.replace(
        "            for f in self._futs:\n                f.result()\n",
        "            item = self.work_q.get()\n",
    )
    findings = lock_flow.analyze_lock_flow(sources={"fake/mod.py": qsrc})
    assert _rules_of(findings) == ["lock-blocking-hold"]


def test_orphan_thread_trips_and_joined_is_clean():
    src = (
        "import threading\n"
        "class Runner:\n"
        "    def __init__(self):\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    findings = lock_flow.analyze_lock_flow(sources={"fake/mod.py": src})
    assert [(f.rule, f.subject) for f in findings] == [
        ("lock-orphan-thread", "fake/mod.py::Runner._t")
    ]
    fixed = src + "    def close(self):\n        self._t.join()\n"
    assert lock_flow.analyze_lock_flow(sources={"fake/mod.py": fixed}) == []


def test_order_cycle_trips_on_seeded_inversion():
    src = (
        "import threading\n"
        "LA = threading.Lock()\n"
        "LB = threading.Lock()\n"
        "def one():\n"
        "    with LA:\n"
        "        with LB:\n"
        "            pass\n"
        "def two():\n"
        "    with LB:\n"
        "        with LA:\n"
        "            pass\n"
    )
    findings = lock_flow.check_lock_order(sources={"fake/mod.py": src})
    assert _rules_of(findings) == ["lock-order-cycle"]
    assert "fake/mod.py::LA" in findings[0].subject
    assert "fake/mod.py::LB" in findings[0].subject
    # one consistent direction: an edge, no cycle
    acyclic = src.replace(
        "def two():\n    with LB:\n        with LA:\n            pass\n", ""
    )
    assert lock_flow.check_lock_order(
        sources={"fake/mod.py": acyclic}
    ) == []
    assert lock_flow.lock_order_edges(sources={"fake/mod.py": acyclic}) == {
        ("fake/mod.py::LA", "fake/mod.py::LB")
    }


def test_lock_allowlist_suppresses_and_stale_entry_trips():
    f = Finding("lock-blocking-hold", "fake/mod.py::C.m", "d")
    kept = lock_flow._apply_allowlist(
        [f], {"lock-blocking-hold::fake/mod.py::C.m": "rationale"}
    )
    assert kept == []
    stale = lock_flow._apply_allowlist(
        [], {"lock-blocking-hold::fake/mod.py::C.m": "rationale"}
    )
    assert [(s.rule, s.subject) for s in stale] == [
        ("lock-blocking-hold", "fake/mod.py::C.m")
    ]
    assert "stale" in stale[0].detail


# ---------------------------------------------------------------------------
# repo-lockwatch-gate: the witness provably dead in prod
# ---------------------------------------------------------------------------

_GOOD_LOCKWATCH_FIXTURE = '''
import os
import threading

WATCHED_LOCKS = {"serve.widget._lock": "guards widget internal state"}

def lockwatch_enabled():
    return os.environ.get("DSL_LOCKWATCH", "") == "1"

def _factory(name, kind):
    if name not in WATCHED_LOCKS:
        raise KeyError(name)
    if lockwatch_enabled():
        return _watched(name, kind)
    return kind()

def named_lock(name):
    if name not in WATCHED_LOCKS:
        raise KeyError(name)
    if lockwatch_enabled():
        return _watched(name)
    return threading.Lock()

def named_rlock(name):
    if name not in WATCHED_LOCKS:
        raise KeyError(name)
    if lockwatch_enabled():
        return _watched(name)
    return threading.RLock()

def named_condition(name):
    if name not in WATCHED_LOCKS:
        raise KeyError(name)
    if lockwatch_enabled():
        return _watched(name)
    return threading.Condition()
'''

_GOOD_GATE_SOURCES = {
    "serve/widget.py": 'lock = named_lock("serve.widget._lock")\n',
}


def test_lockwatch_gate_green_on_minimal_fixture_and_shipped_tree():
    assert lock_flow.check_lockwatch_gate(
        lockwatch_source=_GOOD_LOCKWATCH_FIXTURE,
        sources=_GOOD_GATE_SOURCES, raw_allowlist={},
    ) == []
    findings = lock_flow.check_lockwatch_gate()
    assert findings == [], [str(f) for f in findings]


def test_lockwatch_gate_trips_on_ungated_factory():
    ungated = _GOOD_LOCKWATCH_FIXTURE.replace(
        "def named_lock(name):\n"
        "    if name not in WATCHED_LOCKS:\n"
        "        raise KeyError(name)\n"
        "    if lockwatch_enabled():\n"
        "        return _watched(name)\n"
        "    return threading.Lock()\n",
        "def named_lock(name):\n"
        "    return _watched(name)\n",
    )
    findings = lock_flow.check_lockwatch_gate(
        lockwatch_source=ungated,
        sources=_GOOD_GATE_SOURCES, raw_allowlist={},
    )
    assert [f.subject for f in findings] == ["obs/lockwatch.py::named_lock"]


def test_lockwatch_gate_trips_when_gate_ignores_env_hook():
    wrong = _GOOD_LOCKWATCH_FIXTURE.replace(
        '"DSL_LOCKWATCH"', '"OTHER_VAR"'
    )
    findings = lock_flow.check_lockwatch_gate(
        lockwatch_source=wrong,
        sources=_GOOD_GATE_SOURCES, raw_allowlist={},
    )
    assert [f.subject for f in findings] == [
        "obs/lockwatch.py::lockwatch_enabled"
    ]


def test_lockwatch_gate_trips_on_empty_rationale():
    no_why = _GOOD_LOCKWATCH_FIXTURE.replace(
        '"guards widget internal state"', '""'
    )
    findings = lock_flow.check_lockwatch_gate(
        lockwatch_source=no_why,
        sources=_GOOD_GATE_SOURCES, raw_allowlist={},
    )
    assert [f.subject for f in findings] == [
        "obs/lockwatch.py::serve.widget._lock"
    ]


def test_lockwatch_gate_trips_on_unregistered_and_computed_sites():
    bad = {
        "serve/widget.py": 'lock = named_lock("serve.widget._lock")\n'
                           'other = named_lock("serve.widget.ghost")\n',
        "serve/gadget.py": "lock = named_lock(computed)\n",
    }
    findings = lock_flow.check_lockwatch_gate(
        lockwatch_source=_GOOD_LOCKWATCH_FIXTURE,
        sources=bad, raw_allowlist={},
    )
    assert sorted(f.subject for f in findings) == [
        "serve/gadget.py::<module>",
        "serve/widget.py::serve.widget.ghost",
    ]


def test_lockwatch_gate_trips_on_stale_registry_row():
    findings = lock_flow.check_lockwatch_gate(
        lockwatch_source=_GOOD_LOCKWATCH_FIXTURE,
        sources={"serve/widget.py": "x = 1\n"}, raw_allowlist={},
    )
    assert [f.subject for f in findings] == [
        "obs/lockwatch.py::serve.widget._lock"
    ]
    assert "stale" in findings[0].detail


def test_lockwatch_gate_trips_on_raw_lock_and_allowlist_clears():
    src = {
        "serve/widget.py": 'lock = named_lock("serve.widget._lock")\n'
                           "import threading\n"
                           "raw = threading.Lock()\n",
    }
    findings = lock_flow.check_lockwatch_gate(
        lockwatch_source=_GOOD_LOCKWATCH_FIXTURE,
        sources=src, raw_allowlist={},
    )
    assert [(f.rule, f.subject) for f in findings] == [
        ("repo-lockwatch-gate", "serve/widget.py::<module>")
    ]
    assert lock_flow.check_lockwatch_gate(
        lockwatch_source=_GOOD_LOCKWATCH_FIXTURE,
        sources=src,
        raw_allowlist={"serve/widget.py::<module>": "bootstrap lock"},
    ) == []
