"""Train step with compressed gradient sync over the DCN (cross-slice) axis.

The regular :func:`~distributed_sigmoid_loss_tpu.train.train_step.make_train_step`
leaves gradient synchronization to XLA: autodiff of the pmean'd loss inserts
one fused f32 all-reduce over the whole data axis. That is the right call
within a slice (ICI), but across slices the same bytes ride DCN — the slow
link the reference's NCCL world also crosses (its Gloo/NCCL ``all_reduce``,
/root/reference/test_distributed_sigmoid_loss.py:79-83). This step makes the
sync explicit and splits it by link speed, the way the reference harness's
``average_gradients`` is explicit:

- grads are computed per-device under a **fully-manual** ``shard_map`` over
  ``(dcn, dp)`` (the towers are pure batch functions; everything else in the
  mesh stays compiler-managed),
- the ``dp`` hop is a plain f32 ``psum`` (ICI),
- the ``dcn`` hop is an int8 all-gather + local mean with error feedback
  (parallel/compression.py) — ~4x fewer bytes on the slow wire.

Grad oracle (tests/test_grad_compression.py): identical structure to the
uncompressed step, per-tensor rel err < 1% single-shot and unbiased over
steps with error feedback.

Gradient accumulation (``accum_steps > 1``) composes the natural way for a
compressed link: microbatch grads accumulate LOCALLY, and the params-sized
psum + compressed DCN exchange run ONCE on the accumulated mean — so the
slow-wire GRADIENT bytes per optimizer step are the same as an unaccumulated
step's, i.e. M× fewer per sample. (The regular step's autodiff-inserted psum
rides every microstep's backward instead.) What still crosses the wire per
microstep is the embedding traffic: the loss all-gather and its VJP move
(local_mb, d) tensors — KBs against the params' GBs — and with
``accum_negatives="global"`` (GradCache-exact full-batch negatives, the
shared ``run_gradcache`` recipe) the ONE loss island additionally routes the
full stacked-embedding cotangents across the mesh once per step.
``accum_dtype="bfloat16"`` carries the local accumulator in bf16, same
contract as the regular step's.

Pipeline composition (``pp_microbatches > 0``): both towers' block stacks run
the GPipe schedule over the mesh's ``pp`` axis INSIDE the same fully-manual
region — the shard_map manualizes ``(dcn, dp, pp)`` jointly and
``siglip_forward_pp(enclosing_manual=True)`` enters gpipe's device-level
schedule directly (nested shard_maps over disjoint axis sets are not
supported). Stage params enter pre-sliced by per-leaf ``P(pp)`` in_specs, the
error-feedback tree shards ``(dcn, pp)`` on block leaves, and the compressed
DCN hop quantizes each device's LOCAL stage slice — the pod-realistic pairing
of a multi-slice wire with deep pipelined towers.

MoE towers compose on meshes WITHOUT an ``ep`` axis (``moe_aux_weight=...``;
experts replicated — GSPMD cannot insert expert all-to-alls inside the manual
region, so expert parallelism stays with the regular step).

Scope: ``variant="all_gather"`` (the ring's ppermute has no joint-axis form),
``accum_negatives="global"`` not under pp, and pp towers dense (same
constraints as the regular step) — each raises with a pointer. Sequence
parallelism stays with the regular step by design: sp's economics depend on
GSPMD propagating the sequence sharding through the non-attention tower ops
(MLP/LN run on seq shards), which a fully-manual region cannot provide — a
manual sp composition would replicate that compute sp-fold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_sigmoid_loss_tpu.parallel.adaptive_compression import (
    CODEC_BLOCK,
    CODEC_GROUPS,
    N_SCHEMES,
    SCHEME_INT8,
    SCHEME_TOPK,
    adaptive_axis_mean,
    default_codec,
    leaf_sizes,
    payload_bytes_table,
)
from distributed_sigmoid_loss_tpu.parallel.compression import (
    compressed_axis_mean,
    init_error_feedback,
)
from distributed_sigmoid_loss_tpu.parallel.update_shard import (
    apply_sharded_update,
    capture_shardings,
    ef_slot_shape,
    padded_rows,
    psum_scatter_shard,
    resolve_update_sharding,
    shardable,
    unpad_like,
)
from distributed_sigmoid_loss_tpu.train.train_step import (
    LOSS_ISLAND_SCOPE,
    TrainState,
    _mean_moe_aux,
    accum_add,
    accum_finish,
    accum_zeros,
    health_metrics,
    is_pp_block_leaf,
    run_gradcache,
    validate_accum_args,
    validate_trainable_quant,
)
from distributed_sigmoid_loss_tpu.utils.config import LossConfig

__all__ = [
    "make_compressed_train_step",
    "with_error_feedback",
    "with_adaptive_compression",
    "stage_scheme",
    "stage_codec",
]


def with_error_feedback(
    state: TrainState, mesh: Mesh, dcn_axis: str = "dcn",
    pp_axis: str | None = None, update_sharding: str = "off",
    axis_name: str = "dp",
):
    """Attach a zeroed error-feedback tree to ``state``, sharded over dcn.

    ``pp_axis``: for a pipeline-composed compressed step
    (``make_compressed_train_step(pp_microbatches=...)``) — block-stack
    residuals additionally shard their depth dim over that axis, matching the
    stage-local gradient slices the step compresses.

    ``update_sharding="full"``: the step compresses the dp reduce-scattered
    1/W gradient shard, so the residual it carries is SHARD-LOCAL too —
    leaves the shared placement rule shards get the padded
    ``(n_dcn, padded_rows(d0, W), ...)`` layout sharded ``(dcn, dp)``
    (parallel/update_shard.ef_slot_shape); everything else keeps the
    replicated-grad ``(n_dcn, *shape)`` layout. "zero1" does not touch the
    gradient wire and keeps the classic layout.
    """
    n = mesh.shape[dcn_axis]
    pp_size = mesh.shape[pp_axis] if pp_axis else 1
    mode = "full" if update_sharding == "full" else "off"
    w_dp = dict(mesh.shape).get(axis_name, 1)

    def shard_for(path, p):
        if pp_axis and is_pp_block_leaf(path, p.shape, pp_size):
            # EF leaf is (n_dcn, depth, ...): dcn on dim 0, pp on the depth dim.
            return NamedSharding(mesh, P(dcn_axis, pp_axis))
        if shardable(p.shape, w_dp, mode):
            return NamedSharding(mesh, P(dcn_axis, axis_name))
        return NamedSharding(mesh, P(dcn_axis))

    if mode == "full":
        def build_ef(p):
            return jax.tree.map(
                lambda x: jnp.zeros(
                    ef_slot_shape(x.shape, n, w_dp, mode), x.dtype
                ),
                p,
            )
    else:
        def build_ef(p):
            return init_error_feedback(p, n)

    ef = jax.jit(
        build_ef,
        out_shardings=jax.tree_util.tree_map_with_path(shard_for, state.params),
    )(state.params)
    return state.replace(ef=ef)


def with_adaptive_compression(
    state: TrainState, mesh: Mesh, dcn_axis: str = "dcn",
    update_sharding: str = "off", axis_name: str = "dp",
    learned: bool = False,
):
    """Attach EF plus the adaptive-compression carry (``state.comp``).

    ``comp`` is a small replicated dict the step and the host-side
    :class:`~distributed_sigmoid_loss_tpu.parallel.adaptive_compression.BitController`
    exchange each round: ``scheme`` (int32[n_tensors], controller-written via
    :func:`stage_scheme` — the per-tensor wire format, initially all-int8)
    and the step-written per-tensor stats ``gnorm`` / ``gvar`` /
    ``ef_ratio`` (f32[n_tensors]). It rides the donated state operand, so
    scheme changes are value changes — never recompiles. Like ``ef``, it is
    derived state: checkpoints strip it (train/checkpoint.py) and restore
    re-attaches a fresh zero carry.

    ``learned=True`` (graftcodec, ``compression="learned"``) grows the carry
    with the learned rung's exchange slots: host-written codec weights
    ``codec_enc`` (f32[G, B, L]) / ``codec_dec`` (f32[G, L, B]) staged via
    :func:`stage_codec` (DCT cold start), and the step-written training
    stats ``blockmoment`` (f32[G, B, B]) / ``codec_recon_err`` (f32 scalar)
    the host-side ``CodecTrainer`` consumes. All replicated — codec-weight
    updates are value changes too.
    """
    state = with_error_feedback(
        state, mesh, dcn_axis=dcn_axis, update_sharding=update_sharding,
        axis_name=axis_name,
    )
    n = len(jax.tree.leaves(state.params))
    rep = NamedSharding(mesh, P())
    comp = {
        "scheme": jax.device_put(jnp.zeros((n,), jnp.int32), rep),
        "gnorm": jax.device_put(jnp.zeros((n,), jnp.float32), rep),
        "gvar": jax.device_put(jnp.zeros((n,), jnp.float32), rep),
        "ef_ratio": jax.device_put(jnp.zeros((n,), jnp.float32), rep),
    }
    if learned:
        codec = default_codec()
        comp["codec_enc"] = jax.device_put(jnp.asarray(codec["enc"]), rep)
        comp["codec_dec"] = jax.device_put(jnp.asarray(codec["dec"]), rep)
        comp["blockmoment"] = jax.device_put(
            jnp.zeros((CODEC_GROUPS, CODEC_BLOCK, CODEC_BLOCK), jnp.float32),
            rep,
        )
        comp["codec_recon_err"] = jax.device_put(
            jnp.zeros((), jnp.float32), rep
        )
    return state.replace(comp=comp)


def stage_scheme(state: TrainState, scheme, mesh: Mesh) -> TrainState:
    """Stage a controller-decided scheme table into ``state.comp``.

    Re-placed with the same replicated NamedSharding the carry was created
    with, so the donated jit sees an identical layout (no reshard, no
    recompile) when the VALUES change between rounds."""
    if state.comp is None:
        raise ValueError(
            "state has no comp carry — create it with "
            "with_adaptive_compression(state, mesh)"
        )
    new = jax.device_put(
        jnp.asarray(scheme, jnp.int32), NamedSharding(mesh, P())
    )
    return state.replace(comp=dict(state.comp, scheme=new))


def stage_codec(state: TrainState, codec, mesh: Mesh) -> TrainState:
    """Stage CodecTrainer-solved learned-rung weights into ``state.comp``.

    ``codec``: ``{"enc": f32[G, B, L], "dec": f32[G, L, B]}`` (the trainer's
    :meth:`~...adaptive_compression.CodecTrainer.update` return). Same
    contract as :func:`stage_scheme`: re-placed with the replicated
    NamedSharding the carry was created with, so an online codec retrain is
    an operand VALUE change — no reshard, no recompile."""
    if state.comp is None or "codec_enc" not in state.comp:
        raise ValueError(
            "state has no codec carry — create it with "
            "with_adaptive_compression(state, mesh, learned=True)"
        )
    rep = NamedSharding(mesh, P())
    return state.replace(comp=dict(
        state.comp,
        codec_enc=jax.device_put(
            jnp.asarray(codec["enc"], jnp.float32), rep
        ),
        codec_dec=jax.device_put(
            jnp.asarray(codec["dec"], jnp.float32), rep
        ),
    ))


def validate_compressed_step_args(
    *,
    accum_steps: int,
    accum_dtype: str | None,
    accum_negatives: str,
    pp_microbatches: int,
    zero1: bool = False,
    moe_aux_weight: float | None = None,
    gradcache_embed_dtype: str | None = None,
    compression: str = "int8",
    error_feedback: bool = True,
    topk_frac: float = 0.01,
    loss_variant: str = "all_gather",
    mesh_axis_names: tuple = ("dcn", "dp"),
    update_sharding: str = "",
):
    """Pure config-compatibility refusals for
    :func:`make_compressed_train_step`, returning ``(cached_accum, acc_dt)``.

    Config-space only, same split as train_step.validate_step_args: the
    graftprove probe (analysis/config_space.py) calls this with a superset
    ``mesh_axis_names`` so it exercises exactly the refusals the declarative
    table must mirror; environment checks (tower shapes, quant mode of the
    actual model, the full-mode dp>1 requirement) stay in the builder.
    """
    mode = resolve_update_sharding(update_sharding, zero1)
    acc_dt = validate_accum_args(accum_steps, accum_dtype)
    if accum_negatives not in ("local", "global"):
        raise ValueError(
            f"accum_negatives must be 'local' or 'global', got {accum_negatives!r}"
        )
    cached_accum = accum_negatives == "global" and accum_steps > 1
    if gradcache_embed_dtype is not None and not cached_accum:
        raise ValueError(
            f"gradcache_embed_dtype={gradcache_embed_dtype!r} requires "
            "accum_negatives='global' with accum_steps > 1 (only the "
            "GradCache path stashes embedding tables)"
        )
    if pp_microbatches < 0:
        raise ValueError(f"pp_microbatches must be >= 0, got {pp_microbatches}")
    if pp_microbatches:
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis

        if cached_accum:
            raise ValueError(
                "accum_negatives='global' with pp_microbatches is not "
                "supported (the pp forward is already whole-batch per "
                "accumulation step — same constraint as make_train_step)"
            )
        if mode != "off":
            raise ValueError(
                f"update_sharding={mode!r} with pp_microbatches is not "
                "supported (see make_train_step's rationale: the constrain "
                "would reshard stage-local moments dp-wise every step)"
            )
        if pipeline_axis not in mesh_axis_names:
            raise ValueError(
                f"pp_microbatches={pp_microbatches} needs a mesh with a "
                f"{pipeline_axis!r} axis, got {mesh_axis_names}"
            )
    if moe_aux_weight is not None and pp_microbatches:
        raise ValueError(
            "pp towers are dense (same constraint as make_train_step); "
            "moe_aux_weight requires the non-pp compressed path"
        )
    if compression not in ("int8", "topk", "adaptive", "learned"):
        raise ValueError(f"unknown compression method: {compression!r}")
    if compression == "topk" and not error_feedback:
        raise ValueError(
            "compression='topk' without error feedback silently drops "
            f"{(1 - topk_frac):.0%} of every gradient as pure bias; create "
            "the state with with_error_feedback(state, mesh)"
        )
    if compression == "adaptive" and not error_feedback:
        raise ValueError(
            "compression='adaptive' requires error feedback (its sign/topk "
            "rungs are pure bias without the residual carry, and scheme "
            "CHANGES lean on it to absorb the transition); create the state "
            "with with_adaptive_compression(state, mesh)"
        )
    if compression == "learned" and not error_feedback:
        raise ValueError(
            "compression='learned' requires error feedback (the learned "
            "rung's reconstruction bias — like every adaptive rung's "
            "truncation — is only unbiased through the residual carry); "
            "create the state with "
            "with_adaptive_compression(state, mesh, learned=True)"
        )
    if compression in ("adaptive", "learned") and pp_microbatches:
        raise ValueError(
            f"compression={compression!r} with pp_microbatches is not "
            "supported: the controller's scheme table and stats are per "
            "GLOBAL tensor, but pp shards block-stack gradients "
            "stage-locally — use the fixed int8/topk compressed path under pp"
        )
    if loss_variant != "all_gather":
        raise ValueError(
            "compressed DCN sync supports variant='all_gather' only (the ring "
            "ppermute has no joint-(dcn,dp) axis form); use make_train_step "
            "for ring training within a slice"
        )
    return cached_accum, acc_dt


def make_compressed_train_step(
    model: nn.Module,
    mesh: Mesh,
    loss_cfg: LossConfig = LossConfig(),
    dcn_axis: str = "dcn",
    error_feedback: bool = True,
    zero1: bool = False,
    compression: str = "int8",
    topk_frac: float = 0.01,
    topk_approximate: bool = True,
    accum_steps: int = 1,
    accum_dtype: str | None = None,
    accum_negatives: str = "local",
    pp_microbatches: int = 0,
    moe_aux_weight: float | None = None,
    gradcache_embed_dtype: str | None = None,
    update_sharding: str = "",
):
    """Build ``(state, batch) -> (state, metrics)`` with int8 DCN grad sync.

    ``update_sharding`` ("off" | "zero1" | "full"; ``zero1=True`` is the
    deprecated alias for "zero1"): under "full" the dp hop becomes an
    explicit reduce-scatter (``psum_scatter`` per leaf, leading dim padded
    to a multiple of W) and the compressor quantizes the 1/W SHARD over the
    dcn wire — DCN bytes drop another ~W× on top of the rung ladder, the
    error-feedback residual is shard-local (create the state with
    ``with_error_feedback(..., update_sharding="full")``), and the optax
    update + optimizer state live on the shard
    (parallel/update_shard.apply_sharded_update). Quantization scales are
    then per-shard rather than per-tensor — not bitwise the unsharded
    compressed wire, unbiased under the same EF contract. Requires dp > 1;
    pp is excluded (same refusal as the regular step).

    ``mesh`` must carry ``(dcn_axis, dp axis)``; the batch shards over both.
    With ``error_feedback=True`` create the state via
    :func:`with_error_feedback` (the step raises otherwise). Metrics gain
    ``ef_norm`` — the global norm of the carried residual, a live view of how
    much signal the compressed wire deferred (should stay ~flat, not grow).

    ``compression``: ``"int8"`` (4x fewer DCN bytes) or ``"topk"`` (keep the
    ``topk_frac`` largest-|.| entries per tensor, ~50x fewer at 1% — needs
    error feedback; the step refuses topk without it).
    ``topk_approximate=False`` uses exact ``lax.top_k`` selection (CLI:
    ``--topk-exact``) — 4x slower on TPU, for bit-reproducibility needs.

    ``accum_steps > 1`` scans microbatches per device and syncs the
    ACCUMULATED mean once — per-microbatch negatives stay global over the
    whole (dcn, dp) world (each microstep's loss all-gathers embeddings),
    but the compressed gradient hop happens once per optimizer step.
    ``accum_dtype`` = the regular step's bf16-accumulator contract.

    ``accum_negatives="global"`` (with ``accum_steps > 1``) computes the
    EXACT full-batch loss under accumulation, GradCache-style (the regular
    step's ``grads_and_metrics_cached`` recipe, train_step.py): embed-only
    pass 1, ONE loss island on the full stacked tables (contrasting every
    image against every text across microbatches AND the (dcn, dp) world),
    then a surrogate re-forward whose parameter gradient is exactly the
    full-batch term — still with one compressed hop per optimizer step.

    ``pp_microbatches > 0`` runs both towers' block stacks through the GPipe
    schedule over the mesh's ``pp`` axis with that many microbatches per
    (accumulation) microstep — the compressed analogue of
    ``make_train_step(pp_microbatches=...)``. ``mesh`` must carry
    ``(dcn, dp, pp)``; create the state with
    ``create_train_state(..., pp_axis="pp")`` and
    ``with_error_feedback(..., pp_axis="pp")`` so stage params and EF
    residuals live pp-sharded. Composes with ``accum_steps`` (each
    accumulation microbatch is itself pipelined); dense scan-layer towers
    only, ``accum_negatives="global"`` excluded (same as the regular step).

    ``moe_aux_weight`` (with MoE towers, non-pp) adds that weight times the
    mean router load-balancing loss to the objective — the regular step's
    contract, inside the manual region (experts replicated; no ``ep`` axis).
    Estimator note: Switch eq. 4 is a product of token-means, so the
    per-device aux averaged across the world (what this step optimizes — the
    DDP per-replica convention, each device balancing its local tokens) is
    not bitwise the regular step's global-batch product; the two track within
    a few percent and both bound expert imbalance.

    ``gradcache_embed_dtype`` (e.g. ``"bfloat16"``, with
    ``accum_negatives="global"``): store the GradCache embedding stash in
    that dtype — :func:`train_step.run_gradcache`'s contract.
    """
    # Same trainable-quant rule as make_train_step: inference int8 (zero-grad
    # round) is refused; the STE quant_train mode trains through this step's
    # manual region like any other dot.
    validate_trainable_quant(model)
    cached_accum, acc_dt = validate_compressed_step_args(
        accum_steps=accum_steps,
        accum_dtype=accum_dtype,
        accum_negatives=accum_negatives,
        pp_microbatches=pp_microbatches,
        zero1=zero1,
        moe_aux_weight=moe_aux_weight,
        gradcache_embed_dtype=gradcache_embed_dtype,
        compression=compression,
        error_feedback=error_feedback,
        topk_frac=topk_frac,
        loss_variant=loss_cfg.variant,
        mesh_axis_names=mesh.axis_names,
        update_sharding=update_sharding,
    )
    adaptive = compression in ("adaptive", "learned")
    learned = compression == "learned"
    n_dcn = dict(mesh.shape)[dcn_axis]
    update_mode = resolve_update_sharding(update_sharding, zero1)
    axis_sizes = dict(mesh.shape)
    w_dp = axis_sizes.get(loss_cfg.axis_name, 1)
    full_shard = update_mode == "full"
    if full_shard and w_dp < 2:
        # Environment refusal, mirroring make_train_step: nothing to
        # scatter over on a 1-wide dp axis.
        raise ValueError(
            "update_sharding='full' requires a dp axis of size > 1, got "
            f"{loss_cfg.axis_name!r}={w_dp} on mesh {axis_sizes}"
        )
    pp_size = 1
    if pp_microbatches:
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis
        from distributed_sigmoid_loss_tpu.parallel.pp_towers import (
            validate_pp_tower,
        )

        pp_size = dict(mesh.shape)[pipeline_axis]
        validate_pp_tower(model.cfg.vision, pp_size, "vision")
        validate_pp_tower(model.cfg.text, pp_size, "text")
    axis = loss_cfg.axis_name
    from distributed_sigmoid_loss_tpu.parallel.api import make_per_shard_loss
    from distributed_sigmoid_loss_tpu.train.train_step import (
        _precision,
        resolve_loss_quant,
    )

    per_shard = make_per_shard_loss(
        family=loss_cfg.family, variant="all_gather",
        axis_name=(dcn_axis, axis), bidir=loss_cfg.bidir,
        precision=_precision(loss_cfg.precision),
        # Streamed negatives compose: the chunked scan runs over the joint
        # (dcn, dp) gather's W chunks inside this already-unchecked manual
        # region, with the streaming Pallas kernel as its block body when
        # use_pallas is on (quant derived from the towers, same resolver as
        # make_train_step). ring_overlap is deliberately NOT threaded — this
        # step is all-gather-only (make_per_shard_loss would refuse it
        # anyway).
        loss_impl=loss_cfg.loss_impl,
        use_pallas=loss_cfg.use_pallas,
        quant=resolve_loss_quant(model, loss_cfg),
    )

    def local_loss(params, images, tokens):
        # Per-DEVICE loss only — collectives live in per_shard (whose
        # all_gather/VJP route cross-device cotangents); no pmean here (its
        # transpose under check_vma=False is psum — a W-times overcount).
        if pp_microbatches:
            from distributed_sigmoid_loss_tpu.parallel.pp_towers import (
                siglip_forward_pp,
            )

            # Device-level gpipe schedule over the pp axis of THIS manual
            # region; params arrive stage-pre-sliced via the P(pp) in_specs.
            zimg, ztxt, lp = siglip_forward_pp(
                model.cfg, params, images, tokens, mesh=mesh,
                num_microbatches=pp_microbatches, enclosing_manual=True,
            )
            aux = jnp.zeros(())
        elif moe_aux_weight is None:
            zimg, ztxt, lp = model.apply({"params": params}, images, tokens)
            aux = jnp.zeros(())
        else:
            # MoE towers: experts REPLICATED on this mesh (no ep axis inside
            # the manual region — GSPMD can't insert expert all-to-alls
            # here); router aux is a mean over this device's local tokens, so
            # the explicit psum/W below makes the objective's aux term the
            # per-replica estimator's world mean (see docstring).
            (zimg, ztxt, lp), variables = model.apply(
                {"params": params}, images, tokens, mutable=["intermediates"]
            )
            aux = _mean_moe_aux(variables)
        with jax.named_scope(LOSS_ISLAND_SCOPE):
            loss = per_shard(zimg, ztxt, lp["t_prime"], lp["bias"])
        if moe_aux_weight is not None:
            loss = loss + moe_aux_weight * aux
        return loss, (lp, aux)

    def _split_micro(images, tokens):
        local_b = images.shape[0]
        if local_b % accum_steps:
            raise ValueError(
                f"per-device batch {local_b} must divide by "
                f"accum_steps={accum_steps}"
            )
        return (
            images.reshape(accum_steps, -1, *images.shape[1:]),
            tokens.reshape(accum_steps, -1, *tokens.shape[1:]),
        )

    def cached_grads(params, images, tokens):
        """GradCache inside the shard_map: exact full-batch negatives.

        The shared :func:`train_step.run_gradcache` recipe; here the stacked
        loss island's per_shard contrasts over the joint (dcn, dp) axis, and
        the per-device parameter grads feed the SAME explicit
        psum + compressed-hop normalization chain the local path uses (the
        surrogate identity sum_dev d<z_dev, g_dev>/dp = dL_sum/dp holds
        device-wise, so the downstream /W normalization is unchanged).
        """
        ims, tks = _split_micro(images, tokens)

        def stacked(zi_s, zt_s, t_prime, bias):
            m, mb_local, d = zi_s.shape
            return per_shard(
                zi_s.reshape(m * mb_local, d), zt_s.reshape(m * mb_local, d),
                t_prime, bias,
            )

        ell, lp, mean_aux, grads = run_gradcache(
            model, params, {"images": ims, "tokens": tks}, stacked,
            accum_steps, acc_dt, moe_aux_weight=moe_aux_weight,
            embed_dtype=gradcache_embed_dtype,
        )
        if moe_aux_weight is not None:
            # run_gradcache's loss excludes the aux term; report the same
            # objective the other paths do.
            ell = ell + moe_aux_weight * mean_aux
        return ell, lp, mean_aux, grads

    def grads_body(params, images, tokens, ef, scheme=None, codec=None):
        if cached_accum:
            ell, lp, aux, grads = cached_grads(params, images, tokens)
        elif accum_steps == 1:
            (ell, (lp, aux)), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(params, images, tokens)
        else:
            # Local microbatch scan: contiguous per-device chunks (composition
            # is arbitrary for accumulation). Each microstep still all-gathers
            # EMBEDDINGS (global negatives, KBs); the params-sized gradient
            # sync — the psum + compressed DCN hop below — runs once on the
            # accumulated mean.
            ims, tks = _split_micro(images, tokens)

            def body(carry, mb):
                loss_sum, gsum = carry
                (ell_i, (lp_i, aux_i)), g = jax.value_and_grad(
                    local_loss, has_aux=True
                )(params, *mb)
                return (loss_sum + ell_i, accum_add(gsum, g)), (lp_i, aux_i)

            (loss_sum, gsum), (lps, auxs) = lax.scan(
                body, (jnp.zeros(()), accum_zeros(params, acc_dt)), (ims, tks)
            )
            ell = loss_sum / accum_steps
            grads = accum_finish(gsum, params, scale=accum_steps)
            lp = jax.tree.map(lambda x: x[-1], lps)
            aux = jnp.mean(auxs)
        if pp_microbatches:
            from distributed_sigmoid_loss_tpu.parallel.pipeline import (
                pipeline_axis,
            )

            # Replication repair over pp BEFORE declaring grads P()-replicated
            # (check_vma=False verifies nothing): gpipe consumes the
            # microbatch feed at stage 0 only, so leaves UPSTREAM of the
            # pipeline (patch/pos/token embeddings) carry their full gradient
            # on the stage-0 plane and exactly ZERO on every other plane,
            # while downstream leaves are already equal everywhere. Taking
            # the stage-0 plane's value — a masked psum — is correct for
            # both classes uniformly. Block stacks are stage-local
            # (pp-sharded) by design and must NOT be touched; inside the
            # manual region their local shapes no longer satisfy the global
            # is_pp_block_leaf shape test, so classify by path alone.
            # Teeth: tests/test_grad_compression.py::
            # test_compressed_pp_replicated_leaves_stay_replicated fails
            # with this block removed.
            on_stage0 = lax.axis_index(pipeline_axis) == 0

            def repair(path, g):
                if any(getattr(k, "key", None) == "blocks" for k in path):
                    return g
                return lax.psum(
                    jnp.where(on_stage0, g, jnp.zeros_like(g)), pipeline_axis
                )

            grads = jax.tree_util.tree_map_with_path(repair, grads)
        n_dp = lax.axis_size(axis)
        # Reference-style explicit DP sync (= all_reduce(SUM)/W), split by
        # link: f32 psum-mean on ICI; compressed_axis_mean is itself a MEAN
        # over dcn, so the two hops together divide by the full world size.
        # Under full update sharding the dp hop is a REDUCE-SCATTER instead:
        # each member keeps only its 1/W row block of the mean (padded where
        # d0 % W != 0), so everything downstream — the dcn compressor, its
        # EF residual, and the optax update outside the region — runs on the
        # shard. Leaves the placement rule replicates (scalars, short
        # vectors) keep the plain psum.
        if full_shard:
            grads = jax.tree.map(
                lambda t: (
                    psum_scatter_shard(t, axis, w_dp)
                    if shardable(t.shape, w_dp, "full")
                    else lax.psum(t, axis)
                ) / n_dp,
                grads,
            )
        else:
            grads = jax.tree.map(lambda t: lax.psum(t, axis) / n_dp, grads)
        if adaptive:
            grads, new_ef, stats, wire_bytes = adaptive_axis_mean(
                grads, dcn_axis, ef, scheme, topk_frac=topk_frac,
                topk_approximate=topk_approximate, codec=codec,
            )
            if full_shard:
                # Per-tensor controller stats were computed on this member's
                # 1/W shard and differ across dp; average them so every
                # member (and the P() out spec) carries one consistent
                # shard-scale figure per tensor. wire_bytes needs no repair:
                # it is a table gather over static shard sizes + the
                # replicated scheme, identical on every member.
                stats = jax.tree.map(lambda s: lax.pmean(s, axis), stats)
        else:
            grads, new_ef = compressed_axis_mean(
                grads, dcn_axis, ef, method=compression, topk_frac=topk_frac,
                topk_approximate=topk_approximate,
            )
        loss = lax.pmean(lax.pmean(ell, axis), dcn_axis)
        aux = lax.pmean(lax.pmean(aux, axis), dcn_axis)
        if adaptive:
            return loss, lp, aux, grads, new_ef, stats, wire_bytes
        return loss, lp, aux, grads, new_ef

    data_spec = P((dcn_axis, axis))

    def _param_specs(params):
        """Per-leaf manual specs: block stacks shard their depth dim over pp
        (stage-local slices inside the manual region), everything else
        replicates. Without pp this collapses to the plain P() prefix."""
        if not pp_microbatches:
            return P()
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis

        return jax.tree_util.tree_map_with_path(
            lambda path, p: (
                P(pipeline_axis)
                if is_pp_block_leaf(path, p.shape, pp_size)
                else P()
            ),
            params,
        )

    def _ef_specs(ef):
        if full_shard:
            # EF leaves of shardable params are (n_dcn, padded_rows(d0), ...):
            # dcn on dim 0, the shard rows over dp — each member carries only
            # the residual of the shard it quantizes (mirrors with_error_
            # feedback(update_sharding="full")).
            return jax.tree.map(
                lambda e: (
                    P(dcn_axis, axis)
                    if shardable(e.shape[1:], w_dp, "full")
                    else P(dcn_axis)
                ),
                ef,
            )
        if not pp_microbatches:
            return P(dcn_axis)
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis

        # EF leaves are (n_dcn, *param.shape): dcn on dim 0; block leaves'
        # depth dim (now dim 1) additionally over pp, mirroring _param_specs.
        return jax.tree_util.tree_map_with_path(
            lambda path, e: (
                P(dcn_axis, pipeline_axis)
                if is_pp_block_leaf(path, e.shape[1:], pp_size)
                else P(dcn_axis)
            ),
            ef,
        )

    def _grad_out_specs(params):
        """out_specs of the synced grads: under full sharding each shardable
        leaf leaves the region as its member's row block (local
        (padded/W, ...), global the padded tensor sharded P(dp)); otherwise
        the param specs (replicated, or stage-local under pp)."""
        if not full_shard:
            return _param_specs(params)
        return jax.tree.map(
            lambda p: (
                P(axis) if shardable(p.shape, w_dp, "full") else P()
            ),
            params,
        )

    def _fixed_wire_bytes(params) -> int:
        """Static per-device DCN egress of the fixed int8/topk wire —
        compile-time constant (same accounting as the adaptive path's table
        gather: payload per LOCAL tensor slice, times the (n_dcn - 1)
        all_gather fan-out)."""
        col = SCHEME_INT8 if compression == "int8" else SCHEME_TOPK
        total = 0
        for path, p in jax.tree_util.tree_flatten_with_path(params)[0]:
            sz = p.size
            if pp_microbatches and is_pp_block_leaf(path, p.shape, pp_size):
                sz //= pp_size
            elif full_shard and shardable(p.shape, w_dp, "full"):
                # The wire carries this member's padded 1/W row block.
                sz = (padded_rows(p.shape[0], w_dp) // w_dp) * (
                    sz // p.shape[0]
                )
            total += int(payload_bytes_table(sz, topk_frac)[col])
        return (n_dcn - 1) * total

    def step(state: TrainState, batch: dict, param_out_shardings=None):
        if error_feedback and state.ef is None:
            raise ValueError(
                "error_feedback=True but state.ef is None — create the state "
                "with with_error_feedback(state, mesh)"
            )
        if adaptive and state.comp is None:
            raise ValueError(
                f"compression={compression!r} but state.comp is None — "
                "create the state with with_adaptive_compression(state, mesh)"
            )
        if learned and "codec_enc" not in (state.comp or {}):
            raise ValueError(
                "compression='learned' but state.comp has no codec slots — "
                "create the state with "
                "with_adaptive_compression(state, mesh, learned=True)"
            )
        # Specs depend on the param tree structure (per-leaf pp placement), so
        # the shard_map is built at trace time. The synced grads/loss ARE
        # replicated (post-gather identical on every member) but vma inference
        # cannot prove it through the dequantized mean; unchecked like the
        # loss island (parallel/api.py).
        pspec = _param_specs(state.params)
        gspec = _grad_out_specs(state.params)
        stats = wire_bytes = None
        if adaptive:
            efspec = _ef_specs(state.ef)
            # The scheme table enters REPLICATED (P()) — the per-tensor
            # lax.switch predicate is provably uniform across members, so
            # every member runs the same branch's collectives. Under
            # compression='learned' the codec weights ride in the same way
            # (replicated operands, value-change-only), so a host retrain
            # between rounds never touches the trace.
            codec_in = (
                {"enc": state.comp["codec_enc"],
                 "dec": state.comp["codec_dec"]}
                if learned else None
            )
            sharded_grads = jax.shard_map(
                lambda p, im, tk, e, s, c: grads_body(
                    p, im, tk, e, scheme=s, codec=c
                ),
                mesh=mesh,
                in_specs=(pspec, data_spec, data_spec, efspec, P(), P()),
                out_specs=(P(), P(), P(), gspec, efspec, P(), P()),
                check_vma=False,
            )
            loss, lp, aux, grads, new_ef, stats, wire_bytes = sharded_grads(
                state.params, batch["images"], batch["tokens"], state.ef,
                state.comp["scheme"], codec_in,
            )
        elif error_feedback:
            efspec = _ef_specs(state.ef)
            sharded_grads = jax.shard_map(
                grads_body,
                mesh=mesh,
                in_specs=(pspec, data_spec, data_spec, efspec),
                out_specs=(P(), P(), P(), gspec, efspec),
                check_vma=False,
            )
            loss, lp, aux, grads, new_ef = sharded_grads(
                state.params, batch["images"], batch["tokens"], state.ef
            )
        else:
            # No EF tree in flight at all: compressed_axis_mean's ef=None path.
            sharded_grads = jax.shard_map(
                lambda p, im, tk: grads_body(p, im, tk, None)[:4],
                mesh=mesh,
                in_specs=(pspec, data_spec, data_spec),
                out_specs=(P(), P(), P(), gspec),
                check_vma=False,
            )
            loss, lp, aux, grads = sharded_grads(
                state.params, batch["images"], batch["tokens"]
            )
        if full_shard:
            # Back to param shapes: slice the GSPMD-padded leading dims off
            # (a local mask on a dp-sharded dim, not a gather); the grads
            # stay dp-sharded into the optax update below.
            grads = unpad_like(grads, state.params)
        prev_params = state.params  # update_ratio needs the pre-update tree
        # The shared update-shard recipe (parallel/update_shard.py): plain
        # apply under "off", the historical opt-state re-pin under "zero1",
        # shard-local optax + one param all-gather publish under "full".
        state = apply_sharded_update(
            state, grads, mesh=mesh, axis_name=axis, mode=update_mode,
            param_shardings=param_out_shardings,
        )
        metrics = health_metrics(loss, lp, grads, state.params, prev_params)
        if moe_aux_weight is not None:
            metrics["moe_aux"] = aux
        if error_feedback:
            state = state.replace(ef=new_ef)
            metrics["ef_norm"] = optax.global_norm(new_ef)
            # ef_norm's registered name going forward (obs/metrics_schema.py);
            # both emitted so existing dashboards keep their field.
            metrics["ef_residual_norm"] = metrics["ef_norm"]
        n_params = sum(leaf_sizes(state.params))
        if adaptive:
            scheme_in = state.comp["scheme"]
            # scheme passes through (controller-written between steps); the
            # per-tensor stats are this step's controller inputs.
            state = state.replace(comp=dict(state.comp, **stats))
            metrics["dcn_wire_bytes"] = wire_bytes
            metrics["bits_per_param"] = (
                wire_bytes * 8.0 / ((n_dcn - 1) * n_params)
            )
            metrics["compression_scheme_hist"] = jnp.bincount(
                jnp.clip(scheme_in, 0, N_SCHEMES - 1), length=N_SCHEMES
            )
            if learned:
                # Live view of what the learned rung is dropping before EF
                # recovers it — the CodecTrainer's quality signal.
                metrics["codec_recon_err"] = stats["codec_recon_err"]
        else:
            # Fixed schemes put a compile-time-constant payload on the wire;
            # emit the same accounting so adaptive-vs-fixed A/Bs read one
            # field.
            fixed = _fixed_wire_bytes(state.params)
            metrics["dcn_wire_bytes"] = jnp.asarray(fixed, jnp.float32)
            metrics["bits_per_param"] = jnp.asarray(
                fixed * 8.0 / ((n_dcn - 1) * n_params), jnp.float32
            )
        return state, metrics

    batch_sharding = {
        "images": NamedSharding(mesh, data_spec),
        "tokens": NamedSharding(mesh, data_spec),
    }
    if not full_shard:
        return jax.jit(step, donate_argnums=(0,)), batch_sharding

    # Full mode: capture the params' at-rest shardings (the all-gather
    # publish target) from the first concrete state — same deferred-jit
    # contract as make_train_step's full path; abstract traces capture KEEP
    # and leave the publish to the compiler.
    _jitted = []

    def _inner(state):
        if not _jitted:
            shardings = capture_shardings(state.params)
            _jitted.append(jax.jit(
                lambda s, b: step(s, b, param_out_shardings=shardings),
                donate_argnums=(0,),
            ))
        return _jitted[0]

    def sharded_step(state: TrainState, batch: dict):
        return _inner(state)(state, batch)

    sharded_step._cache_size = (
        lambda: _jitted[0]._cache_size() if _jitted else 0
    )
    # AOT path (a caller's step.lower(...).compile()): same capture, same
    # single inner jit — lowering and calling share one executable.
    sharded_step.lower = lambda state, batch: _inner(state).lower(state, batch)
    return sharded_step, batch_sharding
