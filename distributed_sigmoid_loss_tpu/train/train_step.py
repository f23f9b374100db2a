"""End-to-end SigLIP train step: pjit over a (dp, tp) mesh.

The reference stops at loss + toy backward (its train loop is the test harness,
test_distributed_sigmoid_loss.py:86-119); BASELINE.json's end-to-end target is a real
SigLIP step. TPU-native structure:

- Tower forward/backward runs under jit with GSPMD: batch sharded over ``dp``, tower
  kernels sharded over ``tp`` via the ``nn.with_partitioning`` annotations in
  models/transformer.py — XLA inserts the Megatron-style all-reduces.
- The contrastive loss runs in a ``shard_map`` island over ``dp`` so the all-gather /
  ppermute-ring comm pattern is explicit (parallel/allgather_loss.py, ring_loss.py).
- Gradient averaging over ``dp`` is free: the loss is ``pmean``'d, so autodiff emits the
  reduction the reference does by hand (test_distributed_sigmoid_loss.py:79-83).
- The loss scalars ride the param pytree into optax — the README contract
  (README.md:20) made structural.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from flax.training import train_state
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_sigmoid_loss_tpu.models.transformer import (
    ACCUM_SCOPE,
    GRAD_SINK,
    merge_grad_sink,
    split_grad_sink,
)
from distributed_sigmoid_loss_tpu.parallel.mesh import trace_on
from distributed_sigmoid_loss_tpu.parallel.update_shard import (
    OPTIMIZER_SCOPE,
    apply_sharded_update,
    capture_shardings,
    constrain_update_sharding,
    resolve_update_sharding,
    update_shard_spec,
)
from distributed_sigmoid_loss_tpu.utils.config import LossConfig, TrainConfig

__all__ = [
    "make_optimizer", "create_train_state", "init_params", "make_train_step",
    "zero1_constrain", "is_pp_block_leaf", "validate_trainable_quant",
    "resolve_loss_quant", "TrainState", "health_metrics",
    "LOSS_ISLAND_SCOPE", "ACCUM_SCOPE", "STEP_METRICS_SCOPE",
]

# The step program's own names (``jax.named_scope``), one per layer boundary,
# said once here and in parallel/update_shard.py (``OPTIMIZER_SCOPE``) so both
# step builders lower to the same paths. They are operation metadata only: the
# device program is the same with or without them. A profile's operations
# carry them in their jax path (wrapped by the transformation they went
# through: ``jvp(loss_island)``, ``transpose(jvp(loss_island))``), and
# benchmark/scopes.py turns them into ``loss_island_ms``, ``accum_ms`` and
# (with ``optimizer``) ``update_and_metrics_ms``. The towers need none: flax writes ``visual/...`` and
# ``textual/...`` into every operation's path.
# Beside the names, the step's one counter, written while it traces: where the
# microbatch accumulation adds a parameter's gradient, in parameter bytes
# (``step.accum_record``: ``layer_loop_bytes`` inside the backward layer loop,
# ``accum_add_bytes`` as accum_add's pass of its own; empty for a step that has
# not traced or does not accumulate). obs/attribution.py accum_placement reads it.
LOSS_ISLAND_SCOPE = "loss_island"  # the sharded sigmoid loss, forward and backward
# ACCUM_SCOPE ("accum", models/transformer.py beside the one add that runs in a
# tower): the gradient accumulator's traffic in the microbatch scan.
STEP_METRICS_SCOPE = "step_metrics"  # the health scalars every step pays
# Leaves that decide something discrete and are set by a training recipe's own
# rule, not by the optimizer (models/moe.py SELECT_BIAS, a router's selection
# bias): no gradient reaches them, and create_train_state gives them no decay,
# no update and no optimizer state.
NO_UPDATE_LEAVES = ("select_bias",)


def resolve_loss_quant(model: nn.Module, loss_cfg) -> str:
    """THE loss-matmul quantization resolution, shared by the regular and
    compressed step builders: ``"int8"`` when the towers train through the
    int8 STE (``quant_train="int8"``) AND the streaming Pallas loss kernel is
    on — so ``--quant-train int8`` reaches the loss matmul itself, with the
    same contract as every other STE dot (forward bit-identical to the
    inference int8 product, backward the full-precision VJP). Without
    ``use_pallas`` the loss stays full-precision (the XLA path has no int8
    block product), matching the pre-streaming behavior.
    """
    if not getattr(loss_cfg, "use_pallas", False):
        return ""
    from distributed_sigmoid_loss_tpu.utils.config import tower_quant_mode

    cfg = getattr(model, "cfg", None)
    modes = {
        tower_quant_mode(tcfg)
        for tcfg in (getattr(cfg, "vision", None), getattr(cfg, "text", None))
        if tcfg is not None
    }
    return "int8" if "int8_ste" in modes else ""


def validate_trainable_quant(model: nn.Module) -> None:
    """Reject INFERENCE-quantized towers in trainable contexts — shared by the
    regular and compressed steps so the rule cannot drift between them.

    ``quant="int8"`` routes the projection matmuls through ``round()``, whose
    gradient is zero almost everywhere: a quantized tower trains to a
    standstill silently. ``quant_train="int8"`` is the trainable path — the
    same int8 forward through the straight-through estimator
    (ops/quant.py int8_dot_general_ste), whose backward is the exact
    unquantized VJP — and passes this check.
    """
    cfg = getattr(model, "cfg", None)
    for tower in ("vision", "text"):
        tcfg = getattr(cfg, tower, None)
        if getattr(tcfg, "quant", ""):
            raise ValueError(
                f"{tower} tower has quant={tcfg.quant!r}: int8 quantization "
                "is inference-only (zero gradients through round); train "
                "with quant_train='int8' (STE: int8 forward, full-precision "
                "backward) or quant='' and quantize at eval/export time"
            )


def is_pp_block_leaf(path, shape, pp_size: int) -> bool:
    """THE criterion for pipeline-stage-sharded param leaves — shared by
    :func:`_with_pp_shardings` (regular step) and the compressed step's
    per-leaf manual specs so the two can never drift: nn.scan-stacked block
    leaves (path contains 'blocks') whose leading depth dim splits over
    ``pp_size`` stages."""
    in_blocks = any(getattr(k, "key", None) == "blocks" for k in path)
    return bool(
        in_blocks and shape and shape[0] >= pp_size and shape[0] % pp_size == 0
    )


class TrainState(train_state.TrainState):
    """Flax train state + optional EMA of the params (``ema=None`` = disabled;
    as a pytree-None it adds no leaves, so states without EMA checkpoint and
    shard exactly as before). ``ef`` is the per-slice error-feedback residual
    tree of compressed DCN gradient sync (train/compressed_step.py), None
    when compression is off — same no-leaves contract as ``ema``. ``comp``
    is the adaptive-compression carry (per-tensor scheme table + controller
    stats, compressed_step.with_adaptive_compression), None unless
    ``--grad-compression adaptive`` — again the same contract."""

    ema: Any = None
    ef: Any = None
    comp: Any = None


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """AdamW + global-norm clipping, LR per ``cfg.schedule`` (linear warmup then
    cosine decay / inverse-sqrt / constant)."""
    # warmup_steps=0 means NO warmup (full LR at step 0) in every branch;
    # the sqrt timescale clamps to 1 only to avoid a 0/0, not to re-add warmup.
    warmup = cfg.warmup_steps
    timescale = max(warmup, 1)
    if cfg.schedule == "warmup_cosine":
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=cfg.learning_rate,
            warmup_steps=warmup,
            decay_steps=cfg.total_steps,
        )
    elif cfg.schedule == "rsqrt":
        # peak / sqrt(t / warmup) for t > warmup — continuous at the peak and
        # independent of total_steps (the paper's open-ended pretraining choice).
        def schedule(step):
            step = jnp.asarray(step, jnp.float32)
            warm = cfg.learning_rate * step / timescale
            decay = cfg.learning_rate * jnp.sqrt(
                timescale / jnp.maximum(step, timescale)
            )
            return jnp.where(step < warmup, warm, decay)
    elif cfg.schedule == "constant":
        def schedule(step):
            step = jnp.asarray(step, jnp.float32)
            warm_factor = (
                jnp.minimum(step / warmup, 1.0) if warmup > 0 else jnp.ones_like(step)
            )
            return cfg.learning_rate * warm_factor
    else:
        raise ValueError(f"unknown schedule: {cfg.schedule!r}")
    if cfg.optimizer == "adamw":
        opt = optax.adamw(
            schedule,
            b1=cfg.b1,
            b2=cfg.b2,
            weight_decay=cfg.weight_decay,
            mu_dtype=cfg.adam_mu_dtype,
        )
    elif cfg.optimizer == "lion":
        # Half adam's optimizer state (one momentum slot, no second moment);
        # composes with mu_dtype bf16 for a 4x cut vs f32 adam.
        opt = optax.lion(
            schedule,
            b1=cfg.b1,
            b2=cfg.b2,
            weight_decay=cfg.weight_decay,
            mu_dtype=cfg.adam_mu_dtype,
        )
    elif cfg.optimizer == "adafactor":
        # Factored second moments (rows+cols per kernel): the biggest-model
        # memory option. optax's adafactor owns its own update-clipping and
        # relative step sizing; we feed the schedule and weight decay through.
        opt = optax.adafactor(
            learning_rate=schedule,
            multiply_by_parameter_scale=False,
            weight_decay_rate=cfg.weight_decay,
        )
    else:
        raise ValueError(f"unknown optimizer: {cfg.optimizer!r}")
    return optax.chain(optax.clip_by_global_norm(1.0), opt)


def validate_accum_args(accum_steps: int, accum_dtype: str | None):
    """Shared accum contract (regular + compressed steps): returns the
    accumulator dtype (None = param dtype). Refuse, don't drop: an
    unaccumulated step has no accumulator, and a config claiming accum_dtype
    that never ran poisons comparisons."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if accum_dtype is not None and accum_steps == 1:
        raise ValueError(
            f"accum_dtype={accum_dtype!r} requires accum_steps > 1 "
            f"(got {accum_steps}); the unaccumulated step has no accumulator"
        )
    return jnp.dtype(accum_dtype) if accum_dtype is not None else None


def validate_step_args(
    *,
    accum_steps: int,
    accum_dtype: str | None,
    accum_negatives: str,
    pp_microbatches: int,
    zero1: bool = False,
    moe_aux_weight: float | None = None,
    gradcache_embed_dtype: str | None = None,
    mesh_axis_names: tuple = ("dp",),
    update_sharding: str = "",
):
    """Pure config-compatibility refusals for :func:`make_train_step`,
    returning ``(cached_accum, acc_dt)``.

    Every refusal here is CONFIG-space — a pure statement about argument
    compatibility, cross-checked against the declarative table in
    analysis/config_space.py by the graftprove probe (which calls this with
    a superset ``mesh_axis_names``). Environment checks (tower shapes via
    validate_pp_tower, state contents, the full-mode dp>1 requirement) stay
    in make_train_step: they depend on the model/mesh instance, not the
    config point.

    ``update_sharding`` / ``zero1``: resolved through
    :func:`~distributed_sigmoid_loss_tpu.parallel.update_shard.resolve_update_sharding`
    (``zero1`` is the deprecated alias for ``update_sharding="zero1"``); any
    sharded-update mode is refused under pp.
    """
    mode = resolve_update_sharding(update_sharding, zero1)
    if accum_negatives not in ("local", "global"):
        raise ValueError(
            f"accum_negatives must be 'local' or 'global', got {accum_negatives!r}"
        )
    # accum_steps == 1 with "global" is not an error — an unaccumulated step
    # already contrasts globally — it just takes the plain path.
    cached_accum = accum_negatives == "global" and accum_steps > 1
    acc_dt = validate_accum_args(accum_steps, accum_dtype)
    if gradcache_embed_dtype is not None and not cached_accum:
        raise ValueError(
            f"gradcache_embed_dtype={gradcache_embed_dtype!r} requires "
            "accum_negatives='global' with accum_steps > 1 (only the "
            "GradCache path stashes embedding tables)"
        )
    if cached_accum and pp_microbatches:
        raise ValueError(
            "accum_negatives='global' with pp_microbatches is not supported "
            "(the pp forward is already whole-batch per accumulation step)"
        )
    if pp_microbatches < 0:
        raise ValueError(f"pp_microbatches must be >= 0, got {pp_microbatches}")
    if pp_microbatches:
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis

        if moe_aux_weight is not None:
            raise ValueError(
                "pp towers are dense (Block.apply drops sown aux losses); "
                "moe_aux_weight requires the non-pp path"
            )
        if mode != "off":
            # The update-shard constraints would re-shard the stage-local
            # (pp-sharded) adam moments dp-wise on every step — defeating
            # both memory stories with a silent per-step reshard. Refuse
            # until a pp-aware update-shard placement exists.
            raise ValueError(
                f"update_sharding={mode!r} with pp_microbatches is not "
                "supported"
            )
        if pipeline_axis not in mesh_axis_names:
            raise ValueError(
                f"pp_microbatches={pp_microbatches} needs a mesh with a "
                f"{pipeline_axis!r} axis, got {mesh_axis_names}"
            )
    return cached_accum, acc_dt


def no_update_mask(params):
    """``params``-shaped booleans, False at the NO_UPDATE_LEAVES; None where the
    tree has none (every model but one with a sigmoid router)."""
    mask = jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in NO_UPDATE_LEAVES, params
    )
    return None if all(jax.tree.leaves(mask)) else mask


def _dropless_text(model):
    """The text configuration of a model whose text tower routes without
    dropping (``moe_router="sigmoid"``), else None."""
    t = getattr(getattr(model, "cfg", None), "text", None)
    return t if t is not None and t.moe_experts and t.moe_router == "sigmoid" else None


def _recorded_text(model):
    """The text configuration of a model whose step keeps a ``stack_record``:
    a dropless routed stack, one of windowed chunk attention, one with a
    state-space layer, of one-sub-layer layers or with attention heads of their
    own sizes; else None."""
    t = getattr(getattr(model, "cfg", None), "text", None)
    if t is None:
        return None
    recorded = (
        _dropless_text(model) is not None or {"eva", "ssm"} & set(t.mixers) or t.sublayers == "single"
        or t.num_kv_heads or t.head_dim
    )
    return t if recorded else None


def stack_record_of(t, tokens_shape) -> dict:
    """What a step with a dropless mixed text stack, a stack of windowed chunk
    attention, one with state-space layers or one of one-sub-layer layers runs,
    from shapes alone (``step.stack_record``, read by obs/attribution.py
    mixed_stack)."""
    routed = [bool(t.moe_experts) and i >= t.leading_dense_layers for i in range(t.depth)]
    mixers = t.mixers or ("attn",) * t.depth
    tokens = math.prod(tokens_shape)
    windows = t.attn_windows or (0,) * len(mixers)
    # An attention layer with a window shorter than the sequence is a kind of its own.
    named = [f"window_{m}" if 0 < w < tokens_shape[-1] else m for m, w in zip(mixers, windows)]
    record = {
        # A one-sub-layer layer is its one kind; a pair is mixer+mlp or mixer+moe.
        "layer_kinds": named if t.sublayers == "single"
        else [f"{m}+{'moe' if r else 'mlp'}" for m, r in zip(named, routed)],
        "tokens_per_microbatch": tokens,
    }
    if t.moe_experts:
        held = t.moe_experts_held or t.moe_experts
        record.update({
            "experts_held": held, "experts_total": t.moe_experts,
            "experts_per_token": t.moe_num_selected,
            "expected_local_assignments_per_token": t.moe_num_selected * held / t.moe_experts,
            # The sort's rows, the true worst case: every token picks held experts only.
            "dispatch_rows_bound": tokens * t.moe_num_selected,
        })
    if "kda" in mixers:
        from distributed_sigmoid_loss_tpu.models.mixers import CHUNK
        from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import delta_rule_core, short_conv_core

        # By the rule KdaMixer's call runs by: which core each delta-rule layer takes
        # ("kernel" / "chunked"), where its per-head norms run ("kernel" / "xla") and
        # the rows, heads and chunks of a call.
        rows, length = tokens_shape
        core = delta_rule_core(rows, length, t.num_heads, t.kda_head_dim, t.kda_head_dim, t.dtype, CHUNK)
        record["kda_core"] = {i: dict(core) for i, m in enumerate(mixers) if m == "kda"}
        # By the rule short_conv_silu runs by: the form the q, k and v branches' convolution takes
        # ("kernel": the Pallas pair short_conv_fwd / short_conv_bwd; "xla") and a program's tile.
        conv = short_conv_core(length, t.num_heads * t.kda_head_dim, t.kda_conv_size, t.dtype)
        record["short_conv"] = {i: [dict(conv)] for i, m in enumerate(mixers) if m == "kda"}
    if "mla" in mixers:
        from distributed_sigmoid_loss_tpu.models.mixers import latent_attention_core

        # By the rule LatentAttention's call runs by: the query latent, what is
        # rotated, and what ``latent_attention_core`` says of this length and these
        # head sizes: the core each latent-attention layer takes ("kernel": the
        # repo's Pallas pair on (b, s, h x d); "flash": the library's blocked
        # kernel; "dense": XLA), the query/key and value head sizes it runs at
        # (``core_head_dim``, ``core_v_dim``), zero-padded to or not, the tokens a
        # block of a fused core (``block``) and the sequence with the zero rows
        # that fill its last block (``core_tokens``).
        dqk = t.mla_qk_nope_dim + t.mla_qk_shared_dim
        rotated = t.pos == "rope"
        sizes = latent_attention_core(t.attn_impl, t.dtype, tokens_shape[-1], dqk, t.mla_v_dim)
        made_of = {
            "q_rank": t.mla_q_rank, "kv_rank": t.mla_kv_rank,
            "rotated_dim": t.mla_qk_shared_dim if rotated else 0,
            "rope_theta": t.rope_theta if rotated else None,
            "qk_dim": dqk, "v_dim": t.mla_v_dim,
            **sizes,
        }
        record["mla"] = {i: dict(made_of) for i, m in enumerate(mixers) if m == "mla"}
    if "ssm" in mixers:
        from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import short_conv_core
        from distributed_sigmoid_loss_tpu.ops.ssm import ssm_core

        # What SsmMixer's call runs (ops/ssm.py ssm_core, kernel or chunked): the core of each
        # state-space layer, its chunk, the chunks a sequence, the rows a pass, heads,
        # groups, head size and state, and the bytes a differentiated call keeps.
        rows, length = tokens_shape
        core = ssm_core(rows, length, t.ssm_num_heads, t.ssm_head_dim, t.ssm_groups, t.ssm_state, t.dtype, t.ssm_chunk)
        record["ssm"] = {i: dict(core) for i, m in enumerate(mixers) if m == "ssm"}
        # The convolution's form and tile, as above, at its two widths: x', and B and C.
        convs = [short_conv_core(length, width, t.ssm_conv_size, t.dtype)
                 for width in (t.ssm_num_heads * t.ssm_head_dim, t.ssm_groups * t.ssm_state)]
        record.setdefault("short_conv", {}).update({i: [dict(c) for c in convs] for i, m in enumerate(mixers) if m == "ssm"})
    if "attn" in mixers and (t.num_kv_heads or t.head_dim or any(windows) or t.attn_qk_norm or t.attn_gate):
        from distributed_sigmoid_loss_tpu.models.transformer import _dtype, attention_core

        # By the rule Attention's call runs by: the core each attention layer with head
        # sizes or options of its own takes, whether grouped keys and values are repeated
        # for it, its window (None: a full layer), the block pairs the kernel pair visits
        # and the pairs the softmax admits for a head, whether it rotates q and k, norms
        # their heads and gates its output.
        def made_of(window):
            sizes = attention_core(
                t.attn_impl, _dtype(t.dtype), tokens_shape[-1], t.num_heads, t.num_kv_heads or t.num_heads,
                t.head_dim or t.width // t.num_heads, t.causal, window=window,
            )
            rotated = t.pos == "rope" and (t.rope_layers == "all" or bool(window))
            return {**sizes, "rotated": rotated, "qk_norm": t.attn_qk_norm, "gated": t.attn_gate}

        record["attn"] = {i: made_of(w) for i, (m, w) in enumerate(zip(mixers, windows)) if m == "attn"}
    if "eva" in mixers:
        from distributed_sigmoid_loss_tpu.models.mixers import eva_attention_core
        from distributed_sigmoid_loss_tpu.models.text import layer_specs

        # By the rule EvaAttention's call runs by: the core each layer takes ("kernel":
        # the Pallas pair on (b, s, h x d); "dense": XLA), the tokens a block of the
        # kernel, the windows and the summaries a sequence; and whether the layers
        # are one scanned stack (the accumulator then rides the backward layer loop).
        sizes = eva_attention_core(
            t.attn_impl, t.dtype, tokens_shape[-1], t.eva_window, t.eva_chunk, t.width // t.num_heads
        )
        made_of = {"window": t.eva_window, "chunk": t.eva_chunk, "rope_theta": t.rope_theta, **sizes}
        record["eva"] = {i: dict(made_of) for i, m in enumerate(mixers) if m == "eva"}
        record["scanned"] = bool(t.scan_layers) and len(set(layer_specs(t))) == 1  # Encoder's rule: like layers
    return record


def _route_load(variables) -> dict:
    """The sown ``moe_load`` of every dropless layer (models/moe.py) as the
    metrics line's counters: assignments to experts held here, the fullest and
    the mean held expert's tokens, assignments that did not run (always 0)."""
    leaves = [
        (getattr(path[-1], "key", None), leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(variables.get("intermediates", {}))
        if any(getattr(k, "key", None) == "moe_load" for k in path)
    ]
    tokens = jnp.stack([x for k, x in leaves if k == "tokens"]).astype(jnp.float32)
    return {
        "moe_local_assignments": tokens.sum(),
        "moe_max_expert_tokens": tokens.max(),
        "moe_mean_expert_tokens": tokens.mean(),
        "moe_dropped_tokens": sum(x for k, x in leaves if k == "dropped").astype(jnp.float32),
    }


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def accum_zeros(params, acc_dt):
    """Zeroed gradient accumulator in ``acc_dt`` (None = param dtype)."""
    with jax.named_scope(ACCUM_SCOPE):
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dt or p.dtype), params
        )


def accum_add(acc, g):
    """Upcast-add-round: the sum itself stays f32 per microstep even when the
    carried accumulator is bf16 — THE bf16-accumulator rounding contract
    (tests/test_train_step.py::test_bf16_accumulator_tracks_f32)."""
    with jax.named_scope(ACCUM_SCOPE):
        return jax.tree.map(
            lambda a, g_: (a.astype(g_.dtype) + g_).astype(a.dtype), acc, g
        )


def accum_finish(acc, params, scale=None):
    """Back to param dtype, optionally divided by ``scale`` (the microstep
    count, when the carried value is a sum rather than a mean)."""
    with jax.named_scope(ACCUM_SCOPE):
        return jax.tree.map(
            lambda a, p: (
                a.astype(p.dtype) / scale if scale else a.astype(p.dtype)
            ),
            acc, params,
        )


def health_metrics(loss, lp, grads, params, prev_params) -> dict:
    """The metrics every step flavor reports (the metrics-line contract must
    not differ per step mode): loss, temperature, bias and the health scalars
    obs/health.py's watchdog reads — ``grad_norm``, ``param_norm`` and the
    update-to-param ratio. The per-leaf diff is transient (XLA fuses it into
    the norm reduction) and the norms are scalar reductions: the cheap in-step
    tier, read off the metrics line without any extra device sync."""
    with jax.named_scope(STEP_METRICS_SCOPE):
        param_norm = optax.global_norm(params)
        update_norm = optax.global_norm(
            jax.tree.map(lambda n, o: n - o, params, prev_params)
        )
        return {
            "loss": loss,
            "t": jnp.exp(lp["t_prime"]),
            "bias": lp["bias"],
            "grad_norm": optax.global_norm(grads),
            "param_norm": param_norm,
            "update_ratio": update_norm / (param_norm + 1e-12),
        }


def run_gradcache(
    model, params, micro, island, accum_steps, acc_dt, moe_aux_weight=None,
    embed_dtype=None,
):
    """THE GradCache recipe (Gao et al. 2021), shared by the regular and
    compressed steps so the derivation cannot drift between them.

    ``micro``: dict of (M, mb, ...) arrays. ``island(zis, zts, t', b)`` is
    the caller's full-table loss (shard_map'd stacked loss in the regular
    step; the raw per-shard loss inside the compressed step's shard_map).
    Returns ``(loss, lp, mean_aux, grads)``; ``loss`` excludes the aux term
    (the caller decides whether to add it for reporting).

    Pass 1 scans embeddings only (one microbatch of activations live at a
    time; Z is (M, mb, d) f32 — megabytes). The island runs ONCE for the
    loss value + dL/dZ + direct t_prime/bias grads. Pass 2 re-scans with the
    surrogate ``<z_m, stop_grad(dL/dz_m)>`` (+ the direct loss-param terms
    and the MoE aux, each 1/M per microbatch so their totals land once):
    d(surrogate)/dparams sums to the EXACT full-batch gradient — no /M on
    the z terms, dL/dZ already carries the scale.

    ``embed_dtype`` (e.g. ``"bfloat16"``) stores the stashed embedding tables
    in that dtype: the island's matmuls read bf16 operands (the MXU's native
    gear) and the resident stash halves. The loss value and dL/dZ then carry
    bf16 input rounding (~2^-9 relative on unit-norm embeddings) — the pass-2
    parameter gradients stay exact w.r.t. those cotangents. Default None
    keeps the f32 exactness-oracle contract.
    """

    def embed(_, mb):
        zi, zt, lp_ = model.apply({"params": params}, mb["images"], mb["tokens"])
        if embed_dtype is not None:
            zi = zi.astype(embed_dtype)
            zt = zt.astype(embed_dtype)
        return None, (zi, zt, lp_)

    _, (zis, zts, lps) = lax.scan(embed, None, micro)
    lp = jax.tree.map(lambda x: x[-1], lps)

    with jax.named_scope(LOSS_ISLAND_SCOPE):
        loss, island_grads = jax.value_and_grad(island, argnums=(0, 1, 2, 3))(
            zis, zts, lp["t_prime"], lp["bias"]
        )
    g_zis, g_zts, g_tp, g_bias = jax.tree.map(lax.stop_gradient, island_grads)

    def surrogate(p, mb, g_zi, g_zt):
        if moe_aux_weight is None:
            zi, zt, lp_ = model.apply({"params": p}, mb["images"], mb["tokens"])
            aux_ = jnp.zeros(())
        else:
            (zi, zt, lp_), variables = model.apply(
                {"params": p}, mb["images"], mb["tokens"],
                mutable=["intermediates"],
            )
            aux_ = _mean_moe_aux(variables)
        s = jnp.vdot(zi, g_zi) + jnp.vdot(zt, g_zt)
        s = s + (
            jnp.vdot(lp_["t_prime"], g_tp) + jnp.vdot(lp_["bias"], g_bias)
        ) / accum_steps
        if moe_aux_weight is not None:
            s = s + moe_aux_weight * aux_ / accum_steps
        return s, aux_

    def body(grad_sum, scanned):
        mb, g_zi, g_zt = scanned
        (_, aux_), g = jax.value_and_grad(surrogate, has_aux=True)(
            params, mb, g_zi, g_zt
        )
        return accum_add(grad_sum, g), aux_

    grads, auxs = lax.scan(
        body, accum_zeros(params, acc_dt), (micro, g_zis, g_zts)
    )
    return loss, lp, jnp.mean(auxs), accum_finish(grads, params)


def _mean_moe_aux(variables) -> jax.Array:
    """Mean over every sown router aux scalar (scanned encoders sow one
    (depth,) leaf per tower; unrolled ones sow per-layer scalars). Filter by
    the sow name so other intermediates never leak into the objective."""
    flat = jax.tree_util.tree_flatten_with_path(
        variables.get("intermediates", {})
    )[0]
    leaves = [
        leaf
        for path, leaf in flat
        if any(getattr(k, "key", None) == "moe_aux_loss" for k in path)
    ]
    if not leaves:
        raise ValueError(
            "moe_aux_weight is set but the model sowed no moe_aux_loss — "
            "enable moe_experts on the tower configs"
        )
    return sum(jnp.sum(leaf) for leaf in leaves) / sum(leaf.size for leaf in leaves)


def _precision(name: str):
    return {"highest": lax.Precision.HIGHEST, "default": lax.Precision.DEFAULT}[name]


def _filter_spec(spec: P, mesh: Mesh) -> P:
    """Drop annotation axes the mesh doesn't have (e.g. ``tp`` on a pure-dp mesh), so
    the same model runs on any mesh shape."""
    names = set(mesh.axis_names)

    def keep(p):
        if p is None:
            return None
        if isinstance(p, tuple):
            kept = tuple(a for a in p if a in names)
            return kept if kept else None
        return p if p in names else None

    return P(*(keep(p) for p in spec))


def param_shardings(mesh: Mesh, abstract_params) -> Any:
    """NamedShardings from the ``nn.with_partitioning`` metadata of an abstract
    (eval_shape'd, still boxed) param tree."""
    specs = nn.get_partition_spec(abstract_params)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _filter_spec(s, mesh)),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _zero1_spec(shape, dp: int, axis_name: str) -> P:
    """ZeRO-1 placement for one optimizer-state leaf: shard the leading dim over
    the data axis when it divides evenly, replicate otherwise (scalars, probes,
    position embeddings). Thin alias over the shared
    ``parallel.update_shard.update_shard_spec`` placement rule (mode
    ``"zero1"``) — kept because the spec is part of the zero1 checkpoint-era
    API surface."""
    return update_shard_spec(shape, dp, axis_name, mode="zero1")


def zero1_constrain(opt_state: Any, mesh: Mesh, axis_name: str = "dp") -> Any:
    """Constrain every optimizer-state leaf to its ZeRO-1 sharding.

    Used inside jit: XLA propagates the constraint backward, so the adam moment
    update runs on dp-sharded slices (the grad feeding it becomes a
    reduce-scatter) and the param delta is all-gathered — optimizer memory drops
    from ``3x params`` replicated to ``params + 2x params / dp_size`` per chip,
    which is what makes ~1B-param towers fit v5e HBM. On meshes that also carry
    ``tp``, moments of tp-sharded kernels are re-laid-out dp-wise — still
    correct, with extra resharding comm; the target is the large pure-dp case.

    Deprecated alias for ``constrain_update_sharding(..., mode="zero1")``
    (parallel/update_shard.py) — the one shared placement helper both step
    builders now derive their sharding from; ``update_sharding="full"`` grows
    this into the reduce-scatter / shard-optimizer / gather-publish scheme of
    arXiv:2004.13336.
    """
    return constrain_update_sharding(opt_state, mesh, axis_name, mode="zero1")


def _with_pp_shardings(
    abstract_unboxed: Any, shardings: Any, mesh: Mesh, pp_axis: str
) -> Any:
    """Shard the scanned block stacks over ``pp`` at rest.

    With pipeline parallelism each chip should HOLD only its stage's layer
    params — that is the memory story of pp. The scanned block leaves are
    ``(depth, ...)``; sharding dim 0 over ``pp`` gives stage s the contiguous
    ``depth/S`` chunk that :func:`parallel.pipeline.stack_stage_params`'s
    stage-major reshape assigns it, so gpipe's ``in_specs=P("pp")`` is a
    layout no-op instead of a per-step reshard of replicated weights.
    """
    size = dict(mesh.shape)[pp_axis]

    def fix(path, a, s):
        if is_pp_block_leaf(path, a.shape, size):
            rest = tuple(s.spec)[1:]
            return NamedSharding(mesh, P(pp_axis, *rest))
        return s

    return jax.tree_util.tree_map_with_path(fix, abstract_unboxed, shardings)


def init_params(
    rng: jax.Array, model: nn.Module, sample_batch: dict, mesh: Mesh,
    zeros: bool = False, pp_axis: str | None = None,
) -> Any:
    """Initialize model params directly sharded onto the mesh (no host
    round-trip) — the forward-only half of :func:`create_train_state`, for eval
    paths that never need optimizer slots.

    ``zeros=True`` skips the random initializers and fills every leaf with
    zeros — same shapes/dtypes/shardings at a memset's cost. For checkpoint
    *restore targets* (eval, resume) the values are immediately overwritten,
    and running the real init there costs minutes of host RNG on large towers.

    ``pp_axis`` shards the scanned block stacks' leading (depth) axis over
    that mesh axis — pair with ``make_train_step(pp_microbatches=...)``.
    """

    # Initializers read shapes only. Zeros built INSIDE the jit stand in for
    # the sample: closing over the concrete batch would bake a global-batch
    # sized constant (77 MB at B/16 x 128) into the init program.
    images, tokens = (
        (sample_batch[k].shape, sample_batch[k].dtype)
        for k in ("images", "tokens")
    )

    def init_fn(rng):
        return model.init(rng, jnp.zeros(*images), jnp.zeros(*tokens))["params"]

    abstract = jax.eval_shape(init_fn, rng)
    shardings = param_shardings(mesh, abstract)
    # Unbox the Partitioned metadata: shardings now carry the placement info.
    unboxed_shardings = nn.meta.unbox(shardings)
    # Strip the metadata boxes WITHOUT nn.meta.unbox: under an ambient mesh
    # (jax.set_mesh) flax's unbox() applies an EAGER with_sharding_constraint,
    # which rejects abstract (eval_shape'd) leaves.
    abstract_unboxed = jax.tree.map(
        lambda x: x.value if isinstance(x, nn.meta.AxisMetadata) else x,
        abstract,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )
    if pp_axis is not None:
        unboxed_shardings = _with_pp_shardings(
            abstract_unboxed, unboxed_shardings, mesh, pp_axis
        )
    if zeros:
        return jax.jit(
            lambda: jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), abstract_unboxed
            ),
            out_shardings=unboxed_shardings,
        )()
    params = jax.jit(
        lambda r: nn.meta.unbox(init_fn(r)), out_shardings=unboxed_shardings
    )(rng)
    text = getattr(getattr(model, "cfg", None), "text", None)
    if getattr(text, "moe_balanced_init", False):
        ids = sample_batch["tokens"]
        if not hasattr(ids, "__array__"):  # shapes alone: uniform ids stand in
            ids = jax.random.randint(jax.random.fold_in(rng, 1), tokens[0], 0, text.vocab_size, jnp.int32)
        params = balance_routers(model, params, ids, mesh, unboxed_shardings)
    return params


def balance_routers(model: nn.Module, params: Any, ids, mesh: Mesh, shardings: Any) -> Any:
    """``params`` with every sigmoid router's selection bias set so that its
    experts are chosen evenly (``TextConfig.moe_balanced_init``): one forward
    pass of the text tower over the token ids ``ids``, in which each routed
    layer, in its turn, finds its bias from the tokens that reach it
    (models/moe.py balanced_select_bias) and routes by it, so a later layer
    balances on what the balanced earlier ones hand it. ``init_params`` hands it
    the sample batch's own ids where the batch carries them (the trainer's first
    batch: the routers start balanced on the data) and uniform ids drawn from its
    key where it carries shapes. How far the balance holds on ANOTHER batch is the
    stack's: where every token's router input is its own (Nemotron's), to a few
    percent of the same count; where a norm after each attention hands a
    sequence's tokens a common part (sandwich norms at random weights), a
    sequence loads single experts several times over (PERF.md section 6, PR 47)."""
    from flax import traverse_util

    from distributed_sigmoid_loss_tpu.models.moe import BALANCE

    text = model.cfg.text
    if text.moe_router != "sigmoid" or not text.moe_experts:
        raise ValueError(
            f"moe_balanced_init=True sets the selection bias of sigmoid-routed layers: "
            f"moe_router={text.moe_router!r}, moe_experts={text.moe_experts} has none"
        )

    def balanced(params, ids):
        with trace_on(mesh):
            _, sown = model.apply({"params": params}, ids, method="encode_text", mutable=[BALANCE])
        flat = traverse_util.flatten_dict(params)
        for path, (bias,) in traverse_util.flatten_dict(sown[BALANCE]).items():
            flat[path] = bias
        return traverse_util.unflatten_dict(flat)

    return jax.jit(balanced, out_shardings=shardings, donate_argnums=0)(params, ids)


def create_train_state(
    rng: jax.Array,
    model: nn.Module,
    tx: optax.GradientTransformation,
    sample_batch: dict,
    mesh: Mesh,
    zero1: bool = False,
    axis_name: str = "dp",
    ema: bool = False,
    zeros: bool = False,
    pp_axis: str | None = None,
    update_sharding: str = "",
) -> TrainState:
    """Initialize a full train state, every leaf committed to the mesh.

    ``update_sharding`` places the optimizer state per the shared
    parallel/update_shard.py rule: ``"zero1"`` shards exactly-divisible
    leaves over ``axis_name`` (``zero1=True`` is the deprecated alias),
    ``"full"`` shards every leaf with ``shape[0] >= W`` (ragged tails
    padded) — pass the same mode to :func:`make_train_step` /
    ``make_compressed_train_step`` so the step keeps the placement.
    ``ema=True`` adds an EMA copy of the params (pair with ``ema_decay`` on
    :func:`make_train_step`). ``zeros=True`` builds a zero-filled state (same
    structure/shardings, no random init) — for checkpoint restore targets.
    ``pp_axis`` shards the block stacks over that axis (see :func:`init_params`);
    adam moments inherit the placement through the jitted create.
    """
    mode = resolve_update_sharding(update_sharding, zero1)
    params = init_params(rng, model, sample_batch, mesh, zeros=zeros, pp_axis=pp_axis)

    # Build the optimizer state under jit too, so every leaf (adam moments follow the
    # param shardings — or their update-shard placement — and scalar counters
    # replicate) is committed to the mesh — required for sharding-stable
    # checkpoint restore.
    frozen = no_update_mask(params)
    if frozen is not None:
        # Only the other leaves reach the optimizer: a masked-out leaf has no
        # state, and its update is its gradient, which is zero.
        tx = optax.masked(tx, frozen)

    def create(p):
        state = TrainState.create(apply_fn=model.apply, params=p, tx=tx)
        if mode != "off":
            state = state.replace(
                opt_state=constrain_update_sharding(
                    state.opt_state, mesh, axis_name, mode
                )
            )
        if ema:
            from distributed_sigmoid_loss_tpu.train.ema import init_ema

            state = state.replace(ema=init_ema(p))
        return state

    return jax.jit(create)(params)


def make_train_step(
    model: nn.Module,
    mesh: Mesh,
    loss_cfg: LossConfig = LossConfig(),
    accum_steps: int = 1,
    zero1: bool = False,
    ema_decay: float | None = None,
    moe_aux_weight: float | None = None,
    pp_microbatches: int = 0,
    accum_negatives: str = "local",
    accum_dtype: str | None = None,
    gradcache_embed_dtype: str | None = None,
    update_sharding: str = "",
):
    """Build the jitted ``(state, batch) -> (state, metrics)`` step.

    ``batch`` is a dict of global arrays ``images`` (b, H, W, 3) and ``tokens``
    (b, L) sharded over the ``dp`` mesh axis.

    ``accum_steps > 1`` splits the batch into that many microbatches, runs them
    through a ``lax.scan``, and applies the averaged gradients once — the way to
    reach e.g. the 32k-global north star on fewer chips. Contrastive caveat
    (inherent to accumulation, same as open_clip without its re-encoding trick):
    each microbatch contrasts only against its own texts, so the negative set per
    loss term is ``global/accum_steps``, not ``global`` — UNLESS
    ``accum_negatives="global"`` (below). Where a tower's layers are scanned
    (and dense, and run once) its stack's weight gradients are added into the
    accumulator inside the backward layer loop, in the weight-gradient
    matmul's fusion, and no gradient stack is made for them (``GRAD_SINK``,
    models/transformer.py Encoder); every other leaf, and every leaf of an
    unrolled or looped tower, of the pp towers and of the "global" path, is
    added by ``accum_add`` after the microbatch's backward pass. Same adds,
    same roundings; ``step.accum_record`` says how many parameter bytes went
    which way.

    ``accum_negatives="global"`` (with ``accum_steps > 1``) computes the EXACT
    full-batch loss under accumulation, GradCache-style (Gao et al. 2021;
    open_clip's re-encoding trick): pass 1 scans the microbatches for
    embeddings only (no activation storage beyond one microbatch); the loss +
    its embedding gradients are computed ONCE on the full (global_b, d)
    embedding tables (tiny: 32k x 512 f32 = 67 MB); pass 2 re-scans with the
    surrogate objective ``<z_m, stop_grad(dL/dz_m)>`` whose parameter gradient
    is exactly the full-batch term. Grad oracle: identical (rtol 1e-5) to the
    unaccumulated big-batch step — the property "local" loses. Cost: one extra
    forward per microbatch (~30% step time at save_hot remat ratios).

    ``update_sharding`` ("off" | "zero1" | "full"; ``zero1=True`` is the
    deprecated alias for "zero1") places the weight update per
    parallel/update_shard.py. "zero1" keeps the optimizer state sharded over
    ``dp`` (see :func:`zero1_constrain`). "full" is the automatic
    cross-replica update sharding of arXiv:2004.13336: the gradients are
    constrained to their 1/W shard BEFORE the optax update (XLA's dp
    all-reduce becomes a reduce-scatter), the optimizer math and state live
    on the shard, and one all-gather publishes the updated params back at
    their model shardings (captured from the first concrete state the step
    sees). Requires a dp axis of size > 1; create the state with the same
    mode. Numerics are those of the unsharded step (the constraints move
    placement, not math — clip_by_global_norm and factored adafactor stats
    reduce over the same global tensors).

    ``ema_decay`` maintains the params' exponential moving average in
    ``state.ema`` (decay warmed up per ``ema_decay_schedule``); create the state
    with ``ema=True``.

    ``moe_aux_weight`` (use with ``moe_experts > 0`` towers) adds that weight
    times the mean of the routers' sown load-balancing losses (models/moe.py) to
    the task loss; without it MoE still trains but routing may collapse onto few
    experts.

    ``accum_dtype`` (e.g. ``"bfloat16"``, with ``accum_steps > 1``) stores the
    microbatch-scan gradient accumulator in that dtype instead of the param
    dtype (f32). The adds still run in f32 (the accumulator is upcast, summed
    with the microstep grad, and rounded back), so the only loss is the
    per-microstep bf16 round-off — a ~``sqrt(M) * 2^-9`` relative random walk
    on the sum, far below gradient noise at M=16. What it buys: the
    params-sized accumulator's read+write per microstep halves (the HBM
    traffic behind the accumulation tax: PERF.md's accum_ms), and its
    resident footprint halves — the lever that lets larger microbatches fit.
    Parity oracles keep the f32 default (tests/test_train_step.py).

    ``pp_microbatches > 0`` runs both towers' block stacks through the GPipe
    schedule over the mesh's ``pp`` axis with that many microbatches per step
    (parallel/pp_towers.py) — create the state with the matching
    ``pp_axis="pp"`` so stage params live sharded. Composes with dp (batch
    stays dp-sharded) and with ``accum_steps`` (each accumulation microbatch is
    itself pipelined); dense towers only.

    ``gradcache_embed_dtype`` (e.g. ``"bfloat16"``, with
    ``accum_negatives="global"``) stores the GradCache embedding stash in that
    dtype — see :func:`run_gradcache`; attacks the exact-negatives path's
    bandwidth share of its tax (~21% before PR 22) at the cost of bf16
    rounding on the island's loss/cotangents.
    """
    validate_trainable_quant(model)
    axis = loss_cfg.axis_name
    update_mode = resolve_update_sharding(update_sharding, zero1)
    if update_mode == "full" and dict(mesh.shape).get(axis, 1) < 2:
        # Environment refusal (mesh instance, not config space): a 1-wide dp
        # axis has nothing to scatter over — "full" would silently degrade
        # to a replicated update while claiming the sharded-memory story.
        raise ValueError(
            "update_sharding='full' requires a dp axis of size > 1, got "
            f"{axis!r}={dict(mesh.shape).get(axis, 1)} on mesh "
            f"{dict(mesh.shape)}"
        )
    precision = _precision(loss_cfg.precision)
    # The model's `bias` param plays no role under family="softmax" (zero
    # grad); the uniform per-shard signature keeps one param tree per model.
    from distributed_sigmoid_loss_tpu.parallel.api import make_per_shard_loss

    per_shard = make_per_shard_loss(
        family=loss_cfg.family, variant=loss_cfg.variant, axis_name=axis,
        bidir=loss_cfg.bidir, precision=precision,
        use_pallas=loss_cfg.use_pallas, loss_impl=loss_cfg.loss_impl,
        ring_overlap=loss_cfg.ring_overlap,
        quant=resolve_loss_quant(model, loss_cfg),
    )
    # See parallel/api.py: the pallas interpreter and the chunked scan's
    # replicated-init carry both need the replication check off.
    loss_check_vma = not (loss_cfg.use_pallas or loss_cfg.loss_impl == "chunked")

    # Embeddings enter the loss island sharded over dp, replicated over other axes.
    emb_spec = P(axis)

    def shard_loss(zimg, ztxt, t_prime, bias):
        return lax.pmean(per_shard(zimg, ztxt, t_prime, bias), axis)

    sharded_loss = jax.shard_map(
        shard_loss,
        mesh=mesh,
        in_specs=(emb_spec, emb_spec, P(), P()),
        out_specs=P(),
        check_vma=loss_check_vma,
    )
    if loss_cfg.loss_impl == "chunked" or loss_cfg.use_pallas:
        # Grads of the chunk scan and of the pallas custom_vjp flow through a
        # JITTED shard_map (the arrangement make_sharded_loss_fn's jit=True
        # gives standalone callers); jit-in-jit is a free pjit inline.
        sharded_loss = jax.jit(sharded_loss)

    cached_accum, acc_dt = validate_step_args(
        accum_steps=accum_steps,
        accum_dtype=accum_dtype,
        accum_negatives=accum_negatives,
        pp_microbatches=pp_microbatches,
        zero1=zero1,
        moe_aux_weight=moe_aux_weight,
        gradcache_embed_dtype=gradcache_embed_dtype,
        mesh_axis_names=mesh.axis_names,
        update_sharding=update_sharding,
    )
    if pp_microbatches:
        from distributed_sigmoid_loss_tpu.parallel.pipeline import pipeline_axis
        from distributed_sigmoid_loss_tpu.parallel.pp_towers import (
            siglip_forward_pp,
            validate_pp_tower,
        )

        # Fail at build time, not first step: the model must expose its config
        # (SigLIP does) and both towers must be pipelineable.
        pp_stages = dict(mesh.shape)[pipeline_axis]
        validate_pp_tower(model.cfg.vision, pp_stages, "vision")
        validate_pp_tower(model.cfg.text, pp_stages, "text")

    dropless = _dropless_text(model)
    recorded = _recorded_text(model)
    stack_record: dict = {}

    def loss_fn(params, batch, sink=None):
        """``loss, (lp, aux, the sink as the model hands it back, the routed
        layers' load counters)``."""
        variables, mutable = {"params": params}, []
        if sink:
            variables[GRAD_SINK] = sink
            mutable.append(GRAD_SINK)
        if moe_aux_weight is not None or dropless is not None:
            mutable.append("intermediates")
        if recorded is not None:
            stack_record.update(stack_record_of(recorded, batch["tokens"].shape))
        updated = {}
        if pp_microbatches:
            zimg, ztxt, lp = siglip_forward_pp(
                model.cfg, params, batch["images"], batch["tokens"],
                mesh=mesh, num_microbatches=pp_microbatches,
            )
        elif not mutable:
            zimg, ztxt, lp = model.apply(
                variables, batch["images"], batch["tokens"]
            )
        else:
            (zimg, ztxt, lp), updated = model.apply(
                variables, batch["images"], batch["tokens"], mutable=mutable
            )
        aux = jnp.zeros(()) if moe_aux_weight is None else _mean_moe_aux(updated)
        with jax.named_scope(LOSS_ISLAND_SCOPE):
            loss = sharded_loss(zimg, ztxt, lp["t_prime"], lp["bias"])
        if moe_aux_weight is not None:
            loss = loss + moe_aux_weight * aux
        load = _route_load(updated) if dropless is not None and not pp_microbatches else {}
        return loss, (lp, aux, updated.get(GRAD_SINK, {}), load)

    # accum_negatives="global": the stacked-embedding loss island. Each device
    # sees its LOCAL rows of every microbatch (M, mb/dp, d) and flattens them
    # locally (free reshape) — the per-shard loss + ring/all-gather machinery
    # then contrasts every image against every text GLOBALLY, exactly as the
    # unaccumulated step would. Pair alignment holds because zimg/ztxt are
    # stacked by the same microbatch split, and the pair-set sum is
    # permutation-invariant.
    def stacked_shard_loss(zis, zts, t_prime, bias):
        m, mb_local, d = zis.shape
        return lax.pmean(
            per_shard(
                zis.reshape(m * mb_local, d), zts.reshape(m * mb_local, d),
                t_prime, bias,
            ),
            axis,
        )

    stacked_loss = jax.shard_map(
        stacked_shard_loss,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(), P()),
        out_specs=P(),
        check_vma=loss_check_vma,
    )
    if loss_cfg.loss_impl == "chunked" or loss_cfg.use_pallas:
        stacked_loss = jax.jit(stacked_loss)  # same jitted-shard_map arrangement

    accum_record: dict = {}

    def grads_and_metrics_cached(params, batch):
        from distributed_sigmoid_loss_tpu.parallel.microbatch import (
            microbatch_split,
        )

        accum_record.update(layer_loop_bytes=0, accum_add_bytes=_tree_bytes(params))

        micro = jax.tree.map(
            lambda x: microbatch_split(x, accum_steps, mesh, axis, what="accum_steps"),
            batch,
        )
        loss, lp, mean_aux, grads = run_gradcache(
            model, params, micro, stacked_loss, accum_steps, acc_dt,
            moe_aux_weight=moe_aux_weight, embed_dtype=gradcache_embed_dtype,
        )
        if moe_aux_weight is not None:
            # The optimized objective includes the aux term; report the same
            # loss the other paths do (metrics, divergence check, A/B curves).
            loss = loss + moe_aux_weight * mean_aux
        return loss, lp, mean_aux, grads, {}

    def grads_and_metrics(params, batch):
        if cached_accum:
            return grads_and_metrics_cached(params, batch)
        if accum_steps == 1:
            (loss, (lp, aux, _, load)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, batch)
            return loss, lp, aux, grads, load

        # Interleaved per-device-chunk split (parallel/microbatch.py): the
        # reshuffle is layout-only, no cross-device all-to-all. Microbatch
        # composition is arbitrary for accumulation, so no inverse merge is
        # needed — semantically free.
        from distributed_sigmoid_loss_tpu.parallel.microbatch import (
            microbatch_split,
        )

        micro = jax.tree.map(
            lambda x: microbatch_split(x, accum_steps, mesh, axis, what="accum_steps"),
            batch
        )

        # Where layers are scanned (models/transformer.py Encoder) the stack's
        # accumulator goes through the model as GRAD_SINK: handed back by the
        # forward, seeded with itself as that output's cotangent, it comes out
        # of the backward layer loop with every layer's weight gradient added
        # to its row, on the weight-gradient matmul's result, and no gradient
        # stack is made. Every other leaf keeps accum_add. The pp towers never
        # see it.
        in_loop, outside = split_grad_sink(params)
        if pp_microbatches:
            in_loop, outside = {}, params
        accum_record.update(
            layer_loop_bytes=_tree_bytes(in_loop), accum_add_bytes=_tree_bytes(outside)
        )
        in_loop, outside = (accum_zeros(t, acc_dt) for t in (in_loop, outside))

        def body(carry, mb):
            loss_sum, in_loop, outside = carry

            def through_sink(p, sink):
                loss, (lp, aux, sink, load) = loss_fn(p, mb, sink)
                return (loss, sink), (lp, aux, load)

            (loss, _), grads_of, (lp, aux, load) = jax.vjp(
                through_sink, params, in_loop, has_aux=True
            )
            grads, in_loop = grads_of((jnp.ones_like(loss), in_loop))
            if in_loop:
                grads = split_grad_sink(grads)[1]
            carry = (loss_sum + loss, in_loop, accum_add(outside, grads))
            return carry, (lp, aux, load)

        (loss_sum, in_loop, outside), (lps, auxs, loads) = lax.scan(
            body, (jnp.zeros(()), in_loop, outside), micro
        )
        lp = jax.tree.map(lambda x: x[-1], lps)
        grads = accum_finish(
            merge_grad_sink(in_loop, outside), params, scale=accum_steps
        )
        # Over the microbatches: counts add up, the fullest expert is the fullest
        # of any microbatch, the mean is the mean.
        over = {"moe_max_expert_tokens": jnp.max, "moe_mean_expert_tokens": jnp.mean}
        load = {k: over.get(k, jnp.sum)(v) for k, v in loads.items()}
        return loss_sum / accum_steps, lp, jnp.mean(auxs), grads, load

    def step(state: TrainState, batch: dict, param_out_shardings=None):
        # Traced on the mesh: the towers' fused attention kernels must know
        # which axes shard their operands (parallel/mesh.py trace_on).
        with trace_on(mesh):
            loss, lp, aux, grads, load = grads_and_metrics(state.params, batch)
        prev_step = state.step  # apply_gradients increments; EMA warmup wants
        prev_params = state.params  # update_ratio needs the pre-update tree
        # The shared update-shard recipe (parallel/update_shard.py): plain
        # apply under "off"; the historical opt-state re-pin under "zero1";
        # under "full" the grads are constrained to their 1/W shard first
        # (reduce-scatter), the optax math runs shard-local, and the params
        # are constrained back to their at-rest shardings (the one gather
        # publish). The 0-based update index is prev_step.
        state = apply_sharded_update(
            state, grads, mesh=mesh, axis_name=axis, mode=update_mode,
            param_shardings=param_out_shardings,
        )
        if ema_decay is not None:
            if state.ema is None:
                raise ValueError(
                    "ema_decay is set but state.ema is None — create the train "
                    "state with create_train_state(..., ema=True)"
                )
            from distributed_sigmoid_loss_tpu.train.ema import update_ema

            with jax.named_scope(OPTIMIZER_SCOPE):
                state = state.replace(
                    ema=update_ema(
                        state.ema, state.params, step=prev_step,
                        decay=ema_decay,
                    )
                )
        metrics = health_metrics(loss, lp, grads, state.params, prev_params)
        if moe_aux_weight is not None:
            metrics["moe_aux"] = aux
        metrics.update(load)
        return state, metrics

    batch_sharding = {
        "images": NamedSharding(mesh, P(axis)),
        "tokens": NamedSharding(mesh, P(axis)),
    }
    if update_mode != "full":
        jitted = jax.jit(step, donate_argnums=(0,))
        jitted.accum_record, jitted.stack_record = accum_record, stack_record
        return jitted, batch_sharding

    # Full mode: the publish constraint needs the params' at-rest shardings,
    # which only a CONCRETE state carries — capture them from the first call
    # and jit once. Abstract tracing (jaxpr audits run the step on
    # eval_shape states) captures KEEP sentinels and leaves the publish to
    # the compiler, which is fine trace-side. _cache_size proxies the inner
    # jit so the no-recompile pins keep one probe for every step flavor.
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
    sharded_step.accum_record, sharded_step.stack_record = accum_record, stack_record
    return sharded_step, batch_sharding


# The start-up spans of this file's boundaries (obs/spans.py), applied here at the
# file's end: the compile cache's key holds the source lines of every function jax
# traces above (utils/compile_cache.py), so none of them may move. jax's own trace /
# lower / compile spans of the two init programs fall inside `init_state` by
# containment; its self time is the programs running and the placement.
from distributed_sigmoid_loss_tpu.obs.spans import spanned as _spanned  # noqa: E402

init_params = _spanned("init_params")(init_params)
create_train_state = _spanned("init_state")(create_train_state)
make_train_step = _spanned("startup.step_builder")(make_train_step)
