"""Shared transformer core for both towers — designed for TPU from the start.

The reference has no model layer (its "towers" are toy Linears); the BASELINE.json
end-to-end target adds ViT-B/16 + text transformer. This core is built TPU-first:

- **MXU-friendly**: fused QKV projection (one big matmul), bf16 activations with fp32
  params, static shapes throughout.
- **Tensor parallelism**: weight kernels carry ``nn.with_partitioning`` annotations over
  the ``"tp"`` mesh axis — attention heads and MLP hidden dim are sharded, so under jit
  XLA inserts the all-reduces (Megatron-style column→row split) automatically.
- **Memory**: optional ``nn.remat`` per block (rematerialize activations in backward)
  and ``nn.scan`` over layers (constant compile time in depth).
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

# Mesh axis name used by tensor-parallel kernel annotations (parallel/mesh.py).
TP_AXIS = "tp"
# Mesh axis the batch is sharded over (parallel/mesh.py data_axis).
DP_AXIS = "dp"
# The variable collection in which an accumulating step (train/train_step.py)
# offers a scanned stack its slice of the gradient accumulator, and the step
# program's name for the accumulator's traffic (benchmark/scopes.py reads it).
GRAD_SINK = "grad_sink"
ACCUM_SCOPE = "accum"
# The program's name for the device time of ``Attention``'s single-device core: scores,
# softmax and values, whichever kernel or XLA form runs them (benchmark/scopes_nemotron.py).
ATTN_CORE_SCOPE = "attn_core"
# The same core in a layer with a window (a causal band), so that a profile tells the two kinds of
# layer of one stack apart, and the head norms and the sigmoid gate of a normed, gated
# ``Attention`` (benchmark/scopes_trinity.py).
WINDOW_CORE_SCOPE = "window_attn_core"
ATTN_GATE_SCOPE = "attn_gate"


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


def _dot_general(quant):
    """None = flax's default (lax.dot_general). ``"int8"`` (or legacy ``True``)
    injects the inference-only int8 dot; ``"int8_ste"`` the trainable
    straight-through variant (int8 forward, unquantized VJP — ops/quant.py)."""
    if not quant:
        return None
    from distributed_sigmoid_loss_tpu.ops.quant import (
        int8_dot_general,
        int8_dot_general_ste,
    )

    if quant == "int8_ste":
        return int8_dot_general_ste
    return int8_dot_general


def _fused_attention_per_shard(kernel, q, k, v, *more):
    """Run a fused (Mosaic) attention ``kernel`` on (b, s, h, dh) arrays (and
    ``more`` per-head operands, (b, s, h, ...)) that a surrounding ``jit`` may
    have sharded over several chips.

    A Mosaic kernel is opaque to the SPMD partitioner: where the trace's mesh
    (``jax.sharding.get_abstract_mesh()`` — the step builders trace under
    ``parallel.mesh.trace_on(mesh)``) has automatically partitioned axes
    spanning more than one device, lowering the bare call raises "Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in a
    shard_map" — the first thing the dp=4 train step did on a real four-chip
    host. Attention is independent per (row, head), so the split is exact:
    rows over ``dp``, heads over ``tp``, where those axes exist and divide;
    over every other axis the call is replicated, as the partitioner would
    have left it. On one device, with no mesh in the trace, or where every
    axis is already manual (inside the pp / compressed steps' ``shard_map``)
    the kernel is called directly.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if not auto or mesh.size == 1:
        return kernel(q, k, v, *more)

    def split(axis, n):
        return axis if axis in auto and n % mesh.shape[axis] == 0 else None

    spec = P(split(DP_AXIS, q.shape[0]), None, split(TP_AXIS, q.shape[2]), None)
    operands = (q, k, v, *more)
    return jax.shard_map(
        kernel, in_specs=tuple(P(*spec[:x.ndim]) for x in operands), out_specs=spec,
        axis_names=auto, check_vma=False,
    )(*operands)


def _remat_policy(name: str):
    """None = rematerialize everything (jax.checkpoint default)."""
    if name == "nothing":
        return None
    if name == "save_hot":
        # Save the two expensive-to-recompute intermediates (attention core output,
        # MLP hidden): backward recompute shrinks to qkv projections + layernorms +
        # elementwise gelu (~25% of forward instead of 100%), costing
        # b·s·(width + hidden) of HBM per layer.
        return jax.checkpoint_policies.save_only_these_names(
            "attn_core", "mlp_hidden"
        )
    if name == "save_all_hot":
        # save_hot plus q/k/v: backward recompute is layernorms + gelu only.
        return jax.checkpoint_policies.save_only_these_names(
            "attn_core", "mlp_hidden", "q_proj", "k_proj", "v_proj"
        )
    if name == "save_mlp":
        # The single biggest matmul output only — the low-memory selective option.
        return jax.checkpoint_policies.save_only_these_names("mlp_hidden")
    raise ValueError(f"unknown remat_policy: {name!r}")


@dataclasses.dataclass(frozen=True)
class BlockStyle:
    """What a block is made of: ``TextConfig``'s block options as the modules
    take them (utils/config.py says what each means). The defaults are the
    SigLIP block, which is all the image tower has."""

    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    sandwich_norm: bool = False
    mlp: str = "gelu"  # "gelu" | "swiglu" | "relu2"
    use_bias: bool = True
    rope_theta: float | None = None  # None = no rotary positions
    norm_eps: float = 1e-6
    norm_unit_offset: bool = False  # "rmsnorm" whose leaf is an offset from 1

    @classmethod
    def of(cls, cfg) -> "BlockStyle":
        return cls(
            norm=cfg.norm, sandwich_norm=cfg.sandwich_norm, mlp=cfg.mlp,
            use_bias=cfg.use_bias,
            rope_theta=cfg.rope_theta if cfg.pos == "rope" else None,
            norm_eps=cfg.norm_eps, norm_unit_offset=cfg.norm_unit_offset,
        )

    def make_norm(self, dtype, name: str) -> nn.Module:
        """Both kinds compute their statistics in float32, at ``norm_eps``."""
        if self.norm_unit_offset:
            if self.norm != "rmsnorm":
                raise ValueError(f"norm_unit_offset=True is an RMSNorm's, not built for norm={self.norm!r}")
            return OffsetRMSNorm(epsilon=self.norm_eps, dtype=dtype, name=name)
        if self.norm == "rmsnorm":
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=dtype, name=name)
        if self.norm == "layernorm":
            return nn.LayerNorm(epsilon=self.norm_eps, dtype=dtype, name=name)
        raise ValueError(f"unknown norm: {self.norm!r}")


class OffsetRMSNorm(nn.Module):
    """x rsqrt(mean(x^2) + eps) (1 + offset): an RMSNorm whose leaf is stored
    as an offset from 1, initially 0. The numbers at initialisation are
    ``nn.RMSNorm``'s; under weight decay the scale is pulled to 1, not to 0.
    Statistics and the scaling in float32, the result in ``dtype``."""

    epsilon: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        offset = self.param("offset", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + self.epsilon)
        return (normed * (1.0 + offset)).astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of a stack given layer by layer, as ``Block`` takes it
    (``models/text.py layer_specs`` makes them from ``TextConfig``): the token
    mixer with its module's own size fields, and what the MLP is where
    ``moe_experts > 0``. The default is the block every tower had."""

    mixer: str = "attn"  # "attn" | "kda" | "mla" | "eva" | "ssm" (models/mixers.py) | "none": no mixer
    # (name, value) pairs of KdaMixer / LatentAttention / EvaAttention / SsmMixer, or of Attention: its own
    # head sizes, its window, whether it rotates (``rope_theta``), the head norms and the gate
    mixer_fields: tuple = ()
    dense_mlp: bool = False  # a leading layer keeps the dense MLP
    experts_fields: tuple = ()  # (name, value) pairs of SharedExpertMoe; none = MoeMlp
    feed_forward: bool = True  # False: no feed-forward part. A layer without one, or without a mixer, has one norm


def rope_tables(s: int, dh: int, theta: float):
    """(cos, signed sin) of :func:`rope` for one head: (s, dh) float32 from
    float64, cos tiled over both halves, sin negated in the first."""
    if dh % 2:
        raise ValueError(f"pos='rope' needs an even head size, got {dh}")
    angle = np.arange(s)[:, None] / theta ** (np.arange(0, dh, 2) / dh)  # (s, dh/2)
    cos = np.concatenate([np.cos(angle), np.cos(angle)], -1)
    sin = np.concatenate([-np.sin(angle), np.sin(angle)], -1)
    return cos.astype(np.float32), sin.astype(np.float32)


# Tables of more elements than this are made in the program (:func:`long_rope_tables`);
# up to it they are constants of the trace. A constant lies in the serialized program
# once for every place that reads it: at 8192 tokens and 128-wide heads two tables are
# 8 MB, q's and k's in four layers 67 MB of the StableHLO, and with remat's forward and
# the backward 0.27 GB of a 0.34 GB executable, which no 192 MiB compile cache keeps
# (PERF.md section 6, PR 47). 2^18 is the longest any older cell reads (4096 x 64).
ROPE_CONSTANT_ELEMENTS = 1 << 18
_ROPE_STRIDE = 64


def long_rope_tables(s: int, dh: int, theta: float):
    """:func:`rope_tables` made in the program from two short constant ones, by the
    angle of position 64 a + b being the sum of two: cos(A + B) = cos A cos B - sin A
    sin B, sin(A + B) = sin A cos B + cos A sin B, A over positions 0, 64, 128, ..
    and B over 0..63, each from float64 as there. (s, dh) float32, equal to
    ``rope_tables`` to two roundings (2e-7)."""
    tiled = lambda t: jnp.asarray(np.concatenate([t, t], -1), jnp.float32)  # noqa: E731
    rate = 1.0 / theta ** (np.arange(0, dh, 2) / dh)
    far, near = np.arange(0, s, _ROPE_STRIDE)[:, None] * rate, np.arange(_ROPE_STRIDE)[:, None] * rate
    cos_a, sin_a = (tiled(f(far))[:, None, :] for f in (np.cos, np.sin))
    cos_b, sin_b = (tiled(f(near))[None, :, :] for f in (np.cos, np.sin))
    sign = jnp.asarray(np.repeat([-1.0, 1.0], dh // 2), jnp.float32)
    cos = (cos_a * cos_b - sin_a * sin_b).reshape(-1, dh)[:s]
    sin = ((sin_a * cos_b + cos_a * sin_b) * sign).reshape(-1, dh)[:s]
    return cos, sin


def rope(x, theta: float):
    """Rotary positions on a (b, s, h, dh) projection, rotate-half convention:
    with the head's lanes cut in halves (x1, x2) and angle[p, i] = p / theta^(2i/dh),
    out = (x1 cos - x2 sin, x2 cos + x1 sin) at positions p = 0..s-1.

    That is x * cos + swap(x) * (-sin, sin), swap exchanging a head's halves. The
    exchange is a product with a dh x dh permutation matrix: the MXU moves the
    lanes, exactly (each output is one input times 1.0), and what is left is one
    elementwise pass in float32. Written as split / negate / concatenate, XLA
    moved the halves by relayouts and materialised the float32 copy: 187 ms of a
    1752 ms step at dh 128, s 256 (PERF.md section 6, PR 25). The tables are
    constants of the trace up to ``ROPE_CONSTANT_ELEMENTS`` and made in the
    program past it."""
    s, dh = x.shape[1], x.shape[-1]
    tables = long_rope_tables if s * dh > ROPE_CONSTANT_ELEMENTS else rope_tables
    swap = jnp.asarray(np.roll(np.eye(dh), dh // 2, axis=0), x.dtype)
    with jax.named_scope("rope"):
        cos, sin = (t[None, :, None, :] for t in tables(s, dh, theta))
        swapped = jax.lax.dot_general(
            x, swap, (((3,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,  # a float32 tower's permutation stays exact
            preferred_element_type=jnp.float32,
        )
        return (x.astype(jnp.float32) * cos + swapped * sin).astype(x.dtype)


class Mlp(nn.Module):
    width: int
    # May be fractional (HF so400m: 4304/1152); the hidden dim is rounded back
    # to the exact integer.
    mlp_ratio: int | float
    dtype: Any
    quant: bool | str = False  # "" | "int8" | "int8_ste" (see _dot_general)
    kind: str = "gelu"  # "gelu": wo(gelu(wi x)) | "swiglu": wo(silu(wg x) * (wi x)) | "relu2": wo(relu(wi x)^2)
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        hidden = int(round(self.width * self.mlp_ratio))
        dg = _dot_general(self.quant)
        if self.kind not in ("gelu", "swiglu", "relu2"):
            raise ValueError(f"unknown mlp: {self.kind!r}")

        # Column-parallel in, row-parallel out: the tp all-reduce happens once, after wo.
        def column_parallel(name):
            return nn.Dense(
                hidden,
                dtype=self.dtype,
                use_bias=self.use_bias,
                dot_general=dg,
                kernel_init=nn.with_partitioning(
                    nn.initializers.xavier_uniform(), (None, TP_AXIS)
                ),
                name=name,
            )

        wo = nn.Dense(
            self.width,
            dtype=self.dtype,
            use_bias=self.use_bias,
            dot_general=dg,
            kernel_init=nn.with_partitioning(
                nn.initializers.xavier_uniform(), (TP_AXIS, None)
            ),
            name="wo",
        )
        # Name the wi output so the "save_hot" remat policy keeps it: backward then
        # recomputes only the cheap elementwise gelu, not the big wi matmul.
        hidden_act = checkpoint_name(column_parallel("wi")(x), "mlp_hidden")
        if self.kind == "gelu":
            return wo(nn.gelu(hidden_act, approximate=True))
        if self.kind == "relu2":
            return wo(jnp.square(nn.relu(hidden_act)))
        gate = checkpoint_name(column_parallel("wg")(x), "mlp_hidden")
        return wo(nn.silu(gate) * hidden_act)


def attention_core(attn_impl: str, dtype, tokens: int, heads: int, kv_heads: int, head_dim: int, causal: bool,
                   self_attention: bool = True, window: int = 0) -> dict:
    """Which single-device core an ``Attention`` call takes, from what it can
    see: ``"dense"`` (XLA einsum softmax), ``"short"`` (the VMEM-resident
    ``short_attn_*`` kernels, ops/pallas_short_attention.py), ``"flash"`` (the
    library's blocked kernel, ops/flash_attention.py) or ``"kernel"`` (the repo's
    causal pair ``mla_attn_fwd`` / ``mla_attn_bwd``, ops/pallas_latent_attention.py,
    on (b, s, h x d)). "auto" picks a fused kernel only for bf16 self-attention
    on a TPU: the fused backward matmuls are bf16-grade, which is exactly right
    for bf16 training but would silently degrade an f32 parity run; "flash" asks
    for one whatever the dtype. Of the fused ones, heads that each have their
    own keys and values take the short kernels where a program's footprint fits
    their VMEM budget and the blocked kernel past that, as they always have.
    GROUPED heads (``kv_heads < heads``), causal, with a head size in whole
    128-lane registers whose sequence fits the pair's VMEM take the pair, which
    reads a group's one key / value head where it lies: ``kv_repeated`` is then
    False. Every other core takes one key and value head a query head, so
    grouped keys and values are repeated to that form first (``kv_repeated``
    True: the CPU, float32, a sequence the pair does not admit). ``block`` is
    the tokens a block of the pair or the blocked kernel (None otherwise).

    A ``window`` (> 0: a causal band, query t reads keys t - window + 1 .. t)
    shorter than the sequence has two cores: the pair, which visits the band's
    block pairs only, wherever a fused core would run and the pair admits the
    head size and the sequence, grouped heads or not; ``dense`` with the band as
    a mask everywhere else. The short and the blocked kernels take no window, so
    "auto" passes them by and ``attn_impl="flash"`` without the pair is refused
    by name. A window of at least the sequence is the causal layer itself.
    ``block_pairs`` and ``admitted_pairs`` count, from the shapes, the (query
    block, key block) pairs the pair's loops visit for a head and the (query,
    key) pairs a causal head's softmax admits (None where not causal). The
    module runs what this says and the step's trace-time record
    (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops.flash_attention import flash_attention_available, flash_attention_plan
    from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import (
        admitted_pairs,
        latent_attention_plan,
        visited_block_pairs,
    )
    from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import short_attention_fits

    window = window if 0 < window < tokens else 0
    if attn_impl == "flash" and not self_attention:
        raise ValueError(
            "attn_impl='flash' requires self-attention (the fused kernels "
            "assume q/k/v share one sequence); use 'auto' or 'dense' for "
            "cross-attention"
        )
    if attn_impl == "flash" and not flash_attention_available():
        raise ValueError(
            "attn_impl='flash' requires a TPU backend (current: "
            f"{jax.default_backend()!r}); use 'auto' to fall back to the "
            "dense path automatically"
        )
    use_fused = attn_impl == "flash" or (
        attn_impl == "auto"
        and self_attention
        and dtype == jnp.bfloat16
        and flash_attention_available()
    )
    itemsize = jnp.dtype(dtype).itemsize
    grouped = kv_heads != heads
    paired = use_fused and causal and (grouped or bool(window))
    pair = latent_attention_plan(tokens, head_dim, head_dim, itemsize) if paired else None
    if window and attn_impl == "flash" and pair is None:
        raise ValueError(
            f"attn_impl='flash' with window={window}: of the fused cores only the causal pair (mla_attn_fwd / "
            f"mla_attn_bwd) takes a window, and it does not admit {tokens} tokens in heads of {head_dim} "
            "(a head size in whole 128-lane registers, a head's sequence in VMEM); use 'auto' or 'dense'"
        )
    if not use_fused or (window and pair is None):
        core, block = "dense", None
    elif pair is not None:
        core, block = "kernel", pair["block"]
    elif short_attention_fits(tokens, heads * head_dim, itemsize):
        core, block = "short", None
    else:
        core, block = "flash", flash_attention_plan(tokens)["block"]
    return {"core": core, "block": block, "heads": heads, "kv_heads": kv_heads, "head_dim": head_dim,
            "kv_repeated": grouped and core != "kernel", "window": window or None,
            "block_pairs": visited_block_pairs(pair["tokens"], block, window) if core == "kernel" else None,
            "admitted_pairs": admitted_pairs(tokens, window) if causal and self_attention else None}


class Attention(nn.Module):
    """Multi-head attention; with ``sp_axis`` set, the attention core runs
    sequence-parallel over that mesh axis (long-context path) — ``sp_impl`` picks
    ring (ppermute) or ulysses (all-to-all) attention. Requires an ambient mesh
    (``jax.set_mesh``) containing the axis; the projections stay per-token and are
    partitioned by GSPMD as usual.

    ``attn_impl`` selects the single-device core: "dense" (XLA einsum softmax),
    "flash" (Pallas fused kernel, TPU only), or "auto" (flash on TPU when the shape
    qualifies, dense otherwise): :func:`attention_core` has the rule.

    ``num_kv_heads`` (0 = ``num_heads``) groups the query heads over fewer key /
    value heads: query head h reads key / value head h // (num_heads /
    num_kv_heads). ``head_dim`` (0 = ``width // num_heads``) is a head size of
    its own: q is width -> num_heads x head_dim, k and v width -> num_kv_heads x
    head_dim, out num_heads x head_dim -> width."""

    width: int
    num_heads: int
    dtype: Any
    sp_axis: str | None = None
    sp_impl: str = "ring"  # "ring" (ppermute) or "ulysses" (all-to-all)
    attn_impl: str = "auto"  # "dense" | "flash" | "auto"
    causal: bool = False
    quant: bool | str = False  # "" | "int8" | "int8_ste" (see _dot_general)
    use_bias: bool = True
    rope_theta: float | None = None  # rotary positions on q and k (see rope)
    num_kv_heads: int = 0  # 0 = num_heads
    head_dim: int = 0  # 0 = width // num_heads
    window: int = 0  # > 0: a causal band, query t reads keys t - window + 1 .. t
    qk_norm: bool = False  # RMSNorm over each head of q and of k, before the rotation
    out_gate: bool = False  # sigmoid(x W_gate) times the heads' outputs, before ``out``
    norm_eps: float = 1e-6  # the head norms'

    @nn.compact
    def __call__(self, x_q, x_kv=None):
        is_self_attention = x_kv is None
        x_kv = x_q if x_kv is None else x_kv
        head_dim = self.head_dim or self.width // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        inner = self.num_heads * head_dim  # the width, for heads of width // num_heads
        if self.num_heads % kv_heads:
            raise ValueError(f"num_kv_heads={kv_heads} does not divide num_heads={self.num_heads}")
        if kv_heads != self.num_heads and (self.sp_axis is not None or not is_self_attention):
            raise ValueError(
                f"num_kv_heads={kv_heads} (grouped heads) is not built for sequence-parallel attention "
                f"(sequence_parallel_axis={self.sp_axis!r}) or cross-attention"
            )
        dg = _dot_general(self.quant)
        refused = {
            "causal=False": not self.causal, "cross-attention": not is_self_attention,
            f"sequence_parallel_axis={self.sp_axis!r}": self.sp_axis is not None, f"quant={self.quant!r}": bool(self.quant),
        }
        if self.window < 0 or self.window and any(refused.values()):
            raise ValueError(
                f"window={self.window} (a causal band over one whole sequence, unquantised) is not built for "
                + (", ".join(k for k, v in refused.items() if v) or "a negative number of keys")
            )
        if self.rope_theta is not None and (self.sp_axis is not None or not is_self_attention):
            raise ValueError(
                "pos='rope' numbers the positions 0..s-1 of one whole sequence: "
                "it runs neither sequence-parallel nor in cross-attention"
            )

        qkv_init = nn.with_partitioning(nn.initializers.xavier_uniform(), (None, TP_AXIS))
        out_init = nn.with_partitioning(nn.initializers.xavier_uniform(), (TP_AXIS, None))
        dense = partial(nn.Dense, dtype=self.dtype, use_bias=self.use_bias, dot_general=dg)

        q = dense(inner, kernel_init=qkv_init, name="q")(x_q)
        k = dense(kv_heads * head_dim, kernel_init=qkv_init, name="k")(x_kv)
        v = dense(kv_heads * head_dim, kernel_init=qkv_init, name="v")(x_kv)

        def split(t):
            return t.reshape(t.shape[:-1] + (-1, head_dim))

        # Named for the "save_all_hot" remat policy (saves the projections too, so
        # backward recompute is layernorm+gelu only).
        q, k, v = (checkpoint_name(t, n) for t, n in
                   ((split(q), "q_proj"), (split(k), "k_proj"), (split(v), "v_proj")))
        if self.qk_norm:
            with jax.named_scope(ATTN_GATE_SCOPE):
                q = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="q_norm")(q)
                k = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="k_norm")(k)
        if self.rope_theta is not None:
            # Outside the attention kernel: one elementwise pass over q and k.
            q, k = rope(q, self.rope_theta), rope(k, self.rope_theta)
        if self.sp_axis is not None and is_self_attention:
            # Sequence-parallel exact attention: manual over sp only, GSPMD keeps
            # handling any other mesh axes (dp/tp) automatically.
            from jax.sharding import PartitionSpec as P

            from distributed_sigmoid_loss_tpu.parallel.ring_attention import (
                ring_self_attention,
            )
            from distributed_sigmoid_loss_tpu.parallel.ulysses_attention import (
                ulysses_self_attention,
            )

            sp_impls = {
                "ring": ring_self_attention,
                "ulysses": ulysses_self_attention,
            }
            if self.sp_impl not in sp_impls:
                raise ValueError(
                    f"unknown sp_impl: {self.sp_impl!r} (expected one of "
                    f"{sorted(sp_impls)})"
                )
            sp_fn = sp_impls[self.sp_impl]
            spec = P(None, self.sp_axis)
            out = jax.shard_map(
                partial(sp_fn, axis_name=self.sp_axis, causal=self.causal),
                in_specs=(spec, spec, spec),
                out_specs=spec,
                axis_names={self.sp_axis},
            )(q, k, v)
        else:
            from distributed_sigmoid_loss_tpu.ops.flash_attention import flash_self_attention
            from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import short_self_attention
            from distributed_sigmoid_loss_tpu.parallel.ring_attention import (
                dense_attention,
            )

            sizes = attention_core(
                self.attn_impl, self.dtype, q.shape[1], self.num_heads, kv_heads, head_dim, self.causal,
                is_self_attention, window=self.window,
            )
            window = sizes["window"] or 0  # 0 where the window holds the whole sequence: the causal layer
            with jax.named_scope(WINDOW_CORE_SCOPE if window else ATTN_CORE_SCOPE):
                if sizes["core"] == "kernel":
                    # Grouped heads through the causal pair, the heads on the lanes: a group's
                    # key / value head is read where it lies, nothing is repeated in HBM.
                    from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import kernels_per_shard
                    from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import latent_attention_kernel

                    def pair(q, k, v):  # a shard of whole key / value heads reads its heads off the widths
                        return latent_attention_kernel(
                            q, k, v, head_dims=(head_dim, head_dim), kv_heads=k.shape[-1] // head_dim, window=window
                        )

                    wide = (t.reshape(t.shape[:-2] + (-1,)) for t in (q, k, v))
                    out = kernels_per_shard(pair, kv_heads, *wide)
                    out = out.reshape(out.shape[:-1] + (self.num_heads, head_dim))
                else:
                    if sizes["kv_repeated"]:
                        k, v = (jnp.repeat(t, self.num_heads // kv_heads, axis=-2) for t in (k, v))
                    kernel = {"dense": dense_attention, "short": short_self_attention, "flash": flash_self_attention}
                    attend = partial(kernel[sizes["core"]], causal=self.causal, **({"window": window} if window else {}))
                    out = (
                        _fused_attention_per_shard(attend, q, k, v) if sizes["core"] != "dense"
                        else attend(q, k, v)
                    )
            out = out.astype(self.dtype)
        # Named for the "save_hot" remat policy: with the core output saved, the
        # backward pass needs only q/k/v (for the attention VJP) — the s² core
        # forward is never re-run.
        out = checkpoint_name(out, "attn_core")
        out = out.reshape(out.shape[:-2] + (inner,))
        if self.out_gate:
            gate = dense(inner, kernel_init=qkv_init, name="gate")(x_q)
            with jax.named_scope(ATTN_GATE_SCOPE):
                out = out * nn.sigmoid(gate)
        return dense(self.width, kernel_init=out_init, name="out")(out)


class Block(nn.Module):
    """Pre-LN transformer block. ``moe_experts > 0`` swaps the dense MLP for a
    mixture-of-experts layer (models/moe.py) whose expert weights shard over the
    ``ep`` mesh axis; the residual stream is unchanged, so MoE composes with
    remat/scan/sp exactly like the dense block. ``style`` picks the norm, a
    second norm on each sub-layer's output, the MLP and rotary positions.
    ``spec`` may leave out the mixer (``mixer="none"``) or the feed-forward part
    (``feed_forward=False``): the layer is then ONE sub-layer, x + f(norm(x)),
    under one norm (``ln1``), and has no ``ln2``."""

    width: int
    num_heads: int
    mlp_ratio: int | float
    dtype: Any
    sp_axis: str | None = None
    sp_impl: str = "ring"
    attn_impl: str = "auto"
    causal: bool = False
    moe_experts: int = 0
    moe_num_selected: int = 1
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 512
    quant: bool | str = False
    style: BlockStyle = BlockStyle()
    spec: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x):
        style, spec = self.style, self.spec

        def norm(name):
            return style.make_norm(self.dtype, name)

        def post(name, y):  # the sandwich's second norm, on a sub-layer's output
            return norm(name)(y) if style.sandwich_norm else y

        if spec.mixer == "none" and not spec.feed_forward:
            raise ValueError("a layer with neither a mixer nor a feed-forward part is no layer")
        if spec.mixer == "none":
            mixer = None
        elif spec.mixer == "attn":
            mixer = Attention(
                self.width, self.num_heads, self.dtype,
                sp_axis=self.sp_axis, sp_impl=self.sp_impl,
                attn_impl=self.attn_impl, causal=self.causal,
                quant=self.quant, use_bias=style.use_bias, norm_eps=style.norm_eps,
                # A layer's own fields come last: where only some layers rotate, each says whether it does.
                **{"rope_theta": style.rope_theta, **dict(spec.mixer_fields)}, name="attn",
            )
        elif spec.mixer in ("kda", "mla"):
            from distributed_sigmoid_loss_tpu.models.mixers import KdaMixer, LatentAttention

            sized = dict(
                width=self.width, num_heads=self.num_heads, dtype=self.dtype,
                norm_eps=style.norm_eps, **dict(spec.mixer_fields),
            )
            if spec.mixer == "kda":
                mixer = KdaMixer(**sized, name="kda")
            else:
                mixer = LatentAttention(**sized, attn_impl=self.attn_impl, name="mla")
        elif spec.mixer == "eva":
            from distributed_sigmoid_loss_tpu.models.mixers import EvaAttention

            mixer = EvaAttention(
                width=self.width, num_heads=self.num_heads, dtype=self.dtype,
                attn_impl=self.attn_impl, **dict(spec.mixer_fields), name="eva",
            )
        elif spec.mixer == "ssm":
            from distributed_sigmoid_loss_tpu.models.mixers import SsmMixer

            mixer = SsmMixer(
                width=self.width, dtype=self.dtype, norm_eps=style.norm_eps, **dict(spec.mixer_fields), name="ssm",
            )
        else:
            raise ValueError(f"unknown mixer: mixers has {spec.mixer!r}")
        if mixer is not None:
            x = x + post("ln1_post", mixer(norm("ln1")(x)))
        if not spec.feed_forward:
            return x
        # The one norm of a layer without a mixer is its first.
        ln, ln_post = ("ln1", "ln1_post") if mixer is None else ("ln2", "ln2_post")
        routed = self.moe_experts > 0 and not spec.dense_mlp
        if routed and spec.experts_fields:
            from distributed_sigmoid_loss_tpu.models.moe import SharedExpertMoe

            if style.mlp not in ("swiglu", "relu2") or style.use_bias or self.quant:
                raise ValueError(
                    "moe_router='sigmoid' has the bias-free, unquantised SwiGLU or relu2 experts only: "
                    f"mlp={style.mlp!r}, use_bias={style.use_bias}, quant={self.quant!r} are not built"
                )
            mlp = SharedExpertMoe(
                width=self.width, num_experts=self.moe_experts, num_selected=self.moe_num_selected,
                dtype=self.dtype, kind=style.mlp, **dict(spec.experts_fields), name="moe",
            )
        elif routed:
            from distributed_sigmoid_loss_tpu.models.moe import MoeMlp

            if style.mlp != "gelu" or not style.use_bias:
                raise ValueError(
                    "moe_experts > 0 has the biased GELU experts only: "
                    f"mlp={style.mlp!r}, use_bias={style.use_bias} are not built"
                )
            mlp = MoeMlp(
                self.width, self.mlp_ratio, self.moe_experts, self.dtype,
                num_selected=self.moe_num_selected,
                capacity_factor=self.moe_capacity_factor,
                group_size=self.moe_group_size,
                quant=self.quant,
                name="moe",
            )
        else:
            mlp = Mlp(
                self.width, self.mlp_ratio, self.dtype, quant=self.quant,
                kind=style.mlp, use_bias=style.use_bias, name="mlp",
            )
        x = x + post(ln_post, mlp(norm(ln)(x)))
        return x


@jax.custom_vjp
def _add_grads_into(params, stacks, layer):
    """``(params, stacks)`` as they came. Backwards, the stacks' cotangent
    gains the parameters' gradient at row ``layer`` (accum_add's
    upcast-add-round on that row, in place) and the parameters get none: seeded
    with the accumulator, the cotangent that comes out is the new accumulator."""
    return params, stacks


def _add_grads_into_bwd(layer, cts):
    grads, stacks = cts

    def add_row(acc, g):
        row = jax.lax.dynamic_index_in_dim(acc, layer, 0, keepdims=False)
        row = (row.astype(g.dtype) + g).astype(acc.dtype)
        return jax.lax.dynamic_update_index_in_dim(acc, row, layer, 0)

    with jax.named_scope(ACCUM_SCOPE):
        return None, jax.tree.map(add_row, stacks, grads), None


_add_grads_into.defvjp(
    lambda params, stacks, layer: ((params, stacks), layer), _add_grads_into_bwd
)


def split_grad_sink(tree):
    """``(stacks, rest)`` of a parameter-shaped dict: the scanned, dense,
    unlooped stacks (``blocks`` of an :class:`Encoder`), whose weight gradients
    an accumulating step may have added into the accumulator inside the layer
    loop, and every other leaf. Empty branches are dropped."""
    stacks, rest = {}, {}
    for key, sub in tree.items():
        if key == "blocks" and "moe" not in sub["block"]:
            stacks[key] = sub
        elif isinstance(sub, Mapping) and key != "loop":
            inner, outer = split_grad_sink(sub)
            if inner:
                stacks[key] = inner
            if outer:
                rest[key] = outer
        else:
            rest[key] = sub
    return stacks, rest


def merge_grad_sink(stacks, rest):
    """Inverse of :func:`split_grad_sink`."""
    out = dict(rest)
    for key, sub in stacks.items():
        out[key] = merge_grad_sink(sub, rest[key]) if key in rest else sub
    return out


class _ScanBody(nn.Module):
    """Scan-compatible block wrapper: ``(carry, layer) -> (carry, None)``;
    ``layer`` is the layer's index where an accumulating step offers a sink
    (``GRAD_SINK``, carried whole by the scan), else None."""

    width: int
    num_heads: int
    mlp_ratio: int | float
    dtype: Any
    sp_axis: str | None = None
    sp_impl: str = "ring"
    attn_impl: str = "auto"
    causal: bool = False
    moe_experts: int = 0
    moe_num_selected: int = 1
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 512
    quant: bool | str = False
    style: BlockStyle = BlockStyle()
    spec: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, carry, layer):
        block_cls = Block
        if layer is not None:
            # The accumulating step's sink (Encoder): this layer's parameters
            # pass _add_grads_into with the carried stacks.
            def through_sink(collections):
                params, stacks = _add_grads_into(
                    collections["params"], collections[GRAD_SINK], layer
                )
                return {"params": params, GRAD_SINK: stacks}

            block_cls = nn.map_variables(
                Block, ("params", GRAD_SINK), through_sink, mutable=True
            )
        carry = block_cls(
            self.width, self.num_heads, self.mlp_ratio, self.dtype,
            sp_axis=self.sp_axis, sp_impl=self.sp_impl,
            attn_impl=self.attn_impl, causal=self.causal,
            moe_experts=self.moe_experts,
            moe_num_selected=self.moe_num_selected,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_group_size=self.moe_group_size,
            quant=self.quant, style=self.style, spec=self.spec,
            name="block",
        )(carry)
        return carry, None


class Encoder(nn.Module):
    """Stack of blocks, then the final norm; optionally remat'd and scanned over
    depth. ``loops > 1`` runs that whole pass ``loops`` times on one set of
    weights, which then live under ``loop/`` (a flax path of its own: a profile
    shows the looped stack's operations under it). Layers given one by one
    (``layers``) that are all alike are one stack like any other, scanned where
    ``scan_layers`` says; unlike layers run unrolled, remat per layer.

    A scanned stack run once takes a ``GRAD_SINK`` collection (applied with
    ``mutable=[GRAD_SINK]``): the gradient accumulator of its ``blocks``, which
    it hands back unchanged. Differentiated with that output's cotangent seeded
    with the accumulator, the backward layer loop carries it and adds each
    layer's weight gradients to their row (:func:`_add_grads_into`); what comes
    out as the sink's cotangent is the new accumulator, and ``blocks`` get no
    gradient of their own. Without the collection nothing changes."""

    width: int
    depth: int
    num_heads: int
    mlp_ratio: int | float
    dtype: Any
    remat: bool = False
    scan_layers: bool = False
    # "nothing" = full remat; "save_hot" = save attention-core + MLP-hidden
    # outputs; "save_all_hot" adds q/k/v; "save_mlp" = MLP hidden only. See
    # _remat_policy for the recompute/HBM tradeoffs.
    remat_policy: str = "nothing"
    sp_axis: str | None = None
    sp_impl: str = "ring"
    attn_impl: str = "auto"
    causal: bool = False
    moe_experts: int = 0
    moe_num_selected: int = 1
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 512
    quant: bool | str = False
    style: BlockStyle = BlockStyle()
    loops: int = 1
    # One spec a layer, or none: every layer the default block. Layers that are
    # all alike are one stack (scanned where ``scan_layers`` says), whatever
    # their mixer; unlike layers run unrolled.
    layers: tuple[LayerSpec, ...] = ()

    @nn.compact
    def __call__(self, x):
        sunk = bool(self.variables.get(GRAD_SINK))
        if sunk and not (self.scan_layers and self.loops == 1):
            raise ValueError(
                f"{GRAD_SINK!r} is for a scanned stack run once: a looped stack "
                f"(loops={self.loops}) would add it once a pass, unrolled layers "
                "(scan_layers=False) have no layer loop to add it in"
            )
        layers = self.layers or (LayerSpec(),) * self.depth
        if len(layers) != self.depth:
            raise ValueError(f"mixers names {len(layers)} layers, depth={self.depth}")
        # Several layer kinds, or a leading dense layer: each layer is a module
        # of its own (no stack of like trees to scan), unrolled, remat per layer.
        # Like layers are one stack whatever their mixer: ``_ScanBody`` takes the spec.
        unlike = len(set(layers)) > 1
        self._check_mixers(tuple(spec.mixer for spec in layers))
        if self.loops > 1:
            return self._looped(x)
        moe_kw = dict(
            moe_experts=self.moe_experts,
            moe_num_selected=self.moe_num_selected,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_group_size=self.moe_group_size,
            quant=self.quant, style=self.style,
        )
        if self.scan_layers and not unlike:
            body_cls = _ScanBody
            if self.remat:
                # prevent_cse=False is safe (and faster) under scan.
                body_cls = nn.remat(
                    _ScanBody, prevent_cse=False, static_argnums=(),
                    policy=_remat_policy(self.remat_policy),
                )
            # One set of stacked params, compiled once: lax.scan over depth.
            # The sown MoE aux losses ride the scan with a leading depth axis.
            # A sink rides it whole, as a carry: the backward layer loop then
            # carries the accumulator and adds each layer's row in place.
            scanned = nn.scan(
                body_cls,
                variable_axes={"params": 0, "intermediates": 0},
                variable_carry=GRAD_SINK if sunk else False,
                split_rngs={"params": True},
                length=self.depth,
                metadata_params={nn.PARTITION_NAME: None},
            )
            x, _ = scanned(
                self.width, self.num_heads, self.mlp_ratio, self.dtype,
                sp_axis=self.sp_axis, sp_impl=self.sp_impl,
                attn_impl=self.attn_impl, causal=self.causal, **moe_kw, spec=layers[0],
                name="blocks",
            )(x, jnp.arange(self.depth) if sunk else None)
        else:
            block_cls = (
                nn.remat(Block, policy=_remat_policy(self.remat_policy))
                if self.remat
                else Block
            )
            for i in range(self.depth):
                x = block_cls(
                    self.width, self.num_heads, self.mlp_ratio, self.dtype,
                    sp_axis=self.sp_axis, sp_impl=self.sp_impl,
                    attn_impl=self.attn_impl, causal=self.causal, **moe_kw, spec=layers[i],
                    name=f"block{i}",
                )(x)
        return self.style.make_norm(self.dtype, "ln_final")(x)

    def _check_mixers(self, mixers):
        """What a recurrence or latent attention over one whole causal sequence
        does not run with, each refusal by the option's name (a position table
        is ``models/text.py``'s to refuse, and a rotation beside a recurrence)."""
        refused = {
            "causal=False": not self.causal,
            f"sequence_parallel_axis={self.sp_axis!r}": self.sp_axis is not None,
            f"quant={self.quant!r}": bool(self.quant),
            f"rope_theta={self.style.rope_theta!r} (pos='rope')":
                self.style.rope_theta is not None and bool({"kda", "ssm"} & set(mixers)),
            "sandwich_norm=True": self.style.sandwich_norm,
            f"loops={self.loops}": self.loops > 1,
        }
        if set(mixers) != {"attn"} and any(refused.values()):
            raise ValueError(
                f"mixers={mixers} (a recurrence, latent attention or windowed chunk "
                "attention over one whole causal sequence, unquantised, a recurrence "
                "with no position encoding; or a stack of one-sub-layer layers) is not built for "
                + ", ".join(k for k, v in refused.items() if v)
            )

    def _looped(self, x):
        """One pass (the layers, then the final norm) as a child ``loop``, run
        ``loops`` times by a scan that broadcasts its parameters: one set of
        weights, and the backward pass sums each weight's gradient over the
        passes in the scan's carry."""
        fields = {
            f.name: getattr(self, f.name) for f in dataclasses.fields(self)
            if f.name not in ("parent", "name", "loops")
        }
        one_pass = Encoder(**fields, name="loop")
        x, _ = nn.scan(
            lambda module, carry, _: (module(carry), None),
            variable_broadcast="params", split_rngs={"params": False},
            variable_axes={"intermediates": 0}, length=self.loops,
        )(one_pass, x, None)
        return x


class MapHead(nn.Module):
    """SigLIP's MAP (multihead attention pooling) head: a learned probe token attends
    over the sequence, followed by an MLP residual."""

    width: int
    num_heads: int
    mlp_ratio: int | float
    dtype: Any

    @nn.compact
    def __call__(self, tokens):
        b = tokens.shape[0]
        probe = self.param(
            "probe", nn.initializers.xavier_uniform(), (1, 1, self.width), jnp.float32
        ).astype(self.dtype)
        probe = jnp.broadcast_to(probe, (b, 1, self.width))
        x = Attention(self.width, self.num_heads, self.dtype, name="attn")(probe, tokens)
        x = x + Mlp(self.width, self.mlp_ratio, self.dtype, name="mlp")(
            nn.LayerNorm(dtype=self.dtype, name="ln")(x)
        )
        return x[:, 0]
