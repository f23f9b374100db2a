"""Token mixers of a stack given layer by layer (``TextConfig.mixers``), beside
the block's softmax ``Attention`` (models/transformer.py): a gated delta-rule
layer ("kda"), latent attention ("mla"), windowed chunk attention ("eva") and a
Mamba-2 state-space layer ("ssm"). Imported only where a configuration names
one. All are causal and carry no bias (but the state-space layer's convolution);
their statistics, gates and decays are float32 whatever the tower's dtype. The
recurrences take no position encoding; latent attention none, or a rotation of
its shared-width parts; windowed chunk attention rotates whole heads.

Latent attention's core (scores, causal softmax, values; scope ``mla_core``) is
one of three, by :func:`latent_attention_core`, from what the call can see:
``"kernel"``, the repo's own Pallas pair (``ops/pallas_latent_attention.py``:
``mla_attn_fwd`` / ``mla_attn_bwd``), which reads and writes (b, s, h x d);
``"flash"``, the library's blocked kernel through ``ops/flash_attention.py``,
for a sequence whose head does not fit the pair's VMEM; ``"dense"``, XLA (the
CPU, float32). The layer has one body for the three and, like a KDA layer,
holds no per-head (b, s, h, d) activation around its core (XLA keeps such an
array in another tiling, so a view of it around a Mosaic kernel is a copy
through HBM each way): what a per-head form would cut, pad, join and exchange
in the activations it does to the COLUMNS of the projections' weights
(``_recut``, an ``nn.Dense``'s ``dot_general``), and q, k, v are born, and o is
consumed, with the heads on the lanes. The pair takes them so; the library's
kernel and XLA take a (b, s, h, d) view of them.

With x the (s, width) normalised stream of one sequence:

    KDA   q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))   # conv: causal depthwise, no bias
          q_h = l2norm(q_h) dk^-1/2 ;  k_h = l2norm(k_h)                    # per head
          g   = -exp(A_log_h) softplus((x Wfa) Wfb + dt_bias)               # log-decay per key channel
          beta_h = sigmoid(x Wb)
          o   = gated delta rule (ops/gated_delta_rule.py)
          out = (RMS_head(o) sigmoid((x Wga) Wgb)) Wo
    MLA   q_h = (x Wq)_h, or (RMS(x Wqa) Wqb)_h with a query rank
          [c, kr] = x Wkva ;  [kn_h, v_h] = (RMS(c) Wkvb)_h
          [qn_h, qr_h] = q_h ;  with rope_theta: qr_h = rope(qr_h), kr = rope(kr)
          k_h = [kn_h, kr]   (kr shared by all heads, rotated once)
          out = softmax([qn_h, qr_h] k_h^T (dn + dr)^-1/2 + causal) v_h -> Wo
    EVA   q_h, k_h, v_h = rope((x Wq)_h), rope((x Wk)_h), (x Wv)_h          # whole heads rotated, positions 0..s-1
          chunk c = tokens Cc .. Cc+C-1, in window floor(Cc / W) ;  window(t) = floor(t / W)
          a_j  = softmax over j in c of (k_j . phi_h) d^-1/2                # phi_h, mu_h: (d,) leaves per head
          kc_c = sum_j a_j k_j + mu_h ;  vc_c = sum_j a_j v_j               # scope eva_summary, from the rotated keys
          s_tj = q_t . k_j d^-1/2   for j <= t in window(t)                 # exact, causal, inside the window
          r_tc = q_t . kc_c d^-1/2  for every chunk c of a window before window(t)
          o_t  = (sum_j e^s_tj v_j + sum_c e^r_tc vc_c) / (sum_j e^s_tj + sum_c e^r_tc)   # scope eva_core: one softmax
          out  = concat_h(o_t) Wo

    SSM   [z | xBC | dt] = x W_in                                           # width -> h P + (h P + 2 g N) + h
          xBC = silu(conv(xBC) + b_conv) ;  [x' | B | C] = xBC               # conv: causal depthwise, per channel; scope ssm_conv
          dt = softplus(dt + dt_bias) ;  A = -exp(A_log)                     # (h,), float32
          S_t = exp(dt_t A_h) S_{t-1} + dt_t x'_t[h] (x) B_t[g] ;  y_t[h] = S_t C_t[g] + D_h x'_t[h]   # scope ssm_core, g = h // (h / groups)
          y = y silu(z) ;  y = y rsqrt(mean over each group's h P / g lanes (y^2) + eps) w   # gated RMSNorm, the gate first
          out = y W_out

The state-space recurrence runs as ``ops/ssm.py ssm_scan`` has it, in the form ``ssm_core`` there names from what the
call sees: ``"kernel"`` (the Pallas pair ``ssd_fwd`` / ``ssd_bwd`` of ``ops/pallas_ssm.py``: bfloat16, a TPU, whole
registers) or ``"chunked"`` (XLA); x', B, C and y stay (b, s, h x P) / (b, s, g x N) around either.

Both recurrences' layers run ``silu(conv(.) + b)`` as ``ops/gated_delta_rule.py short_conv_silu`` has it, in the form
``short_conv_core`` there names: ``"kernel"`` (the Pallas pair ``short_conv_fwd`` / ``short_conv_bwd`` of
``ops/pallas_short_conv.py``: bfloat16, a TPU, the channels in whole 128-lane registers and the sequence in whole 16-row
tiles; one pass over HBM each way) or ``"xla"``.

Windowed chunk attention's core is one of two, by :func:`eva_attention_core`:
``"kernel"``, the Pallas pair ``eva_attn_fwd`` / ``eva_attn_bwd``
(``ops/pallas_eva_attention.py``) on (b, s, h x d), or ``"dense"``, XLA on a
per-head view (the CPU, float32). The rotation and the pooling run on (b, s, h
x d) too: a head's halves are exchanged by two shifts of the lanes, a head's
dot with phi and the weights' way back onto its lanes are 0/1-patterned
products.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import (
    kernels_per_shard,
    normed_chunk_gated_delta_rule,
    short_conv_silu,
)

F32 = jnp.float32
# The program's names for these layers' device time (benchmark/scopes_kimi.py):
# flax writes the modules' own names ("kda", "mla") into every operation's path.
KDA_CORE_SCOPE = "kda_core"  # the recurrence alone, inside "kda"
MLA_CORE_SCOPE = "mla_core"  # scores, softmax and values, inside "mla"
MLA_ROPE_SCOPE = "mla_rope"  # the rotation of the queries' and the key's shared-width parts, inside "mla"
EVA_SUMMARY_SCOPE = "eva_summary"  # the pooling of keys and values into chunk summaries, inside "eva"
EVA_CORE_SCOPE = "eva_core"  # both score sets, the one softmax and the values, inside "eva"
SSM_CONV_SCOPE = "ssm_conv"  # the convolution, its bias and the silu, inside "ssm"
SSM_CORE_SCOPE = "ssm_core"  # the recurrence alone (from x', B, C, dt, A, D to y), inside "ssm"
# Tokens a chunk of the delta rule: what one program of the kernels (ops/pallas_delta_rule.py)
# holds in VMEM per head, six halving levels and a 64 x 64 float32 inverse; the XLA form's too.
CHUNK = 64
# The blocked attention kernel's head sizes: a multiple of a vector register's lanes.
FUSED_LANES = 128


def _decay_rate_init(key, shape, dtype=F32):
    """A_log: log of a rate uniform in [1, 16), per head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=F32):
    """dt_bias: softplus^-1 of a step log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KdaMixer(nn.Module):
    """The gated delta-rule layer: ``num_heads`` heads with ``head_dim`` key and
    value channels each, chunks of ``CHUNK`` tokens. The q, k and v branches'
    convolution and silu are one ``short_conv_silu`` each (the Pallas pair
    ``short_conv_fwd`` / ``short_conv_bwd`` in bf16 on a TPU, XLA elsewhere)."""

    width: int
    num_heads: int
    head_dim: int
    conv_size: int
    dtype: Any
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        h, d = self.num_heads, self.head_dim
        dense = partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
        )
        bound = self.conv_size**-0.5

        def conv_init(key, shape, dtype=F32):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        def branch(name):
            y = dense(h * d, name=name)(x)
            taps = self.param(name + "_conv", conv_init, (self.conv_size, h * d), F32)
            return short_conv_silu(y, taps)

        # q and k go to the core raw, o comes back over its head's rms times the scale: the per-head
        # norms run where the core runs, and nothing here is viewed (b, s, h, d).
        q, k, v = branch("q"), branch("k"), branch("v")
        rate = jnp.exp(self.param("A_log", _decay_rate_init, (h,), F32))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h * d,), F32)
        step = dense(h * d, name="f_b")(dense(d, name="f_a")(x)).astype(F32) + dt_bias
        g = -jnp.repeat(rate, d) * jax.nn.softplus(step)
        beta = jax.nn.sigmoid(dense(h, name="beta")(x).astype(F32))
        scale = self.param("o_norm", nn.initializers.ones, (d,), F32)
        with jax.named_scope(KDA_CORE_SCOPE):
            o = normed_chunk_gated_delta_rule(
                q, k, v, g, beta, scale, o_eps=self.norm_eps, chunk=CHUNK, dtype=self.dtype
            )
        gate = dense(h * d, name="g_b")(dense(d, name="g_a")(x)).astype(F32)
        o = (o * jax.nn.sigmoid(gate)).astype(self.dtype)
        return dense(self.width, name="out")(o)


def _whole_registers(d: int) -> int:
    return -(-d // FUSED_LANES) * FUSED_LANES


def latent_attention_core(attn_impl: str, dtype, tokens: int, dqk: int, dv: int) -> dict:
    """Which core a ``LatentAttention`` call takes, from what it can see, and
    the sizes it runs at. ``core``: ``"kernel"`` (the repo's Pallas pair,
    ``ops/pallas_latent_attention.py``, on (b, s, h x d)), ``"flash"`` (the
    library's blocked kernel on a (b, s, h, d) view) or ``"dense"`` (XLA, on the
    same view). As in ``Attention``: a fused kernel's backward is bf16-grade,
    so "auto" takes one for a bf16 tower on a TPU only; "flash" asks for one
    whatever the dtype. Of the fused two the pair is taken wherever a head's
    sequence fits its VMEM (``latent_attention_plan``; 4096 tokens of 256-wide
    heads hold 51 of its 100 MiB), the library's kernel past that.
    ``core_head_dim`` / ``core_v_dim`` are a head's window of the lanes, the
    query/key and value head sizes the core runs at (the pair: each in whole
    128-lane registers; the library's kernel: both the wider of the two, in
    whole registers; dense: as they are), ``padded`` whether any head is
    zero-padded to its window, ``block`` the tokens a block of a fused core
    (None: dense) and ``core_tokens`` the sequence with the zero rows that fill
    its last block. The mixer runs what this says and the step's trace-time
    record (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import latent_attention_plan

    if attn_impl == "flash" and not flash_attention.flash_attention_available():
        raise ValueError("attn_impl='flash' requires a TPU backend; use 'auto'")
    fused = attn_impl == "flash" or (
        attn_impl == "auto" and jnp.dtype(dtype) == jnp.bfloat16 and flash_attention.flash_attention_available()
    )
    core, windows, plan = "dense", (dqk, dv), {"block": None, "tokens": tokens}
    if fused:
        windows = (_whole_registers(dqk), _whole_registers(dv))
        core, plan = "kernel", latent_attention_plan(tokens, *windows, jnp.dtype(dtype).itemsize)
        if plan is None:
            core, windows, plan = "flash", (max(windows),) * 2, flash_attention.flash_attention_plan(tokens)
    return {"core": core, "core_head_dim": windows[0], "core_v_dim": windows[1], "block": plan["block"],
            "core_tokens": plan["tokens"], "padded": windows != (dqk, dv)}


def _head_columns(kernel, heads: int, take, wide: int, at: int = 0):
    """(fan_in, h x d) -> (fan_in, h x wide): of each head's d columns those
    ``take`` names (a slice or indices), from lane ``at`` of the head's window
    of ``wide`` lanes on, zeros in the window's other lanes."""
    fan_in, width = kernel.shape
    if isinstance(take, slice) and take == slice(None) and width == heads * wide:
        return kernel  # every head's columns, already a window each
    cols = kernel.reshape(fan_in, heads, -1)[:, :, take]
    return jnp.pad(cols, ((0, 0), (0, 0), (at, wide - at - cols.shape[-1]))).reshape(fan_in, heads * wide)


def _recut(*cuts):
    """An ``nn.Dense``'s ``dot_general`` that multiplies by the kernel as each
    of ``cuts`` returns it (kernel (fan_in, features) -> a matrix), the products
    side by side on the lanes: what a per-head (b, s, h, d) form cuts, pads,
    joins or permutes in the ACTIVATIONS this does to the weights' columns, so
    that every activation is born (b, s, h x d). The module keeps its
    parameter, under its name, shape and initialiser, and stays an ``nn.Dense``
    to whatever wraps one."""
    def dot_general(x, kernel, dims, precision=None):
        return jnp.concatenate([jax.lax.dot_general(x, cut(kernel), dims, precision=precision) for cut in cuts], -1)
    return dot_general


def _rope_tables_on_the_lanes(tokens: int, theta: float, heads: int, dn: int, dr: int, wide: int):
    """``models/transformer.py rope``'s tables for a (b, s, h x wide) array
    whose heads hold their rotated part in lanes [dn, dn + dr): (s, h x wide)
    float32 cos and signed sin, 1 and 0 in the lanes that are not rotated. Read
    off ``rope`` itself while tracing, which is linear: of a head that is 1 in
    its first half and 0 in its second it returns (cos, sin), of the other
    (-sin, cos). A head's window is a constant of the trace, as in ``rope``."""
    from distributed_sigmoid_loss_tpu.models.transformer import rope

    first = np.arange(dr) < dr // 2
    units = np.stack([first, ~first]).astype(np.float32)[:, None, None, :]
    with jax.ensure_compile_time_eval():
        a, b = np.asarray(rope(jnp.broadcast_to(units, (2, tokens, 1, dr)), theta))[:, :, 0]
    pad = ((0, 0), (dn, wide - dn - dr))
    cos, sin = np.pad(np.where(first, a, b), pad, constant_values=1.0), np.pad(np.where(first, b, a), pad)
    return tuple(jnp.tile(jnp.asarray(t, F32), (1, heads)) for t in (cos, sin))


class LatentAttention(nn.Module):
    """Causal latent attention: per head a key part of ``nope_dim`` expanded
    from the ``kv_rank`` latent and one ``shared_dim`` key part shared by all
    heads; value heads of ``v_dim``. ``q_rank > 0`` brings the queries through
    a normalised latent of that width (``q_a``, ``q_norm``, ``q_b`` in place of
    ``q``); ``rope_theta`` rotates the ``shared_dim`` wide parts, each head's
    query part and the one key part, and nothing else.

    Around the core no activation takes the per-head (b, s, h, d) form: XLA
    holds such an array in another tiling than (b, s, h x d), so every view
    between the two around a Mosaic kernel is a copy through HBM (PERF.md
    section 6, PR 36 and 38). Instead the projections' COLUMNS are recut
    (:func:`_recut`) into a head's window of the lanes as
    :func:`latent_attention_core` sizes it (zero columns pad a head): the
    queries with, for the rotation, their exchanged halves beside them (the
    same weights with the columns exchanged: the same products, so the same
    numbers), the keys from the expansion's key columns with the shared part
    placed into every head's window by a 0/1 product, the values from its value
    columns. The pair takes them as they are; the other cores a view."""

    width: int
    num_heads: int
    nope_dim: int
    shared_dim: int
    v_dim: int
    kv_rank: int
    dtype: Any
    norm_eps: float = 1e-5
    attn_impl: str = "auto"
    q_rank: int = 0
    rope_theta: float | None = None

    @nn.compact
    def __call__(self, x):
        from distributed_sigmoid_loss_tpu.models.transformer import _fused_attention_per_shard, rope
        from distributed_sigmoid_loss_tpu.ops import pallas_latent_attention
        from distributed_sigmoid_loss_tpu.ops.flash_attention import flash_self_attention
        from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention

        b, s, _ = x.shape
        h, dn, dr, dv = self.num_heads, self.nope_dim, self.shared_dim, self.v_dim
        dense = partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
        )
        sizes = latent_attention_core(self.attn_impl, self.dtype, s, dn + dr, dv)
        wq, wv, scale = sizes["core_head_dim"], sizes["core_v_dim"], (dn + dr) ** -0.5
        rotated = self.rope_theta is not None
        halves = np.r_[dn + dr // 2 : dn + dr, dn : dn + dr // 2]  # rope's exchange of a head's halves
        cq, q_name = x, "q"
        if self.q_rank:
            cq, q_name = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="q_norm")(
                dense(self.q_rank, name="q_a")(x)
            ), "q_b"
        window = partial(_head_columns, heads=h, wide=wq)
        queries = [partial(window, take=slice(None))] + [partial(window, take=halves, at=dn)] * rotated
        q = dense(h * (dn + dr), name=q_name, dot_general=_recut(*queries))(cq)
        latent = dense(self.kv_rank + dr, name="kv_a")(x)
        c, shared = latent[..., : self.kv_rank], latent[..., self.kv_rank :]
        c = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="kv_norm")(c)
        keys_and_values = _recut(partial(window, take=slice(0, dn)), partial(window, take=slice(dn, dn + dv), wide=wv))
        k, v = jnp.split(dense(h * (dn + dv), name="kv_b", dot_general=keys_and_values)(c), [h * wq], -1)
        if rotated:
            with jax.named_scope(MLA_ROPE_SCOPE):
                q, exchanged = jnp.split(q, 2, -1)
                cos, sin = _rope_tables_on_the_lanes(s, self.rope_theta, h, dn, dr, wq)
                q = (q.astype(F32) * cos + exchanged.astype(F32) * sin).astype(q.dtype)
                shared = rope(shared[:, :, None, :], self.rope_theta)[:, :, 0]  # the one key part as a single head
        place = np.zeros((dr, h, wq), np.float32)  # the one shared part into every head's window: a 0/1 product
        place[np.arange(dr), :, dn + np.arange(dr)] = 1.0
        place = jnp.asarray(place.reshape(dr, h * wq), shared.dtype)
        with jax.named_scope(MLA_CORE_SCOPE):
            k = k + jnp.dot(shared, place, precision=jax.lax.Precision.HIGHEST)  # exact in a float32 tower too
            if sizes["core"] == "kernel":
                core = partial(pallas_latent_attention.latent_attention_kernel, head_dims=(wq, wv), scale=scale)
                out = kernels_per_shard(core, h, q, k, v)  # a Mosaic kernel under a multi-chip jit sits in a shard_map
            else:
                core = partial(flash_self_attention if sizes["core"] == "flash" else dense_attention, causal=True, scale=scale)
                if sizes["core"] == "flash":
                    core = partial(_fused_attention_per_shard, core)
                out = core(*(t.reshape(b, s, h, -1) for t in (q, k, v)))
        if wv != dv:  # a value head narrower than its window: its zero lanes go
            out = out.reshape(b, s, h, wv)[..., :dv]
        return dense(self.width, name="out")(out.astype(self.dtype).reshape(b, s, h * dv))


def eva_attention_core(attn_impl: str, dtype, tokens: int, window: int, chunk: int, head_dim: int) -> dict:
    """Which core an ``EvaAttention`` call takes, from what it can see:
    ``"kernel"`` (the Pallas pair ``eva_attn_fwd`` / ``eva_attn_bwd``,
    ``ops/pallas_eva_attention.py``, on (b, s, h x d)) or ``"dense"`` (XLA, on
    a per-head view). As in ``Attention``: a fused kernel's backward is
    bf16-grade, so "auto" takes it for a bf16 tower on a TPU only, and only
    where the kernels take the shapes (``eva_attention_plan``); "flash" asks for
    it whatever the dtype and fails where they do not. With the core: ``block``
    (the tokens a block of the kernel; None: dense), ``windows`` and
    ``summaries`` a sequence. The mixer runs what this says and the step's
    trace-time record (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.ops.pallas_eva_attention import eva_attention_plan

    if attn_impl == "flash" and not flash_attention.flash_attention_available():
        raise ValueError("attn_impl='flash' requires a TPU backend; use 'auto'")
    plan = eva_attention_plan(tokens, window, chunk, head_dim, jnp.dtype(dtype).itemsize)
    if attn_impl == "flash" and plan is None:
        raise ValueError(f"attn_impl='flash': the eva kernels do not take {tokens} tokens in windows of {window}, "
                         f"chunks of {chunk} and heads of {head_dim}")
    fused = plan is not None and (attn_impl == "flash" or (
        attn_impl == "auto" and jnp.dtype(dtype) == jnp.bfloat16 and flash_attention.flash_attention_available()
    ))
    return {"core": "kernel" if fused else "dense", "block": plan["block"] if fused else None,
            "windows": tokens // window, "summaries": tokens // chunk}


def rope_on_the_lanes(x, heads: int, theta: float):
    """``models/transformer.py rope`` on (b, s, h x d), the heads on the lanes:
    the same tables and the same float32 pass, the exchange of a head's halves
    by two shifts of the whole lane axis (a lane of a head's first half reads d
    / 2 lanes up, of its second half d / 2 lanes down, which never leaves the
    head), so that no per-head (b, s, h, d) view is made. Bit for bit ``rope``'s
    numbers."""
    from distributed_sigmoid_loss_tpu.models.transformer import rope_tables

    s, d = x.shape[1], x.shape[-1] // heads
    cos, sin = (jnp.tile(jnp.asarray(t), (1, heads)) for t in rope_tables(s, d, theta))
    first = jnp.asarray(np.arange(heads * d) % d < d // 2)
    with jax.named_scope("rope"):
        x32 = x.astype(F32)
        swapped = jnp.where(first, jnp.roll(x32, -(d // 2), -1), jnp.roll(x32, d // 2, -1))
        return (x32 * cos + swapped * sin).astype(x.dtype)


def eva_summaries(k, v, phi, mu, chunk: int, dtype):
    """The chunks' pooled keys and values. k, v: (b, s, h x d), k rotated; phi,
    mu: (h, d) float32. a = softmax over a chunk's tokens of (k . phi_h) d^-1/2,
    kc = sum a k + mu_h, vc = sum a v, in float32, returned (b, s / chunk, h x d)
    in ``dtype``. A head's dot with phi and the weights' way back onto its lanes
    are products with (h x d, h) and (h, h x d) matrices that are zero outside
    the head: the MXU does what a per-head view would relayout for."""
    b, s, width = k.shape
    h, d = phi.shape
    of_head = jnp.asarray(np.repeat(np.eye(h, dtype=np.float32), d, axis=0))  # (h x d, h): lane -> its head
    # Operands in the tower's dtype, float32 sums: exact in a float32 tower, one MXU pass in a bf16 one.
    product = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST, preferred_element_type=F32)
    logits = product(k, (of_head * phi.reshape(width, 1)).astype(k.dtype)) * d**-0.5  # (b, s, h)
    a = jax.nn.softmax(logits.reshape(b, s // chunk, chunk, h), axis=2).reshape(b, s, h)
    # (b, s, h x d): each head's weight on its lanes. Float32 out although the values are the operand's: asked for
    # in bf16 the product cost the cell's step 400 ms more under the matmuls' name (PERF.md section 6, PR 39).
    a = product(a.astype(k.dtype), of_head.T.astype(k.dtype))

    def pooled(x):
        return (a * x.astype(F32)).reshape(b, s // chunk, chunk, width).sum(2)

    return (pooled(k) + mu.reshape(width)).astype(dtype), pooled(v).astype(dtype)


def eva_core_dense(q, k, v, kc, vc, *, heads: int, window: int, scale: float):
    """The core in XLA, on a per-head view: q, k, v (b, s, h x d), kc, vc (b, s /
    chunk, h x d) -> (b, s, h x d). Inside a window ``dense_attention``'s causal
    scores; a query of window w also scores every summary of the windows before
    w; one float32 softmax over both sets. A sequence of one window IS
    ``dense_attention``."""
    from distributed_sigmoid_loss_tpu.parallel.ring_attention import _NEG_INF, dense_attention

    b, s, width = q.shape
    n, d = s // window, width // heads
    per_window = kc.shape[1] // n
    if n == 1:
        return dense_attention(*(t.reshape(b, s, heads, d) for t in (q, k, v)), causal=True, scale=scale).reshape(b, s, width)
    q, k, v = (t.reshape(b, n, window, heads, d) for t in (q, k, v))
    kc, vc = (t.reshape(b, n * per_window, heads, d) for t in (kc, vc))
    exact = jnp.einsum("bwqhd,bwkhd->bwhqk", q, k) * scale
    exact = jnp.where(jnp.tril(jnp.ones((window, window), bool)), exact, _NEG_INF)
    remote = jnp.einsum("bwqhd,bchd->bwhqc", q, kc) * scale
    admitted = (np.arange(n * per_window) // per_window)[None, :] < np.arange(n)[:, None]  # (window, summary)
    remote = jnp.where(jnp.asarray(admitted)[None, :, None, None, :], remote, _NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([exact, remote], -1).astype(F32), axis=-1).astype(v.dtype)
    out = (jnp.einsum("bwhqk,bwkhd->bwqhd", probs[..., :window], v)
           + jnp.einsum("bwhqc,bchd->bwqhd", probs[..., window:], vc))
    return out.reshape(b, s, width)


class EvaAttention(nn.Module):
    """Windowed chunk attention: softmax attention that is exact and causal
    inside a window of ``window`` tokens and reads everything before the
    window as one pooled key and value per ``chunk`` tokens, under one softmax
    (the module docstring has the equations). ``num_heads`` heads across
    ``width``, whole heads rotated; two (heads, head size) float32 leaves, the
    pooling's direction ``phi`` and the summaries' key offset ``mu``. q, k, v
    and the summaries stay (b, s, h x d) around the kernel pair; the XLA core
    takes a per-head view."""

    width: int
    num_heads: int
    window: int
    chunk: int
    rope_theta: float
    dtype: Any
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        from distributed_sigmoid_loss_tpu.ops.pallas_eva_attention import eva_attention_kernel

        s = x.shape[1]
        h, d = self.num_heads, self.width // self.num_heads
        dense = partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
        )
        sizes = eva_attention_core(self.attn_impl, self.dtype, s, self.window, self.chunk, d)
        q, k, v = (dense(self.width, name=name)(x) for name in "qkv")
        q, k = rope_on_the_lanes(q, h, self.rope_theta), rope_on_the_lanes(k, h, self.rope_theta)
        phi = self.param("phi", nn.initializers.normal(1.0), (h, d), F32)
        mu = self.param("mu", nn.initializers.normal(1.0), (h, d), F32)
        with jax.named_scope(EVA_SUMMARY_SCOPE):
            kc, vc = eva_summaries(k, v, phi, mu, self.chunk, self.dtype)
        with jax.named_scope(EVA_CORE_SCOPE):
            if sizes["core"] == "kernel":
                core = partial(eva_attention_kernel, head_dim=d, window=self.window, scale=d**-0.5)
                out = kernels_per_shard(core, h, q, k, v, kc, vc)  # a Mosaic kernel under a multi-chip jit sits in a shard_map
            else:
                out = eva_core_dense(q, k, v, kc, vc, heads=h, window=self.window, scale=d**-0.5)
        return dense(self.width, name="out")(out.astype(self.dtype))


class CutDense(nn.Module):
    """A bias-free ``nn.Dense`` whose output comes cut: one ``kernel`` (fan_in,
    sum(cuts)) under the module's name, as ``nn.Dense`` would hold it, and one
    product a cut with that cut's COLUMNS of it. The outputs are what slices of
    the fused product would be, without an activation-sized slice forward or an
    activation-sized join backward (the kernel's gradient is joined instead)."""

    cuts: tuple
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.xavier_uniform(), (x.shape[-1], sum(self.cuts)), F32)
        x, kernel = x.astype(self.dtype), kernel.astype(self.dtype)
        edges = np.cumsum((0, *self.cuts))
        return tuple(jnp.dot(x, kernel[:, a:b]) for a, b in zip(edges[:-1], edges[1:]))


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """The state-space layer's gated RMSNorm, in float32: the gate first, y
    silu(z), then each of the ``groups`` groups of lanes over its own root mean
    square, times ``scale``. y, z: (b, s, inner); scale: (inner,). A group's sum
    of squares and its way back onto the group's lanes are products with a 0/1
    matrix at full precision: a (b, s, groups, inner / groups) view of an array
    whose lanes hold (groups x width) is a relayout through HBM on a TPU."""
    inner = y.shape[-1]
    member = (np.arange(inner)[:, None] // (inner // groups) == np.arange(groups)[None, :]).astype(np.float32)
    y = y.astype(F32) * nn.silu(z.astype(F32))
    hi = jax.lax.Precision.HIGHEST
    mean_sq = jnp.einsum("bsi,ig->bsg", y * y, member, precision=hi) * (groups / inner)
    return y * jnp.einsum("bsg,ig->bsi", jax.lax.rsqrt(mean_sq + eps), member, precision=hi) * scale


class SsmMixer(nn.Module):
    """A Mamba-2 state-space layer (the module docstring has the equations):
    ``num_heads`` heads of ``head_dim`` channels in ``groups`` groups that share B
    and C of ``state`` channels, one fused input projection cut into the gate z,
    the convolved [x' | B | C] and dt, a causal depthwise convolution of
    ``conv_size`` taps with a bias (``short_conv_silu`` on each of x', B and C
    with its columns of the taps and the bias: the Pallas pair ``short_conv_fwd``
    / ``short_conv_bwd`` in bf16 on a TPU, XLA elsewhere), the recurrence
    (``ops/ssm.py``), a gated RMSNorm over each group's lanes, the output projection. ``A_log``,
    ``dt_bias`` and ``D`` are (num_heads,) float32 leaves, initialised as
    Mamba-2's (a rate uniform in [1, 16), a step log-uniform in [1e-3, 1e-1],
    ones); the step is not clamped above."""

    width: int
    num_heads: int
    head_dim: int
    state: int
    groups: int
    conv_size: int
    chunk: int
    dtype: Any
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        from distributed_sigmoid_loss_tpu.ops.ssm import ssm_scan

        b, s, _ = x.shape
        h, g = self.num_heads, self.groups
        inner, shared = h * self.head_dim, g * self.state
        if h % g or inner % g:
            raise ValueError(f"ssm_groups={g} does not divide ssm_num_heads={h}")
        dense = partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
        )
        bound = self.conv_size**-0.5

        def conv_init(key, shape, dtype=F32):
            return jax.random.uniform(key, shape, dtype, -bound, bound)

        # One fused input projection cut five ways, and one convolution over [x | B | C], cut in the WEIGHTS' columns
        # (as ``_recut`` does it): z, x, B, C and dt are born apart, and no activation is sliced or, backwards, joined.
        z, xs, big_b, big_c, dt = CutDense((inner, inner, shared, shared, h), self.dtype, name="in_proj")(x)
        with jax.named_scope(SSM_CONV_SCOPE):
            taps = self.param("conv", conv_init, (self.conv_size, inner + 2 * shared), F32)
            conv_bias = self.param("conv_bias", conv_init, (inner + 2 * shared,), F32)
            edges = (0, inner, inner + shared, inner + 2 * shared)
            xs, big_b, big_c = (
                short_conv_silu(part, taps[:, a:b], conv_bias[a:b])
                for part, a, b in zip((xs, big_b, big_c), edges, edges[1:])
            )
        rate = -jnp.exp(self.param("A_log", _decay_rate_init, (h,), F32))
        dt = jax.nn.softplus(dt.astype(F32) + self.param("dt_bias", _dt_bias_init, (h,), F32))
        skip = self.param("D", nn.initializers.ones, (h,), F32)
        with jax.named_scope(SSM_CORE_SCOPE):
            y = ssm_scan(xs, big_b, big_c, dt, rate, skip, heads=h, groups=g, chunk=self.chunk, dtype=self.dtype)
        y = gated_group_norm(y, z, self.param("norm", nn.initializers.ones, (inner,), F32), g, self.norm_eps)
        return dense(self.width, name="out")(y.astype(self.dtype))
