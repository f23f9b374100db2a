"""Token mixers of a stack of several layer kinds (``TextConfig.mixers``), beside
the block's softmax ``Attention`` (models/transformer.py): a gated delta-rule
layer ("kda") and latent attention ("mla"). Imported only where a configuration
names one. Both are causal and carry no bias; their statistics, gates and decays
are float32 whatever the tower's dtype. The recurrence takes no position
encoding; latent attention none, or a rotation of its shared-width parts.

A KDA layer keeps every array (b, s, h x d), a head an aligned window of the
lanes, as its kernels read and write them; its per-head statistics (the l2 norm
of q and k, the RMS of o) run where the core runs (``ops/gated_delta_rule.py
delta_rule_core``): on the head's tile inside the Pallas kernels, or in XLA on a
(b, s, h, d) view around the chunked form (the CPU, float32). On a TPU the two
shapes are two tilings, so a per-head view around a reduction over d is a copy
through HBM each way (PERF.md section 6, PR 36).

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
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import (
    normed_chunk_gated_delta_rule,
    short_causal_conv,
)

F32 = jnp.float32
# The program's names for these layers' device time (benchmark/scopes_kimi.py):
# flax writes the modules' own names ("kda", "mla") into every operation's path.
KDA_CORE_SCOPE = "kda_core"  # the recurrence alone, inside "kda"
MLA_CORE_SCOPE = "mla_core"  # scores, softmax and values, inside "mla"
MLA_ROPE_SCOPE = "mla_rope"  # the rotation of the queries' and the key's shared-width parts, inside "mla"
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
    value channels each, chunks of ``CHUNK`` tokens."""

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
            return nn.silu(short_causal_conv(y, taps))

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


def one_head_size(dqk: int, dv: int, multiple: int = 1) -> int:
    """The head size ``pad_heads_to_one_size`` runs a core at."""
    return -(-max(dqk, dv) // multiple) * multiple


def pad_heads_to_one_size(attend, q, k, v, multiple: int = 1):
    """Run an attention core that takes one head size on query/key heads and
    value heads of two: the narrower are zero-padded to the wider, up to a
    ``multiple`` of lanes, and the output cut back to the value's width, which
    is exact (a zero value channel stays zero, zero channels add nothing to a
    score). Heads that are one size, a multiple already, reach the core as they are."""
    dv = v.shape[-1]
    wide = one_head_size(q.shape[-1], dv, multiple)

    def pad(t):
        return jnp.pad(t, ((0, 0),) * 3 + ((0, wide - t.shape[-1]),))

    return attend(pad(q), pad(k), pad(v))[..., :dv]


def latent_attention_core(attn_impl: str, dtype) -> str:
    """Which core a ``LatentAttention`` call takes, from what it can see:
    ``"flash"`` (the library's blocked kernel, which never writes the (b, h, s,
    s) scores to HBM) or ``"dense"`` (XLA). As in ``Attention``: the fused
    kernel's backward is bf16-grade, so "auto" takes it for a bf16 tower on a
    TPU only. The mixer runs what this says and the step's trace-time record
    (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops.flash_attention import flash_attention_available

    if attn_impl == "flash" and not flash_attention_available():
        raise ValueError("attn_impl='flash' requires a TPU backend; use 'auto'")
    fused = attn_impl == "flash" or (
        attn_impl == "auto" and jnp.dtype(dtype) == jnp.bfloat16 and flash_attention_available()
    )
    return "flash" if fused else "dense"


class LatentAttention(nn.Module):
    """Causal latent attention: per head a key part of ``nope_dim`` expanded
    from the ``kv_rank`` latent and one ``shared_dim`` key part shared by all
    heads; value heads of ``v_dim``. ``q_rank > 0`` brings the queries through
    a normalised latent of that width (``q_a``, ``q_norm``, ``q_b`` in place of
    ``q``); ``rope_theta`` rotates the ``shared_dim`` wide parts, each head's
    query part and the one key part, and nothing else."""

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
        from distributed_sigmoid_loss_tpu.ops.flash_attention import flash_self_attention
        from distributed_sigmoid_loss_tpu.parallel.ring_attention import dense_attention

        b, s, _ = x.shape
        h, dn, dr, dv = self.num_heads, self.nope_dim, self.shared_dim, self.v_dim
        dense = partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
        )
        if self.q_rank:
            cq = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="q_norm")(
                dense(self.q_rank, name="q_a")(x)
            )
            q = dense(h * (dn + dr), name="q_b")(cq)
        else:
            q = dense(h * (dn + dr), name="q")(x)
        q = q.reshape(b, s, h, dn + dr)
        latent = dense(self.kv_rank + dr, name="kv_a")(x)
        c, shared = latent[..., : self.kv_rank], latent[..., self.kv_rank :]
        c = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="kv_norm")(c)
        expanded = dense(h * (dn + dv), name="kv_b")(c).reshape(b, s, h, dn + dv)
        if self.rope_theta is not None:
            with jax.named_scope(MLA_ROPE_SCOPE):  # the one key part as a single head
                q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], self.rope_theta)], -1)
                shared = rope(shared[:, :, None, :], self.rope_theta)[:, :, 0]
        k = jnp.concatenate(
            [expanded[..., :dn], jnp.broadcast_to(shared[:, :, None, :], (b, s, h, dr))], -1
        )
        v = expanded[..., dn:]
        fused = latent_attention_core(self.attn_impl, self.dtype) == "flash"
        core = partial(flash_self_attention if fused else dense_attention, causal=True, scale=(dn + dr) ** -0.5)
        if fused:  # a Mosaic kernel under a multi-chip jit sits in a shard_map
            core = partial(_fused_attention_per_shard, core)
        with jax.named_scope(MLA_CORE_SCOPE):
            out = pad_heads_to_one_size(core, q, k, v, multiple=FUSED_LANES if fused else 1)
        out = out.astype(self.dtype).reshape(b, s, h * dv)
        return dense(self.width, name="out")(out)
