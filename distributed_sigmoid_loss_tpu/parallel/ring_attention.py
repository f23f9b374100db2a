"""Ring attention: sequence-parallel exact attention over a ``ppermute`` ring.

The reference's ring variant streams the *batch* dimension of contrastive negatives
around a ring (rwightman_sigmoid_loss.py:71-122) — SURVEY.md §5 identifies this as the
blockwise/ring-attention communication topology. This module applies the same topology
to the *sequence* dimension, making long-context towers first-class: each shard holds a
sequence block of Q/K/V; K/V blocks ride the ring ``W-1`` hops while the local Q block
accumulates exact attention via online (flash-style) softmax. Memory per chip stays
O(s_local²) and the ppermute transfer overlaps the block matmul — the standard TPU
recipe for million-token contexts.

Gradients flow through ``lax.scan`` + ``ppermute`` automatically (the VJP re-runs the
ring in reverse), mirroring how the reference's hand-written ``NeighbourExchange``
backward shifts grads the opposite way (distributed_utils.py:74-77).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_sigmoid_loss_tpu.parallel.collectives import pvary, ring_shift_right

__all__ = ["ring_self_attention", "dense_attention"]

_NEG_INF = -1e30


def dense_attention(q, k, v, *, causal=False, scale=None, window=0):
    """Reference single-device attention. q/k/v: (b, s, h, dh) → (b, s, h, dh).
    ``window`` > 0 (causal only) narrows the mask to a band: a query reads its
    own key and the ``window`` - 1 before it."""
    if window and not causal:
        raise ValueError(f"window={window} is a causal band: causal=False is not built")
    dh = q.shape[-1]
    scale = (dh ** -0.5) if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        if window:
            mask &= ~jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q - window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    scale: float | None = None,
    checkpoint_steps: bool = True,
) -> jax.Array:
    """Exact sequence-parallel attention; call inside ``shard_map``.

    Args:
      q, k, v: (b, s_local, h, dh) — this shard's sequence block, where the global
        sequence is the axis-index-ordered concatenation of shards.
      causal: mask using *global* positions (shard offset = axis_index · s_local).
      checkpoint_steps: rematerialize each ring step in the backward pass instead of
        storing per-step logits (the long-context memory trade).

    Returns (b, s_local, h, dh) — this shard's block of the exact attention output.
    """
    w = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, dh = q.shape
    scale = (dh ** -0.5) if scale is None else scale

    q32 = q.astype(jnp.float32)

    def block_update(carry_o, carry_m, carry_l, k_blk, v_blk, src_idx):
        """One online-softmax accumulation of q against a (k,v) block from shard
        ``src_idx``."""
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
        ) * scale
        if causal:
            q_pos = idx * s + lax.broadcasted_iota(jnp.int32, (s, s), 0)
            k_pos = src_idx * s + lax.broadcasted_iota(jnp.int32, (s, s), 1)
            mask = q_pos >= k_pos
            logits = jnp.where(mask[None, None], logits, _NEG_INF)

        m_blk = logits.max(axis=-1)  # (b, h, q)
        m_new = jnp.maximum(carry_m, m_blk)
        # Guard fully-masked rows: keep exp arguments finite.
        corr = jnp.exp(carry_m - m_new)
        p = jnp.exp(logits - m_new[..., None])  # (b, h, q, k)
        l_new = carry_l * corr + p.sum(axis=-1)
        o_new = carry_o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        return o_new, m_new, l_new

    if checkpoint_steps:
        block_update = jax.checkpoint(block_update, static_argnums=())

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        src_idx = (idx - i) % w  # block i hops ago originated at shard idx - i
        o, m, l = block_update(o, m, l, k_blk, v_blk, src_idx)
        # Shift K/V one hop right for the next iteration (last shift is unused but
        # keeps the scan uniform; XLA overlaps it with the block math above).
        k_blk = ring_shift_right(k_blk, axis_name)
        v_blk = ring_shift_right(v_blk, axis_name)
        return (o, m, l, k_blk, v_blk), None

    # Freshly-created constants are "unvarying" under shard_map's varying-axis typing;
    # mark them as varying over the ring axis so the scan carry types line up.
    o0 = pvary(jnp.zeros((b, h, s, dh), jnp.float32), axis_name)
    m0 = pvary(jnp.full((b, h, s), _NEG_INF, jnp.float32), axis_name)
    l0 = pvary(jnp.zeros((b, h, s), jnp.float32), axis_name)

    (o, m, l, _, _), _ = lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(w), length=w
    )

    out = o / jnp.maximum(l[..., None], 1e-38)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def make_ring_attention(mesh, axis_name: str = "sp", **kw):
    """Convenience wrapper: global (b, S, h, dh) arrays in, sequence sharded over
    ``axis_name``."""
    from jax.sharding import PartitionSpec as P

    fn = functools.partial(ring_self_attention, axis_name=axis_name, **kw)
    spec = P(None, axis_name)
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )
    )
