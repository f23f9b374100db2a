"""Fused (flash) self-attention on TPU via the Pallas MXU kernel.

The towers' dense attention materializes the (b, h, s, s) logits and f32 softmax in
HBM — at ViT-B/16 scale that is the single largest activation (7G+ per step at
batch 256, see the OOM allocation report) and a pure bandwidth tax. The Pallas flash
kernel (jax.experimental.pallas.ops.tpu.flash_attention) streams K/V blocks through
VMEM with an online softmax, so nothing O(s²) ever touches HBM, and its custom VJP
recomputes blocks in the backward pass instead of storing them.

This wrapper adapts the kernel to the towers' (b, s, h, dh) layout and to sequence
lengths that aren't block-aligned (ViT-B/16 has s=196): inputs are zero-padded to a
block multiple and masked via segment ids (pad tokens get a different segment id, so
real queries never attend them; padded query rows are sliced off afterwards).

Who calls it: ``Attention`` (models/transformer.py) for a sequence past the
VMEM-resident kernel (ops/pallas_short_attention.py; 638 tokens at the towers'
widths), and ``LatentAttention`` (models/mixers.py) only for a sequence whose head
does not fit the VMEM of the repo's own causal kernel pair
(ops/pallas_latent_attention.py, which took its place in both routed cells in PR 38:
the pair works on (b, s, h x d) and needs none of the transposes below).

There is no reference analogue (the reference has no model layer — SURVEY.md §1); this
is TPU-first engineering for the BASELINE.json end-to-end throughput target.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["flash_self_attention", "flash_attention_available", "flash_attention_plan"]

# The kernel's minor-most compute tile: sequence blocks must be multiples of this to
# satisfy the (8, 128) f32 / (16, 128) bf16 TPU tiling on the logits' lane dim.
_SEQ_MULTIPLE = 128


def flash_attention_available() -> bool:
    """True when the current default backend can run the Pallas TPU kernel."""
    return jax.default_backend() == "tpu"


def _pad_len(s: int) -> int:
    return (s + _SEQ_MULTIPLE - 1) // _SEQ_MULTIPLE * _SEQ_MULTIPLE


def _block_size(s_pad: int) -> int:
    """Largest power-of-two block ≤512 dividing the padded length — the kernel
    requires divisibility in BOTH grid directions (backward also blocks q)."""
    return next(b for b in (512, 256, 128) if s_pad % b == 0)


def flash_attention_plan(tokens: int) -> dict:
    """What a call at this length runs at, from the length alone (as
    ``ops/pallas_latent_attention.py latent_attention_plan`` says of the repo's
    pair): ``tokens``, the sequence zero-padded to the kernel's tile, and
    ``block``, the tokens a block in every direction."""
    padded = _pad_len(tokens)
    return {"block": _block_size(padded), "tokens": padded}


def _prepare_inputs(q, k, v):
    """Transpose to the kernel's (b, h, s, dh) layout, zero-pad the sequence to a
    block multiple, and build pad-masking segment ids.

    Returns ``(qt, kt, vt, segment_id_rows, s_pad)`` where ``segment_id_rows`` is the
    per-position (b, s_pad) int32 id array (1 = real token, 0 = padding) or ``None``
    when no padding was needed. Real queries never attend padding (different segment);
    padded query rows attend only padding (finite softmax) and are sliced off after
    the kernel.
    """
    b, s, h, dh = q.shape
    s_pad = _pad_len(s)

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))

    ids = None
    if s_pad != s:
        pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
        qt, kt, vt = (jnp.pad(t, pad) for t in (qt, kt, vt))
        ids = (jnp.arange(s_pad, dtype=jnp.int32) < s).astype(jnp.int32)
        ids = jnp.broadcast_to(ids[None], (b, s_pad))
    return qt, kt, vt, ids, s_pad


def flash_self_attention(
    q, k, v, *, causal: bool = False, scale: float | None = None, kernel_fn=None
):
    """Drop-in replacement for ``dense_attention``: (b, s, h, dh) → (b, s, h, dh).

    Self-attention only (q/k/v share a sequence length). Numerics match the dense
    path (f32 online softmax) up to flash's blockwise summation order.

    ``kernel_fn(qt, kt, vt, segment_ids, causal, sm_scale, block_sizes)`` overrides
    the Pallas kernel — used by CPU tests to verify the padding/masking/slicing
    plumbing with a dense stand-in kernel.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        SegmentIds,
        flash_attention,
    )

    b, s, h, dh = q.shape
    sm_scale = (dh**-0.5) if scale is None else scale
    qt, kt, vt, ids, s_pad = _prepare_inputs(q, k, v)
    segment_ids = SegmentIds(q=ids, kv=ids) if ids is not None else None

    block = _block_size(s_pad)
    block_sizes = BlockSizes(
        block_q=block,
        block_k_major=block,
        block_k=block,
        block_b=1,
        block_q_major_dkv=block,
        block_k_major_dkv=block,
        block_k_dkv=block,
        block_q_dkv=block,
        block_k_major_dq=block,
        block_k_dq=block,
        block_q_dq=block,
    )
    kernel = kernel_fn if kernel_fn is not None else flash_attention
    out = kernel(
        qt,
        kt,
        vt,
        segment_ids=segment_ids,
        causal=causal,
        sm_scale=sm_scale,
        block_sizes=block_sizes,
    )
    return jnp.transpose(out[:, :, :s, :], (0, 2, 1, 3))
