"""Compressed gradient synchronization for the slow (DCN) mesh axis.

Multi-slice data parallelism syncs gradients over two very different links:
ICI within a slice (~100s of GB/s per chip) and DCN between slices (~GB/s per
host). The reference's world does the whole sync in one NCCL all-reduce at
f32 (its test harness's ``average_gradients`` = ``all_reduce(SUM)/W``,
/root/reference/test_distributed_sigmoid_loss.py:79-83); production DLRM/LLM
systems compress the slow hop (Zhang et al., "Dual-Level Adaptive Lossy
Compression", arXiv:2407.04272; Abrahamyan et al., "Learned Gradient
Compression", arXiv:2103.08870 — PAPERS.md). This module is the TPU-native
split of that all-reduce by link speed:

- **ICI hop**: plain f32 ``psum`` over the ``dp`` axis — bandwidth is ample,
  precision is free.
- **DCN hop**: per-tensor symmetric **int8** quantization + ``all_gather`` of
  the int8 payloads (+ one f32 scale per tensor) over the ``dcn`` axis, then
  a local dequantized mean — 4x fewer bytes on the slow wire than f32
  all-reduce at dcn=2 (the common 2-slice case), with **error feedback**
  (Seide et al. 1-bit SGD; Karimireddy et al. EF-SGD) carrying each slice's
  quantization residual into its next step so the bias does not accumulate.

Used inside a fully-manual ``shard_map`` over ``(dcn, dp)`` — see
``train/compressed_step.py``. All functions here are pure and collective-free
except :func:`compressed_axis_mean`, which all-gathers over ``axis_name``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "quantize_tensor_int8",
    "dequantize_tensor_int8",
    "sparsify_topk",
    "densify_topk",
    "compressed_axis_mean",
    "init_error_feedback",
]

_QMAX = 127.0
_EPS = 1e-12


def quantize_tensor_int8(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: ``(q, scale)`` with ``q * scale ~= t``.

    Per-tensor (not per-row) scales: gradient tensors are well-conditioned
    after the ICI psum averages ``dp`` microbatches, and error feedback
    absorbs what the coarse scale loses — while the wire format stays ONE
    f32 per tensor.
    """
    scale = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32))), _EPS) / _QMAX
    q = jnp.clip(
        jnp.round(t.astype(jnp.float32) / scale), -_QMAX, _QMAX
    ).astype(jnp.int8)
    return q, scale


def dequantize_tensor_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def sparsify_topk(
    t: jax.Array, k: int, approximate: bool = True
) -> tuple[jax.Array, jax.Array]:
    """Top-``k``-by-magnitude sparsification: ``(values, flat_indices)``.

    The OTHER standard wire format for gradient compression (deep gradient
    compression / EF-SGD with sparsification): keep the k largest-|.| entries,
    error feedback carries the rest. Wire cost 8 bytes/kept entry (f32 value +
    int32 index) vs 4 bytes/entry dense — a win for k/size < ~1/2, typically
    run at 1%.

    ``approximate=True`` (default) selects via ``lax.approx_max_k`` — the
    TPU-optimized bucketed top-k. Measured on chip at b16 gradient scale
    (before PR 22): exact ``lax.top_k`` costs 227 ms/step (61% of a train
    step — compute-prohibitive), approx 55 ms at 98.5% recall. Bucketed
    selection can occasionally miss entries ABOVE the k-th magnitude (bucket
    collisions keep only the bucket max), so approximation is only sound
    together with error feedback: whatever is missed — large or small —
    rides the residual into the next step. Use it with EF (the compressed
    train step already requires EF for topk).
    """
    flat = t.astype(jnp.float32).ravel()
    if approximate:
        _, idx = lax.approx_max_k(jnp.abs(flat), k)
    else:
        _, idx = lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    return flat[idx], idx


def densify_topk(values: jax.Array, idx: jax.Array, size: int) -> jax.Array:
    """Scatter ``values`` back to a flat zeros(size) (inverse of sparsify)."""
    return jnp.zeros((size,), jnp.float32).at[idx].add(values)


def init_error_feedback(params, n_slices: int):
    """Zero error-feedback state: one f32 residual tree per DCN slice.

    Leaves are ``(n_slices, *param.shape)`` so the global state shards over
    the ``dcn`` axis (each slice holds only ITS residual — one param-sized
    f32 tree per device group, the same budget as one adam moment).
    """
    return jax.tree.map(
        lambda p: jnp.zeros((n_slices,) + p.shape, jnp.float32), params
    )


def compressed_axis_mean(tree, axis_name: str, ef=None, method: str = "int8",
                         topk_frac: float = 0.01,
                         topk_approximate: bool = True):
    """Mean of ``tree`` over the (slow) ``axis_name`` with a compressed wire.

    Must run inside ``shard_map`` manual over ``axis_name``. ``tree`` holds
    this member's local contribution (already averaged over any fast axes).
    ``ef`` is this member's error-feedback tree (same structure, leaves with
    a leading size-1 slice dim from the ``P(axis_name)`` in_spec) or None.

    ``method``: ``"int8"`` (per-tensor symmetric quantization, 4x fewer
    bytes) or ``"topk"`` (top-``topk_frac``-by-magnitude sparsification,
    8 bytes/kept entry — ~50x fewer at the standard 1%; run it WITH error
    feedback, the dropped 99% is pure bias otherwise).
    ``topk_approximate=False`` switches the topk selection to exact
    ``lax.top_k`` (4x slower on TPU at gradient scale, see ``sparsify_topk``).

    Returns ``(mean_tree, new_ef)`` — ``mean_tree`` replicated over the axis,
    ``new_ef`` the residual ``(t + ef) - decompress(compress(t + ef))`` to
    carry into the next step (None if ``ef`` is None).
    """
    if method not in ("int8", "topk"):
        raise ValueError(f"unknown compression method: {method!r}")
    n = lax.axis_size(axis_name)

    def one(t, e):
        target = t if e is None else t + jnp.squeeze(e, 0).astype(t.dtype)
        if method == "int8":
            q, s = quantize_tensor_int8(target)
            sent = dequantize_tensor_int8(q, s)
            qs = lax.all_gather(q, axis_name)    # int8 on the wire
            ss = lax.all_gather(s, axis_name)    # one f32 scale per member
            mean = jnp.sum(
                qs.astype(jnp.float32)
                * ss.reshape((n,) + (1,) * t.ndim), axis=0
            ) / n
        else:
            k = max(1, int(round(topk_frac * t.size)))
            vals, idx = sparsify_topk(target, k, approximate=topk_approximate)
            sent = densify_topk(vals, idx, t.size).reshape(t.shape)
            all_vals = lax.all_gather(vals, axis_name)   # (n, k) f32
            all_idx = lax.all_gather(idx, axis_name)     # (n, k) int32
            mean = (
                jnp.zeros((t.size,), jnp.float32)
                .at[all_idx.ravel()]
                .add(all_vals.ravel())
                .reshape(t.shape)
            ) / n
        new_e = None
        if e is not None:
            new_e = (target.astype(jnp.float32) - sent)[None]
        return mean.astype(t.dtype), new_e

    if ef is None:
        mean = jax.tree.map(lambda t: one(t, None)[0], tree)
        return mean, None
    flat_t, treedef = jax.tree.flatten(tree)
    flat_e = treedef.flatten_up_to(ef)
    out = [one(t, e) for t, e in zip(flat_t, flat_e)]
    mean = treedef.unflatten([m for m, _ in out])
    new_ef = treedef.unflatten([e for _, e in out])
    return mean, new_ef
