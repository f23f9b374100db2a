"""Streaming 2-D Pallas TPU kernel for the sigmoid-loss hot op.

The loss block (reference distributed_sigmoid_loss.py:22-33) is a matmul →
scale/shift → logsigmoid → reduce chain. The round-3 kernel fused it, but kept
the whole ``(b, d)`` image block VMEM-resident and grid-ded only over text
tiles — so ``local_b`` was bounded by VMEM (at b=4096, d=768 the image block
alone is 12.6 MB, over the ~11 MB budget), which is exactly the wall the
``_32k_equiv`` push hits. This rebuild streams BOTH operands:

- **Forward**: grid over ``(image-tile i, text-tile j)``; each step does one
  ``(tile_b × tile_n)`` MXU matmul and a VPU softplus reduction into a (1, 1)
  scalar accumulator (same VMEM block across the whole grid — TPU grid
  execution is sequential, so the accumulation is race-free). Per-step VMEM is
  ``(tile_b + tile_n)·d·4 + tile_b·tile_n·4`` bytes regardless of ``b``/``n``.
- **Fused backward**: two Pallas kernels recompute each tile's logits and
  accumulate the gradients in VMEM — ``dzimg``/``dt'``/``dbias`` on a
  ``(i, j)`` grid (``dzimg`` tile ``i`` revisited across the inner ``j``
  steps), ``dztxt`` on a transposed ``(j, i)`` grid. No logits matrix, no
  per-tile residual, ever reaches HBM: the VJP residuals are just the
  embeddings (flash-attention-style rematerialization applied to contrastive
  logits), replacing the round-3 XLA-recompute VJP.
- **int8 MXU path** (``quant="int8"``): operands are symmetric-int8 quantized
  with the SAME shared recipe as the inference dot
  (:func:`~distributed_sigmoid_loss_tpu.ops.quant.quantize_int8` — per-row
  abs-max over the contraction axis, computed once outside the kernel) and
  the per-tile product is ``int8×int8→int32`` on the MXU with the identical
  dequant arithmetic as :func:`~distributed_sigmoid_loss_tpu.ops.quant.
  int8_dot_general` — bit-identical per element to the inference int8 dot on
  the same quantized operands. The backward is the STE contract of
  ``int8_dot_general_ste``: the sigmoid is evaluated at the QUANTIZED
  forward's logits, but the ``dzimg``/``dztxt`` dots run on the saved
  full-precision embeddings — the exact unquantized VJP.

Because no more than one ``(tile_b, tile_n)`` tile is ever live, the kernel is
also the chunk-block body for ``loss_impl="chunked"`` (the all-gather scan)
and the ring's per-hop block — the round-7 "memory-optimal OR kernel-fast"
fork is gone.

Falls back to the XLA path for shapes that don't meet the TPU tiling
constraints (see :func:`pallas_compatible`); the choice RESOLVED at trace time
is recorded process-wide (:func:`traced_loss_kernels`) so bench records can
cross-check engagement against argv instead of trusting the flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_sigmoid_loss_tpu.ops.quant import quantize_int8

__all__ = [
    "streaming_block_loss_sum",
    "streaming_block_loss_or_none",
    "pallas_compatible",
    "traced_loss_kernels",
    "reset_traced_loss_kernels",
    "NEGATIVE_ONLY_OFFSET",
    "DEFAULT_TILE_B",
    "DEFAULT_TILE_N",
]

# Sentinel "positive diagonal offset" that never matches any column: the whole
# block is negatives (ring hops after the first, non-positive scan chunks).
# Exactly representable in float32.
NEGATIVE_ONLY_OFFSET = -(2 ** 24)

# Default tile sizes: one MXU-native 128-sublane image tile against a
# 256-lane text tile keeps the per-step working set ~1.2 MB at d=768 (two
# operand tiles, the logits tile and its temporaries) while the 256-wide tile
# amortizes the revisit traffic on zimg.
DEFAULT_TILE_B = 128
DEFAULT_TILE_N = 256

# Every loss-kernel choice RESOLVED at trace time in this process:
# "streaming" / "streaming_int8" when a dispatch picked the kernel, "xla" when
# a use_pallas request fell back to the XLA block. A record claiming
# use_pallas while every block traced the fallback is config drift between
# argv and the program — chip_smoke.py and the tests read THIS,
# not argv (registered in analysis/repo_lint.py MUTABLE_GLOBAL_ALLOWLIST).
_TRACED_LOSS_KERNELS: set[str] = set()


def traced_loss_kernels() -> tuple[str, ...]:
    """Distinct loss-kernel choices resolved at trace time so far, sorted.

    ``()`` = no pallas-requested loss block has been traced in this process;
    ``("streaming",)`` / ``("streaming_int8",)`` = every dispatch engaged the
    kernel; any tuple containing ``"xla"`` = at least one block fell back to
    the XLA path while ``use_pallas`` was requested (shape not tileable).
    """
    return tuple(sorted(_TRACED_LOSS_KERNELS))


def reset_traced_loss_kernels() -> None:
    """Clear the trace record (test isolation)."""
    _TRACED_LOSS_KERNELS.clear()


def pallas_compatible(
    b: int,
    n: int,
    d: int,
    tile_b: int = DEFAULT_TILE_B,
    tile_n: int = DEFAULT_TILE_N,
    quant: bool = False,
) -> bool:
    """TPU tiling constraints for the streaming kernel.

    Tiles clamp to the block (``min(tile, dim)``); the dims must then tile
    evenly, the contraction axis must be lane-aligned (``d % 128``), and the
    tile sublanes must match the operand dtype's sublane quantum — 8 for f32,
    32 for the int8 path (int8 min tile is (32, 128)). Unlike the round-3
    kernel there is NO bound on ``b`` itself: the image block streams
    tile-by-tile instead of sitting whole in VMEM.
    """
    tb, tn = min(tile_b, b), min(tile_n, n)
    sub = 32 if quant else 8
    return (
        b % tb == 0
        and n % tn == 0
        and d % 128 == 0
        and tb % sub == 0
        and tn % sub == 0
    )


# ---------------------------------------------------------------------------
# Kernel bodies (shared tile math).
# ---------------------------------------------------------------------------


def _tile_raw_f32(zimg_blk, ztxt_blk):
    """(tile_b, d) @ (tile_n, d)^T with f32 MXU accumulation."""
    return lax.dot_general(
        zimg_blk,
        ztxt_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _tile_raw_int8(ziq_blk, zis_blk, ztq_blk, zts_blk):
    """int8×int8→int32 tile product, dequantized with the EXACT arithmetic of
    ops.quant.int8_dot_general (``acc.astype(f32) * lhs_scales * rhs_scales``,
    same association order) — per-element bit-identical to the inference int8
    dot on the same quantized operands, since each output element's int32
    accumulation spans the full contraction axis inside one tile."""
    acc = lax.dot_general(
        ziq_blk,
        ztq_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * zis_blk * jnp.squeeze(zts_blk, 1)


def _tile_labels(tile_b, tile_n, i, j, off):
    """±1 labels for tile (i, j): +1 where global col == global row + off."""
    rows = lax.broadcasted_iota(jnp.int32, (tile_b, tile_n), 0) + i * tile_b
    cols = lax.broadcasted_iota(jnp.int32, (tile_b, tile_n), 1) + j * tile_n
    return jnp.where(cols == rows + jnp.int32(off), 1.0, -1.0)


def _fwd_kernel(quant, tp_ref, bias_ref, off_ref, *refs):
    if quant:
        ziq_ref, zis_ref, ztq_ref, zts_ref, out_ref = refs
        raw = _tile_raw_int8(ziq_ref[:], zis_ref[:], ztq_ref[:], zts_ref[:])
        tile_b, tile_n = raw.shape
    else:
        zimg_ref, ztxt_ref, out_ref = refs
        raw = _tile_raw_f32(zimg_ref[:], ztxt_ref[:])
        tile_b, tile_n = raw.shape
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        # Full-ref (1, 1) stores: element-wise scalar stores to VMEM are
        # interpret-mode-only; Mosaic rejects them on hardware.
        out_ref[...] = jnp.zeros_like(out_ref)

    t = jnp.exp(tp_ref[0])
    logits = raw * t + bias_ref[0]
    labels = _tile_labels(tile_b, tile_n, i, j, off_ref[0])
    # -log_sigmoid(x) == softplus(-x)
    out_ref[...] = out_ref[...] + jnp.sum(jax.nn.softplus(-labels * logits))


def _tile_dlogits(quant, tp_ref, bias_ref, off_ref, g_ref, i, j, recompute):
    """Recompute tile (i, j)'s logits and return (dlogits, raw, t).

    ``recompute`` carries the operands the forward actually consumed (f32
    tiles, or quantized tiles + scales) so the sigmoid is evaluated at the
    same point as the forward pass — the STE contract for the int8 path.
    """
    raw = _tile_raw_int8(*recompute) if quant else _tile_raw_f32(*recompute)
    tile_b, tile_n = raw.shape
    t = jnp.exp(tp_ref[0])
    logits = raw * t + bias_ref[0]
    labels = _tile_labels(tile_b, tile_n, i, j, off_ref[0])
    x = labels * logits
    # d/dlogits of softplus(-x) with x = labels*logits: -labels * sigmoid(-x)
    dlogits = g_ref[0] * (-labels * jax.nn.sigmoid(-x))
    return dlogits, raw, t


def _bwd_img_kernel(quant, tp_ref, bias_ref, off_ref, g_ref, *refs):
    """Grid (i, j), j innermost: dzimg tile i accumulates across its j-row in
    VMEM; dt'/dbias accumulate across the whole grid."""
    if quant:
        (ziq_ref, zis_ref, ztq_ref, zts_ref, ztxt_ref,
         dzimg_ref, dtp_ref, dbias_ref) = refs
        recompute = (ziq_ref[:], zis_ref[:], ztq_ref[:], zts_ref[:])
    else:
        zimg_ref, ztxt_ref, dzimg_ref, dtp_ref, dbias_ref = refs
        recompute = (zimg_ref[:], ztxt_ref[:])
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dzimg_ref[...] = jnp.zeros_like(dzimg_ref)

    @pl.when((i == 0) & (j == 0))
    def _():
        dtp_ref[...] = jnp.zeros_like(dtp_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    dlogits, raw, t = _tile_dlogits(
        quant, tp_ref, bias_ref, off_ref, g_ref, i, j, recompute
    )
    # STE: the VJP dot consumes the FULL-PRECISION text tile even when the
    # forward product ran int8 (ops/quant.py int8_dot_general_ste contract).
    dzimg_ref[...] = dzimg_ref[...] + (
        jnp.dot(dlogits, ztxt_ref[:], preferred_element_type=jnp.float32) * t
    )
    dtp_ref[...] = dtp_ref[...] + jnp.sum(dlogits * raw) * t
    dbias_ref[...] = dbias_ref[...] + jnp.sum(dlogits)


def _bwd_txt_kernel(quant, tp_ref, bias_ref, off_ref, g_ref, *refs):
    """Transposed grid (j, i), i innermost: dztxt tile j accumulates across
    its i-column in VMEM."""
    if quant:
        (ziq_ref, zis_ref, ztq_ref, zts_ref, zimg_ref, dztxt_ref) = refs
        recompute = (ziq_ref[:], zis_ref[:], ztq_ref[:], zts_ref[:])
    else:
        zimg_ref, ztxt_ref, dztxt_ref = refs
        recompute = (zimg_ref[:], ztxt_ref[:])
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dztxt_ref[...] = jnp.zeros_like(dztxt_ref)

    dlogits, _, t = _tile_dlogits(
        quant, tp_ref, bias_ref, off_ref, g_ref, i, j, recompute
    )
    dztxt_ref[...] = dztxt_ref[...] + (
        lax.dot_general(
            dlogits,
            zimg_ref[:],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * t
    )


# ---------------------------------------------------------------------------
# pallas_call plumbing (specs, vma typing).
# ---------------------------------------------------------------------------


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _vma_of(*xs) -> frozenset:
    """Union of the inputs' varying-manual-axes (shard_map's replication
    typing). Under ``jax.shard_map`` with ``check_vma=True`` (the default),
    ``pallas_call`` outputs must declare which mesh axes they vary over; the
    loss varies over every axis any input varies over. Outside shard_map this
    is the empty set."""
    vma = frozenset()
    for x in xs:
        vma |= jax.typeof(x).vma
    return vma


def _align_vma(x, vma: frozenset):
    """Upcast ``x`` to vary over every axis in ``vma`` (no-op when aligned)."""
    missing = tuple(vma - _vma_of(x))
    return lax.pcast(x, missing, to="varying") if missing else x


def _struct(shape, vma: frozenset, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _operand_pack(zimg, ztxt, quant, vma):
    """(arrays, in_specs) for the streamed operands: f32 tiles, or quantized
    int8 tiles + per-row scales (shared ops.quant recipe, computed ONCE out
    here — each tile sees its rows' full contraction axis, so per-tile and
    whole-array quantization coincide). Index maps take the kernel's OWN grid
    order: axis 0 of the grid picks the image tile for fwd/bwd-img, the text
    tile for bwd-txt — callers pass ``img_axis``/``txt_axis`` accordingly."""
    del vma  # aligned by the callers on the packed arrays

    def pack(img_axis, txt_axis, tile_b, tile_n, d):
        def at(axis):
            return lambda *ids: (ids[axis], 0)

        if quant:
            ziq, zis = quantize_int8(zimg, axis=1)
            ztq, zts = quantize_int8(ztxt, axis=1)
            arrays = (ziq, zis, ztq, zts)
            specs = [
                pl.BlockSpec((tile_b, d), at(img_axis), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_b, 1), at(img_axis), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_n, d), at(txt_axis), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile_n, 1), at(txt_axis), memory_space=pltpu.VMEM),
            ]
            return arrays, specs
        arrays = (zimg, ztxt)
        specs = [
            pl.BlockSpec((tile_b, d), at(img_axis), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, d), at(txt_axis), memory_space=pltpu.VMEM),
        ]
        return arrays, specs

    return pack


def streaming_block_loss_or_none(
    zimg,
    ztxt,
    t_prime,
    bias,
    pos_offset,
    *,
    quant: str = "",
    tile_b: int = DEFAULT_TILE_B,
    tile_n: int = DEFAULT_TILE_N,
    normalize: bool = True,
):
    """Dispatch helper for the distributed variants: the streaming block loss
    when shapes meet the TPU tiling constraints, else ``None`` (caller falls
    back to the XLA path). Records the trace-time choice, handles shard_map
    vma alignment and interpret-mode selection (CPU tests) in one place.

    ``normalize=True`` returns the per-image-normalized block loss (what the
    fused/ring block call sites consume); ``normalize=False`` returns the raw
    block SUM (what the chunked scan accumulates before its own ``/ n_img``).
    """
    b, d = zimg.shape
    n = ztxt.shape[0]
    if not pallas_compatible(b, n, d, tile_b, tile_n, quant=bool(quant)):
        _TRACED_LOSS_KERNELS.add("xla")
        return None
    _TRACED_LOSS_KERNELS.add("streaming_int8" if quant else "streaming")
    interpret = jax.default_backend() != "tpu"
    total = streaming_block_loss_sum(
        zimg, ztxt, t_prime, bias,
        jnp.asarray(pos_offset, jnp.float32),
        quant, min(tile_b, b), min(tile_n, n), interpret,
    )
    return total / b if normalize else total


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def streaming_block_loss_sum(
    zimg, ztxt, t_prime, bias, pos_offset,
    quant="", tile_b=DEFAULT_TILE_B, tile_n=DEFAULT_TILE_N, interpret=False,
):
    """SUM of ``-log_sigmoid(labels * (exp(t_prime)·raw + bias))`` over the
    (b × n) block, positives on ``col == row + pos_offset`` (pass
    ``NEGATIVE_ONLY_OFFSET`` for an all-negatives block); ``raw`` is the
    f32-accumulated MXU product, or the int8-dequantized product when
    ``quant="int8"``. Unnormalized — divide by the local batch outside, as the
    reference does (distributed_sigmoid_loss.py:47). ``tile_b``/``tile_n``
    must already be clamped to the block and pass :func:`pallas_compatible`
    (use :func:`streaming_block_loss_or_none` unless you have a reason not
    to)."""
    loss, _ = _fwd(
        zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n, interpret
    )
    return loss


def _prep(zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n, *extra):
    b, d = zimg.shape
    n = ztxt.shape[0]
    assert pallas_compatible(b, n, d, tile_b, tile_n, quant=bool(quant)), (
        b, n, d, tile_b, tile_n, quant,
    )
    vma = _vma_of(zimg, ztxt, t_prime, bias, pos_offset, *extra)
    scalars = [
        _align_vma(jnp.reshape(t_prime.astype(jnp.float32), (1,)), vma),
        _align_vma(jnp.reshape(bias.astype(jnp.float32), (1,)), vma),
        _align_vma(
            jnp.reshape(jnp.asarray(pos_offset, jnp.float32), (1,)), vma
        ),
    ]
    pack = _operand_pack(
        zimg.astype(jnp.float32), ztxt.astype(jnp.float32), bool(quant), vma
    )
    return b, n, d, vma, scalars, pack


def _fwd(zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n, interpret):
    b, n, d, vma, scalars, pack = _prep(
        zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n
    )
    arrays, specs = pack(0, 1, tile_b, tile_n, d)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, bool(quant)),
        grid=(b // tile_b, n // tile_n),
        in_specs=[_scalar_spec()] * 3 + specs,
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_struct((1, 1), vma),
        interpret=interpret,
        name="loss_fwd",  # what a profile calls this kernel
    )(*scalars, *(_align_vma(a, vma) for a in arrays))
    loss = out[0, 0]
    return loss, (zimg, ztxt, t_prime, bias, pos_offset)


def _bwd(quant, tile_b, tile_n, interpret, res, g):
    zimg, ztxt, t_prime, bias, pos_offset = res
    b, n, d, vma, scalars, pack = _prep(
        zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n, g
    )
    scalars.append(_align_vma(jnp.reshape(g.astype(jnp.float32), (1,)), vma))
    zimg32 = _align_vma(zimg.astype(jnp.float32), vma)
    ztxt32 = _align_vma(ztxt.astype(jnp.float32), vma)

    def vspec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    # Pass 1 — grid (i, j), j innermost: dzimg tile i stays resident across
    # its j-row; dt'/dbias ride the same (1, 1) block across the whole grid.
    # The f32 pack already carries the full-precision text tile the VJP dot
    # consumes; only the int8 pack (quantized recompute operands) needs it
    # appended separately.
    arrays, specs = pack(0, 1, tile_b, tile_n, d)
    extra = ((ztxt32,), [vspec((tile_n, d), lambda i, j: (j, 0))]) if quant \
        else ((), [])
    dzimg, dtp, dbias = pl.pallas_call(
        functools.partial(_bwd_img_kernel, bool(quant)),
        grid=(b // tile_b, n // tile_n),
        in_specs=[_scalar_spec()] * 4 + specs + extra[1],
        out_specs=[
            vspec((tile_b, d), lambda i, j: (i, 0)),
            vspec((1, 1), lambda i, j: (0, 0)),
            vspec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            _struct((b, d), vma),
            _struct((1, 1), vma),
            _struct((1, 1), vma),
        ],
        interpret=interpret,
        name="loss_bwd_img",
    )(*scalars, *(_align_vma(a, vma) for a in arrays), *extra[0])

    # Pass 2 — transposed grid (j, i), i innermost: dztxt tile j resident
    # across its i-column. One extra logit recompute vs a single-pass kernel;
    # the price of never parking either gradient block in HBM mid-grid.
    arrays, specs = pack(1, 0, tile_b, tile_n, d)
    extra = ((zimg32,), [vspec((tile_b, d), lambda j, i: (i, 0))]) if quant \
        else ((), [])
    (dztxt,) = pl.pallas_call(
        functools.partial(_bwd_txt_kernel, bool(quant)),
        grid=(n // tile_n, b // tile_b),
        in_specs=[_scalar_spec()] * 4 + specs + extra[1],
        out_specs=[vspec((tile_n, d), lambda j, i: (j, 0))],
        out_shape=[_struct((n, d), vma)],
        interpret=interpret,
        name="loss_bwd_txt",
    )(*scalars, *(_align_vma(a, vma) for a in arrays), *extra[0])

    return (
        dzimg.astype(zimg.dtype),
        dztxt.astype(ztxt.dtype),
        dtp[0, 0].astype(t_prime.dtype),
        dbias[0, 0].astype(bias.dtype),
        jnp.zeros_like(jnp.asarray(pos_offset, jnp.float32)),
    )


def _fwd_rule(zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n,
              interpret):
    return _fwd(
        zimg, ztxt, t_prime, bias, pos_offset, quant, tile_b, tile_n, interpret
    )


streaming_block_loss_sum.defvjp(_fwd_rule, _bwd)
