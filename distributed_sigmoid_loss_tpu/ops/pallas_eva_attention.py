"""Windowed chunk attention's core as two Pallas TPU kernels, ``eva_attn_fwd``
and ``eva_attn_bwd``: softmax attention that is exact and causal inside a
window of ``window`` tokens and reads every EARLIER window as one pooled key and
value per chunk (its summaries), all under one softmax. A sibling of
``mla_attn_fwd`` / ``mla_attn_bwd`` (ops/pallas_latent_attention.py), whose
layout, block loops and numbers it keeps; what is new is the second, short
key/value stream and the joint normaliser.

Operands: q, k, v (b, s, h x d) and the summaries kc, vc (b, s / chunk, h x d),
a head an aligned window of the lanes, so nothing is transposed or copied
around the pair. One program = one batch row, one head and one window: the
window's q, k, v (o and the cotangents) once from and to HBM, and the head's
summaries, which stay in VMEM while the grid walks the head's windows (the
window is the grid's last axis, so their block does not move). A query of
window w admits the summaries of windows < w, every one of them, and no other:
the staircase is by window, so inside a program no summary is masked, and the
loop over them simply ends at w (window 0 runs none).

Forward, query-major: per query block one running maximum, sum and float32
accumulator over, in turn, the admitted summary blocks (``window / chunk``
summaries each), the key blocks under the diagonal, unmasked, and the diagonal
block under the causal mask; normalised once. It leaves o and one float32
log-sum-exp a row, over BOTH sets, stored lane-major.

Backward, key-major, one kernel: ``di`` = rowsum(o do) from the tiles it holds,
then the five products of the chain for every admitted summary block against
every query block of the window (dkc, dvc summed in registers over the query
blocks, then into a float32 VMEM scratch that stays while the grid walks the
head's windows: the grid's last axis is sequential, and the scratch is written
out after the last window) and for every key block against the query blocks
from the diagonal down; dq of the window in a float32 VMEM scratch.

Numbers as the sibling pair: operands in the tower's dtype into every product,
float32 accumulation, scores, statistics and accumulators, p and ds cast to the
operands' dtype only before the products they feed, exact exp and reciprocal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_sigmoid_loss_tpu.ops.pallas_latent_attention import LANES, _as_row, _dot, _rows

__all__ = ["eva_attention_kernel", "eva_attention_plan"]

F32 = jnp.float32
_NEG = -1e30
_BLOCK = 512  # tokens a block: the side of the (block, block) float32 tiles a program works on
_VMEM_LIMIT = 64 * 2**20
_SUBLANES = 16  # rows of a packed bf16 tile: a window's summaries are whole tiles


def eva_attention_plan(tokens: int, window: int, chunk: int, head_dim: int, itemsize: int = 2) -> dict | None:
    """What a call at these sizes runs at, from shapes alone: ``block`` (tokens
    a block), ``windows`` and ``summaries`` a sequence, ``per_window`` (the
    summaries of one window, a block of the second stream). None where the
    kernels do not take the shapes: a head that is not whole 128-lane
    registers, a window that is not whole blocks of whole 128-token tiles, or a
    window's summaries that are not whole sublane tiles."""
    if head_dim % LANES or tokens % window or window % chunk or window % LANES:
        return None
    block = min(_BLOCK, window)
    per_window = window // chunk
    if window % block or per_window % _SUBLANES:
        return None
    held = 2 * (8 * window + 4 * tokens // chunk) * head_dim * itemsize + (window + 2 * tokens // chunk) * head_dim * 4
    if held + 12 * block * block * 4 > _VMEM_LIMIT:
        return None
    return {"block": block, "windows": tokens // window, "summaries": tokens // chunk, "per_window": per_window}


def _fwd_kernel(q_ref, k_ref, v_ref, kc_ref, vc_ref, o_ref, lse_ref, *, scale, block, per_window):
    """One window of one head of one row: per query block the online softmax
    over the summaries of the windows before this one, then over its key blocks
    under the diagonal, then over the diagonal's."""
    w = pl.program_id(2)
    n = q_ref.shape[1] // block
    dv = v_ref.shape[2]
    visible = (lax.broadcasted_iota(jnp.int32, (block, block), 0)
               >= lax.broadcasted_iota(jnp.int32, (block, block), 1))

    def query_block(i, carry):
        rows = _rows(i, block)
        q = q_ref[0, rows, :]

        def step(k, v, stats, mask):
            m, l, acc = stats
            x = _dot(q, k, 1, 1) * scale  # (queries, keys) f32
            if mask:
                x = jnp.where(visible, x, _NEG)
            m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(x - m_new)
            return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + _dot(p.astype(v.dtype), v, 1, 0))

        def summaries(c, stats):
            at = _rows(c, per_window)
            return step(kc_ref[0, at, :], vc_ref[0, at, :], stats, False)

        def keys(j, stats):
            at = _rows(j, block)
            return step(k_ref[0, at, :], v_ref[0, at, :], stats, False)

        stats = (jnp.full((block, 1), _NEG, F32), jnp.zeros((block, 1), F32), jnp.zeros((block, dv), F32))
        stats = lax.fori_loop(0, w, summaries, stats)
        stats = lax.fori_loop(0, i, keys, stats)
        m, l, acc = step(k_ref[0, rows, :], v_ref[0, rows, :], stats, True)
        o_ref[0, rows, :] = (acc * (1.0 / l)).astype(o_ref.dtype)
        lse_ref[0, 0, 0, pl.ds(i, 1), :] = _as_row(m + jnp.log(l))
        return carry

    lax.fori_loop(0, n, query_block, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, kc_ref, vc_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dkc_ref, dvc_ref, dq_acc, di_ref, dkc_acc, dvc_acc, *,
                scale, block, per_window):
    """One window of one head of one row: di of every query block, the chain
    for the admitted summaries against every query block, then per key block
    over the query blocks from the diagonal down."""
    w, last = pl.program_id(2), pl.num_programs(2) - 1
    n = q_ref.shape[1] // block
    visible = (lax.broadcasted_iota(jnp.int32, (block, block), 0)
               <= lax.broadcasted_iota(jnp.int32, (block, block), 1))  # (keys, queries)

    @pl.when(w == 0)
    def _():
        dkc_acc[...] = jnp.zeros(dkc_acc.shape, F32)
        dvc_acc[...] = jnp.zeros(dvc_acc.shape, F32)

    def di_of(i, carry):
        rows = _rows(i, block)
        di = jnp.sum(o_ref[0, rows, :].astype(F32) * do_ref[0, rows, :].astype(F32), axis=1, keepdims=True)
        di_ref[pl.ds(i, 1), :] = _as_row(di)
        return carry

    lax.fori_loop(0, n, di_of, 0)
    dq_acc[...] = jnp.zeros(dq_acc.shape, F32)

    def pair(k, v, i, grads, mask):
        """A key (or summary) block against query block i: (dk, dv) gain their
        terms, dq of the block its own."""
        dk, dv = grads
        rows = _rows(i, block)
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        xt = _dot(k, q, 1, 1) * scale  # (keys, queries) f32
        if mask:
            xt = jnp.where(visible, xt, _NEG)
        pt = jnp.exp(xt - lse_ref[0, 0, 0, pl.ds(i, 1), :])
        dv = dv + _dot(pt.astype(do.dtype), do, 1, 0)
        dpt = _dot(v, do, 1, 1)
        dst = ((dpt - di_ref[pl.ds(i, 1), :]) * pt * scale).astype(q.dtype)
        dk = dk + _dot(dst, q, 1, 0)
        dq_acc[rows, :] += _dot(dst, k, 0, 0)  # ds k: the one product over the tile's rows
        return dk, dv

    def summary_block(c, carry):
        at = _rows(c, per_window)
        kc, vc = kc_ref[0, at, :], vc_ref[0, at, :]
        dkc, dvc = lax.fori_loop(
            0, n, lambda i, g: pair(kc, vc, i, g, False), (jnp.zeros(kc.shape, F32), jnp.zeros(vc.shape, F32)))
        dkc_acc[at, :] += dkc
        dvc_acc[at, :] += dvc
        return carry

    lax.fori_loop(0, w, summary_block, 0)

    def key_block(j, carry):
        keys = _rows(j, block)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        grads = pair(k, v, j, (jnp.zeros(k.shape, F32), jnp.zeros(v.shape, F32)), True)
        dk, dv = lax.fori_loop(j + 1, n, lambda i, g: pair(k, v, i, g, False), grads)
        dk_ref[0, keys, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)
        return carry

    lax.fori_loop(0, n, key_block, 0)
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(w == last)
    def _():
        dkc_ref[0] = dkc_acc[...].astype(dkc_ref.dtype)
        dvc_ref[0] = dvc_acc[...].astype(dvc_ref.dtype)


def _call(kernel, name, operands, outs, scratch, *, sizes, sequential, products, interpret):
    """One of the two kernels over the grid (rows, heads, windows). ``operands``
    and ``outs`` are (kind, array or shape): "window" (b, s, h x d), a window a
    block; "summary" (b, s / chunk, h x d), a head's whole; "lse" (b, h,
    windows, window / block, block)."""
    b, s, h, d, window, block, summaries = sizes

    def spec(kind):
        if kind == "lse":
            return pl.BlockSpec((1, 1, 1, window // block, block), lambda r, j, w: (r, j, w, 0, 0), memory_space=pltpu.VMEM)
        if kind == "summary":
            return pl.BlockSpec((1, summaries, d), lambda r, j, w: (r, 0, j), memory_space=pltpu.VMEM)
        return pl.BlockSpec((1, window, d), lambda r, j, w: (r, w, j), memory_space=pltpu.VMEM)

    moved = sum(x.size * x.dtype.itemsize for _, x in [*operands, *outs])
    pairs = b * h * (s * (window + block) // 2 + summaries // (s // window) * s * (s // window - 1) // 2)
    return pl.pallas_call(
        kernel,
        out_shape=[o for _, o in outs],
        grid=(b, h, s // window),
        in_specs=[spec(kind) for kind, _ in operands],
        out_specs=[spec(kind) for kind, _ in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary" if sequential else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=int(products * 2 * d * pairs), bytes_accessed=int(moved), transcendentals=int(pairs)),
        interpret=interpret,
        name=name,  # what a profile calls this kernel
    )(*(x for _, x in operands))


def _sizes(q, kc, h, window):
    b, s, width = q.shape
    d = width // h
    plan = eva_attention_plan(s, window, s // kc.shape[1], d, q.dtype.itemsize)
    return (b, s, h, d, window, plan["block"], kc.shape[1]), plan


def _forward(q, k, v, kc, vc, h, window, scale, interpret):
    sizes, plan = _sizes(q, kc, h, window)
    b, s, block = sizes[0], sizes[1], plan["block"]
    return _call(
        functools.partial(_fwd_kernel, scale=scale, block=block, per_window=plan["per_window"]), "eva_attn_fwd",
        [("window", q), ("window", k), ("window", v), ("summary", kc), ("summary", vc)],
        [("window", jax.ShapeDtypeStruct(v.shape, v.dtype)),
         ("lse", jax.ShapeDtypeStruct((b, h, s // window, window // block, block), F32))],
        [], sizes=sizes, sequential=False, products=2, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _wide_kernel(q, k, v, kc, vc, h, window, scale, interpret):
    return _forward(q, k, v, kc, vc, h, window, scale, interpret)[0]


def _vjp_fwd(q, k, v, kc, vc, h, window, scale, interpret):
    o, lse = _forward(q, k, v, kc, vc, h, window, scale, interpret)
    return o, (q, k, v, kc, vc, o, lse)


def _vjp_bwd(h, window, scale, interpret, residuals, do):
    q, k, v, kc, vc, o, lse = residuals
    sizes, plan = _sizes(q, kc, h, window)
    d, block, summaries = sizes[3], plan["block"], sizes[6]
    return tuple(_call(
        functools.partial(_bwd_kernel, scale=scale, block=block, per_window=plan["per_window"]), "eva_attn_bwd",
        [("window", q), ("window", k), ("window", v), ("summary", kc), ("summary", vc),
         ("window", o), ("window", do.astype(v.dtype)), ("lse", lse)],
        [(kind, jax.ShapeDtypeStruct(x.shape, x.dtype))
         for kind, x in (("window", q), ("window", k), ("window", v), ("summary", kc), ("summary", vc))],
        [pltpu.VMEM((window, d), F32), pltpu.VMEM((window // block, block), F32),  # dq of the window, di of its rows
         pltpu.VMEM((summaries, d), F32), pltpu.VMEM((summaries, d), F32)],  # dkc, dvc of the head
        sizes=sizes, sequential=True, products=5, interpret=interpret))


_wide_kernel.defvjp(_vjp_fwd, _vjp_bwd)


def eva_attention_kernel(q, k, v, kc, vc, *, head_dim: int, window: int, scale: float | None = None,
                         interpret: bool = False):
    """Windowed chunk attention through the kernel pair, the heads on the lanes
    as the kernels read them: q, k, v: (b, s, h x d), the summaries kc, vc: (b,
    s / chunk, h x d), ``head_dim`` = d, a head an aligned window of the lanes;
    the number of heads is what the widths hold, so a shard of whole heads is a
    call like any other. s is whole windows, a window whole chunks; the chunk is
    read off the summaries' length. Returns o in v's shape and dtype; ``scale``
    defaults to d^-1/2. Differentiated, it saves its operands, o and the
    log-sum-exp column (b, h, s) float32. The summaries of the last window reach
    no query and get a zero gradient. ``interpret=True`` runs the Pallas
    interpreter (CPU testing)."""
    b, s, width = q.shape
    h = width // head_dim
    shapes_agree = (
        width % head_dim == 0 and k.shape == q.shape == v.shape and kc.shape == vc.shape
        and kc.shape[0] == b and kc.shape[2] == width and kc.shape[1] and s % kc.shape[1] == 0
    )
    if not shapes_agree or eva_attention_plan(s, window, s // kc.shape[1], head_dim, q.dtype.itemsize) is None:
        raise ValueError(f"eva_attention_kernel: {q.shape}, {k.shape}, {v.shape} with summaries {kc.shape}, "
                         f"{vc.shape} in heads of {head_dim} and windows of {window} are not its shapes")
    scale = head_dim**-0.5 if scale is None else scale
    return _wide_kernel(q, k, v, kc, vc, h, window, scale, interpret)
