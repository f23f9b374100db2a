"""Latent attention's causal core (scores, causal softmax, values) as two Pallas
TPU kernels, ``mla_attn_fwd`` and ``mla_attn_bwd``, for sequences past the
VMEM-resident ``short_attn_*`` kernels (ops/pallas_short_attention.py) and head
sizes that are whole 128-lane registers (latent attention's 256 / 256, or its
192 / 128 zero-padded).

Both read and write (b, s, h x d), a head an aligned window of the lanes, as
``kda_fwd`` / ``kda_bwd`` and ``short_attn_*`` cut theirs. XLA holds a per-head
(b, s, h, d) array in another tiling, so a view of one around these kernels is
a copy through HBM (4 ms a forward call at 4 x 4096 x 20 x 256, against the
kernel's 5.6: PERF.md section 6, PR 38): ``LatentAttention`` makes q, k and v
with the heads on the lanes and nothing is transposed or copied around the
pair, forward or backward. One program = one batch row and one head, the head's
whole sequence in VMEM (q, k, v, o and the cotangents once from and to HBM);
inside it the sequence goes by blocks of ``block`` tokens in two loops, and a
pair of blocks above the diagonal is never visited: the inner loop's bounds
leave it out, so it costs no grid step, no fetch and no mask.

Forward, query-major (the scores tile is (queries, keys), as ``short_attn_fwd``
holds it): online softmax over the key blocks under the diagonal without a mask,
the causal mask on the diagonal block alone, the float32 accumulator normalised
once after the last key block. It leaves o and, for the backward, ONE float32
column of log-sum-exp a row, stored lane-major as (b, h, s / block, block).

Backward, key-major (the tile is (keys, queries), as ``short_attn_bwd`` holds
it: p^T and ds^T are what the chain has, dv and dk are plain products, and only
dq contracts over the tile's rows), one kernel, the five products the
mathematics needs: s^T = k q^T and p^T = exp(s^T - lse) once, dv += p^T do,
dp^T = v do^T, ds^T = p^T (dp^T - di), dk += ds^T q, dq += ds k. ``di`` =
rowsum(o do) is computed in the kernel from the o and do tiles it holds, dk and
dv of a key block are summed over its query blocks in registers, dq of the
whole head in a float32 VMEM scratch.

The numbers are the blocked library kernel's (``ops/flash_attention.py``):
operands in the tower's dtype into every product, float32 accumulation, float32
scores, statistics, ``di`` and accumulators, p and ds cast to the operands'
dtype only before the products they feed, exact exp and reciprocal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["admitted_pairs", "latent_attention_kernel", "latent_attention_plan", "visited_block_pairs", "LANES"]

F32 = jnp.float32
LANES = 128  # a vector register's lanes: the head sizes the kernels take are multiples
_NEG = -1e30
# Tokens a block: the side of the (block, block) float32 tiles a program works on.
_BLOCK = 512
# What a program may ask of the chip's 128 MiB of VMEM (Mosaic's default limit is 16).
_VMEM_LIMIT = 100 * 2**20
_VMEM_TILES = 12  # (block, block) float32 tiles' worth of temporaries beside the operands


def latent_attention_plan(tokens: int, dqk: int, dv: int, itemsize: int = 2) -> dict | None:
    """What a call at these sizes runs at, from shapes alone: ``block`` (tokens
    a block), ``tokens`` (the sequence zero-padded at its end to whole blocks),
    ``vmem_bytes`` (what the larger program, the backward's, holds: every
    operand and result of a head twice, the pipeline's, and dq in float32).
    None where a head size is no multiple of ``LANES`` or a head's sequence
    does not fit ``_VMEM_LIMIT``: the caller takes another core."""
    if dqk % LANES or dv % LANES:
        return None
    block = min(_BLOCK, -(-tokens // LANES) * LANES)
    padded = -(-tokens // block) * block
    held = 2 * padded * (4 * dqk + 4 * dv) * itemsize + padded * dqk * 4 + _VMEM_TILES * block * block * 4
    if held > _VMEM_LIMIT:
        return None
    return {"block": block, "tokens": padded, "vmem_bytes": held}


def admitted_pairs(tokens: int, window: int = 0) -> int:
    """(query, key) pairs a head's softmax admits over one sequence: key <= query,
    and with a ``window`` key > query - window (the token itself and the
    ``window`` - 1 before it)."""
    w = min(window, tokens) if window else tokens
    return w * (w + 1) // 2 + (tokens - w) * w


def visited_block_pairs(tokens: int, block: int, window: int = 0) -> int:
    """(query block, key block) pairs the kernels' loops visit for one head of a
    sequence of ``tokens`` (whole blocks): those that hold an admitted pair."""
    n = tokens // block
    if not window:
        return n * (n + 1) // 2
    return sum(i - max(i * block - window + 1, 0) // block + 1 for i in range(n))


def _dot(a, b, contract_a: int, contract_b: int):
    return lax.dot_general(a, b, (((contract_a,), (contract_b,)), ((), ())), preferred_element_type=F32)


def _as_row(column):
    """A (n, 1) float32 column as the (1, n) row that holds the same, lane-major."""
    return jnp.transpose(jnp.broadcast_to(column, (column.shape[0], LANES)))[:1]


def _rows(i, block):
    return pl.ds(pl.multiple_of(i * block, block), block)


def _band(i, j, block, window, ahead):
    """Of query block ``i`` and key block ``j``, the pairs the band admits (key >
    query - ``window``), as a mask over a tile whose entry is ``ahead`` = the
    key's place in its block less the query's."""
    return ahead > (i - j) * block - window


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block, window=0):
    """One head of one row: per query block the online softmax over its key
    blocks, those under the diagonal unmasked, then the diagonal's. With a
    ``window`` the key blocks wholly behind the band are left out as those above
    the diagonal are, by the loops' bounds, and the blocks the band's trailing
    edge crosses come first, masked: a row they hide wholly gathers weights
    that the first real score's rescaling multiplies by exactly 0."""
    n = q_ref.shape[1] // block
    dv = v_ref.shape[2]
    visible = (lax.broadcasted_iota(jnp.int32, (block, block), 0)
               >= lax.broadcasted_iota(jnp.int32, (block, block), 1))
    if window:
        ahead = (lax.broadcasted_iota(jnp.int32, (block, block), 1)
                 - lax.broadcasted_iota(jnp.int32, (block, block), 0))
        if window < block:  # the band's edge crosses the diagonal block too
            visible = visible & _band(0, 0, block, window, ahead)

    def query_block(i, carry):
        rows = _rows(i, block)
        q = q_ref[0, rows, :]

        def step(keys, stats, mask):
            m, l, acc = stats
            x = _dot(q, k_ref[0, keys, :], 1, 1) * scale  # (queries, keys) f32
            if mask is not False:
                x = jnp.where(visible if mask is True else mask, x, _NEG)
            m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(x - m_new)
            v = v_ref[0, keys, :]
            return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                    alpha * acc + _dot(p.astype(v.dtype), v, 1, 0))

        stats = (jnp.full((block, 1), _NEG, F32), jnp.zeros((block, 1), F32), jnp.zeros((block, dv), F32))
        first = 0
        if window:
            # Key blocks [behind, first) hold the band's trailing edge, [first, i) lie wholly inside it.
            behind = jnp.maximum(i * block - window + 1, 0) // block
            first = jnp.clip(-((window - (i + 1) * block) // block), behind, i)
            stats = lax.fori_loop(
                behind, first, lambda j, c: step(_rows(j, block), c, _band(i, j, block, window, ahead)), stats)
        stats = lax.fori_loop(first, i, lambda j, c: step(_rows(j, block), c, False), stats)
        m, l, acc = step(rows, stats, True)
        o_ref[0, rows, :] = (acc * (1.0 / l)).astype(o_ref.dtype)
        lse_ref[0, 0, pl.ds(i, 1), :] = _as_row(m + jnp.log(l))
        return carry

    lax.fori_loop(0, n, query_block, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref, dq_acc, di_ref, *,
                scale, block, window=0):
    """One head of one row: di of every query block first, then per key block
    the gradient chain over the query blocks from the diagonal down; with a
    ``window``, down to the last query block the band lets see this key block,
    the blocks its trailing edge crosses masked."""
    n = q_ref.shape[1] // block
    visible = (lax.broadcasted_iota(jnp.int32, (block, block), 0)
               <= lax.broadcasted_iota(jnp.int32, (block, block), 1))  # (keys, queries)
    if window:
        ahead = (lax.broadcasted_iota(jnp.int32, (block, block), 0)
                 - lax.broadcasted_iota(jnp.int32, (block, block), 1))
        if window < block:
            visible = visible & _band(0, 0, block, window, ahead)

    def di_of(i, carry):
        rows = _rows(i, block)
        di = jnp.sum(o_ref[0, rows, :].astype(F32) * do_ref[0, rows, :].astype(F32), axis=1, keepdims=True)
        di_ref[pl.ds(i, 1), :] = _as_row(di)
        return carry

    lax.fori_loop(0, n, di_of, 0)
    dq_acc[...] = jnp.zeros(dq_acc.shape, F32)

    def key_block(j, carry):
        keys = _rows(j, block)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]

        def pair(i, grads, mask):
            dk, dv = grads
            rows = _rows(i, block)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            xt = _dot(k, q, 1, 1) * scale  # (keys, queries) f32
            if mask is not False:
                xt = jnp.where(visible if mask is True else mask, xt, _NEG)
            pt = jnp.exp(xt - lse_ref[0, 0, pl.ds(i, 1), :])
            dv = dv + _dot(pt.astype(do.dtype), do, 1, 0)
            dpt = _dot(v, do, 1, 1)
            dst = ((dpt - di_ref[pl.ds(i, 1), :]) * pt * scale).astype(q.dtype)
            dk = dk + _dot(dst, q, 1, 0)
            dq_acc[rows, :] += _dot(dst, k, 0, 0)  # ds k: the one product over the tile's rows
            return dk, dv

        grads = pair(j, (jnp.zeros(k.shape, F32), jnp.zeros(v.shape, F32)), True)
        last = n
        if window:
            # Query blocks (j, last) see the whole key block, [last, past) through the band's trailing edge.
            last = jnp.clip((j * block + window) // block, j + 1, n)
            past = jnp.minimum(((j + 1) * block + window - 2) // block + 1, n)
        dk, dv = lax.fori_loop(j + 1, last, lambda i, c: pair(i, c, False), grads)
        if window:
            dk, dv = lax.fori_loop(last, past, lambda i, c: pair(i, c, _band(i, j, block, window, ahead)), (dk, dv))
        dk_ref[0, keys, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)
        return carry

    lax.fori_loop(0, n, key_block, 0)
    dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _sizes(q, v, h, group=1):
    (b, s, _), dqk, dv = q.shape, q.shape[-1] // h, v.shape[-1] // (h // group)
    return b, s, dqk, dv, latent_attention_plan(s, dqk, dv, q.dtype.itemsize)["block"]  # s is whole blocks here


def _call(kernel, name, operands, outs, scratch, *, b, s, h, dqk, dv, block, products, interpret, group=1, window=0):
    """One of the two kernels over the grid (rows, heads). ``operands`` and
    ``outs`` are (kind, array or shape): "qk" (b, s, h x dqk), "v" (b, s, h x
    dv), "lse" (b, h, s / block, block); "k_shared" and "v_shared", the keys and
    values of grouped heads, (b, s, h / group x d): query head j reads head j //
    ``group`` of them, where they lie (consecutive programs of a group name the
    same block, which is then not fetched again)."""
    def spec(kind):
        if kind == "lse":
            return pl.BlockSpec((1, 1, s // block, block), lambda r, j: (r, j, 0, 0), memory_space=pltpu.VMEM)
        if kind.endswith("_shared"):
            return pl.BlockSpec((1, s, dqk if kind == "k_shared" else dv), lambda r, j: (r, 0, j // group),
                                memory_space=pltpu.VMEM)
        return pl.BlockSpec((1, s, dqk if kind == "qk" else dv), lambda r, j: (r, 0, j), memory_space=pltpu.VMEM)

    moved = sum(x.size * x.dtype.itemsize for _, x in [*operands, *outs])
    pairs = 2 * visited_block_pairs(s, block, window) * block * block  # twice the visited: s (s + block) without a window
    return pl.pallas_call(
        kernel,
        out_shape=[o for _, o in outs],
        grid=(b, h),
        in_specs=[spec(kind) for kind, _ in operands],
        out_specs=[spec(kind) for kind, _ in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=int(products * b * h * pairs * (dqk + dv) // 2), bytes_accessed=int(moved),
            transcendentals=int(b * h * pairs // 2)),
        interpret=interpret,
        name=name,  # what a profile calls this kernel
    )(*(x for _, x in operands))


def _forward(q, k, v, h, scale, interpret, group=1, window=0):
    b, s, dqk, dv, block = _sizes(q, v, h, group)
    k_kind, v_kind = ("k_shared", "v_shared") if group > 1 else ("qk", "v")
    o, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, block=block, window=window), "mla_attn_fwd",
        [("qk", q), (k_kind, k), (v_kind, v)],
        [("v", jax.ShapeDtypeStruct((b, s, h * dv), v.dtype)),
         ("lse", jax.ShapeDtypeStruct((b, h, s // block, block), F32))],
        [], b=b, s=s, h=h, dqk=dqk, dv=dv, block=block, products=2, interpret=interpret, group=group, window=window)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _wide_kernel(q, k, v, h, scale, interpret, group=1, window=0):
    return _forward(q, k, v, h, scale, interpret, group, window)[0]


def _vjp_fwd(q, k, v, h, scale, interpret, group=1, window=0):
    o, lse = _forward(q, k, v, h, scale, interpret, group, window)
    return o, (q, k, v, o, lse)


def _vjp_bwd(h, scale, interpret, group, window, residuals, do):
    q, k, v, o, lse = residuals
    b, s, dqk, dv, block = _sizes(q, v, h, group)
    k_kind, v_kind = ("k_shared", "v_shared") if group > 1 else ("qk", "v")
    # Grouped heads: dk and dv come out a QUERY head each (every program writes its own block)
    # and a group's are summed outside; k and v themselves are read where they lie.
    dq, dk, dv_ = _call(
        functools.partial(_bwd_kernel, scale=scale, block=block, window=window), "mla_attn_bwd",
        [("qk", q), (k_kind, k), (v_kind, v), ("v", o), ("v", do.astype(v.dtype)), ("lse", lse)],
        [("qk", jax.ShapeDtypeStruct(q.shape, q.dtype)), ("qk", jax.ShapeDtypeStruct((b, s, h * dqk), k.dtype)),
         ("v", jax.ShapeDtypeStruct((b, s, h * dv), v.dtype))],
        [pltpu.VMEM((s, dqk), F32), pltpu.VMEM((s // block, block), F32)],  # dq of the head, di of its rows
        b=b, s=s, h=h, dqk=dqk, dv=dv, block=block, products=5, interpret=interpret, group=group, window=window)
    if group > 1:
        dk, dv_ = (x.astype(F32).reshape(b, s, h // group, group, -1).sum(3).reshape(like.shape).astype(like.dtype)
                   for x, like in ((dk, k), (dv_, v)))
    return dq, dk, dv_


_wide_kernel.defvjp(_vjp_fwd, _vjp_bwd)


def latent_attention_kernel(q, k, v, *, head_dims: tuple[int, int], scale: float | None = None,
                            interpret: bool = False, kv_heads: int | None = None, window: int = 0):
    """Causal self-attention through the kernel pair, the heads on the lanes as
    the kernels read them: q, k: (b, s, h x dqk) and v: (b, s, h x dv) with
    ``head_dims`` = (dqk, dv), a head an aligned window of the lanes. The number
    of heads is what the widths hold, so a shard of whole heads (under a
    ``shard_map`` over ``tp``) is a call like any other. dqk and dv are
    multiples of ``LANES`` (the caller pads the heads), s any length that
    :func:`latent_attention_plan` admits: a sequence that is not whole blocks is
    zero-padded at its end (a later token reaches no earlier output, and a
    padded row's cotangent is zero), the output cut back. Returns o in v's shape
    and dtype; ``scale`` defaults to dqk^-1/2. Differentiated, it saves its
    operands, o and the log-sum-exp column (b, h, s) float32.
    ``interpret=True`` runs the Pallas interpreter (CPU testing).

    ``kv_heads`` (default: every query head its own) is for grouped heads: k
    and v then hold that many heads, (b, s, kv_heads x d), query head j reads
    head j // (h / kv_heads) of them where they lie (nothing is repeated in
    HBM), and o has the queries' heads. Backwards dk and dv leave the kernel a
    query head each and a group's are summed in XLA.

    ``window`` > 0 narrows the causal mask to a band: query t reads keys t -
    window + 1 .. t. Both kernels then visit the band's block pairs only
    (:func:`visited_block_pairs`). 0, or a window of at least the sequence, is
    the causal call itself."""
    (b, s, width), (dqk, dv) = q.shape, head_dims
    h = width // dqk
    kv_heads = h if kv_heads is None else kv_heads
    plan = latent_attention_plan(s, dqk, dv, q.dtype.itemsize)
    if (plan is None or (k.shape[-1], v.shape[-1]) != (kv_heads * dqk, kv_heads * dv) or width % dqk
            or kv_heads < 1 or h % kv_heads):
        raise ValueError(f"latent_attention_kernel: {q.shape}, {k.shape}, {v.shape} in heads of {dqk} / {dv} "
                         f"({kv_heads} key / value heads) are not its shapes")
    if window < 0:
        raise ValueError(f"latent_attention_kernel: window={window} is no number of keys")
    scale = dqk**-0.5 if scale is None else scale
    if plan["tokens"] != s:
        q, k, v = (jnp.pad(x, ((0, 0), (0, plan["tokens"] - s), (0, 0))) for x in (q, k, v))
    return _wide_kernel(q, k, v, h, scale, interpret, h // kv_heads, window if window < s else 0)[:, :s]
