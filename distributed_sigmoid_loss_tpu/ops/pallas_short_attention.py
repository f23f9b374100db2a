"""Fused short-sequence multi-head attention — a Pallas TPU kernel for the towers.

Why not the generic flash kernel: at tower scale (ViT-B/16 s=196, text s=64) the
sequence fits in VMEM whole, so blockwise online softmax is pure overhead — the
generic kernel's (batch, head, q-block, kv-block) grid launches thousands of tiny
programs and loses to XLA's dense path (measured: 46ms vs 15ms per fwd+bwd call at
b=512, s=196). What actually hurts the dense path is HBM traffic: the (b, h, s, s)
logits and f32 softmax round-trip through HBM in forward AND backward — the largest
activations in the whole SigLIP step (7G+ stacked across layers at batch 256).

Design: the kernel consumes q/k/v in the towers' NATIVE (b, s, h·dh) layout — no
transposes, no layout padding (a (s, width) tile is exactly aligned); one program =
one batch row (a few short rows in the forward), heads handled by a static Python
loop over the lanes. Everything O(s²) lives and dies in VMEM: logits → softmax →
out in forward, the 5-matmul gradient chain in backward (probs recomputed, never
stored). HBM traffic collapses to the unavoidable q/k/v/out (+gradients) reads and
writes — measured 5.8× faster than the dense path at ViT-B/16 scale, 2.9× at
text-tower scale. Numerics: f32 logits / softmax / accumulation, matmul inputs in
the activation dtype (bf16 in training) — the same contract as the dense path.

The two kernels hold their logits in opposite orientations, each measured on
the chip against the other (PERF.md section 6, PR 24 and PR 27).

The forward is QUERY-major, (s_q, s_k) = q·kᵀ, and normalises AFTER the product:
out = (exp(l − max) · v) · (1 / rowsum) (:func:`_query_major_out`). The row sum is
then already the (s, 1) column the (s, dh) output needs, no (s, s) tile is divided
and none is transposed; key-major, the forward's one product over rows
(pᵀ-times-v) costs more than the lane reductions it saves at every tower shape.
The backward is KEY-major, (s_k, s_q) = k·qᵀ (:func:`_key_major_grads`): a
query-major chain run backwards contracts twice over the rows of an (s, s) tile
(dv = pᵀ·do, dk = dsᵀ·q: Mosaic transposes the tile for each) and reduces three
times across lanes per head; key-major, pᵀ and dsᵀ are what the chain holds, dv
and dk are plain products, the statistics are adds down the sublanes, and only
dq = ds·k still contracts over rows.

Heads are never cut out of the lanes where a whole register will do; how is chosen
at trace time from the operands' shapes (:func:`_head_cut`, :func:`_head_windows`).
The forward loads, for each head, the aligned 128-lane slabs it lies in (one at
dh = 64 and for half of so400m's dh = 72 heads, two for the heads that straddle a
boundary, the head itself where dh is a multiple of 128) and selects the head by
zeroing the other lanes of q alone: k, v and the product keep the window's lanes,
the head's part of the result is already where the output wants it, and every
store is a whole slab. A lane slice at an offset that is not a register boundary
costs a cross-lane rotate per register of every operand and of the result, which
at dh = 72 was three quarters of the kernel. A forward program takes several batch
rows where they are short (:func:`_fwd_plan`): the rows' chains are independent
and the scheduler interleaves them; at the longest sequences the dispatcher admits
it normalises before the product instead, which holds less VMEM. The backward
keeps lane slices at any dh but 64; at dh = 64 two heads fill one slab, loaded and
stored whole, a head selected by zeroing the other's lanes in one operand of each
product; at dh = 64 and 2·s ≤ 128 (the text tower) the two heads' transposed
logits share one lane tile, so the pair costs one chain, not two.

No reference analogue (the reference has no model layer, SURVEY.md §1); this is the
"pallas kernels for the hot ops" piece of the TPU-first design.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "short_self_attention",
    "short_attention_fits",
    "short_attention_vmem_bytes",
    "SHORT_ATTENTION_MAX_SEQ",
]

_NEG_INF = -1e30

# Lanes of a vector register: where two heads fill them exactly (dh = 64) the
# backward works on whole slabs.
_LANES = 128

# Above this sequence length the O(s²) per-head blocks stop fitting VMEM comfortably
# and a blockwise (true flash / ring) kernel wins; dispatch there instead.
SHORT_ATTENTION_MAX_SEQ = 1024

# TPU VMEM is ~16 MiB/core across v4/v5e/v5p; the budget leaves headroom for the
# compiler's own scratch and pipelining buffers. A program over budget fails at
# Mosaic compile time with no fallback, so the dispatcher must pre-check.
_VMEM_BYTES = 16 * 1024 * 1024
_VMEM_BUDGET_FRACTION = 0.7

# Tokens (rows x s) up to which a forward program takes more than one batch row,
# and the share of Mosaic's limit the forward's own estimate may reach (_fwd_plan).
_FWD_MAX_TOKENS = 512
_FWD_VMEM_FRACTION = 0.9


def _fwd_vmem_bytes(rows: int, s: int, width: int, num_heads: int, dtype_bytes: int,
                    defer: bool) -> int:
    """What Mosaic allocates for one forward program, by what it was measured to
    need (compiled for a described v5e over shapes, PERF.md section 6, PR 27): two
    copies of the 4 (rows, s, width) I/O blocks (the pipeline's), and (s, s) f32
    tiles, lanes padded to 128. Normalising before the second product
    (``defer=False``) the tiles are 2, reused from head to head, as the parent's.
    Normalising after it the exponentials feed the product and the row sum at
    once, the scheduler runs the next heads' chains meanwhile, and up to one tile
    a head is live: the speed of that form (section 6) and its price."""
    tiles = num_heads if defer else 2
    return (8 * rows * s * width * dtype_bytes
            + tiles * s * (-(-s // _LANES) * _LANES) * 4)


def _fwd_plan(b: int, s: int, width: int, num_heads: int, dtype_bytes: int):
    """``(rows, defer)``: the batch rows one forward program takes and whether it
    normalises after the second product, from the shapes alone (trace time).

    Measured on the chip (PERF.md section 6, PR 27). Normalising after the
    product is 1.1 to 1.5 times as fast at every tower shape. A program's chain
    per head is serial (product, row max, exp, product), and rows unrolled side by
    side give the scheduler independent chains to interleave: -38 % at s = 64 with
    four rows (12 x 64), -9 % at s = 196 with two; eight rows, and a loop over rows
    in place of the unrolled copies, gain nothing. Both hold more VMEM, so the
    plan is the most rows of 4, 2, 1 that divide the batch, keep the program at
    ``_FWD_MAX_TOKENS`` tokens and fit ``_FWD_VMEM_FRACTION`` of Mosaic's limit
    with the normalisation deferred; past that (s >= 400 or so at the towers'
    widths) one row, normalised before the product, which needs less than the
    parent's kernel did wherever the dispatcher admits the shape."""
    for rows in (4, 2, 1):
        if (b % rows == 0 and (rows == 1 or rows * s <= _FWD_MAX_TOKENS)
                and _fwd_vmem_bytes(rows, s, width, num_heads, dtype_bytes, True)
                <= _VMEM_BYTES * _FWD_VMEM_FRACTION):
            return rows, True
    return 1, False


def short_attention_vmem_bytes(s: int, width: int, dtype_bytes: int) -> int:
    """Worst-case VMEM footprint of ONE grid program (width = h·dh).

    The backward program is the peak: 7 (s, width) I/O blocks (q, k, v, do, dq, dk,
    dv) resident for the whole program, plus 3 live (s, s) f32 intermediates of
    the key-major chain (pᵀ, dpᵀ, dsᵀ — the compiler reuses them across heads but
    not within the chain; its one transposed copy of dsᵀ for dq is bf16). Where
    two heads share a slab the tiles are the same count: (s, 2s) pairs at
    2·s ≤ 128, where a pair is smaller than one padded (s, 128) tile, and the
    two heads of a masked pair run one after the other.

    The forward holds 4 blocks and 2 such tiles in this count, less than the
    backward at every shape, unless it spends VMEM on speed: more rows a program,
    the normalisation after the product. It does so only where its own, fuller
    estimate fits (:func:`_fwd_plan`, :func:`_fwd_vmem_bytes`), so the figure
    the dispatcher checks stays the backward's.
    """
    return 7 * s * width * dtype_bytes + 3 * s * s * 4


def short_attention_fits(s: int, width: int, dtype_bytes: int) -> bool:
    """True when the fused short kernel's per-program footprint fits the VMEM
    budget AND the sequence is within the design envelope. Callers fall back to
    blockwise flash (TPU) or dense (elsewhere) when False."""
    return (
        s <= SHORT_ATTENTION_MAX_SEQ
        and short_attention_vmem_bytes(s, width, dtype_bytes)
        <= _VMEM_BYTES * _VMEM_BUDGET_FRACTION
    )


def _dot(a, b, contract_a: int, contract_b: int):
    return lax.dot_general(
        a,
        b,
        (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _head_cut(s: int, dh: int, num_heads: int) -> str:
    """How the heads are cut out of the lanes, from the shapes alone (trace time):
    the one decision both kernels read, measured per shape in PERF.md section 6
    (PR 24 the backward, PR 27 the forward).

    ``"packed"``: dh = 64, an even head count and 2·s ≤ 128: two heads fill one
    aligned 128-lane slab and their transposed logits one lane tile; the backward
    runs one chain for the pair. ``"masked"``: dh = 64 otherwise: whole slabs, a
    head selected by zeroing the other's lanes. ``"sliced"``: any other head size
    (so400m's 72: no slab holds whole heads; 128: a head is a slab already): the
    backward takes lane slices. The forward cuts all three alike, by
    :func:`_head_windows`, which at dh = 64 is the masked slab: at four rows a
    program a packed forward pair measured no faster than two masked heads."""
    if 2 * dh != _LANES or num_heads % 2:
        return "sliced"
    return "packed" if 2 * s <= _LANES and s % 16 == 0 else "masked"


def _head_windows(num_heads: int, dh: int, width: int):
    """Per head ``(start, stop, lo, hi)``: lanes [lo, hi) are the head's, and
    [start, stop) the aligned 128-lane slabs they lie in (``stop`` clipped to a
    width that is no multiple of 128): what a kernel loads whole to reach the
    head without a lane rotate."""
    return [
        (lo // _LANES * _LANES, min(-(-hi // _LANES) * _LANES, width), lo, hi)
        for lo, hi in ((j * dh, (j + 1) * dh) for j in range(num_heads))
    ]


def _query_major_out(q_x, k_x, v_x, *, scale, visible, defer):
    """The forward chain of one head, logits held QUERY-major (why: the module
    docstring), normalised after the second product if ``defer`` and before it
    otherwise (:func:`_fwd_plan` says which).

    ``q_x``/``k_x`` are (s, c), zero outside the head's lanes in at least one of
    them; ``v_x`` is (s, n). Returns f32 (s, n): softmax(q·kᵀ)·v on every lane of
    ``v_x``, of which the caller keeps the head's. ``visible`` is the (s_q, s_k)
    causal mask or None. The statistics are f32, the reciprocal exact."""
    logits = _dot(q_x, k_x, 1, 1) * scale  # (s_q, s_k) f32
    if visible is not None:
        logits = jnp.where(visible, logits, _NEG_INF)
    e = jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))
    inv = 1.0 / jnp.sum(e, axis=1, keepdims=True)  # (s, 1): the column the output needs
    if defer:
        return _dot(e.astype(v_x.dtype), v_x, 1, 0) * inv
    return _dot((e * inv).astype(v_x.dtype), v_x, 1, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, num_heads, defer):
    """A few batch rows (:func:`_fwd_plan`): :func:`_query_major_out` per head on
    the aligned window :func:`_head_windows` gives it, the output assembled slab
    by slab in registers and stored whole."""
    rows, s, width = q_ref.shape
    windows = _head_windows(num_heads, width // num_heads, width)
    zero = jnp.zeros((), q_ref.dtype)
    visible = None
    if causal:  # (s_q, s_k): key j is visible to query i >= j
        visible = (lax.broadcasted_iota(jnp.int32, (s, s), 0)
                   >= lax.broadcasted_iota(jnp.int32, (s, s), 1))

    @functools.cache
    def lanes_in(n, lo, hi):  # (s, n) mask of the lanes [lo, hi)
        lane = lax.broadcasted_iota(jnp.int32, (s, n), 1)
        return (lane >= lo) & (lane < hi)

    # The slabs each head's window covers; the last head with lanes in a slab stores it.
    slabs = [range(start // _LANES, -(-stop // _LANES)) for start, stop, _, _ in windows]
    last = {t: j for j, ts in enumerate(slabs) for t in ts}

    def one_row(r, carry):
        pending = {}  # slab index -> f32 (s, <= 128), some heads' lanes still to come
        for j, (start, stop, lo, hi) in enumerate(windows):
            window = slice(start, stop)
            q_w = q_ref[r, :, window]
            if (start, stop) != (lo, hi):
                q_w = jnp.where(lanes_in(stop - start, lo - start, hi - start), q_w, zero)
            out = _query_major_out(q_w, k_ref[r, :, window], v_ref[r, :, window],
                                   scale=scale, visible=visible, defer=defer)
            for t in slabs[j]:
                a, b = _LANES * t, min(_LANES * (t + 1), width)
                piece = out[:, a - start:b - start]
                if t in pending:
                    piece = jnp.where(lanes_in(b - a, lo - a, hi - a), piece, pending.pop(t))
                if last[t] == j:
                    o_ref[r, :, a:b] = piece.astype(o_ref.dtype)
                else:
                    pending[t] = piece
        return carry

    # Unrolled by the loop, not by Python: the same straight-line program for the
    # scheduler, traced once instead of once a row (-2 s of set-up a tower).
    lax.fori_loop(0, rows, one_row, 0, unroll=True)


def _key_major_grads(k_x, q_x, v_x, do_x, *, scale, visible):
    """The gradient chain of one group of query columns, logits held KEY-major
    (why: the module docstring).

    ``k_x``/``v_x`` are (s_k, c), ``q_x``/``do_x`` (n, c): ``k_x·q_xᵀ`` is the
    (s_k, n) transposed logits tile (the transposed-RHS product the forward
    uses) and every per-query statistic is a reduction over axis 0. Only
    ``dq = ds·k`` contracts over rows: the one tile Mosaic still transposes.
    ``visible`` is the (s_k, n) causal mask or None. Returns f32
    (dq (n, c), dk (s_k, c), dv (s_k, c))."""
    lt = _dot(k_x, q_x, 1, 1) * scale  # (s_k, n) f32
    if visible is not None:
        lt = jnp.where(visible, lt, _NEG_INF)
    e = jnp.exp(lt - jnp.max(lt, axis=0, keepdims=True))
    pt = e / jnp.sum(e, axis=0, keepdims=True)  # pᵀ
    dv = _dot(pt.astype(do_x.dtype), do_x, 1, 0)  # pᵀ @ do
    dpt = _dot(v_x, do_x, 1, 1)  # (do @ vᵀ)ᵀ
    # Softmax VJP, transposed: dsᵀ = pᵀ ⊙ (dpᵀ − colsum(dpᵀ ⊙ pᵀ)), then the scale.
    dst = ((pt * (dpt - jnp.sum(dpt * pt, axis=0, keepdims=True))) * scale).astype(
        q_x.dtype
    )
    dk = _dot(dst, q_x, 1, 0)  # dsᵀ @ q
    dq = _dot(dst, k_x, 0, 0)  # ds @ k
    return dq, dk, dv


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref, *, scale, causal, num_heads
):
    """One batch row: :func:`_key_major_grads` per head, or per pair of heads,
    the heads cut out of the lanes as cheaply as the head size allows (chosen
    by :func:`_head_cut`, at trace time, from the operands' shapes; measured in
    PERF.md section 6, PR 24)."""
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    s, width = q.shape
    dh = width // num_heads

    def causal_mask(n):  # (s_k, n): key i is visible to query column j mod s
        key = lax.broadcasted_iota(jnp.int32, (s, n), 0)
        query = lax.broadcasted_iota(jnp.int32, (s, n), 1)
        return jnp.where(query >= s, query - s, query) >= key

    def store(lanes, grads):
        for ref, g in zip((dq_ref, dk_ref, dv_ref), grads):
            ref[0, :, lanes] = g.astype(ref.dtype)

    grads_of = functools.partial(_key_major_grads, scale=scale)
    cut = _head_cut(s, dh, num_heads)
    if cut == "sliced":
        # Any head size: one head at a time, cut out by lane slices (so400m's
        # dh = 72: no head starts on a register boundary).
        visible = causal_mask(s) if causal else None
        for j in range(num_heads):
            sl = slice(j * dh, (j + 1) * dh)
            store(sl, grads_of(k[:, sl], q[:, sl], v[:, sl], do[:, sl], visible=visible))
        return
    # dh = 64: two heads fill one aligned 128-lane slab, and every load and store
    # is a whole slab. A head is cut out by zeroing the other head's lanes (a
    # contraction over 128 lanes, half of them zero, costs the MXU what one over
    # 64 does), never by a slice at a 64-lane offset, which costs a cross-lane
    # rotate per register of every operand and result.
    zero = jnp.zeros((), q.dtype)
    first = lax.broadcasted_iota(jnp.int32, (s, _LANES), 1) < dh
    packed = cut == "packed"
    if packed:
        # The text tower: both heads' transposed logits fit one lane tile. Stack
        # the slab's queries twice along rows, rows [0, s) keeping the first
        # head's lanes and rows [s, 2s) the second's: k·qqᵀ is [LTa | LTb], and
        # pᵀ·dd and dsᵀ·qq come out as the finished dv and dk slabs. One chain
        # for the pair, on full registers.
        rows = lax.broadcasted_iota(jnp.int32, (2 * s, _LANES), 0)
        lanes = lax.broadcasted_iota(jnp.int32, (2 * s, _LANES), 1)
        keep = (rows < s) == (lanes < dh)
    visible = causal_mask(2 * s if packed else s) if causal else None
    for j in range(num_heads // 2):
        sl = slice(_LANES * j, _LANES * (j + 1))
        q2, k2, v2, do2 = q[:, sl], k[:, sl], v[:, sl], do[:, sl]
        if packed:
            qq = jnp.where(keep, jnp.concatenate([q2, q2], axis=0), zero)
            dd = jnp.where(keep, jnp.concatenate([do2, do2], axis=0), zero)
            dqq, dk2, dv2 = grads_of(k2, qq, v2, dd, visible=visible)
            # (2s, 128): the diagonal blocks are the two heads' dq.
            store(sl, (jnp.where(first, dqq[:s], dqq[s:]), dk2, dv2))
        else:
            # One head, then the other, each embedded in the zero-padded slab:
            # its gradients are zero on the other head's lanes, so the slab's
            # are the sum.
            halves = [
                grads_of(jnp.where(m, k2, zero), jnp.where(m, q2, zero), v2,
                         jnp.where(m, do2, zero), visible=visible)
                for m in (first, ~first)
            ]
            store(sl, [a + b for a, b in zip(*halves)])


def _specs(b, s, width, n: int, rows: int = 1):
    block = pl.BlockSpec((rows, s, width), lambda i: (i, 0, 0), memory_space=pltpu.VMEM)
    return dict(grid=(b // rows,), in_specs=[block] * n, out_specs=block)


def _flops(b, s, width, n_matmuls: int) -> int:
    return 2 * b * s * s * width * n_matmuls


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def short_self_attention(q, k, v, causal: bool = False, scale: float | None = None,
                         interpret: bool = False):
    """Fused self-attention for VMEM-resident sequences: (b, s, h, dh) → same.

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU testing).
    """
    out, _ = _short_attention_fwd(q, k, v, causal, scale, interpret)
    return out


def _short_attention_fwd(q, k, v, causal, scale, interpret):
    b, s, h, dh = q.shape
    scale = (dh**-0.5) if scale is None else scale
    wide = (b, s, h * dh)  # free reshape: heads stay on the minor axis
    rows, defer = _fwd_plan(b, s, h * dh, h, q.dtype.itemsize)
    spec = _specs(b, s, h * dh, 3, rows)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, num_heads=h, defer=defer),
        out_shape=jax.ShapeDtypeStruct(wide, q.dtype),
        grid=spec["grid"],
        in_specs=spec["in_specs"],
        out_specs=spec["out_specs"],
        cost_estimate=pl.CostEstimate(
            flops=_flops(b, s, h * dh, 2),
            bytes_accessed=4 * q.size * q.dtype.itemsize,
            transcendentals=b * h * s * s,
        ),
        interpret=interpret,
        name="short_attn_fwd",  # what a profile calls this kernel
    )(q.reshape(wide), k.reshape(wide), v.reshape(wide))
    return out.reshape(q.shape), (q, k, v)


def _short_attention_bwd(causal, scale, interpret, residuals, g):
    q, k, v = residuals
    b, s, h, dh = q.shape
    scale_v = (dh**-0.5) if scale is None else scale
    wide = (b, s, h * dh)
    spec = _specs(b, s, h * dh, 4)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale_v, causal=causal, num_heads=h),
        out_shape=[jax.ShapeDtypeStruct(wide, q.dtype)] * 3,
        grid=spec["grid"],
        in_specs=spec["in_specs"],
        out_specs=[spec["out_specs"]] * 3,
        cost_estimate=pl.CostEstimate(
            flops=_flops(b, s, h * dh, 5),
            bytes_accessed=7 * q.size * q.dtype.itemsize,
            transcendentals=b * h * s * s,
        ),
        interpret=interpret,
        name="short_attn_bwd",
    )(q.reshape(wide), k.reshape(wide), v.reshape(wide), g.reshape(wide))
    shape = q.shape
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


short_self_attention.defvjp(_short_attention_fwd, _short_attention_bwd)
