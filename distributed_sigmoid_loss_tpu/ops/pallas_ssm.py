"""The state-space recurrence's chunked form (ops/ssm.py has the equations) as
two Pallas TPU kernels: a chunk of L tokens of one group lives in VMEM from x, B,
C, dt to y, the group's state crosses chunks in VMEM scratch along a sequential
axis of the grid, and HBM sees x, B, C, dt, y (and, for the backward, each
chunk's incoming state) once. What ``_chunked`` writes to HBM a head at a time,
the (L, L) decays, weights and mixed scores, exists here a head at a time in
registers and VMEM only.

One program = one batch row, one group (its e = heads / groups heads are e x P
lanes of x and y, its B and C N lanes each), one chunk. The grid is (rows,
groups, chunks), chunks innermost and in order (the backward walks them last
to first). Every operand keeps its heads on the lanes, (b, s, h x P) and (b, s,
g x N), cut by ``BlockSpec``; a head's x is never a (L, P) array of its own: the
products that are per head, (L, L) (L, P), run on the whole 128-lane column the
head lies in and each head keeps its own lanes of the result (an MXU pass is
128 wide whatever P is). dt, (b, s, h), comes in both orientations, (b, g, s, e)
and (b, g, e, s), two relayouts of a megabyte in XLA, so that no (L, e) tile is
transposed here. Per program, as ``_chunked`` per chunk:

- G, the running sum of dt A, as a product of the 0/1 lower triangle with dt A
  (float32 in three bfloat16 pieces, exact because the matrix is 0/1, added in
  float32: ``pallas_delta_rule``'s way), once as columns (L, e) and once as rows
  (e, L);
- the group's scores C B^T once; per head exp(G_t - G_i) dt_i under the causal
  mask (one exponent <= 0 an entry, float32) times the scores, rounded to the
  tower's dtype where ``_chunked`` rounds it, times the head's x;
- the incoming state's outputs C S exp(G_t) and the next state exp(G_L) S + B^T
  (x dt exp(G_L - G_i)) for all of the group's heads at once; the state is held
  (N, e x P) float32, so its decay is a multiply along lanes; D x.

The backward is the gradient of the same function. From the forward it takes
each chunk's incoming state (the differentiated forward writes them:
:func:`kept_for_backward`) and y; it makes G, the scores and each head's decay
again (cheap in VMEM) and carries the state's cotangent in scratch. Per head it
runs three products, dy x^T for the mixed scores' cotangent and the mixed scores
transposed times dy twice, with dt_i (x's cotangent) and without (dt's own).
G's cotangent needs no (L, L) reduction: G enters a chunk only through factors
exp(G_t) on what token t reads and exp(-G_i) on what token i writes, so inside
the chunk it is the per-head sum over lanes of dy (y - D x - the state's part)
at t minus that of x dx at i, both sides made of the same rounded factors (the
forward's own y, the same bfloat16 mixed scores and dy), so that a token's
reading of its own and its neighbours' writes cancels to float32 as it does
under ``jax.grad``; through the states it is taken term by term as the forward
made them (the state's part of y, what a token hands to the next state, and at
the chunk's last token all that the next state holds of this chunk and of the
incoming state). Per-head sums over a head's lanes are products with a 0/1
matrix, the reverse running sum one with the triangle transposed (three
pieces again). Products take operands of the tower's dtype where the forward's
do; dA and dD leave as per-row partial sums, accumulated over a row's chunks in
the output block, and dt's cotangent in the (b, g, s, e) orientation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import _dot

__all__ = ["ssd_kernel", "kept_for_backward"]

F32 = jnp.float32
_LANES = 128


def _pieces(x):
    """A float32 x as three bfloat16 pieces whose float32 sum is x to float32
    accuracy: a product of each with a 0/1 matrix is exact."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(F32)).astype(jnp.bfloat16)


def _dot01(ops, x, contract_a=1, contract_b=0):
    """``ops . x`` for a 0/1 matrix (bfloat16) and a float32 x, to float32 accuracy."""
    return sum(_dot(ops, p, contract_a, contract_b) for p in _pieces(x))


def _dot10(x, ops, contract_a=1, contract_b=0):
    """``x . ops`` for a float32 x and a 0/1 matrix (bfloat16), to float32 accuracy."""
    return sum(_dot(p, ops, contract_a, contract_b) for p in _pieces(x))


def _head_sums(lanes: int, e: int) -> np.ndarray:
    """The 0/1 matrix (e x P, e) whose product with a (L, e x P) tile is each
    head's sum over its P lanes."""
    return (np.arange(lanes)[:, None] // (lanes // e) == np.arange(e)[None, :]).astype(np.float32)


def _running_sums(dt_col, dt_row, a_row, a_col):
    """G of a chunk in both orientations, (L, e) and (e, L), float32, with the
    causal mask ([t, i]: i <= t) and the 0/1 triangle they are made with."""
    chunk = dt_col.shape[0]
    seen = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lower = jnp.where(seen, 1.0, 0.0).astype(jnp.bfloat16)
    return seen, lower, _dot01(lower, dt_col * a_row), _dot10(dt_row * a_col, lower, 1, 1)


def _joined(cols):
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _on_lanes(per_head, p):
    """(L, e) -> (L, e x P): a head's column on each of its P lanes, a 128-lane
    column of the result at a time (a lane broadcast, and a select where
    several heads share the column)."""
    rows, e = per_head.shape
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    cols = []
    for c in range(e * p // _LANES):
        first, last = c * _LANES // p, ((c + 1) * _LANES - 1) // p
        col = jnp.broadcast_to(per_head[:, first:first + 1], (rows, _LANES))
        for j in range(first + 1, last + 1):
            col = jnp.where(lane >= j * p - c * _LANES, per_head[:, j:j + 1], col)
        cols.append(col)
    return _joined(cols)


def _columns_of(j, p):
    """The 128-lane columns head j's P lanes lie in, each with the head's
    first lane inside the column (<= 0: the column starts inside the head)."""
    return [(c, j * p - c * _LANES) for c in range(j * p // _LANES, -(-(j + 1) * p // _LANES))]


def _decay(seen, g_col, g_row, j):
    """Head j's exp(G_t - G_i) for i <= t, 0 above: (L, L) float32."""
    return jnp.exp(jnp.where(seen, g_col[:, j:j + 1] - g_row[j:j + 1, :], -jnp.inf))


def _fwd_kernel(x_ref, b_ref, c_ref, dtc_ref, dtr_ref, ar_ref, ac_ref, d_ref, y_ref, *rest, p, save_states):
    # the differentiated forward also writes each chunk's incoming state
    *saved, s_ref = rest

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    dt_col, dt_row = dtc_ref[0, 0], dtr_ref[0, 0]
    chunk, e = dt_col.shape
    seen, _, g_col, g_row = _running_sums(dt_col, dt_row, ar_ref[0], ac_ref[0])
    big_b, big_c = b_ref[0], c_ref[0]
    dtype = big_b.dtype
    state = s_ref[...]  # (N, e x P): what the chunk starts from
    if save_states:
        saved[0][0, 0] = state
    eg = _on_lanes(jnp.exp(g_col), p)  # exp(G_t)
    to_end = _on_lanes(jnp.exp(g_col[chunk - 1:] - g_col) * dt_col, p)  # exp(G_L - G_t) dt_t
    x32 = x_ref[0].astype(F32)
    y = _dot(big_c, state.astype(dtype)) * eg + x32 * d_ref[...]
    s_ref[...] = state * eg[chunk - 1:] + _dot(big_b, (x32 * to_end).astype(dtype), 0, 0)
    # inside the chunk: the group's scores once, each head's decayed and masked copy times its lanes of x
    scores = _dot(big_c, big_b, 1, 1)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    cols = [None] * (e * p // _LANES)
    for j in range(e):
        mixed = (scores * (_decay(seen, g_col, g_row, j) * dt_row[j:j + 1, :])).astype(dtype)
        for c, first in _columns_of(j, p):
            part = _dot(mixed, x_ref[0, :, c * _LANES:(c + 1) * _LANES])
            cols[c] = part if first <= 0 else jnp.where(lane >= first, part, cols[c])
    y_ref[0] = y + _joined(cols)


def _bwd_kernel(sel_ref, x_ref, b_ref, c_ref, dtc_ref, dtr_ref, ar_ref, ac_ref, d_ref, s_ref, y_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref, ds_ref, *, p):
    @pl.when(pl.program_id(2) == 0)
    def _():  # the sequence's last chunk: nothing comes after it
        ds_ref[...] = jnp.zeros_like(ds_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    dt_col, dt_row, a_row = dtc_ref[0, 0], dtr_ref[0, 0], ar_ref[0]
    chunk, e = dt_col.shape
    seen, lower, g_col, g_row = _running_sums(dt_col, dt_row, a_row, ac_ref[0])
    big_b, big_c = b_ref[0], c_ref[0]
    dtype = big_b.dtype
    state, d_state = s_ref[0, 0], ds_ref[...]  # the chunk's incoming state; the cotangent of the state it leaves
    state_b, d_next = state.astype(dtype), d_state.astype(dtype)
    eg = _on_lanes(jnp.exp(g_col), p)
    from_end = jnp.exp(g_col[chunk - 1:] - g_col)  # exp(G_L - G_t)
    x32, dy, skip = x_ref[0].astype(F32), dy_ref[0], d_ref[...]
    # through the states: y += (C S) exp(G_t);  S' = S exp(G_L) + B^T (x dt exp(G_L - G_t))
    from_state = _dot(big_c, state_b) * eg  # as the forward made it
    d_from = (dy * eg).astype(dtype)
    d_c = _dot(d_from, state_b, 1, 1)
    ds_ref[...] = d_state * eg[chunk - 1:] + _dot(big_c, d_from, 0, 0)
    to_end = _on_lanes(from_end * dt_col, p)
    d_b = _dot((x32 * to_end).astype(dtype), d_next, 1, 1)
    via_next = _dot(big_b, d_next)  # (L, e x P): the cotangent of x dt exp(G_L - G_t)
    to_next = x32 * to_end * via_next
    # inside the chunk, a head at a time: the scores' cotangent, and the mixed scores (with dt_i: x's cotangent;
    # without: dt's own) transposed times dy
    scores = _dot(big_c, big_b, 1, 1)
    dy_b = dy.astype(dtype)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    d_scores = jnp.zeros((chunk, chunk), F32)
    d_x, d_xdt = ([None] * (e * p // _LANES) for _ in range(2))
    for j in range(e):
        decay = _decay(seen, g_col, g_row, j)
        weight = decay * dt_row[j:j + 1, :]
        mixed, plain = (scores * weight).astype(dtype), (scores * decay).astype(dtype)
        d_mixed = 0.0
        for c, first in _columns_of(j, p):
            dy_c = dy_b[:, c * _LANES:(c + 1) * _LANES]
            for cols, m in ((d_x, mixed), (d_xdt, plain)):
                part = _dot(m, dy_c, 0, 0)
                cols[c] = part if first <= 0 else jnp.where(lane >= first, part, cols[c])
            if p < _LANES:  # the head's own lanes of the column
                dy_c = jnp.where((lane >= first) & (lane < first + p), dy_c, jnp.zeros_like(dy_c))
            d_mixed = d_mixed + _dot(dy_c, x_ref[0, :, c * _LANES:(c + 1) * _LANES], 1, 1)
        d_scores = d_scores + d_mixed * weight
    d_x = _joined(d_x)  # x's cotangent through what its token writes inside the chunk
    dx_ref[0] = (d_x + to_end * via_next + dy * skip).astype(dx_ref.dtype)
    d_scores = d_scores.astype(dtype)
    dc_ref[0] = (d_c + _dot(d_scores, big_b)).astype(dc_ref.dtype)
    db_ref[0] = (d_b + _dot(d_scores, big_c, 0, 0)).astype(db_ref.dtype)
    # G's cotangent a token and head. Inside the chunk: + what token t reads (y without D x and the state's part), -
    # what token i writes, the same rounded factors on both sides, so that a token's reading of its own and its
    # neighbours' writes cancels to float32. Through the states, term by term as the forward made them: + the
    # state's part of y at t, - what i hands to the next state; at the last token (G_L) + all that is handed on, the
    # incoming state's share of the next one and the chunk's own.
    reads = dy_b.astype(F32) * (y_ref[0] - x32 * skip - from_state) + dy * from_state
    handed_on = jnp.sum(d_state * state, axis=0, keepdims=True) * eg[chunk - 1:] + jnp.sum(to_next, axis=0, keepdims=True)
    d_g = reads - x32 * d_x - to_next
    row = lax.broadcasted_iota(jnp.int32, d_g.shape, 0)
    d_g = jnp.where(row == chunk - 1, d_g + handed_on, d_g)
    sel = sel_ref[...]
    d_dt = _dot10(x32 * (_joined(d_xdt) + _on_lanes(from_end, p) * via_next), sel)  # (L, e): dt's own cotangent
    d_g = _dot10(d_g, sel)
    d_run = _dot01(lower, d_g, 0, 0)  # G is the running sum of dt A: the sum over t >= i of dG_t
    ddt_ref[0, 0] = d_dt + a_row * d_run
    da_ref[0, 0] += jnp.sum(dt_col * d_run, axis=0, keepdims=True)
    dd_ref[0] += jnp.sum(dy * x32, axis=0, keepdims=True)


def _call(kernel, name, operands, outs, *, b, s, g, e, lanes, state, chunk, backward, interpret):
    """One of the two kernels over the grid (rows, groups, chunks), the
    backward's chunks last to first. ``operands`` are (kind, array) and
    ``outs`` (kind, shape): "token" (b, s, g x width), "dt_col" (b, g, s, e),
    "dt_row" (b, g, e, s), "a_row" (g, 1, e), "a_col" (g, e, 1), "lanes" (1, g x
    e x P), "state" (b, chunks, N, g x e x P), "per_row" (b, g, 1, e) and
    "row_lanes" (b, 1, g x e x P) (sums over a row's chunks), "whole"."""
    n = s // chunk

    def at(c):
        return n - 1 - c if backward else c

    def spec(kind, shape):
        block, index = {
            "token": ((1, chunk, shape[-1] // g), lambda r, gi, c: (r, at(c), gi)),
            "dt_col": ((1, 1, chunk, shape[-1]), lambda r, gi, c: (r, gi, at(c), 0)),
            "dt_row": ((1, 1, shape[-2], chunk), lambda r, gi, c: (r, gi, 0, at(c))),
            "a_row": ((1, 1, shape[-1]), lambda r, gi, c: (gi, 0, 0)),
            "a_col": ((1, shape[-2], 1), lambda r, gi, c: (gi, 0, 0)),
            "lanes": ((1, lanes), lambda r, gi, c: (0, gi)),
            "state": ((1, 1, state, lanes), lambda r, gi, c: (r, at(c), 0, gi)),
            "per_row": ((1, 1, 1, shape[-1]), lambda r, gi, c: (r, gi, 0, 0)),
            "row_lanes": ((1, 1, lanes), lambda r, gi, c: (r, 0, gi)),
            "whole": (shape, lambda r, gi, c: (0,) * len(shape)),
        }[kind]
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    moved = sum(x.size * x.dtype.itemsize for _, x in [*operands, *outs])
    return pl.pallas_call(
        kernel,
        out_shape=[o for _, o in outs],
        grid=(b, g, n),
        in_specs=[spec(kind, x.shape) for kind, x in operands],
        out_specs=[spec(kind, o.shape) for kind, o in outs],
        scratch_shapes=[pltpu.VMEM((state, lanes), F32)],  # the state, or its cotangent
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=_chunk_flops(chunk, lanes, state, e, backward) * b * g * n, bytes_accessed=moved,
            transcendentals=b * g * n * e * chunk * chunk),
        interpret=interpret,
        name=name,  # what a profile calls this kernel
    )(*(x for _, x in operands))


def _sizes(x, big_b, heads, groups, chunk):
    b, s, inner = x.shape
    return dict(b=b, s=s, g=groups, e=heads // groups, lanes=inner // groups, state=big_b.shape[-1] // groups, chunk=chunk)


def _chunk_flops(chunk, lanes, state, e, backward):
    """Operations of a chunk of a group's products as they run (a head's on
    its whole 128-lane columns): what the scheduler is told, not a metric."""
    per_head = 2 * chunk * chunk * max(_LANES, lanes // e)
    wide = 2 * chunk * state * lanes
    if not backward:
        return 2 * chunk * chunk * state + e * per_head + 2 * wide
    return 3 * 2 * chunk * chunk * state + 2 * e * per_head + 4 * wide + 6 * 2 * chunk * lanes * e


def _operands(x, big_b, big_c, dt, rate, skip, heads, groups):
    """The kernels' operands in the layouts they read: dt in both orientations
    a group, A as a row and as a column a group, D on its heads' lanes."""
    b, s, _ = x.shape
    e = heads // groups
    by_group = dt.astype(F32).reshape(b, s, groups, e)
    rate = rate.astype(F32)
    return [
        ("token", x), ("token", big_b), ("token", big_c),
        ("dt_col", jnp.transpose(by_group, (0, 2, 1, 3))), ("dt_row", jnp.transpose(by_group, (0, 2, 3, 1))),
        ("a_row", rate.reshape(groups, 1, e)), ("a_col", rate.reshape(groups, e, 1)),
        ("lanes", jnp.repeat(skip.astype(F32), x.shape[-1] // heads)[None, :]),
    ]


def kept_for_backward(b, s, heads, head_dim, state, chunk):
    """What a differentiated call keeps from its forward to its backward
    beside its operands and y, as ``_call``'s (kind, shape): each chunk's
    incoming state, float32, heads on the lanes. ``s`` a multiple of ``chunk``."""
    return [("state", jax.ShapeDtypeStruct((b, s // chunk, state, heads * head_dim), F32))]


def _forward(x, big_b, big_c, dt, rate, skip, heads, groups, chunk, interpret, save_states):
    sizes = _sizes(x, big_b, heads, groups, chunk)
    p = x.shape[-1] // heads
    outs = [("token", jax.ShapeDtypeStruct(x.shape, F32))]
    if save_states:
        outs += kept_for_backward(sizes["b"], sizes["s"], heads, p, sizes["state"], chunk)
    y, *saved = _call(
        functools.partial(_fwd_kernel, p=p, save_states=save_states), "ssd_fwd",
        _operands(x, big_b, big_c, dt, rate, skip, heads, groups), outs,
        **sizes, backward=False, interpret=interpret)
    return y, saved


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _ssd(x, big_b, big_c, dt, rate, skip, heads, groups, chunk, interpret):
    return _forward(x, big_b, big_c, dt, rate, skip, heads, groups, chunk, interpret, False)[0]


def _vjp_fwd(x, big_b, big_c, dt, rate, skip, heads, groups, chunk, interpret):
    y, kept = _forward(x, big_b, big_c, dt, rate, skip, heads, groups, chunk, interpret, True)
    return y, ((x, big_b, big_c, dt, rate, skip), kept, y)


def _vjp_bwd(heads, groups, chunk, interpret, residuals, d_y):
    (x, big_b, big_c, dt, rate, skip), kept, y = residuals
    sizes = _sizes(x, big_b, heads, groups, chunk)
    b, s, e, p = sizes["b"], sizes["s"], sizes["e"], x.shape[-1] // heads
    kept = [(kind, t) for (kind, _), t in zip(kept_for_backward(b, s, heads, p, sizes["state"], chunk), kept)]

    def like(t):
        return ("token", jax.ShapeDtypeStruct(t.shape, t.dtype))

    d_x, d_b, d_c, d_dt, d_rate, d_skip = _call(
        functools.partial(_bwd_kernel, p=p), "ssd_bwd",
        [("whole", jnp.asarray(_head_sums(sizes["lanes"], e), jnp.bfloat16)),
         *_operands(x, big_b, big_c, dt, rate, skip, heads, groups), *kept, ("token", y), ("token", d_y.astype(F32))],
        [like(x), like(big_b), like(big_c), ("dt_col", jax.ShapeDtypeStruct((b, groups, s, e), F32)),
         ("per_row", jax.ShapeDtypeStruct((b, groups, 1, e), F32)),
         ("row_lanes", jax.ShapeDtypeStruct((b, 1, x.shape[-1]), F32))],
        **sizes, backward=True, interpret=interpret)
    d_dt = jnp.transpose(d_dt, (0, 2, 1, 3)).reshape(dt.shape)
    d_skip = d_skip.sum((0, 1)).reshape(heads, p).sum(-1)
    return (d_x, d_b, d_c, d_dt.astype(dt.dtype), d_rate.sum(0).reshape(heads).astype(rate.dtype),
            d_skip.astype(skip.dtype))


_ssd.defvjp(_vjp_fwd, _vjp_bwd)


def ssd_kernel(x, big_b, big_c, dt, rate, skip, *, heads: int, groups: int, chunk: int = 128, interpret: bool = False):
    """y of the state-space recurrence through the kernels. x: (b, s, heads x
    P) and B, C: (b, s, groups x N) in the tower's dtype, the heads and groups
    on the lanes, as the kernels read them; dt: (b, s, heads) float32 >= 0;
    rate (A <= 0) and skip (D): (heads,) float32. ``s`` a multiple of
    ``chunk``; ``chunk``, N and a group's heads x P multiples of 128, P a
    divisor or a multiple of 128 (``ops/ssm.py ssm_core`` is where the mixer
    asks). Returns (b, s, heads x P) float32. Differentiated, it saves its
    operands as they came, y, and each chunk's incoming state (float32, b x s /
    chunk x N x heads x P: :func:`kept_for_backward`; ``ssm_core`` reports its
    bytes); the cotangents come back in the operands' shapes and dtypes.
    ``interpret=True`` runs the Pallas interpreter (CPU testing)."""
    return _ssd(x, big_b, big_c, dt, rate, skip, heads, groups, chunk, interpret)
