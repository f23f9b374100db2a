"""The gated delta rule's chunked form (ops/gated_delta_rule.py has the
equations) as two Pallas TPU kernels: a chunk of C tokens of a few heads lives in
VMEM from its operands to its output, the state crosses chunks in VMEM scratch
along a sequential axis of the grid, and HBM sees q, k, v, g, beta, o (and, for
the backward, each chunk's incoming state and what the forward solved of the
chunk) once.

One program = one batch row, ``heads`` heads (a static loop over aligned lane
windows of the (1, C, heads x d) blocks, as ``short_attn_fwd`` cuts its heads),
one chunk. The grid is (rows, head groups, chunks), chunks innermost and in order
(the backward walks them last to first). Per head the program holds what
``_chunked`` holds per chunk:

- the running sum G of g and G_C - G as one product of a 0/1 matrix with g
  (:func:`_decay_ops`; g is float32, so the product takes it in three bf16 pieces,
  exact because the matrix is 0/1, and adds in float32), and per halving level
  the exponent of each token against its block's reference token, G_t - G_ref or
  G_ref - G_t, the reference rows broadcast along sublanes
  (:func:`_level_exponents`): ``_chunked``'s differences, ``_chunked``'s precision;
- A and P by the same halving as ``_chunked`` (no quotient of decays: both
  factors of a level are exponentials of sums of g <= 0), each level one
  (2C x dk) @ (dk x C) product of operands in the tower's dtype, of which the
  level's quadrants are kept by a mask (:func:`_levels`);
- (I + A)^-1 by the same block recursion, float32 at the MXU's full precision:
  with T block-diagonal at level l, T <- T - T (A_l T) is all of that level's
  [[Ta, 0], [-Tb A_ba Ta, Tb]] at once. A level is two DEPENDENT 64 x 64
  products, and what they cost is their latency: the program's heads go through
  the levels side by side (:func:`_inverses`), which halved the forward on the
  chip (PERF.md section 6, PR 34);
- W, U, O and the next state as in ``_chunked``; the state is kept transposed
  (dv x dk), so its decay is a multiply along lanes;
- the mixer's per-head statistics, where the call asks for them (the mixer's
  does: ``qk_norm``, ``o_eps``), on the (C, d) tile a head is here anyway: a
  raw head of q and k is l2-normalised row by row where it is loaded (a lane
  reduction of eight registers, in float32, then the cast XLA would have made
  before HBM: :func:`_l2norm`), and o leaves over its rows' root mean square
  (:func:`_rms`). Around the kernels these would be reductions over d of a
  (b, s, h, d) view of a (b, s, h x d) array, on a TPU a copy each way.

The backward is the gradient of the same function, and solves nothing again.
Where the call is differentiated the forward writes, beside each chunk's
incoming state, what it solved of the chunk and head: T = (I + A)^-1 and A /
beta in float32 (the inverse's cotangent -T^T dT T^T and beta's sum(dA A /
beta) are made of them at full precision) and P in the tower's dtype (its cast
is all the backward uses), a program's heads side by side on the lanes of one
(C, heads x C) block each, and W and U in the tower's dtype where o lies ((b,
s, h x d)): 72 KB a chunk-head beside the state's 64, alive as long as the
states are (:func:`kept_for_backward`). The backward reads them and makes
again only what is cheap and wide, from the saved operands: the running sums
and the eight exponentials, the l2 norms, the scaled operands; of the
forward's 24 products a head it repeats the running sums' three, so its own
twenty-three start when their operands are loaded and not behind the levels'
seven score products, the inverse's ten dependent ones and the three from T to
W, U and o (PERF.md section 6, PR 40: the backward waited 7.1 of its 16.6 ms
a call for them). The plain forward (nothing differentiated: inference)
writes o alone and is the program it was. Under ``jax.checkpoint`` both of a
layer's forwards are the differentiated one: the first one's blocks are
written and never read (PERF.md section 7).
Products take operands of the tower's dtype where the forward's do, the
inverse's cotangent is float32. G enters a chunk only through factors (row
e^G) and (column e^-G), so its cotangent is accumulated from the scaled
operands' (+ for a level's row tokens, - for its column tokens; the reference
token's cancels) and g's is one reversed running sum of it. The l2 norms' cotangents
rs (d - n (n . d)) are applied to the float32 d_q and d_k before their final
cast, so what is returned are the raw operands' cotangents. The head norm's
backward comes first and waits for nothing the chunk recomputes: it reads the
normalised o the forward stored and each row's 1 / rms, which the
differentiated forward writes beside the states (recomputed from the chunk's
own o it would hold every product of the backward back: 4.2 ms a call on the
chip, PERF.md section 6, PR 36).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import L2_EPS

__all__ = ["delta_rule_kernel", "heads_per_program", "kept_for_backward"]

F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def heads_per_program(num_heads: int) -> int:
    """Heads one program takes: their chains are independent, so the scheduler
    interleaves them (PERF.md section 6, PR 34)."""
    return max(n for n in (4, 2, 1) if num_heads % n == 0)


def _decay_ops(chunk: int) -> np.ndarray:
    """The 0/1 matrix (2C, C) whose product with g (C, dk) is the running sum
    G_t (rows t: the sum up to t) and G_C - G_t (rows C + t: the sum after t)."""
    t = np.arange(chunk)[:, None]
    j = np.arange(chunk)[None, :]
    return np.concatenate([j <= t, j > t]).astype(np.float32)


def _levels(chunk: int) -> np.ndarray:
    """(C, C) int32: for i < t the halving level whose quadrant holds the pair
    (the highest bit in which t and i differ), log2 C on the diagonal, -1 above."""
    t = np.arange(chunk)[:, None]
    i = np.arange(chunk)[None, :]
    top = np.floor(np.log2(np.maximum(t ^ i, 1))).astype(np.int32)
    return np.where(t > i, top, np.where(t == i, chunk.bit_length() - 1, -1)).astype(np.int32)


def _dot(a, b, contract_a=1, contract_b=0, precision=None):
    return lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=F32, precision=precision,
    )


def _dot01(ops, x):
    """``ops @ x`` for a 0/1 matrix (bf16) and a float32 x, to float32 accuracy:
    x in three bf16 pieces, each product exact, summed in float32."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(F32)).astype(jnp.bfloat16)
    return _dot(ops, hi) + _dot(ops, mid) + _dot(ops, lo)


def _level_exponents(run):
    """Per halving level l (half h = 2^l) the exponent of each token against its
    block's reference token, the last of the left half of its 2h-block: G_t -
    G_ref for a token of the right half, G_ref - G_t for one of the left half,
    both <= 0 for g <= 0. ``run`` is G (C, dk) float32; the reference rows are
    sublane broadcasts, whole (8, 128) tiles where a block is that large."""
    chunk, dk = run.shape
    token = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    tiles = run.reshape(chunk // 8, 8, dk)
    row_in_tile = lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
    out = []
    for lv in range(chunk.bit_length() - 1):
        half = 1 << lv
        if half >= 4:
            blocks = run.reshape(chunk // (2 * half), 2 * half, dk)
            ref = jnp.broadcast_to(blocks[:, half - 1:half], blocks.shape)
        else:  # several blocks to a tile: the tile's reference rows, block by block
            ref = jnp.broadcast_to(tiles[:, half - 1:half], tiles.shape)
            for start in range(2 * half, 8, 2 * half):
                ref = jnp.where(row_in_tile >= start,
                                jnp.broadcast_to(tiles[:, start + half - 1:start + half], tiles.shape), ref)
        ref = ref.reshape(chunk, dk)
        out.append(jnp.where((token >> lv) & 1 == 1, run - ref, ref - run))
    return out


def _decays(g, ops):
    """One head's decays from g (C, dk) float32: exp(G), exp(G_C - G) (C, dk) and
    the levels' (:func:`_level_exponents`), all <= 1."""
    chunk = g.shape[0]
    sums = _dot01(ops, g)  # G and G_C - G
    eg, ee = jnp.exp(sums[:chunk]), jnp.exp(sums[chunk:])
    return eg, ee, [jnp.exp(x) for x in _level_exponents(sums[:chunk])]


def _scores(q, k, e_levels, level):
    """One head's chunk up to the matrix to invert. q, k (C, dk) in the tower's
    dtype, the levels' decays of :func:`_decays`. Returns A / beta and P (C, C)
    float32."""
    chunk = q.shape[0]
    dt = q.dtype
    qf, kf = q.astype(F32), k.astype(F32)
    a0 = jnp.zeros((chunk, chunk), F32)
    p = jnp.zeros((chunk, chunk), F32)
    for lv, e_l in enumerate(e_levels):
        kl = (kf * e_l).astype(dt)
        x = _dot(jnp.concatenate([kl, (qf * e_l).astype(dt)], 0), kl, 1, 1)  # (2C, C)
        a0 = jnp.where(level == lv, x[:chunk], a0)
        p = jnp.where(level == lv, x[chunk:], p)
    p = jnp.where(level == len(e_levels), _dot(q, k, 1, 1), p)  # q_t . k_t: a token reads its own write
    return a0, p


def _inverses(a_of, level):
    """(I + A)^-1 of every head of the program, float32, level by level with the
    heads side by side: a level is two dependent products whose latency, not
    whose size, is the cost, and the heads' chains are independent."""
    chunk = level.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = jnp.where(rows == cols, 1.0, 0.0)
    t_of = [eye - jnp.where(level == 0, a, 0.0) for a in a_of]
    for lv in range(1, chunk.bit_length() - 1):
        right = [_dot(jnp.where(level == lv, a, 0.0), t, precision=_HIGHEST) for a, t in zip(a_of, t_of)]
        t_of = [t - _dot(t, x, precision=_HIGHEST) for t, x in zip(t_of, right)]
    return t_of


def _outputs(q, k, v, beta, z, e, p, t_inv):
    """One head's chunk from the inverse on: z the incoming state transposed
    (dv, dk) float32. Returns what the output, the next state and the backward
    are made of."""
    chunk, dk = q.shape
    dt = q.dtype
    qf, kf = q.astype(F32), k.astype(F32)
    eg, ee, _ = e
    kb = (beta * kf * eg).astype(dt)
    vb = (beta * v.astype(F32)).astype(dt)
    tb = t_inv.astype(dt)
    wu = _dot(tb, jnp.concatenate([kb, vb], 1))  # (C, dk + dv)
    wb = wu[:, :dk].astype(dt)
    zb = z.astype(dt)
    qg = (qf * eg).astype(dt)
    ws = _dot(jnp.concatenate([wb, qg], 0), zb, 1, 1)  # (2C, dv)
    ub = (wu[:, dk:] - ws[:chunk]).astype(dt)
    pb = p.astype(dt)
    out = ws[chunk:] + _dot(pb, ub)
    ke = (kf * ee).astype(dt)
    end_decay = eg[chunk - 1:]  # (1, dk): exp(G_C)
    z_next = z * end_decay + _dot(ub, ke, 0, 0)  # (dv, dk)
    return dict(qf=qf, kf=kf, eg=eg, ee=ee, kb=kb, vb=vb, tb=tb, wb=wb, zb=zb, qg=qg, ub=ub,
                pb=pb, ke=ke, end_decay=end_decay, out=out, z_next=z_next)


def _outputs_kept(q, k, v, beta, z, e, t_inv, pb, wb, ub):
    """What the backward takes of :func:`_outputs`, from the T (float32), P, W
    and U (the tower's dtype) the forward kept: the operands scaled again, no
    product."""
    chunk, dt = q.shape[0], q.dtype
    qf, kf = q.astype(F32), k.astype(F32)
    eg, ee, _ = e
    return dict(qf=qf, kf=kf, eg=eg, ee=ee, kb=(beta * kf * eg).astype(dt), vb=(beta * v.astype(F32)).astype(dt),
                tb=t_inv.astype(dt), wb=wb, zb=z.astype(dt), qg=(qf * eg).astype(dt), ub=ub, pb=pb,
                ke=(kf * ee).astype(dt), end_decay=eg[chunk - 1:])


def _cotangents_to_inverse(f, z, d_out, dz_next):
    """The backward of :func:`_outputs` down to the inverse's cotangent."""
    chunk, dk = f["qf"].shape
    dt = d_out.dtype
    dzb = dz_next.astype(dt)
    # the next state: z' = z exp(G_C) + U^T (K exp(G_C - G))
    d_end = jnp.sum(z * dz_next, 0, keepdims=True) * f["end_decay"]  # cotangent of G_C, (1, dk)
    d_ke = _dot(f["ub"], dzb)  # (C, dk)
    # the output: O = (Q e^G) z^T + P U;   U = U0 - W z^T
    d_u = _dot(f["ke"], dzb, 1, 1) + _dot(f["pb"], d_out, 0, 0)  # (C, dv)
    d_ub = d_u.astype(dt)
    d_p = _dot(d_out, f["ub"], 1, 1)  # (C, C)
    both = jnp.concatenate([d_out, -d_ub], 0)
    through_z = _dot(both, f["zb"])  # (2C, dk): d(Q e^G), dW
    d_qg, d_w = through_z[:chunk], through_z[chunk:]
    dz = dz_next * f["end_decay"] + _dot(both, jnp.concatenate([f["qg"], f["wb"]], 0), 0, 0)
    # W, U0 = T [beta K e^G | beta V]
    d_wu = jnp.concatenate([d_w.astype(dt), d_ub], 1)  # (C, dk + dv)
    d_t = _dot(d_wu, jnp.concatenate([f["kb"], f["vb"]], 1), 1, 1)  # (C, C)
    d_kv = _dot(f["tb"], d_wu, 0, 0)  # T^T [dW | dU0]
    return dict(d_end=d_end, d_ke=d_ke, d_p=d_p, d_qg=d_qg, dz=dz, d_t=d_t,
                d_kb=d_kv[:, :dk], d_vb=d_kv[:, dk:])


def _cotangents_of_operands(q, k, v, beta, e, a0, f, c, d_a, ops, level):
    """The rest of the backward: ``c`` from :func:`_cotangents_to_inverse`,
    ``d_a`` the cotangent of A (strict lower triangle). Returns float32 (dq, dk,
    dv, dg, dbeta (C, 1))."""
    chunk = q.shape[0]
    dt = q.dtype
    levels = chunk.bit_length() - 1
    qf, kf, eg, ee = f["qf"], f["kf"], f["eg"], f["ee"]
    through_kb = c["d_kb"] * kf * eg  # its beta, k and G cotangents share this
    d_beta = (jnp.sum(through_kb, 1, keepdims=True) + jnp.sum(c["d_vb"] * v.astype(F32), 1, keepdims=True)
              + jnp.sum(d_a * a0, 1, keepdims=True))
    d_k = c["d_kb"] * beta * eg
    d_g_run = through_kb * beta  # the cotangent of the running sum G, (C, dk)
    d_x = jnp.concatenate([d_a * beta, c["d_p"]], 0)  # (2C, C): the cotangents of A / beta and P
    level2 = jnp.concatenate([level, level], 0)
    d_q = jnp.zeros_like(qf)
    token = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    for lv, e_l in enumerate(e[2]):
        kl_f, ql_f = kf * e_l, qf * e_l
        kl = kl_f.astype(dt)
        d_xl = jnp.where(level2 == lv, d_x, 0.0).astype(dt)
        as_rows = _dot(d_xl, kl)  # (2C, dk): k and q as the level's row tokens
        as_cols = _dot(d_xl, jnp.concatenate([kl, ql_f.astype(dt)], 0), 0, 0)  # (C, dk): k as its column tokens
        d_kl = as_rows[:chunk] + as_cols
        d_ql = as_rows[chunk:]
        d_k = d_k + d_kl * e_l
        d_q = d_q + d_ql * e_l
        # a right-half token's exponent is G_t - G_ref, a left-half token's G_ref - G_t
        sign = jnp.where((token >> lv) & 1 == 1, 1.0, -1.0)
        d_g_run = d_g_run + sign * (d_kl * kl_f + d_ql * ql_f)
    own = jnp.sum(jnp.where(level == levels, c["d_p"], 0.0), 1, keepdims=True)  # dP[t, t]
    d_q = d_q + own * kf + c["d_qg"] * eg
    d_k = d_k + own * qf + c["d_ke"] * ee
    to_end = c["d_ke"] * kf * ee  # cotangent of G_C - G_t
    d_g_run = d_g_run + c["d_qg"] * qf * eg - to_end
    d_end = c["d_end"] + jnp.sum(to_end, 0, keepdims=True)
    d_g_run = jnp.where(token == chunk - 1, d_g_run + d_end, d_g_run)
    # G is the running sum of g: dg_j = sum over t >= j of dG_t
    d_g = lax.dot_general(
        ops[:chunk].astype(F32), d_g_run, (((0,), (0,)), ((), ())),
        preferred_element_type=F32, precision=_HIGHEST)
    return d_q, d_k, c["d_vb"] * beta, d_g, d_beta


def _l2norm(x, scale=1.0):
    """A head's raw (C, d) window as the mixer feeds the rule: ``l2norm`` of
    ops/gated_delta_rule.py row by row in float32 (a lane reduction), times
    ``scale``, in x's dtype: the arithmetic XLA runs where the kernels do not,
    in its order. Also the unit rows and their 1 / norm, for the cotangent."""
    xf = x.astype(F32)
    rs = lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + L2_EPS)
    unit = xf * rs
    return (unit if scale == 1.0 else unit * scale).astype(x.dtype), unit, rs


def _l2norm_cotangent(x, d, scale=1.0):
    """The cotangent of the raw window ``x`` from the float32 cotangent ``d`` of
    ``_l2norm(x, scale)``: rs (d - n (n . d)), one more lane reduction."""
    _, unit, rs = _l2norm(x)
    if scale != 1.0:
        d = d * scale
    return rs * (d - unit * jnp.sum(unit * d, -1, keepdims=True))


def _rms(out, eps):
    """A head's float32 (C, dv) output tile over its rows' root mean square
    (the mixer's head norm before its scale), and 1 / that, (C, 1)."""
    r = lax.rsqrt(jnp.mean(out * out, -1, keepdims=True) + eps)
    return out * r, r


def _loaded(q_ref, k_ref, v_ref, g_ref, beta_ref, heads, qk_norm):
    """What both kernels start with, per head of the program: its operands (q,
    k, v, g, beta (C, 1): aligned lane windows of the blocks; q and k
    normalised here where they arrive raw, ``qk_norm``)."""
    dk, dv = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads

    def rows(ref, j, scale):
        x = ref[0, :, j * dk:(j + 1) * dk]
        return _l2norm(x, scale)[0] if qk_norm else x

    return [
        (rows(q_ref, j, dk**-0.5), rows(k_ref, j, 1.0), v_ref[0, :, j * dv:(j + 1) * dv],
         g_ref[0, :, j * dk:(j + 1) * dk], beta_ref[0, 0, :, j:j + 1])
        for j in range(heads)
    ]


def _fwd_kernel(ops_ref, level_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                heads, save_states, qk_norm, o_eps):
    # the differentiated forward also writes each chunk's incoming state, what it solved (T, A / beta, P, W, U) and,
    # with the head norm, each row's 1 / rms
    *saved, z_ref = rest
    states_ref, t_ref, a_ref, p_ref, w_ref, u_ref, r_ref = (*saved, *[None] * 7)[:7]
    dk, dv = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    ops, level = ops_ref[...], level_ref[...]
    chunk = level.shape[0]
    operands = _loaded(q_ref, k_ref, v_ref, g_ref, beta_ref, heads, qk_norm)
    decays, scores = [], []
    for q, k, _, g, _ in operands:  # a head's decays, then its scores, head by head
        decays.append(_decays(g, ops))
        scores.append(_scores(q, k, decays[-1][2], level))
    inverses = _inverses([a0 * beta for (a0, _), (*_, beta) in zip(scores, operands)], level)
    lane = lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    rs = jnp.zeros(lane.shape, F32)
    for j, ((q, k, v, _, beta), e, (a0, p), t_inv) in enumerate(zip(operands, decays, scores, inverses)):
        z = z_ref[j]
        f = _outputs(q, k, v, beta, z, e, p, t_inv)
        if save_states:
            states_ref[0, 0, :, j * dk:(j + 1) * dk] = z
            for ref, x in ((t_ref, t_inv), (a_ref, a0), (p_ref, f["pb"])):
                ref[0, 0, 0, :, j * chunk:(j + 1) * chunk] = x
            w_ref[0, :, j * dk:(j + 1) * dk] = f["wb"]
            u_ref[0, :, j * dv:(j + 1) * dv] = f["ub"]
        out = f["out"]
        if o_eps is not None:
            out, r = _rms(out, o_eps)
            rs = jnp.where(lane == j, r, rs)  # a program's heads on the lanes, as beta
        o_ref[0, :, j * dv:(j + 1) * dv] = out.astype(o_ref.dtype)
        z_ref[j] = f["z_next"]
    if r_ref is not None:
        r_ref[0, 0] = rs


def _bwd_kernel(ops_ref, level_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, t_ref, a_ref, p_ref, w_ref, u_ref,
                *rest, heads, qk_norm, o_norm):
    # with the head norm: its saved 1 / rms, last of what the forward kept, and after do the normalised output it stored
    r_ref, do_ref, unit_ref = rest[:3] if o_norm else (None, rest[0], None)
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dz_ref = rest[-6:]
    dk, dv = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        dz_ref[...] = jnp.zeros_like(dz_ref)

    ops, level = ops_ref[...], level_ref[...]
    chunk = level.shape[0]
    operands = _loaded(q_ref, k_ref, v_ref, g_ref, beta_ref, heads, qk_norm)

    def solved(ref, j):  # head j's (C, C) window of a block the forward kept
        return ref[0, 0, 0, :, j * chunk:(j + 1) * chunk]

    # what the forward solved, read back: no score, no level of the inverse, neither W nor U is made again here
    inverses = [solved(t_ref, j) for j in range(heads)]
    decays, forwards, partials = [], [], []
    for j, ((q, k, v, g, beta), t_inv) in enumerate(zip(operands, inverses)):
        z = states_ref[0, 0, :, j * dk:(j + 1) * dk]
        e = _decays(g, ops)
        f = _outputs_kept(q, k, v, beta, z, e, t_inv, solved(p_ref, j),
                          w_ref[0, :, j * dk:(j + 1) * dk], u_ref[0, :, j * dv:(j + 1) * dv])
        d_out = do_ref[0, :, j * dv:(j + 1) * dv]
        if o_norm:  # the head norm's backward, from what the forward left: nothing here waits for the chunk's recomputation
            unit, d_unit = unit_ref[0, :, j * dv:(j + 1) * dv].astype(F32), d_out.astype(F32)
            d_out = r_ref[0, 0, :, j:j + 1] * (d_unit - unit * jnp.mean(unit * d_unit, -1, keepdims=True))
            d_out = d_out.astype(do_ref.dtype)
        c = _cotangents_to_inverse(f, z, d_out, dz_ref[j])
        dz_ref[j] = c["dz"]
        decays.append(e)
        forwards.append(f)
        partials.append(c)
    # T = (I + A)^-1: dA = -T^T dT T^T on the strict lower triangle, the heads side by side
    right = [_dot(c["d_t"], t, 1, 1, precision=_HIGHEST) for c, t in zip(partials, inverses)]
    d_as = [jnp.where((level >= 0) & (level < chunk.bit_length() - 1), -_dot(t, x, 0, 0, precision=_HIGHEST), 0.0)
            for t, x in zip(inverses, right)]
    lane = lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    d_betas = jnp.zeros((chunk, heads), F32)
    for j, ((q, k, v, _, beta), e, f, c, d_a) in enumerate(zip(operands, decays, forwards, partials, d_as)):
        d_q, d_k, d_v, d_g, d_beta = _cotangents_of_operands(q, k, v, beta, e, solved(a_ref, j), f, c, d_a, ops, level)
        lk, lv = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        if qk_norm:  # the cotangents of the raw branches, before the final cast
            d_q = _l2norm_cotangent(q_ref[0, :, lk], d_q, dk**-0.5)
            d_k = _l2norm_cotangent(k_ref[0, :, lk], d_k)
        dq_ref[0, :, lk] = d_q.astype(dq_ref.dtype)
        dk_ref[0, :, lk] = d_k.astype(dk_ref.dtype)
        dv_ref[0, :, lv] = d_v.astype(dv_ref.dtype)
        dg_ref[0, :, lk] = d_g
        d_betas = jnp.where(lane == j, d_beta, d_betas)
    dbeta_ref[0, 0] = d_betas


def _by_group(beta, heads):  # (b, s, h) -> (b, h / heads, s, heads): a program's heads on the lanes
    b, s, h = beta.shape
    return jnp.transpose(beta.reshape(b, s, h // heads, heads), (0, 2, 1, 3))


def _call(kernel, name, operands, outs, *, b, s, h, dk, dv, chunk, heads, backward, interpret):
    """One of the two kernels over the grid (rows, head groups, chunks), the
    backward's chunks last to first. ``operands`` are (kind, array) and ``outs``
    (kind, shape): "token" (b, s, h x d), "beta" (b, h / heads, s, heads),
    "state" (b, chunks, dv, h x dk), "solved" (b, chunks, h / heads, C, heads x
    C): a chunk's (C, C) matrices, a program's heads side by side on the lanes
    (a block is the array's whole last two dimensions, whatever ``heads``)."""
    n = s // chunk

    def at(c):
        return n - 1 - c if backward else c

    def spec(kind, shape):
        block, index = {
            "token": ((1, chunk, shape[-1] // h * heads), lambda r, hg, c: (r, at(c), hg)),
            "beta": ((1, 1, chunk, heads), lambda r, hg, c: (r, hg, at(c), 0)),
            "state": ((1, 1, dv, heads * dk), lambda r, hg, c: (r, at(c), 0, hg)),
            "solved": ((1, 1, 1, chunk, heads * chunk), lambda r, hg, c: (r, at(c), hg, 0, 0)),
        }[kind]
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda r, hg, c: (0, 0), memory_space=pltpu.VMEM)

    consts = (jnp.asarray(_decay_ops(chunk), jnp.bfloat16), jnp.asarray(_levels(chunk)))
    moved = sum(x.size * x.dtype.itemsize for _, x in [*operands, *outs])
    return pl.pallas_call(
        kernel,
        out_shape=[o for _, o in outs],
        grid=(b, h // heads, n),
        in_specs=[whole(c) for c in consts] + [spec(kind, x.shape) for kind, x in operands],
        out_specs=[spec(kind, o.shape) for kind, o in outs],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), F32)],  # the state, or its cotangent
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(_chunk_flops(chunk, dk, dv, backward) * b * h * n), bytes_accessed=int(moved),
            transcendentals=int(b * h * n * (chunk.bit_length() + 1) * chunk * dk)),
        interpret=interpret,
        name=name,  # what a profile calls this kernel
    )(*consts, *(x for _, x in operands))


def _chunk_flops(chunk, dk, dv, backward):
    """Operations of a chunk-head's products, a float32 one counted as six bf16
    passes: what the scheduler is told (``cost_estimate``), not a metric."""
    levels = chunk.bit_length() - 1
    cc, cd, dd = chunk * chunk, chunk * (dk + dv) // 2, dk * dv
    sums = 3 * 2 * cc * dk  # G and G_C - G, g in three pieces
    scores = (levels + 1) * 2 * cc * dk
    inverse = (levels - 1) * 2 * 6 * cc * chunk
    rest = 2 * cc * cd + 2 * chunk * dd + cc * dv + chunk * dd
    if not backward:
        return 2 * (sums + scores + inverse + rest)
    # the running sums again and its own products; what the forward solved is read, o and the next state are not made
    return 2 * sums + 2 * (
        5 * chunk * dd + 2 * cc * dv + 4 * cc * cd + 2 * 6 * cc * chunk + levels * 4 * cc * dk + 6 * cc * dk)


def _operands(q, k, v, g, beta, heads):
    return [("token", x) for x in (q, k, v, g.astype(F32))] + [("beta", _by_group(beta.astype(F32), heads))]


def _sizes(q, v, beta):
    (b, s, h), heads = beta.shape, heads_per_program(beta.shape[-1])
    return dict(b=b, s=s, h=h, dk=q.shape[-1] // h, dv=v.shape[-1] // h, heads=heads)


def kept_for_backward(b, s, h, dk, dv, dtype, chunk, o_norm):
    """What a differentiated call keeps from its forward to its backward beside
    its operands and o, as ``_call``'s (kind, shape) in the order both kernels
    take them: each chunk's incoming state (float32); of what the forward
    solved a chunk and head, T = (I + A)^-1 and A / beta (float32: the
    inverse's cotangent and beta's are made of them at full precision), P, W
    and U (``dtype``, the tower's: their casts are all that is used); and with
    the head norm each row's 1 / rms. ``s`` a multiple of ``chunk``."""
    heads, n = heads_per_program(h), s // chunk
    solved = (b, n, h // heads, chunk, heads * chunk)
    kept = [("state", jax.ShapeDtypeStruct((b, n, dv, h * dk), F32))]
    kept += [("solved", jax.ShapeDtypeStruct(solved, x)) for x in (F32, F32, dtype)]
    kept += [("token", jax.ShapeDtypeStruct((b, s, h * d), dtype)) for d in (dk, dv)]
    if o_norm:
        kept.append(("beta", jax.ShapeDtypeStruct((b, h // heads, s, heads), F32)))
    return kept


def _forward(q, k, v, g, beta, chunk, interpret, qk_norm, o_eps, save_states):
    sizes = _sizes(q, v, beta)
    outs = [("token", jax.ShapeDtypeStruct(v.shape, v.dtype))]
    if save_states:
        outs += kept_for_backward(*(sizes[x] for x in ("b", "s", "h", "dk", "dv")), v.dtype, chunk, o_eps is not None)
    out, *saved = _call(
        functools.partial(_fwd_kernel, heads=sizes["heads"], save_states=save_states, qk_norm=qk_norm, o_eps=o_eps),
        "kda_fwd", _operands(q, k, v, g, beta, sizes["heads"]), outs,
        **sizes, chunk=chunk, backward=False, interpret=interpret)
    return out, saved


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _wide_kernel(q, k, v, g, beta, chunk, interpret, qk_norm, o_eps):
    return _forward(q, k, v, g, beta, chunk, interpret, qk_norm, o_eps, False)[0]


def _vjp_fwd(q, k, v, g, beta, chunk, interpret, qk_norm, o_eps):
    out, kept = _forward(q, k, v, g, beta, chunk, interpret, qk_norm, o_eps, True)
    return out, ((q, k, v, g, beta), kept, (out,) if o_eps is not None else ())


def _vjp_bwd(chunk, interpret, qk_norm, o_eps, residuals, d_out):
    (q, k, v, g, beta), kept, normed = residuals  # with the head norm: the normalised output too
    sizes = _sizes(q, v, beta)
    b, s, h, heads = (sizes[x] for x in ("b", "s", "h", "heads"))
    kinds = [kind for kind, _ in kept_for_backward(b, s, h, sizes["dk"], sizes["dv"], v.dtype, chunk, o_eps is not None)]

    def like(x, dtype=None):
        return ("token", jax.ShapeDtypeStruct(x.shape, dtype or x.dtype))

    d_q, d_k, d_v, d_g, d_beta = _call(
        functools.partial(_bwd_kernel, heads=heads, qk_norm=qk_norm, o_norm=o_eps is not None), "kda_bwd",
        [*_operands(q, k, v, g, beta, heads), *zip(kinds, kept), ("token", d_out.astype(v.dtype)),
         *(("token", x) for x in normed)],
        [like(q), like(k), like(v), like(g, F32), ("beta", jax.ShapeDtypeStruct((b, h // heads, s, heads), F32))],
        **sizes, chunk=chunk, backward=True, interpret=interpret)
    d_beta = jnp.transpose(d_beta, (0, 2, 1, 3)).reshape(beta.shape)
    return d_q, d_k, d_v, d_g.astype(g.dtype), d_beta.astype(beta.dtype)


_wide_kernel.defvjp(_vjp_fwd, _vjp_bwd)


def delta_rule_kernel(q, k, v, g, beta, chunk: int = 64, interpret: bool = False, qk_norm: bool = False,
                      o_eps: float | None = None):
    """o of the gated delta rule through the kernels. q, k, g: (b, s, h x dk),
    a head an aligned 128-lane window, as the kernels read them; v: (b, s, h x
    dv); beta: (b, s, h); s a multiple of ``chunk``, a power of two. Returns (b,
    s, h x dv) in v's dtype. The mixer's per-head statistics run on the tile the
    program holds, forward and backward. With ``qk_norm`` q and k arrive raw:
    q_h = l2norm(q_h) dk^-1/2, k_h = l2norm(k_h) at the load, and the cotangents
    are the raw operands'. With ``o_eps`` what is returned is o over its head's
    root mean square, o_h rsqrt(mean o_h^2 + o_eps) (the head RMS norm before
    its scale), taken on the float32 tile before it is stored. The per-head form
    (b, s, h, d) is taken too and returned (free views around the same call).
    Differentiated, it saves its operands as they came, each chunk's incoming
    state (float32, b x s / chunk x h x dk x dv), what the forward solved of
    each chunk and head, T = (I + A)^-1 and A / beta (float32) and P (v's
    dtype), chunk x chunk each, and W and U (v's dtype, o's shape), so that the
    backward starts at its own products and not at the scores and the
    inverse's five levels (under a rematerialised layer all of these live from
    the layer's second forward to its backward: :func:`kept_for_backward`
    lists them, ``delta_rule_core`` reports their bytes), and, with ``o_eps``,
    what it returned and each row's 1 / rms (float32, b x s x h), so that the
    norm's backward waits for nothing the chunk recomputes. ``interpret=True``
    runs the Pallas interpreter (CPU testing)."""
    if q.ndim == 3:
        return _wide_kernel(q, k, v, g, beta, chunk, interpret, qk_norm, o_eps)
    b, s, h, _ = q.shape
    wide = (x.reshape(b, s, -1) for x in (q, k, v, g))
    return _wide_kernel(*wide, beta, chunk, interpret, qk_norm, o_eps).reshape(b, s, h, -1)
