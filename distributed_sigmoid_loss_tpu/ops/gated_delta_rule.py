"""The gated delta rule with a decay per key channel (the recurrence of a KDA
layer), token by token and in the chunked form the towers run.

Per head, with a state S in R^(dk x dv), S_0 = 0, a log-decay g_t <= 0 per key
channel and a write strength beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`gated_delta_rule_recurrent` is that, one token at a time (``lax.scan``
over the sequence): the definition, for tests. :func:`chunk_gated_delta_rule`
computes the same o in chunks of C tokens. With u_t = beta_t (v_t - (exp(g_t)
k_t)^T S_{t-1}) the update is S_t = diag(exp(g_t)) S_{t-1} + k_t u_t^T, and
inside a chunk that starts at state S, with G_t the running sum of g from the
chunk's first token:

    (I + A) U = beta V - (beta K exp(G)) S,   A[t, i] = beta_t sum_c k_tc k_ic exp(G_tc - G_ic), i < t
    O = (Q exp(G)) S + (tril(P) + diag(q_t . k_t)) U,   P[t, i] = sum_c q_tc k_ic exp(G_tc - G_ic), i < t
    S' = diag(exp(G_C)) S + (K exp(G_C - G))^T U

so the chunk's own work is matrix products (on the MXU) and the state crosses
chunks in a scan of s / C steps.

**No quotient of decays.** exp(G_t - G_i) does not factor into exp(G_t) *
exp(-G_i) safely: a channel that forgets fast overflows the second factor
within a chunk. A and P are built by halving instead: for a block [a, a + 2h)
with its left half ending at m, every pair (t in the right half, i in the left
half) has G_t - G_i = (G_t - G_m) + (G_m - G_i), two exponents <= 0, so that
quadrant is one (h x dk) @ (dk x h) product of safely scaled operands; the two
halves recurse, down to single tokens. Six levels at C = 64, the operations of
one C x C product in all, exact for any g <= 0. The inverse of the unit lower
triangular I + A grows in the same sweep, [[Ta, 0], [-Tb A_ba Ta, Tb]] from the
halves' inverses, in float32 (a Neumann series would cancel catastrophically
where keys repeat).

**Which call takes which path** (:func:`delta_rule_core`, from what a call can
see: no option). Operands in bfloat16 on a TPU with dk = dv a multiple of 128
lanes run the chunk's work in two Pallas kernels (ops/pallas_delta_rule.py,
``kda_fwd`` / ``kda_bwd``): a chunk of a few heads stays in VMEM from q, k, v,
g, beta to o, the state crosses chunks in VMEM scratch along a sequential grid
axis, the operands are read where they lie ((b, s, h x d), a head an aligned
128-lane window), and the backward is a kernel of its own that reads, beside
the saved operands, what the differentiated forward wrote of each chunk and
head: the incoming state (float32, 64 KB) and what the forward solved, (I +
A)^-1, A / beta, P, W and U (72 KB), so that it makes no score, no level of
the inverse and no product from it again (``kept_bytes`` of
:func:`delta_rule_core`). The same halving,
the same block recursion for the inverse, the same operand types: bf16-grade
gradients, as the fused attention kernels', hence a bf16 tower only. The
mixer's per-head statistics run in them too (:func:`normed_chunk_gated_delta_rule`,
the mixer's call): q's and k's l2 norm where a head's rows are loaded, forward
and backward (the cotangents the kernels return are the raw branches'), and o
over its head's root mean square on the float32 tile before it is stored, its
backward from the stored o and each row's saved 1 / rms. What stays in XLA
around them: the padding of a sequence that is no multiple of the chunk, the
transposition of beta and of the saved 1 / rms (2 MB each), the head norm's
scale (a product with a (h x d,) vector) and no per-head (b, s, h, d) array:
on a TPU that shape and (b, s, h x d) are two tilings, and a view of one as the
other around a reduction over d is a copy through HBM each way (PERF.md section
6, PR 36). Under a ``jit`` over a mesh the kernels sit in a ``shard_map`` as the
attention kernels do (:func:`kernels_per_shard`). Every other call (float32
operands, the CPU, a head size that is no multiple of 128) takes
:func:`l2norm` and the head norm in XLA on the per-head form and between them
:func:`_chunked` below: XLA operations, its backward ``jax.grad``'s, recomputed
(``jax.checkpoint``) and run a few batch rows at a time (``lax.map``). It is
the oracle next to :func:`gated_delta_rule_recurrent`; on the chip it is a
hundred passes over HBM a call (PERF.md section 6, PR 32 and 34: at 16 rows x
1024 tokens x 32 heads 46.4 ms forward and 151.3 forward + backward where the
kernels take 10.9 and 26.9). (All six levels as one masked batched product were
tried there: fewer operations, 20 % slower, three times the generated code.)

**The branches' short convolution** (:func:`short_conv_silu`, the state-space
layer's too) takes the Pallas pair ``short_conv_fwd`` / ``short_conv_bwd``
(ops/pallas_short_conv.py) by the same kind of rule, :func:`short_conv_core`:
bfloat16 on a TPU, the channels a multiple of 128 and the sequence of 16; the
XLA form, :func:`short_causal_conv` with the bias and ``silu`` after it, anywhere else.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = [
    "gated_delta_rule_recurrent", "chunk_gated_delta_rule", "normed_chunk_gated_delta_rule", "delta_rule_core",
    "l2norm", "short_causal_conv", "short_conv_silu", "short_conv_core", "kernels_per_shard",
]

F32 = jnp.float32
L2_EPS = 1e-6
# Float32 intermediates of one pass of the chunked form, in bytes per array: the
# batch rows of a pass are chosen to stay under it.
_PASS_BYTES = 48 * 2**20


def l2norm(x):
    """x / sqrt(sum x^2 + 1e-6) over the last axis, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def short_causal_conv(x, kernel):
    """Causal depthwise convolution along the sequence: ``x`` (b, s, channels),
    ``kernel`` (taps, channels), out[t] = sum_j kernel[j] x[t - (taps - 1) + j],
    zeros before the first token, no bias. A few shifted products: the taps are
    four."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j : j + s] * kernel[j].astype(x.dtype) for j in range(taps))


def short_conv_core(tokens: int, channels: int, taps: int, dtype) -> dict:
    """Which form a call of :func:`short_conv_silu` takes, from what it can
    see: ``form`` is ``"kernel"`` (the Pallas pair ``short_conv_fwd`` /
    ``short_conv_bwd`` of ops/pallas_short_conv.py: bfloat16 operands, a TPU
    backend, the channels a multiple of 128 lanes and the sequence of 16 rows,
    as ``pallas_short_conv.short_conv_plan`` admits them) or ``"xla"``
    (:func:`short_causal_conv`, the bias and ``silu`` as XLA operations);
    ``tile`` is the (tokens, channels) block a program of the pair holds, None
    on the XLA form. The mixers run what this says and the step's trace-time
    record (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention  # the towers' one question about the backend
    from distributed_sigmoid_loss_tpu.ops.pallas_short_conv import short_conv_plan

    plan = None
    if jnp.dtype(dtype) == jnp.bfloat16 and flash_attention.flash_attention_available():
        plan = short_conv_plan(tokens, channels, taps)
    return {"form": "kernel" if plan else "xla", "channels": channels,
            "tile": (plan["tokens"], plan["channels"]) if plan else None}


def short_conv_silu(x, taps, bias=None):
    """``silu(short_causal_conv(x, taps) + bias)``, the mixers' call: x (b, s,
    channels), ``taps`` (k, channels) float32, ``bias`` (channels,) float32 or
    None; returns x's dtype. Where :func:`short_conv_core` says ``"kernel"``
    one Pallas kernel each way (x read once and y written once; backward x and
    dy read once for dx and the taps' and the bias's float32 cotangents; the
    shifted products summed in float32 and rounded once); anywhere else
    (float32, the CPU, odd widths) the XLA operations, which round each shifted
    product and the bias to x's dtype. Under a ``jit`` over a mesh the kernels
    sit in a ``shard_map`` (:func:`kernels_per_shard`: rows over ``dp``, the
    channels whole)."""
    if short_conv_core(x.shape[1], x.shape[2], taps.shape[0], x.dtype)["form"] == "kernel":
        from distributed_sigmoid_loss_tpu.ops import pallas_short_conv

        return kernels_per_shard(lambda rows: pallas_short_conv.short_conv_kernel(rows, taps, bias), 1, x)
    y = short_causal_conv(x, taps)
    return jax.nn.silu(y if bias is None else y + bias.astype(x.dtype))


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The recurrence, one token at a time, in float32 at full matmul precision.
    q, k, g: (b, s, h, dk); v: (b, s, h, dv); beta: (b, s, h). Returns (b, s, h, dv)."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    b, s, h, dk = q.shape
    hi = jax.lax.Precision.HIGHEST

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=hi)
        state = state + (beta_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state, precision=hi)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(out, 0, 1)


def _chunked(q, k, v, g, beta, *, chunk: int, dt):
    """The chunked form on (rows, s, h, d) operands, s a multiple of ``chunk``."""
    rows, s, h, dk = q.shape
    dv, n = v.shape[-1], s // chunk
    precision = jax.lax.Precision.HIGHEST if dt == F32 else None

    def mm(spec, a, b_):
        return jnp.einsum(
            spec, a.astype(dt), b_.astype(dt), preferred_element_type=F32, precision=precision
        )

    def mm32(spec, a, b_):
        return jnp.einsum(spec, a, b_, precision=jax.lax.Precision.HIGHEST)

    def by_chunk(x):  # (rows, s, h, d) -> (rows, h, n, chunk, d)
        return jnp.moveaxis(x.reshape(rows, n, chunk, h, -1), 3, 1)

    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    big_g = jnp.cumsum(by_chunk(g.astype(F32)), axis=-2)  # G, running from the chunk's start
    beta = by_chunk(beta.astype(F32)[..., None])  # (rows, h, n, chunk, 1)
    lead = big_g.shape[:-2]
    qk = jnp.stack([k, q]).astype(F32)  # the two row operands of A and P

    # A, P and (I + A)^-1 by halving, bottom-up: blocks of h tokens pair into 2h.
    p_low = jnp.zeros((*lead, chunk, 1, 1), F32)
    t_inv = jnp.ones((*lead, chunk, 1, 1), F32)
    half = 1
    while half < chunk:
        nb = chunk // (2 * half)
        g_b = big_g.reshape(*lead, nb, 2, half, dk)
        ref = g_b[..., 0, half - 1 :, :]  # the left half's last token, (…, nb, 1, dk)
        right = qk.reshape(2, *lead, nb, 2, half, dk)[..., 1, :, :] * jnp.exp(g_b[..., 1, :, :] - ref)
        left = k.astype(F32).reshape(*lead, nb, 2, half, dk)[..., 0, :, :] * jnp.exp(ref - g_b[..., 0, :, :])
        quadrant = mm("r...tc,...ic->r...ti", right, left)  # (2, …, nb, half, half)
        a_ba = quadrant[0] * beta.reshape(*lead, nb, 2, half, 1)[..., 1, :, :]
        halves = t_inv.reshape(*lead, nb, 2, half, half)
        t_a, t_b = halves[..., 0, :, :], halves[..., 1, :, :]
        t_ba = -mm32("...ij,...jk->...ik", t_b, mm32("...ij,...jk->...ik", a_ba, t_a))
        zeros = jnp.zeros_like(t_ba)
        t_inv = jnp.concatenate(
            [jnp.concatenate([t_a, zeros], -1), jnp.concatenate([t_ba, t_b], -1)], -2
        )
        lows = p_low.reshape(*lead, nb, 2, half, half)
        p_low = jnp.concatenate(
            [jnp.concatenate([lows[..., 0, :, :], zeros], -1),
             jnp.concatenate([quadrant[1], lows[..., 1, :, :]], -1)], -2
        )
        half *= 2
    t_inv, p_low = t_inv[..., 0, :, :], p_low[..., 0, :, :]  # (…, chunk, chunk)
    diagonal = jnp.sum(q.astype(F32) * k.astype(F32), -1)  # q_t . k_t: a token reads its own write
    p = p_low + diagonal[..., None] * jnp.eye(chunk, dtype=F32)

    decay = jnp.exp(big_g)  # from the chunk's start to each token, <= 1
    to_end = jnp.exp(big_g[..., -1:, :] - big_g)  # from each token to the chunk's end, <= 1
    w = mm("...ti,...ic->...tc", t_inv, beta * k.astype(F32) * decay)
    u_free = mm("...ti,...iv->...tv", t_inv, beta * v.astype(F32))
    q_in = q.astype(F32) * decay
    k_out = k.astype(F32) * to_end
    end_decay = decay[..., -1, :]  # (…, dk)

    def across(state, x):  # one chunk: state (rows, h, dk, dv)
        w_n, u_n, q_n, p_n, k_n, d_n = x
        u = u_n - mm("...tc,...cv->...tv", w_n, state)
        out = mm("...tc,...cv->...tv", q_n, state) + mm("...ti,...iv->...tv", p_n, u)
        state = state * d_n[..., None] + mm("...tc,...tv->...cv", k_n, u)
        return state, out

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u_free, q_in, p, k_out, end_decay))
    _, out = jax.lax.scan(across, jnp.zeros((rows, h, dk, dv), F32), xs)
    # (n, rows, h, chunk, dv) -> (rows, s, h, dv)
    return jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(rows, s, h, dv)


def _rows_per_pass(b: int, s: int, h: int, dk: int) -> int:
    """The most batch rows (a divisor of ``b``) whose float32 (rows, s, h, dk)
    array stays under ``_PASS_BYTES``."""
    fit = max(1, _PASS_BYTES // (s * h * dk * 4))
    return max(r for r in range(1, b + 1) if b % r == 0 and r <= fit)


def delta_rule_core(rows: int, tokens: int, heads: int, dk: int, dv: int, dtype, chunk: int = 64) -> dict:
    """Which core a call of :func:`chunk_gated_delta_rule` or
    :func:`normed_chunk_gated_delta_rule` takes, from what it can see, and the
    sizes of the call: ``core`` is ``"kernel"`` (the Pallas kernels: bfloat16
    operands, a TPU backend, dk = dv a multiple of 128, as ``Attention`` and
    ``LatentAttention`` choose their fused kernels) or ``"chunked"``;
    ``qk_norm`` and ``o_norm`` are where the mixer's l2 norm of q and k and its
    head RMS norm of o run, which follow the core: ``"kernel"`` (on the head's
    tile, inside ``kda_fwd`` / ``kda_bwd``) or ``"xla"``; ``kept_bytes`` is
    what a differentiated call of the mixer keeps from its forward to its
    backward beside its operands and o: on the kernel path each chunk's
    incoming state, what the forward solved of the chunk (T, A / beta, P, W,
    U) and each row's 1 / rms, which ``kda_bwd`` reads where it would make
    them again (``pallas_delta_rule.kept_for_backward``); 0 on the chunked
    path, which keeps nothing and runs its forward again. The mixer runs what
    this says and the step's trace-time record
    (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention  # the towers' one question about the backend

    kernel = (
        jnp.dtype(dtype) == jnp.bfloat16 and flash_attention.flash_attention_available()
        and dk == dv and dk % 128 == 0
    )
    norms = "kernel" if kernel else "xla"
    chunks = -(-tokens // chunk)
    kept = 0
    if kernel:
        from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import kept_for_backward

        kept = sum(math.prod(x.shape) * x.dtype.itemsize
                   for _, x in kept_for_backward(rows, chunks * chunk, heads, dk, dv, dtype, chunk, o_norm=True))
    return {"core": "kernel" if kernel else "chunked", "qk_norm": norms, "o_norm": norms,
            "rows": rows, "heads": heads, "chunks": chunks, "kept_bytes": kept}


def _power_of_two(chunk):
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, dtype=None):
    """o of the recurrence above, chunked. q, k: (b, s, h, dk); v: (b, s, h, dv);
    g: (b, s, h, dk) float32 log-decay <= 0; beta: (b, s, h). ``dtype`` is the
    operand type of the chunk's matrix products (default: v's); sums, decays,
    the triangular inverse and the carried state are float32. Returns (b, s, h,
    dv) in ``dtype``."""
    _power_of_two(chunk)
    dt = jnp.dtype(dtype or v.dtype)
    b, s, h, dk = q.shape
    pad = -s % chunk
    if pad:  # later tokens never reach earlier outputs: zeros at the end are inert
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    if delta_rule_core(b, s, h, dk, v.shape[-1], dt, chunk)["core"] == "kernel":
        from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import delta_rule_kernel

        wide = (x.reshape(b, s + pad, -1) for x in (q.astype(dt), k.astype(dt), v.astype(dt), g))  # free views
        out = kernels_per_shard(partial(delta_rule_kernel, chunk=chunk), h, *wide, beta)
        return out.reshape(b, s + pad, h, -1)[:, :s]
    core = jax.checkpoint(partial(_chunked, chunk=chunk, dt=dt))
    rows = _rows_per_pass(b, s + pad, h, dk)
    if rows == b:
        out = core(q, k, v, g, beta)
    else:
        passes = tuple(x.reshape(b // rows, rows, *x.shape[1:]) for x in (q, k, v, g, beta))
        out = jax.lax.map(lambda xs: core(*xs), passes).reshape(b, s + pad, h, -1)
    return out[:, :s].astype(dt)


def kernels_per_shard(kernel, heads: int, *wide):
    """``models/transformer.py _fused_attention_per_shard`` for operands with
    ``heads`` heads on the lanes ((b, s, h x d); beta (b, s, h)): under a
    ``jit`` over several chips a Mosaic kernel sits in a ``shard_map``, rows
    over ``dp`` and whole heads over ``tp`` where those axes exist and divide.
    ``kernel`` sees a shard: it reads the number of its heads off the widths."""
    from jax.sharding import PartitionSpec as P

    from distributed_sigmoid_loss_tpu.models.transformer import DP_AXIS, TP_AXIS

    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(mesh.axis_names) - frozenset(mesh.manual_axes)
    if not auto or mesh.size == 1:
        return kernel(*wide)
    b, h = wide[0].shape[0], heads

    def split(axis, n):
        return axis if axis in auto and n % mesh.shape[axis] == 0 else None

    spec = P(split(DP_AXIS, b), None, split(TP_AXIS, h))
    return jax.shard_map(
        kernel, in_specs=(spec,) * len(wide), out_specs=spec, axis_names=auto, check_vma=False
    )(*wide)


def normed_chunk_gated_delta_rule(q, k, v, g, beta, o_scale, *, o_eps: float, chunk: int = 64, dtype=None):
    """The mixer's call, the rule between its per-head norms: from the RAW q
    and k, q_h = l2norm(q_h) dk^-1/2 and k_h = l2norm(k_h), o of the recurrence,
    and of o the head RMS norm, o_h rsqrt(mean o_h^2 + ``o_eps``) ``o_scale``
    ((dv,)). Every operand has its heads on the lanes: q, k, g (float32
    log-decay <= 0): (b, s, h x dk); v: (b, s, h x dv); beta: (b, s, h).
    Returns (b, s, h x dv) float32. Where :func:`delta_rule_core` says
    ``"kernel"`` the norms run inside the kernels, on the head's tile (which
    stores what it normalised in ``dtype``, default v's), and no per-head (b, s,
    h, d) array exists around them; anywhere else :func:`l2norm` in XLA, then
    :func:`chunk_gated_delta_rule` on the per-head form, then the head norm of
    its float32 output."""
    _power_of_two(chunk)
    dt = jnp.dtype(dtype or v.dtype)
    b, s, h = beta.shape
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    if delta_rule_core(b, s, h, dk, dv, dt, chunk)["core"] == "kernel":
        from distributed_sigmoid_loss_tpu.ops.pallas_delta_rule import delta_rule_kernel

        operands = (q.astype(dt), k.astype(dt), v.astype(dt), g, beta)
        if s % chunk:  # a zero row stays zero under both norms: inert, as in chunk_gated_delta_rule
            operands = tuple(jnp.pad(x, ((0, 0), (0, -s % chunk), (0, 0))) for x in operands)
        kernel = partial(delta_rule_kernel, chunk=chunk, qk_norm=True, o_eps=o_eps)
        return kernels_per_shard(kernel, h, *operands)[:, :s].astype(F32) * jnp.tile(o_scale, h)
    q, k, v, g = (x.reshape(b, s, h, -1) for x in (q, k, v, g))
    q, k = (l2norm(q) * dk**-0.5).astype(dt), l2norm(k).astype(dt)
    o = chunk_gated_delta_rule(q, k, v, g, beta, chunk=chunk, dtype=dt).astype(F32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + o_eps) * o_scale
    return o.reshape(b, s, h * dv)
