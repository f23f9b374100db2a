"""The scalar-decay state-space recurrence of a Mamba-2 layer (the "ssm" mixer's
core), token by token and in the chunked form the towers run.

Per head h of P channels, in group g = h // (heads / groups) of the ``groups``
that share B and C, with a state S in R^(P x N), S_{-1} = 0, a step dt_t > 0
and a rate A_h < 0 (so a_t = exp(dt_t A_h) in (0, 1]):

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t[g]
    y_t = S_t C_t[g] + D_h x_t

:func:`ssm_recurrent` is that, one token at a time (``lax.scan`` over the
sequence, float32 at full matmul precision): the definition, for tests.
:func:`ssm_scan` computes the same y in chunks of L tokens (Dao and Gu's SSD,
arXiv:2405.21060). With G_t the running sum of dt A from the chunk's first
token (inclusive) and S the state the chunk starts from:

    y_t = sum_{i <= t} exp(G_t - G_i) dt_i (C_t . B_i) x_i  +  exp(G_t) S C_t  +  D x_t
    S'  = exp(G_L) S + sum_i exp(G_L - G_i) dt_i x_i (x) B_i

so the chunk's own work is matrix products: the scores C B^T once a GROUP (L x L,
shared by the group's heads), each head's decayed, masked copy of them times its
x, the chunk's state from x and B, the incoming state's outputs from C; the
state crosses chunks in a scan of s / L steps. A decay is a scalar a head, so
exp(G_t - G_i) for i <= t is one exponent <= 0: nothing overflows and nothing is
factored. Any chunking gives the same y: L is how it is computed, not what.

**The form that runs** is one of two, by :func:`ssm_core`, a rule from what the
call can see, as ``delta_rule_core`` is. ``"kernel"``: the Pallas pair
``ssd_fwd`` / ``ssd_bwd`` (``ops/pallas_ssm.py``) for bfloat16 operands on a TPU
at whole-register shapes: a chunk of one group lives in VMEM from x, B, C, dt to
y, a head's (L, L) decays and mixed scores never reach HBM, the state crosses
chunks in VMEM scratch, all rows of the batch in one call; a differentiated call
keeps each chunk's incoming state for its backward. ``"chunked"``, everywhere
else (float32, the CPU, odd shapes): XLA operations on operands that stay (b, s,
h x P) / (b, s, g x N) up to the core, products in ``dtype`` with float32
accumulation, decays, sums and the carried state in float32, no (s x s) array
(the largest intermediate is a chunk's L x L scores a head), its backward
``jax.grad``'s, recomputed (``jax.checkpoint``: a differentiated call keeps its
operands and nothing else) and run a few batch rows at a time (``lax.map``) so
that a pass's float32 scores stay under ``_PASS_BYTES``. Both round where the
other does: products on operands of ``dtype``, everything else float32.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["ssm_recurrent", "ssm_scan", "ssm_core"]

F32 = jnp.float32
# Float32 (rows, s / L, h, L, L) scores of one pass of the chunked form, in bytes:
# the batch rows of a pass are chosen to stay under it (one row of 4096 tokens,
# 64 heads and chunks of 128 is 134 MB).
_PASS_BYTES = 192 * 2**20


def ssm_recurrent(x, B, C, dt, A, D, *, heads: int, groups: int):
    """The recurrence, one token at a time, in float32 at full matmul precision.
    x: (b, s, heads x P); B, C: (b, s, groups x N); dt: (b, s, heads) > 0; A, D:
    (heads,), A < 0. Returns (b, s, heads x P) float32."""
    x, B, C, dt, A, D = (t.astype(F32) for t in (x, B, C, dt, A, D))
    b, s, _ = x.shape
    per_group = heads // groups
    x = x.reshape(b, s, heads, -1)
    B, C = (jnp.repeat(t.reshape(b, s, groups, -1), per_group, axis=2) for t in (B, C))  # (b, s, heads, N)
    hi = jax.lax.Precision.HIGHEST

    def step(state, token):
        x_t, b_t, c_t, dt_t = token
        state = state * jnp.exp(dt_t * A)[..., None, None] + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=hi)

    tokens = tuple(jnp.moveaxis(t, 1, 0) for t in (x, B, C, dt))
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, x.shape[-1], B.shape[-1]), F32), tokens)
    return (jnp.moveaxis(y, 0, 1) + D[:, None] * x).reshape(b, s, -1)


def _chunked(x, B, C, dt, A, D, *, heads: int, groups: int, chunk: int, dt_):
    """The chunked form on (rows, s, ...) operands, s a multiple of ``chunk``."""
    rows, s, inner = x.shape
    h, g, n, L = heads, groups, s // chunk, chunk
    e, P, N = h // g, inner // h, B.shape[-1] // g
    precision = jax.lax.Precision.HIGHEST if dt_ == F32 else None

    def mm(spec, a, b_):
        return jnp.einsum(spec, a.astype(dt_), b_.astype(dt_), preferred_element_type=F32, precision=precision)

    def on_lanes(per_head):  # (..., h) -> (..., h x P): a head's value on each of its lanes
        return jnp.repeat(per_head, P, axis=-1)

    dt = dt.astype(F32).reshape(rows, n, L, h)
    big_g = jnp.cumsum(dt * A.astype(F32), axis=2)  # G, running from the chunk's start, <= 0
    x32 = x.astype(F32).reshape(rows, n, L, inner)
    xc, bc, cc = x.reshape(rows, n, L, g, e * P), B.reshape(rows, n, L, g, N), C.reshape(rows, n, L, g, N)

    # Inside a chunk: a group's scores once, each head's decayed and masked copy times its x.
    scores = mm("rntgk,rnigk->rngti", cc, bc)  # (rows, n, g, L, L)
    g_h = jnp.moveaxis(big_g, 3, 2)  # (rows, n, h, L)
    seen = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(seen, g_h[..., :, None] - g_h[..., None, :], -jnp.inf))  # [t, i]: exp(G_t - G_i), i <= t
    weight = decay * jnp.moveaxis(dt, 3, 2)[..., None, :]  # ... dt_i
    mixed = scores[:, :, :, None] * weight.reshape(rows, n, g, e, L, L)
    y = mm("rngeti,rnigep->rntgep", mixed, xc.reshape(rows, n, L, g, e, P)).reshape(rows, n, L, inner)

    # Each chunk's own state, from its tokens decayed to the chunk's end.
    to_end = jnp.exp(big_g[:, :, -1:, :] - big_g) * dt  # (rows, n, L, h)
    own = mm("rnigq,rnigk->rngqk", (x32 * on_lanes(to_end)).reshape(rows, n, L, g, e * P), bc)  # (rows, n, g, e x P, N)
    end_decay = on_lanes(jnp.exp(big_g[:, :, -1, :])).reshape(rows, n, g, e * P, 1)

    def across(state, chunk_of):  # state (rows, g, e x P, N): what the chunk starts from
        own_n, decay_n = chunk_of
        return state * decay_n + own_n, state

    _, incoming = jax.lax.scan(
        across, jnp.zeros((rows, g, e * P, N), F32), (jnp.moveaxis(own, 1, 0), jnp.moveaxis(end_decay, 1, 0))
    )
    from_state = mm("rntgk,nrgqk->rntgq", cc, incoming).reshape(rows, n, L, inner)
    y = y + from_state * on_lanes(jnp.exp(big_g)) + x32 * on_lanes(D.astype(F32))
    return y.reshape(rows, s, inner)


def _rows_per_pass(b: int, s: int, h: int, chunk: int) -> int:
    """The most batch rows (a divisor of ``b``) whose float32 (rows, s / L, h, L,
    L) scores stay under ``_PASS_BYTES``."""
    fit = max(1, _PASS_BYTES // (s * h * chunk * 4))
    return max(r for r in range(1, b + 1) if b % r == 0 and r <= fit)


def ssm_core(rows: int, tokens: int, heads: int, head_dim: int, groups: int, state: int, dtype, chunk: int = 128) -> dict:
    """Which form a call of :func:`ssm_scan` takes, from what it can see, and the
    sizes it runs at: ``core`` is ``"kernel"`` (the Pallas pair of
    ``ops/pallas_ssm.py``: bfloat16 operands, a TPU backend, and whole
    registers: the chunk and the state multiples of 128, a group's heads x
    head_dim a multiple of 128 and head_dim a divisor or a multiple of 128, so
    that every head lies in whole 128-lane columns or shares one evenly) or
    ``"chunked"`` (the XLA form above); ``chunk`` (tokens a chunk: the
    configuration's, or the whole sequence where that is shorter), ``chunks`` a
    sequence (its last one zero-padded), ``rows_per_pass`` (batch rows one pass
    holds: all of them on the kernel path, which has no (rows, chunks, heads, L,
    L) array; what fits ``_PASS_BYTES`` on the chunked one), ``heads``,
    ``groups``, ``head_dim``, ``state`` and ``kept_bytes``, what a
    differentiated call keeps from its forward to its backward beside its
    operands and y: each chunk's incoming state on the kernel path
    (``pallas_ssm.kept_for_backward``: float32, rows x chunks x state x heads x
    head_dim), 0 on the chunked one, which runs its forward again. The mixer runs
    what this says and the step's trace-time record
    (``train_step.stack_record_of``) reports it."""
    from distributed_sigmoid_loss_tpu.ops import flash_attention  # the towers' one question about the backend

    chunk = min(chunk, tokens)
    chunks = -(-tokens // chunk)
    per_group = heads // groups * head_dim
    kernel = (
        jnp.dtype(dtype) == jnp.bfloat16 and flash_attention.flash_attention_available()
        and chunk % 128 == 0 and state % 128 == 0 and per_group % 128 == 0
        and (head_dim % 128 == 0 or 128 % head_dim == 0)
    )
    kept = 0
    if kernel:
        from distributed_sigmoid_loss_tpu.ops.pallas_ssm import kept_for_backward

        kept = sum(math.prod(x.shape) * x.dtype.itemsize
                   for _, x in kept_for_backward(rows, chunks * chunk, heads, head_dim, state, chunk))
    return {"core": "kernel" if kernel else "chunked", "chunk": chunk, "chunks": chunks,
            "rows_per_pass": rows if kernel else _rows_per_pass(rows, chunks * chunk, heads, chunk),
            "heads": heads, "groups": groups, "head_dim": head_dim, "state": state, "kept_bytes": kept}


def ssm_scan(x, B, C, dt, A, D, *, heads: int, groups: int, chunk: int = 128, dtype=None):
    """y of the recurrence above, chunked, in the form :func:`ssm_core` names:
    the mixer's call. x: (b, s, heads x P); B, C: (b, s, groups x N); dt: (b, s,
    heads) float32 > 0; A, D: (heads,) float32, A < 0. ``dtype`` is the operand
    type of the chunk's matrix products (default: x's); decays, sums and the
    carried state are float32. Returns (b, s, heads x P) float32."""
    dt_ = jnp.dtype(dtype or x.dtype)
    b, s, inner = x.shape
    if heads % groups or inner % heads or B.shape[-1] % groups or B.shape != C.shape:
        raise ValueError(f"ssm_scan: x {x.shape}, B {B.shape}, C {C.shape} do not cut into {heads} heads in {groups} groups")
    plan = ssm_core(b, s, heads, inner // heads, groups, B.shape[-1] // groups, dt_, chunk)
    pad = plan["chunks"] * plan["chunk"] - s
    if pad:  # dt = 0 and x = 0: a padded token neither decays nor writes, and none precedes a real one
        x, B, C, dt = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, B, C, dt))
    if plan["core"] == "kernel":
        from distributed_sigmoid_loss_tpu.ops.gated_delta_rule import kernels_per_shard
        from distributed_sigmoid_loss_tpu.ops.pallas_ssm import ssd_kernel

        # rows over dp under a jit over several chips; every shard runs all heads (A and D are whole)
        kernel = lambda *wide: ssd_kernel(*wide, A, D, heads=heads, groups=groups, chunk=plan["chunk"])  # noqa: E731
        return kernels_per_shard(kernel, 1, x.astype(dt_), B.astype(dt_), C.astype(dt_), dt)[:, :s]
    core = jax.checkpoint(partial(_chunked, heads=heads, groups=groups, chunk=plan["chunk"], dt_=dt_))
    rows = plan["rows_per_pass"]
    if rows == b:
        y = core(x, B, C, dt, A, D)
    else:
        passes = tuple(t.reshape(b // rows, rows, *t.shape[1:]) for t in (x, B, C, dt))
        y = jax.lax.map(lambda ts: core(*ts, A, D), passes).reshape(b, s + pad, inner)
    return y[:, :s]
