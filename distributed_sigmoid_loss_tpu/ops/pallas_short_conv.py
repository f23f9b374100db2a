"""The mixers' short causal convolution with its bias and silu
(``ops/gated_delta_rule.py short_conv_silu`` has the definition) as two Pallas
TPU kernels: HBM sees x once in and y once out forward; x and dy in, dx out and
a few KB of sums backward. In XLA the same work is a padded copy and four shifted
reads forward and three passes backward, the taps' cotangent a reduction pass of
its own (PERF.md section 6, PR 49).

One program = one batch row, one block of ``tokens`` x ``channels``
(:func:`short_conv_plan`: up to ``_MAX_CHANNELS`` lanes and ``_BLOCK_BYTES`` of x:
2048 tokens of 512 channels, or the whole sequence where it is shorter; of the
shapes tried on the chip, 128 to 2048 tokens of 512 to 4096 lanes, the tall
narrow ones ran fastest and compile in a second). The grid is (rows, channel
blocks, token blocks). A program walks its block a 128-lane column at a time (a
static loop: a column is a copy of the code) and down a column in tiles of
``rows`` tokens: a tile is loaded once, widened to float32 and joined below the
eight rows before it, and the ``taps - 1`` shifted copies are sublane rotations of
that one array (``pltpu.roll``) cut at the tile's first row, so nothing is read
twice and no slice leaves the (8, 128) tiling. What lies before a block's first
row comes in as a block of its own, the sixteen rows (a bfloat16 tile) that end
there, zeros at the sequence's start; the forward hands a tile's last rows to the
next tile in registers.

- ``short_conv_fwd``: pre = bias + sum_j taps[j] x[t - (taps - 1) + j] in float32
  (the taps rounded to x's dtype, as the XLA form rounds them; the bias as it is),
  y = pre sigmoid(pre), rounded once.
- ``short_conv_bwd``: pre again from x (nothing is kept but the operands), d_pre =
  dy silu'(pre) in float32, dx[t] = sum_m taps[taps - 1 - m] d_pre[t + m] (the
  shifts the other way, zeros past the sequence's end), the taps' cotangent
  sum_t d_pre[t + m] x[t] and the bias's sum_t d_pre[t]. A column is walked last
  tile to first, each tile handing its first rows of d_pre up to the one before;
  what the block after this one hands back, d_pre of its first rows, is made from
  the sixteen rows of x and dy that start there (two more small blocks). The sums
  are kept eight sublanes apart in registers down a column, folded once a column
  and added to one (8, channels) float32 block a batch row that stays in VMEM
  along the token axis (rows 0 .. taps - 1 the taps', row ``taps`` the bias's);
  the batch rows are summed outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["short_conv_kernel", "short_conv_plan"]

F32 = jnp.float32
_LANES = 128
_SUBLANES = 8  # rows of a float32 register: what a tile keeps of its neighbour
_EDGE = 16  # rows of a bfloat16 tile: the least a block of x holds
_ROWS = 128  # tokens a tile of a column, at most
_MAX_CHANNELS = 512  # lanes a block, at most
_BLOCK_BYTES = 2 * 2**20  # of x a block, at most
_VMEM_LIMIT = 32 * 2**20  # the backward holds six blocks (x, dy, dx, each twice); Mosaic's default limit is 16 MiB


def _largest(n: int, unit: int, most: int) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most ``most`` (0: none)."""
    return max((d for d in range(unit, min(n, most) + 1, unit) if n % d == 0), default=0)


def short_conv_plan(tokens: int, channels: int, taps: int, itemsize: int = 2) -> dict | None:
    """The block a program of the pair holds, {"tokens", "channels", "rows"}
    (``rows``: tokens a tile of its walk), or None where the kernels do not
    apply: the channels in whole 128-lane registers, the sequence in whole
    16-row tiles of a 2-byte type, and taps that reach no further back than a
    register's eight rows and fit, with the bias, the eight rows of the sums'
    block."""
    if itemsize != 2 or channels % _LANES or tokens % _EDGE or not 2 <= taps < _SUBLANES:
        return None
    wide = _largest(channels, _LANES, _MAX_CHANNELS)
    block = _largest(tokens, _EDGE, max(_EDGE, _BLOCK_BYTES // (wide * itemsize)))
    return {"tokens": block, "channels": wide, "rows": _largest(block, _EDGE, _ROWS)}


def _sigmoid(x, estimate: bool):
    """1 / (1 + exp(-x)), on the chip (``estimate``) by its reciprocal estimate: about 2^-12 of the value, an eighth
    of a bfloat16 step, and relative, so the small values of the negative tail keep their digits. The exact division's
    refinement steps were a quarter of the backward's arithmetic, which is what bounds it (PERF.md section 6, PR 49).
    The interpreter stands in for the estimate with a bfloat16 division, so interpreted kernels divide exactly."""
    return pl.reciprocal(1.0 + jnp.exp(-x), approx=estimate)


def _shifted(below, by: int):
    """Of ``below`` (8 + n, lanes), n rows under the eight before them: row t
    of the n is row t - ``by`` (``by`` < 0: row t + |by| of n rows ABOVE eight)."""
    n = below.shape[0] - _SUBLANES
    if by >= 0:
        return pltpu.roll(below, by, 0)[_SUBLANES:]
    return pltpu.roll(below, below.shape[0] + by, 0)[:n]


def _pre(below, w, bias):
    """The convolution of the rows under ``below``'s first eight, float32."""
    taps = len(w)
    pre = w[taps - 1] * below[_SUBLANES:]
    for j in range(1, taps):
        pre = pre + w[taps - 1 - j] * _shifted(below, j)
    return pre if bias is None else pre + bias


def _in_eights(a):
    """(n, lanes) -> (8, lanes): the rows' sum, eight sublanes apart."""
    return a.reshape(-1, _SUBLANES, a.shape[1]).sum(0)  # whole registers added: no sublane moves


def _columns(w_ref, bias_ref):
    """Per 128-lane column of a block: its lanes, its taps as rows (1, 128) and its bias (None without one)."""
    taps, channels = w_ref.shape
    for c in range(0, channels, _LANES):
        lanes = slice(c, c + _LANES)
        yield lanes, [w_ref[j:j + 1, lanes] for j in range(taps)], bias_ref[0][:, lanes] if bias_ref else None


def _fwd_kernel(x_ref, before_ref, w_ref, *rest, rows, estimate):
    *bias_ref, y_ref = rest
    block = x_ref.shape[1]
    first = pl.program_id(2) == 0
    for lanes, w, bias in _columns(w_ref, bias_ref):  # the loops below are traced here, a column at a time
        before = jnp.where(first, 0.0, before_ref[0, :, lanes].astype(F32)[_SUBLANES:])

        def tile(i, before):
            at = pl.ds(pl.multiple_of(i * rows, rows), rows)
            x = x_ref[0, at, lanes].astype(F32)
            pre = _pre(jnp.concatenate([before, x], 0), w, bias)
            y_ref[0, at, lanes] = (pre * _sigmoid(pre, estimate)).astype(y_ref.dtype)
            return x[rows - _SUBLANES:]

        lax.fori_loop(0, block // rows, tile, before)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref, *rest, rows, estimate):
    *bias_ref, dx_ref, sums_ref = rest
    block, taps = x_ref.shape[1], w_ref.shape[0]
    tiles = block // rows
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    for lanes, w, bias in _columns(w_ref, bias_ref):  # the loops below are traced here, a column at a time
        def d_pre_of(below, dy):
            pre = _pre(below, w, bias)
            sig = _sigmoid(pre, estimate)
            return dy * (sig * (1.0 + pre * (1.0 - sig)))

        # d_pre of the eight rows after the block: from this block's last rows of x and the next one's first of x and dy
        edge = jnp.concatenate([x_ref[0, block - _EDGE:, lanes].astype(F32)[_SUBLANES:],
                                after_ref[0, :, lanes].astype(F32)[:_SUBLANES]], 0)
        handed = jnp.where(last, 0.0, d_pre_of(edge, dy_after_ref[0, :, lanes].astype(F32)[:_SUBLANES]))
        before = jnp.where(first, 0.0, before_ref[0, :, lanes].astype(F32))

        def tile(n, carried):
            handed, *sums = carried
            i = tiles - 1 - n
            at = pl.ds(pl.multiple_of(i * rows, rows), rows)
            above = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(i * rows - _EDGE, 0), _EDGE), _EDGE), lanes].astype(F32)
            x = x_ref[0, at, lanes].astype(F32)
            below = jnp.concatenate([jnp.where(i == 0, before, above)[_SUBLANES:], x], 0)
            d_pre = d_pre_of(below, dy_ref[0, at, lanes].astype(F32))
            over = jnp.concatenate([d_pre, handed], 0)  # the eight rows after the tile under it
            dx = w[taps - 1] * d_pre
            sums[taps - 1] = sums[taps - 1] + _in_eights(d_pre * x)
            for m in range(1, taps):
                later = _shifted(over, -m)  # d_pre[t + m]
                dx = dx + w[taps - 1 - m] * later
                sums[taps - 1 - m] = sums[taps - 1 - m] + _in_eights(later * x)
            if bias is not None:
                sums[taps] = sums[taps] + _in_eights(d_pre)
            dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)
            return (d_pre[:_SUBLANES], *sums)

        zero = jnp.zeros((_SUBLANES, _LANES), F32)
        _, *sums = lax.fori_loop(0, tiles, tile, (handed, *[zero] * (taps + len(bias_ref))))
        for j, total in enumerate(sums):
            sums_ref[0, j:j + 1, lanes] = sums_ref[0, j:j + 1, lanes] + jnp.sum(total, axis=0, keepdims=True)


def _call(kernel, name, operands, outs, *, plan, b, s, c, taps, backward, interpret):
    """One of the two kernels over the grid (rows, channel blocks, token
    blocks). ``operands`` are (kind, array) and ``outs`` (kind, shape): "block"
    (b, s, c); "before" / "after", the same array's sixteen rows that end where
    the block starts / start where it ends (the sequence's first / last sixteen
    where there are none: the kernels put zeros there); "taps" (taps, c);
    "bias" (1, c); "sums" (b, 8, c), resident along the token axis."""
    block, wide = plan["tokens"], plan["channels"]
    edges = block // _EDGE

    def spec(kind):
        shape, index = {
            "block": ((1, block, wide), lambda r, j, i: (r, i, j)),
            "before": ((1, _EDGE, wide), lambda r, j, i: (r, jnp.maximum(i * edges - 1, 0), j)),
            "after": ((1, _EDGE, wide), lambda r, j, i: (r, jnp.minimum((i + 1) * edges, s // _EDGE - 1), j)),
            "taps": ((taps, wide), lambda r, j, i: (0, j)),
            "bias": ((1, wide), lambda r, j, i: (0, j)),
            "sums": ((1, _SUBLANES, wide), lambda r, j, i: (r, 0, j)),
        }[kind]
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    moved = sum(x.size * x.dtype.itemsize for kind, x in [*operands, *outs] if kind not in ("before", "after"))
    return pl.pallas_call(
        functools.partial(kernel, rows=plan["rows"], estimate=not interpret),
        out_shape=[o for _, o in outs],
        grid=(b, c // wide, s // block),
        in_specs=[spec(kind) for kind, _ in operands],
        out_specs=[spec(kind) for kind, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary" if backward else "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=(8 if backward else 3) * taps * b * s * c, bytes_accessed=moved,
            transcendentals=b * s * c),
        interpret=interpret,
        name=name,  # what a profile calls this kernel
    )(*(x for _, x in operands))


def _sizes(x, w, plan):
    b, s, c = x.shape
    return dict(plan=dict(plan), b=b, s=s, c=c, taps=w.shape[0])


def _narrow(x, w, bias):
    """The taps as the products take them, rounded to x's dtype, and the bias as a row."""
    return [("taps", w.astype(x.dtype).astype(F32))] + ([] if bias is None else [("bias", bias.astype(F32)[None, :])])


# Both kernels behind a ``jit`` of their own: a layer calls the pair three times (q, k, v; x', B, C) and a step has 36 /
# 27 call sites at the two cells, each of which would trace and lower a kernel's body again (no cache holds a
# ``pallas_call``'s trace): 41 s of set-up a run on the chip's host (PERF.md section 6, PR 49). Behind the ``jit`` a
# program traces and lowers each kernel once a shape and plan (a static argument: what a trace is cached under) and calls it.
@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _forward(x, w, bias, plan, interpret):
    (y,) = _call(
        _fwd_kernel, "short_conv_fwd", [("block", x), ("before", x), *_narrow(x, w, bias)],
        [("block", jax.ShapeDtypeStruct(x.shape, x.dtype))], **_sizes(x, w, plan), backward=False, interpret=interpret)
    return y


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _backward(x, w, bias, d_y, plan, interpret):
    taps = w.shape[0]
    d_x, sums = _call(
        _bwd_kernel, "short_conv_bwd",
        [("block", x), ("before", x), ("after", x), ("block", d_y), ("after", d_y), *_narrow(x, w, bias)],
        [("block", jax.ShapeDtypeStruct(x.shape, x.dtype)),
         ("sums", jax.ShapeDtypeStruct((x.shape[0], _SUBLANES, x.shape[2]), F32))],
        **_sizes(x, w, plan), backward=True, interpret=interpret)
    sums = sums.sum(0)
    return d_x, sums[:taps].astype(w.dtype), None if bias is None else sums[taps].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _short_conv(x, w, bias, plan, interpret):
    return _forward(x, w, bias, plan, interpret)


def _vjp_fwd(x, w, bias, plan, interpret):
    return _forward(x, w, bias, plan, interpret), (x, w, bias)


def _vjp_bwd(plan, interpret, residuals, d_y):
    x, w, bias = residuals
    return _backward(x, w, bias, d_y.astype(x.dtype), plan, interpret)


_short_conv.defvjp(_vjp_fwd, _vjp_bwd)


def short_conv_kernel(x, taps, bias=None, *, interpret: bool = False):
    """silu(the causal depthwise convolution of x + bias) through the kernels.
    x: (b, s, c) in a 2-byte type; ``taps``: (k, c) float32, out[t] = sum_j
    taps[j] x[t - (k - 1) + j]; ``bias``: (c,) float32 or None. Shapes as
    :func:`short_conv_plan` admits them (``ops/gated_delta_rule.py
    short_conv_core`` is where the mixers ask). Returns x's shape and dtype.
    Differentiated, it keeps its operands and nothing else; the cotangents come
    back in the operands' shapes and dtypes, the taps' and the bias's summed in
    float32. ``interpret=True`` runs the Pallas interpreter (CPU testing)."""
    plan = short_conv_plan(x.shape[1], x.shape[2], taps.shape[0], x.dtype.itemsize)
    if plan is None:
        raise ValueError(f"short_conv_kernel: no plan for x {x.shape} {x.dtype} under {taps.shape[0]} taps")
    return _short_conv(x, taps, bias, tuple(plan.items()), interpret)
