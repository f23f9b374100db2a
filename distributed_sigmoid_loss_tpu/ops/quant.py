"""Dynamic int8 quantized matmul for inference — the v5e's second MXU gear.

TPU v5e executes int8×int8→int32 ``dot_general`` at 394 TOPS, exactly 2× the
bf16 peak (public spec sheet), and XLA lowers integer dots to the MXU
directly. For inference (eval/retrieval/zero-shot serving, ``train`` is NOT
the audience — see below) the towers can run their projection matmuls in int8
with dynamic symmetric quantization:

- **activations**: per-row abs-max over the contraction axis, computed on the
  fly (no calibration pass, no stored stats);
- **weights**: per-output-channel abs-max over the contraction axis.

Per-channel weight scales + per-row dynamic activation scales is the standard
PTQ recipe that keeps ViT/text-transformer quality (~1e-3 relative error per
matmul; the model-level contract is pinned in tests/test_quant.py).

The integration point is flax's ``nn.Dense(dot_general=...)`` injection —
the param tree is untouched, so ANY trained/imported checkpoint can be served
quantized by flipping ``quant="int8"`` on the tower config (utils/config.py).

Two gears, one recipe:

- ``int8_dot_general`` — inference. ``round`` has zero gradient almost
  everywhere, so a tower quantized with THIS dot trains to a standstill
  silently; the train-step guard rejects ``quant`` configs in trainable
  contexts.
- ``int8_dot_general_ste`` — training. The standard low-precision-training
  fix: a straight-through estimator (``jax.custom_vjp``) whose forward is
  bit-identical to ``int8_dot_general`` (the MXU's int8 gear) and whose
  backward is EXACTLY the unquantized ``lax.dot_general`` VJP on the saved
  full-precision operands — the gradient the bf16/f32 layer would have
  produced for the same cotangent. This is what breaks the bf16 roofline
  (the reason for an int8 training track): the v5e int8 MXU peak is 2x
  bf16, and the bf16 MFU=1.0 ceiling sits below the 1.5x-A100 target.
  ``int8_expert_matmul_ste`` is the MoE-expert analogue.

No reference analogue (the reference has no model/serving layer; SURVEY.md
§2 C8 documents docs-only coverage there) — this is TPU-first scope beyond it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "int8_dot_general",
    "int8_dot_general_ste",
    "int8_expert_matmul",
    "int8_expert_matmul_ste",
    "quantize_int8",
    "sign_sketch",
    "sign_sketch_scores",
]

# Symmetric int8: round-to-nearest into [-127, 127] (−128 unused, keeping the
# scale symmetric so dequant is one multiply).
_QMAX = 127.0
# Abs-max floor: an all-zero row/channel would otherwise divide by zero; any
# value below this quantizes to exact zeros with a harmless scale.
_EPS = 1e-12


def quantize_int8(x: jnp.ndarray, axis: int):
    """Symmetric int8 quantization of ``x`` along ``axis``.

    Returns ``(q, scale)`` with ``q`` int8, ``scale`` float32 keeping ``axis``
    as a size-1 dim, such that ``q * scale ≈ x``.
    """
    scale = jnp.maximum(
        jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True), _EPS
    ) / _QMAX
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -_QMAX, _QMAX).astype(
        jnp.int8
    )
    return q, scale


def int8_expert_matmul(x, w, out_dtype):
    """Batched-expert int8 matmul: ``(E, ..., K) @ (E, K, M) -> (E, ..., M)``.

    The MoE layer's expert MLP einsums (``encd,edh->ench`` / ``ench,ehd->encd``,
    models/moe.py expert_apply) in dynamic int8: per-row activation scales over
    K, per-(expert, out-channel) weight scales, int32 accumulation, expert as a
    dot_general batch dim. Zero rows (unused capacity slots) quantize to exact
    zeros. The one-hot dispatch/combine einsums stay in the model dtype — they
    are <20% of the layer's FLOPs and carry the routing weights whose
    precision sets drop behavior.
    """
    e = x.shape[0]
    xq, xs = quantize_int8(x, axis=-1)          # xs (E, ..., 1)
    wq, ws = quantize_int8(w, axis=1)           # ws (E, 1, M)
    acc = lax.dot_general(
        xq, wq,
        (((x.ndim - 1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )                                            # (E, ..., M)
    ws_b = ws.reshape((e,) + (1,) * (x.ndim - 2) + (w.shape[-1],))
    return (acc.astype(jnp.float32) * xs * ws_b).astype(out_dtype)


def int8_dot_general(lhs, rhs, dimension_numbers, precision=None,
                     preferred_element_type=None):
    """Drop-in ``lax.dot_general`` that runs the contraction in int8.

    Specialized to the single-contraction, no-batch-dims pattern every
    ``nn.Dense`` emits; anything else falls through to the real
    ``lax.dot_general`` unquantized (correct, just not accelerated).
    ``precision``/``preferred_element_type`` are accepted for signature
    compatibility; the int8 path fixes accumulation to int32 (the MXU's
    native accumulator — there is nothing to configure).
    """
    (lc, rc), (lb, rb) = dimension_numbers
    if lb or rb or len(lc) != 1 or len(rc) != 1:
        return lax.dot_general(
            lhs, rhs, dimension_numbers, precision=precision,
            preferred_element_type=preferred_element_type,
        )
    # Same output-dtype rule as lax.dot_general, so both branches of this
    # function (and a swap back to the real dot) are drop-in interchangeable.
    out_dtype = (
        preferred_element_type
        if preferred_element_type is not None
        else jnp.promote_types(lhs.dtype, rhs.dtype)
    )
    lq, ls = quantize_int8(lhs, lc[0])   # activations: per-row over K
    rq, rs = quantize_int8(rhs, rc[0])   # weights: per-out-channel over K
    acc = lax.dot_general(
        lq, rq, dimension_numbers, preferred_element_type=jnp.int32
    )
    # Result dims = lhs-free then rhs-free: lhs scales broadcast from the
    # left (padded with one 1 per rhs-free dim), rhs scales from the right.
    ls_free = jnp.squeeze(ls, axis=lc[0])
    rs_free = jnp.squeeze(rs, axis=rc[0])
    n_rhs_free = rhs.ndim - 1
    ls_b = ls_free.reshape(ls_free.shape + (1,) * n_rhs_free)
    return (acc.astype(jnp.float32) * ls_b * rs_free).astype(out_dtype)


# ---------------------------------------------------------------------------
# Binary sign sketches — the 1-bit coarse gear of the serving ANN tier.
#
# "Dissecting Embedding Bag Performance in DLRM Inference" (PAPERS.md): this
# workload is memory-bandwidth-bound, so the candidate-pruning scan's cost is
# the bytes it streams. int8 rows are 4x smaller than f32; sign bits are 32x.
# For L2-normalized embeddings, sign-agreement count (d - 2*hamming) is a
# monotone proxy for the dot product — good enough to PRUNE, never to RANK
# (serve/ann.py re-ranks the survivors exactly). Host-side numpy on purpose:
# the coarse scan runs where the index lives, outside any traced code.
# ---------------------------------------------------------------------------

_POPCOUNT = np.array([bin(v).count("1") for v in range(256)], dtype=np.uint8)


def sign_sketch(x) -> np.ndarray:
    """(n, d) float rows → (n, ceil(d/8)) packed sign bits (bit = row >= 0)."""
    x = np.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"sign_sketch expects (n, d) rows, got {x.shape}")
    return np.packbits(x >= 0.0, axis=1)


def sign_sketch_scores(qbits: np.ndarray, cbits: np.ndarray, dim: int) -> np.ndarray:
    """Coarse scores (q, n) between packed query/corpus sketches: the
    sign-agreement count ``d - 2*hamming`` (∝ the dot of the sign vectors).
    ``dim`` is the unpacked embedding dim (pad bits beyond it cancel out of
    the ORDERING per query row, so they are left in the count)."""
    # XOR per (query, corpus-row) byte panel, popcount via table lookup.
    xor = np.bitwise_xor(qbits[:, None, :], cbits[None, :, :])  # (q, n, B)
    hamming = _POPCOUNT[xor].sum(axis=-1, dtype=np.int32)
    return (dim - 2 * hamming).astype(np.float32)


# ---------------------------------------------------------------------------
# Straight-through estimators: int8 forward on the MXU, full-precision VJP.
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _int8_dot_general_ste(lhs, rhs, dimension_numbers, precision,
                          preferred_element_type):
    return int8_dot_general(
        lhs, rhs, dimension_numbers, precision=precision,
        preferred_element_type=preferred_element_type,
    )


def _ste_dot_fwd(lhs, rhs, dimension_numbers, precision,
                 preferred_element_type):
    out = int8_dot_general(
        lhs, rhs, dimension_numbers, precision=precision,
        preferred_element_type=preferred_element_type,
    )
    # Residuals are the ORIGINAL operands: the backward is the gradient the
    # unquantized layer would have produced, not round()'s zero-a.e. one.
    return out, (lhs, rhs)


def _ste_dot_bwd(dimension_numbers, precision, preferred_element_type, res, g):
    lhs, rhs = res
    _, vjp = jax.vjp(
        lambda l, r: lax.dot_general(
            l, r, dimension_numbers, precision=precision,
            preferred_element_type=preferred_element_type,
        ),
        lhs, rhs,
    )
    return vjp(g)


_int8_dot_general_ste.defvjp(_ste_dot_fwd, _ste_dot_bwd)


def int8_dot_general_ste(lhs, rhs, dimension_numbers, precision=None,
                         preferred_element_type=None):
    """Trainable ``lax.dot_general`` drop-in: int8 forward, unquantized VJP.

    Forward is bit-identical to :func:`int8_dot_general` (same fall-through
    for non-Dense patterns); backward is EXACTLY the ``lax.dot_general`` VJP
    on the saved full-precision operands (straight-through estimator) — the
    oracle ``tests/test_quant_train.py`` pins both sides to equality. The
    keyword wrapper exists because ``jax.custom_vjp`` takes only positional
    arguments, while flax's ``nn.Dense(dot_general=...)`` injection calls
    with ``precision=`` by keyword.
    """
    return _int8_dot_general_ste(
        lhs, rhs, dimension_numbers, precision, preferred_element_type
    )


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def int8_expert_matmul_ste(x, w, out_dtype):
    """STE twin of :func:`int8_expert_matmul` for trainable MoE experts:
    int8 batched-expert forward, backward = the unquantized einsum VJP."""
    return int8_expert_matmul(x, w, out_dtype)


def _expert_ref(x, w, out_dtype):
    # The unquantized op the STE backward differentiates — the same batched
    # dot_general int8_expert_matmul accelerates, in the model dtype.
    acc = lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return acc.astype(out_dtype)


def _ste_expert_fwd(x, w, out_dtype):
    return int8_expert_matmul(x, w, out_dtype), (x, w)


def _ste_expert_bwd(out_dtype, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda xx, ww: _expert_ref(xx, ww, out_dtype), x, w)
    return vjp(g)


int8_expert_matmul_ste.defvjp(_ste_expert_fwd, _ste_expert_bwd)
