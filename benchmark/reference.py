"""The plain reference: both SigLIP towers, pooling and the sigmoid loss over all
pairs, in straight ``jax.numpy`` float32 at ``highest`` matmul precision. No
kernels, no sharding, no remat, no flax: it reads the program's parameter tree
(either layer layout) and nothing else of the program. The blocks of a tower run
as one ``lax.scan`` over their stacked parameters, so that set-up traces and
compiles one block and not twenty-seven. ``correct`` compares the
system with it, on the cell's seeded weights and batch.

Departures from the published model: none in the mathematics. Pre-LN blocks,
LayerNorm eps 1e-6, tanh gelu, attention scaled by 1/sqrt(dh), a learned position
embedding, MAP pooling for the image (a probe attends over the tokens, then an
MLP residual), last-token pooling and a linear head for the text, L2-normalised
embeddings, loss = sum over all pairs of -log sigmoid(label * (t z_i.z_j + b))
divided by the number of images.

The bounds are at the bottom, each with the measurement it was set from.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6
F32 = jnp.float32


def _highest(fn):
    """Trace ``fn`` with every matmul at full float32 precision: on a TPU a
    float32 matmul otherwise runs as bf16 passes."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


# -- the parameter tree -------------------------------------------------------


def stacked_blocks(encoder: dict, depth: int) -> dict:
    """The blocks of an encoder stacked on a leading depth axis: as they lie in
    the scanned layout (``blocks/block``), or stacked here from the unrolled one
    (``block{i}``)."""
    if "blocks" in encoder:
        return encoder["blocks"]["block"]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *(encoder[f"block{i}"] for i in range(depth)))


def first_block(encoder: dict) -> dict:
    if "blocks" in encoder:
        return jax.tree.map(lambda x: x[0], encoder["blocks"]["block"])
    return encoder["block0"]


def first_blocks(params: dict) -> dict:
    """The leaves the gradient check compares: the loss's two scalars and the
    first block of each tower (the one whose gradient has crossed every layer).
    ``t_prime`` and ``bias`` are one leaf, so their gradients share a scale: that
    of ``t_prime`` is t times a sum of cosines that cancel at initialisation (the
    mean positive cosine is +-0.01 on random weights), so relative to itself it
    is ill-conditioned, while that of ``bias`` is close to -1."""
    return {
        "loss_scalars": jnp.stack([params["t_prime"], params["bias"]]),
        "visual": first_block(params["visual"]["encoder"]),
        "textual": first_block(params["textual"]["encoder"]),
    }


# -- the layers ---------------------------------------------------------------


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def attention(x_q, x_kv, p, heads: int):
    b, sq, w = x_q.shape
    dh = w // heads
    q = dense(x_q, p["q"]).reshape(b, sq, heads, dh)
    k = dense(x_kv, p["k"]).reshape(b, -1, heads, dh)
    v = dense(x_kv, p["v"]).reshape(b, -1, heads, dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh**-0.5
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, sq, w)
    return dense(out, p["out"])


def mlp(x, p):
    return dense(jax.nn.gelu(dense(x, p["wi"]), approximate=True), p["wo"])


def block(x, p, heads: int):
    y = layer_norm(x, p["ln1"])
    x = x + attention(y, y, p["attn"], heads)
    return x + mlp(layer_norm(x, p["ln2"]), p["mlp"])


def encoder(x, p, depth: int, heads: int, first=None):
    """The blocks in order, as one scan over the stacked blocks so that one block
    is traced and compiled whatever the depth; nothing is rematerialised. The
    stack is the scan's own input, which lets the backward pass read it where it
    lies instead of saving a copy. ``first`` stands in for block 0 (the gradient
    check differentiates it): it then runs ahead of the scan, whose step 0 is
    computed and dropped."""
    if first is not None:
        x = block(x, first, heads)

    def body(x, scanned):
        i, layer = scanned
        y = block(x, layer, heads)
        return (y if first is None else jnp.where(i == 0, x, y)), None

    x, _ = jax.lax.scan(body, x, (jnp.arange(depth), stacked_blocks(p, depth)))
    return layer_norm(x, p["ln_final"])


def map_head(x, p, heads: int):
    probe = jnp.broadcast_to(p["probe"], (x.shape[0], 1, x.shape[-1]))
    y = attention(probe, x, p["attn"], heads)
    y = y + mlp(layer_norm(y, p["ln"]), p["mlp"])
    return y[:, 0]


def _pool(x, p, tower):
    if tower.pool == "map":
        return map_head(x, p["map_head"], tower.num_heads)
    if tower.pool == "last":
        return x[:, -1]
    if tower.pool == "gap":
        return x.mean(axis=1)
    raise ValueError(f"the reference has no pooling {tower.pool!r}")


def vision_tower(v, p, images, first=None):
    b, hh, ww, c = images.shape
    ps = v.patch_size
    x = images.astype(F32)[:, : hh // ps * ps, : ww // ps * ps]
    x = x.reshape(b, hh // ps, ps, ww // ps, ps, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, (hh // ps) * (ww // ps), ps * ps * c)
    pe = p["patch_embed"]
    x = x @ pe["kernel"].reshape(ps * ps * c, v.width) + pe["bias"] + p["pos_embed"]
    x = encoder(x, p["encoder"], v.depth, v.num_heads, first)
    x = _pool(x, p, v)
    return dense(x, p["proj"]) if v.use_proj else x


def text_tower(t, p, tokens, first=None):
    x = p["token_embed"]["embedding"][tokens] + p["pos_embed"]
    x = encoder(x, p["encoder"], t.depth, t.num_heads, first)
    return dense(_pool(x, p, t), p["proj"])


def l2_normalize(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)


def _check_supported(cfg):
    for name, tower in (("vision", cfg.vision), ("text", cfg.text)):
        if getattr(tower, "moe_experts", 0) or getattr(tower, "causal", False):
            raise ValueError(
                f"the plain reference has no equations for {name} tower "
                "features moe_experts/causal yet: add them to reference.py "
                "before adding such a configuration"
            )
    if cfg.loss.family != "sigmoid":
        raise ValueError("the plain reference has the sigmoid loss only")


def embeddings(cfg, params, images, tokens, first=None):
    """L2-normalised (z_img, z_txt) for a few rows. ``first`` replaces the first
    block of each tower (the gradient check differentiates with respect to it)."""
    fv = first["visual"] if first else None
    ft = first["textual"] if first else None
    zimg = l2_normalize(vision_tower(cfg.vision, params["visual"], images, fv))
    ztxt = l2_normalize(text_tower(cfg.text, params["textual"], tokens, ft))
    return zimg, ztxt


def sigmoid_loss(zimg, ztxt, t_prime, bias):
    """SigLIP Algorithm 1 over one group of n pairs: every image against every
    text, +1 on the diagonal, summed and divided by n."""
    logits = zimg @ ztxt.T * jnp.exp(t_prime) + bias
    labels = 2.0 * jnp.eye(zimg.shape[0], dtype=F32) - 1.0
    return -jax.nn.log_sigmoid(labels * logits).sum() / zimg.shape[0]


# -- what `correct` calls ------------------------------------------------------


def microbatch_rows(n: int, dp: int, accum_steps: int) -> np.ndarray:
    """Which rows of the global batch of ``n`` form each accumulated microbatch:
    microbatch i is chunk i of every data-parallel rank's rows. One group of all
    rows when nothing is accumulated. The loss the mix defines is the mean over
    these groups of :func:`sigmoid_loss` on the group."""
    c = n // (dp * accum_steps)
    rows = np.arange(n).reshape(dp, accum_steps, c)
    return rows.transpose(1, 0, 2).reshape(accum_steps, dp * c)


def make_batch_loss(cfg, slice_rows: int, groups: np.ndarray):
    """``f(params, images, tokens) -> (loss, zimg, ztxt)`` over a whole batch,
    the towers run ``slice_rows`` rows at a time so the float32 activations fit
    beside a training state."""
    _check_supported(cfg)
    groups = jnp.asarray(groups)

    @jax.jit
    @_highest
    def batch_loss(params, images, tokens):
        n = images.shape[0]
        step = min(slice_rows, n)
        if n % step:
            raise ValueError(f"reference_slice_rows={step} must divide the batch {n}")
        sliced = (
            images.reshape(n // step, step, *images.shape[1:]),
            tokens.reshape(n // step, step, *tokens.shape[1:]),
        )
        zimg, ztxt = jax.lax.map(lambda s: embeddings(cfg, params, *s), sliced)
        zimg, ztxt = zimg.reshape(n, -1), ztxt.reshape(n, -1)
        losses = jax.vmap(
            lambda rows: sigmoid_loss(
                zimg[rows], ztxt[rows], params["t_prime"], params["bias"]
            )
        )(groups)
        return losses.mean(), zimg, ztxt

    return batch_loss


def make_sample_grads(cfg):
    """``f(params, images, tokens) -> (loss, grads)`` on a sample small enough
    to differentiate without remat; ``grads`` has the tree of
    :func:`first_blocks`."""
    _check_supported(cfg)

    def loss_of(sel, params, images, tokens):
        zimg, ztxt = embeddings(cfg, params, images, tokens, first=sel)
        return sigmoid_loss(zimg, ztxt, sel["loss_scalars"][0], sel["loss_scalars"][1])

    @jax.jit
    @_highest
    def sample_grads(params, images, tokens):
        return jax.value_and_grad(loss_of)(
            first_blocks(params), params, images, tokens
        )

    return sample_grads


def max_rel_err(got, want) -> float:
    """Max-norm relative error of one array against its reference."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    scale = np.abs(want).max()
    return float(np.abs(got - want).max() / max(scale, 1e-30))


def tree_max_rel_err(got, want) -> dict:
    """``max_rel_err`` per leaf, keyed by the leaf's path. A leaf whose reference
    gradient is zero by symmetry (the key bias: a shift of every key moves all
    of a query's scores alike, and softmax does not see it) has only rounding
    noise to be relative to, so it is measured against the largest leaf of its
    top-level group instead."""
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_w = dict(jax.tree_util.tree_leaves_with_path(want))
    if flat_g.keys() != flat_w.keys():
        raise ValueError("gradient trees differ in structure")
    group_scale: dict = {}
    for k, w in flat_w.items():
        group_scale[k[0]] = max(group_scale.get(k[0], 0.0), float(np.abs(w).max()))
    errs = {}
    for k, w in flat_w.items():
        if np.abs(w).max() < 1e-5 * group_scale[k[0]]:
            errs[jax.tree_util.keystr(k)] = float(np.abs(flat_g[k]).max() / group_scale[k[0]])
        else:
            errs[jax.tree_util.keystr(k)] = max_rel_err(flat_g[k], w)
    return errs


# -- bounds --------------------------------------------------------------------
# Max-norm relative error, system against reference, on the chip. Set in PR 22
# from my chip runs (PERF.md section 6 has the table of what was measured in each
# cell); each is two to five times the widest value seen.

# (1) Normalised embeddings: bf16 activations through 12 or 27 blocks with the
# fused attention kernel (PR 21: 1e-2 for one attention call against dense).
# Measured 0.008 to 0.015 (B/16) and 0.011 to 0.013 (so400m). An int8 forward
# under a bf16 label read 0.020 on one seed: this bound guards against gross
# errors and does not tell int8 from bf16 (PERF.md section 6 says what would).
EMBED_BOUND = 3e-2
# (2) The step's reported loss, and the sample's. At initialisation the loss is
# 10 +- 0.3 and set by bias = -10, so it barely sees the towers; it guards the
# loss island, the averaging over microbatches and the ring's coverage of all
# pairs. Measured up to 6.3e-4.
LOSS_BOUND = 2e-3
# (3) Gradients of the loss scalars and of the first block of each tower: bf16
# activations and bf16-grade backward matmuls in the fused attention. Measured
# on the worst leaf 0.013 to 0.020 (B/16) and 0.018 to 0.029 (so400m).
GRAD_BOUND = 6e-2
