"""The plain reference of a text tower that mixes window and full attention in
one stack (configuration ``trinity-mini-text-b16-224``), in straight
``jax.numpy`` float32 at ``highest`` matmul precision: no kernels, no flax,
nothing of the program but its parameter tree. With x the (s, width) stream of
one caption, RMS(x) = x rsqrt(mean(x^2) + eps) g, every projection bias-free:

    h = embed_scale E[tokens]                       # muP: sqrt(width); no position table
    for l in 0..depth-1:  a = h + RMS_1post(Attn_l(RMS_1(h)));  h = a + RMS_2post(FFN_l(RMS_2(a)))
    z_txt = RMS_final(h)[s-1] W_proj + b            # last token
    FFN_l: SwiGLU for the leading dense layers, else MoE.

    Attn (h query heads, kv key / value heads, all d wide; query head j reads head j // (h / kv)):
      q = u Wq ;  k = u Wk ;  v = u Wv ;  g = u Wg               # Wq, Wg: width -> h d ; Wk, Wv: width -> kv d
      q_j = RMS_q(q_j) ;  k_i = RMS_k(k_i)                       # over a head's d lanes; one d-wide scale each, shared by the heads
      a window layer:  q_j, k_i = rot(q_j), rot(k_i)             # positions 0..s-1; a full layer takes NO position
      admitted (query t, key i):  a full layer  i <= t ;  a window layer  t - w < i <= t     # w keys, the token itself among them
      o_j = softmax over the admitted (q_j k^T d^-1/2) v
      out = ( concat_j(o_j) sigmoid(g) ) Wo
      rot(x)[p] = (x1 cos a_p - x2 sin a_p, x2 cos a_p + x1 sin a_p),  (x1, x2) the halves of the d lanes,
                  a_p[i] = p theta^(-2i/d)
    MoE (E routed, top-k, a shared expert, the experts this chip holds): ``reference_kimi.py``'s
      s = sigmoid(u Wr) ;  I = top_k(s + b) ;  w_i = scale s_i / sum_{j in I} s_j
      y = Shared(u) + sum_{i in I and held} w_i E_i(u) ;  E(u) = (silu(u Wg) (u Wu)) Wd

At 8192 tokens one head's float32 scores are 268 MB, so the tower runs **one
caption at a time** (a ``lax.map`` over the rows) and the queries ``QUERY_BLOCK``
at a time against every key (16 MB a head), the band a mask over all the keys
(nothing is skipped: a key outside the band is scored and then masked), each
caption, layer and block under ``jax.checkpoint``, so that the sample's gradient
fits beside the parameters: recomputation changes no number's mathematics. Each
expert held runs every token and a mask keeps the routed ones. What the absent
experts would add is left out, as in the program; nothing stands in for them.

Taken from ``reference_kimi.py`` (a private instance) where it is the same: the
routed layer, ``rms_norm``, ``swiglu``, ``compared_leaves``, the error measures,
the quick compile and, through it, ``reference.py``'s image tower, loss and
microbatch groups. ``first_blocks`` takes the loss scalars, the image tower's
first block and, of the text tower, layer 0 (a window layer with the dense MLP:
its gradient has crossed every layer above) and layer 1 (a window layer with
experts) without its router, and of every other layer its attention (layer 2 is
the full, unrotated one).

Departures from the published model (arcee-ai/Trinity-Mini, ``afmoe``):
config.json states the widths, the layer kinds, the window, eps, theta, the
router's activation, renormalisation and scale. From the modeling code in
``transformers``, not config.json: the gate and where it multiplies, the head
norms before the rotation, rotation on the window layers only, the embedding's
muP factor, the four norms a layer and their order, the window's convention (w
keys with the token itself), rotate-half. The renormalisation's 1e-20 beside the
sum of eight sigmoids changes no float32 number and is left out. Left out with
the other language-model towers: the LM head, the next-token objective, the
balancing update of the selection bias b (b is read, never written).

The bounds are at the bottom, each with the measurements it was set from.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness

# A private instance of reference_kimi.py, and through it of reference.py: the
# names bound at the bottom of this file are its own.
_kimi = harness.load_module(os.path.join(harness.BENCH_DIR, "reference_kimi.py"))
_base = _kimi._base

F32 = _base.F32
QUERY_BLOCK = 512  # queries scored at a time: 512 x 8192 float32 scores a head are 16 MB

rms_norm, swiglu, moe, compared_leaves = _kimi.rms_norm, _kimi.swiglu, _kimi.moe, _kimi.compared_leaves


# -- the layers, one caption at a time ------------------------------------------


def rotate(x, theta: float):
    """x: (s, h, d), positions 0..s-1 along the first axis, the halves paired."""
    s, d = x.shape[0], x.shape[-1]
    angle = np.arange(s)[:, None] / theta ** (np.arange(0, d, 2) / d)  # (s, d/2), float64
    cos, sin = (jnp.asarray(f(angle), F32)[:, None, :] for f in (np.cos, np.sin))
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def banded_attention(q, k, v, window: int):
    """softmax over the admitted keys of (q k^T d^-1/2) v for one caption; q, k, v:
    (s, h, d). ``window`` 0: every key up to the query's own; w > 0: the w keys
    that end in the query's own. The queries a block at a time, each block
    against every key."""
    s, h, d = q.shape
    block = next(n for n in range(min(QUERY_BLOCK, s), 0, -1) if s % n == 0)
    keys = jnp.arange(s)

    def attend(scanned):
        q_block, start = scanned
        scores = jnp.einsum("qhd,khd->hqk", q_block, k) * d**-0.5
        queries = (start + jnp.arange(block))[:, None]
        seen = keys[None, :] <= queries
        if window:
            seen &= keys[None, :] > queries - window
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v)

    out = jax.lax.map(jax.checkpoint(attend), (q.reshape(s // block, block, h, d), jnp.arange(0, s, block)))
    return out.reshape(s, h, d)


def layer_windows(t) -> tuple[int, ...]:
    return tuple(t.attn_windows) or (0,) * t.depth


def attention(u, p, t, window: int):
    s = u.shape[0]
    h, kv, d = t.num_heads, t.num_kv_heads or t.num_heads, t.head_dim or t.width // t.num_heads
    q = (u @ p["q"]["kernel"]).reshape(s, h, d)
    k, v = ((u @ p[name]["kernel"]).reshape(s, kv, d) for name in "kv")
    if t.attn_qk_norm:
        q, k = rms_norm(q, p["q_norm"]["scale"], t.norm_eps), rms_norm(k, p["k_norm"]["scale"], t.norm_eps)
    if t.pos == "rope" and (t.rope_layers == "all" or window):
        q, k = rotate(q, t.rope_theta), rotate(k, t.rope_theta)
    k, v = (jnp.repeat(x, h // kv, axis=1) for x in (k, v))
    out = banded_attention(q, k, v, window if window < s else 0).reshape(s, h * d)
    if t.attn_gate:
        out = out * jax.nn.sigmoid(u @ p["gate"]["kernel"])
    return out @ p["out"]["kernel"]


def layer(x, p, t, window: int, chosen=None):
    eps = t.norm_eps
    a = attention(rms_norm(x, p["ln1"]["scale"], eps), p["attn"], t, window)
    a = x + (rms_norm(a, p["ln1_post"]["scale"], eps) if t.sandwich_norm else a)
    u = rms_norm(a, p["ln2"]["scale"], eps)
    if "moe" in p:
        y = moe(u, p["moe"], t, chosen)
    else:
        m = p["mlp"]
        y = swiglu(u, m["wg"]["kernel"], m["wi"]["kernel"], m["wo"]["kernel"])
    return a + (rms_norm(y, p["ln2_post"]["scale"], eps) if t.sandwich_norm else y)


# -- the parameter tree -------------------------------------------------------


def compared(encoder: dict) -> dict:
    """The text leaves whose gradients ``correct`` compares, from the tree alone:
    the first dense layer and the first routed layer whole (without the router:
    ``compared_leaves``), and of every other layer its attention, so that both
    kinds of attention layer are compared wherever the full one stands."""
    names = [f"block{i}" for i in range(sum(k.startswith("block") for k in encoder))]
    whole = {next(n for n in names if kind in encoder[n]) for kind in ("mlp", "moe") if any(kind in encoder[n] for n in names)}
    return {n: compared_leaves(encoder[n]) if n in whole else {"attn": encoder[n]["attn"]} for n in names}


def first_blocks(params: dict) -> dict:
    return {
        "loss_scalars": jnp.stack([params["t_prime"], params["bias"]]),
        "visual": _base.first_block(params["visual"]["encoder"]),
        "textual": compared(params["textual"]["encoder"]),
    }


def text_tower(t, p, tokens, first=None, chosen=None):
    """(rows, s) token ids -> (rows, embed_dim). ``first`` stands in for the
    compared leaves of the tree's own layers; ``chosen`` ({layer: (rows, s, k)}),
    where given, for the routed layers' own selection (benchmark/tests/controls_trinity.py)."""
    blocks = []
    for i, window in enumerate(layer_windows(t)):
        name = f"block{i}"
        block = p["encoder"][name]
        if first and name in first:
            block = {**block, **first[name], **({"moe": {**block["moe"], **first[name]["moe"]}} if "moe" in first[name] else {})}
        blocks.append((name, block, window))

    def caption(scanned):
        row, chosen_here = scanned
        x = p["token_embed"]["embedding"][row] * t.embed_scale
        for name, block, window in blocks:
            x = jax.checkpoint(lambda x, block, c, w=window: layer(x, block, t, w, c))(x, block, chosen_here.get(name))
        return rms_norm(x, p["encoder"]["ln_final"]["scale"], t.norm_eps)[-1]  # the last token

    pooled = jax.lax.map(jax.checkpoint(caption), (tokens, chosen or {}))
    return _base.dense(pooled, p["proj"])


def _check_supported(cfg):
    """This file has the equations of one stack: the options that select it."""
    t = cfg.text
    want = dict(norm="rmsnorm", mlp="swiglu", use_bias=False, causal=True, loops=1, moe_router="sigmoid", pool="last",
                mixers=(), sublayers="pair", norm_unit_offset=False)
    differs = {k: getattr(t, k) for k, v in want.items() if getattr(t, k) != v}
    if differs or t.pos not in ("rope", "none") or len(layer_windows(t)) != t.depth:
        raise ValueError(f"reference_trinity.py has the equations for text {want}, pos 'rope' or 'none' and one window a "
                         f"layer, got {differs or (t.pos, t.attn_windows)}")
    if cfg.vision.moe_experts or cfg.loss.family != "sigmoid":
        raise ValueError("reference_trinity.py takes reference.py's dense image tower and sigmoid loss only")


# -- what `correct` calls: reference.py's, over the text tower above -----------

_base.text_tower = text_tower
_base.first_blocks = first_blocks
_base._check_supported = _check_supported

embeddings = _base.embeddings
microbatch_rows = _base.microbatch_rows
# Each program runs once a process: compiled at the compiler's least effort (reference_kimi.py says why).
make_batch_loss = _kimi._compiled_quickly(_base.make_batch_loss)
make_sample_grads = _kimi._compiled_quickly(_base.make_sample_grads)
# The error measures are reference_kimi.py's, for its reason: the router's top-k is
# discrete, and a bf16 tower gives a few tokens in a hundred another held expert
# than this float32 reference gives them. Each row's relative l2 error and of the
# rows the best; each leaf's relative Frobenius error; of the routed experts'
# stacks only that no expert the reference trains is left without a gradient.
max_rel_err = _kimi.max_rel_err
tree_max_rel_err = _kimi.tree_max_rel_err

# -- bounds --------------------------------------------------------------------
# Relative error, system against reference, by the measures above, on the chip at
# the cell's sizes (my chip runs, PR 47; PERF.md section 6 has every reading).
# Sound: the cell's check through run.py on fourteen seeds (4700000101-102,
# 4700001001-007, 4700004001-007; the last seven with the selection bias fitted
# over sixteen batches, which moves no reading). Controls, planted in the program's
# trace by ``benchmark/tests/controls_trinity.py``: on the chip at the cell's
# sizes, seed 4700000201, all reads in one process (sound there: 0.0103 / 3.1e-4 /
# 0.042): no window in the window layers; rotation on the full layer too; no gate;
# no head norms; the text cores' operands and output rounded to float8_e4m3's
# precision by ``lax.reduce_precision``, the nearest precision below the
# configuration's bfloat16.

# (1) The best row's l2 error, image and text (the text rows are the larger; a
# near-tie of the router at a pooled token spares the best row). Sound 0.0097 to
# 0.0112: a caption crosses ten sub-layers in bf16. fp8 in the cores 0.0916, rotation
# everywhere 0.160, the head norms left out 0.176, the gate left out 0.418, the
# window left out 1.013: every control is outside, the nearest 3.7 times the limit. The limit is
# Nemotron's and 2.2 times the largest sound reading.
EMBED_BOUND = 2.5e-2
# (2) The step's reported loss (sound 1.5e-5 to 1.0e-3) and the sample's (1e-6 to
# 1.4e-3). At initialisation the loss is 10.0, set by bias = -10: precision hardly
# moves it, so the limit is the accepted routed cells' (``reference_kimi.py``,
# ``reference_glm.py``, ``reference_nemotron.py``: 1.5e-2), sixty times the first
# reading (2.6e-4) and ten times the largest. It guards the loss island and the
# averaging over microbatches; of the faults it sees the grossest.
LOSS_BOUND = 1.5e-2
# (3) The worst leaf of the loss scalars, the image tower's first block, text
# layers 0 and 1 without the router and every other layer's attention, by l2; an
# idle expert reads 1. It guards the backward passes (the pair's with a window,
# the head norms', the gate's): a leaf whose gradient is lost, or wrong by its own
# size, reads about 1 whatever the forward does. Sound, fourteen seeds: 0.036 to
# 0.045 on twelve (the worst leaf a head norm's scale or a q kernel), 0.097 and
# 0.116 on two (4700001004, 4700000101: the sample's loss 1.4e-3 and 1.0e-3 beside
# the others' 1e-4, a near-tie's mark). No seed of these met a near-tie at a
# pooled token, which in the other routed cells read 0.33 to 0.53 on one run in
# fifteen (``reference_nemotron.py``): the limit is Nemotron's, 5.6 times the
# largest sound reading, for that. Controls: the gate left out and the
# head norms left out exactly 1 (their leaves get nothing), rotation everywhere
# 1.14, the window left out 1.32. fp8 reads 0.247 here (typical leaf 0.161): (1)'s
# to catch and not this limit's; its sample loss reads 2.0e-3, the window left out
# 6.7e-3, under (2)'s limit both.
GRAD_BOUND = 6.5e-1
