"""The plain reference of a text tower of windowed chunk attention
(configuration ``evabyte-text-b16-224``), in straight ``jax.numpy`` float32 at
``highest`` matmul precision: no kernels, no flax, nothing of the program but
its parameter tree. With x the (s, D) stream of one caption, H heads of d, windows
of W tokens, chunks of C, every projection bias-free:

    RMS(x)  = x rsqrt(mean(x^2) + eps) (1 + g)                         # g: the leaf ``offset``, initially 0
    h = E[tokens]                                                      # no position table
    for l in 0..depth-1:  a = h + Eva_l(RMS_1(h)) ;  h = a + (silu(RMS_2(a) Wg) * (RMS_2(a) Wu)) Wd
    z_txt = RMS_final(h)[s-1] W_proj + b                               # last token
    Eva:  q_h, k_h, v_h = (u Wq)_h, (u Wk)_h, (u Wv)_h ;  q_h, k_h = rot(q_h), rot(k_h)   # all d lanes, positions 0..s-1
          chunk c = tokens Cc .. Cc+C-1, in window floor(Cc / W) ;  window(t) = floor(t / W)
          a_j   = softmax over j in c of (k_j . phi_h) d^-1/2          # phi_h, mu_h: (d,) leaves per head
          kc_c  = sum_j a_j k_j + mu_h ;  vc_c = sum_j a_j v_j         # from the rotated keys
          s_tj  = q_t . k_j d^-1/2   for j <= t in window(t)           # exact, causal, inside the window
          r_tc  = q_t . kc_c d^-1/2  for every chunk c of a window before window(t)
          o_t   = (sum_j e^s_tj v_j + sum_c e^r_tc vc_c) / (sum_j e^s_tj + sum_c e^r_tc)   # one softmax over both sets
          out   = concat_h(o_t) Wo
    rot(x)[p] = (x1 cos a_p - x2 sin a_p, x2 cos a_p + x1 sin a_p),  (x1, x2) the halves of the d lanes,
                a_p[i] = p theta^(-2i/d)

What this costs, and why it is cut so. At the cell's widths (D 4096, 11008 in the
MLP, 8192 tokens) one caption's float32 stream is 134 MB, a layer's q, k, v, the
scores of a query block and the MLP's hidden (8192 x 11008 x 4 = 361 MB an array,
three of them) about 2.4 GB if kept whole, beside a training state of 10.9 GB on a
16 GB chip. So the tower runs **one caption at a time** (a ``lax.map`` over the
rows), each layer under ``jax.checkpoint`` (a caption keeps four 134 MB layer
inputs), the queries ``QUERY_BLOCK`` at a time against their window's keys and
every summary, the MLP ``MLP_BLOCK`` tokens at a time, each block under
``jax.checkpoint`` too; and the sample's gradient is taken with respect to the
compared leaves only (``first_blocks``: the loss scalars and the first layer of
each tower, 0.8 GB for the text tower's; all 907 M float32 gradients would be 3.6
GB). Recomputation changes no number's mathematics; it costs the reference about
one more forward a caption, some seconds of set-up on the chip.

The image tower, the loss, the microbatch groups and the error measures are
``reference.py``'s (a private instance).

Departures from the published model (EvaByte/EvaByte, ``model_type evabyte``,
``attention_class eva``): config.json states the widths, the window, the chunk,
eps, theta and ``norm_add_unit_offset``. From the paper (Zheng et al.,
arXiv:2302.04542) and the published modeling code as far as they can be recalled
offline: the pooling's form (a softmax over a chunk's tokens of k . phi, the same
weights for the values), the two per-head leaves (``adaptive_phi``,
``adaptive_mu_k``), the summaries taken from the rotated keys, the staircase by
window. The rotation pairs lane i with lane i + d/2 (the program's ``rope``); an
implementation that pairs lanes 2i and 2i + 1 differs by one fixed permutation of
Wq's and Wk's columns within a head (and of phi's and mu's lanes), which leaves
every score as it is. ``fp32_skip_add``: each residual sum is formed in float32
(here everything is). Left out: the LM head, the 8 multi-byte prediction heads and
the next-byte objective.

The bounds are at the bottom, each with the measurements it was set from.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

# benchmark/ is on sys.path: run.py and the tools under tests/ put it there.
import harness

# A private instance of reference.py: the names bound at the bottom of this file are its own.
_base = harness.load_module(os.path.join(harness.BENCH_DIR, "reference.py"))

F32 = _base.F32
QUERY_BLOCK = 512  # queries scored at a time: 32 x 512 x (2048 + 512) float32 scores are 168 MB
MLP_BLOCK = 2048  # tokens through the MLP at a time: 2048 x 11008 float32 is 90 MB an array


def _by_blocks(fn, x, block: int):
    """``fn`` over the leading axis of ``x`` in blocks of at most ``block`` rows
    (a divisor of the length), each under ``jax.checkpoint``."""
    n = x.shape[0]
    block = next(m for m in range(min(block, n), 0, -1) if n % m == 0)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(n // block, block, *x.shape[1:]))
    return out.reshape(n, *out.shape[2:])


# -- the layers, one caption at a time ------------------------------------------


def rms_norm(x, offset, eps: float):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + offset)


def rotate(x, theta: float):
    """x: (s, h, d), positions 0..s-1 along the first axis, the halves paired."""
    s, d = x.shape[0], x.shape[-1]
    angle = np.arange(s)[:, None] / theta ** (np.arange(0, d, 2) / d)  # (s, d/2), float64
    cos, sin = (jnp.asarray(f(angle), F32)[:, None, :] for f in (np.cos, np.sin))
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, phi, mu, chunk: int):
    """k, v: (s, h, d) -> the chunks' pooled keys and values (s / chunk, h, d)."""
    s, h, d = k.shape
    k, v = (x.reshape(s // chunk, chunk, h, d) for x in (k, v))
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", k, phi) * d**-0.5, axis=1)[..., None]
    return (a * k).sum(1) + mu, (a * v).sum(1)


def windowed_chunk_attention(q, k, v, kc, vc, window: int, chunk: int):
    """One caption: q, k, v (s, h, d), kc, vc (s / chunk, h, d) -> (s, h, d). The
    queries a block at a time (a block lies in one window), each against its
    window's keys and every summary, under the two masks, one softmax."""
    s, h, d = q.shape
    block = next(n for n in range(min(QUERY_BLOCK, window), 0, -1) if window % n == 0)
    summary_window = jnp.arange(s // chunk) * chunk // window

    def attend(scanned):
        q_block, start = scanned
        w = start // window
        keys, values = (jax.lax.dynamic_slice_in_dim(x, w * window, window) for x in (k, v))
        at = start + jnp.arange(block)
        exact = jnp.einsum("qhd,khd->hqk", q_block, keys) * d**-0.5
        exact = jnp.where(at[:, None] >= (w * window + jnp.arange(window))[None, :], exact, -jnp.inf)
        remote = jnp.einsum("qhd,chd->hqc", q_block, kc) * d**-0.5
        remote = jnp.where(summary_window[None, :] < w, remote, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([exact, remote], -1), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs[..., :window], values) + jnp.einsum("hqc,chd->qhd", probs[..., window:], vc)

    out = jax.lax.map(jax.checkpoint(attend), (q.reshape(s // block, block, h, d), jnp.arange(0, s, block)))
    return out.reshape(s, h, d)


def eva(x, p, t):
    s = x.shape[0]
    h = t.num_heads
    q, k, v = ((x @ p[name]["kernel"]).reshape(s, h, -1) for name in "qkv")
    q, k = rotate(q, t.rope_theta), rotate(k, t.rope_theta)
    kc, vc = summaries(k, v, p["phi"], p["mu"], t.eva_chunk)
    out = windowed_chunk_attention(q, k, v, kc, vc, t.eva_window, t.eva_chunk)
    return out.reshape(s, -1) @ p["out"]["kernel"]


def swiglu(x, p):
    def tokens(y):
        return (jax.nn.silu(y @ p["wg"]["kernel"]) * (y @ p["wi"]["kernel"])) @ p["wo"]["kernel"]

    return _by_blocks(tokens, x, MLP_BLOCK)


def layer(x, p, t):
    a = x + eva(rms_norm(x, p["ln1"]["offset"], t.norm_eps), p["eva"], t)
    return a + swiglu(rms_norm(a, p["ln2"]["offset"], t.norm_eps), p["mlp"])


# -- the tower ------------------------------------------------------------------


def text_tower(t, p, tokens, first=None):
    """(rows, s) token ids -> (rows, embed_dim). The layers as one scan over
    their stack, read where it lies (either layout: ``reference.stacked_blocks``;
    a slice a layer would be a 0.8 GB copy each). ``first`` stands in for layer 0
    (the gradient check differentiates with respect to it) as in
    ``reference.encoder``: it runs ahead of the scan, whose step 0 is computed
    and dropped."""
    stack = _base.stacked_blocks(p["encoder"], t.depth)
    one_layer = jax.checkpoint(lambda x, block: layer(x, block, t))

    def caption(row):
        x = p["token_embed"]["embedding"][row]
        if first is not None:
            x = one_layer(x, first)

        def body(x, scanned):
            i, block = scanned
            y = one_layer(x, block)
            return (y if first is None else jnp.where(i == 0, x, y)), None

        x, _ = jax.lax.scan(body, x, (jnp.arange(t.depth), stack))
        return rms_norm(x, p["encoder"]["ln_final"]["offset"], t.norm_eps)[-1]  # the last token

    return _base.dense(jax.lax.map(jax.checkpoint(caption), tokens), p["proj"])


def _check_supported(cfg):
    """This file has the equations of one stack: the options that select it."""
    t = cfg.text
    want = dict(norm="rmsnorm", norm_unit_offset=True, sandwich_norm=False, mlp="swiglu", use_bias=False, causal=True,
                loops=1, pos="rope", pool="last", moe_experts=0, leading_dense_layers=0)
    differs = {k: getattr(t, k) for k, v in want.items() if getattr(t, k) != v}
    if differs or set(t.mixers) != {"eva"} or len(t.mixers) != t.depth:
        raise ValueError(f"reference_eva.py has the equations for text {want} and mixers of 'eva' alone, "
                         f"got {differs or t.mixers}")
    if cfg.vision.moe_experts or cfg.loss.family != "sigmoid":
        raise ValueError("reference_eva.py takes reference.py's dense image tower and sigmoid loss only")


# -- what `correct` calls: reference.py's, over the text tower above -----------

_base.text_tower = text_tower
_base._check_supported = _check_supported

# Each program runs once a process and is large (a caption's loop over four
# checkpointed layers and their blocks): compiled at the compiler's least effort.
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0}


def _compiled_quickly(make):
    """``make``, its jitted program jitted again with ``QUICK_COMPILE``."""

    @functools.wraps(make)
    def made(*args):
        return jax.jit(make(*args).__wrapped__, compiler_options=QUICK_COMPILE)

    return made


first_blocks = _base.first_blocks
embeddings = _base.embeddings
microbatch_rows = _base.microbatch_rows
make_batch_loss = _compiled_quickly(_base.make_batch_loss)
make_sample_grads = _compiled_quickly(_base.make_sample_grads)
max_rel_err = _base.max_rel_err
tree_max_rel_err = _base.tree_max_rel_err

# -- bounds --------------------------------------------------------------------
# Max-norm relative error, system against reference, as reference.py measures them,
# on the chip at the cell's sizes (my chip runs, PR 39; PERF.md section 6 has every
# reading). Sound, fourteen readings of the shipped program: the cell's check through
# run.py (seeds 3900000011-13, 3900000051-57) and two by
# ``benchmark/tests/controls_eva.py --read`` (seeds 3900000021-22). Controls, two seeds each, planted in the program's trace
# by that tool: the summaries left out; mu left out; the summaries' mask a window
# off; the chunks' weights uniform; the cores' operands and output rounded to
# float8_e4m3's precision by ``lax.reduce_precision`` (the nearest precision below
# the configuration's bfloat16; ``fp8_dense`` rounds every Dense output so instead).

# (1) Normalised embeddings, image and text (the text rows are the larger). Sound
# 0.0086 to 0.0121: a caption crosses four layers in bf16. fp8 in
# the cores 0.0579 and 0.0627 (in every Dense output 0.0872 and 0.0986), mu left
# out 0.132 and 0.140, the mask a window off 0.259 and 0.299, uniform pooling 0.265
# and 0.373, no summaries 0.751 and 0.762. 2.2 times the largest sound reading,
# under half of the smallest control's.
EMBED_BOUND = 2.7e-2
# (2) The step's reported loss (sound 2.1e-5 to 4.7e-4) and the sample's (7.0e-6 to
# 7.9e-4). At initialisation the loss is 10 +- 0.3, set by bias = -10: precision
# hardly moves it (fp8 reads 1.4e-4 and 1.6e-4, as sound), so the limit is an
# accepted cell's (reference.py: 2e-3), twelve times the first reading and two and
# a half times the largest. It guards the loss island and the averaging over microbatches;
# of the faults it sees the grossest: no summaries 1.9e-2 and 2.0e-2, uniform
# pooling 2.1e-3 and 1.1e-2, the mask a window off 2.1e-3 and 3.0e-3.
LOSS_BOUND = 2e-3
# (3) The worst leaf of the loss scalars and of the first layer of each tower (the
# text tower's: both norms' offsets, q, k, v, out, phi, mu and the MLP's three).
# Sound 0.0151 to 0.0248 (the worst leaf is mu, phi or a q / k kernel). fp8 in the
# cores 0.0997 and 0.1225 (in every Dense output 0.116 and 0.146), the mask a window
# off 0.656 and 0.739; mu left out and uniform pooling exactly 1 (the leaf gets no
# gradient), no summaries 1.0 and 1.05. Twice the largest sound reading, half of the
# smallest control's.
GRAD_BOUND = 5e-2
