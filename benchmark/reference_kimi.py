"""The plain reference of the hybrid text tower (configuration
``kimi-linear-48b-text-b16-224``), in straight ``jax.numpy`` float32 at
``highest`` matmul precision: no kernels, no chunks, no flax. It reads the
program's parameter tree and nothing else of the program. With x the (s, width)
stream of one caption, RMS(x) = x rsqrt(mean(x^2) + eps) g, every projection
bias-free:

    h = E[tokens]                                   # no position table
    for l in 1..depth:  a = h + Mixer_l(RMS(h));  h = a + FFN_l(RMS(a))
    z_txt = RMS_final(h)[s-1] W_proj + b            # last token
    Mixer_l: "kda" or "mla" (``text.mixers``).  FFN_l: SwiGLU for the leading dense layers, else MoE.

    KDA (h heads, dk = dv = d):
      q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))    # conv: causal depthwise, 4 taps, no bias
      q_h = l2norm(q_h) d^-1/2 ;  k_h = l2norm(k_h)                     # l2norm(x) = x rsqrt(sum x^2 + 1e-6)
      g   = -exp(A_log_h) softplus((x Wfa) Wfb + dt_bias)               # log-decay per key channel, < 0
      beta_h = sigmoid(x Wb)
      S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T ;  S_0 = 0 ;  o_t = S_t^T q_t
      out = ( RMS_head(o) sigmoid((x Wga) Wgb) ) Wo
    MLA:
      q_h = (x Wq)_h ;  [c, kr] = x Wkva ;  [kn_h, v_h] = (RMS(c) Wkvb)_h ;  k_h = [kn_h, kr]
      out = softmax(q_h k_h^T (dn + dr)^-1/2 + causal) v_h -> Wo
    MoE (E routed, top-k, shared experts, the experts this chip holds):
      s = sigmoid(x Wr) ;  I = top_k(s + b) ;  w_i = scale s_i / sum_{j in I} s_j
      y = Shared(x) + sum_{i in I and held} w_i E_i(x) ;  E(x) = (silu(x Wg) (x Wu)) Wd

The delta rule runs **token by token** (a ``lax.scan`` over the sequence; 32
tokens at a time under ``jax.checkpoint`` and each layer under another, so that
the sample's gradient fits beside a training state: recomputation changes no
number's mathematics). Each expert held runs every token and a mask keeps the
routed ones (one product over the stacked experts). Attention is a full softmax over (s, s) scores. What the absent
experts would add is left out, as in the program; nothing stands in for them.

The image tower, the pooling, the loss and the microbatch groups are
``reference.py``'s own, from a private instance of that module; the error
measures are this file's (l2 norms; "the error measures" below says why).
``first_blocks`` takes the loss scalars, the image tower's first block and, of
the text tower, its first "kda" layer and its first "mla" layer (layers 1 and 4
of the cell, the second with experts) without the router (``compared_leaves``
says why): the delta rule's, latent attention's and the shared expert's
gradients are compared, after crossing every routed layer above them, and of the
routed experts' stacks that every expert gets a gradient.

Departures from the published model (moonshotai/Kimi-Linear-48B-A3B-Instruct):
config.json states the widths, the layer order, eps, the router's activation,
renormalisation and scale. From the paper and the modeling code, not config.json:
no biases, the convolution without bias, ``A_log`` per head, l2norm and d^-1/2 on
q, the sigmoid output gate, the head norm's eps. Left out: the LM head, the
next-token objective, the balancing update of the selection bias b (a
training-recipe step; b is read, never written).

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

# A private instance of reference.py: the names bound at the bottom of this file
# are its own, and ``import reference`` elsewhere sees none of it.
_base = harness.load_module(os.path.join(harness.BENCH_DIR, "reference.py"))

F32 = _base.F32
L2_EPS = 1e-6
SCAN_GROUP = 32  # tokens of the recurrence between two saved states


# -- the layers ---------------------------------------------------------------


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def causal_conv(y, taps):
    """Depthwise along the sequence: out[t] = sum_j taps[j] y[t - 3 + j]."""
    n, s = taps.shape[0], y.shape[1]
    padded = jnp.pad(y, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j : j + s] * taps[j] for j in range(n))


def l2norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token at a time. q, k, g: (b, s, h, dk); v: (b, s, h,
    dv); beta: (b, s, h)."""
    b, s, h, dk = q.shape

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (beta_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    group = next(n for n in range(min(SCAN_GROUP, s), 0, -1) if s % n == 0)
    xs = tuple(
        jnp.moveaxis(x, 1, 0).reshape(s // group, group, *x.shape[:1], *x.shape[2:])
        for x in (q, k, v, g, beta)
    )
    state, out = jax.lax.scan(
        jax.checkpoint(lambda state, x: jax.lax.scan(token, state, x)),
        jnp.zeros((b, h, dk, v.shape[-1]), F32), xs,
    )
    return jnp.moveaxis(out.reshape(s, b, h, -1), 0, 1)


def kda(x, p, t):
    b, s, _ = x.shape
    h, d = t.num_heads, t.kda_head_dim

    def branch(n):
        return jax.nn.silu(causal_conv(x @ p[n]["kernel"], p[n + "_conv"])).reshape(b, s, h, d)

    q, k, v = l2norm(branch("q")) * d**-0.5, l2norm(branch("k")), branch("v")
    step = (x @ p["f_a"]["kernel"]) @ p["f_b"]["kernel"] + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(step).reshape(b, s, h, d)
    beta = jax.nn.sigmoid(x @ p["beta"]["kernel"])
    o = rms_norm(delta_rule(q, k, v, g, beta), p["o_norm"], t.norm_eps)
    gate = jax.nn.sigmoid((x @ p["g_a"]["kernel"]) @ p["g_b"]["kernel"]).reshape(b, s, h, d)
    return (o * gate).reshape(b, s, h * d) @ p["out"]["kernel"]


def mla(x, p, t):
    b, s, _ = x.shape
    h, dn, dr, dv, rank = t.num_heads, t.mla_qk_nope_dim, t.mla_qk_shared_dim, t.mla_v_dim, t.mla_kv_rank
    q = (x @ p["q"]["kernel"]).reshape(b, s, h, dn + dr)
    latent = x @ p["kv_a"]["kernel"]
    c = rms_norm(latent[..., :rank], p["kv_norm"]["scale"], t.norm_eps)
    expanded = (c @ p["kv_b"]["kernel"]).reshape(b, s, h, dn + dv)
    shared = jnp.broadcast_to(latent[:, :, None, rank:], (b, s, h, dr))
    k, v = jnp.concatenate([expanded[..., :dn], shared], -1), expanded[..., dn:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dn + dr) ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, s, h * dv) @ p["out"]["kernel"]


def moe(x, p, t, chosen=None):
    """``chosen`` (tokens, k), where given, stands in for the layer's own
    selection (benchmark/tests/controls_kimi.py runs this reference on the
    program's choices to tell a near-tie from a fault); ``correct`` gives none."""
    scores = jax.nn.sigmoid(x @ p["router"])
    if chosen is None:
        _, chosen = jax.lax.top_k(scores + p["select_bias"], t.moe_num_selected)
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = t.moe_route_scale * picked / picked.sum(-1, keepdims=True)
    shared = p["shared"]
    y = swiglu(x, shared["wg"]["kernel"], shared["wi"]["kernel"], shared["wo"]["kernel"])
    # Every expert held here (experts 0..held-1) runs every token; a token's weight
    # for an expert it did not choose is 0.
    held = jnp.arange(p["wg"].shape[0])
    weight = jnp.where(chosen[..., None, :] == held[:, None], weights[..., None, :], 0.0).sum(-1)  # (..., held)
    hidden = jax.nn.silu(jnp.einsum("...d,edn->...en", x, p["wg"])) * jnp.einsum("...d,edn->...en", x, p["wi"])
    return y + jnp.einsum("...en,end->...d", hidden * weight[..., None], p["wo"])


def layer(x, p, t, chosen=None):
    y = rms_norm(x, p["ln1"]["scale"], t.norm_eps)
    a = x + (kda(y, p["kda"], t) if "kda" in p else mla(y, p["mla"], t))
    y = rms_norm(a, p["ln2"]["scale"], t.norm_eps)
    if "moe" in p:
        return a + moe(y, p["moe"], t, chosen)
    m = p["mlp"]
    return a + swiglu(y, m["wg"]["kernel"], m["wi"]["kernel"], m["wo"]["kernel"])


# -- the parameter tree -------------------------------------------------------


def compared_layers(encoder: dict) -> list[str]:
    """The text layers whose gradients ``correct`` compares: the first with each
    kind of mixer."""
    names = [f"block{i}" for i in range(sum(k.startswith("block") for k in encoder))]
    return [next(n for n in names if kind in encoder[n]) for kind in ("kda", "mla")]


def compared_leaves(block: dict) -> dict:
    """A text layer without its router (and the selection bias, which takes no
    gradient). The loss sees three pooled tokens, so an expert's gradient from
    the three-row sample is mostly one or two late tokens', and bf16 activations
    give 1 to 3 % of the tokens another local expert than this float32 reference
    gives them: where such a token goes the other way the router's and that
    expert's gradients move by their own size in any norm (a sound run read a
    held expert's gradient at 0.048 of the reference's, PERF.md section 6). The
    experts' stacks stay, for what survives that: ``tree_max_rel_err``."""
    if "moe" not in block:
        return block
    return {**block, "moe": {k: v for k, v in block["moe"].items() if k not in ("router", "select_bias")}}


def first_blocks(params: dict) -> dict:
    encoder = params["textual"]["encoder"]
    return {
        "loss_scalars": jnp.stack([params["t_prime"], params["bias"]]),
        "visual": _base.first_block(params["visual"]["encoder"]),
        "textual": {name: compared_leaves(encoder[name]) for name in compared_layers(encoder)},
    }


def text_tower(t, p, tokens, first=None, chosen=None):
    x = p["token_embed"]["embedding"][tokens]
    for i in range(t.depth):
        name = f"block{i}"
        block = p["encoder"][name]
        if first and name in first:  # the compared leaves stand in for the tree's own
            block = {**block, **first[name], **({"moe": {**block["moe"], **first[name]["moe"]}} if "moe" in block else {})}
        x = jax.checkpoint(lambda x, block, c: layer(x, block, t, c))(x, block, (chosen or {}).get(name))
    x = rms_norm(x, p["encoder"]["ln_final"]["scale"], t.norm_eps)
    return _base.dense(_base._pool(x, p, t), p["proj"])


def _check_supported(cfg):
    """This file has the equations of one stack: the options that select it."""
    t = cfg.text
    want = dict(norm="rmsnorm", sandwich_norm=False, mlp="swiglu", use_bias=False, pos="none",
                causal=True, loops=1, moe_router="sigmoid")
    differs = {k: getattr(t, k) for k, v in want.items() if getattr(t, k) != v}
    if differs or not set(t.mixers) <= {"kda", "mla"} or len(t.mixers) != t.depth:
        raise ValueError(f"reference_kimi.py has the equations for text {want} and mixers of 'kda' / 'mla' only, "
                         f"got {differs or t.mixers}")
    if cfg.vision.moe_experts or cfg.loss.family != "sigmoid":
        raise ValueError("reference_kimi.py takes reference.py's dense image tower and sigmoid loss only")


# -- what `correct` calls: reference.py's, over the text tower above -----------

_base.text_tower = text_tower
_base.first_blocks = first_blocks
_base._check_supported = _check_supported

# Each of the reference's two programs runs once a process, so they are compiled
# for a short compile and not for a fast run: the compiler's least effort gives
# the same float32 numbers from a fifth of the generated code (compiled for a
# described v5e: ``batch_loss`` 41 -> 6 s and 101 -> 19 MB, ``sample_grads`` 172
# -> 18 s and 320 -> 61 MB), and at full effort they were half of this cell's
# set-up and pushed its step out of the machine's compile cache (PERF.md
# section 6, PR 32).
QUICK_COMPILE = {"exec_time_optimization_effort": -1.0}


def _compiled_quickly(make):
    """``make``, its jitted program jitted again with ``QUICK_COMPILE``."""

    @functools.wraps(make)
    def made(*args):
        return jax.jit(make(*args).__wrapped__, compiler_options=QUICK_COMPILE)

    return made


embeddings = _base.embeddings
microbatch_rows = _base.microbatch_rows
make_batch_loss = _compiled_quickly(_base.make_batch_loss)
make_sample_grads = _compiled_quickly(_base.make_sample_grads)
# -- the error measures --------------------------------------------------------
# reference.py's are max norms. Here the router's top-k is discrete: a bf16
# tower's 2 % error at a router's input gives 1 to 3 % of the tokens another
# local expert than this float32 reference gives them (a near-tie between the
# k-th and the next of the scores), and where that token is a pooled one, or
# carries most of an expert's gradient, what it touches moves by its own size.
# So the measures below are l2 norms over a row and over a leaf, take the best
# of the rows, and ask of the experts' stacks only what a flip cannot undo.

IDLE = 1e-3  # an expert's gradient under this share of the reference's: no token reached the expert


def _rel_l2(got, want, axis=None):
    return np.sqrt(np.square(got - want).sum(axis)) / np.maximum(np.sqrt(np.square(want).sum(axis)), 1e-30)


def max_rel_err(got, want) -> float:
    """(1)'s measure for embeddings (rows, d): each row's relative l2 error, and
    of the rows the smallest. A near-tie at a pooled (last) token that falls the
    other way moves that one row by an expert's whole output (0.09 to 0.32 where
    the others read 0.04 to 0.06; with this reference run on the program's own
    choices every row reads 0.04: benchmark/tests/controls_kimi.py --forced). It
    happens to one sound row in fifteen, so to two rows of three in 1.3 % of the
    runs, which is too often for a median to stand in a check made a dozen times
    a PR, and to all three in 0.03 %. A fault of the arithmetic (a lower
    precision, a dropped decay, a selection by other scores) moves every row; one
    that spares a row still moves the sample's loss and every gradient."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return float("inf")
    return float(_rel_l2(got, want, axis=-1).min())


def tree_max_rel_err(got, want) -> dict:
    """(3)'s measure per leaf, keyed by the leaf's path: the relative l2
    (Frobenius) error. A leaf whose reference gradient is zero (the key bias, by
    symmetry) is measured against the largest leaf of its top-level group by rms
    instead, as reference.py does by max norm. A stack of routed experts (a 3-D
    leaf of a "moe" layer) reads 1 where the program gives an expert no gradient
    (under ``IDLE`` of the reference's norm) that the reference gives one, and 0
    otherwise: a lost expert, or a selection that never reaches it, leaves
    exactly none, a token gone the other way leaves the other tokens' (see
    ``compared_leaves`` for why the size of such a gradient is not compared)."""
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_w = dict(jax.tree_util.tree_leaves_with_path(want))
    if flat_g.keys() != flat_w.keys():
        raise ValueError("gradient trees differ in structure")

    def rms(x, axis=None):
        return np.sqrt(np.square(x).mean(axis))

    def is_stack(k, w):
        return w.ndim == 3 and any(getattr(part, "key", None) == "moe" for part in k)

    group_rms: dict = {}
    for k, w in flat_w.items():
        if not is_stack(k, w):
            group_rms[k[0]] = max(group_rms.get(k[0], 0.0), float(rms(np.asarray(w, np.float64))))
    errs = {}
    for k, w in flat_w.items():
        g, w = np.asarray(flat_g[k], np.float64), np.asarray(w, np.float64)
        if not (np.isfinite(g).all() and np.isfinite(w).all()):
            errs[jax.tree_util.keystr(k)] = float("inf")
        elif is_stack(k, w):
            size_g, size_w = rms(g, (1, 2)), rms(w, (1, 2))
            idle = (size_w > 1e-5 * size_w.max()) & (size_g < IDLE * size_w)
            errs[jax.tree_util.keystr(k)] = float(idle.any())
        elif rms(w) < 1e-5 * group_rms[k[0]]:
            errs[jax.tree_util.keystr(k)] = float(rms(g - w) / max(group_rms[k[0]], 1e-30))
        else:
            errs[jax.tree_util.keystr(k)] = float(_rel_l2(g, w))
    return errs


# -- bounds --------------------------------------------------------------------
# Relative error, system against reference, by the measures above (PERF.md
# section 6, PR 32, has every reading). Sound: the cell's check through run.py on
# the chip (one run under these measures, seed 3200000001, the seed with a pooled
# near-tie; sixteen under reference.py's max norms, which read higher than these
# where both were read) and, by ``controls_kimi.py --read --any-device``, the
# same check on this sandbox's CPU at full width on fifteen seeds, which reads a
# tenth to a quarter above the chip (the one seed read on both: rows 0.045 / 0.320
# / 0.051 there, 0.036 / 0.291 / 0.038 on the chip; worst leaf 0.269 and 0.249,
# the same leaf). Controls, at full width on the CPU (the chip call that was to
# repeat them hung in the tool, not in the program) and, where said, from the
# chip under the max norms: every Dense output through float8_e4m3fn, the nearest
# precision below bf16; selection by s where the reference selects by s + b, b
# drawn N(0, 0.05^2) and N(0, 0.1^2); one held expert lost; the decay dropped.

# (1) The best row's l2 error, image and text. Sound 0.0357 on the chip (the
# other rows 0.0375 and 0.291), 0.038 to 0.047 on the CPU's fifteen seeds.
# Selection by s 0.092 (b of 0.05) and 0.127 (0.1), fp8 0.210 and 0.222 (its rows
# 0.21 to 0.25); on the chip by max norm fp8 0.119 and the decay dropped 1.30. A lost
# expert reads as sound here (0.044): (3) has it.
EMBED_BOUND = 7e-2
# (2) The step's reported loss (sound 1.4e-5 to 3.8e-4 on the chip) and the
# sample's (4.3e-5 to 4.7e-3 on the chip, to 3.5e-3 on the CPU; the decay dropped
# 1.9e-2, fp8 1.3e-3 to 4.1e-3). The accepted cells' 2e-3 / 4e-3 leave the first
# reading no room: three times it (PERF.md section 7 asks for the harness to take
# two limits).
LOSS_BOUND = 1.5e-2
# (3) The worst leaf of the loss scalars, the image tower's first block and text
# layers 1 and 4 without the router, by l2; an idle expert reads 1. Sound 0.249 on
# the chip (0.074 to 0.42 by max norm on its sixteen seeds), 0.078 to 0.27 on the
# CPU, where the smallest share of its reference's norm that a sound run left an
# expert was 0.048 (one token carried the rest and went the other way), fifty
# times ``IDLE``. A lost expert exactly 1 (its gate and up stacks get nothing);
# the decay dropped 1.30 and over (max norm, chip). fp8 reads 0.36 and 0.58 here,
# selection by s 0.35: they are (1)'s to catch.
GRAD_BOUND = 8e-1
