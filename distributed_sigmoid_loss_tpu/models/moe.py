"""Mixture-of-Experts MLP with expert parallelism over an ``ep`` mesh axis.

The reference has no model layer at all (its towers are toy Linears,
/root/reference/test_distributed_sigmoid_loss.py:71-76); MoE is part of this
framework's beyond-reference scale story — the standard way to grow tower
capacity without growing per-token FLOPs.

TPU-native design (GShard/Switch, not a torch-style loop over experts):

- **Dispatch is einsum, not gather.** Routing builds one-hot dispatch/combine
  tensors and moves tokens with group-batched einsums — dense matmuls the MXU
  executes directly, with no data-dependent shapes or scatter ops that would
  defeat XLA. Tokens route within fixed-size GROUPS (GShard's groups), so the
  static capacity ``C = ceil(k·group/E · capacity_factor)`` — and with it the
  dispatch/combine memory — is independent of the global batch.
- **Expert parallelism is a sharding annotation.** Expert kernels are stacked
  ``(E, d, h)`` and partitioned over ``ep`` (composable with ``tp`` on the hidden
  dim); under jit GSPMD turns the dispatch einsums into the all-to-alls that ship
  token slots to their expert's chip — no hand-written comm, same recipe as the
  tp all-reduces in models/transformer.py.
- **Static drop semantics.** Tokens routed past a full expert buffer contribute
  zero output (the residual connection carries them through unchanged) — the
  schedule every tick is shape-identical, which is what keeps one compiled step.
- **Router in f32.** Softmax over expert logits runs in float32 regardless of the
  activation dtype (bf16 router logits visibly perturb top-k order); the expert
  matmuls themselves stay in the model dtype.

The load-balancing auxiliary loss (Switch Transformers eq. 4: ``E · Σ_e f_e·P_e``)
is sown into the ``"intermediates"`` collection as ``"moe_aux_loss"``; training
code pulls it with ``mutable=["intermediates"]`` and adds
``moe_aux_weight · mean`` to the task loss (see train/train_step.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Mesh axis name for expert parallelism (mirrors TP_AXIS in transformer.py).
EP_AXIS = "ep"

__all__ = [
    "MoeMlp",
    "EP_AXIS",
    "router_topk",
    "build_dispatch",
    "expert_apply",
    "moe_capacity",
    "SharedExpertMoe",
    "SELECT_BIAS",
    "BALANCE",
    "MOE_ROUTE_SCOPE",
    "sigmoid_route",
    "balanced_select_bias",
    "dispatch_plan",
    "routed_experts",
]


# Pure stages of the MoE layer, factored out so a per-stage timing or test
# (tests/test_moe.py) runs EXACTLY the code the module runs.


def router_topk(xg: jax.Array, wr: jax.Array, k: int):
    """Router in f32: ``(probs, gates, idx)`` for grouped tokens ``(n, g, d)``."""
    logits = jnp.einsum("ntd,de->nte", xg.astype(jnp.float32), wr)
    probs = jax.nn.softmax(logits, axis=-1)  # (n, g, E)
    gates, idx = jax.lax.top_k(probs, k)  # (n, g, k)
    if k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return probs, gates, idx


def moe_capacity(group: int, e: int, k: int, capacity_factor: float) -> int:
    """Static per-expert buffer: ``min(group, ceil(k·group/E · cf))``."""
    return min(group, max(1, int(-(-k * group * capacity_factor // e))))


def build_dispatch(
    gates: jax.Array, idx: jax.Array, e: int, capacity: int, dtype=jnp.float32
):
    """One-hot dispatch/combine tensors from the router's top-k choices.

    Slot positions via a cumulative count in choice-major order within each
    group: every token's 1st choice outranks any token's 2nd choice (GShard's
    priority rule), and within a choice earlier tokens win — all static-shape.
    Returns ``(dispatch (n,g,E,C), combine (n,g,E,C))``.

    ``dtype`` is the OUTPUT dtype of the dispatch/combine tensors (the model
    activation dtype in the layer). The slot arithmetic — the cumulative
    count, whose values reach ``group`` and would corrupt past 256 in bf16 —
    always runs in f32; only the one-hots and gate weights, whose exact
    values (0/1 and softmax gates) bf16 carries fine, are emitted in
    ``dtype``. That halves the HBM traffic of the (tokens, E, C) tensors,
    the round-3 breakdown's "dispatch build" cost.
    """
    n_groups, group, k = idx.shape
    choice_f32 = jax.nn.one_hot(
        jnp.moveaxis(idx, -1, 1), e, dtype=jnp.float32
    )  # (n, k, g, E)
    position = (
        jnp.cumsum(choice_f32.reshape(n_groups, k * group, e), axis=1) - 1.0
    ).reshape(n_groups, k, group, e)
    slot = jnp.sum(position * choice_f32, axis=-1).astype(jnp.int32)  # (n, k, g)
    choice_onehot = choice_f32.astype(dtype)
    # Over-capacity drops come free: one_hot emits an all-zero row for any
    # slot >= capacity (out-of-range index), so no separate keep mask exists.
    slot_onehot = jax.nn.one_hot(slot, capacity, dtype=dtype)  # (n, k, g, C)
    if k == 1:
        # Switch top-1 (the headline MoE config): the (n, k, g, E, C)
        # per-choice tensor collapses — build dispatch directly and weight by
        # the single gate, skipping one 5-D einsum materialization.
        dispatch = jnp.einsum(
            "nte,ntc->ntec", choice_onehot[:, 0], slot_onehot[:, 0]
        )
        combine = dispatch * gates.astype(dtype)[..., 0][:, :, None, None]
        return dispatch, combine
    # Per-choice dispatch (n, k, g, E, C); choices land in disjoint slots so
    # the sum over k is still one-hot per (E, C) slot.
    per_choice = jnp.einsum("nkte,nktc->nktec", choice_onehot, slot_onehot)
    combine = jnp.einsum(
        "ntk,nktec->ntec", gates.astype(dtype), per_choice
    )  # gate-weighted
    dispatch = jnp.sum(per_choice, axis=1)  # (n, g, E, C)
    return dispatch, combine


def expert_apply(xg, dispatch, combine, wi, wo, dtype, quant=False):
    """Dispatch-einsum → per-expert MLP → combine-einsum (model dtype).

    ``quant="int8"`` (legacy ``True``) runs the two expert MLP matmuls in
    dynamic int8 (ops/quant.py int8_expert_matmul — inference only, like the
    dense towers' quant flag); ``quant="int8_ste"`` uses the trainable
    straight-through twin (int8 forward, unquantized VJP). Dispatch/combine
    stay in the model dtype either way (one-hot routing, <20% of layer FLOPs).
    """
    expert_in = jnp.einsum(
        "ntec,ntd->encd", dispatch.astype(dtype), xg.astype(dtype)
    )
    if quant:
        from distributed_sigmoid_loss_tpu.ops.quant import (
            int8_expert_matmul,
            int8_expert_matmul_ste,
        )

        matmul = (
            int8_expert_matmul_ste if quant == "int8_ste" else int8_expert_matmul
        )
        # Same checkpoint tag as the dense path (moot at inference, but the
        # remat policies stay total over block variants).
        hidden_act = checkpoint_name(
            matmul(expert_in, wi, dtype), "mlp_hidden"
        )
        h = nn.gelu(hidden_act, approximate=True)
        return jnp.einsum(
            "ntec,encd->ntd", combine.astype(dtype),
            matmul(h, wo, dtype),
        )
    # Same checkpoint tag as the dense Mlp (transformer.py): the save_hot /
    # save_mlp remat policies keep the expert hidden activation, so backward
    # recompute stops at the elementwise gelu for MoE blocks too.
    hidden_act = checkpoint_name(
        jnp.einsum("encd,edh->ench", expert_in, wi.astype(dtype)),
        "mlp_hidden",
    )
    h = nn.gelu(hidden_act, approximate=True)
    expert_out = jnp.einsum("ench,ehd->encd", h, wo.astype(dtype))
    return jnp.einsum("ntec,encd->ntd", combine.astype(dtype), expert_out)


class MoeMlp(nn.Module):
    """Drop-in MoE replacement for the dense transformer ``Mlp``.

    Args:
      width: model dim d.
      mlp_ratio: expert hidden dim = ``round(width * mlp_ratio)``.
      num_experts: E, total experts (shard-count over ``ep`` divides this).
      num_selected: k experts per token (1 = Switch, 2 = GShard-style top-2 with
        renormalized gates).
      capacity_factor: per-expert buffer slack over the perfectly-balanced
        ``k·T/E`` load; tokens past the buffer are dropped (residual carries them).
      dtype: activation dtype for the expert matmuls (router stays f32).
    """

    width: int
    mlp_ratio: int | float
    num_experts: int
    dtype: Any
    num_selected: int = 1
    capacity_factor: float = 1.25
    # Routing-group TARGET size (GShard "groups"): tokens route and compete for
    # capacity within fixed-size groups, so the (tokens, E, C) dispatch/combine
    # tensors stay O(tokens · E · group/E · cf) instead of O(tokens²·cf) — at
    # bench scale (50k tokens/step) single-group routing OOMs 16G HBM. The
    # actual group is the largest divisor of the token count ≤ this target.
    group_size: int = 512
    # "" | "int8" (inference) | "int8_ste" (trainable STE) expert MLP matmuls.
    quant: bool | str = False

    @nn.compact
    def __call__(self, x):
        if self.num_selected not in (1, 2):
            raise ValueError(f"num_selected must be 1 or 2, got {self.num_selected}")
        if self.num_experts < 2:
            raise ValueError(f"num_experts must be >= 2, got {self.num_experts}")
        if self.group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {self.group_size}")
        d, e, k = self.width, self.num_experts, self.num_selected
        hidden = int(round(self.width * self.mlp_ratio))
        *lead, d_in = x.shape
        assert d_in == d, f"input dim {d_in} != width {d}"
        tokens = 1
        for n in lead:
            tokens *= n
        group = max(
            g for g in range(1, min(self.group_size, tokens) + 1) if tokens % g == 0
        )
        n_groups = tokens // group
        xg = x.reshape(n_groups, group, d)

        # --- Router (f32 end-to-end) ------------------------------------------
        wr = self.param(
            "router", nn.initializers.normal(0.02), (d, e), jnp.float32
        )
        probs, gates, idx = router_topk(xg, wr, k)

        # --- Per-group capacity assignment ------------------------------------
        capacity = moe_capacity(group, e, k, self.capacity_factor)
        dispatch, combine = build_dispatch(
            gates, idx, e, capacity, dtype=self.dtype
        )

        # --- Load-balancing auxiliary loss (Switch eq. 4, over all tokens) ----
        # f_e: fraction of tokens whose first choice is e; P_e: mean router prob.
        first_choice = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
        aux = e * jnp.sum(
            jnp.mean(first_choice, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1))
        )
        self.sow("intermediates", "moe_aux_loss", aux)

        # --- Expert compute (model dtype; E sharded over ep) ------------------
        # Each expert processes its n_groups · C slots in one batched matmul.
        wi = self.param(
            "wi",
            nn.with_partitioning(
                nn.initializers.xavier_uniform(), (EP_AXIS, None, "tp")
            ),
            (e, d, hidden),
            jnp.float32,
        )
        wo = self.param(
            "wo",
            nn.with_partitioning(
                nn.initializers.xavier_uniform(), (EP_AXIS, "tp", None)
            ),
            (e, hidden, d),
            jnp.float32,
        )
        y = expert_apply(
            xg, dispatch, combine, wi, wo, self.dtype, quant=self.quant
        )
        return y.reshape(*lead, d)


# -- sigmoid-routed, dropless, with a shared expert and a chip's share ---------
#
# The routed layer of the latent-attention language models (``moe_router =
# "sigmoid"``). With x one token, E experts, k chosen:
#
#     s = sigmoid(x Wr) in R^E ;  I = top_k(s + b) ;  w_i = scale * s_i / sum_{j in I} s_j
#     y = Shared(x) + sum_{i in I, i held here} w_i E_i(x) ;  E(x) = (silu(x Wg) * (x Wu)) Wd
#                                                        or, ungated ("relu2"), E(x) = relu(x Wu)^2 Wd
#
# ``b`` decides the selection only (SELECT_BIAS: no gradient reaches it, and
# train/train_step.py gives it no decay and no optimizer state). A chip holds
# experts [first_held, first_held + held): the router scores all E, and what
# the absent experts would add is left out. Nothing is dropped: the assignments
# to held experts are sorted by expert, and each expert runs its own segment in
# blocks of rows, as many blocks as its load needs (one loop over all of them,
# whose trip count the device reads). The rows are bounded by T * k, the true worst case, and
# only the index arrays have that size: tokens are gathered a block at a time.
#
# The float32 sums the loops scatter a block's rows into (y forward, dx backward)
# are carried as (T, n, 8, 128), n = ceil(d / 1024), a token's row in n whole
# tiles with zeros after lane d, and become (T, d) once, after the loop. The chip
# keeps float32 in (8, 128) tiles: of a 2-D (T, d) sum a tile holds 128 lanes of
# EIGHT tokens, and the compiler's scatter reads and writes all eight rows to add
# one (0.23-0.35 ms a block of 512 rows: PERF.md section 6, PR 45). With the token
# on the leading axis a scattered row moves its own tiles. The additions and their
# order are a 2-D sum's, bit for bit (tests/test_moe.py keeps that form as oracle).
#
# Outside the loops the plan indexes no array by a computed position. The chip
# takes 7-10 ns an ELEMENT to gather from, or scatter-add into, a 1-D array at
# computed places (0.5-1.35 ms for the T * k = 65536-131072 assignments) and
# sorts the same elements, two operands carried along, in 0.06-0.14 ms (PERF.md
# section 6, PR 48). So what the permutation must move, the sort moves: one stable
# sort by expert carries each assignment's position and weight, a sort by position
# takes the weights' cotangent back, the chosen scores are a masked sum over E
# (one dense pass over (T, k, E), dense backwards too) and the segments' edges are
# counted. The values and their places are the indexed form's, bit for bit, forward
# and backward: no sum here ever adds two non-zero terms (tests/test_moe.py keeps
# that form as oracle, and reads the jaxprs for a gather or a scatter).

SELECT_BIAS = "select_bias"
# The collection whose being mutable makes a pass the initialisation's balancing
# pass (``TextConfig.moe_balanced_init``, train/train_step.py balance_routers):
# each routed layer then sets its selection bias from the tokens it is given by
# :func:`balanced_select_bias`, routes by it and sows it there.
BALANCE = "balance"
# The program's name for everything of the layer but the expert and shared
# products: scores, selection, the sorts, the loops' row gathers and scatters.
MOE_ROUTE_SCOPE = "moe_route"
# Rows of one block of an expert's segment: a held expert of the cell sees 512
# tokens a microbatch, so a balanced expert is one block and one pair of products.
BLOCK_ROWS = 512
F32 = jnp.float32


def router_scores(x, wr):
    """sigmoid(x Wr) of ``(T, E)``: float32 at full matmul precision."""
    return jax.nn.sigmoid(
        jnp.dot(x.astype(F32), wr, precision=jax.lax.Precision.HIGHEST)
    )


def balanced_select_bias(scores, k: int, rounds: int = 20):
    """The selection bias ``(E,)`` under which every expert is among the ``k``
    best of ``T * k / E`` of the ``T`` tokens whose ``scores`` (T, E) these are:
    what the recipe's balancing update holds a trained router at, found here at
    once. A round sets each expert's bias to what gives it just that many tokens
    while the others keep theirs: per token the bias the expert needs to be
    chosen (the token's k-th best without it, less its own score), and of those
    the ``T * k / E``-th smallest."""
    tokens, experts = scores.shape
    if k >= experts:  # every token takes every expert
        return jnp.zeros((experts,), F32)
    target = max(1, tokens * k // experts)

    def one_round(_, bias):
        biased = scores + bias
        best, _ = jax.lax.top_k(biased, k + 1)
        kth, following = best[:, k - 1:k], best[:, k:k + 1]
        to_beat = jnp.where(biased >= kth, following, kth)
        return jnp.sort(to_beat - scores, axis=0)[target - 1]

    return jax.lax.fori_loop(0, rounds, one_round, jnp.zeros((experts,), F32))


def _chosen(scores, idx):
    """``scores`` (T, E) at ``idx`` (T, k), as a masked sum over E: one term of
    each sum is the score and the others are 0.0, so the sum is the score to the
    bit; backwards a token's k cotangents go to k different experts, so nothing
    is added there either. One fused pass over (T, k, E) and no gather (the note
    above the layer). The barrier keeps the caller's sum over k out of the pass:
    the compiler would fold the two into one sum over (k, E), which adds a token's
    scores in another order (weights off in the last bit) and runs slower."""
    experts = jnp.arange(scores.shape[-1], dtype=idx.dtype)
    chosen = jnp.sum(jnp.where(idx[..., None] == experts, scores[:, None, :], 0.0), -1)
    return jax.lax.optimization_barrier(chosen)


def sigmoid_route(x, wr, select_bias, k: int, scale: float):
    """``(idx, weights)`` of ``(T, k)`` for tokens ``x`` (T, d): float32 scores
    at full matmul precision, selection by score + bias, weights by score (the
    chosen scores by :func:`_chosen`, not by index)."""
    scores = router_scores(x, wr)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(select_bias), k)
    chosen = _chosen(scores, idx)
    return idx, scale * chosen / jnp.sum(chosen, -1, keepdims=True)


@jax.custom_vjp
def _sort_by_key(key, values):
    """``(sorted key, order, values[order])`` for ``key`` and ``values`` of
    ``(n,)``, ``order`` the stable argsort: ONE sort that carries each element's
    position and value along, so nothing is indexed by ``order`` afterwards."""
    position = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, position, values), num_keys=1, is_stable=True)


def _sort_by_key_fwd(key, values):
    out = _sort_by_key(key, values)
    return out, out[1]


def _sort_by_key_bwd(order, cts):
    """``order`` is a permutation: sorted by it, every cotangent is back at its
    position, and none is added to another (what the sort's own rule, a gather
    transposed, does by a scatter-add: the same values)."""
    return None, jax.lax.sort((order, cts[2]), num_keys=1)[1]


_sort_by_key.defvjp(_sort_by_key_fwd, _sort_by_key_bwd)


def dispatch_plan(idx, weights, first: int, held: int):
    """The assignments to experts ``[first, first + held)`` sorted by expert:
    ``(token, row_weight, starts, counts)``, the first two ``(T * k,)`` (rows
    past ``starts[-1] + counts[-1]`` belong to absent experts), the last two
    ``(held,)``: expert e's rows are ``starts[e] : starts[e] + counts[e]``.
    The sort moves what the plan needs (:func:`_sort_by_key`) and the segments'
    edges are counted, not searched: no array is indexed by a computed position."""
    k = idx.shape[-1]
    local = idx - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    sorted_key, order, row_weight = _sort_by_key(key, weights.reshape(-1))
    edges = jnp.searchsorted(sorted_key, jnp.arange(held + 1, dtype=key.dtype), method="compare_all")
    return (
        (order // k).astype(jnp.int32), row_weight,
        edges[:-1].astype(jnp.int32), jnp.diff(edges).astype(jnp.int32),
    )


def _block_plan(starts, counts, block: int):
    """The held experts' segments cut into blocks of ``block`` rows, expert after
    expert: ``(blocks in all, first block of each expert)``."""
    per_expert = -(-counts // block)
    first = jnp.cumsum(per_expert) - per_expert
    return jnp.sum(per_expert), first


def _block_rows(i, first, block, token, row_weight, starts, counts, tokens):
    """Block ``i`` of the plan: its expert, its rows, their tokens and weights,
    and where to scatter (a row past its segment's end scatters out of range,
    which drops it, and weighs nothing)."""
    e = jnp.sum(first <= i) - 1  # the last expert whose first block is not after i
    rows = starts[e] + (i - first[e]) * block + jnp.arange(block, dtype=jnp.int32)
    valid = rows < starts[e] + counts[e]
    rows = jnp.where(valid, rows, 0)
    tok = token[rows]
    return e, rows, valid, tok, jnp.where(valid, tok, tokens), jnp.where(valid, row_weight[rows], 0.0)


def _gather_rows(x, tok):
    with jax.named_scope(MOE_ROUTE_SCOPE):
        return x[tok]


TILE = (8, 128)  # sublanes x lanes of one float32 tile


def _row_tiles(d: int):
    """``(n, padded width)``: the whole tiles a ``d``-wide float32 row takes."""
    n = -(-d // (TILE[0] * TILE[1]))
    return n, n * TILE[0] * TILE[1]


def _zero_sum(tokens: int, d: int):
    """The float32 sum over ``tokens`` rows of ``d``, a row in whole tiles."""
    with jax.named_scope(MOE_ROUTE_SCOPE):
        return jnp.zeros((tokens, _row_tiles(d)[0], *TILE), F32)


def _scatter_add_rows(acc, to, rows):
    """``rows`` (block, d) float32 added into ``acc`` (T, n, 8, 128) at tokens
    ``to`` (one past the end drops a row): the rows are padded to whole tiles,
    so the scatter moves each token's own tiles and not its seven neighbours'
    (the note above the layer)."""
    with jax.named_scope(MOE_ROUTE_SCOPE):
        d = rows.shape[-1]
        tiles = jnp.pad(rows, ((0, 0), (0, _row_tiles(d)[1] - d))).reshape(-1, *acc.shape[1:])
        return acc.at[to].add(tiles, mode="drop")


def _sum_rows(acc, d: int, dtype):
    """The sum as ``(T, d)`` of ``dtype``, after the loop; cast first, on the
    tiles, where the compiler puts the cast anyway (under its own name and no scope)."""
    with jax.named_scope(MOE_ROUTE_SCOPE):
        return acc.astype(dtype).reshape(acc.shape[0], -1)[:, :d]


def _expert_mlp(xb, stacks, e, dt):
    """One block through expert ``e`` of the stacked weights: ``(gate, up,
    down)`` for a SwiGLU expert, ``(up, down)`` for a relu2 one (the kind is
    what the stacks are). Float32 products, the hidden activation and the
    output, with what the backward needs of them."""
    ws = tuple(jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False).astype(dt) for w in stacks)
    if len(ws) == 2:  # relu2: down(relu(up x)^2)
        up = jnp.dot(xb, ws[0], preferred_element_type=F32)
        hidden = jnp.square(jax.nn.relu(up)).astype(dt)
        return ws, (up, hidden), jnp.dot(hidden, ws[1], preferred_element_type=F32)
    wg_e, wu_e, wd_e = ws
    gate = jnp.dot(xb, wg_e, preferred_element_type=F32)
    up = jnp.dot(xb, wu_e, preferred_element_type=F32)
    sig = jax.nn.sigmoid(gate)
    hidden = (gate * sig * up).astype(dt)
    return ws, (gate, up, sig, hidden), jnp.dot(hidden, wd_e, preferred_element_type=F32)


def _expert_mlp_bwd(xb, ws, kept, dyw, dt):
    """The expert's chain backwards from the weighted cotangent ``dyw`` of its
    output: ``(dxb, products)``, the block's input cotangent (float32) and, per
    stack, the two operands (a, b) whose a^T b is the stack's weight gradient."""
    d_hidden = jnp.dot(dyw, ws[-1].T, preferred_element_type=F32)
    if len(ws) == 2:
        up, hidden = kept
        d_up = (d_hidden * 2.0 * jax.nn.relu(up)).astype(dt)
        return jnp.dot(d_up, ws[0].T, preferred_element_type=F32), ((xb, d_up), (hidden, dyw))
    wg_e, wu_e, _ = ws
    gate, up, sig, hidden = kept
    d_up = (d_hidden * gate * sig).astype(dt)
    d_gate = (d_hidden * up * sig * (1.0 + gate * (1.0 - sig))).astype(dt)
    dxb = jnp.dot(d_gate, wg_e.T, preferred_element_type=F32) + jnp.dot(
        d_up, wu_e.T, preferred_element_type=F32
    )
    return dxb, ((xb, d_gate), (xb, d_up), (hidden, dyw))


def _routed_forward(x, stacks, token, row_weight, starts, counts, block):
    tokens, dt = x.shape[0], x.dtype
    total, first = _block_plan(starts, counts, block)

    def step(i, carry):
        y, done = carry
        e, _, valid, tok, to, wts = _block_rows(i, first, block, token, row_weight, starts, counts, tokens)
        _, _, out = _expert_mlp(_gather_rows(x, tok), stacks, e, dt)
        return _scatter_add_rows(y, to, out * wts[:, None]), done + jnp.sum(valid, dtype=jnp.int32)

    y, done = jax.lax.fori_loop(0, total, step, (_zero_sum(*x.shape), jnp.zeros((), jnp.int32)))
    return _sum_rows(y, x.shape[1], dt), done


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def routed_experts(x, stacks, token, row_weight, starts, counts, block):
    """``(y, rows_done)``: the held experts' weighted part of the layer's output
    for tokens ``x`` (T, d), following :func:`dispatch_plan`, ``block`` rows at
    a time, one loop over every expert's blocks whose trip count the device
    reads; ``rows_done`` counts the assignments that ran (all of them).
    ``stacks`` are the held experts' stacked weights and say the experts' kind:
    ``(gate, up, down)`` SwiGLU, ``(up, down)`` relu2."""
    return _routed_forward(x, stacks, token, row_weight, starts, counts, block)


def _routed_experts_fwd(x, stacks, token, row_weight, starts, counts, block):
    out = _routed_forward(x, stacks, token, row_weight, starts, counts, block)
    return out, (x, stacks, token, row_weight, starts, counts)


def _routed_experts_bwd(block, saved, cts):
    """The same loop backwards, each block's forward recomputed; the weight
    gradients ride it in float32, a block adding into its expert's row."""
    x, stacks, token, row_weight, starts, counts = saved
    dy = cts[0]
    tokens, dt = x.shape[0], x.dtype
    total, first = _block_plan(starts, counts, block)

    def add_row(acc, e, g):
        return jax.lax.dynamic_update_index_in_dim(
            acc, jax.lax.dynamic_index_in_dim(acc, e, 0, keepdims=False) + g, e, 0
        )

    def step(i, carry):
        dx, d_weight, *grads = carry
        e, rows, valid, tok, to, wts = _block_rows(i, first, block, token, row_weight, starts, counts, tokens)
        xb = _gather_rows(x, tok)
        ws, kept, out = _expert_mlp(xb, stacks, e, dt)
        dyb = jnp.where(valid[:, None], _gather_rows(dy, tok).astype(F32), 0.0)
        # a block's rows are consecutive from its first, so a slice is written and not a scatter
        old = jax.lax.dynamic_slice_in_dim(d_weight, rows[0], block)
        d_weight = jax.lax.dynamic_update_slice_in_dim(
            d_weight, jnp.where(valid, jnp.sum(dyb * out, -1), old), rows[0], 0
        )
        dyw = (dyb * wts[:, None]).astype(dt)
        dxb, products = _expert_mlp_bwd(xb, ws, kept, dyw, dt)
        return (
            _scatter_add_rows(dx, to, dxb), d_weight,
            *(add_row(g, e, jnp.dot(a.T, b, preferred_element_type=F32)) for g, (a, b) in zip(grads, products)),
        )

    # d_weight is one block longer than the rows, so that the last block's slice fits whatever its tail
    zeros = (
        _zero_sum(*x.shape), jnp.zeros((row_weight.shape[0] + block,), F32),
        *(jnp.zeros(w.shape, F32) for w in stacks),
    )
    dx, d_weight, *grads = jax.lax.fori_loop(0, total, step, zeros)
    return (
        _sum_rows(dx, x.shape[1], dt), tuple(g.astype(w.dtype) for g, w in zip(grads, stacks)),
        None, d_weight[: row_weight.shape[0]].astype(row_weight.dtype), None, None,
    )


routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


class SharedExpertMoe(nn.Module):
    """The sigmoid-routed layer above: ``num_experts`` routed bias-free experts
    ``hidden`` wide, ``num_selected`` a token, ``shared_experts`` more that
    every token runs (as one MLP ``shared_hidden`` wide, 0 = ``shared_experts x
    hidden``), and of the routed ones ``experts_held`` (0 = all) here, from
    ``first_held`` on. ``kind`` is every expert's, the shared one's too:
    ``"swiglu"``, down(silu(gate x) * (up x)), three stacks; ``"relu2"``,
    down(relu(up x)^2), two (no ``wg``). Sows ``moe_load`` into
    ``"intermediates"``: the held experts' token counts and the assignments
    that did not run (0)."""

    width: int
    hidden: int
    num_experts: int
    num_selected: int
    dtype: Any
    route_scale: float = 1.0
    shared_experts: int = 0
    experts_held: int = 0
    first_held: int = 0
    kind: str = "swiglu"  # "swiglu" | "relu2"
    shared_hidden: int = 0

    @nn.compact
    def __call__(self, x):
        from distributed_sigmoid_loss_tpu.models.transformer import Mlp

        d, e, k = self.width, self.num_experts, self.num_selected
        held = self.experts_held or e
        if self.kind not in ("swiglu", "relu2"):
            raise ValueError(f"unknown expert kind: {self.kind!r} (want 'swiglu' or 'relu2')")
        if not 0 < k <= e or not 0 <= self.first_held <= e - held:
            raise ValueError(
                f"moe_num_selected={k}, moe_experts_held={held} (from {self.first_held}) "
                f"do not fit moe_experts={e}"
            )
        lead = x.shape[:-1]
        xt = x.reshape(-1, d)
        block = min(BLOCK_ROWS, xt.shape[0])
        with jax.named_scope(MOE_ROUTE_SCOPE):
            wr = self.param("router", nn.initializers.normal(0.02), (d, e), F32)
            select_bias = self.param(SELECT_BIAS, nn.initializers.zeros, (e,), F32)
            if self.is_mutable_collection(BALANCE) and not self.is_initializing():
                select_bias = balanced_select_bias(router_scores(xt, wr), k)
                self.sow(BALANCE, SELECT_BIAS, select_bias)
            idx, weights = sigmoid_route(xt, wr, select_bias, k, self.route_scale)
            token, row_weight, starts, counts = dispatch_plan(idx, weights, self.first_held, held)
        per_expert = nn.initializers.variance_scaling(1.0, "fan_avg", "uniform", batch_axis=(0,))
        stacks = () if self.kind == "relu2" else (self.param("wg", per_expert, (held, d, self.hidden), F32),)
        stacks += (
            self.param("wi", per_expert, (held, d, self.hidden), F32),
            self.param("wo", per_expert, (held, self.hidden, d), F32),
        )
        y, done = routed_experts(xt, stacks, token, row_weight, starts, counts, block)
        self.sow("intermediates", "moe_load", {"tokens": counts, "dropped": jnp.sum(counts) - done})
        y = y.reshape(*lead, d)
        if self.shared_experts:
            y = y + Mlp(
                d, (self.shared_hidden or self.shared_experts * self.hidden) / d, self.dtype,
                kind=self.kind, use_bias=False, name="shared",
            )(x)
        return y
