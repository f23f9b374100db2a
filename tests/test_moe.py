"""Mixture-of-Experts MLP (expert parallelism over ``ep``) — parity oracles.

Oracle pattern follows SURVEY.md §4: the einsum-dispatched MoE must equal the
obvious per-token computation (select expert, run its MLP, weight by the gate)
whenever capacity is ample; capacity drops must zero exactly the over-quota
tokens; and the ep-sharded run must match the single-device one.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_sigmoid_loss_tpu.analysis import jaxpr_audit
from distributed_sigmoid_loss_tpu.models import moe as moe_lib
from distributed_sigmoid_loss_tpu.models.moe import MoeMlp
from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh


def _setup(num_selected=1, T=16, d=8, E=4, capacity_factor=8.0, seed=0):
    m = MoeMlp(
        width=d, mlp_ratio=2, num_experts=E, dtype=jnp.float32,
        num_selected=num_selected, capacity_factor=capacity_factor,
    )
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, T // 2, d)), jnp.float32)
    params = nn.meta.unbox(m.init(jax.random.key(seed), x)["params"])
    return m, params, x


def _expert_mlp(params, i, xv):
    h = nn.gelu(xv @ params["wi"][i], approximate=True)
    return h @ params["wo"][i]


def _dense_reference(params, x, num_selected):
    """Per-token top-k expert compute — the semantics the einsum dispatch encodes."""
    xt = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(xt @ params["router"], axis=-1)
    gates, idx = jax.lax.top_k(probs, num_selected)
    if num_selected > 1:
        gates = gates / gates.sum(-1, keepdims=True)
    out = jnp.stack([
        sum(
            gates[t, j] * _expert_mlp(params, idx[t, j], xt[t])
            for j in range(num_selected)
        )
        for t in range(xt.shape[0])
    ])
    return out.reshape(x.shape)


@pytest.mark.parametrize("num_selected", [1, 2])
def test_moe_matches_dense_per_token(num_selected):
    m, params, x = _setup(num_selected)
    y, _ = m.apply({"params": params}, x, mutable=["intermediates"])
    want = _dense_reference(params, x, num_selected)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_moe_capacity_drops_over_quota_tokens():
    """With capacity_factor forcing C=1, only the first token routed to each expert
    produces output; later ones drop to exactly zero (residual carries them)."""
    T, d, E = 8, 8, 2
    m = MoeMlp(
        width=d, mlp_ratio=2, num_experts=E, dtype=jnp.float32,
        capacity_factor=1.0 / (T / E),  # k*T/E * cf = 1 slot per expert
    )
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, T, d)), jnp.float32)
    params = nn.meta.unbox(m.init(jax.random.key(3), x)["params"])
    y, _ = m.apply({"params": params}, x, mutable=["intermediates"])

    xt = x.reshape(T, d)
    probs = jax.nn.softmax(xt @ params["router"], axis=-1)
    idx = np.asarray(jnp.argmax(probs, -1))
    gate = np.asarray(jnp.max(probs, -1))
    seen = set()
    for t in range(T):
        if idx[t] not in seen:  # first arrival: served
            seen.add(idx[t])
            want = gate[t] * _expert_mlp(params, idx[t], xt[t])
            np.testing.assert_allclose(
                np.asarray(y[0, t]), np.asarray(want), rtol=1e-5, atol=1e-6
            )
        else:  # over quota: dropped to zero
            np.testing.assert_array_equal(np.asarray(y[0, t]), 0.0)


def test_moe_aux_loss_balanced_routing_is_one():
    """Uniform router probs + all-to-one-expert argmax ties give the Switch aux
    loss its reference values: E·Σ f_e·P_e = 1 at perfect balance."""
    d, E = 8, 4
    m = MoeMlp(width=d, mlp_ratio=2, num_experts=E, dtype=jnp.float32)
    x = jnp.ones((1, 8, d), jnp.float32)
    params = nn.meta.unbox(m.init(jax.random.key(0), x)["params"])
    # Zero router => uniform probs (P_e = 1/E); argmax ties resolve to expert 0
    # (f = onehot(0)), so aux = E * (1 * 1/E) = 1.
    params = dict(params, router=jnp.zeros_like(params["router"]))
    _, state = m.apply({"params": params}, x, mutable=["intermediates"])
    (aux,) = state["intermediates"]["moe_aux_loss"]
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)


def test_moe_sharded_matches_single_device():
    """Experts sharded over a 4-device ep mesh: same outputs and gradients as the
    unsharded run (the all-to-alls GSPMD inserts are semantics-free)."""
    mesh = make_mesh(4, "ep")
    m, params, x = _setup(T=32, E=4)

    def loss(p, x):
        y, _ = m.apply({"params": p}, x, mutable=["intermediates"])
        return jnp.sum(y**2)

    want_loss = loss(params, x)
    want_grads = jax.grad(loss)(params, x)

    shardings = {
        "router": NamedSharding(mesh, P()),
        "wi": NamedSharding(mesh, P("ep")),
        "wo": NamedSharding(mesh, P("ep")),
    }
    params_s = jax.device_put(params, shardings)
    x_s = jax.device_put(x, NamedSharding(mesh, P()))
    got_loss = jax.jit(loss)(params_s, x_s)
    got_grads = jax.jit(jax.grad(loss))(params_s, x_s)

    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(got_grads[k]), np.asarray(want_grads[k]),
            rtol=1e-4, atol=1e-5,
        )


def test_moe_validates_args():
    x = jnp.zeros((1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="num_selected"):
        MoeMlp(width=8, mlp_ratio=2, num_experts=4, dtype=jnp.float32,
               num_selected=3).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="num_experts"):
        MoeMlp(width=8, mlp_ratio=2, num_experts=1, dtype=jnp.float32).init(
            jax.random.key(0), x
        )


def test_moe_train_step_end_to_end():
    """Full SigLIP train step with MoE towers over a (dp=2, ep=4) mesh: loss and
    aux finite, moe_aux reported, and the misconfiguration (aux weight without
    MoE towers) raises clearly."""
    import dataclasses

    from jax.sharding import Mesh

    from distributed_sigmoid_loss_tpu.models import SigLIP
    from distributed_sigmoid_loss_tpu.train import (
        create_train_state,
        make_optimizer,
        make_train_step,
    )
    from distributed_sigmoid_loss_tpu.utils.config import (
        LossConfig,
        SigLIPConfig,
        TrainConfig,
    )

    cfg = SigLIPConfig.tiny_test()
    cfg = dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, moe_experts=4),
        text=dataclasses.replace(cfg.text, moe_experts=4, moe_num_selected=2),
    )
    model = SigLIP(cfg)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("dp", "ep"))
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(rng.standard_normal((8, 16, 16, 3)), jnp.float32),
        "tokens": jnp.asarray(rng.integers(0, 64, (8, 8)), jnp.int32),
    }
    tx = make_optimizer(TrainConfig(warmup_steps=1, total_steps=10))
    state = create_train_state(jax.random.key(0), model, tx, batch, mesh)
    step, shardings = make_train_step(
        model, mesh, LossConfig(variant="ring"), moe_aux_weight=0.01
    )
    batch = jax.device_put(batch, shardings)
    _, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["moe_aux"]))

    plain = SigLIP(SigLIPConfig.tiny_test())
    state_p = create_train_state(jax.random.key(0), plain, tx, batch, mesh)
    step_p, _ = make_train_step(
        plain, mesh, LossConfig(variant="ring"), moe_aux_weight=0.01
    )
    with pytest.raises(ValueError, match="sowed no moe_aux_loss"):
        step_p(state_p, batch)


def test_moe_scanned_remat_encoder_aux_and_grads():
    """The production encoder path (scan_layers=True + remat + save_hot) with MoE:
    sown aux leaves ride nn.scan with a leading depth axis, gradients reach the
    routers, and the remat'd values match the unremat'd ones."""
    from distributed_sigmoid_loss_tpu.models.transformer import Encoder

    def build(remat, remat_policy="save_hot"):
        return Encoder(
            width=16, depth=4, num_heads=2, mlp_ratio=2, dtype=jnp.float32,
            remat=remat, scan_layers=True, remat_policy=remat_policy,
            moe_experts=4,
        )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    enc = build(remat=True)
    params = nn.meta.unbox(enc.init(jax.random.key(0), x)["params"])

    def loss(p, model):
        y, variables = model.apply({"params": p}, x, mutable=["intermediates"])
        leaves = jax.tree.leaves(variables["intermediates"])
        assert leaves and leaves[0].shape[0] == 4  # (depth,) scan axis
        return jnp.sum(y**2), leaves[0]

    (val, aux), grads = jax.value_and_grad(
        lambda p: loss(p, enc), has_aux=True
    )(params)
    assert np.isfinite(float(val))
    assert np.isfinite(np.asarray(aux)).all()
    router_grad = grads["blocks"]["block"]["moe"]["router"]
    assert float(jnp.abs(router_grad).max()) > 0.0

    # Remat must not change the math.
    (val_nr, _), grads_nr = jax.value_and_grad(
        lambda p: loss(p, build(remat=False)), has_aux=True
    )(params)
    np.testing.assert_allclose(float(val), float(val_nr), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["blocks"]["block"]["moe"]["router"]),
        np.asarray(grads_nr["blocks"]["block"]["moe"]["router"]),
        rtol=1e-4, atol=1e-6,
    )


def test_moe_grouped_routing_matches_dense():
    """Tokens route within groups (T=64 over groups of 16): with ample per-group
    capacity the result still equals the per-token dense computation, and slot
    competition stays inside each group."""
    d, E, T = 8, 4, 64
    m = MoeMlp(
        width=d, mlp_ratio=2, num_experts=E, dtype=jnp.float32,
        capacity_factor=8.0, group_size=16,
    )
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, T // 4, d)), jnp.float32)
    params = nn.meta.unbox(m.init(jax.random.key(7), x)["params"])
    y, _ = m.apply({"params": params}, x, mutable=["intermediates"])
    want = _dense_reference(params, x, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5, atol=1e-6)


def _dispatch_reference(gates, idx, e, capacity):
    """Straight-line numpy oracle of the GShard slot assignment: choice-major
    priority within each group, earlier tokens win, over-capacity dropped."""
    n, g, k = idx.shape
    dispatch = np.zeros((n, g, e, capacity), np.float32)
    combine = np.zeros((n, g, e, capacity), np.float32)
    for ni in range(n):
        counts = np.zeros(e, np.int64)
        for kj in range(k):
            for t in range(g):
                ex = int(idx[ni, t, kj])
                slot = counts[ex]
                counts[ex] += 1
                if slot < capacity:
                    dispatch[ni, t, ex, slot] = 1.0
                    combine[ni, t, ex, slot] = float(gates[ni, t, kj])
    return dispatch, combine


@pytest.mark.parametrize("k", [1, 2])
def test_build_dispatch_matches_numpy_oracle(k):
    """Covers BOTH code paths: the k=1 fast path (no 5-D per-choice tensor)
    and the general top-k einsum path, against an independent slot-assignment
    oracle — including over-capacity drops."""
    from distributed_sigmoid_loss_tpu.models.moe import build_dispatch

    rng = np.random.default_rng(5)
    n, g, e, capacity = 3, 12, 4, 3  # tight capacity: drops occur
    idx = rng.integers(0, e, (n, g, k))
    if k > 1:  # distinct experts per token, as top_k guarantees
        idx[..., 1] = (idx[..., 0] + 1 + rng.integers(0, e - 1, (n, g))) % e
    gates = rng.random((n, g, k)).astype(np.float32)
    d_ref, c_ref = _dispatch_reference(gates, idx, e, capacity)
    d, c = build_dispatch(
        jnp.asarray(gates), jnp.asarray(idx), e, capacity
    )
    np.testing.assert_array_equal(np.asarray(d), d_ref)
    np.testing.assert_allclose(np.asarray(c), c_ref, rtol=1e-6)


def test_build_dispatch_bf16_keeps_f32_routing():
    """dtype=bfloat16 emits bf16 tensors but must make the IDENTICAL routing
    decisions (the slot arithmetic stays f32 — values reach `group`, which
    bf16 would corrupt past 256): the dispatch one-hots are bitwise equal and
    the combine weights differ only by bf16 rounding of the gates."""
    from distributed_sigmoid_loss_tpu.models.moe import build_dispatch

    rng = np.random.default_rng(6)
    n, g, e, k = 2, 512, 4, 1  # group 512 > 256: the bf16-corruptible regime
    idx = rng.integers(0, e, (n, g, k))
    gates = rng.random((n, g, k)).astype(np.float32)
    capacity = 160  # some drops
    d32, c32 = build_dispatch(jnp.asarray(gates), jnp.asarray(idx), e, capacity)
    d16, c16 = build_dispatch(
        jnp.asarray(gates), jnp.asarray(idx), e, capacity, dtype=jnp.bfloat16
    )
    assert d16.dtype == jnp.bfloat16 and c16.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(d16, np.float32), np.asarray(d32)
    )
    np.testing.assert_allclose(
        np.asarray(c16, np.float32), np.asarray(c32), rtol=1e-2, atol=1e-3
    )


# -- the sigmoid-routed layer's loops (routed_experts): the tile-aligned sums ----
#
# routed_experts carries its float32 sums (y forward, dx backward) as
# (T, n, 8, 128), a token's row in whole tiles, and writes d_weight by slices.
# The oracle below is the loop as it was before: a 2-D (T, d) sum, a scatter-add
# of a block's rows into it, d_weight by a scatter. Same additions, same order,
# one rounding at the same place: everything is equal bit for bit.

F32 = jnp.float32


def _oracle_forward(x, stacks, token, row_weight, starts, counts, block):
    tokens, dt = x.shape[0], x.dtype
    total, first = moe_lib._block_plan(starts, counts, block)

    def step(i, y):
        e, _, _, tok, to, wts = moe_lib._block_rows(i, first, block, token, row_weight, starts, counts, tokens)
        _, _, out = moe_lib._expert_mlp(x[tok], stacks, e, dt)
        return y.at[to].add(out * wts[:, None], mode="drop")

    return jax.lax.fori_loop(0, total, step, jnp.zeros(x.shape, F32)).astype(dt)


def _oracle_backward(x, stacks, token, row_weight, starts, counts, block, dy):
    tokens, dt = x.shape[0], x.dtype
    total, first = moe_lib._block_plan(starts, counts, block)

    def add_row(acc, e, g):
        return jax.lax.dynamic_update_index_in_dim(
            acc, jax.lax.dynamic_index_in_dim(acc, e, 0, keepdims=False) + g, e, 0
        )

    def step(i, carry):
        dx, d_weight, *grads = carry
        e, rows, valid, tok, to, wts = moe_lib._block_rows(i, first, block, token, row_weight, starts, counts, tokens)
        xb = x[tok]
        ws, kept, out = moe_lib._expert_mlp(xb, stacks, e, dt)
        dyb = jnp.where(valid[:, None], dy[tok].astype(F32), 0.0)
        d_weight = d_weight.at[jnp.where(valid, rows, d_weight.shape[0])].set(jnp.sum(dyb * out, -1), mode="drop")
        dyw = (dyb * wts[:, None]).astype(dt)
        dxb, products = moe_lib._expert_mlp_bwd(xb, ws, kept, dyw, dt)
        return (
            dx.at[to].add(dxb, mode="drop"), d_weight,
            *(add_row(g, e, jnp.dot(a.T, b, preferred_element_type=F32)) for g, (a, b) in zip(grads, products)),
        )

    zeros = (jnp.zeros(x.shape, F32), jnp.zeros(row_weight.shape, F32), *(jnp.zeros(w.shape, F32) for w in stacks))
    dx, d_weight, *grads = jax.lax.fori_loop(0, total, step, zeros)
    return dx.astype(dt), tuple(g.astype(w.dtype) for g, w in zip(grads, stacks)), d_weight.astype(row_weight.dtype)


ROUTED_T, ROUTED_BLOCK, ROUTED_HIDDEN = 80, 32, 16


@pytest.fixture(scope="module", params=[(1024, "swiglu"), (1024, "relu2"), (1152, "swiglu"), (1152, "relu2"),
                                        (2688, "swiglu"), (2688, "relu2")], ids=lambda p: f"d{p[0]}-{p[1]}")
def routed_pair(request):
    """``(got, want, plan)`` at one width and kind: routed_experts and its
    gradients beside the 2-D oracle's, bf16 tokens as the cells have them. Eight
    experts, three a token, held here 2..5: expert 5 is never chosen (empty),
    expert 2 by every second token (40 rows: a block of 32 and a ragged one of 8)."""
    d, kind = request.param
    k = jax.random.split(jax.random.key(d), 8)
    t, e, held, first, top = ROUTED_T, 8, 4, 2, 3
    x = jax.random.normal(k[0], (t, d)).astype(jnp.bfloat16)
    shapes = ([(held, d, ROUTED_HIDDEN)] if kind == "swiglu" else []) + [(held, d, ROUTED_HIDDEN), (held, ROUTED_HIDDEN, d)]
    stacks = tuple(jax.random.normal(kk, s) * s[1] ** -0.5 for kk, s in zip(k[1:4], shapes))
    others = jnp.stack([jax.random.permutation(kk, jnp.asarray([0, 1, 3, 4, 6, 7]))[:top] for kk in jax.random.split(k[4], t)])
    idx = jnp.where((jnp.arange(t) % 2 == 0)[:, None] & (jnp.arange(top) == 0), 2, others)
    weights = jax.random.uniform(k[5], (t, top)) + 0.2
    dy = jax.random.normal(k[6], (t, d)).astype(jnp.bfloat16)
    token, row_weight, starts, counts = moe_lib.dispatch_plan(idx, weights, first, held)

    def routed(x, stacks, row_weight):
        return moe_lib.routed_experts(x, stacks, token, row_weight, starts, counts, ROUTED_BLOCK)

    (y, done), back = jax.vjp(routed, x, stacks, row_weight)
    dx, d_stacks, d_weight = back((dy, jnp.zeros((), jax.dtypes.float0)))
    want_y = _oracle_forward(x, stacks, token, row_weight, starts, counts, ROUTED_BLOCK)
    want_dx, want_stacks, want_weight = _oracle_backward(x, stacks, token, row_weight, starts, counts, ROUTED_BLOCK, dy)
    got = {"y": y, "dx": dx, "stacks": d_stacks, "d_weight": d_weight}
    want = {"y": want_y, "dx": want_dx, "stacks": want_stacks, "d_weight": want_weight}
    return got, want, (token, counts, int(done))


@pytest.mark.parametrize("leaf", ["y", "dx", "stacks", "d_weight"])
def test_routed_experts_is_the_2d_scatter_adds_bit_for_bit(routed_pair, leaf):
    got, want, (token, counts, done) = routed_pair
    counts = np.asarray(counts)
    assert counts[3] == 0 and counts[0] == ROUTED_T // 2 and counts[0] % ROUTED_BLOCK  # an empty expert, a ragged tail
    assert done == counts.sum() and np.bincount(np.asarray(token)[:done]).max() > 1  # a token of several held experts
    for g, w in zip(jax.tree.leaves(got[leaf]), jax.tree.leaves(want[leaf])):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert float(jnp.abs(w.astype(F32)).max()) > 1e-3
        np.testing.assert_array_equal(np.asarray(g.astype(F32)), np.asarray(w.astype(F32)))


@pytest.mark.parametrize("d, tiles", [(8, 1), (1024, 1), (1152, 2), (2048, 2), (2304, 3), (2688, 3)])
def test_the_sums_shape_follows_from_the_width_alone(d, tiles):
    """A token's row of the carried sums is whole (8, 128) float32 tiles, and the
    loops scatter into nothing 2-D: read off the jaxpr of the layer's gradient."""
    assert moe_lib._row_tiles(d) == (tiles, tiles * 1024)
    t, hidden = 24, 8
    x = jnp.zeros((t, d), jnp.bfloat16)
    stacks = (jnp.zeros((2, d, hidden)), jnp.zeros((2, hidden, d)))
    plan = moe_lib.dispatch_plan(jnp.zeros((t, 1), jnp.int32), jnp.ones((t, 1)), 0, 2)

    def loss(x, stacks, w):
        return moe_lib.routed_experts(x, stacks, plan[0], w, *plan[2:], 16)[0].astype(F32).sum()

    def scatters(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("scatter"):
                yield eqn.invars[0].aval.shape
            for value in eqn.params.values():
                for inner in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from scatters(inner)

    found = list(scatters(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, stacks, plan[1]).jaxpr))
    assert found == [(t, tiles, 8, 128)] * 2, found  # y's and dx's; d_weight is written by slices


# -- the sigmoid-routed layer's plan (sigmoid_route, dispatch_plan): nothing indexed ----
#
# The plan indexes no array by a computed position: the chosen scores are a masked
# sum over E, one stable sort carries key, position and weight, the weights'
# cotangent goes back by a sort by position, the segments' edges are counted. The
# oracle below is the plan as it was before: take_along_axis, argsort, two 1-D
# gathers and a searched edge, whose transposes are scatter-adds. The same values
# at the same places and no addition of two non-zero terms anywhere: everything
# is equal bit for bit.


def _oracle_chosen(scores, idx):
    return jnp.take_along_axis(scores, idx, -1)


def _oracle_route(x, wr, select_bias, k, scale):
    scores = moe_lib.router_scores(x, wr)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(select_bias), k)
    chosen = _oracle_chosen(scores, idx)
    return idx, scale * chosen / jnp.sum(chosen, -1, keepdims=True)


def _oracle_plan(idx, weights, first, held):
    k = idx.shape[-1]
    local = idx - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    edges = jnp.searchsorted(key[order], jnp.arange(held + 1, dtype=key.dtype))
    return (
        (order // k).astype(jnp.int32), weights.reshape(-1)[order],
        edges[:-1].astype(jnp.int32), jnp.diff(edges).astype(jnp.int32),
    )


# name: (T, k, E, first held, held, how the experts are chosen)
PLAN_CASES = {
    "small-e8": (24, 2, 8, 2, 4, "scores"),
    "e64-of-0": (96, 4, 64, 0, 8, "scores"),
    "e256-of-8": (64, 8, 256, 8, 8, "scores"),
    "rows65536-e64": (16384, 4, 64, 0, 8, "scores"),
    "all-held": (40, 3, 8, 0, 8, "scores"),
    "ties": (48, 2, 8, 2, 4, "same"),  # every token chooses experts 3 and 6: one long run of equal keys a side
    "no-row": (56, 3, 8, 2, 4, "never-4"),  # held expert 4 (local 2) is never chosen
}


@pytest.fixture(scope="module", params=list(PLAN_CASES), ids=list(PLAN_CASES))
def plan_pair(request):
    """``(got, want, counts)`` of one case: the plan and the cotangents of its
    weights and of the scores, from models/moe.py and from the oracle."""
    t, k, e, first, held, how = PLAN_CASES[request.param]
    keys = jax.random.split(jax.random.key(t * k + e), 4)
    scores = jax.nn.sigmoid(jax.random.normal(keys[0], (t, e)))
    select = {"scores": scores, "same": jnp.zeros((e,)).at[jnp.asarray([3, 6])].set(1.0) + 0.0 * scores,
              "never-4": scores.at[:, 4].set(-1.0)}[how]
    _, idx = jax.lax.top_k(select, k)
    d_row = jax.random.normal(keys[1], (t * k,))
    d_chosen = jax.random.normal(keys[2], (t, k))

    def both(chosen_of, plan_of):
        def weights_of(scores):
            chosen = chosen_of(scores, idx)
            return 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True)

        weights, back_scores = jax.vjp(weights_of, scores)
        _, back = jax.vjp(lambda w: plan_of(idx, w, first, held)[1], weights)
        token, row_weight, starts, counts = plan_of(idx, weights, first, held)
        (d_weights,) = back(d_row)
        return {
            "weights": weights, "token": token, "row_weight": row_weight, "starts": starts, "counts": counts,
            "d_weights": d_weights, "d_scores": back_scores(d_weights)[0],
            "chosen": chosen_of(scores, idx), "d_scores_of_chosen": jax.vjp(lambda s: chosen_of(s, idx), scores)[1](d_chosen)[0],
        }

    return both(moe_lib._chosen, moe_lib.dispatch_plan), both(_oracle_chosen, _oracle_plan), request.param


@pytest.mark.parametrize("leaf", ["weights", "token", "row_weight", "starts", "counts", "d_weights", "d_scores",
                                  "chosen", "d_scores_of_chosen"])
def test_plan_is_the_argsort_and_gathers_bit_for_bit(plan_pair, leaf):
    got, want, name = plan_pair
    t, k, e, first, held, how = PLAN_CASES[name]
    counts = np.asarray(want["counts"])
    assert counts.sum() <= t * k and (held == e) == (counts.sum() == t * k)  # absent experts unless all are held
    if how == "same":
        assert counts.tolist() == [0, t, 0, 0]  # expert 3 of 2..5 by every token; expert 6 is absent
    if how == "never-4":
        assert counts[2] == 0 and counts[[0, 1, 3]].min() > 0
    g, w = got[leaf], want[leaf]
    assert g.dtype == w.dtype and g.shape == w.shape
    assert float(jnp.abs(w).max()) > 0
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture(scope="module", params=[(8, 2, "swiglu"), (8, 2, "relu2"), (64, 4, "swiglu"), (64, 4, "relu2"),
                                        (256, 8, "swiglu"), (256, 8, "relu2")], ids=lambda p: f"e{p[0]}-k{p[1]}-{p[2]}")
def layer_pair(request):
    """``(got, want)``: a whole SharedExpertMoe's output and the gradients of
    its parameters and its tokens, as the module is and with the oracle's route
    and plan in their place. Experts 2..5 held of E: absent experts exist."""
    e, k, kind = request.param
    layer = moe_lib.SharedExpertMoe(
        width=32, hidden=16, num_experts=e, num_selected=k, dtype=jnp.float32, route_scale=2.5,
        shared_experts=1, experts_held=4, first_held=2, kind=kind,
    )
    keys = jax.random.split(jax.random.key(e), 3)
    x = jax.random.normal(keys[0], (2, 40, 32))
    params = layer.init(keys[1], x)["params"]
    # a router that spreads its choices, so held experts get rows at every E
    params = dict(params, router=jax.random.normal(keys[2], params["router"].shape))

    def loss(params, x):
        y, state = layer.apply({"params": params}, x, mutable=["intermediates"])
        return jnp.sum(jnp.sin(y)), (y, state["intermediates"]["moe_load"][0]["tokens"])

    def run():
        (_, (y, load)), (d_params, dx) = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
        return {"y": y, "load": load, "dx": dx, **{f"d_{name}": g for name, g in d_params.items() if name != moe_lib.SELECT_BIAS}}

    got = run()
    patch = pytest.MonkeyPatch()
    patch.setattr(moe_lib, "sigmoid_route", _oracle_route)
    patch.setattr(moe_lib, "dispatch_plan", _oracle_plan)
    try:
        want = run()
    finally:
        patch.undo()
    return got, want


@pytest.mark.parametrize("leaf", ["y", "load", "dx", "d_router", "d_wi", "d_wo", "d_shared"])
def test_layer_gradients_are_the_gathers_bit_for_bit(layer_pair, leaf):
    got, want = layer_pair
    assert got.keys() == want.keys()
    if leaf == "load":
        assert 0 < int(want["load"].sum()) < 80 * 8  # held experts have rows, absent ones too
    for g, w in zip(jax.tree.leaves(got[leaf]), jax.tree.leaves(want[leaf]), strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _primitives(jaxpr):
    """Every primitive's name in ``jaxpr`` and in whatever its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for _, inner in jaxpr_audit._sub_jaxprs(eqn.params):
            yield from _primitives(inner)


@pytest.mark.parametrize("k, e, first, held", [(2, 8, 2, 4), (4, 64, 0, 8), (6, 128, 0, 8), (8, 128, 0, 16), (8, 256, 0, 8)])
def test_the_plan_indexes_nothing(k, e, first, held):
    """No gather in sigmoid_route + dispatch_plan and no scatter in their VJP:
    on the chip a 1-D gather or scatter-add costs 7-10 ns an ELEMENT, a sort of
    the same elements a hundredth of that (the note above the layer in
    models/moe.py), so neither may come back unnoticed. The oracle's form is
    read the same way, so the walk is known to find them."""
    t, d = 32, 16
    x, wr, bias = jnp.ones((t, d)), jnp.ones((d, e)), jnp.zeros((e,))

    def plan_of(route, plan):
        def run(x, wr, bias):
            idx, weights = route(x, wr, bias, k, 2.5)
            return plan(idx, weights, first, held)
        return run

    def vjp_of(run):
        def back(x, wr, bias, ct):
            return jax.vjp(lambda x, wr: run(x, wr, bias)[1], x, wr)[1](ct)
        return back

    def indexed(fn, *args):
        return sorted({p for p in _primitives(jax.make_jaxpr(fn)(*args).jaxpr) if p.startswith(("gather", "scatter", "dynamic"))})

    ours = plan_of(moe_lib.sigmoid_route, moe_lib.dispatch_plan)
    was = plan_of(_oracle_route, _oracle_plan)
    ct = jnp.ones((t * k,))
    assert indexed(was, x, wr, bias) == ["gather"] and indexed(vjp_of(was), x, wr, bias, ct) == ["gather", "scatter-add"]
    assert indexed(ours, x, wr, bias) == []
    assert indexed(vjp_of(ours), x, wr, bias, ct) == []
    sorts = [p for p in _primitives(jax.make_jaxpr(vjp_of(ours))(x, wr, bias, ct).jaxpr) if p == "sort"]
    assert len(sorts) == 2, sorts  # the plan's, and the one that takes the weights' cotangent back
