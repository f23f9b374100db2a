"""Where layers are scanned, the accumulating step adds each layer's weight
gradient into the accumulator inside the backward layer loop
(models/transformer.py GRAD_SINK, train/train_step.py): same adds, same
roundings, same parameter tree, no gradient stack."""

import dataclasses
import functools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.models.transformer import (
    GRAD_SINK,
    Encoder,
    merge_grad_sink,
    split_grad_sink,
)
from distributed_sigmoid_loss_tpu.obs.attribution import accum_placement
from distributed_sigmoid_loss_tpu.parallel.mesh import make_mesh
from distributed_sigmoid_loss_tpu.train import create_train_state, make_train_step
from distributed_sigmoid_loss_tpu.train import train_step as train_step_module
from distributed_sigmoid_loss_tpu.utils.config import LossConfig, SigLIPConfig

DEPTH = 3


def tiny_cfg(layout="scanned", policy="nothing"):
    """The tiny model with both towers ``scanned`` (the step's sink engages),
    ``unrolled``, or with a ``looped`` text tower beside a scanned image tower."""
    cfg = SigLIPConfig.tiny_test()
    towers = dict(
        depth=DEPTH, remat=True, remat_policy=policy, scan_layers=layout != "unrolled"
    )
    text = dict(towers, loops=2) if layout == "looped" else towers
    return dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, **towers),
        text=dataclasses.replace(cfg.text, **text),
    )


def tiny_batch(cfg, rows=8, seed=0):
    ki, kt = jax.random.split(jax.random.key(seed))
    size = cfg.vision.image_size
    return {
        "images": jax.random.normal(ki, (rows, size, size, 3), jnp.float32),
        "tokens": jax.random.randint(
            kt, (rows, cfg.text.context_length), 0, cfg.text.vocab_size, jnp.int32
        ),
    }


@functools.lru_cache(maxsize=None)
def _initial_state(cfg):
    """One initialisation a configuration; the steps donate their state, so
    ``build`` hands out copies."""
    return create_train_state(
        jax.random.key(0), SigLIP(cfg), optax.sgd(1.0), tiny_batch(cfg), make_mesh(2)
    )


def build(cfg, monkeypatch=None, sink=True, **step_kw):
    """State, placed batch and step; ``sink=False`` builds the step with the
    mechanism forced off (every leaf through accum_add, as before it)."""
    model, mesh = SigLIP(cfg), make_mesh(2)
    batch = tiny_batch(cfg)
    state = jax.tree.map(jnp.copy, _initial_state(cfg))
    if not sink:
        monkeypatch.setattr(train_step_module, "split_grad_sink", lambda tree: ({}, tree))
    step, shardings = make_train_step(model, mesh, LossConfig(variant="ring"), **step_kw)
    return state, jax.device_put(batch, shardings), step


@pytest.mark.parametrize("accum_steps", [2, 4])
@pytest.mark.parametrize("policy", ["nothing", "save_hot"])
@pytest.mark.parametrize("accum_dtype", [None, "bfloat16"])
def test_scanned_accumulation_is_bit_identical(monkeypatch, accum_dtype, policy, accum_steps):
    """sgd(1.0) makes the update the accumulated gradient: with the sink and
    with it forced off the new parameters and the loss are equal bit for bit."""
    kw = dict(accum_steps=accum_steps, accum_dtype=accum_dtype)
    cfg = tiny_cfg(policy=policy)
    state, batch, step = build(cfg, **kw)
    with_sink, metrics = step(state, batch)
    assert accum_placement(step)["layer_loop_share"] > 0.5
    state, batch, step = build(cfg, monkeypatch, sink=False, **kw)
    without, metrics_off = step(state, batch)
    assert accum_placement(step)["layer_loop_share"] == 0.0
    assert float(metrics["loss"]) == float(metrics_off["loss"])
    assert jax.tree.structure(with_sink.params) == jax.tree.structure(without.params)
    for a, b in zip(jax.tree.leaves(with_sink.params), jax.tree.leaves(without.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parameter_tree_and_init_are_unchanged():
    """``init`` makes the ``params`` collection alone, with the stacks where
    hf_import.stack_for_scan and the checkpoints expect them, and an
    accumulated step hands the same tree back."""
    cfg = tiny_cfg()
    batch = tiny_batch(cfg)
    variables = SigLIP(cfg).init(jax.random.key(0), batch["images"], batch["tokens"])
    assert set(variables) == {"params"}
    width = cfg.vision.width
    kernel = variables["params"]["visual"]["encoder"]["blocks"]["block"]["mlp"]["wi"]["kernel"]
    assert jnp.shape(kernel.value) == (DEPTH, width, width * cfg.vision.mlp_ratio)
    state, batch, step = build(cfg, accum_steps=2)
    before = jax.tree.map(lambda x: (x.shape, x.dtype), state.params)
    after, _ = step(state, batch)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), after.params) == before
    stacks, rest = split_grad_sink(after.params)
    assert set(stacks) == {"visual", "textual"} and "blocks" not in str(jax.tree.structure(rest))
    assert jax.tree.structure(merge_grad_sink(stacks, rest)) == jax.tree.structure(after.params)


@pytest.mark.parametrize("layout", ["scanned", "unrolled", "looped"])
def test_counter_reads_where_the_accumulation_runs(layout):
    """The step's record of parameter bytes accumulated inside the layer loop
    against bytes accum_add still carries: the stacks' share for scanned
    towers, nothing for unrolled ones, the image tower alone beside a looped
    text tower."""
    cfg = tiny_cfg(layout)
    state, batch, step = build(cfg, accum_steps=2)
    assert accum_placement(step) is None  # nothing traced yet
    step.lower(state, batch)
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    stacks = {"scanned": ("visual", "textual"), "unrolled": (), "looped": ("visual",)}[layout]
    inside = sum(nbytes(state.params[t]["encoder"]["blocks"]) for t in stacks)
    placement = accum_placement(step)
    assert placement["layer_loop_bytes"] == inside
    assert placement["accum_add_bytes"] == nbytes(state.params) - inside
    assert placement["layer_loop_share"] == pytest.approx(inside / nbytes(state.params))
    # An unaccumulated step has no accumulator to place.
    _, _, plain = build(cfg)
    plain.lower(state, batch)
    assert accum_placement(plain) is None


def _nested_loop_tuples(hlo: str) -> list[str]:
    """The carried tuple's type of every ``while`` that sits inside another
    ``while``'s body (the layer loops of the microbatch loop)."""
    bodies = set(re.findall(r"while\(.*?body=%?([\w.\-]+)", hlo))
    nested, inside = [], None
    for line in hlo.splitlines():
        head = re.match(r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->.*\{\s*$", line)
        if head:
            inside = head.group(1)
        elif inside in bodies and " while(" in line:
            nested.append(line.split(" while(")[0])
    return nested


def test_backward_layer_loop_carries_no_gradient_stack(monkeypatch):
    """In the optimized program the stacked f32 gradient of the MLP's first
    kernel is gone from the layer loops; forced off, it is there (the same
    count then holds the weights and their gradients)."""
    cfg = tiny_cfg()
    width = cfg.vision.width
    stack = f"f32[{DEPTH},{width},{width * cfg.vision.mlp_ratio}]"
    counts = {}
    for sink in (True, False):
        state, batch, step = build(
            cfg, monkeypatch, sink=sink, accum_steps=2, accum_dtype="bfloat16"
        )
        tuples = _nested_loop_tuples(step.lower(state, batch).compile().as_text())
        assert len(tuples) >= 4  # forward and backward, two towers
        counts[sink] = sum(t.count(stack) for t in tuples)
    assert counts[True] < counts[False]
    # What is left are the weights themselves, read by each loop.
    assert counts[False] - counts[True] == 2


def _encoder(**kw):
    return Encoder(32, DEPTH, 2, 2, jnp.float32, remat=True, **kw)


def _encoder_params(enc, x):
    return nn.meta.unbox(enc.init(jax.random.key(0), x)["params"])


def test_model_without_a_sink_returns_ordinary_gradients():
    """Applied with ``params`` alone (what the benchmark's own gradient check
    calls) the scanned stack is differentiated as ever; handed a zero sink the
    same values come out as the sink (0 + g = g) and the stacks get none."""
    enc, x = _encoder(scan_layers=True), jax.random.normal(jax.random.key(1), (2, 5, 32))
    params = _encoder_params(enc, x)
    plain = jax.grad(lambda p: jnp.sum(enc.apply({"params": p}, x) ** 2))(params)
    plain_stacks, plain_rest = split_grad_sink(plain)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(plain_stacks))

    def through_sink(p, sink):
        y, updated = enc.apply({"params": p, GRAD_SINK: sink}, x, mutable=[GRAD_SINK])
        return jnp.sum(y ** 2), updated[GRAD_SINK]

    zero = jax.tree.map(jnp.zeros_like, split_grad_sink(params)[0])
    (value, _), grads_of = jax.vjp(through_sink, params, zero)
    grads, sunk = grads_of((jnp.ones_like(value), zero))
    stacks, rest = split_grad_sink(grads)
    for got, want in ((sunk, plain_stacks), (rest, plain_rest)):
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(float(jnp.max(jnp.abs(g))) == 0 for g in jax.tree.leaves(stacks))


@pytest.mark.parametrize(
    "kw,under,named",
    [
        (dict(scan_layers=True, loops=2), ("loop", "blocks"), "looped stack"),
        (dict(scan_layers=False), ("block0",), "unrolled layers"),
    ],
)
def test_stack_that_cannot_take_a_sink_refuses_it_by_name(kw, under, named):
    enc, x = _encoder(**kw), jnp.ones((2, 5, 32))
    params = _encoder_params(enc, x)
    sink = params
    for key in under:
        sink = sink[key]
    for key in reversed(under):
        sink = {key: sink}
    with pytest.raises(ValueError, match=named):
        enc.apply({"params": params, GRAD_SINK: sink}, x, mutable=[GRAD_SINK])
