"""The step program names itself: ``jax.named_scope`` around the optimizer, the
loss island, the accumulator and the step metrics in both step builders, a
``name=`` on every ``pallas_call``, and a compile-cache key that tells two
programs apart when only those names differ. benchmark/scopes.py reads the
names out of a profile; these tests pin that the program writes them."""

import re

import jax
import jax.numpy as jnp
import pytest

from distributed_sigmoid_loss_tpu.analysis.jaxpr_audit import (
    _abstract_batch,
    _abstract_state,
)
from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.parallel.mesh import make_2d_mesh, make_mesh
from distributed_sigmoid_loss_tpu.train import (
    make_compressed_train_step,
    make_optimizer,
    make_train_step,
)
from distributed_sigmoid_loss_tpu.utils.config import (
    LossConfig,
    SigLIPConfig,
    TrainConfig,
)


def scopes_in(lowered) -> set:
    """Every name that appears as a component of an operation's jax path in the
    lowered module, wrappers (``jvp(...)``, ``transpose(...)``) peeled."""
    paths = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    return {token for p in paths for token in re.split(r"[/()]", p) if token}


def build_plain(model):
    return make_train_step(model, make_mesh(4), LossConfig(variant="ring"))[0]


def build_accumulated(model):
    return make_train_step(
        model, make_mesh(4), LossConfig(variant="ring"), accum_steps=2,
        accum_dtype="bfloat16",
    )[0]


def build_gradcache(model):
    return make_train_step(
        model, make_mesh(4), LossConfig(variant="ring"), accum_steps=2,
        accum_negatives="global",
    )[0]


def build_full_update_sharding(model):
    return make_train_step(
        model, make_mesh(4), LossConfig(variant="ring"), update_sharding="full"
    )[0]


def build_compressed(model):
    mesh = make_2d_mesh(2, 4, axis_names=("dcn", "dp"))
    return make_compressed_train_step(
        model, mesh, LossConfig(variant="all_gather"), error_feedback=False
    )[0]


def build_compressed_accumulated(model):
    mesh = make_2d_mesh(2, 4, axis_names=("dcn", "dp"))
    return make_compressed_train_step(
        model, mesh, LossConfig(variant="all_gather"), error_feedback=False,
        accum_steps=2,
    )[0]


@pytest.mark.parametrize("build, accumulates", [
    (build_plain, False),
    (build_accumulated, True),
    (build_gradcache, True),
    (build_full_update_sharding, False),
    (build_compressed, False),
    (build_compressed_accumulated, True),
], ids=lambda x: getattr(x, "__name__", None))
def test_lowered_step_carries_its_scopes(build, accumulates):
    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    batch = _abstract_batch(cfg, 16)
    state = _abstract_state(model, make_optimizer(TrainConfig()), batch)
    names = scopes_in(build(model).lower(state, batch))
    assert {"optimizer", "loss_island", "step_metrics"} <= names
    assert ("accum" in names) == accumulates
    # What flax writes by itself: the towers need no scope of their own.
    assert {"visual", "textual"} <= names


def test_ema_update_is_in_the_optimizer_scope():
    """The EMA is part of what the weight update costs: with ``ema_decay`` the
    ``optimizer`` scope holds more operations."""
    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)
    batch = _abstract_batch(cfg, 16)
    state = _abstract_state(model, make_optimizer(TrainConfig()), batch)

    def paths(state, **kw):
        step, _ = make_train_step(model, make_mesh(4), LossConfig(), **kw)
        return re.findall(r'loc\("([^"]+)"', step.lower(state, batch).as_text(debug_info=True))

    def in_optimizer(found):
        return [p for p in found if "optimizer" in re.split(r"[/()]", p)]

    plain = paths(state)
    with_ema = paths(state.replace(ema=state.params), ema_decay=0.999)
    assert len(in_optimizer(with_ema)) > len(in_optimizer(plain))


def pallas_names(jaxpr) -> list:
    """``name`` of every pallas_call in a jaxpr, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += pallas_names(sub)
    return found


def test_attention_kernels_are_named():
    from distributed_sigmoid_loss_tpu.ops.pallas_short_attention import (
        short_self_attention,
    )

    q = jnp.zeros((2, 64, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return short_self_attention(q, k, v, interpret=True).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert sorted(pallas_names(jaxpr.jaxpr)) == ["short_attn_bwd", "short_attn_fwd"]


def test_loss_kernels_are_named():
    from distributed_sigmoid_loss_tpu.ops.pallas_sigmoid_loss import (
        streaming_block_loss_sum,
    )

    z = jnp.zeros((16, 128), jnp.float32)

    def loss(zimg, ztxt, t_prime, bias):
        return streaming_block_loss_sum(
            zimg, ztxt, t_prime, bias, jnp.float32(0.0), "", 16, 16, True
        )

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        z, z, jnp.float32(2.0), jnp.float32(-10.0)
    )
    assert sorted(pallas_names(jaxpr.jaxpr)) == ["loss_bwd_img", "loss_bwd_txt", "loss_fwd"]


def test_cache_key_tells_scope_names_apart():
    """jax hashes a module after strip-debuginfo unless told otherwise, and the
    scope names live in that debug info: a warm cache would hand a program that
    differs only by a scope the other one's executable, stale names and all.
    ``configure_compile_cache`` puts the metadata in the key."""
    import numpy as np
    from jax._src import cache_key, compiler, config
    from distributed_sigmoid_loss_tpu.utils.compile_cache import configure_compile_cache

    def key_of(scope):
        def f(x):
            with jax.named_scope(scope):
                return x * 2.0

        devices = np.array(jax.devices()[:1])
        return cache_key.get(
            jax.jit(f).lower(jnp.zeros((8,))).compiler_ir(), devices,
            compiler.get_compile_options(num_replicas=1, num_partitions=1),
            devices[0].client,
        )

    # One call site: with the metadata in it, the key also holds the call stack.
    configure_compile_cache()
    a, a_again, b = [key_of(s) for s in ("scope_a", "scope_a", "scope_b")]
    assert a == a_again and a != b
    with config.compilation_cache_include_metadata_in_key(False):  # jax's default: the trap
        a, b = [key_of(s) for s in ("scope_a", "scope_b")]
    assert a == b


def test_cache_key_is_the_same_in_another_checkout(monkeypatch, tmp_path):
    """With the metadata in it the key holds source file names;
    ``configure_compile_cache`` writes them relative to the checkout, so the
    same source in two checkouts (a parent and a change side by side, an
    unpacked archive) shares one cache entry."""
    import importlib.util

    import numpy as np
    from jax._src import cache_key, compiler, config
    from distributed_sigmoid_loss_tpu.utils import compile_cache

    def key_in(checkout, canonical=True):
        checkout.mkdir(exist_ok=True)
        source = checkout / "traced.py"
        source.write_text("def f(x):\n    return x * 2.0\n")
        spec = importlib.util.spec_from_file_location("traced", source)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        monkeypatch.setattr(compile_cache, "_CHECKOUT", str(checkout))
        compile_cache.configure_compile_cache()
        devices = np.array(jax.devices()[:1])
        with config.hlo_source_file_canonicalization_regex(
            config.hlo_source_file_canonicalization_regex.value if canonical else None
        ):
            return cache_key.get(
                jax.jit(module.f).lower(jnp.zeros((8,))).compiler_ir(), devices,
                compiler.get_compile_options(num_replicas=1, num_partitions=1),
                devices[0].client,
            )

    try:
        # One call site: a second one would differ by its column in the call stack.
        a, b, absolute_a, absolute_b = [
            key_in(tmp_path / name, canonical) for canonical in (True, False) for name in "ab"
        ]
    finally:
        monkeypatch.undo()
        compile_cache.configure_compile_cache()
    assert a == b and absolute_a != absolute_b
