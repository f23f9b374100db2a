"""Cross-replica update sharding: ONE placement rule for grads, optimizer
state, and the param publish (graftshard).

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (Xu et al., arXiv:2004.13336, PAPERS.md) shows the whole
gradient -> optimizer -> new-param path can run on 1/W of each tensor per
replica: reduce-scatter the gradient sum, update the shard, all-gather the
new params once. The XLA paper does this as a compiler pass; the JAX-native
spelling is sharding *constraints* placed where the dataflow forks —
GSPMD then emits exactly that reduce-scatter / shard-compute / all-gather
program. This module is the one home of that placement logic; before it,
``zero1_constrain`` (train_step.py) re-pinned the optimizer tree after the
fact per-builder, and the compressed step compressed the *whole* gradient
instead of the 1/W shard.

Three modes (``UPDATE_SHARDING_MODES``), CLI ``--update-sharding``:

- ``"off"``   — replicated update, the plain data-parallel step.
- ``"zero1"`` — the historical ZeRO-1 placement: optimizer state sharded
  over the data axis, but only leaves whose leading dim divides the axis
  size exactly (``shape[0] % W == 0``); grads and params stay replicated.
  Kept bit-compatible with the ``--zero1`` era so existing checkpoints
  restore onto identical layouts.
- ``"full"``  — the 2004.13336 scheme: grads are constrained to the shard
  spec *before* the optax update (XLA turns the dp all-reduce into a
  reduce-scatter), optimizer state lives sharded, and the updated params
  are constrained back to their model shardings (one all-gather publishes
  the weights). The leading-dim rule is permissive: any leaf with
  ``shape[0] >= W`` shards. Ragged tails (``shape[0] % W != 0``) are
  zero-padded explicitly in the manual compressed path
  (:func:`psum_scatter_shard` / :func:`ef_slot_shape`), so their wire and
  EF residuals genuinely shard; in the constraint-based path jax (0.4.x)
  cannot represent uneven shardings and ``with_sharding_constraint``
  silently degrades those leaves to replicated — numerics are unchanged,
  only their at-rest moment bytes stay un-sharded.
  zero1 checkpoints stay loadable — orbax restores by value into the
  target's shardings, and full shards a superset of zero1's leaves.

The compressed step (train/compressed_step.py) cannot lean on GSPMD inside
its fully-manual shard_map region, so it uses the explicit collective
helpers here: :func:`psum_scatter_shard` (zero-pad the leading dim to a
multiple of W, then a tiled ``lax.psum_scatter``) produces the same
shard the constraint-based path owns, the per-rung compressor then sees
1/W of every tensor on the DCN wire, and the error-feedback residual is
shard-local (:func:`ef_slot_shape`).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, Sharding

__all__ = [
    "UPDATE_SHARDING_MODES",
    "OPTIMIZER_SCOPE",
    "resolve_update_sharding",
    "shardable",
    "padded_rows",
    "update_shard_spec",
    "constrain_update_sharding",
    "capture_shardings",
    "apply_sharded_update",
    "psum_scatter_shard",
    "unpad_like",
    "ef_slot_shape",
    "shard_leaf_sizes",
    "opt_mem_bytes_per_replica",
]

UPDATE_SHARDING_MODES = ("off", "zero1", "full")

# Sentinel for "no captured sharding — leave this leaf to the compiler";
# distinct from None so pytrees of shardings keep their leaf structure.
KEEP = object()

# The ``jax.named_scope`` of everything the weight update costs the device:
# the optax update, the zero1/full constraints, the publish, the EMA. Every
# operation's path in a profile carries it. XLA fuses each leaf's update with
# the norms of ``step_metrics`` that read its result, so the benchmark's
# ``update_and_metrics_ms`` (benchmark/scopes.py) is the device time under both.
OPTIMIZER_SCOPE = "optimizer"


def resolve_update_sharding(update_sharding: str = "", zero1: bool = False) -> str:
    """Resolve the mode from the new flag + the deprecated ``zero1`` alias.

    ``update_sharding=""`` (unset) defers to the legacy flag: ``zero1=True``
    means ``"zero1"``, else ``"off"``. An explicit mode wins — except the
    contradiction ``zero1=True`` with ``update_sharding="off"``, which is
    refused rather than silently dropping either flag.
    """
    if update_sharding in ("", None):
        return "zero1" if zero1 else "off"
    if update_sharding not in UPDATE_SHARDING_MODES:
        raise ValueError(
            f"update_sharding must be one of {UPDATE_SHARDING_MODES}, "
            f"got {update_sharding!r}"
        )
    if zero1 and update_sharding == "off":
        raise ValueError(
            "zero1=True contradicts update_sharding='off' — drop the "
            "deprecated zero1 flag (it is the same lever as "
            "update_sharding='zero1')"
        )
    return update_sharding


def shardable(shape, w: int, mode: str = "full") -> bool:
    """Does a leaf of ``shape`` shard its leading dim over a size-``w`` axis?

    THE placement predicate — both step builders, the EF layout, the wire
    accounting, and the tests ask this one function, so the rule cannot
    drift per call site. zero1 keeps the historical exact-divisibility rule
    (layout-identical to the ``--zero1`` era); full shards every leaf with
    at least one row per replica and pads the ragged tail.
    """
    if mode == "off" or w <= 1 or not shape:
        return False
    if mode == "zero1":
        return shape[0] >= w and shape[0] % w == 0
    if mode == "full":
        return shape[0] >= w
    raise ValueError(f"unknown update_sharding mode {mode!r}")


def padded_rows(dim0: int, w: int) -> int:
    """``dim0`` rounded up to a multiple of ``w`` (the padded shard layout)."""
    return -(-dim0 // w) * w


def update_shard_spec(shape, w: int, axis_name: str = "dp", mode: str = "full") -> P:
    """PartitionSpec for one update-path leaf: ``P(axis)`` iff shardable."""
    return P(axis_name) if shardable(shape, w, mode) else P()


def constrain_update_sharding(
    tree: Any, mesh: Mesh, axis_name: str = "dp", mode: str = "full"
) -> Any:
    """Constrain every array leaf of ``tree`` to its update-shard placement.

    Inside jit this is where GSPMD learns the intent: constraining the
    *gradients* makes the dp sync a reduce-scatter, constraining the
    *optimizer state* keeps the optax math on shards. ``mode="off"`` (or a
    trivial axis) is the identity.
    """
    if mode == "off":
        return tree
    w = dict(mesh.shape).get(axis_name, 1)
    if w <= 1:
        return tree

    def con(x):
        if not hasattr(x, "shape"):
            return x
        return lax.with_sharding_constraint(
            x, NamedSharding(mesh, update_shard_spec(x.shape, w, axis_name, mode))
        )

    return jax.tree.map(con, tree)


def capture_shardings(tree: Any) -> Any:
    """Concrete leaf shardings of ``tree`` (``KEEP`` where unavailable).

    Used by the full-mode step builders to record the model's at-rest param
    placements from the first concrete state they see — the all-gather
    publish target. Tracers and abstract leaves (the jaxpr-audit path traces
    steps on ``eval_shape`` states) capture as ``KEEP``, which
    :func:`apply_sharded_update` treats as "compiler's choice".
    """

    def of(x):
        if isinstance(x, jax.core.Tracer):
            return KEEP
        s = getattr(x, "sharding", None)
        return s if isinstance(s, Sharding) else KEEP

    return jax.tree.map(of, tree)


def apply_sharded_update(
    state: Any,
    grads: Any,
    *,
    mesh: Mesh,
    axis_name: str = "dp",
    mode: str = "off",
    param_shardings: Any = None,
):
    """``state.apply_gradients`` with the update path placed per ``mode``.

    The one shared optimizer-application recipe of both step builders
    (regular + compressed), replacing their per-builder ``zero1_constrain``
    re-pin branches:

    - ``off``: plain ``apply_gradients``.
    - ``zero1``: ``apply_gradients`` then the optimizer tree constrained to
      the zero1 spec — byte-identical to the historical behavior.
    - ``full``: grads constrained to the shard spec *first* (the
      reduce-scatter), the optimizer tree constrained sharded, and —
      when ``param_shardings`` is given — the updated params constrained
      back to their at-rest placements (the single all-gather publish;
      without it GSPMD may propagate the shard layout into the returned
      params and the next donated call recompiles on the new layout).
    """
    w = dict(mesh.shape).get(axis_name, 1)
    with jax.named_scope(OPTIMIZER_SCOPE):
        if mode == "off" or w <= 1:
            return state.apply_gradients(grads=grads)
        if mode == "full":
            grads = constrain_update_sharding(grads, mesh, axis_name, mode)
        state = state.apply_gradients(grads=grads)
        state = state.replace(
            opt_state=constrain_update_sharding(
                state.opt_state, mesh, axis_name, mode
            )
        )
        if mode == "full" and param_shardings is not None:
            def publish(p, s):
                if not isinstance(s, Sharding):
                    return p
                return lax.with_sharding_constraint(p, s)

            state = state.replace(
                params=jax.tree.map(publish, state.params, param_shardings)
            )
        return state


def psum_scatter_shard(x: jax.Array, axis_name: str, w: int) -> jax.Array:
    """Reduce-scatter one gradient leaf inside a manual (shard_map) region.

    Zero-pads the leading dim to a multiple of ``w`` then runs a tiled
    ``lax.psum_scatter``: member i of ``axis_name`` receives the SUM of row
    block i — exactly the rows :func:`update_shard_spec` assigns it, so the
    shard that leaves the region under an ``out_specs=P(axis)`` lands where
    the constraint-based optimizer path expects it, no reshard. Returns the
    (padded_rows/w, ...) shard of the SUM — callers divide for the mean.
    """
    pad = padded_rows(x.shape[0], w) - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)


def unpad_like(tree: Any, ref: Any) -> Any:
    """Slice padded leading dims back to the reference tree's shapes.

    The inverse of :func:`psum_scatter_shard`'s padding, applied OUTSIDE the
    manual region where shapes are global again: slicing a dp-sharded array
    along its sharded dim is a local mask under GSPMD (uneven sharding), not
    a gather.
    """
    return jax.tree.map(
        lambda x, r: x[: r.shape[0]] if x.shape != r.shape else x, tree, ref
    )


def ef_slot_shape(shape, n_slices: int, w: int, mode: str = "off") -> tuple:
    """Error-feedback slot shape for one param leaf.

    ``(n_slices, *shape)`` replicated-grad layout, except under full update
    sharding where the residual is SHARD-LOCAL: ``(n_slices,
    padded_rows(shape[0], w), *shape[1:])``, sharded ``(dcn, dp)`` — each
    replica carries only the residual of the shard it quantizes.
    """
    if shardable(shape, w, mode):
        return (n_slices, padded_rows(shape[0], w)) + tuple(shape[1:])
    return (n_slices,) + tuple(shape)


def shard_leaf_sizes(params: Any, w: int, mode: str = "full") -> list:
    """Per-leaf element counts of the update-path operand each replica owns.

    Under full sharding the compressor (and the BitController's payload
    table) sees the padded 1/W shard, not the whole tensor; other modes see
    full tensors. Matches ``adaptive_compression.leaf_sizes`` ordering.
    """
    sizes = []
    for p in jax.tree.leaves(params):
        shape = tuple(p.shape)
        if shardable(shape, w, mode):
            sizes.append(
                (padded_rows(shape[0], w) // w) * int(math.prod(shape[1:]))
            )
        else:
            sizes.append(int(math.prod(shape)))
    return sizes


def opt_mem_bytes_per_replica(opt_state: Any) -> int | None:
    """Measured per-replica bytes of the optimizer tree, for the bench
    record / LEDGER field of the same name.

    Primary: ``compiled_memory_stats`` of an identity-shaped jit over the
    tree — the compiler's own per-device output allocation, the figure the
    ≥0.6·W× regression pin asserts. Fallback (backends without memory
    stats): sum of addressable shard bytes. None when neither is available.
    """
    from distributed_sigmoid_loss_tpu.utils.profiling import (
        memory_stats_of_compiled,
    )

    try:
        compiled = jax.jit(lambda o: jax.tree.map(jnp.copy, o)).lower(
            opt_state
        ).compile()
        stats = memory_stats_of_compiled(compiled)
    except Exception:
        stats = None
    if stats is not None and stats.get("output_size_in_bytes") is not None:
        return int(stats["output_size_in_bytes"])
    total = 0
    for leaf in jax.tree.leaves(opt_state):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(leaf.shape)
        else:
            shape = getattr(leaf, "shape", ())
        total += int(math.prod(shape)) * int(
            getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        )
    return total
