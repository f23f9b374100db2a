"""The one table of named-axis primitives, as the installed jax (0.9.0) spells
them in a jaxpr. ``analysis/jaxpr_audit.py``, ``analysis/shard_flow.py`` and
``obs/attribution.py`` all read it; no other module spells these names.

Inside ``shard_map(check_vma=True)`` jax types every value with the axes it
varies over (``aval.vma``) and emits ``psum_invariant`` where an unchecked
region emits ``psum``, and ``pvary`` wherever an invariant value meets a
varying one. ``all_gather`` and ``reduce_scatter`` keep their names in both
kinds of region (``lax.psum_scatter`` traces to ``reduce_scatter``).

Each row: ``name -> (role, bucket, wire)``.

``role`` is what the primitive does to a value across the shards of its axes:

- ``sum``: every shard ends with the sum (the result is invariant);
- ``scatter``: the sum, each shard keeping its 1/W piece;
- ``extremum``: max/min, idempotent on a replicated value;
- ``gather``: every shard ends with all W pieces;
- ``permute``: each shard's piece goes to one other shard (a bijection, or
  some shard receives zeros);
- ``move``: pieces change shards some other way, nothing is combined;
- ``retype`` and ``index`` put nothing on the wire: ``pvary`` marks an
  invariant value as varying, the same bytes; ``axis_index`` reads the
  shard's coordinate.

``bucket`` is the ``comm_bytes_<bucket>`` field ``obs/attribution`` reports
the primitive under, and ``wire(W)`` the bytes a device moves per byte of its
per-shard operand over axes of total size ``W`` (ring conventions; the table
in ``obs/attribution``'s docstring). Both are ``None`` where nothing moves.
Stdlib only: importing this initializes nothing.
"""

from __future__ import annotations

__all__ = [
    "COLLECTIVES", "BUCKETS", "names_with_role", "collective_axes",
    "manual_axis_sizes",
]


def _all_reduce(w):
    return 2.0 * (w - 1) / w


def _one_share(w):
    return (w - 1) / w


def _other_shards(w):
    return w - 1


def _one_hop(w):
    return 1.0


COLLECTIVES = {
    "psum": ("sum", "psum", _all_reduce),
    "psum_invariant": ("sum", "psum", _all_reduce),
    "pmax": ("extremum", "psum", _all_reduce),
    "pmin": ("extremum", "psum", _all_reduce),
    "reduce_scatter": ("scatter", "psum_scatter", _one_share),
    "all_gather": ("gather", "all_gather", _other_shards),
    "all_gather_invariant": ("gather", "all_gather", _other_shards),
    "pgather": ("move", "all_gather", _other_shards),
    "ppermute": ("permute", "ppermute", _one_hop),
    "all_to_all": ("move", "all_to_all", _one_share),
    "pbroadcast": ("move", "all_to_all", _one_share),
    "pvary": ("retype", None, None),
    "axis_index": ("index", None, None),
}

# The reporting buckets, in the order records list them.
BUCKETS = ("all_gather", "ppermute", "psum", "psum_scatter", "all_to_all")


def names_with_role(*roles: str) -> frozenset:
    return frozenset(n for n, row in COLLECTIVES.items() if row[0] in roles)


def collective_axes(eqn) -> tuple:
    """The mesh axis names a collective equation runs over."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name"))
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    flat = []
    for a in axes:
        flat.extend(a if isinstance(a, (tuple, list)) else (a,))
    # positional (int) axes come from vmap, not meshes
    return tuple(a for a in flat if isinstance(a, str))


def manual_axis_sizes(shard_map_eqn) -> dict:
    """Axis name -> size for the mesh axes a ``shard_map`` equation binds for
    its body (its ``manual_axes``; the rest stay with the partitioner)."""
    params = shard_map_eqn.params
    return {
        ax: size for ax, size in params["mesh"].shape.items()
        if ax in params["manual_axes"]
    }
