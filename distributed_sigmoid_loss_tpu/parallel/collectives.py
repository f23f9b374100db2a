"""Differentiable ring communication primitives built on ``jax.lax.ppermute``.

TPU-native equivalent of the reference's hand-rolled autograd P2P layer
(/root/reference/distributed_utils.py): there, ``neighbour_exchange`` batches an
``isend`` to one neighbor with an ``irecv`` from the other (distributed_utils.py:10-27),
and custom ``autograd.Function``s re-run the exchange in the *reverse* direction for the
backward pass (``NeighbourExchange.backward``, distributed_utils.py:74-77;
``NeighbourExchangeBidir.backward``, :94-98).

On TPU none of that machinery is needed: ``jax.lax.ppermute`` IS a batched homogeneous
send/recv over the ICI ring, and its autodiff transpose is the inverse permutation — the
exact semantics the reference hand-writes. These wrappers only fix the ring topology
(left/right neighbors on a named mesh axis) so the loss code reads like the reference's
comm pattern.

All functions must be called inside ``shard_map`` (they take a mesh ``axis_name``).
"""

from __future__ import annotations

import jax
from jax import lax

__all__ = [
    "ring_shift_right",
    "ring_shift_left",
    "neighbour_exchange",
    "neighbour_exchange_bidir",
    "double_buffered_scan",
    "pvary",
    "ring_perm_problems",
    "validate_ring_perm",
]


def ring_perm_problems(perm, axis_size: int) -> list:
    """Why ``perm`` is NOT a total bijection on an axis of ``axis_size``.

    THE shared bijection check: the trace-time guard below and the jaxpr
    auditor (analysis/jaxpr_audit.py, rule ``jaxpr-ppermute-bijection``) both
    call it, so the runtime error and the static finding can never disagree
    about what a valid ring permutation is. A non-bijective perm silently
    zero-fills the shards nobody sends to (``ppermute`` semantics) — the
    broken-ring class: the loss simply loses negative blocks, with no error.

    Returns a list of human-readable problem strings; empty = bijection.
    """
    problems = []
    try:
        pairs = [(int(s), int(d)) for s, d in perm]
    except (TypeError, ValueError):
        return [f"perm is not a sequence of (src, dst) pairs: {perm!r}"]
    oob = [p for p in pairs if not (0 <= p[0] < axis_size and 0 <= p[1] < axis_size)]
    if oob:
        problems.append(f"pairs out of range [0, {axis_size}): {oob}")
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    dup_src = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_dst = sorted({d for d in dsts if dsts.count(d) > 1})
    if dup_src:
        problems.append(f"duplicate source shard(s) {dup_src} (send twice)")
    if dup_dst:
        problems.append(
            f"duplicate destination shard(s) {dup_dst} (collide; the shards "
            "nobody sends to receive ZEROS)"
        )
    if not problems and len(pairs) != axis_size:
        missing = sorted(set(range(axis_size)) - set(srcs))
        problems.append(
            f"partial permutation: only {len(pairs)}/{axis_size} shards "
            f"send (shard(s) {missing} drop their payload and their "
            "neighbors receive zeros)"
        )
    return problems


def validate_ring_perm(perm, axis_size: int, axis_name) -> None:
    """Trace-time twin of the auditor's bijection rule: raise a clear error
    naming the axis and size when ``perm`` is not a total bijection."""
    problems = ring_perm_problems(perm, axis_size)
    if problems:
        raise ValueError(
            f"ppermute permutation over axis {axis_name!r} (size {axis_size}) "
            "is not a bijection: " + "; ".join(problems)
        )


def pvary(x: jax.Array, axis_name):
    """Mark ``x`` as varying over ``axis_name`` under shard_map's replication typing."""
    return lax.pcast(x, axis_name, to="varying")


def _ring_perm(world_size: int, shift: int) -> list[tuple[int, int]]:
    return [(i, (i + shift) % world_size) for i in range(world_size)]


def ring_shift_right(x: jax.Array, axis_name: str) -> jax.Array:
    """Every shard sends ``x`` to its right neighbor ``(i+1) % W``; returns the shard
    received from the *left* neighbor.

    Equivalent to the reference's ``neighbour_exchange(from=left, to=right, tensor)``
    (distributed_utils.py:10-27) executed simultaneously on all ranks. Differentiable:
    the VJP is a left-shift — identical to ``NeighbourExchange.backward`` swapping
    from_rank/to_rank (distributed_utils.py:74-77).
    """
    w = lax.axis_size(axis_name)
    perm = _ring_perm(w, +1)
    validate_ring_perm(perm, w, axis_name)
    return lax.ppermute(x, axis_name, perm=perm)


def ring_shift_left(x: jax.Array, axis_name: str) -> jax.Array:
    """Mirror of :func:`ring_shift_right`: send to ``(i-1) % W``, receive from the
    right neighbor."""
    w = lax.axis_size(axis_name)
    perm = _ring_perm(w, -1)
    validate_ring_perm(perm, w, axis_name)
    return lax.ppermute(x, axis_name, perm=perm)


def neighbour_exchange(x: jax.Array, axis_name: str, *, to_right: bool = True):
    """One unidirectional ring hop (reference ``neighbour_exchange_with_grad``,
    distributed_utils.py:80-81). ``to_right=True`` matches the reference's default
    call pattern ``neighbour_exchange(left_rank, right_rank, tensor_to_right)``
    (rwightman_sigmoid_loss.py:97-99, 110-112)."""
    return ring_shift_right(x, axis_name) if to_right else ring_shift_left(x, axis_name)


def double_buffered_scan(issue, consume, first, acc, n_hops: int):
    """Comm/compute-overlapped ring loop: issue hop ``k+1`` BEFORE consuming
    hop ``k``.

    The serial ring (``exchange → compute → exchange → ...``) leaves every ICI
    transfer exposed: the MXU idles while the wire moves the next chunk. This
    carry restructure puts each iteration's ``ppermute`` and the PREVIOUS
    hop's block matmuls in the same scan body with no data dependency between
    them, so XLA's scheduler can run the DMA behind the matmul — the standard
    double-buffering cure for exposed exchange latency (the reference gets the
    same overlap from ``batch_isend_irecv`` + interleaved compute).

    Args:
      issue: ``payload -> next_payload`` — the exchange (any pytree payload;
        the bidir ring passes the ``(from_right, from_left)`` pair).
      consume: ``(payload, acc) -> acc`` — hop k's compute.
      first: hop 1's payload, ALREADY issued by the caller (before its own
        local compute, so hop 1 also overlaps).
      n_hops: total hops to consume.

    Returns ``(last_payload, acc)`` where ``last_payload`` is hop
    ``n_hops``'s payload, NOT yet consumed — the caller folds it in the
    epilogue, optionally issuing a final remainder exchange first. Identical
    accumulation order to the serial loop (the adds are merely interleaved
    with comm issue, never reordered), so results stay bitwise-comparable.
    """
    if n_hops < 1:
        raise ValueError(f"n_hops must be >= 1, got {n_hops}")
    if n_hops == 1:
        return first, acc

    def step(carry, _):
        cur, a = carry
        nxt = issue(cur)  # hop k+1 on the wire ...
        a = consume(cur, a)  # ... while hop k feeds the MXU
        return (nxt, a), None

    (last, acc), _ = lax.scan(step, (first, acc), None, length=n_hops - 1)
    return last, acc


def neighbour_exchange_bidir(
    to_left: jax.Array, to_right: jax.Array, axis_name: str
) -> tuple[jax.Array, jax.Array]:
    """Simultaneous exchange with both neighbors; returns ``(from_right, from_left)``.

    Matches the reference's ``neighbour_exchange_bidir_with_grad(left_rank, right_rank,
    tensor_to_left, tensor_to_right) -> (tensor_from_right, tensor_from_left)``
    (distributed_utils.py:30-62, 101-106): two ``ppermute``s — one leftward, one
    rightward — which XLA issues as a single fused bidirectional ICI transfer. The VJP
    is the mirrored pair of permutes, exactly ``NeighbourExchangeBidir.backward``
    (distributed_utils.py:94-98).
    """
    from_left = ring_shift_right(to_right, axis_name)
    from_right = ring_shift_left(to_left, axis_name)
    return from_right, from_left
