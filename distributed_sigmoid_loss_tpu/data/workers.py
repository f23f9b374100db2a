"""Host worker-count resolution for the input pipeline.

One resolver for every host-side thread pool (native JPEG decode, the C++
synthetic engine, the fused decode+tokenize batcher): derive the worker count
from what the host actually has, instead of the static defaults that shipped
with each component (``cpu_count // 2`` decode threads, ``num_threads=4`` in
the native loader). The train loop always runs a prefetch thread and the main
(dispatch/augment) thread next to the pool, so those cores are reserved —
oversubscribing a 1-core TPU-VM host with 4 generator threads just adds
context-switch tax to the exact path the pipeline is trying to hide.

Stdlib-only: imported by modules (native bindings, the data-bench's argument
plumbing) that must not initialize jax at import time.
"""

from __future__ import annotations

import os
import warnings

__all__ = ["RESERVED_HOST_THREADS", "default_data_workers", "resolve_data_workers"]

# Threads the train loop keeps busy outside the data worker pool: the
# data.loader.prefetch producer (decode/tokenize dispatch + host->device
# commit) and the main thread (step dispatch, on-device augment).
RESERVED_HOST_THREADS = 2


def default_data_workers(reserve: int = RESERVED_HOST_THREADS) -> int:
    """Worker threads for host data work: ``cpu_count - reserve``, min 1.

    ``DSL_DATA_WORKERS`` overrides (the same escape hatch pattern as
    ``DSL_DECODE_THREADS``, which stays decode-specific and wins over this
    for the decode pool).
    """
    env = os.environ.get("DSL_DATA_WORKERS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            warnings.warn(f"DSL_DATA_WORKERS={env!r} is not an int; ignoring")
    return max(1, (os.cpu_count() or 1) - reserve)


def resolve_data_workers(requested: int | None) -> int:
    """CLI ``--data-workers`` resolution: 0/None = auto-derive, else the
    explicit positive value. The resolved number is what data-bench records carry —
    a record that says "auto" is not reproducible on a different host."""
    if requested:
        if requested < 0:
            raise ValueError(f"data workers must be >= 1, got {requested}")
        return requested
    return default_data_workers()
