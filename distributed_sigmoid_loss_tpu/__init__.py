"""distributed_sigmoid_loss_tpu — a TPU-native (JAX/XLA/pjit/shard_map) framework with
the capabilities of the reference ``ahmdtaha/distributed_sigmoid_loss``.

Built from scratch for TPU: the compute path is pure-functional JAX jitted onto the MXU,
the communication path is XLA collectives (``jax.lax.all_gather`` / ``jax.lax.ppermute``)
over a ``jax.sharding.Mesh``, and the learnable temperature/bias scalars are replicated
optax parameters.

Public surface (mirrors the reference component inventory, see SURVEY.md §2):

- :mod:`.ops.sigmoid_loss` — the paper's Algorithm 1 as pure functions (single device).
- :mod:`.parallel.collectives` — differentiable neighbor exchange (ring P2P) built on
  ``ppermute`` (reference: distributed_utils.py).
- :mod:`.parallel.allgather_loss` — the all-gather variant
  (reference: distributed_sigmoid_loss.py ``DDPSigmoidLoss``).
- :mod:`.parallel.ring_loss` — the ring / neighbor-exchange variant
  (reference: rwightman_sigmoid_loss.py ``SigLipLoss``).
- :mod:`.parallel.ring_attention` — sequence-parallel exact attention over the same
  ppermute ring topology (long-context path).
- :mod:`.ops.pallas_sigmoid_loss` — streaming 2-D Pallas TPU kernel (fused
  backward, int8 MXU path) for the loss hot op.
- :mod:`.ops.pallas_short_attention` / :mod:`.ops.flash_attention` — fused attention
  kernels for the towers (VMEM-resident short-sequence kernel; blockwise flash for
  long context).
- :mod:`.models` — toy linear towers (reference test harness) plus real ViT + text
  transformer towers for the SigLIP training target.
- :mod:`.train` — pjit train step (with gradient accumulation), optax optimizer
  wiring, orbax checkpointing.
- :mod:`.eval` — zero-shot retrieval recall@K, sharded over the mesh.
- :mod:`.data` / :mod:`.utils` — synthetic data + input pipeline (multi-host global
  batches, prefetch), configs, parity-data recipe, metrics logging, profiling.
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()  # before the eager imports below, jax's included

__version__ = "0.1.0"

from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import (  # noqa: E402, F401
    init_loss_params,
    pairwise_logits,
    sigmoid_xent,
    sigmoid_loss,
    sigmoid_loss_block,
)
from distributed_sigmoid_loss_tpu.obs.spans import RECORDER as _RECORDER  # noqa: E402

# The first span of the process's record of start-up (obs/spans.py): this package's
# eager imports, with jax's where this import is the first to ask for it.
_RECORDER.record("startup.import", _IMPORT_T0, _time.perf_counter())
