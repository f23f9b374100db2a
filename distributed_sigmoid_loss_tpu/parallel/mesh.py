"""Mesh construction helpers — the TPU-native replacement for the reference's
``torch.distributed`` process-group runtime.

The reference brings up a Gloo process group with localhost TCP rendezvous
(/root/reference/test_distributed_sigmoid_loss.py:35-51) and fans out OS processes with
``mp.spawn``. On TPU there is no rendezvous code at all: a ``jax.sharding.Mesh`` over
the ICI fabric names the device axes, ``shard_map``/``pjit`` partition arrays over them,
and XLA inserts the collectives. Multi-rank emulation on one host (the reference's
``mp.spawn`` + Gloo trick) becomes ``--xla_force_host_platform_device_count=N`` virtual
CPU devices — same collective semantics, no processes.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis names used across the framework.
data_axis = "dp"  # batch / replica axis — the reference's "world" of DDP ranks
model_axis = "tp"  # tensor-parallel axis for tower weights (absent in the reference)
sequence_axis = "sp"  # sequence-parallel axis for long-context ring attention


def make_mesh(
    world_size: int | None = None,
    axis_name: str = data_axis,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """1-D mesh of ``world_size`` devices along ``axis_name``.

    ``world_size=None`` uses every visible device. Using fewer devices than visible is
    allowed (e.g. a 3-device mesh out of 8 virtual CPU devices, mirroring the
    reference's odd world_size=3 test configs, test_distributed_sigmoid_loss.py:144).
    """
    if devices is None:
        devices = jax.devices()
    if world_size is None:
        world_size = len(devices)
    if world_size > len(devices):
        raise ValueError(
            f"world_size={world_size} exceeds visible devices ({len(devices)}); "
            "for CPU emulation set XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    return Mesh(np.asarray(devices[:world_size]), (axis_name,))


def trace_on(mesh: Mesh):
    """Context manager: trace the enclosed code with ``mesh`` as jax's abstract
    mesh, so code deep in the towers can see which axes exist without the mesh
    being threaded through every module. The fused attention kernels need it
    (models/transformer.py ``_fused_attention_per_shard``): a Mosaic kernel
    under a multi-chip ``jit`` must sit in a ``shard_map``, and ``shard_map``
    needs a mesh. Enter it INSIDE the jitted function, around ``model.apply``
    — never around ``model.init``: under a mesh flax applies the kernels'
    ``tp`` partitioning eagerly, which a mesh without that axis rejects.
    """
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def make_2d_mesh(
    dp: int,
    tp: int,
    *,
    devices: Sequence[jax.Device] | None = None,
    axis_names: tuple[str, str] = (data_axis, model_axis),
) -> Mesh:
    """(dp × tp) mesh for combined data + tensor parallelism of the towers."""
    if devices is None:
        devices = jax.devices()
    if dp * tp > len(devices):
        raise ValueError(f"dp*tp={dp * tp} exceeds visible devices ({len(devices)})")
    grid = np.asarray(devices[: dp * tp]).reshape(dp, tp)
    return Mesh(grid, axis_names)


# The start-up span of the mesh's construction (obs/spans.py), applied here at the
# file's end: the compile cache's key holds the source lines of whatever jax traces
# through this file (utils/compile_cache.py), so nothing above may move.
from distributed_sigmoid_loss_tpu.obs.spans import spanned as _spanned  # noqa: E402

make_mesh = _spanned("startup.mesh")(make_mesh)
