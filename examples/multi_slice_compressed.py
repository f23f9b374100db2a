#!/usr/bin/env python
"""Multi-slice training with compressed cross-slice gradient sync — runnable
on any machine via an emulated (dcn=2, dp=4) CPU mesh.

The scenario: data parallelism spans two TPU slices. Within a slice,
gradients sync over ICI at f32 (bandwidth is ample); between slices they
cross DCN — the slow link — so the framework quantizes that hop to int8 (or
top-k-sparsifies it) with error feedback carrying the residual into the next
step (train/compressed_step.py, parallel/compression.py; for the compute
price on one chip see examples/microbench_grad_compression.py). The same thing via the CLI:

    python -m distributed_sigmoid_loss_tpu train --cpu-devices 8 --tiny \\
        --dcn-slices 2 --grad-compression int8 --steps 20 --batch 16

On real multi-slice hardware drop --cpu-devices; the mesh builder groups the
dcn axis by actual slice boundaries (mesh_utils.create_hybrid_device_mesh).
"""

import os
import sys

# Runnable from a fresh checkout: put the repo root on sys.path (same
# bootstrap as examples/train_siglip.py).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
import optax

from distributed_sigmoid_loss_tpu.models import SigLIP
from distributed_sigmoid_loss_tpu.parallel.mesh import make_2d_mesh
from distributed_sigmoid_loss_tpu.train import (
    create_train_state,
    make_compressed_train_step,
    with_error_feedback,
)
from distributed_sigmoid_loss_tpu.utils.config import LossConfig, SigLIPConfig


def main():
    mesh = make_2d_mesh(2, 4, axis_names=("dcn", "dp"))
    cfg = SigLIPConfig.tiny_test()
    model = SigLIP(cfg)

    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(
            rng.standard_normal(
                (16, cfg.vision.image_size, cfg.vision.image_size, 3)
            ),
            jnp.float32,
        ),
        "tokens": jnp.asarray(
            rng.integers(0, cfg.text.vocab_size, (16, cfg.text.context_length)),
            jnp.int32,
        ),
    }

    state = with_error_feedback(
        create_train_state(
            jax.random.key(0), model, optax.adam(3e-3), batch, mesh
        ),
        mesh,
    )
    # accum_steps=2: microbatch grads accumulate locally (bf16 carry) and the
    # compressed DCN hop runs ONCE on the mean — 2x fewer slow-wire bytes per
    # sample than syncing every microstep.
    step, shardings = make_compressed_train_step(
        model, mesh, LossConfig(variant="all_gather"), compression="int8",
        accum_steps=2, accum_dtype="bfloat16",
    )
    b = jax.device_put(batch, shardings)
    for i in range(10):
        state, m = step(state, b)
        print(
            f"step {i + 1:2d}  loss={float(m['loss']):7.4f}  "
            f"grad_norm={float(m['grad_norm']):8.3f}  "
            f"ef_norm={float(m['ef_norm']):.3e}"
        )


def main_pp():
    """Scenario 2 (round 5): the same compressed wire with both towers
    PIPELINED over a pp axis — a (dcn 2, dp 2, pp 2) mesh. Stage params and
    error-feedback residuals live pp-sharded; gpipe's schedule runs inside
    the same fully-manual region as the compressed hop. CLI equivalent:

        python -m distributed_sigmoid_loss_tpu train --cpu-devices 8 --tiny \\
            --dcn-slices 2 --pp 2 --grad-compression int8 --steps 20 --batch 16
    """
    import dataclasses

    from jax.sharding import Mesh

    mesh = Mesh(
        np.array(jax.devices()).reshape(2, 2, 2), ("dcn", "dp", "pp")
    )
    cfg = SigLIPConfig.tiny_test()
    # Pipeline stages are the nn.scan-stacked block params.
    cfg = dataclasses.replace(
        cfg,
        vision=dataclasses.replace(cfg.vision, scan_layers=True),
        text=dataclasses.replace(cfg.text, scan_layers=True),
    )
    model = SigLIP(cfg)
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(
            rng.standard_normal(
                (16, cfg.vision.image_size, cfg.vision.image_size, 3)
            ),
            jnp.float32,
        ),
        "tokens": jnp.asarray(
            rng.integers(0, cfg.text.vocab_size, (16, cfg.text.context_length)),
            jnp.int32,
        ),
    }
    state = with_error_feedback(
        create_train_state(
            jax.random.key(0), model, optax.adam(3e-3), batch, mesh,
            pp_axis="pp",
        ),
        mesh, pp_axis="pp",
    )
    step, shardings = make_compressed_train_step(
        model, mesh, LossConfig(variant="all_gather"), compression="int8",
        pp_microbatches=2,
    )
    b = jax.device_put(batch, shardings)
    for i in range(6):
        state, m = step(state, b)
        print(
            f"pp step {i + 1:2d}  loss={float(m['loss']):7.4f}  "
            f"ef_norm={float(m['ef_norm']):.3e}"
        )


if __name__ == "__main__":
    main()
    main_pp()
