#!/usr/bin/env python3
"""A cell's step program as jax lowers it for a TPU v5e that is described and not
attached, hashed: two checkouts whose hashes agree run the same program.

    JAX_PLATFORMS=cpu python benchmark/tests/lowered_hash.py --workload so400m-mb32x4 [--keep out.txt]

Run it from each checkout (the parent unpacked by ``git archive`` beside the
change); nothing is compiled and nothing runs. ``program`` hashes the StableHLO
text, which carries no source locations, with the Mosaic kernels' serialised
bodies cut out: those carry the line numbers of every caller, so they differ
wherever a file on the way to a kernel gained a line. ``with_kernel_bodies``
hashes the text whole: equal only where those files are untouched too.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import harness  # noqa: E402

KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+(\\22)')


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--keep", help="write the lowered text here")
    args = ap.parse_args()

    cell = harness.Cell(args.workload, rehearse=False)
    sys.path.insert(0, harness.CHECKOUT)

    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_sigmoid_loss_tpu.ops import flash_attention
    from distributed_sigmoid_loss_tpu.train import create_train_state

    # The towers ask the default backend whether the fused kernels exist; it is
    # the CPU here, and the program hashed is the chip's.
    flash_attention.flash_attention_available = lambda: True
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    job = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", cell.traffic["job"] + ".py"))
    built = job.build_step(cell, topo.devices[: cell.chips])
    replicated = NamedSharding(built.mesh, P())
    state = jax.eval_shape(
        lambda key: create_train_state(key, built.model, built.tx, built.batch_shapes, built.mesh),
        jax.random.key(0),
    )
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated), state)
    batch = {
        k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=built.batch_shardings[k])
        for k, s in built.batch_shapes.items()
    }
    text = built.step.lower(state, batch).as_text()
    if args.keep:
        with open(args.keep, "w") as f:
            f.write(text)
    stripped, kernels = KERNEL_BODY.subn(r"\1\2", text)
    print(json.dumps({
        "workload": cell.name, "checkout": harness.CHECKOUT, "kernels": kernels,
        "program": hashlib.sha256(stripped.encode()).hexdigest(),
        "with_kernel_bodies": hashlib.sha256(text.encode()).hexdigest(),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
