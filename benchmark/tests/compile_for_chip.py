#!/usr/bin/env python3
"""The third chip-free rehearsal: compile a cell's real step program for a TPU
v5e that is described and not attached, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_for_chip.py --workload so400m-mb32x4 [--microbatch 48]

What the chip's compiler refuses (a program that does not fit 16 GB, a kernel it
cannot tile or partition) it refuses here, at no chip time. Nothing runs: this
says nothing about results or times and is never reported as a chip run.
``--microbatch`` overrides the mix's microbatch (pairs per step follow), to find
where the compiler's refusal lies.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--microbatch", type=int)
    args = ap.parse_args()

    cell = harness.Cell(args.workload, rehearse=False)
    if args.microbatch:
        mix = cell.traffic
        mix["microbatch"] = args.microbatch
        mix["pairs_per_chip_per_step"] = args.microbatch * mix["step"]["accum_steps"]
    sys.path.insert(0, harness.CHECKOUT)

    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    # The compile is for a device that cannot read the cache back: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    # The towers ask the default backend whether the fused kernels exist; it is
    # the CPU here, and the program under rehearsal is the chip's.
    from distributed_sigmoid_loss_tpu.ops import flash_attention

    flash_attention.flash_attention_available = lambda: True

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    job = harness.load_module(os.path.join(harness.BENCH_DIR, "jobs", cell.traffic["job"] + ".py"))
    built = job.build_step(cell, topo.devices[: cell.chips])

    from distributed_sigmoid_loss_tpu.train import create_train_state

    replicated = NamedSharding(built.mesh, P())
    state = jax.eval_shape(
        lambda key: create_train_state(key, built.model, built.tx, built.batch_shapes, built.mesh),
        jax.random.key(0),
    )
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=replicated), state
    )
    batch = {
        k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=built.batch_shardings[k])
        for k, s in built.batch_shapes.items()
    }
    t = time.perf_counter()
    lowered = built.step.lower(state, batch)
    t_lower = time.perf_counter() - t
    out = {"workload": cell.name, "microbatch": cell.traffic["microbatch"],
           "pairs_per_chip_per_step": cell.traffic["pairs_per_chip_per_step"],
           "trace_lower_s_on_this_host": round(t_lower, 2)}
    try:
        t = time.perf_counter()
        compiled = lowered.compile()
        out["compile_s_on_this_host"] = round(time.perf_counter() - t, 2)
    except Exception as e:  # noqa: BLE001 — the refusal is the result
        out["refused"] = str(e)[:1500]
        print(json.dumps(out, indent=1))
        return 1
    memory = compiled.memory_analysis()
    for field in harness.MEMORY_FIELDS:
        out[field] = getattr(memory, field, None)
    text = compiled.as_text()
    out["tpu_custom_calls"] = text.count("tpu_custom_call")
    for name in ("all-reduce", "collective-permute", "all-gather", "reduce-scatter"):
        out[name] = text.count(f" {name}(") + text.count(f" {name}-start(")
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
