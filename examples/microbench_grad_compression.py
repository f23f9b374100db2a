"""Microbenchmark: per-step COMPUTE cost of the DCN gradient-compression wire
formats (parallel/compression.py), on one chip.

The collectives need >= 2 slices, but the quantize/sparsify halves run per
device and their cost lands on every training step — this measures that
overhead at real gradient scale (a b16-shaped gradient tree, ~110M f32 entries) so the
feature's price is a measured number, not a guess. The
tree below sums to ~110M entries — b16's 86M tower params plus the
32k-vocab embedding table's gradient.

Run on the real chip: ``python examples/microbench_grad_compression.py``.
"""

import jax
import jax.numpy as jnp

from distributed_sigmoid_loss_tpu.parallel.compression import (
    dequantize_tensor_int8,
    quantize_tensor_int8,
    sparsify_topk,
)
from distributed_sigmoid_loss_tpu.utils.profiling import time_step


def main():
    # b16-shaped gradient leaves: the dominant tensor shapes (MLP, qkv/out,
    # embedding table) — ~110M entries total, printed below.
    shapes = (
        [(768, 3072)] * 12 + [(3072, 768)] * 12          # MLP
        + [(768, 768)] * 48                              # qkv/out x 12
        + [(32000, 768), (196, 768), (768, 512)]         # embeds, pos, proj
    )
    keys = jax.random.split(jax.random.key(0), len(shapes))
    tree = [jax.random.normal(k, s, jnp.float32) * 1e-3
            for k, s in zip(keys, shapes)]
    n = sum(t.size for t in tree)
    print(f"tree: {len(tree)} tensors, {n/1e6:.1f}M f32 entries "
          f"({n*4/1e6:.0f} MB)")

    int8_rt = jax.jit(lambda tr: [
        dequantize_tensor_int8(*quantize_tensor_int8(t)) for t in tr
    ])
    topk_approx = jax.jit(lambda tr: [
        sparsify_topk(t, max(1, t.size // 100)) for t in tr
    ])
    topk_exact = jax.jit(lambda tr: [
        sparsify_topk(t, max(1, t.size // 100), approximate=False)
        for t in tr
    ])

    for name, fn in [
        ("int8 quantize+dequantize", int8_rt),
        ("topk-1% approx_max_k (default)", topk_approx),
        ("topk-1% exact top_k", topk_exact),
    ]:
        dt = time_step(fn, tree, warmup=3, iters=10)
        print(f"{name:32s} {dt*1e3:7.2f} ms/step "
              f"({n*4/dt/1e9:.0f} GB/s effective)")


if __name__ == "__main__":
    main()
