"""Microbenchmark: does an int8xint8->int32 dot_general reach the v5e's 394-TOPS
MXU gear through XLA, and what do the quantize/dequantize passes around it cost?

Run on the real chip: ``python examples/microbench_int8_mxu.py``. Times four
variants (bf16; raw int8 with both operands pre-quantized; dynamic int8
quantizing both in-step; static int8 with weights pre-quantized) at a
serving-relevant GEMM shape (the b16 wi projection at batch 512, s=196:
M=100352) and prints achieved TOP/s so the int8 serving design can be
grounded in what the compiler actually emits.
"""

import jax
import jax.numpy as jnp
from jax import lax

from distributed_sigmoid_loss_tpu.ops.quant import int8_dot_general, quantize_int8
from distributed_sigmoid_loss_tpu.utils.profiling import time_step


def main():
    m, k, n = 100352, 768, 3072  # b16 wi projection at batch 512 (512*196 rows)
    flops = 2 * m * k * n
    x = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (k, n), jnp.bfloat16)
    xq, xs = quantize_int8(x, 1)
    wq, ws = quantize_int8(w, 0)
    dn = (((1,), (0,)), ((), ()))

    bf = jax.jit(lambda a, b: lax.dot_general(a, b, dn))
    raw8 = jax.jit(
        lambda a, b: lax.dot_general(a, b, dn, preferred_element_type=jnp.int32)
    )
    dyn8 = jax.jit(lambda a, b: int8_dot_general(a, b, dn))

    def static8(a, bq, bs):  # weights pre-quantized; activations dynamic
        aq, ascale = quantize_int8(a, 1)
        acc = lax.dot_general(aq, bq, dn, preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * ascale * jnp.squeeze(bs, 0)).astype(a.dtype)

    st8 = jax.jit(static8)

    for name, fn, args in [
        ("bf16", bf, (x, w)),
        ("raw int8 (pre-quantized both)", raw8, (xq, wq)),
        ("dynamic int8 (quantize both in-step)", dyn8, (x, w)),
        ("static int8 (weights pre-quantized)", st8, (x, wq, ws)),
    ]:
        dt = time_step(fn, *args, warmup=3, iters=20)
        print(f"{name:40s} {dt*1e3:8.2f} ms   {flops/dt/1e12:7.1f} TOP/s")


if __name__ == "__main__":
    main()
