"""Trace capture + offline summarization (utils/profiling.py).

SURVEY.md §5 tracing plan: jax.profiler traces; summarize_trace turns a capture
into the op-family time table PERF.md's where-the-time-goes section uses,
without TensorBoard.
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_sigmoid_loss_tpu.utils.profiling import (
    summarize_trace,
    throughput,
    time_step,
)


def test_trace_and_summarize(tmp_path):
    d = str(tmp_path / "tr")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    float(f(x))  # compile outside the capture
    with jax.profiler.trace(d):
        for _ in range(3):
            float(f(x))
    summary = summarize_trace(d, top=5)
    assert summary, "no tracks found"
    for track, rows in summary.items():
        assert len(rows) <= 5
        for fam, ms, share in rows:
            assert ms >= 0 and 0.0 <= share <= 1.0
    # The matmul shows up on some track (fused or named dot_general).
    all_fams = {fam for rows in summary.values() for fam, _, _ in rows}
    assert any("dot" in f_ or "fusion" in f_ or "jit" in f_.lower()
               for f_ in all_fams), all_fams


def test_summarize_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        summarize_trace(str(tmp_path / "nope"))


def test_time_step_and_throughput():
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((64,))
    dt = time_step(f, x, warmup=1, iters=3)
    assert dt > 0
    assert throughput(f, x, items_per_call=64, warmup=1, iters=3) > 0
