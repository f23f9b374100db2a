"""The CPU rehearsal of run.py, on one and on four virtual devices: the same
control flow as a measurement, `correct` checked, and no device metric printed."""

import json
import os
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = os.path.join(BENCH_DIR, "run.py")


def run(*args, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, RUN, *args], capture_output=True, text=True, timeout=600, env=env
    )


@pytest.mark.parametrize("cell, devices", [("tiny-mb8x2", 1), ("tiny-looped-mb8x2", 1), ("tiny-bs8-dp4", 4)])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_prints_no_device_metric(cell, devices, trace):
    proc = run("--rehearse", "--workload", cell, "--seed", "3", "--seconds", "1", "--trace", trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == devices
    assert "busy_s" not in result["device"]
    earlier = [json.loads(line) for line in proc.stdout.splitlines()[:-1]]
    assert [info["info"] for info in earlier] == ["correct"]  # no time, rate or size from a CPU
    # Each number `correct` compared beside its limit: the line's last key and stderr's last lines.
    compared = result["compared"]
    assert set(compared) == {"embed_err", "grad_err", "loss_err", "sample_loss_err", "nonfinite_losses", "recompiles"}
    assert all(value <= limit for value, limit in compared.values())
    assert compared["grad_err"][1] == (0.125 if "looped" in cell else 0.06)  # the configuration's own reference's
    last = proc.stderr.strip().splitlines()[-len(compared):]
    assert [line.split()[2] for line in last] == list(compared) and all("limit" in line for line in last)


def test_measurement_refuses_without_a_listed_tpu():
    proc = run("--workload", "b16-bs256", "--seed", "0", "--seconds", "1", "--trace", "0",
               env_extra={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refused" in proc.stderr


def test_unknown_workload_is_refused():
    proc = run("--rehearse", "--workload", "no-such-cell")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_memory_watch_keeps_what_was_held():
    """What the chip holds falls back once the job frees its state and its step
    (b16-bs256, PR 22: 10.89 GB held, 3.26 GB the high-water mark of live arrays
    alone); the watch keeps the most it saw, and a job that never sampled is an
    error, not a small number."""
    import types

    import harness

    stats = {"bytes_in_use": 3078e6, "bytes_reserved": 7813e6, "peak_bytes_in_use": 3263e6}
    device = types.SimpleNamespace(memory_stats=lambda: dict(stats), platform="tpu",
                                   device_kind="TPU v5 lite")
    watch = harness.MemoryWatch([device, types.SimpleNamespace(memory_stats=lambda: None)])
    with pytest.raises(RuntimeError, match="sample"):
        harness.device_report([device], watch)
    assert watch.sample() == 10891e6
    stats.update(bytes_in_use=1e6, bytes_reserved=0)  # the job has returned
    assert watch.sample() == 10891e6


SAMPLES_WHILE_HELD = """
import sys, json
sys.path.insert(0, {bench_dir!r})
import harness, run
seen = []
sample = harness.MemoryWatch.sample
def watched(self):
    import jax
    seen.append(sum(a.nbytes for a in jax.live_arrays()))
    return sample(self)
harness.MemoryWatch.sample = watched
run.main(["--rehearse", "--workload", "tiny-mb8x2", "--seed", "3", "--seconds", "1", "--trace", "0"])
import jax
print(json.dumps({{"seen": seen, "after": sum(a.nbytes for a in jax.live_arrays())}}))
"""


def test_the_job_samples_memory_while_it_holds_its_state():
    """The fault the driver found in PR 22: the allocator was read after the job
    had returned. Every sample is taken with the train state and the batch live."""
    proc = subprocess.run(
        [sys.executable, "-c", SAMPLES_WHILE_HELD.format(bench_dir=BENCH_DIR)],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(got["seen"]) >= 2  # after the warm-up and after the window
    assert min(got["seen"]) > 500_000 > got["after"]  # tiny's state + batch: 0.96 MB; freed on return
