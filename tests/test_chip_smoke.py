"""What the chip entry points do where there is no chip, and where the compile
cache goes. (That chip_smoke.py passes ON the chip is proven by running it
there — PERF.md's bring-up table; nothing here can show it.)"""

import os
import re
import subprocess
import sys

import jax

from distributed_sigmoid_loss_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_chip_means_nonzero_exit_and_no_result():
    """chip_smoke.py refuses before any compile and prints no result, in its
    timing mode too (no time from a CPU under a kernel's name). Both at once:
    the cost is two interpreter start-ups, side by side."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    commands = (("chip_smoke.py",), ("chip_smoke.py", "--time-attention"))
    procs = {
        command: subprocess.Popen(
            [sys.executable, os.path.join(REPO, command[0]), *command[1:]],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO,
        )
        for command in commands
    }
    out = {}
    for command, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=120)
        assert proc.returncode not in (0, None), (command, stdout, stderr[-500:])
        out[command] = (stdout, stderr)

    for command in commands:
        stdout, stderr = out[command]
        assert "platform=cpu" in stdout  # the banner names what it found
        assert "refusing to run" in stderr
        assert '"ok"' not in stdout and "==" not in stdout and "compile" not in stdout
        assert "us/" not in stdout


def test_compile_cache_dir_rule(monkeypatch):
    """A set JAX_COMPILATION_CACHE_DIR is left alone (jax reads it itself);
    unset, the cache is <checkout>/.jax_cache. Either way the key includes the
    program's metadata, with source files named relative to the checkout
    (tests/test_scopes.py says why)."""
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))
    metadata_in_key = ("jax_compilation_cache_include_metadata_in_key", True)
    relative_sources = (
        "jax_hlo_source_file_canonicalization_regex", "^" + re.escape(REPO + os.sep)
    )

    monkeypatch.setenv(compile_cache.CACHE_ENV, "/some/dir")
    assert compile_cache.configure_compile_cache() == "/some/dir"
    assert updates == [metadata_in_key, relative_sources]

    monkeypatch.delenv(compile_cache.CACHE_ENV)
    del updates[:]
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert updates == [metadata_in_key, relative_sources, ("jax_compilation_cache_dir", want)]
