"""Pin __graft_entry__'s bootstrap helpers.

The driver calls dryrun_multichip() directly; its bootstrap decision must never probe
an uninitialized backend (a fresh init would claim the host's accelerator first).
That logic leans on the private ``jax._src.xla_bridge._backends`` registry — these
tests pin that dependency so a jax upgrade that renames it fails loudly here instead
of silently forcing a redundant subprocess re-run.
"""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge


def test_xla_bridge_backends_registry_exists():
    from jax._src import xla_bridge

    assert hasattr(xla_bridge, "_backends")
    assert isinstance(xla_bridge._backends, dict)


def test_visible_device_count_sees_initialized_backend():
    # conftest already initialized the 8-device CPU backend for this process.
    jax.devices()
    assert ge._visible_device_count() == jax.device_count()


def test_with_host_device_count_replaces_stale_flag():
    assert (
        ge._with_host_device_count("--xla_force_host_platform_device_count=4", 8)
        == "--xla_force_host_platform_device_count=8"
    )
    assert ge._with_host_device_count("", 8) == (
        "--xla_force_host_platform_device_count=8"
    )
    out = ge._with_host_device_count("--xla_dump_to=/tmp/x", 8)
    assert "--xla_dump_to=/tmp/x" in out
    assert "--xla_force_host_platform_device_count=8" in out


@pytest.mark.slow
def test_dryrun_runs_in_process_when_devices_available(monkeypatch):
    # slow: ~107 s on the 1-core tier-1 host (the single biggest line in the
    # time-boxed gate, --durations=15) — the dryrun body itself runs in the
    # driver's own environment every round; the module's cheap structural
    # tests (bootstrap/device-count/flag handling) stay in standard.
    # With the backend live at >= n devices, no subprocess may be spawned.
    import subprocess

    # numpy imports numpy.testing LAZILY on first attribute access, and that
    # import probes SVE support via a subprocess ('lscpu') — pre-import it so
    # the monkeypatch below only sees subprocesses the dryrun itself spawns.
    import numpy.testing  # noqa: F401

    def _boom(*a, **k):  # pragma: no cover - would indicate a regression
        raise AssertionError("dryrun_multichip spawned a subprocess unnecessarily")

    monkeypatch.setattr(subprocess, "run", _boom)
    if jax.device_count() < 2:
        pytest.skip("needs the multi-device CPU conftest environment")
    ge.dryrun_multichip(2)
