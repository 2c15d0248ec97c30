"""Test harness config: force CPU JAX with a virtual 8-device mesh.

Mirrors the reference's approach of testing multi-node logic in-process
(topology_test.go constructs Topology + fake heartbeats instead of spinning
clusters): we test multi-chip sharding on a virtual CPU mesh instead of
requiring a pod.  Execution on the chip is chip_smoke.py's job; the
kernels' compiles for a described v5e are tests/test_tpu_compile.py.

This is the ONE place that steers JAX to the CPU: the environment
variable for subprocesses the tests spawn, and jax.config for this
process in case JAX was imported before this file.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture(autouse=True, scope="session")
def _lockwatch_sweep():
    """Opt-in suite-wide lock-order sweep: SWFS_LOCKWATCH=1 instruments
    every lock the suite creates (tests/lockwatch.py) and fails the run
    at teardown on any observed acquisition-order cycle — the dynamic
    complement of graftlint's static GL104 that reaches through
    callbacks and executor hops.  Off by default: instrumenting every
    stdlib lock adds measurable overhead to the full tier-1 run."""
    if os.environ.get("SWFS_LOCKWATCH") != "1":
        yield
        return
    import lockwatch

    with lockwatch.watch() as w:
        yield
    w.assert_no_cycles()


@pytest.fixture(autouse=True, scope="session")
def _viewguard_sweep():
    """Opt-in suite-wide view-lifetime sweep: SWFS_VIEWGUARD=1 wraps the
    zero-copy/staging buffer sources (tests/viewguard.py) and fails the
    run on any view that outlives its buffer's reuse or whose bytes
    drift while a holder is still reading — the dynamic complement of
    graftlint's GL109/GL110.  Off by default: fingerprinting every
    zero-copy payload adds per-read overhead to the tier-1 run."""
    if os.environ.get("SWFS_VIEWGUARD") != "1":
        yield
        return
    import viewguard

    with viewguard.watch() as g:
        yield
    g.assert_clean()
