"""Concurrent-load harness for the volume-server / S3 front door.

It drives closed-loop HTTP and S3 readers with zipf-skewed keys,
hot-volume contention, slow-client dribble, and connection churn against
a RUNNING cluster, byte-verifies every read, and reports counts plus
client-side latency percentiles.  Consumed two ways:

  * `python -m seaweedfs_tpu loadtest` — the weed-benchmark-style CLI
    against any live cluster;
  * tests/test_loadgen.py — the drivers in-process against the tests'
    degraded cluster, so the harness itself can't rot.

The benchmark (`benchmark/generators/`) has its own closed-loop driver and
does not use this one (ROADMAP C1).

Reference: weed/command/benchmark.go ships the same kind of driver
(`weed benchmark`); this one adds the adversarial client behaviors the
front door's stall budget and QoS admission exist for.
"""
from .workload import LoadScenario, ZipfPicker, zipf_ranks
from .driver import (
    LoadResult,
    run_http_load,
    run_mixed_http_load,
    run_s3_load,
)
from .chaos import ChaosInjector

__all__ = [
    "ChaosInjector",
    "LoadResult",
    "LoadScenario",
    "ZipfPicker",
    "run_http_load",
    "run_mixed_http_load",
    "run_s3_load",
    "zipf_ranks",
]
