"""Device-side timing via the JAX profiler.

`block_until_ready()` wall clock includes dispatch and host scheduling;
the profiler's device-stream events are the device's own account.  We
run N dispatches under `jax.profiler.trace` and average the TPU-side
`jit_*` executable durations.

Used by bench.py.  A device time exists only on the device: with no TPU
this raises instead of handing back a wall clock under the same name.
"""
from __future__ import annotations

import collections
import glob
import gzip
import json
import shutil
import tempfile


def device_avg_ms(fn, n: int = 10, warmup: int = 1) -> float:
    """Average device execution time in ms of the jitted callable `fn`
    (no-arg thunk returning a jax.Array)."""
    import jax

    r = None
    for _ in range(warmup):
        r = fn()
    if r is not None:
        r.block_until_ready()

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "device_avg_ms needs a TPU (JAX's default backend is "
            f"{jax.default_backend()!r}): a host wall clock is not a "
            "device time"
        )

    d = tempfile.mkdtemp(prefix="swfs_devtime_")
    try:
        with jax.profiler.trace(d):
            for _ in range(n):
                r = fn()
            r.block_until_ready()
        traces = sorted(glob.glob(d + "/plugins/profile/*/*.trace.json.gz"))
        if not traces:
            raise RuntimeError("profiler produced no trace")
        with gzip.open(traces[-1]) as fh:
            tr = json.load(fh)
        ev = tr["traceEvents"]
        pids = {
            e["pid"]: e["args"].get("name", "")
            for e in ev
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        durs = collections.defaultdict(float)
        counts = collections.defaultdict(int)
        for e in ev:
            if (
                e.get("ph") == "X"
                and "TPU" in pids.get(e.get("pid"), "")
                and e["name"].startswith("jit_")
            ):
                durs[e["name"]] += e["dur"]
                counts[e["name"]] += 1
        if not durs:
            raise RuntimeError("no TPU executable events in trace")
        # Sum across all executables the thunk launched, averaged over n runs.
        total_us = sum(durs.values())
        runs = max(counts.values())
        return total_us / runs / 1e3
    finally:
        shutil.rmtree(d, ignore_errors=True)
