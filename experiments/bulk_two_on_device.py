#!/usr/bin/env python3
"""What keeping two batches on the device is worth with nothing else
running (PERF.md PR 34): a rebuild's codec (two lost shards, the pallas
backend, `direct` payloads of [10, 4 MiB]) fed from kept buffers by this
script alone — no reader leg, no writer leg, no server.  Run it on the
chip tool's machine; it needs the chip.

First the three things a batch's fetch waits for, each alone and waited
out: the put of 40 MiB, the program on a resident input, the copy back
of 8 MiB.  Then the codec worker's cycle a batch, by its own part
counters (ec_bulk_codec_seconds), in two feeds: one batch at a time
(submit, resolve, submit: the worker never finds a successor, which is
the parent's order) and PIPELINE_DEPTH batches ahead as bulk.run's
caller keeps them (the worker enqueues n+1 before it fetches n).
"""
from __future__ import annotations

import os
import statistics
import sys
import time
from collections import deque

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCHES = 104  # a window's four rebuild verbs
WIDTH = 4 << 20


def median_ms(fn, repeats: int = 30) -> str:
    fn()
    took = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        took.append((time.perf_counter() - t0) * 1e3)
    q = statistics.quantiles(took, n=4)
    return (f"median {statistics.median(took):7.3f} ms  "
            f"q1 {q[0]:7.3f}  q3 {q[2]:7.3f}")


def main() -> int:
    import jax

    from seaweedfs_tpu.ops import gf256, rs_tpu
    from seaweedfs_tpu.stats import metrics
    from seaweedfs_tpu.storage.ec import bulk

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}; cores usable "
          f"{len(os.sched_getaffinity(0))}", flush=True)
    present = [i for i in range(14) if i not in (3, 11)]
    rmat, _use = gf256.reconstruction_matrix(10, 14, present, [3, 11])
    rng = np.random.default_rng(34)
    payloads = [rng.integers(0, 256, size=(10, WIDTH), dtype=np.uint8)
                for _ in range(bulk.PIPELINE_DEPTH + 1)]

    def parts() -> list[float]:
        return [metrics.VOLUME_SERVER_EC_BULK_CODEC_SECONDS.labels(
            pipeline="rebuild", part=part)._value.get()
            for part in metrics.EC_BULK_CODEC_PARTS]

    def pipelined() -> float:
        return metrics.VOLUME_SERVER_EC_BULK_PIPELINED_BATCHES.labels(
            pipeline="rebuild")._value.get()

    codec = bulk.Codec(rmat, "pallas", threaded=True, pipeline="rebuild")
    try:
        wants = [codec.resolve(codec.submit(p, direct=True))  # compiles
                 for p in payloads]

        groups = codec.segments(WIDTH)
        flat = payloads[0].reshape(-1)
        x = jax.block_until_ready(jax.device_put(flat))

        def program():
            return rs_tpu.apply_matrix_device_flat(
                codec._a_blk, x, k=groups * 10, m=groups * codec.rows,
                tile=rs_tpu.BLOCKDIAG_TILE, interpret=codec._interpret)

        print("put 40 MiB, waited out:     ", median_ms(
            lambda: jax.block_until_ready(jax.device_put(flat))), flush=True)
        print("program on a resident input:", median_ms(
            lambda: jax.block_until_ready(program())), flush=True)
        outs = deque(jax.block_until_ready(program()) for _ in range(32))
        print("copy back of 8 MiB:         ", median_ms(
            lambda: np.asarray(outs.popleft())), flush=True)

        for ahead in (1, bulk.PIPELINE_DEPTH, 1, bulk.PIPELINE_DEPTH):
            before, engaged, busy = parts(), pipelined(), codec.busy_s
            pending: deque = deque()
            t0 = time.perf_counter()
            first = last = None
            for n in range(BATCHES + ahead - 1):
                if n < BATCHES:
                    pending.append(codec.submit(
                        payloads[n % len(payloads)], direct=True))
                if len(pending) >= ahead or n >= BATCHES:
                    last = codec.resolve(pending.popleft())
                    if first is None:
                        first = last
            wall = (time.perf_counter() - t0) * 1e3 / BATCHES
            took = [(b - a) * 1e3 / BATCHES for a, b in zip(before, parts())]
            print(f"{ahead} ahead: {wall:7.3f} ms a batch; worker "
                  f"{(codec.busy_s - busy) * 1e3 / BATCHES:7.3f} = stage "
                  f"{took[0]:.3f} + enqueue {took[1]:.3f} + fetch "
                  f"{took[2]:.3f} + unstack {took[3]:.3f}; pipelined "
                  f"{int(pipelined() - engaged)} of {BATCHES}", flush=True)
            # in order: the first is payloads[0]'s, the last the last fed
            assert not pending
            np.testing.assert_array_equal(first, wants[0])
            np.testing.assert_array_equal(
                last, wants[(BATCHES - 1) % len(payloads)])
    finally:
        codec.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
