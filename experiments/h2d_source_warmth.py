#!/usr/bin/env python3
"""What the bulk codec worker's enqueue and fetch cost by WHERE the
batch it puts on the device comes from, on the chip this runs on (run it
through the chip tool; PERF.md PR 31).

One rebuild batch, [10, 4 MiB] -> [2, 4 MiB], through storage/ec/bulk.py
Codec on the pallas backend, one batch at a time and nothing else
running, 40 batches a case.  The parts are the codec's own
(ec_bulk_codec_seconds: stage / enqueue / fetch / unstack):

  staged           plain rows, the worker stages its own copy right
                   before the put (every pipeline before PR 31; encode
                   and verify since)
  direct, one      one stacked buffer put again and again: what the
                   device read last is what it reads next
  direct, eleven   eleven stacked buffers in turn, each 440 MiB of other
                   buffers ago: what a rebuild's payload is when the
                   worker gets it (read from the shard files several
                   batches earlier)
  direct, rewritten   eleven in turn, each written anew (a 40 MiB
                   memcpy, untimed) right before its put: a payload as
                   warm as a staged copy
"""
from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from seaweedfs_tpu.ops import gf256, rs_tpu  # noqa: E402
from seaweedfs_tpu.storage.ec import bulk  # noqa: E402

K, WIDTH, BATCHES, BUFFERS = 10, 4 << 20, 40, 11


def main() -> int:
    import jax

    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    present = [i for i in range(14) if i not in (3, 11)]
    rmat, _use = gf256.reconstruction_matrix(10, 14, present, [3, 11])
    codec = bulk.Codec(rmat, "pallas", threaded=True, pipeline="rebuild")
    groups = codec.segments(WIDTH)
    rng = np.random.default_rng(31)
    plain = rng.integers(0, 256, size=(K, WIDTH), dtype=np.uint8)
    stacked = rs_tpu.stack_segments(plain, groups).reshape(K, WIDTH)
    kept = [stacked.copy() for _ in range(BUFFERS)]
    bulk.POOL.keep = 1  # the worker's one staging buffer circulates
    want = codec.resolve(codec.submit(plain))  # compiles

    def parts() -> list[float]:
        return [c._value.get() for c in codec._part_seconds]

    def case(label: str, batch_of, direct: bool, before=None) -> None:
        for warm in range(3):
            codec.resolve(codec.submit(batch_of(warm), direct))
        t0, p0 = time.perf_counter(), parts()
        prep = 0.0
        for i in range(BATCHES):
            batch = batch_of(i)
            if before is not None:
                b0 = time.perf_counter()
                before(batch)
                prep += time.perf_counter() - b0
            out = codec.resolve(codec.submit(batch, direct))
        wall = (time.perf_counter() - t0 - prep) / BATCHES * 1e3
        ms = [(b - a) / BATCHES * 1e3 for a, b in zip(p0, parts())]
        assert np.array_equal(out, want)
        print(f"{label:18s}: stage {ms[0]:6.2f}  enqueue {ms[1]:6.2f}  "
              f"fetch {ms[2]:6.2f}  unstack {ms[3]:6.2f}  "
              f"a batch {wall:6.2f} ms", flush=True)

    try:
        for sweep in (1, 2):
            print(f"sweep {sweep}", flush=True)
            case("staged", lambda i: plain, False)
            case("direct, one", lambda i: kept[0], True)
            case("direct, eleven", lambda i: kept[i % BUFFERS], True)
            case("direct, rewritten", lambda i: kept[i % BUFFERS], True,
                 before=lambda batch: np.copyto(batch, stacked))
    finally:
        codec.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
