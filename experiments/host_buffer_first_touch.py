#!/usr/bin/env python3
"""What a bulk batch pays for a NEW host buffer against a KEPT one, on
the host this runs on (no JAX, no chip needed: run it on the chip tool's
machine to read that host's numbers, PERF.md PR 25).

Two statements of storage/ec/bulk.py, at an encode batch's [10, 1 MiB]
and a rebuild batch's [10, 4 MiB]:

  stage   the codec worker's segment-stacking copy of a batch
          (rs_tpu.stack_segments), into a new array each time against
          into one kept array
  read    the reader leg's fill of a batch from ten files in the page
          cache: os.pread to bytes and a copy into a new array (the old
          read_shard_rows), os.preadv straight into a new array, and
          os.preadv into a kept array

Prints one line per case: median and quartiles of 12 repeats, in ms.
glibc serves a request above its mmap threshold (at most 32 MiB) by a
new mapping whose pages fault on first touch; 10 MiB comes back from the
heap already touched once the threshold has grown.
"""
from __future__ import annotations

import os
import statistics
import sys
import tempfile
import time

import numpy as np

REPEATS = 12
GROUPS = 4
K = 10


def timed(fn) -> str:
    fn()
    took = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        took.append((time.perf_counter() - t0) * 1e3)
    q = statistics.quantiles(took, n=4)
    return f"median {statistics.median(took):8.3f} ms  q1 {q[0]:8.3f}  q3 {q[2]:8.3f}"


def main() -> int:
    rng = np.random.default_rng(25)
    with tempfile.TemporaryDirectory() as tmp:
        for width in (1 << 20, 4 << 20):
            label = f"[{K}, {width >> 20} MiB]"
            shards = rng.integers(0, 256, size=(K, width), dtype=np.uint8)
            stacked = shards.reshape(K, GROUPS, width // GROUPS).transpose(1, 0, 2)
            kept = np.empty(K * width, dtype=np.uint8)
            kept[:] = 0

            def stage_fresh():
                np.ascontiguousarray(stacked.reshape(GROUPS * K, -1)).reshape(-1)

            def stage_kept():
                np.copyto(kept.reshape(GROUPS, K, -1), stacked)

            print(f"stage {label} fresh : {timed(stage_fresh)}", flush=True)
            print(f"stage {label} kept  : {timed(stage_kept)}", flush=True)

            fds = []
            for i in range(K):
                path = os.path.join(tmp, f"s{width}_{i}")
                with open(path, "wb") as f:
                    f.write(shards[i].tobytes())
                fds.append(os.open(path, os.O_RDONLY))

            def read_bytes_fresh():
                out = np.empty((K, width), dtype=np.uint8)
                for j, fd in enumerate(fds):
                    buf = os.pread(fd, width, 0)
                    out[j, : len(buf)] = np.frombuffer(buf, dtype=np.uint8)

            def read_into_fresh():
                out = np.empty((K, width), dtype=np.uint8)
                for j, fd in enumerate(fds):
                    os.preadv(fd, [out[j]], 0)

            rows = kept.reshape(K, width)

            def read_into_kept():
                for j, fd in enumerate(fds):
                    os.preadv(fd, [rows[j]], 0)

            print(f"read  {label} bytes+copy, fresh : {timed(read_bytes_fresh)}",
                  flush=True)
            print(f"read  {label} preadv, fresh     : {timed(read_into_fresh)}",
                  flush=True)
            print(f"read  {label} preadv, kept      : {timed(read_into_kept)}",
                  flush=True)
            for fd in fds:
                os.close(fd)
    return 0


if __name__ == "__main__":
    sys.exit(main())
