#!/usr/bin/env python3
"""How many threads should read one rebuild batch's ten shard files, on
the host this runs on (no JAX, no chip needed: run it on the chip tool's
machine to read that host's numbers, PERF.md PR 31).

The statement measured is storage/ec/bulk.py read_shard_rows: ten
os.preadv of 4 MiB out of the page cache into one KEPT [10, 4 MiB]
buffer, a call a file, as plain rows (one iovec a call) and in the
block-diagonal kernel's segment-stacked order (four iovecs a call, shard
i's segment s at row s*10 + i).  `inline` is the caller's own loop, one
call after another (the reader leg before PR 31); `N threads` hands the
ten calls to a ThreadPoolExecutor of N and waits for all of them.

Prints the host's cores, then one line per case, every case twice:
median and quartiles of 30 repeats, in ms.
"""
from __future__ import annotations

import os
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor, wait

import numpy as np

REPEATS = 30
GROUPS = 4
K = 10
WIDTH = 4 << 20
THREADS = (1, 2, 3, 4, 5, 10)


def timed(fn) -> str:
    fn()
    took = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        fn()
        took.append((time.perf_counter() - t0) * 1e3)
    q = statistics.quantiles(took, n=4)
    return (f"median {statistics.median(took):8.3f} ms  "
            f"q1 {q[0]:8.3f}  q3 {q[2]:8.3f}")


def main() -> int:
    print(f"cores: os.cpu_count() {os.cpu_count()}, usable "
          f"{len(os.sched_getaffinity(0))}", flush=True)
    rng = np.random.default_rng(31)
    kept = np.empty(K * WIDTH, dtype=np.uint8)
    kept[:] = 0
    layouts = {
        "plain  ": kept.reshape(1, K, WIDTH),
        "stacked": kept.reshape(GROUPS, K, WIDTH // GROUPS),
    }
    with tempfile.TemporaryDirectory() as tmp:
        fds = []
        for i in range(K):
            path = os.path.join(tmp, f"s{i}")
            with open(path, "wb") as f:
                f.write(rng.integers(
                    0, 256, size=WIDTH, dtype=np.uint8).tobytes())
            fds.append(os.open(path, os.O_RDONLY))
        iovs = {label: [list(rows[:, j]) for j in range(K)]
                for label, rows in layouts.items()}
        pools = {n: ThreadPoolExecutor(max_workers=n) for n in THREADS}
        for sweep in (1, 2):  # twice over: a neighbour's burst shows
            for n in (0, *THREADS):
                for label, iov_of in iovs.items():

                    def inline():
                        for fd, iov in zip(fds, iov_of):
                            os.preadv(fd, iov, 0)

                    def fanned():
                        wait([pools[n].submit(os.preadv, fd, iov, 0)
                              for fd, iov in zip(fds, iov_of)])

                    how = f"{n:2d} threads" if n else "inline    "
                    print(f"sweep {sweep} read [{K}, 4 MiB] {label} {how} : "
                          f"{timed(fanned if n else inline)}", flush=True)
        for pool in pools.values():
            pool.shutdown()
        for fd in fds:
            os.close(fd)
    return 0


if __name__ == "__main__":
    sys.exit(main())
