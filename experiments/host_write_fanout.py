#!/usr/bin/env python3
"""How many threads should write one bulk batch's shard rows, on the
host this runs on (no JAX, no chip needed: run it on the host the chip
sits in to read that host's numbers; PERF.md has them for a v5e host).

The statement measured is storage/ec/bulk.py's writer leg: a batch's
rows, each appended to its own shard file through a buffered file
object after an all-zero scan (`row.any()`, write_or_seek), batch after
batch into the same files as an encode or a rebuild does.  Two shapes:
encode's fourteen rows of 1 MiB (ten data rows out of the reader's
payload, four parity rows), and rebuild's two rows of 4 MiB.  `inline`
is one thread writing the rows one after another (the old writer leg);
`N threads` hands one task a row to a ThreadPoolExecutor of N
and waits for all of them before the next batch.  `tobytes` copies each
row into a new bytes object first (the old leg), `buffer`
writes from the row's own memory.

Every case writes BATCHES batches into fresh files, times each batch,
then fsyncs and deletes the files untimed, so the next case starts on
a quiet page cache.  Prints the host's cores, then one line per case,
every case twice: median and quartiles of the batches, in ms.
"""
from __future__ import annotations

import os
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor, wait

import numpy as np

BATCHES = 30
SHAPES = {"encode  [14, 1 MiB]": (14, 1 << 20),
          "rebuild [2, 4 MiB] ": (2, 4 << 20)}
THREADS = (2, 3, 4, 5, 7, 14)


def write_row(f, row: np.ndarray, copy: bool) -> None:
    if row.any():
        f.write(row.tobytes() if copy else row)
    else:
        f.seek(len(row), os.SEEK_CUR)


def case(tmp: str, rows: np.ndarray, threads: int, copy: bool) -> str:
    files = [open(os.path.join(tmp, f"s{j}"), "wb")
             for j in range(len(rows))]
    pool = ThreadPoolExecutor(max_workers=threads) if threads else None
    took = []
    try:
        for _ in range(BATCHES):
            t0 = time.perf_counter()
            if pool is None:
                for f, row in zip(files, rows):
                    write_row(f, row, copy)
            else:
                wait([pool.submit(write_row, f, row, copy)
                      for f, row in zip(files, rows)])
            took.append((time.perf_counter() - t0) * 1e3)
    finally:
        if pool is not None:
            pool.shutdown()
        for f in files:
            f.flush()
            os.fsync(f.fileno())
            f.close()
            os.remove(f.name)
    q = statistics.quantiles(took, n=4)
    return (f"median {statistics.median(took):8.3f} ms  "
            f"q1 {q[0]:8.3f}  q3 {q[2]:8.3f}")


def main() -> int:
    print(f"cores: os.cpu_count() {os.cpu_count()}, usable "
          f"{len(os.sched_getaffinity(0))}", flush=True)
    rng = np.random.default_rng(39)
    kept = {label: rng.integers(0, 256, size=shape, dtype=np.uint8)
            for label, shape in SHAPES.items()}
    with tempfile.TemporaryDirectory() as tmp:
        for sweep in (1, 2):  # twice over: a neighbour's burst shows
            for label, rows in kept.items():
                for n in (0, *THREADS):
                    if n > len(rows):
                        continue
                    for copy in (True, False):
                        how = f"{n:2d} threads" if n else "inline    "
                        src = "tobytes" if copy else "buffer "
                        print(f"sweep {sweep} write {label} {how} {src} : "
                              f"{case(tmp, rows, n, copy)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
