"""After `encode`: seeded column windows of every main volume's 14 shard
files against the plain reference's encode of the kept .dat (`load` with
`keep_dat`), half of the windows in the large-block rows where the volume
has any, one of them across the boundary between the two tiers.

A full compare of a 16 GiB volume would take the reference minutes a
run; 32 windows of 1 MiB say whether `ec.encode` placed and encoded both
tiers as the upstream encoder does, and every GET of the window is still
compared with the seed's bytes.  The count of windows that differ goes to
the run's `compared` (limit 0) through `ctx.setup_compared`; the kept
.dat is removed afterwards.
"""
from __future__ import annotations

import asyncio
import os
import random
import time

import numpy as np

from ..cluster import check, say
from ..reference import rs_layout_plain, rs_plain


def pick_windows(shard_size: int, large_end: int, n: int, length: int,
                 seed) -> list[tuple[int, int]]:
    """-> [(start, length)] in shard-file columns: the first half in
    [0, large_end) where there is one, the rest past it, and one laid
    across `large_end`."""
    rng = random.Random(f"{seed}/encode_windows")
    length = min(length, shard_size)
    windows = []
    for i in range(n):
        lo, hi = 0, shard_size - length
        if large_end and i < n // 2:
            hi = max(0, large_end - length)
        elif large_end:
            lo = min(large_end, hi)
        windows.append((rng.randint(lo, hi), length))
    if 0 < large_end < shard_size:
        half = min(length // 2, large_end, shard_size - large_end)
        windows[-1] = (large_end - half, 2 * half)
    return windows


def differing(vol, windows) -> int:
    parity = rs_plain.coding_matrix()[rs_plain.DATA_SHARDS:]
    shards = [open(f"{vol.base}.ec{i:02d}", "rb") for i in range(14)]
    bad = 0
    try:
        with open(vol.kept_dat, "rb") as dat:
            for start, length in windows:
                want = rs_layout_plain.encode_window(
                    lambda at, n: os.pread(dat.fileno(), n, at),
                    vol.dat_size, start, length, parity)
                got = np.stack([
                    np.frombuffer(os.pread(f.fileno(), length, start),
                                  dtype=np.uint8) for f in shards])
                if not np.array_equal(got, want):
                    bad += 1
                    say(f"volume {vol.vid}: columns [{start}, "
                        f"{start + length}) of shards "
                        f"{np.flatnonzero((got != want).any(axis=1)).tolist()}"
                        " differ from the reference")
    finally:
        for f in shards:
            f.close()
    return bad


async def run(ctx, windows: int = 32, window_bytes: int = 1 << 20) -> None:
    t0 = time.monotonic()
    bad = total = in_large = 0
    for vol in ctx.main_volumes():
        check(vol.kept_dat, "check_encode_windows needs `load` with keep_dat")
        want_size = rs_layout_plain.shard_size_of(vol.dat_size)
        check(vol.shard_size == want_size, f"volume {vol.vid}: shard files "
              f"hold {vol.shard_size} bytes, the reference {want_size}")
        large_end = (rs_layout_plain.n_large_rows(vol.dat_size)
                     * rs_layout_plain.LARGE_BLOCK)
        picked = pick_windows(vol.shard_size, large_end, windows,
                              window_bytes, ctx.seed)
        bad += await asyncio.to_thread(differing, vol, picked)
        total += len(picked)
        in_large += sum(start < large_end for start, _ in picked)
        os.remove(vol.kept_dat)
        vol.kept_dat = ""
    ctx.setup_compared = {"encode_windows_differing": (bad, 0)}
    say(f"encode windows: {total} column windows of 14 shards against the "
        f"plain reference, {in_large} starting in a large-block row, {bad} "
        f"differing, in {time.monotonic() - t0:.1f} s")
