"""`closed_loop_get` for volumes of either striping: the pool and the
work count are taken from benchmark/reference/rs_layout_plain.py, which
knows the 1 GB large-block rows of a volume above 10 GB and is rs_plain's
1 MB striping below it.

The loop, the clients, the comparison of every body and the window are
closed_loop_get's own.  What differs is the pool (still fixed by
`pool_seed`, still `pool_per_size` keys of every size):

  region_split      the share of each size's keys whose record starts in
                    a large-block row; the rest start in the 1 MB rows.
                    Taken as 0 for a volume without large rows.
  lost_shard_share  the share of each (size, region) group with bytes on
                    a lost data shard.  0 means none: no pool needle has
                    a byte on a lost shard (closed_loop_get's `or 1`
                    keeps one a size).

A group that cannot be filled as asked ends the run: the pool is the
traffic, and a smaller or another one would be another cell (a CPU
rehearsal's few needles are dealt out as far as they go).  Where a
set-up step left a count of its own on the run (`ctx.setup_compared`,
steps/check_encode_windows.py) it is compared with the window's counts.
"""
from __future__ import annotations

import asyncio
import random

from ..cluster import check, say
from ..dataset import needle_bytes, read_index
from ..reference import rs_layout_plain
from .closed_loop_get import Generator as ClosedLoopGet


def pick_pool(groups: dict, per_size: int, region_split: float,
              lost_share: float, seed: int, strict: bool = True) -> list[int]:
    """`groups` is {size: {in_large_row: ([keys with lost bytes], [keys
    without])}}.  -> `per_size` keys of every size: of each size
    `region_split` from the large-row region, of each (size, region)
    group `lost_share` with bytes on the lost shard, exactly; without
    `strict` as many of each kind as the volume has."""
    rng = random.Random(seed)
    pool = []
    for size in sorted(groups):
        from_large = round(per_size * region_split)
        for in_large, want in ((True, from_large),
                               (False, per_size - from_large)):
            lost, healthy = groups[size].get(in_large, ([], []))
            lost, healthy = sorted(lost), sorted(healthy)
            rng.shuffle(lost)
            rng.shuffle(healthy)
            n_lost = round(want * lost_share)
            check(not strict or (
                len(lost) >= n_lost and len(healthy) >= want - n_lost),
                  f"size {size}, large-row region {in_large}: the volume "
                  f"has {len(lost)} keys with and {len(healthy)} without "
                  f"bytes on a lost shard, the pool asks for {n_lost} and "
                  f"{want - n_lost}")
            take = lost[:n_lost] + healthy[: want - n_lost]
            spare = lost[n_lost:] + healthy[want - n_lost:]
            pool += take + spare[: want - len(take)]
    return pool


class Generator(ClosedLoopGet):
    async def prepare(self) -> None:
        """closed_loop_get's set-up share, with the layout-aware pool."""
        ctx, vol, params = self.ctx, self.vol, self.params
        lost_data = [s for s in ctx.config.get("lost_shards", []) if s < 10]
        index = await asyncio.to_thread(read_index, vol.base + ".ecx")
        check(len(index) == len(vol.sizes), f"{vol.base}.ecx lists "
              f"{len(index)} needles, {len(vol.sizes)} were written")
        large_end = (rs_layout_plain.n_large_rows(vol.dat_size)
                     * rs_layout_plain.DATA_SHARDS
                     * rs_layout_plain.LARGE_BLOCK)
        groups: dict = {}
        for key, (off, ln) in index.items():
            self.lost_bytes[key] = sum(
                rs_layout_plain.bytes_on_shard(vol.dat_size, off, ln, s)
                for s in lost_data)
            by_region = groups.setdefault(vol.sizes[key - 1], {})
            by_region.setdefault(off < large_end, ([], []))[
                self.lost_bytes[key] == 0].append(key)
        per_size = min(params["pool_per_size"],
                       len(vol.sizes) // len(ctx.config["size_mix"]))
        self.pool = pick_pool(
            groups, per_size,
            params.get("region_split", 0.0) if large_end else 0.0,
            params["lost_shard_share"], params["pool_seed"],
            strict=ctx.enforce)
        self.want = await asyncio.to_thread(lambda: {
            key: needle_bytes(ctx.seed, vol.vid, key, vol.sizes[key - 1])
            for key in self.pool})
        on_lost = sum(self.lost_bytes[k] > 0 for k in self.pool)
        in_large = sum(index[k][0] < large_end for k in self.pool)
        say(f"read pool: {len(self.pool)} needles ({per_size} of each "
            f"size), {on_lost} with bytes on lost data shard(s) "
            f"{lost_data}, {in_large} starting in a large-block row "
            f"(the .dat's first {large_end} bytes)")
        warm = iter(self.sequence(-1)[: params["warmup_gets"]])
        await self.drive(lambda: next(warm, None))
        if ctx.enforce and not ctx.control:
            check(not (self.http_failed or self.mismatched),
                  "a warm-up GET failed")
        self.records.clear()
        self.http_failed = self.mismatched = 0

    async def window(self, seconds: float, budget: float | None) -> dict:
        result = await super().window(seconds, budget)
        result["compared"].update(getattr(self.ctx, "setup_compared", {}))
        return result
