"""Closed loop of GETs: `clients` callers, each sends its next GET when
the last returned.

The mix fixes a pool of keys (`pool_seed`): every size of the mix equally
often, `lost_shard_share` of each size's keys having bytes on the lost
data shard (as many as the volume has).  --seed makes the needles' bytes
and the order: the whole pool is read in seeded shuffles, so every seed
sends the same reads in another order.  Every body
is compared, as it arrives, with the needle's bytes made again from the
seed (a memcmp against bytes generated during set-up).
"""
from __future__ import annotations

import asyncio
import math
import random
import time

from ..cluster import check, say, scrape, series_sum
from ..dataset import fid_of, needle_bytes, read_index
from ..work_counts import bytes_on_shard


def pick_pool(sizes, lost_bytes: dict, per_size: int, share: float,
              seed: int) -> list[int]:
    """`per_size` distinct keys of every size, up to `share` of them with
    bytes on the lost shard (chip_smoke.py's pick_reads rule)."""
    rng = random.Random(seed)
    by_size: dict[int, tuple[list[int], list[int]]] = {}
    for key, size in enumerate(sizes, start=1):
        by_size.setdefault(size, ([], []))[lost_bytes[key] == 0].append(key)
    pool = []
    for size in sorted(by_size):
        lost, healthy = by_size[size]
        rng.shuffle(lost)
        rng.shuffle(healthy)
        take = lost[: int(per_size * share) or 1]
        take += healthy[: per_size - len(take)]
        take += lost[len(take):][: per_size - len(take)]
        pool += take[:per_size]
    return pool


def percentile(sorted_values: list[float], q: float) -> float:
    return sorted_values[max(0, math.ceil(q * len(sorted_values)) - 1)]


class Generator:
    def __init__(self, ctx, params: dict):
        self.ctx, self.params = ctx, params
        self.vol = ctx.main_volumes()[0]
        self.pool: list[int] = []
        self.want: dict[int, bytes] = {}
        self.lost_bytes: dict[int, int] = {}
        self.records: list[tuple[float, int, bool]] = []
        self.http_failed = self.mismatched = 0

    async def prepare(self) -> None:
        """Set-up's share: the key pool, the bytes every GET is compared
        with, and a warm-up pass so that connections are open and every
        client has turned over before the window."""
        ctx, vol, params = self.ctx, self.vol, self.params
        lost_data = [s for s in ctx.config.get("lost_shards", []) if s < 10]
        index = await asyncio.to_thread(read_index, vol.base + ".ecx")
        check(len(index) == len(vol.sizes), f"{vol.base}.ecx lists "
              f"{len(index)} needles, {len(vol.sizes)} were written")
        self.lost_bytes = {
            key: sum(bytes_on_shard(off, ln, s) for s in lost_data)
            for key, (off, ln) in index.items()
        }
        per_size = min(params["pool_per_size"],
                       len(vol.sizes) // len(ctx.config["size_mix"]))
        # the pool is the mix's (one constant seed): every run reads the
        # same needles, so the same work; --seed orders them and makes
        # their bytes
        self.pool = pick_pool(vol.sizes, self.lost_bytes, per_size,
                              params["lost_shard_share"], params["pool_seed"])
        self.want = await asyncio.to_thread(lambda: {
            key: needle_bytes(ctx.seed, vol.vid, key, vol.sizes[key - 1])
            for key in self.pool})
        on_lost = sum(self.lost_bytes[k] > 0 for k in self.pool)
        say(f"read pool: {len(self.pool)} needles ({per_size} of each "
            f"size), {on_lost} with bytes on lost data shard(s) {lost_data}")
        warm = iter(self.sequence(-1)[: params["warmup_gets"]])
        await self.drive(lambda: next(warm, None))
        if ctx.enforce and not ctx.control:
            check(not (self.http_failed or self.mismatched),
                  "a warm-up GET failed")
        self.records.clear()
        self.http_failed = self.mismatched = 0

    def sequence(self, cycle: int) -> list[int]:
        order = list(self.pool)
        random.Random(f"{self.ctx.seed}/{cycle}").shuffle(order)
        return order

    async def drive(self, next_key) -> None:
        async def client():
            while (key := next_key()) is not None:
                await self.get(key)

        await asyncio.gather(
            *(client() for _ in range(self.params["clients"])))

    async def get(self, key: int) -> None:
        vol = self.vol
        url = f"http://{self.ctx.cluster.volume_http}/{fid_of(vol.vid, key)}"
        t0 = time.monotonic()
        try:
            async with self.ctx.session.get(url) as r:
                body = await r.read()
                ok = r.status == 200
                if not ok:
                    body = f"HTTP {r.status} {body[:120]!r}".encode()
        except Exception as e:  # noqa: BLE001 — a failed GET is counted
            ok, body = False, repr(e).encode()
        latency = time.monotonic() - t0
        if not ok:
            self.http_failed += 1
            if self.http_failed <= 3:
                say(f"GET {url} failed: {body.decode(errors='replace')}")
        elif body != self.want[key]:
            ok = False
            self.mismatched += 1
            if self.mismatched <= 3:
                say(f"GET {url}: body differs from what was written "
                    f"({len(body)} vs {len(self.want[key])} bytes)")
        self.records.append((latency, key, ok))

    async def window(self, seconds: float, budget: float | None) -> dict:
        ctx = self.ctx
        if budget is not None:
            seconds = min(seconds, budget)
        before = await scrape(ctx.session, ctx.cluster)
        t0 = time.monotonic()
        deadline = t0 + seconds
        cycle, queue = 0, []

        def next_key():
            nonlocal cycle, queue
            if time.monotonic() >= deadline:
                return None
            if not queue:
                queue = self.sequence(cycle)[::-1]
                cycle += 1
            return queue.pop()

        await self.drive(next_key)
        window_s = time.monotonic() - t0
        after = await scrape(ctx.session, ctx.cluster)
        memo = {r: int(series_sum(after, "ec_degraded_memo_total",
                                  {"result": r})
                       - series_sum(before, "ec_degraded_memo_total",
                                    {"result": r}))
                for r in ("hit", "miss")}
        say(f"reconstruct memo over the window: {memo}")

        n = len(self.records)
        good = sum(ok for _, _, ok in self.records)
        worst = max(lat for lat, _, _ in self.records)
        lats = sorted(lat if ok else worst for lat, _, ok in self.records)
        body_bytes = sum(len(self.want[k]) for _, k, ok in self.records if ok)
        say(f"window: {n} GETs in {window_s:.3f} s at "
            f"c={self.params['clients']}, {good} byte-equal, "
            f"{self.http_failed} failed, {self.mismatched} with a wrong "
            f"body; {body_bytes} body bytes; p50 "
            f"{percentile(lats, 0.5) * 1e3:.1f} ms, p99 "
            f"{percentile(lats, 0.99) * 1e3:.1f} ms")
        return {
            "attempted": n,
            "failed": n - good,
            "window_s": window_s,
            "values": {
                "rate": good / window_s,
                "p50_ms": percentile(lats, 0.50) * 1e3,
                "p99_ms": percentile(lats, 0.99) * 1e3,
            },
            "facts": {
                "gets": n,
                "reconstruct_lost_bytes": sum(
                    self.lost_bytes[k] for _, k, _ in self.records),
            },
            "compared": {
                "failed_gets": (self.http_failed, 0),
                "wrong_bodies": (self.mismatched, 0),
            },
        }
