"""`ec.encode` every main volume through the shell verb, as set-up (the
serving cells: the verb is not what they measure)."""
from __future__ import annotations

import os
import time

from ..cluster import check, say, scrape, shell


async def encode_volume(ctx, vol) -> float:
    """One `ec.encode -volumeId` -> seconds the client waited."""
    t0 = time.monotonic()
    out = await shell(ctx.env, f"ec.encode -volumeId {vol.vid}")
    dt = time.monotonic() - t0
    check(f"ec encoded volume {vol.vid}" in out, "ec.encode did not finish")
    vol.shard_size = os.path.getsize(vol.base + ".ec00")
    return dt


async def run(ctx) -> None:
    before = await scrape(ctx.session, ctx.cluster)
    for vol in ctx.main_volumes():
        dt = await encode_volume(ctx, vol)
        say(f"set-up ec.encode of volume {vol.vid}: {dt:.1f} s")
    after = await scrape(ctx.session, ctx.cluster)
    ctx.check_bulk_on_device(before, after, "encode", "bulk")
