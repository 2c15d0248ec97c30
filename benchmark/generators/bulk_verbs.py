"""One shell client running the bulk verbs over the loaded volumes, one
volume after the other: `ec.encode -volumeId v`, lose the
configuration's shards of v, `ec.rebuild -force`.

Losing the shards (VolumeEcShardsDelete, then waiting until the master's
topology shows the survivors only) is the harness's reset, paced by the
master's heartbeat pulse: the two rates divide by the seconds the client
waited inside the verbs, every verb of the window counted whole, so a
stall inside any verb shows and a slow heartbeat does not.  The window
ends with the last volume, or at the first volume boundary after
--seconds.
"""
from __future__ import annotations

import os
import time

from ..cluster import (check, ec_shards_rpc, say, scrape, series_sum, shell,
                       wait_master_sees_shards)
from ..steps.encode import encode_volume
from ..steps.stale_shard import zero_shard


async def batches_done(ctx, pipeline: str) -> int:
    samples = await scrape(ctx.session, ctx.cluster)
    return int(series_sum(samples, "ec_bulk_batches_total",
                          {"pipeline": pipeline}))


async def encode_lose_rebuild(ctx, vol) -> dict:
    """One volume's turn -> {"encode_s", "rebuild_s": seconds the client
    waited in each verb, "encode_batches", "rebuild_batches": device
    batches the server counted behind each}."""
    lost = ctx.config["lost_shards"]
    n0 = await batches_done(ctx, "encode")
    encode_s = await encode_volume(ctx, vol)
    encode_batches = await batches_done(ctx, "encode") - n0
    for sid in lost:
        # the encode's own output of the shards about to be lost stays
        # comparable under another name (a hard link: no bytes written)
        os.link(f"{vol.base}.ec{sid:02d}",
                os.path.join(ctx.cluster.keep_dir,
                             f"{vol.vid}.encoded.ec{sid:02d}"))
    await ec_shards_rpc(ctx.env, ctx.cluster, "Delete", vol.vid, lost)
    for sid in lost:
        check(not os.path.exists(f"{vol.base}.ec{sid:02d}"),
              f"shard {sid} file survived its delete")
    if ctx.control == "stale_shard" and vol.role == "main":
        zero_shard(vol, 10)
    await wait_master_sees_shards(
        ctx.session, ctx.cluster, vol.vid, 14 - len(lost))
    n0 = await batches_done(ctx, "rebuild")
    t0 = time.monotonic()
    out = await shell(ctx.env, "ec.rebuild -force")
    rebuild_s = time.monotonic() - t0
    check(f"ec volume {vol.vid}: rebuilt {sorted(lost)}" in out,
          f"ec.rebuild did not rebuild {sorted(lost)} of volume {vol.vid}")
    return {
        "encode_s": encode_s, "rebuild_s": rebuild_s,
        "encode_batches": encode_batches,
        "rebuild_batches": await batches_done(ctx, "rebuild") - n0,
    }


class Generator:
    def __init__(self, ctx, params: dict):
        self.ctx, self.params = ctx, params

    async def prepare(self) -> None:
        pass

    async def window(self, seconds: float, budget: float | None) -> dict:
        ctx = self.ctx
        lost = ctx.config["lost_shards"]
        before = await scrape(ctx.session, ctx.cluster)
        t0 = time.monotonic()
        done, turns, last = [], [], 0.0
        for vol in ctx.main_volumes():
            elapsed = time.monotonic() - t0
            if done and (elapsed >= seconds or (
                    budget is not None and elapsed + last > budget)):
                break
            turn = await encode_lose_rebuild(ctx, vol)
            say(f"volume {vol.vid}: ec.encode {turn['encode_s']:.3f} s "
                f"({turn['encode_batches']} batches), ec.rebuild "
                f"{turn['rebuild_s']:.3f} s ({turn['rebuild_batches']})")
            turns.append(turn)
            last = time.monotonic() - t0 - elapsed
            done.append(vol)
        encode_s = sum(t["encode_s"] for t in turns)
        rebuild_s = sum(t["rebuild_s"] for t in turns)
        window_s = time.monotonic() - t0
        after = await scrape(ctx.session, ctx.cluster)
        if ctx.enforce:
            ctx.check_bulk_on_device(before, after, "encode", "bulk")
            ctx.check_bulk_on_device(before, after, "rebuild", "repair")
        dat_bytes = sum(v.dat_size for v in done)
        rebuilt_bytes = sum(v.shard_size * len(lost) for v in done)
        mib = float(1 << 20)
        say(f"window: {len(done)} volume(s) in {window_s:.3f} s: "
            f"{dat_bytes} .dat bytes encoded in {encode_s:.3f} s of verbs, "
            f"{rebuilt_bytes} shard bytes rebuilt in {rebuild_s:.3f} s")
        return {
            "attempted": 2 * len(done),
            "failed": 0,
            "window_s": window_s,
            "done": done,
            "values": {
                "encode_mib_per_s": dat_bytes / mib / encode_s,
                "rebuild_mib_per_s": rebuilt_bytes / mib / rebuild_s,
            },
            "facts": {
                "encode_s": encode_s,
                "rebuild_s": rebuild_s,
                "encode_dat_bytes": dat_bytes,
                "rebuild_shard_bytes": sum(v.shard_size for v in done),
                "rebuild_lost_shards": len(lost),
                # the device batches of the window in the order they ran:
                # how a trace's programs are told apart by verb
                "verb_batches": [
                    [verb, t[verb + "_batches"]]
                    for t in turns for verb in ("encode", "rebuild")],
            },
            "compared": {},
        }
