"""The control's step: one surviving shard of every main volume is
replaced by a stale generation (zeros) behind the server's back, then
mounted again, so that whatever is reconstructed from it is not what was
written.  A run with this step has to come out `correct: false`."""
from __future__ import annotations

from ..cluster import (ec_shards_rpc, say, wait_master_sees_shards,
                       wait_resident)

CHUNK = 8 << 20


def zero_shard(vol, shard: int) -> None:
    with open(f"{vol.base}.ec{shard:02d}", "r+b") as f:
        for off in range(0, vol.shard_size, CHUNK):
            f.write(bytes(min(CHUNK, vol.shard_size - off)))


async def run(ctx, shard: int = 10) -> None:
    for vol in ctx.main_volumes():
        zero_shard(vol, shard)
        if ctx.cluster_has_cache():
            # the resident copy is what serves: pin the file again
            await ec_shards_rpc(ctx.env, ctx.cluster, "Unmount", vol.vid,
                                [shard])
            await wait_master_sees_shards(
                ctx.session, ctx.cluster, vol.vid, 13)
            await ec_shards_rpc(ctx.env, ctx.cluster, "Mount", vol.vid,
                                [shard])
            await wait_resident(ctx.session, ctx.cluster, vol.vid,
                                list(range(14)), vol.shard_size, 300)
        say(f"CONTROL: shard {shard} of volume {vol.vid} replaced by zeros")
