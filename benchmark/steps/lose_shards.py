"""Lose the configuration's shards of every main volume: delete the
files through VolumeEcShardsDelete and wait until exactly the survivors
are resident."""
from __future__ import annotations

import os

from ..cluster import check, ec_shards_rpc, say, wait_resident


async def run(ctx) -> None:
    lost = ctx.config["lost_shards"]
    survivors = [s for s in range(14) if s not in lost]
    for vol in ctx.main_volumes():
        await ec_shards_rpc(ctx.env, ctx.cluster, "Delete", vol.vid, lost)
        for sid in lost:
            check(not os.path.exists(f"{vol.base}.ec{sid:02d}"),
                  f"shard {sid} file survived its delete")
        await wait_resident(ctx.session, ctx.cluster, vol.vid, survivors,
                            vol.shard_size, 120)
        say(f"volume {vol.vid}: lost shards {lost}, {len(survivors)} "
            "survivors resident")
