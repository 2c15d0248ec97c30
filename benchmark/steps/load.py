"""Fill the configuration's volumes (and its small warm-up volume, if it
has one) through the front door, from --seed."""
from __future__ import annotations

import os
import time

from ..cluster import check, say, volume_status
from ..dataset import load_volume, plan_needles
from ..harness import Volume


async def run(ctx, keep_dat: bool = False) -> None:
    from seaweedfs_tpu.operation.ready import wait_cluster_ready

    sizes_cfg = ctx.sizes
    wanted = [("main", sizes_cfg["volume_bytes"])] * sizes_cfg["volumes"]
    if sizes_cfg.get("warmup_volume_bytes"):
        wanted.insert(0, ("warmup", sizes_cfg["warmup_volume_bytes"]))
    await wait_cluster_ready(ctx.cluster.master_http, timeout=120)
    status = await volume_status(ctx.session, ctx.cluster)
    vids = sorted(v["id"] for v in status["Volumes"])
    if len(vids) < len(wanted):
        url = (f"http://{ctx.cluster.master_http}/vol/grow"
               f"?count={len(wanted) - len(vids)}")
        async with ctx.session.get(url) as r:
            check(r.status == 200, f"/vol/grow: HTTP {r.status}")
            vids = sorted(vids + (await r.json())["vids"])
    t0 = time.monotonic()
    for (role, target), vid in zip(wanted, vids):
        sizes = plan_needles(target, ctx.config["size_mix"])
        await load_volume(ctx.session, ctx.cluster, vid, ctx.seed, sizes)
        base = ctx.cluster.base(vid)
        dat_size = os.path.getsize(base + ".dat")
        check(dat_size >= target,
              f"{base}.dat holds {dat_size} bytes, target {target}")
        vol = Volume(vid=vid, role=role, sizes=sizes, dat_size=dat_size,
                     base=base)
        if keep_dat:
            # ec.encode deletes the .dat: keep its bytes under another name
            vol.kept_dat = os.path.join(ctx.cluster.keep_dir, f"{vid}.dat")
            os.link(base + ".dat", vol.kept_dat)
        ctx.volumes.append(vol)
    total = sum(v.dat_size for v in ctx.volumes)
    dt = time.monotonic() - t0
    say(f"loaded: {len(ctx.volumes)} volume(s), "
        f"{sum(len(v.sizes) for v in ctx.volumes)} needles, {total} bytes "
        f"of .dat in {dt:.1f} s ({total / dt / (1 << 20):.0f} MiB/s)")
