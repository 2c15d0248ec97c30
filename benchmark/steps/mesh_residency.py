"""After `lose_shards`: hold the configuration's residency guarantee to
what the server itself reports, and print what the pin cost.

Every main volume's survivors are resident (`lose_shards` waited for
that), its placement is the one the configuration states
(`residency.placement`), the cache spans `residency.devices` devices and
none of them holds under `residency.min_device_share` of the resident
bytes (`/status` `Device.cache.per_device`).  The pin thread's seconds
per volume and phase (`ec_pin_seconds_total`: read, stage, h2d, warm)
are printed among set-up's facts where the program counts them.  A CPU
rehearsal runs on one device and only reports.
"""
from __future__ import annotations

from ..cluster import PREFIX, check, device_status, say, scrape


async def run(ctx) -> None:
    want = ctx.config["residency"]
    dev = await device_status(ctx.session, ctx.cluster)
    cache = dev["cache"]
    per_device = [d["used_bytes"] for d in cache["per_device"]]
    total = sum(per_device)
    shares = [b / total for b in per_device] if total else []
    say(f"residency: {total} bytes over {len(per_device)} device(s), "
        f"shares {[round(s, 4) for s in shares]}; "
        + "; ".join(f"volume {vid}: placement {v['placement']}, "
                    f"{len(v['resident_shards'])} shards"
                    for vid, v in cache["volumes"].items()))
    samples = await scrape(ctx.session, ctx.cluster)
    pin = {dict(labels)["volume"] + "/" + dict(labels)["phase"]: round(v, 2)
           for (name, labels), v in samples.items()
           if name == PREFIX + "ec_pin_seconds_total"}
    if pin:
        say(f"pin seconds by volume/phase: {pin}")
    if not ctx.enforce:
        return
    check(len(per_device) == want["devices"], f"the cache spans "
          f"{len(per_device)} device(s), the configuration {want['devices']}")
    for vol in ctx.main_volumes():
        placed = cache["volumes"][str(vol.vid)]["placement"]
        check(placed == want["placement"], f"volume {vol.vid} is placed "
              f"{placed!r}, the configuration says {want['placement']!r}")
    check(min(shares) >= want["min_device_share"], f"a device holds "
          f"{min(shares):.3f} of the resident bytes, under "
          f"{want['min_device_share']}")
