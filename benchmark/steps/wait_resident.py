"""Wait for every main volume's 14 shards to be pinned in HBM and its
AOT warm plan to finish."""
from __future__ import annotations

import time

from ..cluster import compile_cache_counts, say, wait_resident


async def run(ctx, timeout: float = 1100.0) -> None:
    t0 = time.monotonic()
    for vol in ctx.main_volumes():
        dev = await wait_resident(
            ctx.session, ctx.cluster, vol.vid, list(range(14)),
            vol.shard_size, timeout)
    resident = sum(d["used_bytes"] for d in dev["cache"]["per_device"])
    say(f"pin + warm plan: {time.monotonic() - t0:.1f} s, "
        f"{dev['aot']['compiled']} shapes AOT-compiled, "
        f"{compile_cache_counts(dev)}; {resident} bytes resident")
