"""Run the bulk verbs once on the small warm-up volume, so that every
program the window's verbs use (the encode batch, the full and the
partial rebuild batch) is compiled or loaded from the cache before the
window opens."""
from __future__ import annotations

import time

from ..cluster import say
from ..generators.bulk_verbs import encode_lose_rebuild


async def run(ctx) -> None:
    t0 = time.monotonic()
    for vol in ctx.volumes:
        if vol.role == "warmup":
            await encode_lose_rebuild(ctx, vol)
    say(f"bulk warm-up verbs: {time.monotonic() - t0:.1f} s")
