"""Wait until a freshly launched cluster can take a write.

A volume server started as its own OS process registers with the master
on its first heartbeat, some time after both HTTP ports answer.  An
upload sent before that fails at assign ("no writable volumes"), so
whoever launches real processes — the multi-process test, chip_smoke.py —
polls the master first: until the expected volume servers are in the
topology, then until an assign succeeds (the master grows a volume on
the first one, which proves a writable volume exists).
"""
from __future__ import annotations

import asyncio
import time

import aiohttp


def topology_nodes(topology: dict) -> list[dict]:
    """The data nodes of a master /dir/status "Topology" document."""
    return [
        node
        for dc in topology.get("data_centers", [])
        for rack in dc.get("racks", [])
        for node in rack.get("nodes", [])
    ]


async def wait_cluster_ready(
    master_http: str, volume_servers: int = 1, timeout: float = 90.0
) -> dict:
    """Poll `master_http` (host:port) until `volume_servers` data nodes
    have registered and /dir/assign hands out a fid; -> that assign's
    JSON ({"fid", "url", ...}).  Raises TimeoutError naming what was
    still missing at the deadline."""
    deadline = time.monotonic() + timeout
    waiting_for = "the master to answer"
    async with aiohttp.ClientSession() as s:
        while time.monotonic() < deadline:
            try:
                async with s.get(f"http://{master_http}/dir/status") as r:
                    nodes = topology_nodes((await r.json())["Topology"])
                if len(nodes) < volume_servers:
                    waiting_for = (
                        f"{volume_servers} volume server(s) to register "
                        f"(master knows {len(nodes)})"
                    )
                else:
                    async with s.get(f"http://{master_http}/dir/assign") as r:
                        doc = await r.json()
                    if r.status == 200 and doc.get("fid"):
                        return doc
                    waiting_for = f"a writable volume ({doc.get('error')})"
            except (aiohttp.ClientError, asyncio.TimeoutError) as e:
                waiting_for = f"the master to answer ({e!r})"
            await asyncio.sleep(0.25)
    raise TimeoutError(
        f"cluster at {master_http} not ready after {timeout:.0f}s: "
        f"still waiting for {waiting_for}"
    )
