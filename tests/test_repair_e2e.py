"""Chaos e2e for the self-healing repair plane (seaweedfs_tpu/repair):
a real in-process cluster, real faults from the chaos harness
(loadgen/chaos.py), and the master's autonomous scheduler closing the
loop the reference leaves to a human in `weed shell`:

  * kill a volume server mid-operation -> the scheduler detects the
    missing shards and re-converges to all 14, byte-verified reads
    throughout;
  * corrupt a parity shard on disk -> the master-driven scrub sweep
    localizes it, the corrupt copy is dropped BEFORE the rebuild, and
    the volume returns to full redundancy;
  * partition a holder's heartbeats -> the node goes STALE and the
    scheduler re-establishes its shards on fresh nodes without
    gathering from the suspect;

plus the operator surface: the repair block of /cluster/health.json
and the volume.repair.status / pause / resume shell verbs.
"""
import asyncio
import io
import os
import time

import aiohttp
import numpy as np
import pytest

from seaweedfs_tpu.loadgen import ChaosInjector
from seaweedfs_tpu.operation import assign, upload_data
from seaweedfs_tpu.pb import Stub, channel, volume_server_pb2
from seaweedfs_tpu.repair import RepairConfig
from seaweedfs_tpu.server.cluster import LocalCluster
from seaweedfs_tpu.storage.ec import TOTAL_SHARDS


def run(coro):
    return asyncio.run(coro)


async def fetch(url):
    async with aiohttp.ClientSession() as s:
        async with s.get(url) as r:
            return r.status, await r.read()


def _vs_stub(vs):
    return Stub(channel(vs.grpc_url), volume_server_pb2, "VolumeServer")


async def _fill_one_volume(cluster, n_blobs=10):
    """Write blobs until one volume holds `n_blobs`; returns (vid,
    {fid: bytes})."""
    master = cluster.master.advertise_url
    rng = np.random.default_rng(41)
    blobs, vid = {}, None
    for i in range(n_blobs * 12):
        if len(blobs) >= n_blobs:
            break
        a = await assign(master)
        v = int(a.fid.split(",")[0])
        if vid is None:
            vid = v
        if v != vid:
            continue
        data = rng.integers(0, 256, 1200 + i * 97, dtype=np.uint8).tobytes()
        await upload_data(f"http://{a.url}/{a.fid}", data)
        blobs[a.fid] = data
    assert len(blobs) >= max(4, n_blobs // 2)
    return vid, blobs


async def _encode_and_spread(cluster, vid, spread=True):
    """EC-encode `vid` on its holder; when `spread`, distribute the 14
    shards over all servers (holder keeps the first group).  Returns
    the holder server."""
    holder = next(
        vs for vs in cluster.volume_servers if vs.store.has_volume(vid)
    )
    stub = _vs_stub(holder)
    await stub.VolumeMarkReadonly(
        volume_server_pb2.VolumeMarkReadonlyRequest(volume_id=vid)
    )
    await stub.VolumeEcShardsGenerate(
        volume_server_pb2.VolumeEcShardsGenerateRequest(volume_id=vid)
    )
    await stub.VolumeEcShardsMount(
        volume_server_pb2.VolumeEcShardsMountRequest(
            volume_id=vid, shard_ids=list(range(TOTAL_SHARDS))
        )
    )
    if spread:
        others = [vs for vs in cluster.volume_servers if vs is not holder]
        per = TOTAL_SHARDS // (len(others) + 1)
        start = TOTAL_SHARDS - per * len(others)
        for j, vs in enumerate(others):
            sids = list(range(start + j * per, start + (j + 1) * per))
            peer = _vs_stub(vs)
            await peer.VolumeEcShardsCopy(
                volume_server_pb2.VolumeEcShardsCopyRequest(
                    volume_id=vid, shard_ids=sids,
                    copy_ecx_file=True, copy_ecj_file=True,
                    copy_vif_file=True,
                    source_data_node=holder.grpc_url,
                )
            )
            await peer.VolumeEcShardsMount(
                volume_server_pb2.VolumeEcShardsMountRequest(
                    volume_id=vid, shard_ids=sids
                )
            )
            await stub.VolumeEcShardsUnmount(
                volume_server_pb2.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=sids
                )
            )
            for sid in sids:
                p = holder.store._ec_base(vid, "") + f".ec{sid:02d}"
                if os.path.exists(p):
                    os.remove(p)
    await stub.VolumeUnmount(
        volume_server_pb2.VolumeUnmountRequest(volume_id=vid)
    )
    return holder


def _held_sids(master, vid, exclude_urls=()) -> set:
    locs = master.topo.lookup_ec_shards(vid)
    if locs is None:
        return set()
    return {
        sid for sid, nodes in enumerate(locs.locations)
        if any(n.url not in exclude_urls for n in nodes)
    }


async def _wait_full_redundancy(
    master, vid, timeout=30.0, exclude_urls=()
) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if len(_held_sids(master, vid, exclude_urls)) == TOTAL_SHARDS:
            return time.monotonic() - t0
        await asyncio.sleep(0.2)
    raise TimeoutError(
        f"volume {vid} never reached full redundancy: "
        f"{sorted(_held_sids(master, vid, exclude_urls))}"
    )


async def _verify_reads(front, blobs):
    for fid, data in blobs.items():
        status, body = await fetch(f"http://{front.url}/{fid}")
        assert status == 200, fid
        assert body == data, f"read of {fid} not byte-exact"


def test_kill_volume_server_autonomous_reconvergence(tmp_path):
    """SIGKILL a shard holder mid-operation: the scheduler must rebuild
    its shards onto the survivors without an operator, and every read
    stays byte-verified before, during, and after."""

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=4, pulse_seconds=1,
            ec_backend="native",
            master_kwargs=dict(ec_repair=RepairConfig(
                interval_seconds=0.25, backoff_base_seconds=0.2,
            )),
        )
        await cluster.start()
        try:
            vid, blobs = await _fill_one_volume(cluster)
            front = await _encode_and_spread(cluster, vid)
            await asyncio.sleep(1.5)  # heartbeat deltas reach the master
            assert len(_held_sids(cluster.master, vid)) == TOTAL_SHARDS

            chaos = ChaosInjector(cluster)
            victim_idx = next(
                i for i, vs in enumerate(cluster.volume_servers)
                if vs is not front
            )
            victim_url = cluster.volume_servers[victim_idx].url
            await chaos.kill_volume_server(victim_idx)
            await asyncio.sleep(0.3)
            front._ec_locations.clear()
            # degraded but recoverable (the victim held < 4 shards),
            # and every read is byte-exact while it is
            assert len(_held_sids(cluster.master, vid)) >= 10
            await _verify_reads(front, blobs)

            # the repair plane converges on its own
            await _wait_full_redundancy(
                cluster.master, vid, exclude_urls=(victim_url,)
            )
            sched = cluster.master.repair
            # the census is whole as soon as the rebuilt shards are
            # mounted; the job is counted when its executor returns
            deadline = time.monotonic() + 10
            while (
                sched.totals["completed"] < 1
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.1)
            assert sched.totals["completed"] >= 1
            front._ec_locations.clear()
            await _verify_reads(front, blobs)

            # convergence is measured and visible on the status plane
            deadline = time.monotonic() + 10
            while (
                sched.last_time_to_healthy_s is None
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.2)
            st = sched.status()
            assert st["last_time_to_healthy_s"] is not None
            assert st["totals"]["completed"] >= 1

            # health.json carries the repair block
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://{cluster.master.url}/cluster/health.json"
                ) as r:
                    assert r.status == 200
                    doc = await r.json()
            assert doc["repair"]["enabled"]
            assert doc["repair"]["totals"]["completed"] >= 1
        finally:
            await cluster.stop()

    run(go())


def test_corrupt_shard_scrub_verdict_repair(tmp_path):
    """Bit-rot a parity shard on disk: the master's scrub sweep must
    localize it, drop the bad copy before rebuilding, and return the
    volume to full redundancy — reads byte-verified after."""

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=2, pulse_seconds=1,
            ec_backend="native",
            master_kwargs=dict(ec_repair=RepairConfig(
                interval_seconds=0.25, scrub_interval_seconds=0.5,
                backoff_base_seconds=0.2,
            )),
        )
        await cluster.start()
        try:
            vid, blobs = await _fill_one_volume(cluster, n_blobs=6)
            # keep all 14 shards on the holder: scrub needs a full set
            front = await _encode_and_spread(cluster, vid, spread=False)
            await asyncio.sleep(1.5)
            holder_idx = cluster.volume_servers.index(front)

            chaos = ChaosInjector(cluster)
            chaos.corrupt_shard(holder_idx, vid, shard_id=11)

            # scrub verdict -> corrupt drop -> rebuild -> full redundancy
            sched = cluster.master.repair
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if sched.totals["completed"] >= 1:
                    break
                await asyncio.sleep(0.2)
            assert sched.totals["completed"] >= 1, sched.status()
            await _wait_full_redundancy(cluster.master, vid)
            # the repaired copy lives somewhere, and reads are byte-exact
            front._ec_locations.clear()
            await _verify_reads(front, blobs)
            # the scrub sweep may transiently re-queue the volume while
            # the post-repair census settles; wait for the steady state
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                v = sched.status()["volumes"][str(vid)]
                if v["state"] in ("repaired", "healthy"):
                    break
                await asyncio.sleep(0.2)
            assert v["state"] in ("repaired", "healthy"), v
        finally:
            await cluster.stop()

    run(go())


def test_heartbeat_partition_stale_node_repair(tmp_path):
    """Partition a holder's heartbeats (stream alive, pulses stopped):
    the master flags it STALE and the scheduler re-establishes its
    shards on fresh nodes WITHOUT gathering from the suspect."""

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=3, pulse_seconds=1,
            ec_backend="native",
            master_kwargs=dict(ec_repair=RepairConfig(
                interval_seconds=0.25, backoff_base_seconds=0.2,
            )),
        )
        await cluster.start()
        try:
            vid, blobs = await _fill_one_volume(cluster, n_blobs=6)
            front = await _encode_and_spread(cluster, vid)
            await asyncio.sleep(1.5)
            chaos = ChaosInjector(cluster)
            victim_idx = next(
                i for i, vs in enumerate(cluster.volume_servers)
                if vs is not front
            )
            victim = cluster.volume_servers[victim_idx]
            chaos.partition_heartbeats(victim_idx)
            # staleness window = 2 pulse intervals
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                if victim.url in cluster.master.telemetry.stale_node_urls():
                    break
                await asyncio.sleep(0.2)
            assert victim.url in cluster.master.telemetry.stale_node_urls()

            # every shard ends up held by at least one FRESH node
            await _wait_full_redundancy(
                cluster.master, vid, exclude_urls=(victim.url,)
            )
            assert cluster.master.repair.totals["completed"] >= 1
            chaos.partition_heartbeats(victim_idx, partitioned=False)
            front._ec_locations.clear()
            await _verify_reads(front, blobs)
        finally:
            await cluster.stop()

    run(go())


def test_repair_shell_commands(tmp_path):
    """volume.repair.status / pause / resume against a live master."""
    from seaweedfs_tpu.shell import CommandEnv, run_command

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=1, pulse_seconds=1,
            master_kwargs=dict(ec_repair=RepairConfig(
                interval_seconds=0.25,
            )),
        )
        await cluster.start()
        try:
            out = io.StringIO()
            env = CommandEnv([cluster.master.advertise_url], out=out)
            await run_command(env, "volume.repair.pause")
            assert cluster.master.repair.paused
            await run_command(env, "volume.repair.status")
            text = out.getvalue()
            assert "PAUSED" in text
            await run_command(env, "volume.repair.resume")
            assert not cluster.master.repair.paused
            out.truncate(0)
            out.seek(0)
            await run_command(env, "volume.repair.status -json")
            import json

            doc = json.loads(out.getvalue())
            assert doc["enabled"] and not doc["paused"]
            assert "totals" in doc and "queue_depth" in doc
        finally:
            await cluster.stop()

    run(go())


def test_breaker_open_defers_repair_cycle(tmp_path):
    """With a volume degraded AND a fresh node reporting an open
    interactive breaker, the scheduler defers instead of repairing —
    the measurable 'repair never competes with the front door'."""

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=3, pulse_seconds=1,
            ec_backend="native",
            master_kwargs=dict(ec_repair=RepairConfig(
                interval_seconds=0.25, breaker_pause_seconds=1.0,
                backoff_base_seconds=0.2,
            )),
        )
        await cluster.start()
        try:
            vid, blobs = await _fill_one_volume(cluster, n_blobs=6)
            front = await _encode_and_spread(cluster, vid)
            await asyncio.sleep(1.5)

            # force the front door's interactive breaker OPEN before
            # the fault, so the first repair cycles meet it open
            qos = front.ec_dispatcher.qos
            from seaweedfs_tpu.serving.qos import INTERACTIVE

            br = qos._breakers[INTERACTIVE]
            for _ in range(br.trip_after + 1):
                br.record_rejection()
            br.cooldown_s = 4.0  # hold it open past a few pulses
            await asyncio.sleep(1.5)  # telemetry carries the state
            assert cluster.master.telemetry.breakers_open() >= 1
            # baseline, not assumed 0: a loaded full-suite box can
            # delay heartbeats past the staleness window during spin-up,
            # and the resulting spurious stale-node repair may complete
            # BEFORE the breaker trips — only post-trip launches matter
            completed_before = cluster.master.repair.totals["completed"]

            chaos = ChaosInjector(cluster)
            victim_idx = next(
                i for i, vs in enumerate(cluster.volume_servers)
                if vs is not front
            )
            victim_url = cluster.volume_servers[victim_idx].url
            await chaos.kill_volume_server(victim_idx)

            sched = cluster.master.repair
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if sched.totals["backoff_breaker"] >= 1:
                    break
                await asyncio.sleep(0.1)
            # the shed is measurable: cycles deferred, nothing launched
            # while the breaker was open
            assert sched.totals["backoff_breaker"] >= 1
            assert sched.totals["completed"] == completed_before

            # once the breaker closes, repair proceeds to convergence
            br.record_success()
            await asyncio.sleep(1.5)
            await _wait_full_redundancy(
                cluster.master, vid, timeout=30,
                exclude_urls=(victim_url,),
            )
            assert sched.totals["completed"] >= completed_before + 1
            front._ec_locations.clear()
            await _verify_reads(front, blobs)
        finally:
            await cluster.stop()

    run(go())
