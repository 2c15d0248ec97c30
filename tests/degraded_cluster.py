"""Cluster choreography shared by the tests.

One place boots a `LocalCluster`, fills and EC-encodes a volume and
destroys (`build_degraded_cluster`) or spreads (`chaos_encode_spread`) its
shards, so the serving, tracing, health, tail-path, incident, S3 and load
tests cannot drift apart in how they degrade a volume.
`benchmark/cluster.py` starts real processes and shares nothing with this
file.
"""
import asyncio
import os
import time

import numpy as np

from seaweedfs_tpu.operation import assign, upload_data
from seaweedfs_tpu.pb import Stub, channel, volume_server_pb2
from seaweedfs_tpu.server.cluster import LocalCluster
from seaweedfs_tpu.storage.ec.layout import TOTAL_SHARDS


async def _encode_and_mount(stub, vid):
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


async def build_degraded_cluster(
    base_dir: str,
    n_blobs: int = 64,
    blob_size=None,  # callable i -> bytes length; default varies sizes
    device_cache: bool = False,
    drop_shards: tuple = (0, 11),
    with_filer: bool = False,
    with_s3: bool = False,
    fill=None,  # async callable(cluster): more writes before the encode
    master_kwargs: dict | None = None,
) -> tuple:
    """THE canonical degrade choreography: boot a LocalCluster, fill ONE
    volume with blobs (and let `fill` write what it wants through the
    filer or S3), EC-encode + mount every volume that holds data,
    optionally pin the shards in the device cache, then destroy
    `drop_shards` so every read must reconstruct.  Returns (cluster,
    volume_server, blobs, vid): `vid` is the blobs' volume."""
    cluster = LocalCluster(
        base_dir=base_dir, n_volume_servers=1, pulse_seconds=1,
        ec_backend="native", with_filer=with_filer, with_s3=with_s3,
        master_kwargs=master_kwargs,
    )
    await cluster.start()
    vs = cluster.volume_servers[0]
    if device_cache:
        from seaweedfs_tpu.ops.rs_resident import (
            SHARD_QUANTUM,
            DeviceShardCache,
        )
        from seaweedfs_tpu.serving import ServingConfig

        # `fill` spreads data over several volumes: at the cache's own
        # 64 MiB a padded shard, three volumes overrun the budget and
        # the pin never completes
        cache = DeviceShardCache(
            budget_bytes=1 << 30,
            shard_quantum=SHARD_QUANTUM if fill is None else 1 << 22,
        )
        # injected after VolumeServer construction, so apply the serving
        # config here the way the constructor path does — BOTH knobs, or
        # the tests' pipeline shape drifts from a real server's
        cfg = ServingConfig()
        cache.layout = cfg.layout
        cache.pipeline.set_slots(cfg.pipeline_slots)
        # no pre-warm in CI: the XLA-fallback kernels compile in
        # milliseconds at first use, and the full warm plan (every count
        # bucket x size) would dominate a test's runtime
        cache.warm_sizes = ()
        vs.store.ec_device_cache = cache
    master = cluster.master.advertise_url
    rng = np.random.default_rng(17)
    if blob_size is None:
        blob_size = lambda i: 1500 + i * 613  # noqa: E731
    blobs, vid = {}, None
    for i in range(max(120, n_blobs * 12)):
        if len(blobs) >= n_blobs:
            break
        a = await assign(master)
        v = int(a.fid.split(",")[0])
        if vid is None:
            vid = v
        if v != vid:  # assigns round-robin over several volumes
            continue
        data = rng.integers(
            0, 256, blob_size(i), dtype=np.uint8
        ).tobytes()
        await upload_data(f"http://{a.url}/{a.fid}", data)
        blobs[a.fid] = data
    assert len(blobs) >= max(6, n_blobs // 2), "could not fill one volume"

    vids = [vid]
    if fill is not None:
        # the caller's own writes (S3 objects, filer files) land on
        # whichever volumes the master picks: encode every one with data
        await fill(cluster)
        vids = sorted(
            v.id
            for loc in vs.store.locations
            for v in loc.volumes.values()
            if v.info().file_count > 0
        )
    stub = Stub(channel(vs.grpc_url), volume_server_pb2, "VolumeServer")
    for v in vids:
        await _encode_and_mount(stub, v)
        await stub.VolumeUnmount(
            volume_server_pb2.VolumeUnmountRequest(volume_id=v)
        )
    if device_cache:
        cache = vs.store.ec_device_cache

        def pinned():
            return all(len(cache.shard_ids(v)) == TOTAL_SHARDS for v in vids)

        deadline = time.time() + 600
        while time.time() < deadline and not pinned():
            await asyncio.sleep(0.5)
        assert pinned(), "pin timeout"
        await asyncio.to_thread(
            lambda: [t.join(timeout=900) for t in vs.store._pin_threads]
        )
    # shard 0 holds every needle of a small volume (intervals start at
    # offset 0), so dropping it forces every read to reconstruct;
    # dropping a second shard leaves exactly 10 survivors
    for v in vids:
        for sid in drop_shards:
            await stub.VolumeEcShardsUnmount(
                volume_server_pb2.VolumeEcShardsUnmountRequest(
                    volume_id=v, shard_ids=[sid]
                )
            )
            if device_cache:
                vs.store.ec_device_cache.evict(v, sid)
            p = vs.store._ec_base(v, "") + f".ec{sid:02d}"
            if os.path.exists(p):
                os.remove(p)
    return cluster, vs, blobs, vid


async def chaos_encode_spread(cluster, vid, victim_idx):
    """EC-encode `vid` on its holder and spread the shards via the
    SHARED shell choreography (spread_ec_shards: copy -> mount ->
    source-unmount -> source-delete); server `victim_idx` gets the
    leading group (including shard 0, where a small volume's every
    needle lives), so that reads against the holder must fetch remote
    shards and killing the victim puts the DEGRADED reconstruct path on
    the reads.  Returns the holder (the front door for this volume)."""
    from seaweedfs_tpu.repair.executor import RepairEnv
    from seaweedfs_tpu.shell.command_ec import spread_ec_shards
    from seaweedfs_tpu.shell.command_env import TopoNode

    holder = next(
        vs for vs in cluster.volume_servers if vs.store.has_volume(vid)
    )
    stub = Stub(channel(holder.grpc_url), volume_server_pb2, "VolumeServer")
    await _encode_and_mount(stub, vid)

    def _tnode(vs):
        return TopoNode(
            url=vs.url, grpc_port=vs.grpc_port,
            data_center="dc1", rack="r1",
        )

    others = [vs for vs in cluster.volume_servers if vs is not holder]
    victim = cluster.volume_servers[victim_idx]
    assert victim is not holder, "victim must not be the front door"
    # victim first: it receives the leading group (shard 0 included)
    others.sort(key=lambda vs: 0 if vs is victim else 1)
    per = TOTAL_SHARDS // (len(others) + 1)
    targets = [
        (_tnode(vs), list(range(j * per, (j + 1) * per)))
        for j, vs in enumerate(others)
    ]  # holder keeps the trailing TOTAL_SHARDS - len(others)*per
    await spread_ec_shards(RepairEnv(), vid, "", _tnode(holder), targets)
    await stub.VolumeUnmount(
        volume_server_pb2.VolumeUnmountRequest(volume_id=vid)
    )
    return holder
