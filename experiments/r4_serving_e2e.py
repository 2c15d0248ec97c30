"""Full-stack serving proof on the REAL TPU: HTTP degraded reads through
the volume server's EcReadBatcher -> Store.read_ec_needles_batch ->
EcVolume resident cache -> the fused Pallas reconstruct kernel.

Shape: write blobs into a volume, ec.encode + mount shards, pin them in
HBM (ec_device_cache), delete two shards from disk so reads MUST
reconstruct, then read every blob back over plain HTTP and time a
concurrent burst (the batcher's coalescing path).  Byte-exactness is
asserted against the original blobs.
"""
import asyncio
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def main():
    import aiohttp
    import numpy as np

    from seaweedfs_tpu.operation import assign, upload_data
    from seaweedfs_tpu.ops import rs_tpu
    from seaweedfs_tpu.pb import Stub, channel, volume_server_pb2
    from seaweedfs_tpu.server.cluster import LocalCluster
    from seaweedfs_tpu.storage.ec.layout import TOTAL_SHARDS

    assert rs_tpu.on_tpu(), "this drive needs the real TPU"
    out = {"on_tpu": True}

    # exercise the production compile-cache path (JAX_COMPILATION_CACHE_DIR,
    # else the fixed in-checkout path): run this script twice and compare
    # the first-touch latencies
    from seaweedfs_tpu.ops.rs_resident import enable_persistent_compile_cache

    out["compile_cache"] = enable_persistent_compile_cache()

    tmp = tempfile.mkdtemp(prefix="serving_e2e_")
    cluster = LocalCluster(
        base_dir=tmp, n_volume_servers=1, pulse_seconds=1, ec_backend="pallas",
    )
    await cluster.start()
    try:
        vs = cluster.volume_servers[0]
        # pin mounted EC shards in HBM (the -ec.device.cache.mb flag path)
        from seaweedfs_tpu.ops.rs_resident import DeviceShardCache

        vs.store.ec_device_cache = DeviceShardCache(budget_bytes=2 << 30)

        master = cluster.master.advertise_url
        rng = np.random.default_rng(11)
        blobs = {}
        vid = None
        for i in range(150):
            if len(blobs) >= 12:
                break
            a = await assign(master)
            v = int(a.fid.split(",")[0])
            if vid is None:
                vid = v
            if v != vid:  # assigns round-robin over several volumes
                continue
            data = rng.integers(0, 256, 2000 + i * 731, dtype=np.uint8).tobytes()
            await upload_data(f"http://{a.url}/{a.fid}", data)
            blobs[a.fid] = data
        assert len(blobs) >= 8, "need a handful of needles in one volume"
        out["needles"] = len(blobs)

        stub = Stub(channel(vs.grpc_url), volume_server_pb2, "VolumeServer")
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
        await stub.VolumeUnmount(
            volume_server_pb2.VolumeUnmountRequest(volume_id=vid)
        )
        # wait for the async HBM pin + kernel warm to finish
        deadline = time.time() + 300
        while time.time() < deadline:
            if len(vs.store.ec_device_cache.shard_ids(vid)) == TOTAL_SHARDS:
                break
            await asyncio.sleep(1.0)
        resident = len(vs.store.ec_device_cache.shard_ids(vid))
        out["resident_shards"] = resident
        assert resident == TOTAL_SHARDS, "shards never became resident"

        # force DEGRADED reads: drop two shards from disk AND device.
        # Shard 0 holds every needle of a small volume (intervals start at
        # offset 0), so removing it makes EVERY read reconstruct.
        ev = vs.store.find_ec_volume(vid)
        for sid in (0, 11):
            await stub.VolumeEcShardsUnmount(
                volume_server_pb2.VolumeEcShardsUnmountRequest(
                    volume_id=vid, shard_ids=[sid]
                )
            )
            vs.store.ec_device_cache.evict(vid, sid)
            base = vs.store._ec_base(vid, "")
            p = base + f".ec{sid:02d}"
            if os.path.exists(p):
                os.remove(p)

        async with aiohttp.ClientSession() as sess:
            async def read(fid):
                async with sess.get(f"http://{vs.url}/{fid}") as r:
                    assert r.status == 200, (fid, r.status)
                    return await r.read()

            # sequential correctness pass
            t0 = time.perf_counter()
            for fid, want in blobs.items():
                got = await read(fid)
                assert got == want, f"{fid}: degraded read corrupt"
            out["sequential_s"] = round(time.perf_counter() - t0, 2)

            # concurrent bursts: the batcher coalesces into fused calls.
            # burst 1 still pays jit compiles for this volume's interval
            # shapes; bursts 2-3 are the warm serving steady state.
            fids = list(blobs) * 4
            for trial in (1, 2, 3):
                t0 = time.perf_counter()
                results = await asyncio.gather(*(read(f) for f in fids))
                burst_s = time.perf_counter() - t0
                for f, got in zip(fids, results):
                    assert got == blobs[f]
                out[f"burst{trial}_ms_per_read"] = round(
                    burst_s / len(fids) * 1e3, 2
                )
            # warm sequential (single-read latency, no coalescing)
            lats = []
            for fid in blobs:
                t0 = time.perf_counter()
                await read(fid)
                lats.append(time.perf_counter() - t0)
            out["warm_single_ms_p50"] = round(
                sorted(lats)[len(lats) // 2] * 1e3, 2
            )
            out["burst_reads"] = len(fids)
        print(json.dumps(out))
    finally:
        await cluster.stop()


asyncio.run(main())
