"""EC parity scrub (ec.scrub / VolumeEcShardsVerify): recompute parity
over a mounted volume's shards and count mismatching bytes.

Three layers: the CPU file scrub (encoder.verify_ec_files), the
device-resident scrub (rs_resident.scrub_volume — only a [4] mismatch
vector leaves the device), and the volume-server RPC end-to-end (the
path chip_smoke.py drives on the real TPU).  Reference analogue: the
read-verify passes of volume.fsck / ec.rebuild.
"""
import asyncio
import os

import numpy as np

from seaweedfs_tpu.ops import rs
from seaweedfs_tpu.ops.rs_resident import DeviceShardCache, scrub_volume
from seaweedfs_tpu.pb import Stub, channel, volume_server_pb2
from seaweedfs_tpu.storage.ec import encoder, layout


def run(coro):
    return asyncio.run(coro)


def _make_shards(tmp_path, mb=2, vid=7):
    base = str(tmp_path / str(vid))
    rng = np.random.default_rng(3)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, mb << 20, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, backend="cpu")
    return base


def test_file_scrub_clean_and_corrupt(tmp_path):
    base = _make_shards(tmp_path)
    mism, span = encoder.verify_ec_files(base, backend="cpu")
    assert mism == [0, 0, 0, 0]
    assert span == os.path.getsize(base + layout.to_ext(0))

    # one flipped byte in a PARITY shard -> exactly one mismatch there
    with open(base + layout.to_ext(12), "r+b") as f:
        f.seek(1234)
        b = f.read(1)
        f.seek(1234)
        f.write(bytes([b[0] ^ 0xFF]))
    mism, _ = encoder.verify_ec_files(base, backend="cpu")
    assert mism == [0, 0, 1, 0]

    # one flipped byte in a DATA shard -> that column's parity recomputes
    # differently in (almost surely) all four parity rows
    with open(base + layout.to_ext(3), "r+b") as f:
        f.seek(777)
        b = f.read(1)
        f.seek(777)
        f.write(bytes([b[0] ^ 0x5A]))
    mism, _ = encoder.verify_ec_files(base, backend="cpu")
    assert mism[2] >= 1 and sum(1 for v in mism if v >= 1) >= 3


def test_resident_scrub_matches_file_scrub(tmp_path):
    base = _make_shards(tmp_path)
    cache = DeviceShardCache(budget_bytes=1 << 30)
    for sid in range(layout.TOTAL_SHARDS):
        cache.put(7, sid, np.fromfile(base + layout.to_ext(sid), np.uint8))
    mism, span = scrub_volume(cache, 7)
    assert mism == [0, 0, 0, 0]
    assert span >= os.path.getsize(base + layout.to_ext(0))

    # corrupt the RESIDENT copy of a parity shard: the scrub sees memory,
    # not files
    bad = np.fromfile(base + layout.to_ext(11), np.uint8)
    bad[4096] ^= 0x01
    cache.put(7, 11, bad)
    mism, _ = scrub_volume(cache, 7)
    assert mism == [0, 1, 0, 0]
    cache.clear()


def test_scrub_rpc_end_to_end(tmp_path):
    """VolumeEcShardsVerify through a live volume server: the resident
    backend when the cache holds the volume, the CPU backend otherwise,
    and corruption detected through the same RPC."""
    from test_serving_e2e import _build_degraded_cluster

    async def go():
        cluster, vs, _ = await _build_degraded_cluster(
            tmp_path, n_blobs=6, device_cache=True, drop_shards=()
        )
        try:
            vid = next(iter(vs.store.ec_device_cache.resident_by_vid()))
            stub = Stub(channel(vs.grpc_url), volume_server_pb2, "VolumeServer")
            r = await stub.VolumeEcShardsVerify(
                volume_server_pb2.VolumeEcShardsVerifyRequest(volume_id=vid)
            )
            assert list(r.parity_mismatch_bytes) == [0, 0, 0, 0]
            assert r.backend == "device_resident"
            assert r.bytes_verified > 0 and r.seconds >= 0

            # corrupt one resident parity shard -> RPC reports it
            ev = vs.store.find_ec_volume(vid)
            bad = np.fromfile(
                ev.base_name + layout.to_ext(13), np.uint8
            )
            bad[100] ^= 0x40
            vs.store.ec_device_cache.put(vid, 13, bad)
            r = await stub.VolumeEcShardsVerify(
                volume_server_pb2.VolumeEcShardsVerifyRequest(volume_id=vid)
            )
            assert list(r.parity_mismatch_bytes) == [0, 0, 0, 1]

            # cache dropped -> same RPC serves from the files on the CPU
            vs.store.ec_device_cache.clear()
            r = await stub.VolumeEcShardsVerify(
                volume_server_pb2.VolumeEcShardsVerifyRequest(volume_id=vid)
            )
            assert list(r.parity_mismatch_bytes) == [0, 0, 0, 0]
            assert r.backend in ("native", "numpy")
        finally:
            await cluster.stop()

    run(go())


def test_scrub_shell_command(tmp_path):
    """`ec.scrub` reports OK for a clean co-located volume."""
    from test_serving_e2e import _build_degraded_cluster

    async def go():
        cluster, vs, _ = await _build_degraded_cluster(
            tmp_path, n_blobs=6, device_cache=False, drop_shards=()
        )
        try:
            from seaweedfs_tpu.shell.command_env import CommandEnv
            from seaweedfs_tpu.shell.commands import COMMANDS

            lines = []
            env = CommandEnv([cluster.master.advertise_url])
            env.write = lambda s: lines.append(s)
            # the mounted shards reach the master via the next heartbeat
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                lines.clear()
                await COMMANDS["ec.scrub"](env, [])
                if lines:
                    break
                await asyncio.sleep(0.3)
            assert any("OK" in l for l in lines), lines
        finally:
            await cluster.stop()

    run(go())


def test_auto_scrub_loop_detects_corruption(tmp_path):
    """-ec.scrub.intervalSeconds: the volume server's background scrub
    finds a corrupted parity shard and raises the corrupt-volume gauge;
    a clean pass later clears it."""
    import time as time_mod

    from seaweedfs_tpu import stats
    from seaweedfs_tpu.server.volume import VolumeServer

    base = _make_shards(tmp_path, vid=1)

    async def go():
        # minimal sidecars BEFORE construction: discovery scans at init
        from seaweedfs_tpu.storage.volume_info import save_volume_info

        save_volume_info(base + ".vif", {"version": 3})
        # graftlint: allow(async-blocking): test fixture touch, nothing
        # else shares this loop
        open(base + ".ecx", "ab").close()
        vs = VolumeServer(
            masters=[], directories=[str(tmp_path)], port=0, grpc_port=0,
            ec_backend="cpu", ec_scrub_interval_seconds=1,
        )
        await vs.start(heartbeat=False)
        try:
            deadline = time_mod.time() + 15
            while time_mod.time() < deadline:
                if stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 0:
                    break
                await asyncio.sleep(0.2)

            # corrupt a parity shard on disk -> next cycle flags it
            # graftlint: allow(async-blocking): 1-byte test patch, nothing
            # else shares this loop
            with open(base + layout.to_ext(10), "r+b") as f:
                f.seek(64)
                b = f.read(1)
                f.seek(64)
                f.write(bytes([b[0] ^ 0x80]))
            deadline = time_mod.time() + 20
            while time_mod.time() < deadline:
                if stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 1:
                    break
                await asyncio.sleep(0.2)
            assert stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 1

            # repair (restore the byte) -> gauge clears
            # graftlint: allow(async-blocking): 1-byte test patch, nothing
            # else shares this loop
            with open(base + layout.to_ext(10), "r+b") as f:
                f.seek(64)
                f.write(bytes([b[0]]))
            deadline = time_mod.time() + 20
            while time_mod.time() < deadline:
                if stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 0:
                    break
                await asyncio.sleep(0.2)
            assert stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 0
        finally:
            await vs.stop()

    run(go())
