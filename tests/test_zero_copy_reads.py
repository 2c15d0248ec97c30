"""Zero-copy read-path suite (r13): the memoryview parse must be
byte-identical to the copying parse at every layer — unit (Needle),
e2e whole-needle, range, and degraded (reconstructed) HTTP reads — and
the zero-copy route must keep response_copy_bytes_total at exactly 0.
Plus the slow-client guard: a dribbling reader is disconnected inside
its stall budget instead of holding the response open."""
import asyncio
import time

import aiohttp
import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.storage.needle import CrcError, Needle


def run(coro):
    return asyncio.run(coro)


def _copy_bytes():
    return stats.REGISTRY.get_sample_value(
        "SeaweedFS_volumeServer_response_copy_bytes_total"
    ) or 0.0


# ------------------------------------------------------------------- unit


def test_from_bytes_zero_copy_equals_copying():
    n = Needle(
        id=0xABC, cookie=7, data=b"payload" * 100, name=b"f.bin",
        mime=b"application/x-thing", last_modified=1700000000,
        pairs=b'{"k":"v"}',
    )
    raw = n.to_bytes()
    a = Needle.from_bytes(raw)
    b = Needle.from_bytes(raw, copy=False)
    assert isinstance(a.data, bytes) and isinstance(b.data, memoryview)
    assert bytes(b.data) == a.data
    for attr in ("id", "cookie", "name", "mime", "pairs", "last_modified",
                 "checksum", "flags", "size"):
        assert getattr(a, attr) == getattr(b, attr), attr
    # the view really aliases the source buffer (no hidden copy)
    assert b.data.obj is raw


def test_from_bytes_zero_copy_over_bytearray_and_crc():
    n = Needle(id=1, cookie=2, data=b"x" * 1000)
    raw = bytearray(n.to_bytes())
    m = Needle.from_bytes(raw, copy=False)
    assert bytes(m.data) == b"x" * 1000
    # the corruption below is DELIBERATE: under a SWFS_VIEWGUARD sweep,
    # release the export first so the sanitizer doesn't (correctly!)
    # flag this fixture as a stale-byte serve
    import viewguard

    vg = viewguard.current()
    if vg is not None:
        vg.release(m.data)
    raw[20] ^= 0xFF  # corrupt the payload under the view
    with pytest.raises(CrcError):
        Needle.from_bytes(bytes(raw))


def test_from_bytes_zero_copy_tombstone_and_v1():
    t = Needle(id=5, cookie=0, size=-1)
    import struct

    hdr = struct.pack(">IQi", 0, 5, -1)
    parsed = Needle.from_bytes(hdr, copy=False)
    assert parsed.size == -1 and parsed.data == b""
    v1 = Needle(id=9, cookie=1, data=b"abc")
    raw1 = v1.to_bytes(version=1)
    p1 = Needle.from_bytes(raw1, version=1, copy=False)
    assert isinstance(p1.data, memoryview) and bytes(p1.data) == b"abc"
    assert t.size == -1


# ------------------------------------------------------------ e2e serving


def test_zero_copy_http_reads_byte_equal_and_copyless(tmp_path):
    """Whole-needle, range, and degraded (every read here reconstructs:
    two shards are destroyed) HTTP reads must be byte-identical between
    the zero-copy and the copying path — and the zero-copy route must
    add exactly 0 to response_copy_bytes_total while the copying route
    visibly pays."""
    from degraded_cluster import build_degraded_cluster

    async def go():
        cluster, vs, blobs, _vid = await build_degraded_cluster(
            str(tmp_path), n_blobs=6, device_cache=True
        )
        try:
            cfg = vs.ec_dispatcher.cfg
            fid = next(iter(blobs))
            want = blobs[fid]
            results = {}
            async with aiohttp.ClientSession() as sess:
                for mode in ("zero_copy", "copying"):
                    cfg.zero_copy = mode == "zero_copy"
                    c0 = _copy_bytes()
                    whole, ranged = {}, {}
                    for f, data in blobs.items():
                        async with sess.get(f"http://{vs.url}/{f}") as r:
                            assert r.status == 200
                            whole[f] = await r.read()
                        lo, hi = 100, min(900, len(data) - 1)
                        async with sess.get(
                            f"http://{vs.url}/{f}",
                            headers={"Range": f"bytes={lo}-{hi}"},
                        ) as r:
                            assert r.status == 206, r.status
                            assert r.headers["Content-Range"] == (
                                f"bytes {lo}-{hi}/{len(data)}"
                            )
                            ranged[f] = (lo, hi, await r.read())
                    # suffix range: last N bytes, spec-valid Content-Range
                    async with sess.get(
                        f"http://{vs.url}/{fid}",
                        headers={"Range": "bytes=-64"},
                    ) as r:
                        assert r.status == 206
                        assert await r.read() == want[-64:]
                        assert r.headers["Content-Range"] == (
                            f"bytes {len(want) - 64}-{len(want) - 1}"
                            f"/{len(want)}"
                        )
                    # unsatisfiable range: 416, never an empty 206
                    async with sess.get(
                        f"http://{vs.url}/{fid}",
                        headers={
                            "Range": f"bytes={len(want) + 5}-{len(want) + 9}"
                        },
                    ) as r:
                        assert r.status == 416
                        assert r.headers["Content-Range"] == (
                            f"bytes */{len(want)}"
                        )
                    results[mode] = (whole, ranged, _copy_bytes() - c0)
            zc_whole, zc_rng, zc_copied = results["zero_copy"]
            cp_whole, cp_rng, cp_copied = results["copying"]
            for f, data in blobs.items():
                assert zc_whole[f] == data  # degraded read, byte-exact
                assert cp_whole[f] == data
                lo, hi, body = zc_rng[f]
                assert body == data[lo : hi + 1]
                assert zc_rng[f] == cp_rng[f]
            assert zc_copied == 0, (
                f"zero-copy route copied {zc_copied} bytes"
            )
            assert cp_copied > 0
            assert fid and want  # coverage fixture sanity
        finally:
            await cluster.stop()
            from seaweedfs_tpu.pb.rpc import close_all_channels

            await close_all_channels()

    run(go())


# --------------------------------------------------------- slow-client guard


def test_dribbling_client_releases_server_resources_at_budget(tmp_path):
    """A reader draining an 8MB body at a dribble must stop costing the
    SERVER anything once the per-response stall budget lapses: the
    handler aborts (response_stall_aborts_total +1) and the download
    byte-lease goes back to 0 while the dribbler is still dribbling —
    it can keep draining kernel-buffered TCP data, but no handler, no
    lease, and no needle buffer are held for it.  A concurrent fast
    reader is served byte-exact throughout."""
    from seaweedfs_tpu.operation import assign, upload_data
    from seaweedfs_tpu.server.cluster import LocalCluster

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=1, pulse_seconds=1,
        )
        await cluster.start()
        drib = None
        try:
            vs = cluster.volume_servers[0]
            cfg = vs.ec_dispatcher.cfg
            cfg.stall_budget_seconds = 1.0
            cfg.stall_min_rate_kbps = 1 << 20  # budget ≈ the base second
            # track the download byte-lease (LocalCluster leaves the
            # throttle off; the lease is the held-resource probe)
            vs.download_limiter.limit = 64 << 20
            payload = bytes(range(256)) * (32 * 1024)  # 8MB
            a = await assign(cluster.master.advertise_url)
            await upload_data(f"http://{a.url}/{a.fid}", payload)

            stalls0 = stats.REGISTRY.get_sample_value(
                "SeaweedFS_volumeServer_response_stall_aborts_total"
            ) or 0.0
            dribbling = asyncio.Event()

            async def dribble():
                reader, writer = await asyncio.open_connection(
                    vs.ip, vs.port
                )
                writer.write(
                    f"GET /{a.fid} HTTP/1.1\r\n"
                    f"Host: {vs.url}\r\nConnection: close\r\n\r\n".encode()
                )
                await writer.drain()
                got = 0
                try:
                    while True:
                        chunk = await reader.read(1024)
                        if not chunk:
                            break
                        got += len(chunk)
                        dribbling.set()
                        await asyncio.sleep(0.05)  # ~20KB/s
                except ConnectionResetError:
                    pass  # the stall guard aborted us: expected
                finally:
                    writer.close()
                return got

            drib = asyncio.create_task(dribble())
            await asyncio.wait_for(dribbling.wait(), timeout=30)
            # give the 1s budget time to lapse, then probe the server
            deadline = time.perf_counter() + 10
            while time.perf_counter() < deadline:
                stalls = stats.REGISTRY.get_sample_value(
                    "SeaweedFS_volumeServer_response_stall_aborts_total"
                )
                if stalls == stalls0 + 1 and vs.download_limiter.in_flight == 0:
                    break
                await asyncio.sleep(0.2)
            assert stats.REGISTRY.get_sample_value(
                "SeaweedFS_volumeServer_response_stall_aborts_total"
            ) == stalls0 + 1, "stall guard never fired"
            assert vs.download_limiter.in_flight == 0, (
                "dribbler still holds the download byte-lease"
            )
            assert not drib.done()  # ...while the client is STILL dribbling
            # bystander: served fully and byte-exact after the abort
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"http://{vs.url}/{a.fid}") as r:
                    assert r.status == 200
                    assert await r.read() == payload
        finally:
            if drib is not None:
                drib.cancel()
                try:
                    await drib
                except asyncio.CancelledError:
                    pass
            await cluster.stop()
            from seaweedfs_tpu.pb.rpc import close_all_channels

            await close_all_channels()

    run(go())
