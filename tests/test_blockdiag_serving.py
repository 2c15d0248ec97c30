"""Block-diagonal resident serving layout + double-buffered pipeline.

Covers the r09 perf round's correctness surface on the CPU test mesh:
the blockdiag gather+reconstruct variants (XLA fallback and fused
interpret) against the numpy oracle, the blockdiag parity scrub, the
DevicePipeline's staging-slot semantics and overlap accounting,
eviction/unmount racing an in-flight batch, warm()'s observed-bucket
prioritization, and the e2e three-way byte equality (blockdiag vs flat
vs host reconstruct) through the real volume server.  On the chip only
blockdiag with overlap on has been measured (PERF_LEDGER.jsonl).
"""
import asyncio
import os
import random
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs, rs_resident

from test_ec import encode_volume, make_volume


@pytest.fixture(scope="module")
def coded():
    rng = np.random.default_rng(97)
    codec = rs.RSCodec(backend="numpy")
    data = rng.integers(0, 256, size=(10, 300_000), dtype=np.uint8)
    return codec.encode_all(data)  # [14, length]


def fill_cache(shards, missing=(), vid=7, layout="blockdiag"):
    cache = rs_resident.DeviceShardCache(
        shard_quantum=1 << 20, layout=layout
    )
    for sid in range(shards.shape[0]):
        if sid not in missing:
            cache.put(vid, sid, shards[sid])
    return cache


class TestBlockdiagReconstruct:
    def test_oracle_mixed_sizes_xla(self, coded):
        """The XLA-fallback blockdiag gather (the CPU serving path) on
        unaligned offsets, bucket-spanning sizes, and tails."""
        cache = fill_cache(coded, missing=(3, 11))
        length = coded.shape[1]
        rng = random.Random(5)
        reqs = [
            (3, 5, 4096),
            (11, 131000, 70000),
            (3, 0, 1),
            (11, length - 1000, 1000),
        ] + [
            (rng.choice([3, 11]), rng.randrange(0, length - 4096), 4096)
            for _ in range(28)
        ]
        outs = rs_resident.reconstruct_intervals(cache, 7, reqs)
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()

    def test_oracle_fused_interpret(self, coded):
        """The fused DMA blockdiag kernel (the real-TPU serving path) in
        pallas interpret mode: segment-aligned DMA sources, per-group
        row select, host delta trim."""
        cache = fill_cache(coded, missing=(3, 11))
        length = coded.shape[1]
        rng = random.Random(6)
        reqs = [
            (3, 5, 100),
            (11, 131, 40000),
            (3, length - 1000, 1000),
        ] + [
            (rng.choice([3, 11]), rng.randrange(0, length - 8192), 8192)
            for _ in range(13)
        ]
        outs = rs_resident.reconstruct_intervals(
            cache, 7, reqs, kernel="pallas", interpret=True
        )
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()

    def test_chunk_split_both_kernels(self):
        """Requests larger than the biggest size bucket split, ride the
        coarser blockdiag fetch ladder, and reassemble byte-exact."""
        big = rs_resident.MAX_TILE + 12345
        rng = np.random.default_rng(8)
        codec = rs.RSCodec(backend="numpy")
        data = rng.integers(0, 256, size=(10, big + 4096), dtype=np.uint8)
        shards = codec.encode_all(data)
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 22, layout="blockdiag"
        )
        for sid in range(14):
            if sid != 0:
                cache.put(9, sid, shards[sid])
        for kw in ({}, {"kernel": "pallas", "interpret": True}):
            (out,) = rs_resident.reconstruct_intervals(
                cache, 9, [(0, 17, big)], **kw
            )
            assert out == shards[0][17 : 17 + big].tobytes()

    def test_sharded_layouts_equal_single_device_and_oracle(self, coded):
        """r19: the mesh-sharded twins (flat AND blockdiag) serve the
        same bytes as the single-device kernels and the encode oracle —
        including requests the planner splits at per-device chunk
        boundaries."""
        single = fill_cache(coded, missing=(3,))
        caches = {
            layout: rs_resident.DeviceShardCache(
                shard_quantum=1 << 20, layout=layout,
                mesh_devices=0, mesh_min_shard_bytes=0,
            )
            for layout in ("flat", "blockdiag")
        }
        for cache in caches.values():
            for sid in range(coded.shape[0]):
                if sid != 3:
                    cache.put(7, sid, coded[sid])
        length = coded.shape[1]
        rng = random.Random(12)
        # (chunk-boundary straddles need data longer than one per-device
        # chunk — test_mesh_serving covers them with a 4MB volume; this
        # fixture's 300KB sits inside chunk 0)
        reqs = [
            (3, 5, 4096),
            (3, length // 2 - 99, 4096),
            (3, length - 900, 900),
        ] + [
            (3, rng.randrange(0, length - 70000), rng.choice([512, 4096, 33000]))
            for _ in range(20)
        ]
        want = rs_resident.reconstruct_intervals(single, 7, reqs)
        for layout, cache in caches.items():
            assert cache.placement(7) == "mesh"
            outs = rs_resident.reconstruct_intervals(cache, 7, reqs)
            for (sid, off, size), out, w in zip(reqs, outs, want):
                assert out == w == coded[sid][off : off + size].tobytes(), (
                    f"sharded {layout} drifted at off={off} size={size}"
                )

    def test_layout_flat_blockdiag_equal(self, coded):
        """Same cache bytes, both layouts, byte-identical results — the
        layout knob must never change what a read returns."""
        cache = fill_cache(coded, missing=(3, 11))
        reqs = [(3, 5, 4096), (11, 131000, 70000), (3, 0, 1)]
        flat = rs_resident.reconstruct_intervals(cache, 7, reqs, layout="flat")
        blk = rs_resident.reconstruct_intervals(
            cache, 7, reqs, layout="blockdiag"
        )
        assert flat == blk

    def test_blockdiag_fetch_tile_ladder(self):
        g = 4
        q = g * rs_resident.FUSED_ALIGN
        for fetch in (2048, 3072, 4096, 6144, 8192, rs_resident.MAX_TILE):
            f2, tile = rs_resident._blockdiag_fetch_tile(fetch, g)
            assert f2 >= fetch and f2 % q == 0
            assert f2 % tile == 0 and (tile // g) % rs_resident.FUSED_ALIGN == 0


class TestBlockdiagScrub:
    def test_clean_and_corrupt(self, coded):
        for layout in ("flat", "blockdiag"):
            cache = fill_cache(coded, vid=12, layout=layout)
            mism, span = rs_resident.scrub_volume(cache, 12)
            assert mism == [0, 0, 0, 0]
            assert span >= coded.shape[1]
            bad = coded[11].copy()
            bad[54321] ^= 0x5A  # parity shard 11 = parity row 1
            cache.put(12, 11, bad)
            mism, _ = rs_resident.scrub_volume(cache, 12)
            assert mism == [0, 1, 0, 0], (layout, mism)

    def test_blockdiag_span_covers_group_lanes(self, coded):
        cache = fill_cache(coded, vid=13, layout="blockdiag")
        _, span = rs_resident.scrub_volume(cache, 13)
        quant = cache.groups * rs_resident.LANE
        assert span % quant == 0 and span >= coded.shape[1]


class TestDevicePipeline:
    def _section(self, pipe, hold, started, release):
        with pipe.slot():
            started.append(time.perf_counter())
            release.wait(hold)

    def test_single_slot_serializes(self):
        pipe = rs_resident.DevicePipeline(slots=1)
        started, release = [], threading.Event()
        t1 = threading.Thread(
            target=self._section, args=(pipe, 5.0, started, release)
        )
        t1.start()
        while not started:
            time.sleep(0.005)
        t2 = threading.Thread(
            target=self._section, args=(pipe, 0.0, started, release)
        )
        t2.start()
        time.sleep(0.1)
        assert len(started) == 1  # second section waits for the slot
        release.set()
        t1.join()
        t2.join()
        assert len(started) == 2

    @pytest.mark.skipif(
        (os.cpu_count() or 1) < 2,
        reason="overlap gauge needs two sections genuinely concurrent — "
        "a 1-core box timeslices them and busy/wall can round below 1",
    )
    def test_two_slots_overlap_and_gauge(self):
        pipe = rs_resident.DevicePipeline(slots=2)
        started, release = [], threading.Event()
        threads = [
            threading.Thread(
                target=self._section, args=(pipe, 5.0, started, release)
            )
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        deadline = time.time() + 2
        while len(started) < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert len(started) == 2  # both sections live at once
        release.set()
        for t in threads:
            t.join()
        # two ~concurrent sections: busy/wall over the window must show
        # the overlap (> 1 means the staging slots genuinely overlapped)
        assert pipe.last_overlap > 1.0

    def test_set_slots_wakes_waiters(self):
        pipe = rs_resident.DevicePipeline(slots=1)
        started, release = [], threading.Event()
        threads = [
            threading.Thread(
                target=self._section, args=(pipe, 5.0, started, release)
            )
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        time.sleep(0.1)
        assert len(started) == 1
        pipe.set_slots(2)  # widening must admit the queued section
        deadline = time.time() + 2
        while len(started) < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert len(started) == 2
        release.set()
        for t in threads:
            t.join()


class TestEvictionRaces:
    def test_eviction_midbatch_clean_exceptions(self, tmp_path, monkeypatch):
        """Eviction + shard-file destruction racing an in-flight batch:
        every member gets a clean exception — never stale bytes."""
        v, blobs = make_volume(tmp_path, count=8)
        encode_volume(v)
        from seaweedfs_tpu.storage import ec

        ev = ec.EcVolume(str(tmp_path), v.id)
        down = {0, 11}
        for i in range(14):
            if i not in down:
                ev.add_shard(i)
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag"
        )
        ev.load_shards_to_device(cache)
        real = rs_resident.reconstruct_intervals

        def racing(*a, **kw):
            # the batch's device call finds the volume unmounted under
            # it: cache evicted AND the shard files destroyed, so both
            # the resident path (CacheMiss) and the host fallback
            # (InsufficientShards) are exercised mid-flight
            cache.evict(v.id)
            for sid in list(ev.shards):
                ev.delete_shard(sid).destroy()
            return real(*a, **kw)

        monkeypatch.setattr(rs_resident, "reconstruct_intervals", racing)
        results = ev.read_needles_batch(list(blobs))
        assert results, "batch returned nothing"
        for r in results:
            assert isinstance(r, Exception), f"stale bytes served: {r!r}"
        ev.close()

    def test_cross_volume_eviction_isolated(self, tmp_path, monkeypatch):
        """Evicting volume A mid-batch must not corrupt or stall volume
        B's in-flight batch — the cache is keyed by (vid, shard)."""
        a_dir = tmp_path / "a"
        b_dir = tmp_path / "b"
        a_dir.mkdir()
        b_dir.mkdir()
        va, _blobs_a = make_volume(a_dir, vid=1, count=4)
        vb, blobs_b = make_volume(b_dir, vid=2, count=6)
        encode_volume(va)
        encode_volume(vb)
        from seaweedfs_tpu.storage import ec

        eva = ec.EcVolume(str(a_dir), va.id)
        evb = ec.EcVolume(str(b_dir), vb.id)
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag"
        )
        for i in range(14):
            if i != 0:
                eva.add_shard(i)
                evb.add_shard(i)
        eva.load_shards_to_device(cache)
        evb.load_shards_to_device(cache)
        real = rs_resident.reconstruct_intervals
        evicted = []

        def racing(cache_, vid, *a, **kw):
            if vid == vb.id and not evicted:
                evicted.append(True)
                cache_.evict(va.id)  # A dies while B's batch is in flight
            return real(cache_, vid, *a, **kw)

        monkeypatch.setattr(rs_resident, "reconstruct_intervals", racing)
        results = evb.read_needles_batch(list(blobs_b))
        for nid, n in zip(blobs_b, results):
            cookie, data = blobs_b[nid]
            assert n.data == data and n.cookie == cookie
        assert evicted and cache.shard_ids(va.id) == []
        eva.close()
        evb.close()


class TestWarmPriority:
    def test_observed_buckets_order_warm_grid(self, coded, monkeypatch):
        """Legacy (aot=False) trace-and-execute warm keeps the observed-
        first walk."""
        cache = fill_cache(coded, missing=(3, 11))
        seen = []

        def spying(cache_, vid, reqs, **kw):
            seen.append((reqs[0][2], len(reqs)))
            return [b""] * len(reqs)

        monkeypatch.setattr(rs_resident, "reconstruct_intervals", spying)
        # the observed shape (8192-size bucket, count 16) must compile
        # first even though it is not the grid's natural first entry
        rs_resident.warm(
            cache, 7, sizes=(65536, 4096), counts=(1, 16),
            observed=[(8192, 16)], aot=False,
        )
        assert seen[0] == (4096, 16), seen[:4]

    def test_aot_warm_walks_observed_first(self, coded, monkeypatch):
        """AOT warm (the default) plans compile jobs in the same
        observed-buckets-first order — the single-worker executor makes
        submission order the compile order."""
        cache = fill_cache(coded, missing=(3, 11))
        seen = []
        real = rs_resident._pack_calls

        def spying(cache_, vid, reqs, *a, **kw):
            seen.append((reqs[0][2], len(reqs)))
            return real(cache_, vid, reqs, *a, **kw)

        monkeypatch.setattr(rs_resident, "_pack_calls", spying)
        monkeypatch.setattr(
            rs_resident, "_schedule_aot_compiles", lambda keys: []
        )
        rs_resident.warm(
            cache, 7, sizes=(65536, 4096), counts=(1, 16),
            observed=[(8192, 16)],
        )
        assert seen[0] == (4096, 16), seen[:4]
        assert cache.aot_state(7) == "done"

    def test_observed_buckets_recorded(self, coded):
        cache = fill_cache(coded, missing=(3, 11))
        rs_resident.reconstruct_intervals(cache, 7, [(3, 0, 4096)] * 16)
        key = (rs_resident._bucket(rs_resident.SIZE_BUCKETS, 4096 + 1), 16)
        assert key in rs_resident.observed_buckets()


class TestTelemetryPlumbing:
    def test_health_doc_carries_overlap(self):
        from seaweedfs_tpu.pb import master_pb2
        from seaweedfs_tpu.stats import ClusterTelemetry

        tel = master_pb2.VolumeServerTelemetry(
            device_budget_bytes=100,
            overlap_fraction=1.62,
            ec_h2d_bytes=4096,
            ec_d2h_bytes=8192,
        )
        ct = ClusterTelemetry(pulse_seconds=1)
        ct.observe("n1:8080", tel, now=100.0)
        doc = ct.health(now=100.5)
        disp = doc["nodes"]["n1:8080"]["dispatcher"]
        assert disp["overlap_fraction"] == 1.62
        assert disp["h2d_bytes_total"] == 4096
        assert disp["d2h_bytes_total"] == 8192


def test_e2e_blockdiag_flat_host_byte_equal(tmp_path):
    """The satellite's three-way equality on the REAL serving path: the
    same degraded cluster serves every blob byte-identically through the
    blockdiag resident layout (the default), the flat resident layout,
    and the host CPU reconstruct (the dispatcher's shed path) — and the
    pipeline's new series are live on /metrics."""
    import aiohttp

    from degraded_cluster import build_degraded_cluster

    async def go():
        cluster, vs, blobs, _vid = await build_degraded_cluster(
            str(tmp_path), n_blobs=8, device_cache=True
        )
        try:
            cache = vs.store.ec_device_cache
            assert cache.layout == "blockdiag"  # the serving default
            async with aiohttp.ClientSession() as sess:

                async def read(fid):
                    async with sess.get(f"http://{vs.url}/{fid}") as r:
                        assert r.status == 200, (fid, r.status)
                        return await r.read()

                async def burst():
                    fids = list(blobs) * 3
                    got = await asyncio.gather(*(read(f) for f in fids))
                    return dict(zip(fids, got))

                by_layout = {}
                for layout in ("blockdiag", "flat"):
                    cache.layout = layout
                    by_layout[layout] = await burst()
                for fid, want in blobs.items():
                    assert by_layout["blockdiag"][fid] == want
                    assert by_layout["flat"][fid] == want
                from seaweedfs_tpu.storage import types as t

                for fid, want in blobs.items():
                    vid, nid, cookie = t.parse_fid(fid)
                    host = vs.store.read_ec_needle(
                        vid, nid, cookie, use_device=False
                    )
                    assert host.data == want
                async with sess.get(f"http://{vs.url}/metrics") as r:
                    text = await r.text()
            for series in (
                "SeaweedFS_volumeServer_ec_h2d_bytes_total",
                "SeaweedFS_volumeServer_ec_d2h_bytes_total",
                "SeaweedFS_volumeServer_ec_overlap_fraction",
            ):
                assert series in text, f"missing series: {series}"
            h2d_line = next(
                l for l in text.splitlines()
                if l.startswith("SeaweedFS_volumeServer_ec_h2d_bytes_total ")
            )
            assert float(h2d_line.split()[-1]) > 0
        finally:
            await cluster.stop()

    asyncio.run(go())
