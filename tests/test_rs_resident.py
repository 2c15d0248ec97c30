"""Device-resident EC shard cache: batched on-device degraded reads.

Validates ops/rs_resident.py against the numpy oracle and the EcVolume
wiring (resident fast path + read_needles_batch coalescing).  Runs on the
CPU test mesh (Pallas interpret / XLA); the chip's numbers are the
benchmark's GET cells (PERF_LEDGER.jsonl).
"""
import random

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs, rs_resident
from seaweedfs_tpu.stats import metrics as stats_metrics
from seaweedfs_tpu.storage import ec

from test_ec import encode_volume, make_volume


@pytest.fixture(scope="module")
def coded():
    rng = np.random.default_rng(42)
    length = 300_000
    codec = rs.RSCodec(backend="numpy")
    data = rng.integers(0, 256, size=(10, length), dtype=np.uint8)
    return codec.encode_all(data)  # [14, length]


def fill_cache(shards, missing=(), vid=7, quantum=1 << 20):
    cache = rs_resident.DeviceShardCache(shard_quantum=quantum)
    for sid in range(shards.shape[0]):
        if sid not in missing:
            cache.put(vid, sid, shards[sid])
    return cache


class TestCache:
    def test_put_get_sizes(self, coded):
        cache = fill_cache(coded, missing=range(4, 14))
        assert cache.shard_ids(7) == [0, 1, 2, 3]
        assert cache.shard_size(7, 0) == coded.shape[1]
        assert cache.get(7, 9) is None
        got = np.asarray(cache.get(7, 2))[: coded.shape[1]]
        np.testing.assert_array_equal(got, coded[2])

    def test_budget_evicts_lru(self, coded):
        one = rs_resident.DeviceShardCache(shard_quantum=1 << 20).\
            _padded_len(coded.shape[1])
        cache = rs_resident.DeviceShardCache(
            budget_bytes=3 * one, shard_quantum=1 << 20
        )
        for sid in range(4):
            cache.put(7, sid, coded[sid])
        assert cache.shard_ids(7) == [1, 2, 3]  # 0 evicted (LRU)
        assert cache.bytes_used == 3 * one
        cache.get(7, 1)  # refresh 1
        cache.put(7, 9, coded[9])
        assert cache.shard_ids(7) == [1, 3, 9]  # 2 was the new LRU

    def test_evict_volume(self, coded):
        cache = fill_cache(coded)
        cache.put(8, 0, coded[0])
        cache.evict(7)
        assert cache.shard_ids(7) == []
        assert cache.shard_ids(8) == [0]
        cache.clear()
        assert cache.bytes_used == 0


TRANSFER_KINDS = ("h2d_async", "h2d_waited", "d2h_shard_fetched",
                  "d2h_shard_skipped")


def transfers():
    family = stats_metrics.VOLUME_SERVER_EC_DEVICE_TRANSFERS
    return {k: family.labels(kind=k)._value.get() for k in TRANSFER_KINDS}


def _device_calls():
    """Device calls dispatched so far: each is one hit or one miss."""
    family = stats_metrics.VOLUME_SERVER_EC_DEVICE_COMPILE
    return sum(
        family.labels(result=r)._value.get() for r in ("hit", "miss")
    )


def five_buckets(length):
    """One mixed batch with one request in each of five size buckets
    (2 KiB .. 512 KiB), on both lost shards, offsets unaligned."""
    sizes = [700, 5000, 20000, 100000, 250000]
    assert [rs_resident._bucket(rs_resident.SIZE_BUCKETS, n)
            for n in sizes] == list(rs_resident.SIZE_BUCKETS[:5])
    return [(3 if i % 2 else 11, 17 + i * 9001, n)
            for i, n in enumerate(sizes) if 17 + i * 9001 + n <= length]


class TestStagingArena:
    def test_two_calls_of_a_batch_get_disjoint_rows(self):
        arena = rs_resident.StagingArena(width=32)
        assert len(arena.blocks) == len(rs_resident.SIZE_BUCKETS)
        first, second = arena.take(), arena.take()
        assert first != second
        a = arena.stage_fused([5, 6, 7], 1, first)
        b = arena.stage_xla([1, 2], [3, 4], [5, 6], 0, second)
        assert not np.shares_memory(a, b)
        # the second call's staging leaves the first call's rows as its
        # put may still be reading them
        assert a.tolist() == [5, 6, 7, 0]
        assert b.tolist() == [[1, 2], [3, 4], [5, 6]]

    def test_blocks_run_out_and_come_back(self, monkeypatch):
        monkeypatch.setattr(rs_resident.StagingArena, "BLOCKS", 2)
        arena = rs_resident.StagingArena(width=8)
        taken = [arena.take(), arena.take()]
        assert sorted(taken) == [0, 1] and arena.take() is None
        arena.give(taken[0])
        assert arena.take() == taken[0] and arena.take() is None
        arena.reset()  # a released slot frees every block
        assert sorted([arena.take(), arena.take()]) == [0, 1]

    def test_a_released_slot_hands_out_a_free_arena(self):
        pipe = rs_resident.DevicePipeline(slots=1)
        with pipe.slot() as held:
            while held.arena.take() is not None:
                pass
        with pipe.slot() as again:
            assert again.arena is held.arena
            assert again.arena.take() is not None


class TestReconstruct:
    @pytest.mark.parametrize("mode", [
        {}, {"kernel": "pallas", "interpret": True},
    ], ids=["xla", "fused"])
    @pytest.mark.parametrize("batch", ["mixed", "five_buckets"])
    def test_oracle_mixed_sizes(self, coded, batch, mode):
        cache = fill_cache(coded, missing=(3, 11))
        length = coded.shape[1]
        reqs = five_buckets(length) if batch == "five_buckets" else [
            (3, 5, 4096),        # unaligned offset
            (11, 131000, 70000),  # parity shard, spans buckets
            (3, 0, 1),
            (11, length - 1000, 1000),  # tail
        ]
        before = transfers()
        outs = rs_resident.reconstruct_intervals(cache, 7, reqs, **mode)
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()
        moved = {k: n - before[k] for k, n in transfers().items()}
        # one call a size bucket present, every put left in flight; no
        # mesh, so no shard is counted either way
        buckets = {rs_resident._bucket(rs_resident.SIZE_BUCKETS, n)
                   for _, _, n in reqs}
        assert moved["h2d_async"] >= len(buckets)
        if batch == "five_buckets":
            assert moved["h2d_async"] == len(buckets) == 5
        assert moved["h2d_waited"] == 0
        assert moved["d2h_shard_fetched"] == moved["d2h_shard_skipped"] == 0

    @pytest.mark.parametrize("mode", [
        {"kernel": "xla", "interpret": True},
        {"kernel": "pallas", "interpret": True},
    ], ids=["xla", "fused"])
    def test_more_calls_than_arena_blocks(self, coded, monkeypatch, mode):
        """Five calls through an arena of two row-blocks (the arena is a
        TPU's, so the test claims to be one for the batch): the third,
        fourth and fifth call each collect the oldest call first and
        stage into the rows it gave back; the answers are the codec's."""
        cache = fill_cache(coded, missing=(3, 11))
        reqs = five_buckets(coded.shape[1])
        assert len(reqs) == 5
        monkeypatch.setattr(rs_resident.StagingArena, "BLOCKS", 2)
        monkeypatch.setattr(rs_resident.rs_tpu, "on_tpu", lambda: True)
        staged = []
        real_stage = rs_resident._stage_call_vec

        def stage(kind, cols, pad, arena=None, block=0):
            staged.append(block)
            return real_stage(kind, cols, pad, arena, block)

        monkeypatch.setattr(rs_resident, "_stage_call_vec", stage)
        before = transfers()
        outs = rs_resident.reconstruct_intervals(cache, 7, reqs, **mode)
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()
        moved = {k: n - before[k] for k, n in transfers().items()}
        assert moved["h2d_async"] == 2 and moved["h2d_waited"] == 3
        # the first two calls took a block each; every later call took
        # the one the oldest call in flight had just given back
        assert staged == [0, 1, 0, 1, 0]

    def test_oracle_chunk_split(self, coded):
        # larger than the biggest size bucket: must split and reassemble
        big = rs_resident.MAX_TILE + 12345
        rng = np.random.default_rng(1)
        codec = rs.RSCodec(backend="numpy")
        data = rng.integers(0, 256, size=(10, big + 4096), dtype=np.uint8)
        shards = codec.encode_all(data)
        cache = fill_cache(shards, missing=(0,), vid=9, quantum=1 << 22)
        (out,) = rs_resident.reconstruct_intervals(cache, 9, [(0, 17, big)])
        assert out == shards[0][17 : 17 + big].tobytes()

    def test_batch_64(self, coded):
        cache = fill_cache(coded, missing=(3, 11))
        rng = random.Random(2)
        length = coded.shape[1]
        reqs = [
            (rng.choice([3, 11]), rng.randrange(0, length - 4096), 4096)
            for _ in range(64)
        ]
        outs = rs_resident.reconstruct_intervals(cache, 7, reqs)
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()

    def test_fused_kernel_matches_oracle(self, coded):
        """The fused DMA gather+reconstruct kernel (the real-TPU serving
        path) in pallas interpret mode, against the numpy oracle: mixed
        sizes, unaligned offsets, multi-chunk grids, and a 64-batch."""
        cache = fill_cache(coded, missing=(3, 11))
        length = coded.shape[1]
        rng = random.Random(3)
        reqs = [
            (3, 5, 100),
            (11, 131, 40000),
            (3, length - 1000, 1000),
        ] + [
            (rng.choice([3, 11]), rng.randrange(0, length - 8192), 8192)
            for _ in range(61)
        ]
        outs = rs_resident.reconstruct_intervals(
            cache, 7, reqs, kernel="pallas", interpret=True
        )
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()

    def test_homogeneous_batch_is_one_call_under_both_kernels(self, coded):
        """Eight 4 KiB requests of one size bucket are ONE device call
        (a hit or a miss of ec_device_compile_total, what the
        benchmark's device_calls_per_get reads) under the fused and the
        gather kernel, byte-equal to the oracle."""
        cache = fill_cache(coded, missing=(3,))
        reqs = [(3, 4096 * i, 4096) for i in range(8)]
        for kernel in ("pallas", "xla"):
            calls0 = _device_calls()
            outs = rs_resident.reconstruct_intervals(
                cache, 7, reqs, kernel=kernel, interpret=True
            )
            assert _device_calls() - calls0 == 1
            for (sid, off, size), out in zip(reqs, outs):
                assert out == coded[sid][off : off + size].tobytes()

    def test_cache_miss(self, coded):
        cache = fill_cache(coded, missing=range(5, 14))
        with pytest.raises(rs_resident.CacheMiss):
            rs_resident.reconstruct_intervals(cache, 7, [(3, 0, 100)])

    def test_empty_requests(self, coded):
        cache = fill_cache(coded)
        assert rs_resident.reconstruct_intervals(cache, 7, []) == []


def test_serving_warm_grid_covers_timed_needle_shapes():
    """Every fetch-ladder shape a 4KB needle read can produce (any
    sub-FUSED_ALIGN alignment) is covered by a warm grid of
    warm_sizes=(4096,) in both warm alignment classes, for the
    single-wanted case: an edit to SIZE_BUCKETS, _fetch_cover or
    _blockdiag_fetch_tile that pushes such a read onto an unwarmed shape
    fails here instead of compiling inside a serving window."""
    from seaweedfs_tpu.ops import rs_tpu
    from seaweedfs_tpu.storage import needle as needle_mod

    needle_size = needle_mod.actual_size(4096, needle_mod.CURRENT_VERSION)

    def fused_shape(size, extra_delta):
        # mirror _plan + _fused_vectors: LANE-align, then FUSED_ALIGN
        # re-align; span = delta + take
        span = extra_delta + size
        fetch = rs_resident._fetch_cover(span)
        blk_fetch, blk_tile = rs_resident._blockdiag_fetch_tile(
            fetch, rs_tpu.BLOCKDIAG_GROUPS
        )
        return (
            rs_resident._bucket(rs_resident.SIZE_BUCKETS, span),
            blk_fetch,
            blk_tile,
        )

    warm_shapes = {fused_shape(4096, off) for off in (0, 1)}
    timed_shapes = {
        fused_shape(needle_size, delta)
        for delta in range(rs_resident.FUSED_ALIGN)
    }
    missing = timed_shapes - warm_shapes
    assert not missing, (
        f"4KB needle reads can hit fetch shapes a (4096,) warm grid "
        f"never compiles: {sorted(missing)}"
    )


class TestEcVolumeWiring:
    def test_degraded_read_via_resident(self, tmp_path, monkeypatch):
        v, blobs = make_volume(tmp_path)
        encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        down = {0, 11}  # shard 0 holds needle data in a small volume
        for i in range(14):
            if i not in down:
                ev.add_shard(i)
        cache = rs_resident.DeviceShardCache(shard_quantum=1 << 20)
        assert ev.load_shards_to_device(cache) == 12
        # count resident calls to prove the fast path actually serves
        calls = []
        real = rs_resident.reconstruct_intervals

        def counting(*a, **kw):
            calls.append(a)
            return real(*a, **kw)

        monkeypatch.setattr(rs_resident, "reconstruct_intervals", counting)
        for nid, (cookie, data) in blobs.items():
            assert ev.read_needle(nid, cookie=cookie).data == data
        assert calls, "resident path never used"
        ev.close()

    def test_batch_read_coalesces(self, tmp_path, monkeypatch):
        v, blobs = make_volume(tmp_path, count=16)
        encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        down = {0, 7}
        for i in range(14):
            if i not in down:
                ev.add_shard(i)
        cache = rs_resident.DeviceShardCache(shard_quantum=1 << 20)
        ev.load_shards_to_device(cache)
        calls = []
        real = rs_resident.reconstruct_intervals

        def counting(*a, **kw):
            calls.append(a[2])
            return real(*a, **kw)

        monkeypatch.setattr(rs_resident, "reconstruct_intervals", counting)
        nids = list(blobs)
        needles = ev.read_needles_batch(nids)
        for nid, n in zip(nids, needles):
            cookie, data = blobs[nid]
            assert n.data == data and n.cookie == cookie
        # every missing-shard interval went through ONE coalesced call
        assert len(calls) == 1 and len(calls[0]) >= 2
        ev.close()

    def test_batch_read_isolates_bad_ids(self, tmp_path):
        v, blobs = make_volume(tmp_path, count=6)
        encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            ev.add_shard(i)
        nids = list(blobs)
        mixed = [nids[0], 0xDEAD_BEEF, nids[1]]  # middle id doesn't exist
        results = ev.read_needles_batch(mixed)
        assert results[0].data == blobs[nids[0]][1]
        assert isinstance(results[1], ec.volume.NeedleNotFound)
        assert results[2].data == blobs[nids[1]][1]
        ev.close()

    def test_batch_read_without_cache_falls_back(self, tmp_path):
        v, blobs = make_volume(tmp_path, count=6)
        encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            if i not in (2,):
                ev.add_shard(i)
        nids = list(blobs)
        needles = ev.read_needles_batch(nids)
        for nid, n in zip(nids, needles):
            assert n.data == blobs[nid][1]
        ev.close()

    def test_server_dispatcher_coalesces(self, tmp_path):
        """EcReadDispatcher: concurrent reads of a resident volume land
        in one Store.read_ec_needles_batch call; failures stay
        per-needle.  (The dispatcher's own unit suite is
        tests/test_serving_dispatcher.py — this keeps the resident-path
        contract pinned next to the cache tests.)"""
        import asyncio

        from seaweedfs_tpu.serving import EcReadDispatcher, ServingConfig

        calls = []

        class FakeStore:
            def ec_volume_is_resident(self, vid):
                return True

            def read_ec_needles_batch(
                self, vid, requests, remote_read=None, zero_copy=False
            ):
                calls.append(list(requests))
                out = []
                for nid, _cookie in requests:
                    if nid == 99:
                        out.append(KeyError("nope"))
                    else:
                        out.append(f"needle-{vid}-{nid}")
                return out

        async def go():
            b = EcReadDispatcher(
                FakeStore(), lambda vid: None,
                ServingConfig(max_inflight=1, max_wait_us=0),
            )

            # first read starts a drain; the rest arrive while it runs
            # and must coalesce into ONE follow-up batch
            results = await asyncio.gather(
                b.read(1, 1, None),
                b.read(1, 2, None),
                b.read(1, 3, None),
                b.read(1, 99, None),
                return_exceptions=True,
            )
            assert results[0] == "needle-1-1"
            assert results[1] == "needle-1-2"
            assert results[2] == "needle-1-3"
            assert isinstance(results[3], KeyError)
            assert len(calls) <= 2  # 1 leading + 1 coalesced batch
            total = sum(len(c) for c in calls)
            assert total == 4

        asyncio.run(go())

    def test_eviction_on_shard_delete(self, tmp_path):
        v, _ = make_volume(tmp_path, count=4)
        encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            ev.add_shard(i)
        cache = rs_resident.DeviceShardCache(shard_quantum=1 << 20)
        ev.load_shards_to_device(cache)
        assert len(cache.shard_ids(v.id)) == 14
        ev.delete_shard(5)
        assert 5 not in cache.shard_ids(v.id)
        ev.destroy()
        assert cache.shard_ids(v.id) == []
