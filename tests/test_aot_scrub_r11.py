"""r11 device-path overheads: AOT serving grid + cold-shape shed, fused
multi-volume scrub megakernel, packed-meta/donation staging.

CPU-mesh correctness surface for the three r11 attacks: warm() compiling
the ladder ahead-of-time into the executable registry (dispatch routes
through it, never the jit cache), ColdShape shedding a serving read to
the host path — byte-equal, counted, and never blocked behind a
compile — while the background executor compiles the shape,
scrub_all_resident matching the per-volume verdicts in one device pass,
the packed [N] meta halving the staged H2D bytes, and the
observed-shape / compile-cache persistence satellites.  On the chip the
scrub runs in chip_smoke.py only; no cell times it yet (PERF.md).
"""
import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs, rs_resident
from seaweedfs_tpu.stats import metrics as stats_metrics

from test_ec import encode_volume, make_volume


@pytest.fixture(scope="module")
def coded():
    rng = np.random.default_rng(11)
    codec = rs.RSCodec(backend="numpy")
    data = rng.integers(0, 256, size=(10, 300_000), dtype=np.uint8)
    return codec.encode_all(data)  # [14, length]


def fill_cache(shards, missing=(), vid=7, layout="blockdiag", quantum=1 << 20):
    cache = rs_resident.DeviceShardCache(
        shard_quantum=quantum, layout=layout
    )
    for sid in range(shards.shape[0]):
        if sid not in missing:
            cache.put(vid, sid, shards[sid])
    return cache


def _counter(name, labels=None):
    from seaweedfs_tpu import stats

    return stats.REGISTRY.get_sample_value(name, labels or {}) or 0.0


class TestAotWarm:
    def test_warm_populates_registry_and_dispatch_hits(self, coded):
        cache = fill_cache(coded, missing=(3, 11))
        assert cache.aot_state(7) == "none"
        before = rs_resident.aot_stats()["compiled"]
        rs_resident.warm(cache, 7, sizes=(4096,), counts=(1,))
        assert cache.aot_state(7) == "done"
        assert rs_resident.aot_stats()["compiled"] > before
        # a warm-covered dispatch goes through the AOT executable: the
        # compile counter must record a HIT, never a miss
        miss0 = _counter(
            "SeaweedFS_volumeServer_ec_device_compile_total",
            {"result": "miss"},
        )
        (out,) = rs_resident.reconstruct_intervals(
            cache, 7, [(3, 0, 4096)]
        )
        assert out == coded[3][:4096].tobytes()
        assert _counter(
            "SeaweedFS_volumeServer_ec_device_compile_total",
            {"result": "miss"},
        ) == miss0

    def test_empty_warm_plan_keeps_inline_compiles(self, coded):
        """warm_sizes=() (the CI convention) must leave the volume
        without a plan: cold shapes compile inline instead of shedding,
        so direct callers and cache-only tests are unaffected."""
        cache = fill_cache(coded, missing=(3, 11), vid=8)
        rs_resident.warm(cache, 8, sizes=(), counts=())
        assert cache.aot_state(8) == "none"
        (out,) = rs_resident.reconstruct_intervals(cache, 8, [(3, 7, 999)])
        assert out == coded[3][7:1006].tobytes()


class TestWarmPlanCoversDispatch:
    """warm()'s plan is what keeps a pinned volume's reads off the
    cold-shape shed: every device-call shape _pack_calls can emit for a
    batch wanting the lost shard must be one warm() compiles.  The fetch
    ladders warm enumerates (_fused_fetch_rungs, _sharded_fetch_rungs)
    restate by hand what _fetch_cover / _plan produce, so the two are
    held together here over random request mixes in every size and
    count bucket — the CPU rehearsal of chip_smoke.py does not enforce
    sheds, and on the chip only a few hundred reads would."""

    SHARD = 4 << 20
    MISSING = 3

    def _cache(self, placement, layout):
        kw = dict(shard_quantum=1 << 20, layout=layout)
        if placement == "mesh":
            kw.update(mesh_devices=4, mesh_min_shard_bytes=0)
        cache = rs_resident.DeviceShardCache(**kw)
        rng = np.random.default_rng(5)
        for sid in range(14):
            if sid not in (self.MISSING, 11):
                # the planner never looks at the bytes
                cache.put(40, sid, rng.integers(
                    0, 256, size=self.SHARD, dtype=np.uint8))
        return cache

    def _keys(self, cache, requests, layout):
        calls, _subs, survivors, a_prep, use, w_true, place = (
            rs_resident._pack_calls(
                cache, 40, requests, "pallas", True, layout,
                rs_resident.DATA_SHARDS, rs_resident.TOTAL_SHARDS,
                record_observed=False,
            )
        )
        groups = cache.groups if layout == "blockdiag" else 1
        return {
            rs_resident._call_key(
                kind, "pallas", groups, w_true, tile, fetch, n_bucket,
                len(use), a_prep.shape, int(survivors[0].size), True,
                rs_resident._key_place(cache, place),
            )
            for kind, _p, _c, _pad, fetch, tile, n_bucket, _d in calls
        }

    def _requests(self, rng, lo, hi, n):
        """n reads of the lost shard whose lane-aligned delta+take lies
        in (lo, hi], anywhere in the shard."""
        out = []
        for _ in range(n):
            off = int(rng.integers(0, self.SHARD - hi))
            delta = off % rs_resident.LANE
            take = int(rng.integers(max(1, lo + 1 - delta), hi - delta + 1))
            out.append((self.MISSING, off, take))
        return out

    @pytest.mark.parametrize(
        "placement,layout",
        [("one_device", "flat"), ("one_device", "blockdiag"),
         ("mesh", "blockdiag")],
    )
    def test_every_packed_shape_is_in_the_warm_plan(
        self, monkeypatch, placement, layout
    ):
        cache = self._cache(placement, layout)
        planned = set()
        monkeypatch.setattr(
            rs_resident, "_schedule_aot_compiles",
            lambda keys: planned.update(keys) or [],
        )
        try:
            rs_resident.warm(
                cache, 40, sizes=cache.warm_sizes, counts=cache.warm_counts,
                kernel="pallas", interpret=True,
            )
            assert cache.aot_state(40) == "done" and planned
            rng = np.random.default_rng(21)
            sizes = (0,) + rs_resident.SIZE_BUCKETS
            counts = (0,) + rs_resident.COUNT_BUCKETS
            for lo, hi in zip(sizes, sizes[1:]):
                for c_lo, c_hi in zip(counts, counts[1:]):
                    for _trial in range(3):
                        n = int(rng.integers(c_lo + 1, c_hi + 1))
                        reqs = self._requests(rng, lo, hi, n)
                        cold = self._keys(cache, reqs, layout) - planned
                        assert not cold, (lo, hi, n, cold)
            # batches mixed over every bucket, with reads long enough
            # to split into CHUNK-sized sub-requests
            for _trial in range(40):
                n = int(rng.integers(1, 300))
                reqs = []
                for _ in range(n):
                    size = int(2 ** rng.uniform(0, 21.6))
                    reqs.append((
                        self.MISSING,
                        int(rng.integers(0, self.SHARD - size)), size,
                    ))
                cold = self._keys(cache, reqs, layout) - planned
                assert not cold, (n, cold)
        finally:
            cache.clear()


class TestColdShapeShed:
    def test_shed_raises_before_device_work_and_counts(self, coded):
        cache = fill_cache(coded, missing=(3, 11), vid=9)
        cache._set_aot_state(9, "warming")
        shed0 = _counter("SeaweedFS_volumeServer_ec_shed_cold_shape_total")
        route0 = _counter(
            "SeaweedFS_volumeServer_ec_read_route_total",
            {"route": "shed_cold_shape"},
        )
        reqs = [(3, 0, 50_000), (11, 5, 4096)]
        with pytest.raises(rs_resident.ColdShape):
            rs_resident.reconstruct_intervals(cache, 9, reqs)
        assert _counter(
            "SeaweedFS_volumeServer_ec_shed_cold_shape_total"
        ) == shed0 + len(reqs)
        assert _counter(
            "SeaweedFS_volumeServer_ec_read_route_total",
            {"route": "shed_cold_shape"},
        ) == route0 + len(reqs)
        # ColdShape IS a CacheMiss: every existing host-fallback site
        # catches it without new plumbing
        assert issubclass(rs_resident.ColdShape, rs_resident.CacheMiss)

    def test_shed_disabled_compiles_inline(self, coded):
        cache = fill_cache(coded, missing=(3, 11), vid=10)
        cache._set_aot_state(10, "warming")
        cache.shed_cold = False  # -ec.serving.aot.disable
        (out,) = rs_resident.reconstruct_intervals(cache, 10, [(3, 3, 777)])
        assert out == coded[3][3:780].tobytes()

    def test_shed_read_serves_host_bytes_without_blocking(
        self, tmp_path, monkeypatch
    ):
        """The satellite's e2e contract: a read arriving before AOT
        finishes its shape returns host-reconstructed bytes (byte-equal
        to resident) and increments the shed counter, never blocking on
        the (deliberately slowed) compile."""
        v, blobs = make_volume(tmp_path, count=4)
        encode_volume(v)
        from seaweedfs_tpu.storage import ec

        ev = ec.EcVolume(str(tmp_path), v.id)
        down = {0, 11}
        for i in range(14):
            if i not in down:
                ev.add_shard(i)
        cache = rs_resident.DeviceShardCache(shard_quantum=1 << 20)
        ev.load_shards_to_device(cache)
        cache._set_aot_state(v.id, "warming")  # AOT "still running"

        compile_calls = []

        def slow_compile(key):
            compile_calls.append(key)
            time.sleep(3.0)  # stands in for the 20-40s real compile
            with rs_resident._shapes_lock:  # the real compile's cleanup
                rs_resident._aot_pending.discard(key)

        monkeypatch.setattr(rs_resident, "_compile_shape", slow_compile)
        shed0 = _counter("SeaweedFS_volumeServer_ec_shed_cold_shape_total")
        t0 = time.perf_counter()
        for nid, (cookie, data) in blobs.items():
            n = ev.read_needle(nid, cookie=cookie)
            assert n.data == data  # byte-equal to the resident bytes
        elapsed = time.perf_counter() - t0
        assert elapsed < 2.5, (
            f"shed reads took {elapsed:.1f}s — they blocked on a compile"
        )
        assert _counter(
            "SeaweedFS_volumeServer_ec_shed_cold_shape_total"
        ) > shed0
        # the compile job runs on the shared single-worker executor,
        # possibly queued behind earlier tests' real compiles — poll for
        # the pickup rather than racing it
        deadline = time.time() + 90
        while not compile_calls and time.time() < deadline:
            time.sleep(0.1)
        assert compile_calls, "shed never scheduled the background compile"
        ev.close()

    def test_shed_then_background_compile_serves_device(self, coded):
        # unique quantum -> unique surv_len in the call key: no other
        # test (e.g. vid 7's warm of the 4096 ladder rung) can have
        # AOT-compiled this shape already, so the first read MUST shed
        cache = fill_cache(coded, missing=(3, 11), vid=12, quantum=1 << 21)
        cache._set_aot_state(12, "warming")
        with pytest.raises(rs_resident.ColdShape):
            rs_resident.reconstruct_intervals(cache, 12, [(3, 1, 4096)])
        # the shed scheduled the compile: retry until the executor lands
        # it, then the same request serves on-device, byte-exact
        deadline = time.time() + 120
        while True:
            try:
                (out,) = rs_resident.reconstruct_intervals(
                    cache, 12, [(3, 1, 4096)]
                )
                break
            except rs_resident.ColdShape:
                assert time.time() < deadline, "background compile never landed"
                time.sleep(0.1)
        assert out == coded[3][1:4097].tobytes()


    def test_failed_compile_never_requeued(self, monkeypatch):
        """A deterministically failing AOT compile must not be re-queued
        by every matching shed — it lands in the failed memo and the
        shape keeps shedding to the host path without executor churn."""
        key = ("fused", 1, 0, 512, 1024, 1, 10, (1, 2, 3), 99, True)

        def boom(k):
            raise RuntimeError("synthetic compile failure")

        monkeypatch.setattr(rs_resident, "_compile_shape", boom)
        (fut,) = rs_resident._schedule_aot_compiles([key])
        fut.result()  # swallowed by _compile_shape_logged
        assert rs_resident.aot_stats()["failed"] >= 1
        with rs_resident._shapes_lock:
            assert key in rs_resident._aot_failed
            assert key not in rs_resident._aot_pending
        assert rs_resident._schedule_aot_compiles([key]) == []
        assert not rs_resident._shape_is_warm(key)  # still sheds to host
        with rs_resident._shapes_lock:
            rs_resident._aot_failed.discard(key)


class TestScrubMegakernel:
    def test_matches_per_volume_both_layouts(self, coded):
        for layout in ("flat", "blockdiag"):
            cache = rs_resident.DeviceShardCache(
                shard_quantum=1 << 20, layout=layout
            )
            for vid in (1, 2, 3):
                for sid in range(14):
                    cache.put(vid, sid, coded[sid])
            bad = coded[11].copy()
            bad[54321] ^= 0x5A  # parity shard 11 = parity row 1
            cache.put(2, 11, bad)
            mk0 = _counter(
                "SeaweedFS_volumeServer_ec_scrub_device_dispatch_total",
                {"mode": "megakernel"},
            )
            results, stats = rs_resident.scrub_all_resident(cache)
            assert stats["volumes"] == 3
            # three volumes share one n_lanes class: ONE device call
            assert stats["device_calls"] == 1
            assert _counter(
                "SeaweedFS_volumeServer_ec_scrub_device_dispatch_total",
                {"mode": "megakernel"},
            ) == mk0 + 1
            for vid in (1, 2, 3):
                assert results[vid] == rs_resident.scrub_volume(cache, vid), (
                    layout, vid,
                )
            assert results[2][0] == [0, 1, 0, 0]
            cache.clear()

    def test_partial_and_mixed_size_volumes(self, coded):
        """Partially resident volumes are skipped (the per-volume file
        path owns them); distinct shard sizes land in separate lane
        stacks but still scrub correctly."""
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag"
        )
        for sid in range(14):
            cache.put(1, sid, coded[sid])
            cache.put(3, sid, coded[sid][:150_016])  # different span
            if sid != 5:
                cache.put(2, sid, coded[sid])  # 13/14: not scrubbable
        results, stats = rs_resident.scrub_all_resident(cache)
        assert set(results) == {1, 3}
        assert stats["device_calls"] == 2  # two n_lanes classes
        assert results[1][0] == [0, 0, 0, 0]
        # a truncated shard set is parity-consistent over its own span
        # only if it was encoded that way — shard prefixes are NOT, so
        # just assert the span bookkeeping, not cleanliness
        assert results[3][1] < results[1][1]
        cache.clear()

    def test_store_scrub_all_attributes_pinned_location(self, tmp_path):
        """Store.scrub_all_resident covers exactly the volumes whose
        PINNED location asks, in the scrub_ec result shape."""
        from seaweedfs_tpu.storage import ec
        from seaweedfs_tpu.storage.disk_location import DiskLocation
        from seaweedfs_tpu.storage.store import Store

        a_dir = tmp_path / "a"
        a_dir.mkdir()
        va, _ = make_volume(a_dir, vid=1, count=4)
        encode_volume(va)
        store = Store([DiskLocation(str(a_dir), max_volume_count=4)])
        try:
            cache = rs_resident.DeviceShardCache(shard_quantum=1 << 20)
            cache.warm_sizes = ()
            store.ec_device_cache = cache
            ev = ec.EcVolume(str(a_dir), 1)
            for sid in range(14):
                ev.add_shard(sid)
            store.locations[0].ec_volumes[1] = ev
            ev.device_cache = cache
            ev.load_shards_to_device(cache)
            results = store.scrub_all_resident()
            assert set(results) == {1}
            r = results[1]
            assert r["backend"] == "device_megakernel"
            assert r["parity_mismatch_bytes"] == [0, 0, 0, 0]
            assert r["dir"] == str(a_dir)
            assert r["bytes_verified"] > 0 and r["device_calls"] == 1
            # evict -> nothing resident -> empty pass
            cache.clear()
            assert store.scrub_all_resident() == {}
        finally:
            store.close()


class TestScrubWindows:
    """Scrub walks a shard in _SCRUB_WINDOW-lane windows and sums the
    windows' counts on the host.  Every other scrub test fits in one
    window; here the window is patched small, so that full windows, the
    remainder window and (megakernel) the window shared by a two-volume
    stack all run, with corruption in each."""

    WINDOW = 128 << 10

    @pytest.mark.parametrize("layout", ["flat", "blockdiag"])
    def test_remainder_window_and_two_volume_stack(
        self, coded, monkeypatch, layout
    ):
        monkeypatch.setattr(rs_resident, "_SCRUB_WINDOW", self.WINDOW)
        length = coded.shape[1]  # 300_000: two full windows + remainder
        assert 2 * self.WINDOW < length < 3 * self.WINDOW
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout=layout
        )
        try:
            for vid in (1, 2):
                for sid in range(14):
                    cache.put(vid, sid, coded[sid])
            first = coded[10].copy()
            first[17] ^= 0x01  # volume 1, parity row 0, first window
            cache.put(1, 10, first)
            tail = coded[12].copy()
            tail[self.WINDOW + 5] ^= 0x80  # second window
            tail[length - 3] ^= 0x04  # remainder window, last lanes
            cache.put(2, 12, tail)
            want = {1: [1, 0, 0, 0], 2: [0, 0, 2, 0]}

            d0 = _counter(
                "SeaweedFS_volumeServer_ec_scrub_device_dispatch_total",
                {"mode": "per_volume"},
            )
            for vid in (1, 2):
                mismatch, n_lanes = rs_resident.scrub_volume(cache, vid)
                assert mismatch == want[vid], (layout, vid)
            per_volume = -(-n_lanes // self.WINDOW)
            assert per_volume == 3
            assert _counter(
                "SeaweedFS_volumeServer_ec_scrub_device_dispatch_total",
                {"mode": "per_volume"},
            ) == d0 + 2 * per_volume

            # the stack of two shares one window: half the lanes each
            results, stats = rs_resident.scrub_all_resident(cache)
            assert {v: r[0] for v, r in results.items()} == want
            assert stats["device_calls"] == -(-n_lanes // (self.WINDOW // 2))
        finally:
            cache.clear()


class TestPackedMetaWire:
    def test_fused_call_ships_packed_single_row(self, coded):
        """ONE [n_bucket] int32 vector per fused call — 4 bytes/slot,
        half the r09 [2, N] wire — measured off the H2D byte counter."""
        cache = fill_cache(coded, missing=(3, 11), vid=20)
        reqs = [(3, 4096 * i, 4096) for i in range(16)]
        # untimed first call compiles; second call's delta is pure wire
        rs_resident.reconstruct_intervals(
            cache, 20, reqs, kernel="pallas", interpret=True
        )
        h2d0 = _counter("SeaweedFS_volumeServer_ec_h2d_bytes_total")
        outs = rs_resident.reconstruct_intervals(
            cache, 20, reqs, kernel="pallas", interpret=True
        )
        h2d = _counter("SeaweedFS_volumeServer_ec_h2d_bytes_total") - h2d0
        assert h2d == 4 * 16  # packed [16] int32; r09 shipped 8 * 16
        for (sid, off, size), out in zip(reqs, outs):
            assert out == coded[sid][off : off + size].tobytes()
        cache.clear()

    def test_staging_arena_views(self):
        arena = rs_resident.StagingArena(width=32)
        fused = arena.stage_fused([5, 6, 7], pad=2)
        assert fused.dtype == np.int32 and fused.tolist() == [5, 6, 7, 0, 0]
        xla = arena.stage_xla([1, 2], [3, 4], [5, 6], pad=1)
        assert xla.shape == (3, 3)
        assert xla.tolist() == [[1, 2, 0], [3, 4, 0], [5, 6, 0]]
        # views alias the arena block: restaging reuses, never allocates
        fused2 = arena.stage_fused([9], pad=0)
        assert fused2.base is xla.base


class TestObservedShapePersistence:
    def test_roundtrip_atomic_and_corrupt(self, tmp_path):
        path = str(tmp_path / "observed_shapes.json")
        rs_resident._note_observed(8192, 16)
        assert rs_resident.persist_observed_shapes(path)
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        assert [8192, 16] in [b[:2] for b in data["buckets"]]
        assert not os.path.exists(path + ".tmp")  # atomic: tmp renamed
        before = dict(rs_resident._observed_buckets)
        n = rs_resident.load_observed_shapes(path)
        assert n >= 1
        # loading MERGES (adds hits) rather than replacing
        assert (
            rs_resident._observed_buckets[(8192, 16)]
            > before.get((8192, 16), 0) - 1
        )
        # corrupt file: tolerated, path still adopted for future saves
        with open(path, "w", encoding="utf-8") as f:
            f.write("{nope")
        assert rs_resident.load_observed_shapes(path) == 0
        # valid JSON, wrong shape: just as corrupt, must not raise
        for bad in ({"buckets": 3}, {"buckets": [[4096, 1]]}, {}):
            with open(path, "w", encoding="utf-8") as f:
                json.dump(bad, f)
            assert rs_resident.load_observed_shapes(path) == 0
        assert rs_resident.persist_observed_shapes()
        with open(path, encoding="utf-8") as f:
            json.load(f)  # valid again

    def test_dispatch_marks_dirty(self, coded):
        cache = fill_cache(coded, missing=(3, 11), vid=30)
        rs_resident._observed_dirty = False
        rs_resident.reconstruct_intervals(cache, 30, [(3, 0, 2048)])
        assert rs_resident._observed_dirty
        cache.clear()


class TestCompileCacheStatus:
    @pytest.fixture
    def fresh_cache_state(self, monkeypatch):
        """enable_persistent_compile_cache is once-per-process: give the
        test a process that has not called it, and put jax.config back."""
        import jax

        monkeypatch.setattr(rs_resident, "_COMPILE_CACHE_SET", False)
        monkeypatch.setattr(rs_resident, "_observed_path", None)
        was_dir = jax.config.jax_compilation_cache_dir
        was_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", was_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", was_min
        )

    def test_bad_path_observable(
        self, tmp_path, monkeypatch, fresh_cache_state
    ):
        """A bad cache dir must not just log once: the failure is a
        gauge plus a status field operators can query."""
        blocker = tmp_path / "not_a_dir"
        blocker.write_text("file, not dir")
        monkeypatch.delenv(rs_resident.COMPILE_CACHE_ENV, raising=False)
        monkeypatch.setattr(
            rs_resident, "COMPILE_CACHE_DIR", str(blocker / "cache")
        )
        assert not rs_resident.enable_persistent_compile_cache()
        st = rs_resident.compile_cache_status()
        assert st["enabled"] is False and st["error"]
        assert str(blocker / "cache") == st["path"]
        assert (
            stats_metrics.VOLUME_SERVER_EC_COMPILE_CACHE_ENABLED._value.get()
            == 0
        )

    def test_env_dir_left_alone(
        self, tmp_path, monkeypatch, fresh_cache_state
    ):
        """With JAX_COMPILATION_CACHE_DIR set the program uses that
        directory and sets none in code: jax.config's value is whatever
        it was (JAX itself adopts the variable at import)."""
        import jax

        env_dir = tmp_path / "from_env"
        monkeypatch.setenv(rs_resident.COMPILE_CACHE_ENV, str(env_dir))
        jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
        assert rs_resident.enable_persistent_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
        assert rs_resident.compile_cache_status()["path"] == str(env_dir)
        # every warm shape persists, however fast it compiled
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # observed_shapes.json follows the same directory
        assert rs_resident._observed_path == str(
            env_dir / rs_resident.OBSERVED_SHAPES_FILE
        )

    def test_unset_env_one_fixed_path(self, monkeypatch, fresh_cache_state):
        """Unset, the cache goes to ONE fixed path inside the checkout —
        two servers started on two different -dir values resolve to the
        same directory (it is part of the cache key; one that moved with
        the data would never hit)."""
        import jax

        monkeypatch.delenv(rs_resident.COMPILE_CACHE_ENV, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert rs_resident.COMPILE_CACHE_DIR == os.path.join(
            repo, ".jax_compile_cache"
        )
        seen = []
        for _data_dir in ("/data/a", "/data/b"):
            # the bootstrap takes no directory at all: -dir cannot
            # reach the cache path
            monkeypatch.setattr(rs_resident, "_COMPILE_CACHE_SET", False)
            assert rs_resident.enable_persistent_compile_cache()
            seen.append(
                (
                    jax.config.jax_compilation_cache_dir,
                    rs_resident.compile_cache_status()["path"],
                )
            )
        assert seen == [(rs_resident.COMPILE_CACHE_DIR,) * 2] * 2

    def test_telemetry_carries_compile_cache_state(self):
        from seaweedfs_tpu.pb import master_pb2
        from seaweedfs_tpu.stats import ClusterTelemetry

        tel = master_pb2.VolumeServerTelemetry(
            device_budget_bytes=1, compile_cache_enabled=True
        )
        ct = ClusterTelemetry(pulse_seconds=1)
        ct.observe("n1:8080", tel, now=50.0)
        doc = ct.health(now=50.1)
        assert doc["nodes"]["n1:8080"]["device"]["compile_cache_enabled"]


def test_scrub_all_rpc_and_idle_loop(tmp_path):
    """The megakernel through the serving surfaces: VolumeEcShardsVerify
    all_resident returns per-volume rows for two pinned volumes, and the
    serving-idle scrub loop consumes the fused pass (corruption raises
    the gauge through the megakernel path)."""
    import asyncio

    from seaweedfs_tpu import stats
    from seaweedfs_tpu.pb import Stub, channel, volume_server_pb2
    from seaweedfs_tpu.server.volume import VolumeServer
    from seaweedfs_tpu.storage.ec import encoder, layout
    from seaweedfs_tpu.storage.volume_info import save_volume_info

    rng = np.random.default_rng(17)
    for vid in (1, 2):
        base = str(tmp_path / str(vid))
        with open(base + ".dat", "wb") as f:
            f.write(rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes())
        encoder.write_ec_files(base, backend="cpu")
        save_volume_info(base + ".vif", {"version": 3})
        open(base + ".ecx", "ab").close()
        os.remove(base + ".dat")

    async def go():
        vs = VolumeServer(
            masters=[], directories=[str(tmp_path)], port=0, grpc_port=0,
            ec_backend="cpu", ec_scrub_interval_seconds=1,
        )
        # small quantum: the default 64MB-per-shard padding would blow
        # the budget with 28 tiny shards and evict forever
        cache = rs_resident.DeviceShardCache(
            budget_bytes=1 << 30, shard_quantum=1 << 20
        )
        cache.warm_sizes = ()  # CI convention: no reconstruct warm plan
        vs.store.ec_device_cache = cache
        for vid in (1, 2):
            ev = vs.store.find_ec_volume(vid)
            ev.device_cache = cache
            vs.store._pin_ec_shards_async(ev)
        await vs.start(heartbeat=False)
        try:
            deadline = time.time() + 120
            while time.time() < deadline:
                if all(len(cache.shard_ids(v)) == 14 for v in (1, 2)):
                    break
                await asyncio.sleep(0.2)
            assert all(len(cache.shard_ids(v)) == 14 for v in (1, 2))

            stub = Stub(channel(vs.grpc_url), volume_server_pb2,
                        "VolumeServer")
            r = await stub.VolumeEcShardsVerify(
                volume_server_pb2.VolumeEcShardsVerifyRequest(
                    all_resident=True
                )
            )
            assert r.backend == "device_megakernel"
            rows = {row.volume_id: row for row in r.volumes}
            assert set(rows) == {1, 2}
            for row in rows.values():
                assert list(row.parity_mismatch_bytes) == [0, 0, 0, 0]
                assert row.bytes_verified > 0

            # corrupt volume 2's RESIDENT parity copy: the idle loop's
            # megakernel pass must flag it (files untouched — only the
            # fused pass sees memory)
            base = str(tmp_path / "2")
            bad = np.fromfile(base + layout.to_ext(11), np.uint8)
            bad[2048] ^= 0x20
            cache.put(2, 11, bad)
            deadline = time.time() + 30
            while time.time() < deadline:
                if stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 1:
                    break
                await asyncio.sleep(0.2)
            assert (
                stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE._value.get() == 1
            )
            r = await stub.VolumeEcShardsVerify(
                volume_server_pb2.VolumeEcShardsVerifyRequest(
                    all_resident=True
                )
            )
            rows = {row.volume_id: row for row in r.volumes}
            assert list(rows[2].parity_mismatch_bytes) == [0, 1, 0, 0]
        finally:
            await vs.stop()
        from seaweedfs_tpu.pb.rpc import close_all_channels

        await close_all_channels()

    asyncio.run(go())
