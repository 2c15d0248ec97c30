"""Viewguard-instrumented stress: zero-copy reads racing budget
eviction, vacuum/compaction, and in-flight DevicePipeline batches — the
runtime half of graftlint's GL109/GL110 dataflow rules.

Contracts:
  * guard semantics — a mutated-under-the-holder view, an arena reuse
    with outstanding exports, and a donated outstanding view all raise
    ViewGuardViolation; the clean patterns (release, slot-scoped arena
    exports, copies) stay quiet;
  * EC race — zero-copy batch reads of a degraded volume racing budget
    eviction stay byte-exact or fail a clean CacheMiss, never stale
    bytes, with every payload view verified at release;
  * vacuum race — a compaction that rewrites the .dat under outstanding
    zero-copy views leaves every one of them byte-stable (the pread
    `bytes` + refcounted old-fd design is what PROVES it, at the
    `vacuum.commit` verification hook).

All device work runs on the CPU test mesh (conftest); the EC stress
pins a DeviceShardCache exactly like the lockwatch stress does.
"""
import random
import threading
import time

import numpy as np
import pytest

import viewguard
from seaweedfs_tpu.ops import rs_resident
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage import vacuum as vacuum_mod
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume


def _make_volume(tmp_path, vid=31, count=24, seed=11):
    rng = random.Random(seed)
    v = Volume(str(tmp_path), vid)
    blobs = {}
    for i in range(1, count + 1):
        size = rng.choice([100, 1337, 4096, 70_000])
        data = rng.randbytes(size)
        cookie = rng.getrandbits(32)
        v.write(i, cookie, data, name=f"f{i}".encode())
        blobs[i] = (cookie, data)
    v.sync()
    return v, blobs


# ------------------------------------------------------- guard semantics


def test_guard_detects_mutation_under_outstanding_view():
    g = viewguard.ViewGuard()
    src = bytearray(b"stable bytes here")
    view = memoryview(src)[7:12]
    g.export(view, src, "window")
    src[8] ^= 0xFF  # scribble under the holder
    with pytest.raises(viewguard.ViewGuardViolation, match="changed"):
        g.release(view)


def test_guard_clean_release_and_copy():
    g = viewguard.ViewGuard()
    src = bytearray(b"stable bytes here")
    view = memoryview(src)[7:12]
    g.export(view, src, "window")
    g.release(view)
    src[0] ^= 0xFF  # mutation AFTER release is fine
    g.assert_clean()
    assert g.releases_total == 1


def test_guard_arena_reuse_with_outstanding_export_fails():
    with viewguard.watch() as g:
        arena = rs_resident.StagingArena(width=64)
        arena.stage_fused([1, 2, 3], 1)  # export outstanding
        with pytest.raises(viewguard.ViewGuardViolation, match="reuses"):
            arena.stage_fused([4, 5], 0)
    assert g.violations


def test_guard_calls_of_one_batch_stage_into_their_own_blocks():
    """Two calls of a batch in flight hold a row-block each: staging
    the second is clean, restaging the first's block before it is given
    back is the scribble, and after `give` the block is free."""
    with viewguard.watch() as g:
        arena = rs_resident.StagingArena(width=64)
        first, second = arena.take(), arena.take()
        arena.stage_fused([1, 2, 3], 1, first)
        arena.stage_fused([4, 5], 0, second)  # its own rows: clean
        assert g.outstanding == 2 and not g.violations
        with pytest.raises(viewguard.ViewGuardViolation, match="reuses"):
            arena.stage_fused([6], 0, first)
    with viewguard.watch() as g:
        arena = rs_resident.StagingArena(width=64)
        first = arena.take()
        arena.stage_fused([1, 2, 3], 1, first)
        arena.give(first)  # the call has its result
        assert g.outstanding == 0
        arena.stage_fused([6], 0, arena.take())
    g.assert_clean()


def test_guard_slot_scoped_arena_exports_release_cleanly():
    with viewguard.watch() as g:
        pipe = rs_resident.DevicePipeline(slots=1)
        for _ in range(3):  # same arena reused across slots: clean
            with pipe.slot() as s:
                s.arena.stage_fused([7, 8, 9], 0)
        assert g.outstanding == 0
    g.assert_clean()
    assert g.exports_total == 3 and g.releases_total == 3


def test_guard_donation_of_outstanding_view_fails():
    with viewguard.watch() as g:
        arena = rs_resident.StagingArena(width=64)
        vec = arena.stage_fused([1], 0)
        with pytest.raises(viewguard.ViewGuardViolation, match="donates"):
            g.check_donation(vec, "jit call")
    # a fresh (untracked) array is not a donation hazard
    g.check_donation(np.zeros(4, dtype=np.int32), "jit call")


def test_guard_dispatch_boundary_rejects_live_export_on_cpu():
    """The wired enforcement: on a zero-copy PJRT client (the CPU test
    mesh), an outstanding arena export reaching the donated position of
    `_dispatch_call` fails BEFORE any device work — the regression
    guard for reconstruct_intervals' arena-gating invariant."""
    with viewguard.watch() as g:
        arena = rs_resident.StagingArena(width=64)
        vec = arena.stage_fused([1, 2], 0)
        with pytest.raises(viewguard.ViewGuardViolation, match="donates"):
            rs_resident._dispatch_call(
                "fused", vec, None, (), 0, 0, 1, 0, 0, "xla", True
            )
    assert g.violations


def test_guard_tracks_zero_copy_needle_parse():
    with viewguard.watch() as g:
        raw = Needle(id=0xBEE, cookie=3, data=b"z" * 500).to_bytes()
        n = Needle.from_bytes(raw, copy=False)
        assert g.outstanding == 1
        g.release(n.data)
        assert g.outstanding == 0
        # copying parse registers nothing
        Needle.from_bytes(raw, copy=True)
        assert g.outstanding == 0
    g.assert_clean()


def test_guard_catches_bytearray_scribble_at_exit():
    with viewguard.watch() as g:
        raw = bytearray(Needle(id=0xF00, cookie=1, data=b"q" * 256).to_bytes())
        n = Needle.from_bytes(raw, copy=False)
        assert isinstance(n.data, memoryview)
        raw[30] ^= 0xFF  # payload byte under the outstanding view
    with pytest.raises(viewguard.ViewGuardViolation, match="changed"):
        g.assert_clean()


# ---------------------------------------------------------- EC race


VID = 33
MISSING = 5


def test_zero_copy_ec_reads_race_eviction_under_viewguard(tmp_path):
    """Readers pull zero-copy batches through the device-resident
    reconstruct while an evictor cycles shards across the budget: every
    successful read is byte-exact (views verified at release), losses
    fail as clean CacheMiss, and no view ever reads drifted bytes."""
    v, blobs = _make_volume(tmp_path, vid=VID)
    base = Volume.base_name(v.dir, v.id, v.collection)
    ec.write_ec_files(base, backend="cpu")
    ec.write_sorted_file_from_idx(base)
    v.close()

    errors: list[BaseException] = []
    good_reads = 0
    clean_misses = 0
    stop = threading.Event()
    lock = threading.Lock()

    with viewguard.watch() as g:
        ev = ec.EcVolume(str(tmp_path), v.id)
        for sid in range(14):
            if sid != MISSING:
                ev.add_shard(sid)
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag"
        )
        cache.warm_sizes = ()  # CI convention: no AOT grid compile
        ev.load_shards_to_device(cache)
        per_shard = cache.bytes_used // 13
        cache.budget = per_shard * 12  # every re-pin evicts the LRU

        nids = sorted(blobs)

        def reader(seed: int):
            nonlocal good_reads, clean_misses
            rng = random.Random(seed)
            deadline = time.time() + 20
            mine = 0
            while time.time() < deadline and mine < 8:
                batch = rng.sample(nids, 3)
                try:
                    out = ev.read_needles_batch(
                        batch, backend="cpu", zero_copy=True
                    )
                except rs_resident.CacheMiss:
                    with lock:
                        clean_misses += 1
                    time.sleep(0.01)
                    continue
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                for nid, res in zip(batch, out):
                    if isinstance(res, rs_resident.CacheMiss):
                        with lock:
                            clean_misses += 1
                        continue
                    if isinstance(res, Exception):
                        errors.append(res)
                        return
                    want = blobs[nid][1]
                    if bytes(res.data) != want:
                        errors.append(
                            AssertionError(f"stale bytes for {nid}")
                        )
                        return
                    # done reading: verify-and-drop the exported view
                    if isinstance(res.data, memoryview):
                        g.release(res.data)
                mine += 1
                with lock:
                    good_reads += 1

        def evictor():
            i = 0
            sids = [s for s in range(14) if s != MISSING]
            while not stop.is_set():
                sid = sids[i % len(sids)]
                try:
                    cache.put(
                        VID, sid,
                        np.fromfile(ev.shards[sid].path, dtype=np.uint8),
                    )
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                i += 1

        threads = [
            threading.Thread(target=reader, args=(1,), name="reader"),
            threading.Thread(target=reader, args=(2,), name="reader2"),
            threading.Thread(target=evictor, name="evictor"),
        ]
        for t in threads:
            t.start()
        threads[0].join()
        threads[1].join()
        stop.set()
        threads[2].join()
        ev.close()

    assert not errors, errors
    assert good_reads > 0
    assert g.exports_total > 0, "no zero-copy views were ever tracked"
    g.assert_clean()


def test_sharded_zero_copy_reads_race_eviction_and_warm(tmp_path):
    """r19 mesh-layout race: readers pull zero-copy batches through the
    LANE-SHARDED reconstruct while an evictor cycles shards across the
    per-device budgets AND a warm thread keeps re-arming the sharded
    AOT plan.  Every successful read is byte-exact (views verified at
    release), losses are clean CacheMiss (ColdShape sheds included —
    the host path serves the same bytes), never stale bytes."""
    v, blobs = _make_volume(tmp_path, vid=VID)
    base = Volume.base_name(v.dir, v.id, v.collection)
    ec.write_ec_files(base, backend="cpu")
    ec.write_sorted_file_from_idx(base)
    v.close()

    errors: list[BaseException] = []
    good_reads = 0
    clean_misses = 0
    stop = threading.Event()
    lock = threading.Lock()

    with viewguard.watch() as g:
        ev = ec.EcVolume(str(tmp_path), v.id)
        for sid in range(14):
            if sid != MISSING:
                ev.add_shard(sid)
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag",
            mesh_devices=0, mesh_min_shard_bytes=0,
        )
        cache.warm_sizes = (4096,)
        cache.warm_counts = (4,)
        ev.load_shards_to_device(cache)
        assert cache.placement(VID) == "mesh"
        # per-device budget of 12 of the 13 pinned shards' chunks:
        # every re-pin crosses the per-device budgets and evicts the
        # LRU sharded entry on EVERY device at once
        cache.budget = (cache.bytes_used // 13) * 12

        nids = sorted(blobs)

        def reader(seed: int):
            nonlocal good_reads, clean_misses
            rng = random.Random(seed)
            deadline = time.time() + 20
            mine = 0
            while time.time() < deadline and mine < 8:
                batch = rng.sample(nids, 3)
                try:
                    out = ev.read_needles_batch(
                        batch, backend="cpu", zero_copy=True
                    )
                except rs_resident.CacheMiss:
                    with lock:
                        clean_misses += 1
                    time.sleep(0.01)
                    continue
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                for nid, res in zip(batch, out):
                    if isinstance(res, rs_resident.CacheMiss):
                        with lock:
                            clean_misses += 1
                        continue
                    if isinstance(res, Exception):
                        errors.append(res)
                        return
                    want = blobs[nid][1]
                    if bytes(res.data) != want:
                        errors.append(
                            AssertionError(f"stale bytes for {nid}")
                        )
                        return
                    if isinstance(res.data, memoryview):
                        g.release(res.data)
                mine += 1
                with lock:
                    good_reads += 1

        def evictor():
            i = 0
            sids = [s for s in range(14) if s != MISSING]
            while not stop.is_set():
                sid = sids[i % len(sids)]
                try:
                    cache.put(
                        VID, sid,
                        np.fromfile(ev.shards[sid].path, dtype=np.uint8),
                    )
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                i += 1

        def warmer():
            while not stop.is_set():
                try:
                    rs_resident.warm(
                        cache, VID, sizes=cache.warm_sizes,
                        counts=cache.warm_counts, aot=True, wait=False,
                    )
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                time.sleep(0.05)

        threads = [
            threading.Thread(target=reader, args=(5,), name="s-reader"),
            threading.Thread(target=reader, args=(6,), name="s-reader2"),
            threading.Thread(target=evictor, name="s-evictor"),
            threading.Thread(target=warmer, name="s-warmer"),
        ]
        for t in threads:
            t.start()
        threads[0].join()
        threads[1].join()
        stop.set()
        threads[2].join()
        threads[3].join()
        ev.close()

    assert not errors, errors
    assert good_reads > 0
    assert g.exports_total > 0, "no zero-copy views were ever tracked"
    g.assert_clean()


# ------------------------------------------- tier promote/demote race


def test_zero_copy_reads_race_tier_promotion_demotion(tmp_path):
    """r15 ladder race: readers pull zero-copy batches while a tiering
    controller flips two volumes between HBM, the host-RAM tier, and
    disk (budget fits only one volume, hysteresis disabled so every
    flip is a promote+demote pair).  Demotion routes through the
    claim/evict release path and host staging, so every successful read
    is byte-exact (views — over reconstruct output AND host-tier
    arrays — verified at release) and losses are clean CacheMiss, never
    stale bytes."""
    from seaweedfs_tpu.ops.rs_resident import DeviceShardCache
    from seaweedfs_tpu.serving import ServingConfig
    from seaweedfs_tpu.serving.tiering import TieringController
    from seaweedfs_tpu.storage.disk_location import DiskLocation
    from seaweedfs_tpu.storage.store import Store

    vids = (61, 62)
    blobs = {}
    for vid in vids:
        v, vol_blobs = _make_volume(tmp_path, vid=vid, count=10, seed=vid)
        base = Volume.base_name(v.dir, v.id, v.collection)
        ec.write_ec_files(base, backend="cpu")
        ec.write_sorted_file_from_idx(base)
        v.close()
        import os

        for ext in (".dat", ".idx"):
            if os.path.exists(base + ext):
                os.remove(base + ext)
        blobs[vid] = vol_blobs

    errors: list[BaseException] = []
    good_reads = 0
    clean_misses = 0
    stop = threading.Event()
    lock = threading.Lock()

    with viewguard.watch() as g:
        store = Store([DiskLocation(str(tmp_path))])
        cache = DeviceShardCache(shard_quantum=1 << 20, layout="blockdiag")
        cache.warm_sizes = ()  # CI convention: no AOT grid compile
        evs = {}
        for vid in vids:
            store.mount_ec_shards(vid, list(range(14)))
            ev = store.find_ec_volume(vid)
            ev.device_cache = cache
            # degrade each volume differently so batch reads exercise
            # the device/host reconstruct, not just local preads
            ev.shards.pop(vid % 14).close()
            evs[vid] = ev
        # cache attached AFTER the mounts: the controller owns every
        # placement (no mount-time pin threads racing the ladder)
        store.ec_device_cache = cache
        ctl = TieringController(
            store,
            ServingConfig(
                tier_host_cache_mb=64,
                tier_min_residency_seconds=0.0,
                tier_promote_ratio=1.0,
                tier_interval_seconds=0.0,
            ).validated(),
        )
        ev0 = evs[vids[0]]
        cache.budget = len(ev0.shards) * cache._padded_len(ev0.shard_size)

        def reader(seed: int):
            nonlocal good_reads, clean_misses
            rng = random.Random(seed)
            deadline = time.time() + 30
            # read until the mover finished its flips (stop) so every
            # promotion/demotion races live zero-copy reads
            while time.time() < deadline and not stop.is_set():
                vid = vids[rng.random() > 0.5]
                nids = rng.sample(sorted(blobs[vid]), 3)
                try:
                    out = evs[vid].read_needles_batch(
                        nids, backend="cpu", zero_copy=True
                    )
                except rs_resident.CacheMiss:
                    with lock:
                        clean_misses += 1
                    time.sleep(0.005)
                    continue
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                for nid, res in zip(nids, out):
                    if isinstance(res, rs_resident.CacheMiss):
                        with lock:
                            clean_misses += 1
                        continue
                    if isinstance(res, Exception):
                        errors.append(res)
                        return
                    if bytes(res.data) != blobs[vid][nid][1]:
                        errors.append(
                            AssertionError(f"stale bytes for {vid}/{nid}")
                        )
                        return
                    if isinstance(res.data, memoryview):
                        g.release(res.data)
                with lock:
                    good_reads += 1

        def mover():
            try:
                for flip in range(6):
                    hot = vids[flip % 2]
                    for v in vids:
                        ctl.heat.forget(v)
                    for _ in range(10):
                        ctl.note_read(hot)
                    ctl.rebalance()
                    time.sleep(0.05)  # let reads land between flips
            except BaseException as e:  # noqa: BLE001 — collected
                errors.append(e)
            finally:
                stop.set()

        threads = [
            threading.Thread(target=reader, args=(1,), name="tier-reader"),
            threading.Thread(target=reader, args=(2,), name="tier-reader2"),
            threading.Thread(target=mover, name="tier-mover"),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        promos = sum(ctl.promotions.values())
        demos = sum(ctl.demotions.values())
        for ev in evs.values():
            ev.close()
        store.close()

    assert not errors, errors
    assert good_reads > 0
    # the race actually raced: the ladder moved under the readers
    assert promos >= 2 and demos >= 1, (promos, demos)
    assert g.exports_total > 0
    g.assert_clean()


# -------------------------------------------------------- vacuum race


def test_vacuum_rewrite_keeps_outstanding_views_byte_stable(tmp_path):
    """Hold zero-copy views over live needles while vacuum compacts the
    volume (twice, with deletes in between): the commit-time guard hook
    re-verifies every outstanding view, and every held view still reads
    its original bytes afterwards."""
    v, blobs = _make_volume(tmp_path, vid=41, count=16)
    with viewguard.watch() as g:
        held = []
        for nid in sorted(blobs)[:6]:
            n = v.read(nid, cookie=blobs[nid][0], zero_copy=True)
            assert isinstance(n.data, memoryview)
            held.append((nid, n))
        # create garbage, then compact UNDER the outstanding views
        for nid in sorted(blobs)[10:]:
            v.delete(nid, cookie=blobs[nid][0])
        assert vacuum_mod.vacuum(v) > 0
        # second cycle: delete some of the very needles being held
        for nid, _ in held[:2]:
            v.delete(nid, cookie=blobs[nid][0])
        vacuum_mod.vacuum(v)
        for nid, n in held:
            assert bytes(n.data) == blobs[nid][1], f"needle {nid} drifted"
            g.release(n.data)
        # post-vacuum reads still serve the survivors byte-exact
        for nid in sorted(blobs)[6:10]:
            n = v.read(nid, cookie=blobs[nid][0], zero_copy=True)
            assert bytes(n.data) == blobs[nid][1]
            g.release(n.data)
    g.assert_clean()
    v.close()


def test_concurrent_vacuum_vs_zero_copy_readers(tmp_path):
    """Threaded race: readers stream zero-copy views while a vacuum
    thread compacts repeatedly; every read is byte-exact and the guard
    verifies every view at release and at each commit."""
    v, blobs = _make_volume(tmp_path, vid=43, count=20)
    live = sorted(blobs)[:12]
    for nid in sorted(blobs)[12:]:
        v.delete(nid, cookie=blobs[nid][0])

    errors: list[BaseException] = []
    stop = threading.Event()
    reads = 0
    lock = threading.Lock()

    with viewguard.watch() as g:
        def reader(seed: int):
            nonlocal reads
            rng = random.Random(seed)
            while not stop.is_set():
                nid = rng.choice(live)
                try:
                    n = v.read(nid, cookie=blobs[nid][0], zero_copy=True)
                    time.sleep(0.001)  # hold the view across the race
                    if bytes(n.data) != blobs[nid][1]:
                        errors.append(AssertionError(f"drift on {nid}"))
                        return
                    g.release(n.data)
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                with lock:
                    reads += 1

        def vacuumer():
            try:
                for _ in range(5):
                    vacuum_mod.vacuum(v)
                    time.sleep(0.01)
            except BaseException as e:  # noqa: BLE001 — collected
                errors.append(e)

        threads = [
            threading.Thread(target=reader, args=(1,)),
            threading.Thread(target=reader, args=(2,)),
            threading.Thread(target=vacuumer),
        ]
        for t in threads:
            t.start()
        threads[2].join()
        stop.set()
        threads[0].join()
        threads[1].join()

    assert not errors, errors
    assert reads > 0
    g.assert_clean()
    v.close()
