"""Ingest plane tests (seaweedfs_tpu/ingest/ + ops/rs_ingest.py): the
streaming write-path EC encode, unit-tested at small stripe geometry.

Covers the PR's contracts:
  * byte equality — a volume grown by ragged appends and stream-encoded
    row by row seals to EXACTLY the shard bytes the offline
    `write_ec_files` computes (the layout invariant the plane rests on);
  * escape hatch — crossing the large-row boundary invalidates the
    pipeline, seal() falls back to offline, and the parity scratch is
    cleaned up;
  * backpressure — a starved arena first blocks the writer, then (past
    the budget) sheds the pipeline to offline instead of wedging the
    upload;
  * group commit — N concurrent writers are durably acked by FEWER
    fsyncs than writers, one per volume per batch, with flush errors
    propagated to every parked writer;
  * admission — doomed uploads (too big for the remaining deadline at
    the floor rate) are refused at the door, and the bulk write tier
    binds first under queue pressure while interactive keeps admitting;
  * viewguard — the staged-row lifecycle (stage/seal/reclaim) and the
    CPU donation gate are enforced at test time, including a full race
    of streamed writes vs zero-copy reads vs host-tier churn on the
    SAME volume.

All geometry-dependent tests monkeypatch the pipeline module's block
constants (read at call time, never captured) so a "10 MB stripe row"
is 10 KB and the suite stays seconds-scale.
"""
import os
import shutil
import threading
import time

import numpy as np
import pytest

import viewguard
from seaweedfs_tpu import stats
from seaweedfs_tpu.ingest import GroupCommitter, IngestConfig, IngestPipeline, IngestPlane
from seaweedfs_tpu.ingest import pipeline as pipeline_mod
from seaweedfs_tpu.ops import rs_ingest
from seaweedfs_tpu.serving.tiering import HeatTracker, HostShardCache
from seaweedfs_tpu.storage.ec import encoder
from seaweedfs_tpu.storage.ec.layout import DATA_SHARDS, to_ext
from seaweedfs_tpu.storage.volume import Volume

SMALL = 1024
LARGE = 8192
ROW = DATA_SHARDS * SMALL  # 10 KB stripe row
STREAMABLE = DATA_SHARDS * LARGE  # 80 KB small-row regime


def _sample(name, labels=None):
    return stats.REGISTRY.get_sample_value(name, labels or {}) or 0.0


@pytest.fixture
def small_geometry(monkeypatch):
    """Shrink the stripe geometry 1024x; every constant is read from the
    pipeline module at call time, so patching the module globals is
    enough (the arena, feed loop, and seal all follow)."""
    monkeypatch.setattr(pipeline_mod, "SMALL_BLOCK_SIZE", SMALL)
    monkeypatch.setattr(pipeline_mod, "LARGE_BLOCK_SIZE", LARGE)
    monkeypatch.setattr(pipeline_mod, "ROW_BYTES", ROW)
    monkeypatch.setattr(pipeline_mod, "STREAMABLE_BYTES", STREAMABLE)


class FakeVolume:
    """The minimal surface IngestPipeline/GroupCommitter touch."""

    def __init__(self, dat_path, vid=7):
        self.id = vid
        self.dat_path = dat_path
        self.syncs = 0

    @property
    def content_size(self):
        return os.path.getsize(self.dat_path)

    def sync(self):
        self.syncs += 1


def _append(path, nbytes, rng):
    data = rng.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes()
    with open(path, "ab") as f:
        f.write(data)
    return data


def shard_bytes(base):
    out = {}
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out[i] = f.read()
    return out


def _cfg(**kw):
    kw.setdefault("backend", "cpu")
    return IngestConfig(**kw)


# --------------------------------------------------- streamed == offline


def test_streamed_seal_matches_offline_encode(tmp_path, small_geometry):
    """Ragged appends + feed() after each; seal() consumes the streamed
    parity and the 14 shard files are byte-identical to the offline
    write_ec_files on a copy of the same .dat."""
    base = str(tmp_path / "v1")
    dat = base + ".dat"
    open(dat, "wb").close()
    vol = FakeVolume(dat, vid=1)
    p = IngestPipeline(vol, rs_ingest.StreamEncoder("cpu"), _cfg())
    rng = np.random.default_rng(5)
    # 3 complete rows + a ragged tail, grown in awkward chunk sizes
    for nbytes in (4097, ROW, 9999, ROW + 1, 123):
        _append(dat, nbytes, rng)
        p.feed()
    assert vol.content_size == 4097 + ROW + 9999 + ROW + 1 + 123
    assert p.staged_rows == vol.content_size // ROW == 3

    assert p.seal(backend="cpu") is True
    assert p.encoded_rows == 3
    assert p.rows_host == 3 and p.rows_device == 0  # cpu backend

    # offline oracle on an identical .dat
    base2 = str(tmp_path / "v2")
    shutil.copyfile(dat, base2 + ".dat")
    encoder.write_ec_files(
        base2, backend="cpu", large_block=LARGE, small_block=SMALL
    )
    got, want = shard_bytes(base), shard_bytes(base2)
    for i in range(14):
        assert got[i] == want[i], f"shard {i} diverged from offline encode"
    # scratch consumed by the rename, not left behind
    assert not [f for f in os.listdir(tmp_path) if ".ing" in f]


def test_large_row_boundary_invalidates_and_cleans_scratch(
    tmp_path, small_geometry
):
    """One byte past DATA_SHARDS x LARGE_BLOCK the small-row layout is
    void: the pipeline invalidates, seal() reports offline, and no
    parity scratch survives to poison a later encode."""
    base = str(tmp_path / "v9")
    dat = base + ".dat"
    open(dat, "wb").close()
    vol = FakeVolume(dat, vid=9)
    p = IngestPipeline(vol, rs_ingest.StreamEncoder("cpu"), _cfg())
    rng = np.random.default_rng(6)
    _append(dat, 2 * ROW, rng)
    p.feed()
    _append(dat, STREAMABLE, rng)  # now past the boundary
    p.feed()
    assert not p.valid
    assert "large-row" in p.invalid_reason
    assert p.seal(backend="cpu") is False
    assert not [f for f in os.listdir(tmp_path) if ".ing" in f]


# ------------------------------------------------------- backpressure


class _BlockedEncoder(rs_ingest.StreamEncoder):
    """Host encode parks on an event: the arena cannot drain."""

    def __init__(self):
        super().__init__("cpu")
        self.release = threading.Event()

    def encode_host(self, rows):
        assert self.release.wait(10), "test forgot to release the encoder"
        return super().encode_host(rows)


def test_arena_stage_blocks_then_raises():
    arena = rs_ingest.IngestArena(2, 64, slots=1)
    buf = arena.stage(timeout_s=0.01)
    assert arena.free_slots == 0
    with pytest.raises(rs_ingest.ArenaExhausted):
        arena.stage(timeout_s=0.01)
    assert arena.waits == 1
    arena.reclaim(buf)
    assert arena.stage(timeout_s=0.01) is buf  # pool recycles the row


def test_starved_arena_sheds_pipeline_to_offline(tmp_path, small_geometry):
    """Encode leg wedged + 1-slot arena: the second row's stage() waits
    out the backpressure budget, the pipeline invalidates (writes keep
    landing), and seal() runs offline — the upload never wedges."""
    base = str(tmp_path / "v3")
    dat = base + ".dat"
    open(dat, "wb").close()
    vol = FakeVolume(dat, vid=3)
    enc = _BlockedEncoder()
    p = IngestPipeline(vol, enc, _cfg(arena_slots=1, backpressure_ms=50))
    rng = np.random.default_rng(7)
    shed_before = _sample(
        "SeaweedFS_volumeServer_ingest_shed_total", {"reason": "arena"}
    )
    _append(dat, 2 * ROW, rng)
    t0 = time.monotonic()
    p.feed()  # row 0 stages; row 1 starves behind the wedged encoder
    assert time.monotonic() - t0 >= 0.05  # the writer genuinely waited
    assert not p.valid
    assert "arena starved" in p.invalid_reason
    assert p.arena.waits >= 1
    assert _sample(
        "SeaweedFS_volumeServer_ingest_shed_total", {"reason": "arena"}
    ) == shed_before + 1
    enc.release.set()  # unwedge so the worker drains and close() joins
    assert p.seal(backend="cpu") is False
    # the volume is still perfectly encodable offline
    encoder.write_ec_files(
        base, backend="cpu", large_block=LARGE, small_block=SMALL
    )
    assert len(shard_bytes(base)) == 14


# ------------------------------------------------------- group commit


class _Counting:
    def __init__(self, vid):
        self.id = vid
        self.syncs = 0

    def sync(self):
        self.syncs += 1


def test_stream_encoder_sheds_a_cold_row_then_serves_it_from_the_device():
    """A live write never compiles inline: a row width the AOT registry
    does not hold raises ColdShape (the caller encodes that row on the
    host) and arms the compile off the write path; once parked, the same
    width encodes on the device, byte-equal to the host codec."""
    from seaweedfs_tpu.ops import rs, rs_ingest, rs_resident

    enc = rs_ingest.StreamEncoder(backend="xla")
    block = 5 * 1024 + 384  # a width no other test warms
    rows = np.random.default_rng(23).integers(
        0, 256, size=(10, block), dtype=np.uint8
    )
    want = rs.RSCodec(backend="numpy").encode_all(rows)[10:]
    assert not enc.shape_is_warm(block)
    with pytest.raises(rs_resident.ColdShape):
        enc.encode(rows)
    assert enc.device_rows == 0
    np.testing.assert_array_equal(enc.encode_host(rows), want)
    assert enc.host_rows == 1
    # the shed armed the compile on the background executor: wait for
    # the registry, not for a clock
    deadline = time.time() + 120
    while not enc.shape_is_warm(block) and time.time() < deadline:
        time.sleep(0.05)
    assert enc.shape_is_warm(block), rs_resident.aot_stats()
    np.testing.assert_array_equal(enc.encode(rows), want)
    assert (enc.device_rows, enc.host_rows) == (1, 1)


def test_group_commit_batches_and_dedups_per_volume():
    """12 writers over 2 volumes pile into shared batches: every writer
    is acked, but the flusher issued FEWER syncs than writers (one per
    volume per batch) — the whole point of group commit."""
    gc = GroupCommitter(max_batch=64, max_delay_s=0.15)
    try:
        vols = [_Counting(1), _Counting(2)]
        barrier = threading.Barrier(12)
        errs = []

        def writer(i):
            try:
                barrier.wait(5)
                gc.commit(vols[i % 2], timeout_s=10)
            except BaseException as e:  # noqa: BLE001 — collected
                errs.append(e)

        threads = [
            threading.Thread(target=writer, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(15)
        assert not errs
        total = vols[0].syncs + vols[1].syncs
        assert vols[0].syncs >= 1 and vols[1].syncs >= 1
        assert total < 12, f"no batching: {total} syncs for 12 writers"
    finally:
        gc.close()


def test_group_commit_propagates_flush_error_to_writers():
    class Exploding:
        id = 5

        def sync(self):
            raise OSError("disk gone")

    gc = GroupCommitter(max_batch=4, max_delay_s=0.01)
    try:
        with pytest.raises(OSError, match="disk gone"):
            gc.commit(Exploding(), timeout_s=5)
    finally:
        gc.close()


def test_group_commit_degrades_to_direct_sync_after_close():
    gc = GroupCommitter()
    gc.close()
    v = _Counting(8)
    gc.commit(v)  # must not hang on a dead flusher
    assert v.syncs == 1


# ---------------------------------------------------------- admission


def test_doomed_upload_refused_at_the_door():
    """10 MB at a 100 KB/s floor needs ~102 s; with 0.5 s of deadline
    budget left the PUT is refused NOW, not at the fsync it was never
    going to reach."""
    plane = IngestPlane(_cfg(min_rate_kbps=100))
    try:
        assert (
            plane.admit("interactive", 10 * 2**20, remaining_s=0.5)
            == "deadline"
        )
        assert plane.shed_counts["deadline"] == 1
        # same body with no propagated deadline: admitted
        assert plane.admit("interactive", 10 * 2**20, remaining_s=None) is None
        plane.complete("interactive", 0.01)
        # doom check disabled by min_rate_kbps=0
        plane2 = IngestPlane(_cfg(min_rate_kbps=0))
        try:
            assert plane2.admit("interactive", 10 * 2**20, 0.5) is None
            plane2.complete("interactive", 0.01)
        finally:
            plane2.close()
    finally:
        plane.close()


def test_s3_put_under_a_write_tier_rides_the_ingest_plane(tmp_path):
    """The front doors' wiring, end to end: an S3 PutObject stamped
    X-Seaweed-QoS: bulk reaches the volume server's ingest plane (its
    bytes are counted in ingest_bytes_total, its admission under the
    bulk write tier) and reads back byte-equal."""
    import asyncio

    import aiohttp

    from seaweedfs_tpu.server.cluster import LocalCluster

    payload = np.random.default_rng(31).integers(
        0, 256, 40_000, dtype=np.uint8
    ).tobytes()

    async def go():
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=1, with_s3=True
        )
        await cluster.start()
        try:
            plane = cluster.volume_servers[0].ingest
            admitted = []
            admit = plane.admit

            def spy(tier, nbytes, remaining_s):
                admitted.append(plane._normalize(tier))
                return admit(tier, nbytes, remaining_s)

            plane.admit = spy
            bytes0 = _sample("SeaweedFS_volumeServer_ingest_bytes_total")
            base = f"http://{cluster.s3.url}/ingest"
            async with aiohttp.ClientSession() as sess:
                async with sess.put(base) as r:
                    assert r.status == 200
                async with sess.put(
                    f"{base}/obj", data=payload,
                    headers={"X-Seaweed-QoS": "bulk"},
                ) as r:
                    assert r.status == 200
                async with sess.get(f"{base}/obj") as r:
                    assert r.status == 200
                    assert await r.read() == payload
            assert admitted and set(admitted) == {"bulk"}
            assert (
                _sample("SeaweedFS_volumeServer_ingest_bytes_total") - bytes0
                >= len(payload)
            )
        finally:
            await cluster.stop()

    asyncio.run(go())


def test_bulk_write_tier_binds_first_under_pressure():
    """Bulk queue budget exhausts while interactive keeps admitting —
    multipart batch parts shed before a user-facing PUT does."""
    plane = IngestPlane(_cfg(bulk_queue=2, interactive_queue=8))
    try:
        assert plane.admit("bulk", 1024, None) is None
        assert plane.admit("bulk", 1024, None) is None
        assert plane.admit("bulk", 1024, None) == "qos"
        assert plane.shed_counts["qos"] == 1
        assert plane.admit("interactive", 1024, None) is None
        # draining a bulk writer reopens the bulk budget
        plane.complete("bulk", 0.01)
        assert plane.admit("bulk", 1024, None) is None
    finally:
        plane.close()


def test_on_write_counts_heats_feeds_and_commits(tmp_path, small_geometry):
    """The post-append hook: bytes counter, write heat into the tiering
    ladder (junk tier normalized), pipeline feed, group-commit ack."""

    class Heat:
        def __init__(self):
            self.notes = []

        def note(self, vid, tier):
            self.notes.append((vid, tier))

    heat = Heat()
    plane = IngestPlane(
        _cfg(fsync=True, fsync_max_batch=1, fsync_max_delay_ms=1.0),
        heat=heat,
    )
    try:
        dat = str(tmp_path / "v4.dat")
        open(dat, "wb").close()
        vol = FakeVolume(dat, vid=4)
        rng = np.random.default_rng(8)
        _append(dat, ROW + 5, rng)
        before = _sample("SeaweedFS_volumeServer_ingest_bytes_total")
        plane.on_write(vol, ROW + 5, tier="bulk")
        assert _sample(
            "SeaweedFS_volumeServer_ingest_bytes_total"
        ) == before + ROW + 5
        assert heat.notes == [(4, "bulk")]
        assert vol.syncs == 1  # group commit acked durably
        p = plane.pipelines[4]
        assert p.staged_rows == 1
        plane.on_write(vol, 0, tier="not-a-tier")
        assert heat.notes[-1] == (4, "interactive")
        snap = plane.snapshot()
        assert snap["pipelines"] == 1
    finally:
        plane.close()


def test_plane_seal_cleans_stale_scratch_without_pipeline(tmp_path):
    """Scratch from a previous process must never be trusted into
    .ec files: plane.seal of an unknown volume removes it and reports
    offline."""
    plane = IngestPlane(_cfg())
    try:
        base = str(tmp_path / "v5")
        stale = base + ".ing10"
        with open(stale, "wb") as f:
            f.write(b"poison")
        assert plane.seal(55, base) is False
        assert not os.path.exists(stale)
    finally:
        plane.close()


# ----------------------------------------------------------- viewguard


def test_viewguard_ingest_row_lifecycle_clean():
    """stage -> fill -> seal (export) -> reclaim (verify + release):
    the encode leg only READ the sealed row, so the guard stays quiet
    and the pool recycles the buffer without complaint."""
    with viewguard.watch() as g:
        arena = rs_ingest.IngestArena(2, 64, slots=1)
        buf = arena.stage(timeout_s=0.1)
        buf[:] = 7
        sealed = arena.seal(buf)
        assert g.outstanding == 1
        arena.reclaim(sealed)
        assert g.outstanding == 0
        arena.stage(timeout_s=0.1)  # clean reuse after reclaim
    g.assert_clean()
    assert g.exports_total == 1 and g.releases_total == 1


def test_viewguard_catches_scribble_between_seal_and_reclaim():
    """Anything mutating a sealed row before its parity hit disk would
    corrupt the shard files silently — the guard turns it into a loud
    test failure at reclaim."""
    with viewguard.watch() as g:
        arena = rs_ingest.IngestArena(2, 64, slots=1)
        buf = arena.stage(timeout_s=0.1)
        buf[:] = 1
        sealed = arena.seal(buf)
        sealed[0, 0] ^= 0xFF  # scribble under the outstanding export
        with pytest.raises(viewguard.ViewGuardViolation, match="changed"):
            arena.reclaim(sealed)
    assert g.violations


def test_viewguard_catches_reclaim_skip_reuse():
    """A regression that returns a row to the pool WITHOUT reclaim()
    (no verify, export left outstanding) is caught the moment stage()
    hands the same buffer out again."""
    with viewguard.watch() as g:
        arena = rs_ingest.IngestArena(2, 64, slots=1)
        buf = arena.stage(timeout_s=0.1)
        arena.seal(buf)
        arena._free.put(buf)  # the buggy shortcut reclaim() exists for
        with pytest.raises(viewguard.ViewGuardViolation, match="reuses"):
            arena.stage(timeout_s=0.1)
    assert g.violations


def test_viewguard_catches_donation_gate_regression(monkeypatch):
    """_donatable must copy on a zero-copy CPU client; a regression that
    hands the live arena row through fails at the donation boundary."""

    def broken(rows, on_tpu):
        return rows  # the copy the gate exists for, skipped

    monkeypatch.setattr(rs_ingest, "_donatable", broken)
    with viewguard.watch() as g:
        arena = rs_ingest.IngestArena(2, 64, slots=1)
        sealed = arena.seal(arena.stage(timeout_s=0.1))
        with pytest.raises(viewguard.ViewGuardViolation, match="donates"):
            rs_ingest._donatable(sealed, False)
    assert g.violations


def test_viewguard_passes_correct_donation_gate():
    """The real gate copies on CPU — no violation even with the export
    outstanding (that copy IS the discipline)."""
    with viewguard.watch() as g:
        arena = rs_ingest.IngestArena(2, 64, slots=1)
        sealed = arena.seal(arena.stage(timeout_s=0.1))
        out = rs_ingest._donatable(sealed, False)
        assert out is not sealed
        arena.reclaim(sealed)
    g.assert_clean()


# ------------------------------------------------ the three-way race


def test_streamed_writes_race_zero_copy_reads_and_tier_churn(
    tmp_path, small_geometry
):
    """The whole plane under contention on ONE volume: a writer appends
    needles and feeds the stream encoder, readers pull zero-copy needle
    views off the same .dat, and a tier thread churns write heat plus
    host-cache promotion/eviction for the same vid.  Every read is
    byte-exact, the guard verifies every staged row and payload view,
    and the final seal still matches the offline encode bit for bit."""
    v = Volume(str(tmp_path), 41)
    vol_dir = str(tmp_path)
    errors: list[BaseException] = []
    blobs: dict[int, bytes] = {}
    blobs_lock = threading.Lock()
    stop = threading.Event()
    heat = HeatTracker(half_life_s=1e9)
    cache = HostShardCache(budget_bytes=1 << 20)

    with viewguard.watch() as g:
        p = IngestPipeline(
            v, rs_ingest.StreamEncoder("cpu"), _cfg(arena_slots=2)
        )

        def writer():
            rng = np.random.default_rng(11)
            nid = 0
            try:
                # grow well past 3 stripe rows so the stream encoder has
                # real interior work racing the readers
                while v.content_size < 4 * ROW and not stop.is_set():
                    nid += 1
                    data = rng.integers(
                        0, 256, size=int(rng.integers(200, 3000)),
                        dtype=np.uint8,
                    ).tobytes()
                    v.write(nid, 0xABC, data, name=b"race")
                    with blobs_lock:
                        blobs[nid] = data
                    p.feed()
                    heat.note(v.id, "interactive")
            except BaseException as e:  # noqa: BLE001 — collected
                errors.append(e)
            finally:
                stop.set()

        def reader(seed):
            rng = np.random.default_rng(seed)
            try:
                while not stop.is_set() or rng.random() < 0.5:
                    with blobs_lock:
                        nids = list(blobs)
                    if not nids:
                        time.sleep(0.001)
                        continue
                    nid = nids[int(rng.integers(0, len(nids)))]
                    n = v.read(nid, zero_copy=True)
                    if bytes(n.data) != blobs[nid]:
                        errors.append(
                            AssertionError(f"stale bytes for needle {nid}")
                        )
                        return
                    if isinstance(n.data, memoryview):
                        g.release(n.data)
                    if stop.is_set():
                        return
            except BaseException as e:  # noqa: BLE001 — collected
                errors.append(e)

        def tier_churn():
            rng = np.random.default_rng(13)
            try:
                while not stop.is_set():
                    heat.note(v.id, "bulk")
                    shard = rng.integers(
                        0, 256, size=2048, dtype=np.uint8
                    )
                    cache.put_volume(v.id, {0: shard, 1: shard.copy()})
                    cache.evict(v.id)
            except BaseException as e:  # noqa: BLE001 — collected
                errors.append(e)

        threads = [
            threading.Thread(target=writer, name="ingest-writer"),
            threading.Thread(target=reader, args=(21,), name="reader1"),
            threading.Thread(target=reader, args=(22,), name="reader2"),
            threading.Thread(target=tier_churn, name="tier-churn"),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        stop.set()
        assert not errors, errors[0]
        assert not any(t.is_alive() for t in threads)

        # quiesce and seal while the guard is still watching the arena
        v.sync()
        p.feed()
        assert p.staged_rows >= 4
        base = Volume.base_name(vol_dir, v.id, v.collection)
        assert p.seal(backend="cpu") is True
        assert p.valid
    g.assert_clean()
    assert g.exports_total > 0 and g.outstanding == 0
    assert heat.value(41) > 0  # write heat registered on the ladder

    # offline oracle over the exact same .dat
    base2 = str(tmp_path / "oracle")
    shutil.copyfile(base + ".dat", base2 + ".dat")
    encoder.write_ec_files(
        base2, backend="cpu", large_block=LARGE, small_block=SMALL
    )
    got, want = shard_bytes(base), shard_bytes(base2)
    for i in range(14):
        assert got[i] == want[i], f"shard {i} diverged under the race"
    v.close()
