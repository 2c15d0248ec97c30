"""Staged bulk EC pipeline tests (storage/ec/bulk.py + encoder.py).

Covers the stats contract for all three pipelines (serial accounting sums
to wall; overlapped legs strictly exceed wall on a synthetic slow-IO
fixture), byte equality between overlapped and serial modes, sparse
rebuilds, the preadv fast path, .vif preservation on rebuild, and the
concurrent shell fan-out (spread copies in parallel with `.vif` shipped
exactly once; ec.rebuild's gather with per-RPC retry)."""
import asyncio
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec import bulk, encoder
from seaweedfs_tpu.storage.ec.layout import to_ext

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import rs_plain  # noqa: E402


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def make_dat(path, nbytes, seed=3):
    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 256, size=nbytes, dtype=np.uint8)
    with open(path, "wb") as f:
        f.write(payload.tobytes())
    return payload


def shard_bytes(base):
    out = {}
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out[i] = f.read()
    return out


# --------------------------------------------------------- slow-IO fixture


@pytest.fixture
def slow_io(monkeypatch):
    """Deterministic leg latencies: every pread, every shard write, and
    every codec multiply sleeps, so each leg's duration is dominated by
    injected time and the overlap inequality is decided by structure,
    not scheduler luck."""
    real_pread = bulk._pread

    def slow_pread(fd, n, off):
        time.sleep(0.002)
        return real_pread(fd, n, off)

    monkeypatch.setattr(bulk, "_preadv", None)  # force the per-row path
    monkeypatch.setattr(bulk, "_pread", slow_pread)

    real_write = bulk.write_or_seek

    def slow_write(fobj, row):
        time.sleep(0.001)
        return real_write(fobj, row)

    # write_shard_rows looks write_or_seek up in bulk at every row
    monkeypatch.setattr(bulk, "write_or_seek", slow_write)

    real_apply = rs.RSCodec.apply_matrix

    def slow_apply(self, matrix, shards):
        time.sleep(0.010)
        return real_apply(self, matrix, shards)

    monkeypatch.setattr(rs.RSCodec, "apply_matrix", slow_apply)
    return None


def _legs_sum(stats):
    return stats["read_s"] + stats["write_s"] + stats["device_busy_s"]


def _overlap_window(stats):
    # the contract window: fsync follows the last write by definition, so
    # no pipeline could ever hide it — it is excluded from the inequality
    # (same rule as the ec_bulk_overlap_fraction gauge)
    return stats["wall_s"] - stats["fsync_s"]


def _serial_sum(stats):
    return (
        stats["read_s"] + stats["submit_s"] + stats["wait_s"]
        + stats["write_s"] + stats["fsync_s"]
    )


# ----------------------------------------------------- stats contract


class TestStatsContract:
    """With overlap disabled every leg runs on the caller thread, so the
    per-leg clocks tile the wall clock; with overlap enabled on slow IO
    the legs' sum strictly exceeds wall — the measured proof the ISSUE's
    contract (`read_s + write_s + device_busy_s > wall_s`) names."""

    def _encode(self, tmp_path, overlap):
        base = str(tmp_path / f"v{int(overlap)}")
        make_dat(base + ".dat", 3 * 4096 * 10 + 777)
        stats = {}
        encoder.write_ec_files(
            base, backend="cpu", large_block=4096, small_block=512,
            fsync=True, stats=stats, overlap=overlap,
        )
        return base, stats

    def test_encode_serial_sums_to_wall(self, tmp_path, slow_io):
        _, stats = self._encode(tmp_path, overlap=False)
        assert stats["overlap"] is False
        assert stats["batches"] >= 3
        gap = stats["wall_s"] - _serial_sum(stats)
        assert gap >= -0.005, stats  # components are subsets of the wall
        assert gap <= max(0.15, 0.3 * stats["wall_s"]), stats

    def test_encode_overlap_legs_exceed_wall(self, tmp_path, slow_io):
        _, stats = self._encode(tmp_path, overlap=True)
        assert stats["overlap"] is True
        assert _legs_sum(stats) > _overlap_window(stats), stats

    def test_rebuild_contracts_both_modes(self, tmp_path, slow_io):
        base, _ = self._encode(tmp_path, overlap=True)
        for overlap in (False, True):
            for i in (1, 4, 11, 12):
                os.remove(base + to_ext(i))
            stats = {}
            rebuilt = encoder.rebuild_ec_files(
                base, backend="cpu", stride=4 * 1024, stats=stats,
                overlap=overlap,
            )
            assert sorted(rebuilt) == [1, 4, 11, 12]
            if overlap:
                assert _legs_sum(stats) > _overlap_window(stats), stats
            else:
                gap = stats["wall_s"] - _serial_sum(stats)
                assert -0.005 <= gap <= max(0.15, 0.3 * stats["wall_s"])

    def test_verify_contracts_both_modes(self, tmp_path, slow_io):
        base, _ = self._encode(tmp_path, overlap=False)
        for overlap in (False, True):
            stats = {}
            mism, span = encoder.verify_ec_files(
                base, backend="cpu", stride=4 * 1024, stats=stats,
                overlap=overlap,
            )
            assert mism == [0, 0, 0, 0]
            assert span == os.path.getsize(base + to_ext(0))
            if overlap:
                assert _legs_sum(stats) > _overlap_window(stats), stats
            else:
                gap = stats["wall_s"] - _serial_sum(stats)
                assert -0.005 <= gap <= max(0.15, 0.3 * stats["wall_s"])

    def test_overlap_metrics_published(self, tmp_path, slow_io):
        from seaweedfs_tpu.stats import metrics as m

        self._encode(tmp_path, overlap=True)
        gauge = m.VOLUME_SERVER_EC_BULK_OVERLAP_FRACTION.labels(
            pipeline="encode"
        )
        assert gauge._value.get() > 1.0
        read_leg = m.VOLUME_SERVER_EC_BULK_SECONDS.labels(
            pipeline="encode", leg="read"
        )
        assert read_leg._value.get() > 0.0


# ------------------------------------------------------- byte equality


class TestByteEquality:
    @pytest.mark.parametrize("backend", ["cpu", "xla", "pallas"])
    def test_encode_overlap_matches_serial(self, tmp_path, backend):
        payload = None
        digests = []
        for overlap in (False, True):
            base = str(tmp_path / f"e{int(overlap)}")
            if payload is None:
                payload = make_dat(base + ".dat", 2 * 8192 * 10 + 5000)
            else:
                with open(base + ".dat", "wb") as f:
                    f.write(payload.tobytes())
            encoder.write_ec_files(
                base, backend=backend, large_block=8192, small_block=1024,
                overlap=overlap,
            )
            digests.append(shard_bytes(base))
        assert digests[0] == digests[1]

    @pytest.mark.parametrize("backend", ["cpu", "xla", "pallas"])
    def test_rebuild_overlap_matches_serial_and_original(
        self, tmp_path, backend
    ):
        base = str(tmp_path / "r")
        make_dat(base + ".dat", 8192 * 10 + 300)
        encoder.write_ec_files(
            base, backend="cpu", large_block=8192, small_block=1024
        )
        originals = shard_bytes(base)
        for overlap in (False, True):
            for i in (2, 7, 10, 13):
                os.remove(base + to_ext(i))
            encoder.rebuild_ec_files(
                base, backend=backend, stride=4096, overlap=overlap
            )
            assert shard_bytes(base) == originals, f"overlap={overlap}"
            assert encoder.verify_ec_files(
                base, backend=backend, stride=4096, overlap=overlap
            )[0] == [0, 0, 0, 0]

    def test_rebuild_of_sparse_volume_stays_sparse(self, tmp_path):
        """Where encode punched holes, rebuild must punch holes too —
        byte-identical on read AND no dense zero blocks on disk."""
        base = str(tmp_path / "s")
        large, small = 8192, 1024
        data = np.zeros(3 * large * 10, dtype=np.uint8)
        data[:256] = np.arange(256, dtype=np.uint8)  # tiny nonzero head
        with open(base + ".dat", "wb") as f:
            f.write(data.tobytes())
        encoder.write_ec_files(
            base, backend="cpu", large_block=large, small_block=small
        )
        shard_size = os.path.getsize(base + to_ext(0))
        # control: the same size written densely
        dense = str(tmp_path / "dense")
        with open(dense, "wb") as f:
            f.write(b"\0" * shard_size)
        dense_blocks = os.stat(dense).st_blocks
        encoded_blocks = os.stat(base + to_ext(5)).st_blocks
        if encoded_blocks >= dense_blocks:
            pytest.skip("filesystem does not materialize holes")
        originals = shard_bytes(base)
        for overlap in (False, True):
            for i in (0, 5, 11, 13):
                os.remove(base + to_ext(i))
            encoder.rebuild_ec_files(base, backend="cpu", overlap=overlap)
            assert shard_bytes(base) == originals
            # shard 5 is all zeros (data lives in shard 0's head): the
            # rebuilt file must be a hole, not written zeros
            assert os.stat(base + to_ext(5)).st_blocks < dense_blocks
            assert os.path.getsize(base + to_ext(5)) == shard_size


# --------------------------------------------------- reader fast path


class TestReadStripe:
    def test_preadv_matches_per_row_path(self, tmp_path, monkeypatch):
        if bulk._preadv is None:
            pytest.skip("platform without preadv")
        path = str(tmp_path / "d.dat")
        dat_size = 10 * 1024 + 777  # EOF mid-row: tail rows zero-padded
        make_dat(path, dat_size, seed=9)
        with open(path, "rb") as f:
            cases = [
                (0, 1024, 0, 1024),     # contiguous full-block -> preadv
                (0, 1024, 0, 512),      # sub-block -> per-row path
                (8192, 512, 0, 512),    # EOF lands mid-stripe
            ]
            fast = [
                bulk.read_stripe(f, dat_size, *c).copy() for c in cases
            ]
            monkeypatch.setattr(bulk, "_preadv", None)
            slow = [bulk.read_stripe(f, dat_size, *c) for c in cases]
        for a, b, c in zip(fast, slow, cases):
            np.testing.assert_array_equal(a, b, err_msg=str(c))

    def test_rows_past_eof_are_zero(self, tmp_path):
        path = str(tmp_path / "t.dat")
        make_dat(path, 3 * 1024, seed=2)  # only 3 of 10 rows exist
        with open(path, "rb") as f:
            out = bulk.read_stripe(f, 3 * 1024, 0, 1024, 0, 1024)
        assert out.shape == (10, 1024)
        assert not out[3:].any()


class TestBulkConfig:
    def test_non_dividing_stride_rejected(self):
        # 3MB doesn't divide the 1GB large block: the encode plan would
        # fall back to [10, 1GB] staging batches (OOM); fail at parse time
        with pytest.raises(ValueError, match="large block"):
            bulk.BulkConfig(stride=3 << 20).validated()

    def test_power_of_two_and_zero_strides_ok(self):
        bulk.BulkConfig(stride=0).validated()
        bulk.BulkConfig(stride=1 << 20).validated()
        bulk.BulkConfig(stride=4 << 20).validated()

    def test_bad_prefetch_rejected(self):
        with pytest.raises(ValueError, match="prefetch"):
            bulk.BulkConfig(prefetch=0).validated()


# ------------------------------------------------- executor edge cases


class TestExecutorErrors:
    def test_reader_exception_propagates(self):
        codec = bulk.Codec(rs.RSCodec().matrix[10:], "cpu", threaded=True)

        def bad_read(desc):
            raise ValueError("boom-read")

        try:
            with pytest.raises(ValueError, match="boom-read"):
                bulk.run(
                    "encode", [1, 2, 3], bad_read, codec,
                    lambda *a: None, overlap=True, prefetch=2,
                )
        finally:
            codec.shutdown()

    def test_writer_exception_propagates(self):
        codec = bulk.Codec(rs.RSCodec().matrix[10:], "cpu", threaded=True)
        batch = np.ones((10, 512), dtype=np.uint8)

        def bad_write(desc, payload, result):
            raise ValueError("boom-write")

        try:
            with pytest.raises(ValueError, match="boom-write"):
                bulk.run(
                    "encode", list(range(8)), lambda d: batch, codec,
                    bad_write, overlap=True, prefetch=2,
                )
        finally:
            codec.shutdown()


# ------------------------------------------------------ pooled buffers


def _handed(pipeline):
    """(reused, fresh): the buffers POOL has handed this pipeline."""
    from seaweedfs_tpu.stats import metrics as m

    return tuple(
        m.VOLUME_SERVER_EC_BULK_BUFFERS.labels(
            pipeline=pipeline, source=source
        )._value.get()
        for source in ("reused", "fresh")
    )


def _handed_since(pipeline, before):
    return tuple(b - a for a, b in zip(before, _handed(pipeline)))


def _counter(family, pipeline):
    from seaweedfs_tpu.stats import metrics as m

    return getattr(m, family).labels(pipeline=pipeline)._value.get()


def _direct(pipeline):
    return _counter("VOLUME_SERVER_EC_BULK_DIRECT_BATCHES", pipeline)


def _pipelined(pipeline):
    return _counter("VOLUME_SERVER_EC_BULK_PIPELINED_BATCHES", pipeline)


class PoisonPool(bulk.BufferPool):
    """Every buffer goes out all 0xFF, past the batch's view too: a byte
    the reader or the staging copy does not write ends up in a file."""

    def take(self, pipeline, rows, width):
        batch = super().take(pipeline, rows, width)
        batch.base[:] = 0xFF
        return batch


@pytest.fixture
def pool(monkeypatch):
    """A pool of the test's own in place of the process's."""
    monkeypatch.setattr(bulk, "POOL", PoisonPool())
    return bulk.POOL


# one 8 KB-block row, then 1 KB-block rows of which the last ends 777
# bytes into its first block: shards of 12 KB, so a 5 KB rebuild / verify
# stride leaves a 2 KB last batch, and a 4 KB encode stride takes the
# large row by sub-block reads and the small ones by one preadv each
_LARGE, _SMALL = 8192, 1024
_DAT_SIZE = 10 * _LARGE + 3 * 10 * _SMALL + 777
_SHARD_SIZE = _LARGE + 4 * _SMALL


def _host_codec_shards(payload):
    """The 14 shard files of `payload` by the host codec over arrays of
    this function's own (np.zeros, no pool)."""
    codec = rs.RSCodec(backend="cpu")
    shards = [[] for _ in range(14)]
    for row_start, block in encoder._iter_rows(len(payload), _LARGE, _SMALL):
        data = np.zeros((10, block), dtype=np.uint8)
        for i in range(10):
            chunk = payload[row_start + i * block:row_start + (i + 1) * block]
            data[i, : len(chunk)] = chunk
        parity = codec.apply_matrix(codec.matrix[10:], data)
        for i in range(10):
            shards[i].append(data[i].tobytes())
        for i in range(4):
            shards[10 + i].append(parity[i].tobytes())
    return {i: b"".join(parts) for i, parts in enumerate(shards)}


def _encode_short_tail(base, backend, overlap):
    ec.write_ec_files(
        base, backend=backend, stride=4096, large_block=_LARGE,
        small_block=_SMALL, overlap=overlap, prefetch=2,
    )


class TestPooledBuffers:
    @pytest.mark.parametrize("overlap", [True, False])
    @pytest.mark.parametrize("backend", ["cpu", "pallas", "xla"])
    def test_stale_bytes_stay_out_of_short_batches(
        self, tmp_path, pool, backend, overlap
    ):
        base = str(tmp_path / "1")
        want = _host_codec_shards(make_dat(base + ".dat", _DAT_SIZE, seed=25))
        _encode_short_tail(base, backend, overlap)
        assert shard_bytes(base) == want
        for lost in (3, 11):
            os.remove(base + to_ext(lost))
        rebuilt = ec.rebuild_ec_files(
            base, backend=backend, stride=5120, overlap=overlap, prefetch=2
        )
        assert rebuilt == [3, 11]
        assert shard_bytes(base) == want
        assert ec.verify_ec_files(
            base, backend=backend, stride=5120, overlap=overlap, prefetch=2
        ) == ([0, 0, 0, 0], _SHARD_SIZE)

    @pytest.mark.parametrize("overlap", [True, False])
    @pytest.mark.parametrize("backend", ["cpu", "xla", "pallas"])
    def test_fresh_up_to_the_bound_then_reused(
        self, tmp_path, pool, backend, overlap
    ):
        """Two rebuild verbs of 24 batches each, one buffer a batch on
        every backend: the payload, which a device codec puts as it is
        (no staging buffer).  Overlapped, the pipeline can hold
        2*2 + 3 + 2 payloads: no more than that many are allocated over
        both verbs, however the legs interleave.  Serial, the one
        payload in flight is the buffer the encode verb left."""
        base = str(tmp_path / "1")
        make_dat(base + ".dat", _DAT_SIZE, seed=26)
        _encode_short_tail(base, "cpu", overlap)
        want = shard_bytes(base)
        fresh_by_verb = []
        for _verb in range(2):
            os.remove(base + to_ext(5))
            before = _handed("rebuild")
            ec.rebuild_ec_files(
                base, backend=backend, stride=512, overlap=overlap,
                prefetch=2,
            )
            reused, fresh = _handed_since("rebuild", before)
            assert reused + fresh == 24
            fresh_by_verb.append(fresh)
            assert len(pool._free) <= pool.keep
            assert shard_bytes(base) == want
        if overlap:
            assert pool.keep == 2 * 2 + bulk.PIPELINE_DEPTH + 2
            assert sum(fresh_by_verb) <= pool.keep
        else:
            assert pool.keep == 1
            assert fresh_by_verb == [0, 0]

    @pytest.mark.parametrize("overlap", [True, False])
    @pytest.mark.parametrize("backend", ["cpu", "xla", "pallas"])
    def test_a_staged_pipeline_keeps_a_buffer_for_each_batch_on_the_device(
        self, tmp_path, pool, backend, overlap
    ):
        """Two encode verbs of six batches each: a payload a batch and,
        under a device codec, the staging buffer the worker lays it out
        in.  The worker stages a successor before it fetches the oldest
        batch, so an overlapped run keeps DEVICE_DEPTH buffers beside
        its payloads where a rebuild keeps none; the serial mode, which
        never has a second batch submitted, keeps one."""
        base = str(tmp_path / "1")
        want = _host_codec_shards(make_dat(base + ".dat", _DAT_SIZE, seed=29))
        a_batch = 1 if backend == "cpu" else 2
        fresh_by_verb = []
        for _verb in range(2):
            before = _handed("encode")
            _encode_short_tail(base, backend, overlap)
            reused, fresh = _handed_since("encode", before)
            assert reused + fresh == 6 * a_batch
            fresh_by_verb.append(fresh)
            assert len(pool._free) <= pool.keep
            assert shard_bytes(base) == want
        if overlap:
            assert pool.keep == (
                2 * 2 + bulk.PIPELINE_DEPTH + 2 + bulk.DEVICE_DEPTH
            )
            assert sum(fresh_by_verb) <= pool.keep
        else:
            assert pool.keep == 1 + 1
            assert fresh_by_verb == [a_batch, 0]

    @pytest.mark.parametrize("leg", ["reader", "writer"])
    def test_failed_leg_ends_the_run_and_leaves_the_pool_usable(
        self, tmp_path, pool, monkeypatch, leg
    ):
        base = str(tmp_path / "1")
        want = _host_codec_shards(make_dat(base + ".dat", _DAT_SIZE, seed=27))
        import itertools

        calls = itertools.count(1)  # next() is atomic: writes run side by side

        def failing(real):
            def wrapped(*args):
                if next(calls) == 6:
                    raise OSError(f"boom-{leg}")
                return real(*args)
            return wrapped

        with monkeypatch.context() as patch:
            if leg == "reader":
                patch.setattr(bulk, "_preadv", failing(bulk._preadv))
            else:
                patch.setattr(
                    bulk, "write_or_seek", failing(bulk.write_or_seek)
                )
            t0 = time.monotonic()
            with pytest.raises(OSError, match=f"boom-{leg}"):
                _encode_short_tail(base, "cpu", True)
            assert time.monotonic() - t0 < 10.0
        _encode_short_tail(base, "cpu", True)
        assert shard_bytes(base) == want
        assert 0 < len(pool._free) <= pool.keep

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_staging_buffer_is_kept_until_its_batch_is_fetched(
        self, monkeypatch, backend
    ):
        """Six batches of distinct content submitted before the worker's
        first turn: it stages and enqueues a successor before it fetches
        the oldest batch, so two staging buffers circulate; each goes
        back to the pool only after the blocking fetch of the batch that
        staged it, and every parity is right."""
        import contextlib
        import threading

        log = []

        @contextlib.contextmanager
        def logged_event(name, **_anns):
            yield
            log.append(name)

        class LoggingPool(bulk.BufferPool):
            def take(self, pipeline, rows, width):
                log.append("take")
                return super().take(pipeline, rows, width)

            def give(self, batch):
                log.append("give")
                super().give(batch)

        pool = LoggingPool()
        pool.keep = bulk.DEVICE_DEPTH
        monkeypatch.setattr(bulk, "POOL", pool)
        before = _handed("encode")
        pipelined = _pipelined("encode")
        monkeypatch.setattr(bulk.obs_trace, "event", logged_event)
        host = rs.RSCodec(backend="cpu")
        matrix = host.matrix[10:]
        rng = np.random.default_rng(28)
        batches = [
            rng.integers(0, 256, size=(10, 1024), dtype=np.uint8)
            for _ in range(6)
        ]
        codec = bulk.Codec(matrix, backend, threaded=True)
        gate = threading.Event()
        try:
            codec._pool.submit(gate.wait)
            handles = [codec.submit(b) for b in batches]
            gate.set()
            for b, h in zip(batches, handles):
                np.testing.assert_array_equal(
                    codec.resolve(h), host.apply_matrix(matrix, b)
                )
        finally:
            gate.set()
            codec.shutdown()
        enqueue = ["take", "bulk_stage", "bulk_enqueue"]
        fetch = ["bulk_fetch", "give", "bulk_unstack"]
        assert log == enqueue + (enqueue + fetch) * 5 + fetch
        assert _handed_since("encode", before) == (4, 2)
        assert _pipelined("encode") - pipelined == 5


# ------------------------------------- rebuild's read leg: layout, fan-out


def _bulk_threads():
    import threading

    return sorted(
        t.name for t in threading.enumerate()
        if t.name.startswith(("ec-bulk", "ec-dev", "ec-host"))
    )


@pytest.fixture
def readers():
    pool = bulk.row_readers()
    yield pool
    pool.shutdown()


_K, _GROUPS = 10, 4


class TestShardRowsLayout:
    """read_shard_rows into the codec's stacked order against
    rs_tpu.stack_segments of the plain rows, into a buffer that held
    0xFF everywhere."""

    # (bytes a shard file holds, offset, n): segments of n/4
    CASES = {
        "full_batch": (4096, 0, 2048),
        "short_last_batch": (2048 + 512, 2048, 512),
        "eof_inside_a_segment": (1000, 0, 2048),
        "eof_on_a_segment_boundary": (1024, 0, 2048),
        "eof_before_the_batch": (100, 2048, 2048),
    }

    @pytest.mark.parametrize("vectored", [True, False])
    @pytest.mark.parametrize("case", list(CASES))
    def test_stacked_read_equals_stacked_plain_rows(
        self, tmp_path, monkeypatch, readers, case, vectored
    ):
        from seaweedfs_tpu.ops import rs_tpu

        if not vectored:
            monkeypatch.setattr(bulk, "_preadv", None)
        elif bulk._preadv is None:
            pytest.skip("platform without preadv")
        size, off, n = self.CASES[case]
        rng = np.random.default_rng(31)
        # no zero byte in a file: a zero in a batch is a filled one
        files = rng.integers(1, 256, size=(_K, size), dtype=np.uint8)
        handles = {}
        for i in range(_K):
            path = str(tmp_path / f"s{i}")
            with open(path, "wb") as f:
                f.write(files[i].tobytes())
            handles[i + 2] = open(path, "rb")
        ids = list(handles)
        want = np.zeros((_K, n), dtype=np.uint8)
        have = max(0, min(n, size - off))
        want[:, :have] = files[:, off:off + have]
        try:
            plain = bulk.read_shard_rows(
                handles, ids, off, np.full((_K, n), 0xFF, np.uint8), readers
            )
            stacked = bulk.read_shard_rows(
                handles, ids, off, np.full((_K, n), 0xFF, np.uint8), readers,
                _GROUPS,
            )
        finally:
            for h in handles.values():
                h.close()
        np.testing.assert_array_equal(plain, want)
        np.testing.assert_array_equal(
            stacked.reshape(_GROUPS * _K, n // _GROUPS),
            rs_tpu.stack_segments(want, _GROUPS),
        )


class TestReadFanOut:
    def _shards(self, tmp_path, seed):
        base = str(tmp_path / "1")
        payload = make_dat(base + ".dat", _DAT_SIZE, seed=seed)
        want = _host_codec_shards(payload)
        _encode_short_tail(base, "cpu", True)
        return base, want

    @pytest.mark.parametrize("vectored", [True, False])
    def test_one_read_a_row_side_by_side_on_the_runs_threads(
        self, tmp_path, monkeypatch, readers, vectored
    ):
        import threading

        base, _ = self._shards(tmp_path, 32)
        lock = threading.Lock()
        seen = {"calls": [], "now": 0, "most": 0, "threads": set()}

        def watched(real):
            def wrapped(fd, arg, off):
                with lock:
                    seen["calls"].append((fd, arg if vectored else None))
                    seen["threads"].add(threading.current_thread().name)
                    seen["now"] += 1
                    seen["most"] = max(seen["most"], seen["now"])
                time.sleep(0.01)
                try:
                    return real(fd, arg, off)
                finally:
                    with lock:
                        seen["now"] -= 1
            return wrapped

        if vectored:
            if bulk._preadv is None:
                pytest.skip("platform without preadv")
            monkeypatch.setattr(bulk, "_preadv", watched(bulk._preadv))
        else:
            monkeypatch.setattr(bulk, "_preadv", None)
            monkeypatch.setattr(bulk, "_pread", watched(bulk._pread))
        handles = {i: open(base + to_ext(i), "rb") for i in range(14)}
        fds = sorted(h.fileno() for h in handles.values())
        try:
            out = bulk.read_shard_rows(
                handles, range(14), 1024, np.empty((14, 2048), np.uint8),
                readers, _GROUPS,
            )
        finally:
            for h in handles.values():
                h.close()
        assert sorted(fd for fd, _ in seen["calls"]) == fds
        if vectored:  # a shard's segments are the iovecs of its one call
            assert {len(iov) for _, iov in seen["calls"]} == {_GROUPS}
        assert 1 < seen["most"] <= bulk.READ_THREADS
        assert all(name.startswith("ec-bulk-row") for name in seen["threads"])
        assert out.shape == (14, 2048)

    @pytest.mark.parametrize("overlap", [True, False])
    def test_a_failed_row_ends_the_run_and_leaves_no_thread(
        self, tmp_path, pool, monkeypatch, overlap
    ):
        base, want = self._shards(tmp_path, 33)
        os.remove(base + to_ext(3))
        os.remove(base + to_ext(11))
        calls = {"n": 0}
        real = bulk._preadv

        def failing(fd, iov, off):
            calls["n"] += 1
            if calls["n"] == 14:  # a row of the second batch
                raise OSError("boom-row")
            return real(fd, iov, off)

        with monkeypatch.context() as patch:
            patch.setattr(bulk, "_preadv", failing)
            t0 = time.monotonic()
            with pytest.raises(OSError, match="boom-row"):
                ec.rebuild_ec_files(
                    base, backend="cpu", stride=5120, overlap=overlap,
                    prefetch=2,
                )
            assert time.monotonic() - t0 < 10.0
        assert _bulk_threads() == []
        for lost in (3, 11):  # what the failed run left of them
            os.remove(base + to_ext(lost))
        assert ec.rebuild_ec_files(
            base, backend="cpu", stride=5120, overlap=overlap, prefetch=2
        ) == [3, 11]
        assert shard_bytes(base) == want
        assert _bulk_threads() == []


class TestDirectRebuild:
    # 12 KB shards: a 5120 stride is three batches the block-diagonal
    # program takes stacked (5120, 5120, 2048 divide by 4*128), a 5000
    # stride three it takes as plain rows
    @pytest.mark.parametrize("overlap", [True, False])
    @pytest.mark.parametrize("stride", [5120, 5000])
    @pytest.mark.parametrize("backend", ["pallas", "xla", "cpu"])
    def test_rebuild_puts_its_payload_and_nobody_else_does(
        self, tmp_path, pool, backend, stride, overlap
    ):
        base = str(tmp_path / "1")
        want = _host_codec_shards(make_dat(base + ".dat", _DAT_SIZE, seed=34))
        before = {p: _direct(p) for p in ("encode", "rebuild", "verify")}
        _encode_short_tail(base, backend, overlap)
        for lost in (0, 3, 11):
            os.remove(base + to_ext(lost))
        handed = _handed("rebuild")
        batches = _counter("VOLUME_SERVER_EC_BULK_BATCHES", "rebuild")
        assert ec.rebuild_ec_files(
            base, backend=backend, stride=stride, overlap=overlap, prefetch=2
        ) == [0, 3, 11]
        assert shard_bytes(base) == want
        assert ec.verify_ec_files(
            base, backend=backend, stride=stride, overlap=overlap, prefetch=2
        ) == ([0, 0, 0, 0], _SHARD_SIZE)
        plan = -(-_SHARD_SIZE // stride)
        assert _counter(
            "VOLUME_SERVER_EC_BULK_BATCHES", "rebuild") - batches == plan
        # one buffer a batch: the payload, no staging buffer beside it
        reused, fresh = _handed_since("rebuild", handed)
        assert reused + fresh == plan
        assert fresh <= pool.keep
        # the host codec puts nothing on a device
        assert _direct("rebuild") - before["rebuild"] == (
            0 if backend == "cpu" else plan
        )
        assert _direct("encode") == before["encode"]
        assert _direct("verify") == before["verify"]

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_direct_payload_is_put_without_a_take(self, monkeypatch, backend):
        """The codec worker takes no buffer for a direct batch, and what
        it computes from a payload in its own order is what it computes
        from the plain rows it stages itself."""
        from seaweedfs_tpu.ops import rs_tpu

        taken = []

        class CountingPool(bulk.BufferPool):
            def take(self, pipeline, rows, width):
                taken.append((pipeline, rows, width))
                return super().take(pipeline, rows, width)

        monkeypatch.setattr(bulk, "POOL", CountingPool())
        host = rs.RSCodec(backend="cpu")
        matrix = host.matrix[10:]
        rng = np.random.default_rng(35)
        plain = rng.integers(0, 256, size=(10, 2048), dtype=np.uint8)
        codec = bulk.Codec(matrix, backend, threaded=True, pipeline="rebuild")
        try:
            groups = codec.segments(2048)
            assert groups == (_GROUPS if backend == "pallas" else 1)
            assert codec.segments(2000) == 1
            laid_out = (
                rs_tpu.stack_segments(plain, groups).reshape(10, 2048).copy()
                if groups > 1 else plain.copy()
            )
            before = _direct("rebuild")
            direct = codec.resolve(codec.submit(laid_out, direct=True))
            assert taken == [] and _direct("rebuild") - before == 1
            staged = codec.resolve(codec.submit(plain))
            assert taken == [("rebuild", 10, 2048)]
            assert _direct("rebuild") - before == 1
        finally:
            codec.shutdown()
        np.testing.assert_array_equal(direct, host.apply_matrix(matrix, plain))
        np.testing.assert_array_equal(staged, direct)


# ------------------------------- the codec worker: two on the device


class DeviceSeams:
    """jax.device_put and bulk's np.asarray wrapped: a batch is on the
    device from its put until its fetch has returned.  `fail` names the
    seam ("put" / "fetch") whose sixth call raises."""

    def __init__(self, monkeypatch, fail=None):
        import jax

        self.on_device = self.most = self.puts = self.fetches = 0
        self.fail = fail
        real_put = jax.device_put

        def device_put(x, *args, **kw):
            if isinstance(x, np.ndarray) and x.ndim == 1:  # a flat batch
                self.puts += 1
                if self.fail == "put" and self.puts == 6:
                    raise RuntimeError("boom-put")
                self.on_device += 1
                self.most = max(self.most, self.on_device)
            return real_put(x, *args, **kw)

        seams = self

        class Numpy:
            """bulk's `np`: numpy, but for asarray of a device array."""

            def __getattr__(self, name):
                return getattr(np, name)

            def asarray(self, a, *args, **kw):
                if not isinstance(a, jax.Array):
                    return np.asarray(a, *args, **kw)
                seams.fetches += 1
                if seams.fail == "fetch" and seams.fetches == 6:
                    seams.on_device -= 1
                    raise RuntimeError("boom-fetch")
                out = np.asarray(a, *args, **kw)
                seams.on_device -= 1
                return out

        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(bulk, "np", Numpy())


def _codec_parts(pipeline):
    from seaweedfs_tpu.stats import metrics as m

    return sum(
        m.VOLUME_SERVER_EC_BULK_CODEC_SECONDS.labels(
            pipeline=pipeline, part=part
        )._value.get()
        for part in m.EC_BULK_CODEC_PARTS
    )


class TestTwoOnTheDevice:
    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_handles_complete_in_plan_order_two_on_the_device_at_most(
        self, monkeypatch, backend
    ):
        """Eight direct batches submitted before the worker's first
        turn: every fetch but the last begins with the successor
        enqueued, never a third batch, and the handles complete in the
        order they were submitted."""
        import threading

        seams = DeviceSeams(monkeypatch)
        host = rs.RSCodec(backend="cpu")
        matrix = host.matrix[10:]
        rng = np.random.default_rng(36)
        batches = [
            rng.integers(0, 256, size=(10, 1000), dtype=np.uint8)
            for _ in range(8)
        ]
        done = []
        before = _pipelined("rebuild")
        codec = bulk.Codec(matrix, backend, threaded=True, pipeline="rebuild")
        gate = threading.Event()
        try:
            assert codec.segments(1000) == 1  # plain rows are direct as is
            codec._pool.submit(gate.wait)
            handles = [codec.submit(b, direct=True) for b in batches]
            for n, h in enumerate(handles):
                h.add_done_callback(lambda _h, n=n: done.append(n))
            gate.set()
            for b, h in zip(batches, handles):
                np.testing.assert_array_equal(
                    codec.resolve(h), host.apply_matrix(matrix, b)
                )
        finally:
            gate.set()
            codec.shutdown()
        assert done == list(range(8))
        assert (seams.puts, seams.fetches, seams.on_device) == (8, 8, 0)
        assert seams.most == bulk.DEVICE_DEPTH
        assert _pipelined("rebuild") - before == 7
        assert _bulk_threads() == []

    @pytest.mark.parametrize("overlap", [True, False])
    @pytest.mark.parametrize("backend", ["pallas", "xla", "cpu"])
    def test_a_successor_is_enqueued_only_where_one_was_submitted(
        self, tmp_path, pool, monkeypatch, backend, overlap
    ):
        """Encode, rebuild and verify through the seams: never more
        than two batches on the device, the parts sum to device_busy_s,
        and a fetch counts as pipelined only where a successor was
        there: never in the serial mode, never for a one-batch plan,
        never under the host codec, at most all but a verb's last."""
        seams = DeviceSeams(monkeypatch)
        base = str(tmp_path / "1")
        want = _host_codec_shards(make_dat(base + ".dat", _DAT_SIZE, seed=37))
        verbs = {
            "encode": lambda stats: ec.write_ec_files(
                base, backend=backend, stride=4096, large_block=_LARGE,
                small_block=_SMALL, overlap=overlap, prefetch=2, stats=stats,
            ),
            "rebuild": lambda stats: ec.rebuild_ec_files(
                base, backend=backend, stride=512, overlap=overlap,
                prefetch=2, stats=stats,
            ),
            "verify": lambda stats: ec.verify_ec_files(
                base, backend=backend, stride=512, overlap=overlap,
                prefetch=2, stats=stats,
            ),
            # the whole shard in one batch
            "rebuild-one-batch": lambda stats: ec.rebuild_ec_files(
                base, backend=backend, stride=_SHARD_SIZE, overlap=overlap,
                prefetch=2, stats=stats,
            ),
        }
        for name, verb in verbs.items():
            pipeline = name.split("-")[0]
            if pipeline == "rebuild":
                for lost in (3, 11):
                    os.remove(base + to_ext(lost))
            pipelined, parts = _pipelined(pipeline), _codec_parts(pipeline)
            stats = {}
            verb(stats)
            assert shard_bytes(base) == want, name
            pipelined = _pipelined(pipeline) - pipelined
            parts = _codec_parts(pipeline) - parts
            busy = stats["device_busy_s"]
            if backend == "cpu":
                assert (pipelined, parts, seams.puts) == (0, 0, 0), name
                continue
            assert seams.on_device == 0 and seams.most <= 2, name
            assert 0 < parts <= busy, (name, parts, busy)
            assert busy - parts <= max(0.05 * busy, 0.005), (name, parts, busy)
            if overlap and stats["batches"] > 1:
                assert 0 <= pipelined <= stats["batches"] - 1, name
            else:
                assert pipelined == 0, name
        if backend != "cpu":
            assert seams.puts == seams.fetches == 6 + 24 + 24 + 1
            if not overlap:
                assert seams.most == 1

    @pytest.mark.parametrize("seam", ["put", "fetch"])
    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_a_failed_enqueue_or_fetch_ends_the_run_and_leaves_no_thread(
        self, tmp_path, pool, monkeypatch, backend, seam
    ):
        base = str(tmp_path / "1")
        make_dat(base + ".dat", _DAT_SIZE, seed=38)
        _encode_short_tail(base, "cpu", True)
        want = shard_bytes(base)
        os.remove(base + to_ext(5))
        with monkeypatch.context() as patch:
            seams = DeviceSeams(patch, fail=seam)
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match=f"boom-{seam}"):
                ec.rebuild_ec_files(
                    base, backend=backend, stride=512, overlap=True,
                    prefetch=2,
                )
            assert time.monotonic() - t0 < 10.0
            # shutdown() drained what was submitted behind the failure
            assert seams.on_device == 0 and seams.most <= 2
        assert _bulk_threads() == []
        os.remove(base + to_ext(5))  # what the failed run left of it
        assert ec.rebuild_ec_files(
            base, backend=backend, stride=512, overlap=True, prefetch=2
        ) == [5]
        assert shard_bytes(base) == want
        assert 0 < len(pool._free) <= pool.keep
        assert _bulk_threads() == []


# ------------------------------------------ the write leg: rows side by side


def _parallel_writes(pipeline):
    return _counter("VOLUME_SERVER_EC_BULK_PARALLEL_WRITE_BATCHES", pipeline)


def _plain_reference_dat(path):
    """Four 1 MiB rows whose zeros land on every kind of hole: shard 6's
    first block (a hole at a file's start), shard 1's second (in the
    middle), the whole third row (every file, parity too), all of shard
    8, and a short last row that ends 1000 bytes into shard 3's block
    (holes at the ends of shards 4-9) -> the 14 shards by
    benchmark/reference/rs_plain.py."""
    block = rs_plain.BLOCK
    rng = np.random.default_rng(39)
    dat = rng.integers(1, 256, size=3 * 10 * block + 3 * block + 1000,
                       dtype=np.uint8)

    def zero(row, shard):
        start = (row * 10 + shard) * block
        dat[start:start + block] = 0

    zero(0, 6)
    zero(1, 1)
    for shard in range(10):
        zero(2, shard)
    for row in range(3):
        zero(row, 8)
    with open(path, "wb") as f:
        f.write(dat.tobytes())
    shards = rs_plain.encode_rows(
        dat.tobytes(), 0, 4, rs_plain.coding_matrix()[rs_plain.DATA_SHARDS:]
    )
    return {i: shards[i].tobytes() for i in range(14)}


class RowLog:
    """bulk.write_or_seek wrapped: every call's file, position, thread,
    what it did and when, and how many calls were in flight at once.
    `slow` names the shard file (by extension) whose rows take 20 ms;
    `fail` = (extension, nth call on that file) raises instead."""

    def __init__(self, monkeypatch, slow=None, fail=None, sleep=0.0):
        import threading

        self.lock = threading.Lock()
        self.calls = []
        self.now = self.most = 0
        real = bulk.write_or_seek

        def watched(fobj, row):
            ext = os.path.splitext(fobj.name)[1]
            with self.lock:
                nth = sum(1 for c in self.calls if c["ext"] == ext)
                call = {
                    "ext": ext, "nth": nth, "at": fobj.tell(),
                    "len": len(row), "start": time.monotonic(),
                    "thread": threading.current_thread().name,
                }
                self.calls.append(call)
                self.now += 1
                self.most = max(self.most, self.now)
            try:
                time.sleep(0.02 if ext == slow else sleep)
                if fail == (ext, nth):
                    raise OSError("boom-write")
                call["wrote"] = real(fobj, row)
                return call["wrote"]
            finally:
                call["end"] = time.monotonic()
                with self.lock:
                    self.now -= 1

        monkeypatch.setattr(bulk, "write_or_seek", watched)

    def by_file(self):
        out = {}
        for call in self.calls:
            out.setdefault(call["ext"], []).append(call)
        return out


class TestParallelWrites:
    """The writer leg hands a batch's rows to the run's writer threads,
    one task a row written from the row's own buffer, and starts the
    next batch only when every task has ended; the serial mode writes
    inline."""

    @pytest.mark.parametrize("overlap", [True, False])
    def test_the_files_are_the_plain_references_holes_and_sizes_included(
        self, tmp_path, monkeypatch, overlap
    ):
        base = str(tmp_path / "1")
        want = _plain_reference_dat(base + ".dat")
        half = 1 << 19  # two batches a 1 MiB row
        log = RowLog(monkeypatch, sleep=0.001)
        counted = _parallel_writes("encode")
        ec.write_ec_files(base, backend="cpu", stride=half, overlap=overlap)
        assert shard_bytes(base) == want
        assert {os.path.getsize(base + to_ext(i)) for i in range(14)} == {
            4 << 20}
        files = log.by_file()
        # 8 batches: every file took its rows in order, and each all-zero
        # half row became a hole, not written zeros
        for i in range(14):
            calls = files[to_ext(i)]
            assert [c["at"] for c in calls] == [n * half for n in range(8)]
            rows = np.frombuffer(want[i], dtype=np.uint8).reshape(8, half)
            assert [c["wrote"] for c in calls] == list(rows.any(axis=1))
        assert not any(c["wrote"] for c in files[to_ext(8)][:6])
        assert not files[to_ext(6)][0]["wrote"]
        assert not files[to_ext(1)][2]["wrote"]
        assert not any(c["wrote"] for c in files[to_ext(9)][6:])
        threads = {c["thread"] for c in log.calls}
        if overlap:
            assert all(t.startswith("ec-bulk-write") for t in threads)
            assert 1 < log.most <= bulk.WRITE_THREADS
            # the third row's two batches are holes in every file
            assert _parallel_writes("encode") - counted == 6
        else:
            assert log.most == 1
            assert _parallel_writes("encode") - counted == 0
        assert _bulk_threads() == []
        # rebuild three data shards and a parity shard the same way
        lost = (1, 6, 8, 11)
        for lost_shard in lost:
            os.remove(base + to_ext(lost_shard))
        counted = _parallel_writes("rebuild")
        assert ec.rebuild_ec_files(
            base, backend="cpu", stride=half, overlap=overlap
        ) == list(lost)
        assert shard_bytes(base) == want
        # two rows of four written in every batch but the third row's
        assert _parallel_writes("rebuild") - counted == (6 if overlap else 0)

    @pytest.mark.parametrize("backend", ["cpu", "xla", "pallas"])
    def test_a_slow_file_keeps_every_files_order_across_batches(
        self, tmp_path, pool, monkeypatch, backend
    ):
        base = str(tmp_path / "1")
        want = _host_codec_shards(make_dat(base + ".dat", _DAT_SIZE, seed=39))
        log = RowLog(monkeypatch, slow=to_ext(5), sleep=0.001)
        _encode_short_tail(base, backend, True)
        assert shard_bytes(base) == want
        files = log.by_file()
        assert sorted(files) == [to_ext(i) for i in range(14)]
        for calls in files.values():
            at = 0
            for call in calls:
                assert call["at"] == at
                at += call["len"]
        # a batch's rows all ended before the next batch's first began,
        # the slow file's included
        batches = len(files[to_ext(0)])
        assert batches == 6
        for n in range(batches - 1):
            ended = max(calls[n]["end"] for calls in files.values())
            began = min(calls[n + 1]["start"] for calls in files.values())
            assert ended <= began, n

    @pytest.mark.parametrize("pipeline", ["encode", "rebuild"])
    def test_a_failed_write_ends_the_run_after_every_task_and_no_early_give(
        self, tmp_path, monkeypatch, pipeline
    ):
        """The write of shard 3's second row raises while the batch's
        other rows are still being written: the run raises it, every
        task of that batch has ended by then, and no payload went back
        to the pool while a write was in flight."""
        base = str(tmp_path / "1")
        make_dat(base + ".dat", _DAT_SIZE, seed=40)
        _encode_short_tail(base, "cpu", True)
        want = shard_bytes(base)
        if pipeline == "rebuild":
            for lost in (3, 11):
                os.remove(base + to_ext(lost))
        log = RowLog(monkeypatch, fail=(to_ext(3), 1), sleep=0.01)
        gives = []  # the writes in flight at each give

        class WatchingPool(bulk.BufferPool):
            def give(self, batch):
                gives.append(log.now)
                super().give(batch)

        monkeypatch.setattr(bulk, "POOL", WatchingPool())
        real_rows = bulk.write_shard_rows
        in_flight_at_raise = []

        def rows_then_count(*args):
            try:
                return real_rows(*args)
            except OSError:
                in_flight_at_raise.append(log.now)
                raise

        monkeypatch.setattr(bulk, "write_shard_rows", rows_then_count)
        t0 = time.monotonic()
        with pytest.raises(OSError, match="boom-write"):
            if pipeline == "encode":
                _encode_short_tail(base, "cpu", True)
            else:
                ec.rebuild_ec_files(
                    base, backend="cpu", stride=4096, overlap=True,
                    prefetch=2,
                )
        assert time.monotonic() - t0 < 10.0
        assert in_flight_at_raise == [0]
        assert all(c.get("end") for c in log.calls)
        assert gives and set(gives) == {0}
        assert _bulk_threads() == []
        monkeypatch.undo()
        if pipeline == "rebuild":
            for lost in (3, 11):  # what the failed run left of them
                os.remove(base + to_ext(lost))
            ec.rebuild_ec_files(base, backend="cpu", stride=4096)
        else:
            _encode_short_tail(base, "cpu", True)
        assert shard_bytes(base) == want

    @pytest.mark.parametrize("overlap", [True, False])
    def test_one_row_a_batch_and_verify_count_no_parallel_write(
        self, tmp_path, overlap
    ):
        base = str(tmp_path / "1")
        make_dat(base + ".dat", _DAT_SIZE, seed=41)
        _encode_short_tail(base, "cpu", True)
        want = shard_bytes(base)
        before = {p: _parallel_writes(p) for p in ("rebuild", "verify")}
        os.remove(base + to_ext(7))
        assert ec.rebuild_ec_files(
            base, backend="cpu", stride=4096, overlap=overlap
        ) == [7]
        assert ec.verify_ec_files(
            base, backend="cpu", stride=4096, overlap=overlap
        ) == ([0, 0, 0, 0], _SHARD_SIZE)
        assert shard_bytes(base) == want
        assert {p: _parallel_writes(p) for p in before} == before


# ------------------------------------------------- .vif + fsync satellite


class TestRebuildSidecars:
    def test_rebuild_restores_vif_from_ec00_superblock(self, tmp_path):
        from seaweedfs_tpu.storage.volume import Volume
        from seaweedfs_tpu.storage.volume_info import load_volume_info

        v = Volume(str(tmp_path), 9)
        v.write(1, 0xAB, b"payload under superblock")
        v.sync()
        base = Volume.base_name(str(tmp_path), 9, "")
        encoder.write_ec_files(base, backend="cpu")
        want = load_volume_info(base + ".vif")
        assert want  # encode derived it from the .dat superblock
        os.remove(base + ".vif")
        for i in (3, 12):
            os.remove(base + to_ext(i))
        encoder.rebuild_ec_files(base, backend="cpu", fsync=True)
        assert load_volume_info(base + ".vif") == want

    def test_rebuild_keeps_existing_vif(self, tmp_path):
        from seaweedfs_tpu.storage.volume_info import (
            load_volume_info,
            save_volume_info,
        )

        base = str(tmp_path / "7")
        make_dat(base + ".dat", 4096 * 10)
        encoder.write_ec_files(base, backend="cpu")
        save_volume_info(base + ".vif", {"version": 2})
        os.remove(base + to_ext(1))
        encoder.rebuild_ec_files(base, backend="cpu")
        assert load_volume_info(base + ".vif") == {"version": 2}


# ----------------------------------------------------- shell fan-out


class RecordingStub:
    """Fake volume stub: records every RPC with its request, tracks
    concurrent in-flight copies, and can fail the first N attempts of a
    call to exercise the retry path."""

    def __init__(self, log, gauge, fail_copies=0):
        self.log = log
        self.gauge = gauge  # dict: {"now": int, "max": int}
        self.fail_copies = fail_copies

    async def VolumeEcShardsCopy(self, req):
        if self.fail_copies > 0:
            self.fail_copies -= 1
            self.log.append(("copy_fail", req))
            raise ConnectionError("transient")
        self.gauge["now"] += 1
        self.gauge["max"] = max(self.gauge["max"], self.gauge["now"])
        await asyncio.sleep(0.02)
        self.gauge["now"] -= 1
        self.log.append(("copy", req))

    async def VolumeEcShardsMount(self, req):
        self.log.append(("mount", req))

    async def VolumeEcShardsUnmount(self, req):
        self.log.append(("unmount", req))

    async def VolumeEcShardsDelete(self, req):
        self.log.append(("delete", req))


def _node(url):
    from seaweedfs_tpu.shell.command_env import TopoNode

    host, port = url.rsplit(":", 1)
    return TopoNode(
        url=url, grpc_port=int(port) + 10000, data_center="dc", rack="r"
    )


class TestSpreadFanout:
    def _run_spread(self, n_targets, fail_copies=0, concurrency=4):
        from seaweedfs_tpu.shell.command_ec import spread_ec_shards

        log, gauge = [], {"now": 0, "max": 0}
        source = _node("src:8080")
        targets = [
            (_node(f"t{i}:8080"), [i * 3, i * 3 + 1])
            for i in range(n_targets)
        ]
        stubs = {}

        def volume_stub(addr):
            if addr not in stubs:
                stubs[addr] = RecordingStub(
                    log, gauge,
                    fail_copies=fail_copies if addr.startswith("t0") else 0,
                )
            return stubs[addr]

        env = SimpleNamespace(volume_stub=volume_stub)
        run(
            spread_ec_shards(
                env, 5, "col", source, [(source, [13])] + targets,
                concurrency=concurrency,
            )
        )
        return log, gauge

    def test_vif_ships_exactly_once_under_concurrent_copy(self):
        log, gauge = self._run_spread(4)
        copies = [req for op, req in log if op == "copy"]
        assert len(copies) == 4
        assert sum(1 for r in copies if r.copy_vif_file) == 1
        # the copies genuinely overlapped (and stayed within the bound)
        assert 1 < gauge["max"] <= 4
        # per-target ordering held: each target mounted after its copy,
        # and the source unmount+delete happened per shard set
        unmounts = [req for op, req in log if op == "unmount"]
        deletes = [req for op, req in log if op == "delete"]
        assert len(unmounts) == len(deletes) == 4

    def test_transient_copy_failure_is_retried(self):
        log, _ = self._run_spread(2, fail_copies=1)
        fails = [1 for op, _ in log if op == "copy_fail"]
        copies = [req for op, req in log if op == "copy"]
        assert len(fails) == 1
        assert len(copies) == 2  # both targets served despite the failure
        assert sum(1 for r in copies if r.copy_vif_file) == 1

    def test_exhausted_retries_raise(self):
        with pytest.raises(RuntimeError, match="failed after"):
            self._run_spread(1, fail_copies=10)


class TestRebuildGather:
    def test_gather_concurrent_with_sidecars_once(self):
        from seaweedfs_tpu.shell.command_ec import gather_ec_shards

        log, gauge = [], {"now": 0, "max": 0}
        stub = RecordingStub(log, gauge)
        to_copy = {"a:18080": [1, 2], "b:18080": [5], "c:18080": [9, 10]}
        run(gather_ec_shards(stub, 5, "col", to_copy))
        copies = [req for op, req in log if op == "copy"]
        assert len(copies) == 3
        assert gauge["max"] > 1
        for flag in ("copy_ecx_file", "copy_ecj_file", "copy_vif_file"):
            assert sum(1 for r in copies if getattr(r, flag)) == 1, flag
        # sidecars ride with the copy from the designated first holder
        sidecar = next(r for r in copies if r.copy_vif_file)
        assert sidecar.source_data_node == next(iter(to_copy))

    def test_gather_retries_transient_failure(self):
        from seaweedfs_tpu.shell.command_ec import gather_ec_shards

        log, gauge = [], {"now": 0, "max": 0}
        stub = RecordingStub(log, gauge, fail_copies=1)
        run(gather_ec_shards(stub, 5, "", {"a:1": [1], "b:1": [2]}))
        assert len([1 for op, _ in log if op == "copy"]) == 2
