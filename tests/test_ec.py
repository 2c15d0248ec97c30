"""EC layer tests, modeled on the reference's test shape
(/root/reference/weed/storage/erasure_coding/ec_test.go): encode a real
volume, validate every needle readable via interval math AND via
reconstruction from random shard subsets, plus rebuild/decode
byte-equivalence."""
import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec import layout
from seaweedfs_tpu.storage.volume import Volume


def make_volume(tmp_path, vid=1, count=24, seed=7):
    rng = random.Random(seed)
    v = Volume(str(tmp_path), vid)
    blobs = {}
    for i in range(1, count + 1):
        size = rng.choice([10, 100, 1337, 4096, 70_000])
        data = rng.randbytes(size)
        cookie = rng.getrandbits(32)
        v.write(i, cookie, data, name=f"f{i}".encode())
        blobs[i] = (cookie, data)
    v.sync()
    return v, blobs


def encode_volume(v):
    base = v.base_name(v.dir, v.id, v.collection)
    ec.write_ec_files(base, backend="cpu")
    ec.write_sorted_file_from_idx(base)
    return base


class TestLayout:
    def test_locate_small_only(self):
        # 3MB volume: all small blocks
        dat = 3 * layout.SMALL_BLOCK_SIZE
        ivs = ec.locate_data(dat, 0, dat)
        assert sum(iv.size for iv in ivs) == dat
        assert all(not iv.is_large_block for iv in ivs)
        assert [iv.block_index for iv in ivs] == [0, 1, 2]

    def test_locate_cross_block(self):
        small = layout.SMALL_BLOCK_SIZE
        ivs = ec.locate_data(10 * small, small - 10, 30)
        assert [iv.size for iv in ivs] == [10, 20]
        sid0, off0 = ivs[0].to_shard_and_offset()
        sid1, off1 = ivs[1].to_shard_and_offset()
        assert (sid0, off0) == (0, small - 10)
        assert (sid1, off1) == (1, 0)

    def test_locate_large_then_small(self):
        large, small = 4096, 512
        # 2 full large rows + tail => first row large, then smalls
        dat = 2 * large * 10 + 3 * small
        ivs = ec.locate_data(dat, 0, dat, large_block=large, small_block=small)
        assert sum(iv.size for iv in ivs) == dat
        assert ivs[0].is_large_block and ivs[0].size == large
        assert not ivs[-1].is_large_block
        # large area covers rows where remaining > one large row
        n_large = sum(1 for iv in ivs if iv.is_large_block)
        assert n_large == dat // (large * 10) * 10

    def test_shard_offsets_roundtrip(self):
        """Striping is a bijection: reassembling every byte through
        locate_data reproduces the encoder's shard files exactly."""
        large, small = 2048, 256
        rng = np.random.default_rng(3)
        dat = rng.integers(0, 256, size=2 * large * 10 + 777, dtype=np.uint8)
        shard_len = layout.shard_file_size(len(dat), large, small)
        shards = np.zeros((10, shard_len), dtype=np.uint8)
        ivs = ec.locate_data(len(dat), 0, len(dat), large, small)
        pos = 0
        for iv in ivs:
            sid, off = iv.to_shard_and_offset(large, small)
            shards[sid, off : off + iv.size] = dat[pos : pos + iv.size]
            pos += iv.size
        assert pos == len(dat)
        # independently stripe with the encoder row loop: per-shard
        # sequential assembly of each row's blocks
        from seaweedfs_tpu.storage.ec.encoder import _iter_rows

        expect = np.zeros_like(shards)
        cursors = [0] * 10
        for row_start, bs in _iter_rows(len(dat), large, small):
            for i in range(10):
                src = dat[row_start + i * bs : row_start + i * bs + bs]
                block = np.zeros(bs, dtype=np.uint8)
                block[: len(src)] = src
                expect[i, cursors[i] : cursors[i] + bs] = block
                cursors[i] += bs
        np.testing.assert_array_equal(shards, expect)

    def test_shard_bits(self):
        b = layout.ShardBits(0).add(0).add(13).add(5)
        assert b.shard_ids() == [0, 5, 13]
        assert b.count() == 3
        assert b.minus_parity().shard_ids() == [0, 5]
        assert b.remove(5).shard_ids() == [0, 13]


class TestEncodeDecode:
    def test_roundtrip_all_needles(self, tmp_path):
        v, blobs = make_volume(tmp_path)
        base = encode_volume(v)
        # all 14 shard files exist, equal size
        sizes = {os.path.getsize(base + ec.to_ext(i)) for i in range(14)}
        assert len(sizes) == 1
        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            ev.add_shard(i)
        for nid, (cookie, data) in blobs.items():
            n = ev.read_needle(nid, cookie=cookie)
            assert n.data == data
        ev.close()

    def test_degraded_read_two_shards_down(self, tmp_path):
        v, blobs = make_volume(tmp_path)
        base = encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        down = {3, 11}
        for i in range(14):
            if i not in down:
                ev.add_shard(i)
        for nid, (cookie, data) in blobs.items():
            n = ev.read_needle(nid, cookie=cookie)
            assert n.data == data
        ev.close()

    def test_degraded_read_four_down_random_subsets(self, tmp_path):
        v, blobs = make_volume(tmp_path, count=8)
        base = encode_volume(v)
        rng = random.Random(11)
        for _ in range(3):
            down = set(rng.sample(range(14), 4))
            ev = ec.EcVolume(str(tmp_path), v.id)
            for i in range(14):
                if i not in down:
                    ev.add_shard(i)
            for nid, (cookie, data) in blobs.items():
                assert ev.read_needle(nid, cookie=cookie).data == data
            ev.close()

    def test_insufficient_shards_raises(self, tmp_path):
        v, blobs = make_volume(tmp_path, count=4)
        encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        # shard 0 (where a small volume's data lives) is down and only 9
        # survivors are reachable: reconstruction must fail
        for i in range(1, 10):
            ev.add_shard(i)
        nid = next(iter(blobs))
        with pytest.raises(ec.volume.InsufficientShards):
            ev.read_needle(nid)
        ev.close()

    def test_remote_read_hook(self, tmp_path):
        """Intervals on non-local shards are served by the remote hook
        before reconstruction is attempted (store_ec.go:199-229)."""
        v, blobs = make_volume(tmp_path, count=6)
        base = encode_volume(v)
        files = {i: open(base + ec.to_ext(i), "rb") for i in range(14)}
        calls = []

        def remote(shard_id, off, size):
            calls.append(shard_id)
            return os.pread(files[shard_id].fileno(), size, off)

        ev = ec.EcVolume(str(tmp_path), v.id)
        # shard 0 holds a small volume's data and is NOT local
        for i in range(1, 6):
            ev.add_shard(i)
        for nid, (cookie, data) in blobs.items():
            assert ev.read_needle(nid, cookie=cookie, remote_read=remote).data == data
        assert 0 in calls, "remote hook should have served shard 0"
        ev.close()
        for f in files.values():
            f.close()

    def test_degraded_memo_serves_repeat_reads_without_regather(
        self, tmp_path
    ):
        """While a shard is lost cluster-wide, a repeat read of the same
        interval is a memo hit (ec_degraded_memo{result}): the same
        bytes, no second survivor gather off the peers — and a shard
        mounting mid-window does not wipe the hot set (shard content is
        immutable once encoded)."""
        from seaweedfs_tpu import stats

        def memo(result):
            return stats.REGISTRY.get_sample_value(
                "SeaweedFS_volumeServer_ec_degraded_memo_total",
                {"result": result},
            ) or 0

        v, blobs = make_volume(tmp_path, count=6)
        base = encode_volume(v)
        files = {i: open(base + ec.to_ext(i), "rb") for i in range(1, 14)}
        calls = []

        def remote(shard_id, off, size):
            calls.append(shard_id)
            if shard_id not in files:  # shard 0: lost everywhere
                return None
            return os.pread(files[shard_id].fileno(), size, off)

        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(1, 6):
            ev.add_shard(i)
        try:
            hit0, miss0 = memo("hit"), memo("miss")
            for nid, (cookie, data) in blobs.items():
                got = ev.read_needle(nid, cookie=cookie, remote_read=remote)
                assert got.data == data
            assert memo("miss") > miss0 and memo("hit") == hit0
            misses, gathered = memo("miss"), len(calls)
            ev.add_shard(6)  # a repair re-mounts a shard mid-window
            for nid, (cookie, data) in blobs.items():
                got = ev.read_needle(nid, cookie=cookie, remote_read=remote)
                assert got.data == data
            assert memo("miss") == misses, "a repeat interval re-gathered"
            assert memo("hit") - hit0 == misses - miss0
            # the only peer traffic left is the probe of lost shard 0
            assert set(calls[gathered:]) <= {0}
        finally:
            ev.close()
            for f in files.values():
                f.close()

    def test_rebuild_byte_equivalence(self, tmp_path):
        v, _ = make_volume(tmp_path)
        base = encode_volume(v)
        originals = {}
        for i in (2, 7, 10, 13):
            with open(base + ec.to_ext(i), "rb") as f:
                originals[i] = f.read()
            os.remove(base + ec.to_ext(i))
        rebuilt = ec.rebuild_ec_files(base, backend="cpu")
        assert sorted(rebuilt) == [2, 7, 10, 13]
        for i, want in originals.items():
            with open(base + ec.to_ext(i), "rb") as f:
                assert f.read() == want

    def test_rebuild_noop_when_complete(self, tmp_path):
        v, _ = make_volume(tmp_path, count=3)
        base = encode_volume(v)
        assert ec.rebuild_ec_files(base) == []

    def test_decode_back_to_dat(self, tmp_path):
        v, _ = make_volume(tmp_path)
        base = encode_volume(v)
        with open(base + ".dat", "rb") as f:
            original = f.read()
        os.remove(base + ".dat")
        ec.write_dat_file(base)
        with open(base + ".dat", "rb") as f:
            decoded = f.read()
        assert decoded == original

    def test_decode_idx_with_deletes(self, tmp_path):
        v, blobs = make_volume(tmp_path, count=6)
        base = encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            ev.add_shard(i)
        victim = list(blobs)[2]
        ev.delete_needle(victim)
        with pytest.raises(ec.NeedleNotFound):
            ev.read_needle(victim)
        ev.close()
        # decode: .idx ends with a tombstone for the victim
        ec.write_idx_file_from_ec_index(base)
        from seaweedfs_tpu.storage.needle_map import CompactMap

        m = CompactMap.load_from_idx(base + ".idx")
        assert not m.has(victim)
        for nid in blobs:
            if nid != victim:
                assert m.has(nid)

    def test_rebuild_ecx_replays_journal(self, tmp_path):
        v, blobs = make_volume(tmp_path, count=6)
        base = encode_volume(v)
        ev = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            ev.add_shard(i)
        victim = list(blobs)[0]
        ev.delete_needle(victim)
        ev.close()
        # fresh .ecx (as after a rebuild) + journal replay
        ec.write_sorted_file_from_idx(base)
        ec.rebuild_ecx_file(base)
        assert not os.path.exists(base + ".ecj")
        ev2 = ec.EcVolume(str(tmp_path), v.id)
        for i in range(14):
            ev2.add_shard(i)
        with pytest.raises(ec.NeedleNotFound):
            ev2.read_needle(victim)
        ev2.close()

    def test_custom_blocks_large_phase_roundtrip(self, tmp_path):
        """Both encode phases (large rows then small rows) survive an
        encode -> rebuild -> decode cycle byte-for-byte."""
        base = str(tmp_path / "9")
        rng = np.random.default_rng(5)
        large, small = 8192, 1024
        payload = rng.integers(0, 256, size=3 * large * 10 + 5000, dtype=np.uint8)
        with open(base + ".dat", "wb") as f:
            f.write(payload.tobytes())
        ec.write_ec_files(base, backend="cpu", large_block=large, small_block=small)
        want = layout.shard_file_size(len(payload), large, small)
        assert os.path.getsize(base + ec.to_ext(0)) == want
        for i in (0, 10):
            os.remove(base + ec.to_ext(i))
        ec.rebuild_ec_files(base, backend="cpu")
        os.remove(base + ".dat")
        ec.write_dat_file(
            base, dat_size=len(payload), large_block=large, small_block=small
        )
        with open(base + ".dat", "rb") as f:
            assert f.read() == payload.tobytes()

    def test_version1_volume_roundtrip(self, tmp_path):
        """EcVolume derives the true needle version from the .ec00
        superblock when no .vif exists (regression: defaulting to v3 broke
        v1/v2 volume reads)."""
        v = Volume(str(tmp_path), 5, version=1)
        v.write(1, 0xAB, b"version-one payload")
        v.sync()
        base = encode_volume(v)
        os.remove(base + ".vif")  # simulate shards copied without sidecar
        ev = ec.EcVolume(str(tmp_path), 5)
        assert ev.version == 1
        for i in range(14):
            ev.add_shard(i)
        assert ev.read_needle(1, cookie=0xAB).data == b"version-one payload"
        ev.close()

    def test_tpu_backend_parity(self, tmp_path):
        """Encode with the device (xla) backend matches the CPU encode
        byte-for-byte — the fixture-equivalence shape of ec_test.go."""
        v, _ = make_volume(tmp_path, count=6)
        base = encode_volume(v)  # cpu
        cpu_shards = {}
        for i in range(14):
            with open(base + ec.to_ext(i), "rb") as f:
                cpu_shards[i] = f.read()
            os.remove(base + ec.to_ext(i))
        ec.write_ec_files(base, backend="xla")
        for i in range(14):
            with open(base + ec.to_ext(i), "rb") as f:
                assert f.read() == cpu_shards[i], f"shard {i} mismatch"
