"""The two-tier striping (rows of large blocks, then rows of small ones)
against benchmark/reference/rs_layout_plain.py, at small block sizes:
the program's locate_data and shard_file_size, the 14 files ec.encode's
writer produces, and a degraded read through EcVolume out of a cache that
is lane-sharded over a four-device CPU mesh, byte for byte on seeded
data, with a needle across the large/small boundary and one across a lane
stripe."""
import os
import random

import numpy as np
import pytest

from benchmark.reference import rs_layout_plain as ref
from benchmark.reference import rs_plain
from seaweedfs_tpu import stats
from seaweedfs_tpu.ops import rs_resident
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec import layout
from seaweedfs_tpu.storage.needle import actual_size
from seaweedfs_tpu.storage.volume import Volume

LARGE, SMALL = 64 * 1024, 1024
ROW = 10 * LARGE
# one and two large rows, each with a ragged tail of small rows, and a
# volume below one large row (rs_plain's single tier)
DAT_SIZES = [ROW + 12_345, 2 * ROW + 10 * SMALL * 7 + 1, ROW - 999, 4_321]


def program_pieces(dat_size, offset, length):
    """locate_data's intervals as the reference's pieces, with the
    dat_size an EcVolume derives from its shard files."""
    shard = layout.shard_file_size(dat_size, LARGE, SMALL)
    out = []
    for iv in layout.locate_data(10 * shard, offset, length, LARGE, SMALL):
        sid, at = iv.to_shard_and_offset(LARGE, SMALL)
        out.append((sid, at, iv.size, iv.is_large_block))
    return out


@pytest.mark.parametrize("dat_size", DAT_SIZES)
def test_shard_file_size_agrees_with_the_reference(dat_size):
    assert layout.shard_file_size(dat_size, LARGE, SMALL) == (
        ref.shard_size_of(dat_size, LARGE, SMALL))


@pytest.mark.parametrize("dat_size", DAT_SIZES)
def test_locate_agrees_with_the_reference(dat_size):
    rng = random.Random(dat_size)
    large_end = ref.n_large_rows(dat_size, LARGE) * ROW
    extents = [(0, dat_size), (max(0, large_end - 700), 1_500),
               (max(0, large_end - 1), 2)]
    for _ in range(200):
        off = rng.randrange(dat_size)
        extents.append((off, rng.randint(1, min(3 * LARGE, dat_size - off))))
    for off, length in extents:
        length = min(length, dat_size - off)
        want = ref.locate(dat_size, off, length, LARGE, SMALL)
        assert program_pieces(dat_size, off, length) == want, (off, length)
        assert sum(n for _, _, n, _ in want) == length
        for shard in (0, 3, 9):
            assert ref.bytes_on_shard(
                dat_size, off, length, shard, LARGE, SMALL
            ) == sum(n for s, _, n, _ in want if s == shard)
    if large_end:
        kinds = {large for *_, large in ref.locate(
            dat_size, large_end - 700, 1_500, LARGE, SMALL)}
        assert kinds == {True, False}  # the extent crosses the tiers


def test_single_tier_is_rs_plain():
    """Below one large row the reference is rs_plain's 1 MB striping."""
    for dat_size in (1, rs_plain.BLOCK * 10, rs_plain.BLOCK * 25 + 3):
        assert ref.shard_size_of(dat_size) == rs_plain.shard_size_of(dat_size)
    assert ref.n_large_rows(10 << 30) == 0  # exactly one row: small rows
    assert ref.n_large_rows((10 << 30) + 1) == 1
    assert ref.shard_size_of(16 << 30) == (1 << 30) + 615 * (1 << 20)


@pytest.mark.parametrize("dat_size", DAT_SIZES[:3])
def test_encoded_files_agree_with_the_reference(tmp_path, dat_size):
    base = str(tmp_path / "7")
    dat = np.random.default_rng(dat_size).integers(
        0, 256, size=dat_size, dtype=np.uint8).tobytes()
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    ec.write_ec_files(base, backend="cpu", large_block=LARGE,
                      small_block=SMALL)
    shard = ref.shard_size_of(dat_size, LARGE, SMALL)
    parity = rs_plain.coding_matrix()[rs_plain.DATA_SHARDS:]
    want = ref.encode_window(
        lambda at, n: dat[at:at + n], dat_size, 0, shard, parity,
        LARGE, SMALL)
    for sid in range(14):
        with open(base + ec.to_ext(sid), "rb") as f:
            got = np.frombuffer(f.read(), dtype=np.uint8)
        assert got.size == shard
        assert np.array_equal(got, want[sid]), f"shard {sid} differs"
    # a window across the tiers' boundary alone, as the benchmark's
    # set-up check reads them
    edge = ref.n_large_rows(dat_size, LARGE) * LARGE
    if edge:
        window = ref.encode_window(
            lambda at, n: dat[at:at + n], dat_size, edge - 300, 900,
            parity, LARGE, SMALL)
        assert np.array_equal(window, want[:, edge - 300: edge + 600])


# ------------------------------------------- degraded reads out of the mesh

N_DEV = 4
LOST = (3, 11)


@pytest.fixture(scope="module")
def mesh_volume(tmp_path_factory):
    """A sealed volume of two large rows and a ragged tail of small rows,
    shards 3 and 11 lost, the twelve survivors lane-sharded over the
    first four CPU devices in 16 KiB stripes."""
    tmp = tmp_path_factory.mktemp("two_tier")
    rng = random.Random(2026)
    v = Volume(str(tmp), 9)
    blobs, sizes = {}, [700, 5_000, 23_000, 41_000, 90_000]
    key = 0
    while v.content_size < 2 * ROW + 30 * SMALL * 10:
        key += 1
        data = rng.randbytes(sizes[key % len(sizes)] + rng.randrange(64))
        v.write(key, 0x5EED, data)
        blobs[key] = data
    v.sync()
    base = v.base_name(v.dir, v.id, v.collection)
    dat_size = os.path.getsize(base + ".dat")
    v.close()
    ec.write_ec_files(base, backend="cpu", large_block=LARGE,
                      small_block=SMALL)
    ec.write_sorted_file_from_idx(base)
    ev = ec.EcVolume(str(tmp), 9)
    ev.large_block, ev.small_block = LARGE, SMALL
    for sid in range(14):
        if sid not in LOST:
            ev.add_shard(sid)
    cache = rs_resident.DeviceShardCache(
        shard_quantum=64 * 1024, mesh_devices=N_DEV, mesh_min_shard_bytes=0)
    cache.warm_sizes = ()  # no AOT grid: shapes compile as they come
    assert ev.load_shards_to_device(cache) == 12
    yield ev, cache, blobs, dat_size
    ev.close()


def counter(family, **labels):
    return family.labels(**labels)._value.get()


def test_mesh_holds_the_survivors_in_even_quarters(mesh_volume):
    ev, cache, _, dat_size = mesh_volume
    assert cache.placement(ev.id) == "mesh" and cache.n_devices == N_DEV
    assert cache.stripe == 16 * 1024
    assert ev.shard_size == ref.shard_size_of(dat_size, LARGE, SMALL)
    per_device = [d["used_bytes"] for d in cache.device_stats()]
    assert len(set(per_device)) == 1 and per_device[0] > 0
    assert sorted(cache.shard_ids(ev.id)) == [
        s for s in range(14) if s not in LOST]


@pytest.mark.parametrize("width", [16, 3])
def test_degraded_reads_agree_with_what_was_written(
        mesh_volume, monkeypatch, width):
    """Batches of `width` needles through the mesh: 16 as the serving
    dispatcher cuts them, 3 so that most bucket groups leave devices
    without rows."""
    ev, cache, blobs, dat_size = mesh_volume
    # what every sharded call of the test asks of the mesh, as packed:
    # (devices with rows, n_bucket, fetch)
    asked = []
    real_pack = rs_resident._pack_calls

    def pack(*args, **kw):
        out = real_pack(*args, **kw)
        asked.extend(
            (sum(bool(offs) for offs, _ in cols[0]), n_bucket, fetch)
            for kind, _, cols, _, fetch, _, n_bucket, _ in out[0]
            if kind == "sharded")
        return out

    monkeypatch.setattr(rs_resident, "_pack_calls", pack)
    extents = {}
    for key in blobs:
        off, size = ev.find_needle(key)
        extents[key] = ref.locate(
            dat_size, off, actual_size(size, ev.version), LARGE, SMALL)
    across_tiers = [k for k, p in extents.items()
                    if {large for *_, large in p} == {True, False}]
    across_stripe = [
        k for k, p in extents.items()
        if any(s == 3 and at // cache.stripe != (at + n - 1) // cache.stripe
               for s, at, n, _ in p)]
    assert across_tiers, "no needle crosses the large/small boundary"
    assert across_stripe, "no lost-shard piece crosses a lane stripe"
    on_lost = [k for k, p in extents.items() if any(s == 3 for s, *_ in p)]
    assert len(on_lost) > 8

    lanes = stats.VOLUME_SERVER_EC_MESH_LANE_REQUESTS
    rows = stats.VOLUME_SERVER_EC_INTERVAL_ROWS
    d2h = stats.VOLUME_SERVER_EC_MESH_D2H_BYTES
    moved = stats.VOLUME_SERVER_EC_DEVICE_TRANSFERS
    before = {
        "fetched": counter(moved, kind="d2h_shard_fetched"),
        "skipped": counter(moved, kind="d2h_shard_skipped"),
        "async": counter(moved, kind="h2d_async"),
        "waited": counter(moved, kind="h2d_waited"),
        "lanes": [counter(lanes, device=str(d)) for d in range(N_DEV)],
        "large": counter(rows, kind="large"),
        "small": counter(rows, kind="small"),
        "wire": counter(d2h, kind="wire"),
        "useful": counter(d2h, kind="useful"),
    }
    keys = sorted(blobs)
    for start in range(0, len(keys), width):
        batch = keys[start:start + width]
        for key, needle in zip(batch, ev.read_needles_batch(batch)):
            assert not isinstance(needle, Exception), (key, needle)
            assert bytes(needle.data) == blobs[key], key

    lane_counts = [counter(lanes, device=str(d)) - before["lanes"][d]
                   for d in range(N_DEV)]
    assert all(n > 0 for n in lane_counts), lane_counts
    pieces = [p for ext in extents.values() for p in ext]
    assert counter(rows, kind="large") - before["large"] == sum(
        large for *_, large in pieces)
    assert counter(rows, kind="small") - before["small"] == sum(
        not large for *_, large in pieces)
    useful = counter(d2h, kind="useful") - before["useful"]
    assert useful == sum(n for s, _, n, _ in pieces if s == 3)
    # a device is fetched if and only if it holds an asked-for row, and
    # wire is the fetched shards' n_bucket rows of fetch bytes
    wire = counter(d2h, kind="wire") - before["wire"]
    assert wire == sum(n * n_bucket * fetch for n, n_bucket, fetch in asked)
    assert wire >= useful
    fetched = counter(moved, kind="d2h_shard_fetched") - before["fetched"]
    skipped = counter(moved, kind="d2h_shard_skipped") - before["skipped"]
    assert fetched == sum(n for n, *_ in asked) > 0
    assert skipped == sum(N_DEV - n for n, *_ in asked)
    if width == 3:
        assert skipped > fetched, (skipped, fetched)
    # the CPU mesh stages a fresh vector a call: every put flies
    assert counter(moved, kind="h2d_async") - before["async"] == len(asked)
    assert counter(moved, kind="h2d_waited") == before["waited"]


# ----------------------------------------------------------- the pin path


@pytest.mark.parametrize("size", [1, 16 * 1024, 5 * 16 * 1024 + 77,
                                  8 * 16 * 1024])
def test_put_file_lays_a_shard_out_as_put_does(tmp_path, size):
    """A shard file read straight into the staging buffer lands on the
    mesh byte for byte where put() of its bytes lands, zeros after it."""
    data = np.random.default_rng(size).integers(
        0, 256, size=size, dtype=np.uint8)
    path = tmp_path / "1.ec00"
    path.write_bytes(data.tobytes())
    cache = rs_resident.DeviceShardCache(
        shard_quantum=64 * 1024, mesh_devices=N_DEV, mesh_min_shard_bytes=0)
    cache.put(1, 0, data)
    cache.put_file(2, 0, str(path))
    a, b = np.asarray(cache.get(1, 0)), np.asarray(cache.get(2, 0))
    assert np.array_equal(a, b)
    assert cache.shard_size(2, 0) == size
    # device d holds stripes d, d+4, ...: undo the permutation
    stripes = a.reshape(N_DEV, -1, cache.stripe).transpose(1, 0, 2).ravel()
    assert np.array_equal(stripes[:size], data) and not stripes[size:].any()
    # a kept staging buffer (a TPU's path) that held another shard
    # before: nothing of it is left behind
    kept = np.full(a.size, 0xAA, dtype=np.uint8)
    cache._lay_out(kept, size, "mesh",
                   lambda out, start: np.copyto(
                       out, data[start:start + out.size]))
    assert np.array_equal(kept, a)
    whole = np.full(a.size, 0xAA, dtype=np.uint8)
    cache._lay_out(whole, size, 0, lambda out, start: np.copyto(
        out, data[start:start + out.size]))
    assert np.array_equal(whole[:size], data) and not whole[size:].any()
