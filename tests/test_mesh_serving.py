"""Pod-scale resident serving (r19): the mesh-sharded DeviceShardCache
layout, its cross-device reconstruct kernels, per-device budget
accounting, the sharded AOT grid, and the tiering ladder's per-device
pressure/fit arithmetic.

All device work runs on the conftest's 8-device CPU mesh
(xla_force_host_platform_device_count=8).
"""
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs, rs_resident
from seaweedfs_tpu.parallel import mesh as mesh_mod

N_DEV = 8


@pytest.fixture(scope="module")
def encoded():
    """One 256KB volume's 14 shards + the numpy oracle."""
    rng = np.random.default_rng(77)
    data = rng.integers(0, 256, size=(10, 256 * 1024), dtype=np.uint8)
    return rs.RSCodec(backend="numpy").encode_all(data)


@pytest.fixture(scope="module")
def encoded_big():
    """A 4MB-shard volume: big enough that its padded buffers span
    several per-device chunks, so gather windows genuinely land on
    (and straddle) different devices."""
    rng = np.random.default_rng(78)
    data = rng.integers(0, 256, size=(10, 4 * 1024 * 1024), dtype=np.uint8)
    return rs.RSCodec(backend="numpy").encode_all(data)


def _sharded_cache(**kw):
    kw.setdefault("shard_quantum", 1 << 20)
    kw.setdefault("mesh_devices", 0)
    kw.setdefault("mesh_min_shard_bytes", 0)
    c = rs_resident.DeviceShardCache(**kw)
    c.warm_sizes = ()  # CI convention: no AOT grid compile unless asked
    return c


# ------------------------------------------------------------ mesh helper


def test_serving_mesh_is_cached_and_shared():
    m1 = mesh_mod.serving_mesh(0)
    m2 = mesh_mod.serving_mesh(0)
    assert m1 is m2, "serving_mesh must return ONE object per width"
    assert m1.axis_names == (mesh_mod.SHARD_AXIS,)
    assert int(m1.devices.size) == N_DEV


def test_serving_mesh_degrades_to_none_on_one_device():
    assert mesh_mod.serving_mesh(1) is None


def test_bulk_make_mesh_shares_the_axis_home():
    from seaweedfs_tpu.parallel import distributed

    m = distributed.make_mesh(2)
    assert m.axis_names == (mesh_mod.SHARD_AXIS, mesh_mod.BATCH_AXIS)


# ---------------------------------------------------- placement/accounting


def test_sharded_put_splits_evenly_across_devices(encoded):
    c = _sharded_cache()
    for sid in range(14):
        c.put(5, sid, encoded[sid])
    assert c.placement(5) == "mesh"
    assert c.vid_sharded(5)
    per = c._dev_bytes[0]
    assert per > 0 and all(b == per for b in c._dev_bytes)
    assert c.bytes_used == sum(c._dev_bytes)
    stats = c.device_stats()
    assert len(stats) == N_DEV
    assert all(s["budget_bytes"] == c.budget // N_DEV for s in stats)


def test_small_volume_pins_whole_on_least_loaded_device(encoded):
    c = _sharded_cache(mesh_min_shard_bytes=1 << 30)
    for sid in range(4):
        c.put(1, sid, encoded[sid])
    p1 = c.placement(1)
    assert isinstance(p1, int)
    for sid in range(4):
        c.put(2, sid, encoded[sid])
    p2 = c.placement(2)
    assert isinstance(p2, int) and p2 != p1, (
        "the second whole-pin must land on a different (less loaded) "
        "device"
    )
    foot1 = c.vid_device_bytes(1)
    assert set(foot1) == {p1} and foot1[p1] == c.bytes_used // 2


def test_size_threshold_splits_placement(encoded, encoded_big):
    c = _sharded_cache(mesh_min_shard_bytes=1 << 20)
    c.put(1, 0, encoded[0])       # 256KB shard -> whole-pin
    c.put(2, 0, encoded_big[0])   # 4MB shard  -> lane-sharded
    assert isinstance(c.placement(1), int)
    assert c.placement(2) == "mesh"


def test_placement_is_claimed_for_the_whole_volume(encoded, encoded_big):
    """One volume must never straddle placements: the first put's
    claim binds later puts even when their shard size alone would
    decide differently (the reconstruct kernels assume a uniform
    survivor layout)."""
    c = _sharded_cache(mesh_min_shard_bytes=1 << 20)
    c.put(9, 0, encoded_big[0])  # claims "mesh"
    c.put(9, 1, encoded[1])      # small, but the claim stands
    assert c.placement(9) == "mesh"
    assert all((9, s) in c._foot for s in (0, 1))
    assert c._foot[(9, 1)][0] == "mesh"


def test_eviction_targets_the_over_budget_device(encoded):
    """Per-device pressure: overfilling ONE device evicts only keys
    holding bytes there — whole-pins parked on other devices survive."""
    c = _sharded_cache(mesh_devices=2, mesh_min_shard_bytes=1 << 30)
    pad = c._padded_len(len(encoded[0]))
    # per-device budget = exactly 4 shards = two 2-shard volumes
    c.budget = 2 * (4 * pad)
    for vid in (1, 2, 3, 4):
        for sid in (0, 1):
            c.put(vid, sid, encoded[sid])
    # alternating least-loaded placement: 1,3 on one device, 2,4 on the
    # other — both devices exactly full
    devs = {vid: c.placement(vid) for vid in (1, 2, 3, 4)}
    assert devs[1] == devs[3] != devs[2] == devs[4]
    # a fifth whole-pin lands on the tie-broken device and must evict
    # ONLY that device's LRU volume
    for sid in (0, 1):
        c.put(5, sid, encoded[sid])
    victim = 1 if c.placement(5) == devs[1] else 2
    survivor_same_dev = {1: 3, 2: 4}[victim]
    assert c.resident_count(victim) == 0
    assert c.resident_count(survivor_same_dev) == 2
    for vid in (1, 2, 3, 4):
        if vid not in (victim,):
            assert c.resident_count(vid) == 2, f"vid {vid} was evicted"
    budget = c.device_budget
    assert all(b <= budget for b in c._dev_bytes)


def test_per_device_gauge_tracks_puts_and_evicts(encoded):
    from seaweedfs_tpu import stats as swfs_stats

    c = _sharded_cache()
    for sid in range(2):
        c.put(6, sid, encoded[sid])
    g = swfs_stats.REGISTRY.get_sample_value
    per = c._dev_bytes[0]
    assert g(
        "SeaweedFS_volumeServer_ec_device_cache_bytes", {"device": "0"}
    ) == per
    c.clear()
    assert g(
        "SeaweedFS_volumeServer_ec_device_cache_bytes", {"device": "0"}
    ) == 0


# ------------------------------------------------------------- planner


def test_plan_splits_at_chunk_boundaries():
    l_loc = 1 << 20
    # crosses the first chunk boundary: must split there
    subs = rs_resident._plan([(3, l_loc - 1000, 5000)], l_loc)
    assert len(subs) >= 2
    covered = []
    for _idx, aligned, delta, take, bucket in subs:
        assert delta + take <= bucket
        # the whole window sits inside ONE chunk
        assert aligned // l_loc == (aligned + bucket - 1) // l_loc
        assert aligned % rs_resident.LANE == 0
        covered.append((aligned + delta, take))
    # splits cover the request contiguously in order
    pos = l_loc - 1000
    for start, take in covered:
        assert start == pos
        pos += take
    assert pos == l_loc - 1000 + 5000


def test_plan_backward_aligns_windows_overhanging_a_boundary():
    l_loc = 1 << 20
    # a request ENDING just before the boundary whose bucket window
    # would overhang it: the window must end AT the boundary and the
    # grown delta still satisfies delta + take <= bucket
    off = l_loc - 3000
    subs = rs_resident._plan([(3, off, 2999)], l_loc)
    (idx, aligned, delta, take, bucket) = subs[0]
    assert aligned + bucket <= l_loc
    assert aligned + delta == off and take == 2999
    assert delta + take <= bucket


def test_plan_without_l_loc_is_unchanged():
    a = rs_resident._plan([(3, 12345, 70000)])
    b = rs_resident._plan([(3, 12345, 70000)], 0)
    assert a == b


# --------------------------------------------------- sharded reconstruct


@pytest.mark.parametrize("layout", ["flat", "blockdiag"])
def test_sharded_reconstruct_matches_oracle(encoded_big, layout):
    c = _sharded_cache(layout=layout)
    down = (3, 11)
    for sid in range(14):
        if sid not in down:
            c.put(21, sid, encoded_big[sid])
    l_loc = c._foot[(21, 0)][1] // N_DEV
    rng = np.random.default_rng(4)
    L = encoded_big[3].shape[0]
    reqs = [
        (3, int(rng.integers(0, L - 70000)), int(size))
        for size in rng.choice([100, 4096, 33000, 70000], size=24)
    ]
    # deliberate chunk straddles, tails, and the other wanted shard
    reqs += [
        (3, l_loc - 17, 4096),
        (3, 3 * l_loc - 60000, 65536),
        (11, L - 1500, 1500),
        (11, 0, 1),
    ]
    got = rs_resident.reconstruct_intervals(c, 21, reqs)
    for (sid, off, size), piece in zip(reqs, got):
        assert piece == encoded_big[sid][off : off + size].tobytes(), (
            f"sharded {layout} mismatch at sid={sid} off={off} size={size}"
        )


@pytest.mark.parametrize("times_one_device", [2, 4])
def test_working_set_beyond_one_device_stays_resident_only_when_sharded(
    encoded, times_one_device
):
    """The capacity contrast the mesh exists for, in counts: with one
    device's budget sized for ONE volume's twelve survivors, a working
    set of 2x / 4x that stays whole lane-sharded over the mesh (no
    eviction, every degraded read byte-equal) while whole-volume
    pinning on one device evicts and a read of an evicted volume is a
    CacheMiss (the store's route to the host)."""
    down = (3, 11)
    vids = list(range(1, times_one_device + 1))
    reqs = [(3, 1000, 4096), (11, 200_000, 3000)]

    def fill(c):
        for vid in vids:
            for sid in range(14):
                if sid not in down:
                    c.put(vid, sid, encoded[sid])

    sharded = _sharded_cache()
    one_device = 12 * sharded._padded_len(len(encoded[0]))
    sharded.budget = sharded.n_devices * one_device
    fill(sharded)
    assert sharded.evictions == 0
    assert all(sharded.resident_count(v) == 12 for v in vids)
    assert all(b <= one_device for b in sharded._dev_bytes)
    for vid in vids:
        got = rs_resident.reconstruct_intervals(sharded, vid, reqs)
        for (sid, off, size), piece in zip(reqs, got):
            assert piece == encoded[sid][off : off + size].tobytes()

    single = rs_resident.DeviceShardCache(
        budget_bytes=one_device, shard_quantum=1 << 20
    )
    single.warm_sizes = ()
    fill(single)
    assert single.evictions > 0
    assert single.bytes_used <= one_device
    shed = [v for v in vids if single.resident_count(v) < 10]
    assert shed, "one device held a working set it has no room for"
    with pytest.raises(rs_resident.CacheMiss):
        rs_resident.reconstruct_intervals(single, shed[0], reqs)


def _counts(family, kinds):
    return {k: family.labels(kind=k)._value.get() for k in kinds}


@pytest.mark.parametrize("idle", [0, 1, 2, 3])
def test_sharded_fetch_reads_only_devices_with_rows(encoded_big, idle):
    """One mixed batch of five size buckets on a 2x2 mesh whose bucket
    groups leave `idle` of the four devices without rows: the answers
    equal the host codec's, a device without an asked-for row is never
    fetched, and `wire` is the fetched shards' padded rows."""
    from seaweedfs_tpu.stats import metrics as stats

    c = _sharded_cache(mesh_devices=4, shard_quantum=1 << 21)
    for sid in range(14):
        if sid not in (3, 11):
            c.put(23, sid, encoded_big[sid])
    assert c.n_devices == 4 and c.stripe == 512 * 1024
    owners = range(4 - idle)
    sizes = [700, 5000, 20000, 100000, 400000]
    # stripe k lives on device k % 4: every size asks each owner once
    reqs = [
        (3 if (d + i) % 2 else 11, (d + 4 * (i % 2)) * c.stripe + 900 + i,
         size)
        for i, size in enumerate(sizes) for d in owners
    ]
    packed = rs_resident._pack_calls(
        c, 23, reqs, "xla", True, c.layout, 10, 14, record_observed=False
    )[0]
    assert [call[0] for call in packed] == ["sharded"] * len(sizes)
    assert all(
        sum(bool(offs) for offs, _ in call[2][0]) == len(owners)
        for call in packed
    )
    transfers = stats.VOLUME_SERVER_EC_DEVICE_TRANSFERS
    d2h = stats.VOLUME_SERVER_EC_MESH_D2H_BYTES
    kinds = ("h2d_async", "h2d_waited", "d2h_shard_fetched",
             "d2h_shard_skipped")
    before = _counts(transfers, kinds) | _counts(d2h, ("wire", "useful"))
    got = rs_resident.reconstruct_intervals(c, 23, reqs)
    after = _counts(transfers, kinds) | _counts(d2h, ("wire", "useful"))
    for (sid, off, size), piece in zip(reqs, got):
        assert piece == encoded_big[sid][off : off + size].tobytes()
    moved = {k: after[k] - before[k] for k in after}
    assert moved["h2d_async"] == len(sizes) and moved["h2d_waited"] == 0
    assert moved["d2h_shard_fetched"] == len(sizes) * len(owners)
    assert moved["d2h_shard_skipped"] == len(sizes) * idle
    # call = (kind, part, cols, pad, fetch, tile, n_bucket, deltas)
    assert moved["wire"] == sum(
        len(owners) * call[6] * call[4] for call in packed
    )
    assert moved["useful"] == sum(size for _, _, size in reqs)
    assert moved["wire"] >= moved["useful"]
    c.clear()


def test_sharded_multi_chunk_large_read(encoded_big):
    c = _sharded_cache(layout="blockdiag")
    for sid in range(14):
        if sid != 0:
            c.put(22, sid, encoded_big[sid])
    n = 3 * 1024 * 1024 + 777
    got = rs_resident.reconstruct_intervals(c, 22, [(0, 999, n)])
    assert got[0] == encoded_big[0][999 : 999 + n].tobytes()


def test_whole_pin_on_mesh_device_serves_reads(encoded):
    """A small volume parked whole on a non-default mesh device must
    reconstruct through the per-device compiled path."""
    c = _sharded_cache(mesh_min_shard_bytes=1 << 30)
    # park something on device 0 first so the volume under test lands
    # on a different device
    c.put(90, 0, encoded[0])
    for sid in range(14):
        if sid != 2:
            c.put(91, sid, encoded[sid])
    assert isinstance(c.placement(91), int) and c.placement(91) != 0
    got = rs_resident.reconstruct_intervals(c, 91, [(2, 4000, 9000)])
    assert got[0] == encoded[2][4000:13000].tobytes()


def test_plan_pin_follows_a_retained_placement_claim(encoded):
    """Budget-pressure eviction deliberately KEEPS a vid's placement
    claim, and a re-pin follows it — so the tiering ladder's fit
    preview (plan_pin with vid) must judge the claimed device, not the
    least-loaded one a fresh volume would get."""
    c = _sharded_cache(mesh_devices=2, mesh_min_shard_bytes=1 << 30)
    pad = c._padded_len(len(encoded[0]))
    c.budget = 2 * (4 * pad)  # per-device budget = 4 shards
    c.put(81, 0, encoded[0])          # claims device 0
    for sid in range(3):
        c.put(82, sid, encoded[sid])  # claims device 1 (3 shards)
    for sid in range(4):
        c.put(83, sid, encoded[sid])  # claims device 0; the 4th put
        # overflows it and pressure-evicts vid 81's shard (LRU head)
    assert c.resident_count(81) == 0
    assert c.placement(81) == 0, "pressure eviction must keep the claim"
    # least-loaded preview says device 1 — but vid 81's re-pin will
    # land on its claimed device 0
    assert set(c.plan_pin(1, len(encoded[0]))) == {1}
    assert set(c.plan_pin(1, len(encoded[0]), vid=81)) == {0}


def test_put_drops_stale_placement_when_claim_vanishes_mid_put(encoded):
    """evict() racing put()'s off-lock staging window must not let the
    in-flight array land under its vanished claim: a later put re-claims
    (possibly a different device) and a mixed-placement shard set turns
    reads into jit device-mismatch errors instead of a clean CacheMiss."""
    c = _sharded_cache(mesh_min_shard_bytes=1 << 30)
    c.put(71, 0, encoded[0])  # claims a whole-pin device
    orig = c._device_of
    fired = {}

    def hooked(place):
        # runs inside put's off-lock staging window, after the claim
        # was read: a racing tiering demotion evicts the vid here
        if not fired:
            fired["x"] = True
            c.evict(71)
        return orig(place)

    c._device_of = hooked
    try:
        c.put(71, 1, encoded[1])  # staged against the vanished claim
    finally:
        c._device_of = orig
    assert c.resident_count(71) == 0, "the stale-place insert must drop"
    assert c.placement(71) is None
    assert not c.vid_device_bytes(71), "no orphaned per-device bytes"
    c.put(71, 2, encoded[2])  # a fresh put re-claims cleanly
    assert c.resident_count(71) == 1
    assert isinstance(c.placement(71), int)


def test_scrub_all_resident_stacks_split_by_placement(encoded):
    """Equal-size volumes whole-pinned on DIFFERENT mesh devices (and a
    lane-sharded one) must land in separate megakernel stacks: one
    _scrub_all_call mixing committed device sets is a jit
    device-mismatch ValueError, not a slow path."""
    rng = np.random.default_rng(91)
    small = rs.RSCodec(backend="numpy").encode_all(
        rng.integers(0, 256, size=(10, 64 * 1024), dtype=np.uint8)
    )
    c = _sharded_cache(mesh_min_shard_bytes=128 * 1024)
    for sid in range(14):
        c.put(201, sid, small[sid])    # whole-pin, least-loaded device
    for sid in range(14):
        c.put(202, sid, small[sid])    # whole-pin, a DIFFERENT device
    for sid in range(14):
        c.put(203, sid, encoded[sid])  # 256KB >= threshold: lane-sharded
    assert isinstance(c.placement(201), int)
    assert isinstance(c.placement(202), int)
    assert c.placement(201) != c.placement(202)
    assert c.placement(203) == "mesh"
    results, stats = rs_resident.scrub_all_resident(c)
    assert set(results) == {201, 202, 203}
    # 201/202 share n_lanes but not a device: three placement stacks
    assert stats["device_calls"] == 3
    for vid in (201, 202, 203):
        assert results[vid][0] == [0, 0, 0, 0], (vid, results[vid])


# ------------------------------------------------------------- AOT grid


def test_warm_covers_sharded_shapes_and_first_read_is_compile_free(
    encoded_big,
):
    from seaweedfs_tpu import stats as swfs_stats

    c = _sharded_cache(layout="blockdiag")
    for sid in range(14):
        if sid != 3:
            c.put(31, sid, encoded_big[sid])
    before = rs_resident.aot_stats()["compiled"]
    rs_resident.warm(c, 31, sizes=(4096,), counts=(16,), aot=True, wait=True)
    assert rs_resident.aot_stats()["compiled"] > before
    assert c.aot_state(31) == "done"
    g = swfs_stats.REGISTRY.get_sample_value
    miss0 = g(
        "SeaweedFS_volumeServer_ec_device_compile_total",
        {"result": "miss"},
    ) or 0
    rng = np.random.default_rng(5)
    L = encoded_big[3].shape[0]
    # any owner-distribution of a 16-wide batch must hit a compiled
    # shape: the plan expanded every count rung at or below the probe's
    reqs = [(3, int(rng.integers(0, L - 4096)), 4000) for _ in range(16)]
    got = rs_resident.reconstruct_intervals(c, 31, reqs)
    for (sid, off, size), piece in zip(reqs, got):
        assert piece == encoded_big[sid][off : off + size].tobytes()
    miss1 = g(
        "SeaweedFS_volumeServer_ec_device_compile_total",
        {"result": "miss"},
    ) or 0
    assert miss1 == miss0, "a warmed sharded read paid a compile"


def test_warm_covers_stripe_boundary_shapes(encoded_big):
    """Reads near a stripe boundary backward-align (fetch grows to the
    full bucket) or split (halves land in buckets no probe size maps
    to): a warmed sharded volume must serve them from parked
    executables, never shed ColdShape or pay an inline compile."""
    from seaweedfs_tpu import stats as swfs_stats

    c = _sharded_cache(layout="blockdiag")
    for sid in range(14):
        if sid != 3:
            c.put(42, sid, encoded_big[sid])
    rs_resident.warm(c, 42, sizes=(4096,), counts=(16,), aot=True, wait=True)
    assert c.aot_state(42) == "done"
    g = swfs_stats.REGISTRY.get_sample_value
    miss0 = g(
        "SeaweedFS_volumeServer_ec_device_compile_total",
        {"result": "miss"},
    ) or 0
    stripe = c.stripe
    assert stripe > 0
    reqs = []
    for b in range(1, 9):
        edge = b * stripe
        # bucket window overhangs the boundary -> backward-aligned,
        # fetch = the full 8192 bucket (no probe span reaches it)
        reqs.append((3, edge - 3000, 2900))
        # straddles the boundary -> split into bucket-2048 halves
        reqs.append((3, edge - 2000, 4000))
    got = rs_resident.reconstruct_intervals(c, 42, reqs)
    for (sid, off, size), piece in zip(reqs, got):
        assert piece == encoded_big[sid][off : off + size].tobytes()
    miss1 = g(
        "SeaweedFS_volumeServer_ec_device_compile_total",
        {"result": "miss"},
    ) or 0
    assert miss1 == miss0, "a boundary-placed warmed read paid a compile"


def test_cold_sharded_shape_sheds_instead_of_compiling(encoded_big):
    c = _sharded_cache(layout="blockdiag")
    for sid in range(14):
        if sid != 3:
            c.put(32, sid, encoded_big[sid])
    rs_resident.warm(c, 32, sizes=(4096,), counts=(1,), aot=True, wait=True)
    with pytest.raises(rs_resident.ColdShape):
        rs_resident.reconstruct_intervals(c, 32, [(3, 0, 400000)])


def test_homogeneous_sharded_batch_is_one_call_and_matches_oracle(
    encoded_big,
):
    """Eight 4 KiB requests of one size bucket against a lane-sharded
    volume are ONE sharded device call (a hit or a miss of
    ec_device_compile_total), byte-equal to the oracle."""
    from test_rs_resident import _device_calls as calls

    c = _sharded_cache(layout="blockdiag")
    for sid in range(14):
        if sid != 1:
            c.put(33, sid, encoded_big[sid])
    rng = np.random.default_rng(6)
    L = encoded_big[1].shape[0]
    reqs = [(1, int(rng.integers(0, L - 8192)), 4096) for _ in range(8)]
    calls0 = calls()
    got = rs_resident.reconstruct_intervals(c, 33, reqs)
    assert calls() - calls0 == 1
    for (sid, off, size), piece in zip(reqs, got):
        assert piece == encoded_big[sid][off : off + size].tobytes()


# ----------------------------------------------- tiering per-device fit


class _FakeShard:
    def __init__(self, size: int):
        self.size = size


class _FakeVol:
    def __init__(self, vid, data: dict[int, bytes]):
        self.id = vid
        self.dir = f"/fake/{vid}"
        self._data = data
        self.shards = {sid: _FakeShard(len(b)) for sid, b in data.items()}

    def load_shards_to_device(self, cache):
        n = 0
        for sid, b in self._data.items():
            if cache.get(self.id, sid) is None:
                cache.put(self.id, sid, b)
                n += 1
        return n

    def stage_host_shards(self):
        return {
            sid: np.frombuffer(b, dtype=np.uint8)
            for sid, b in self._data.items()
        }


class _FakeLoc:
    def __init__(self, vols):
        self.ec_volumes = {v.id: v for v in vols}


class _FakeStore:
    def __init__(self, vols, cache):
        self._lock = threading.Lock()
        self.locations = [_FakeLoc(vols)]
        self.ec_device_cache = cache
        self.ec_host_cache = None

    def set_ec_host_cache(self, hc):
        self.ec_host_cache = hc

    def ec_volume_tier(self, vid):
        from seaweedfs_tpu.storage.ec.layout import DATA_SHARDS

        if self.ec_device_cache.resident_count(vid) >= DATA_SHARDS:
            return "hbm"
        return "disk"


def _fake_volume(vid, shard_bytes, rng):
    return _FakeVol(
        vid,
        {
            sid: rng.integers(0, 256, size=shard_bytes, dtype=np.uint8)
            .tobytes()
            for sid in range(10)
        },
    )


def _controller(store, cache):
    from seaweedfs_tpu.serving import ServingConfig
    from seaweedfs_tpu.serving.tiering import TieringController

    return TieringController(
        store,
        ServingConfig(
            tier_min_residency_seconds=0.0,
            tier_promote_ratio=1.0,
            tier_interval_seconds=0.0,
        ).validated(),
    )


def test_pressure_demotes_from_the_full_device_not_the_coldest_volume():
    """A (hot) volume on the over-budget device must be demoted even
    when a colder victim exists on a device with headroom — the r15
    aggregate logic would have picked the cold one and freed nothing
    where the pressure is."""
    rng = np.random.default_rng(9)
    cache = _sharded_cache(mesh_devices=2, mesh_min_shard_bytes=1 << 30)
    big = _fake_volume(101, 2 * 1024 * 1024, rng)   # padded 4MB/shard
    small = _fake_volume(102, 64 * 1024, rng)       # padded 3MB/shard
    store = _FakeStore([big, small], cache)
    ctl = _controller(store, cache)
    big.load_shards_to_device(cache)     # 40MB on device A
    small.load_shards_to_device(cache)   # 30MB on device B
    dev_big = cache.placement(101)
    assert dev_big != cache.placement(102)
    # per-device budget 35MB: only big's device is over
    cache.budget = 2 * 35 * 1024 * 1024
    ctl.heat.note(101)  # big is HOT, small is cold
    moves = ctl.rebalance()
    assert ("demote_hbm", 101) in moves, moves
    assert cache.resident_count(102) == 10, (
        "the cold volume on the healthy device must not be demoted"
    )
    assert not cache.pressure_devices()
    assert dev_big is not None


def test_promotion_fit_uses_per_device_headroom():
    """An aggregate-fits check would refuse this promotion (total used
    + need > total budget/2 per device on average) — the per-device
    preview sees the idle device and places there."""
    rng = np.random.default_rng(10)
    cache = _sharded_cache(mesh_devices=2, mesh_min_shard_bytes=1 << 30)
    parked = _fake_volume(111, 64 * 1024, rng)
    cand = _fake_volume(112, 64 * 1024, rng)
    store = _FakeStore([parked, cand], cache)
    ctl = _controller(store, cache)
    parked.load_shards_to_device(cache)  # 30MB on device A
    # per-device budget 32MB: A has 2MB headroom, B has 32MB
    cache.budget = 2 * 32 * 1024 * 1024
    ctl.heat.note(112, n=5)
    need = ctl._pin_need(cache, 112, (10, 64 * 1024))
    # whole-pin preview: one device, and it is the idle one
    assert len(need) == 1
    assert next(iter(need)) != cache.placement(111)
    moves = ctl.rebalance()
    assert ("promote_hbm", 112) in moves, moves
    assert cache.placement(112) != cache.placement(111)
    assert cache.resident_count(111) == 10  # no demotion was needed


def test_swap_victims_come_only_from_the_needed_device():
    """The promotion swap loop must skip residents parked on devices
    the candidate does NOT need room on: demoting them frees nothing
    where the pin lands, loses their residency for nothing, and can
    exhaust the victim cap before a useful victim is reached."""
    rng = np.random.default_rng(11)
    cache = _sharded_cache(mesh_devices=2, mesh_min_shard_bytes=1 << 30)
    vol_d0 = _fake_volume(121, 64 * 1024, rng)  # padded 3MB/shard
    vol_d1 = _fake_volume(122, 64 * 1024, rng)
    cand = _fake_volume(123, 64 * 1024, rng)
    store = _FakeStore([vol_d0, vol_d1, cand], cache)
    ctl = _controller(store, cache)
    vol_d0.load_shards_to_device(cache)  # 30MB on device 0
    vol_d1.load_shards_to_device(cache)  # 30MB on device 1
    assert cache.placement(121) != cache.placement(122)
    # per-device budget 32MB: neither device fits the 30MB candidate
    # without a swap, and plan_pin targets the least-loaded (tied ->
    # device 0, where vol_d0 sits)
    cache.budget = 2 * 32 * 1024 * 1024
    need = ctl._pin_need(cache, 123, (10, 64 * 1024))
    assert set(need) == {cache.placement(121)}
    ctl.heat.note(121)       # vol_d1 (heat 0) is the COLDEST victim —
    ctl.heat.note(123, n=5)  # but it holds nothing on the needed device
    moves = ctl.rebalance()
    assert ("demote_hbm", 121) in moves, moves
    assert ("promote_hbm", 123) in moves, moves
    assert cache.resident_count(122) == 10, (
        "a victim on a device the candidate needs no room on must "
        "not be demoted"
    )


# ------------------------------------------------------------ telemetry


def test_node_telemetry_per_device_block():
    from seaweedfs_tpu.stats.cluster import NodeTelemetry

    nt = NodeTelemetry(
        last_seen=100.0,
        has_payload=True,
        device_budget_bytes=80,
        device_used_bytes=50,
        device_bytes_per_device=[30, 20],
    )
    d = nt.to_dict(now=100.5, stale_after=10.0)
    per = d["device"]["per_device"]
    assert per == [
        {"device": 0, "used_bytes": 30, "budget_bytes": 40,
         "headroom_bytes": 10},
        {"device": 1, "used_bytes": 20, "budget_bytes": 40,
         "headroom_bytes": 20},
    ]


def test_telemetry_roundtrips_per_device_bytes():
    from seaweedfs_tpu.pb import master_pb2

    tel = master_pb2.VolumeServerTelemetry()
    tel.device_bytes_per_device.extend([7, 8, 9])
    back = master_pb2.VolumeServerTelemetry.FromString(
        tel.SerializeToString()
    )
    assert list(back.device_bytes_per_device) == [7, 8, 9]


# --------------------------------------------------------------- config


def test_serving_config_validates_mesh_knobs():
    from seaweedfs_tpu.serving import ServingConfig

    assert ServingConfig().validated().mesh is True
    with pytest.raises(ValueError):
        ServingConfig(mesh_devices=-1).validated()
    with pytest.raises(ValueError):
        ServingConfig(mesh_min_shard_mb=-1).validated()
