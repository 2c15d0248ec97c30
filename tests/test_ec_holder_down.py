"""One holder of four down: shards 0, 4, 8 and 12 lost, exactly ten
survivors resident, and a batch may want any subset of the three lost
data shards.

The block-diagonal reconstruct kernels take the wanted-set width static,
so the plan made at pin time (one wanted shard) does not cover such a
volume.  Held here, on the CPU, by bytes and counts: the bytes of every
wanted set against benchmark/reference/rs_plain.py; the warm plan that
follows the loss (Store.unmount_ec_shards -> rs_resident.warm_replan):
its states, the shed while it runs, that it covers every shape the pack
stage can emit, that a loss of one data shard queues nothing, and that
overlapping losses leave no state and no thread behind; and the rows
wanted / rows computed counters.  Seconds of a re-plan are the chip's
(PERF.md).
"""
import itertools
import os
import random
import sys
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ops import rs_resident
from seaweedfs_tpu.shell.command_ec import balanced_ec_distribution
from seaweedfs_tpu.stats import metrics as stats_metrics
from seaweedfs_tpu.shell.command_env import TopoNode
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.store import Store

from test_ec import encode_volume, make_volume

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import rs_plain  # noqa: E402

LOST = (0, 4, 8, 12)
LOST_DATA = (0, 4, 8)
SUBSETS = [
    c for n in (1, 2, 3) for c in itertools.combinations(LOST_DATA, n)
]
KERNELS = [("xla", False), ("pallas", True)]  # (kernel, interpret)


def _sample(name, **labels):
    from seaweedfs_tpu import stats

    return stats.REGISTRY.get_sample_value(name, labels) or 0.0


SHED = "SeaweedFS_volumeServer_ec_shed_cold_shape_total"
COMPILE = "SeaweedFS_volumeServer_ec_device_compile_total"
PIN_SECONDS = "SeaweedFS_volumeServer_ec_pin_seconds_total"
ROWS = "SeaweedFS_volumeServer_ec_reconstruct_rows_total"


def test_the_lost_holder_is_the_first_of_four_equal_nodes():
    nodes = [
        TopoNode(url=f"n{i}:8080", grpc_port=0, data_center="dc", rack="r",
                 max_volume_counts={"hdd": 10})
        for i in range(4)
    ]
    spread = balanced_ec_distribution(nodes)
    assert spread[0][0] is nodes[0] and tuple(spread[0][1]) == LOST
    assert sorted(len(sids) for _n, sids in spread) == [3, 3, 4, 4]


# ------------------------------------------------------------ (a) the bytes


@pytest.fixture(scope="module")
def seeded():
    """One 1 MB row of a seeded .dat -> all 14 shards by the plain
    reference, and a cache that holds exactly the ten survivors."""
    rng = np.random.default_rng(20261004)
    dat = rng.integers(0, 256, size=10 * rs_plain.BLOCK - 777,
                       dtype=np.uint8).tobytes()
    shards = rs_plain.encode_rows(
        dat, 0, 1, rs_plain.coding_matrix()[rs_plain.DATA_SHARDS:])
    cache = rs_resident.DeviceShardCache(
        shard_quantum=1 << 20, layout="blockdiag")
    for sid in range(14):
        if sid not in LOST:
            cache.put(21, sid, shards[sid])
    return shards, cache


@pytest.mark.parametrize("kernel,interpret", KERNELS)
@pytest.mark.parametrize("subset", SUBSETS, ids=lambda s: "w" + "_".join(
    map(str, s)))
def test_every_wanted_set_equals_the_plain_reference(
        seeded, subset, kernel, interpret):
    shards, cache = seeded
    assert cache.shard_ids(21) == [s for s in range(14) if s not in LOST]
    rng = random.Random(f"{subset}/{kernel}")
    requests = []
    for i in range(len(subset) + 1 if interpret else 2 * len(subset) + 1):
        sid = subset[i % len(subset)]
        # aligned and unaligned offsets, sizes across two size buckets
        # (delta + take up to 8192, and up to 32768)
        size = rng.choice([3000, 4096, 7000, 9000, 20000])
        off = rng.choice([0, 1024, 4096 * rng.randrange(200),
                          rng.randrange(rs_plain.BLOCK - size)])
        requests.append((sid, off, size))
    out = rs_resident.reconstruct_intervals(
        cache, 21, requests, kernel=kernel, interpret=interpret)
    for (sid, off, size), got in zip(requests, out):
        assert got == shards[sid][off:off + size].tobytes(), (sid, off, size)


def test_the_survivors_are_not_a_choice(seeded):
    """With ten present the reconstruction uses all ten, whatever is
    wanted: storage/ec and the dispatcher need no branch of their own."""
    from seaweedfs_tpu.ops import gf256

    present = [s for s in range(14) if s not in LOST]
    for subset in SUBSETS:
        _rmat, use = gf256.reconstruction_matrix(10, 14, present, subset)
        assert use == present


# ------------------------------------------- the plan covers what pack emits


class TestWidePlanCoversDispatch:
    """Every device-call shape _pack_calls can emit for a batch that
    wants several of the lost data shards is one the re-plan compiles:
    the wide family's counts (_WIDE_COUNTS) and fetch rungs are stated
    twice, in the pack stage and in the plan's probes."""

    SHARD = 4 << 20

    @pytest.fixture(scope="class")
    def cache(self):
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag")
        rng = np.random.default_rng(5)
        for sid in range(14):
            if sid not in LOST:  # the planner never looks at the bytes
                cache.put(41, sid, rng.integers(
                    0, 256, size=self.SHARD, dtype=np.uint8))
        return cache

    def _plan(self, cache, kernel, interpret):
        keys = set()
        for reqs in rs_resident._wide_probes(cache, 41, cache.warm_sizes):
            keys.update(rs_resident._probe_keys(
                cache, 41, reqs, kernel, interpret, "blockdiag", 14))
        return keys

    def test_the_family_is_small(self, cache):
        # ten fetch rungs (the ladder's powers of two), one count a size
        # class, two rungs shared by classes of different counts: what
        # the chip compiles in well under a minute cold (PERF.md)
        plan = self._plan(cache, "pallas", False)
        assert len(plan) == 12
        assert all(key[4] & (key[4] - 1) == 0 for key in plan)
        assert {key[2] for key in plan} == {3}  # one width: all lost

    @pytest.mark.parametrize("bucket_index", range(6))
    def test_random_batches_stay_inside_the_plan(self, cache, bucket_index):
        plan = self._plan(cache, "pallas", False)
        hi = rs_resident.SIZE_BUCKETS[bucket_index]
        lo = rs_resident.SIZE_BUCKETS[bucket_index - 1] if bucket_index else 0
        hi = min(hi, rs_resident.CHUNK)
        rng = np.random.default_rng(100 + bucket_index)
        for n in (2, 3, 5, 9, 17, 40):
            for subset in SUBSETS[3:]:
                requests = []
                for i in range(n):
                    off = int(rng.integers(0, self.SHARD - hi))
                    delta = off % rs_resident.LANE
                    take = int(rng.integers(
                        max(1, lo + 1 - delta), hi - delta + 1))
                    requests.append((subset[i % len(subset)], off, take))
                calls, _s, surv, a_prep, use, w_true, place = (
                    rs_resident._pack_calls(
                        cache, 41, requests, "pallas", False, "blockdiag",
                        10, 14, record_observed=False))
                assert w_true == 3
                for kind, _p, _c, _pad, fetch, tile, n_bucket, _d in calls:
                    key = rs_resident._call_key(
                        kind, "pallas", cache.groups, w_true, tile, fetch,
                        n_bucket, len(use), a_prep.shape,
                        int(surv[0].size), False, 0)
                    assert key in plan, (n, subset, key)

    def test_a_batch_that_wants_one_lost_shard_keeps_the_pin_time_family(
            self, cache):
        for sid in LOST_DATA:
            calls, _s, _surv, a_prep, _use, w_true, _p = (
                rs_resident._pack_calls(
                    cache, 41, [(sid, 0, 4096)] * 3, "pallas", False,
                    "blockdiag", 10, 14, record_observed=False))
            assert w_true == 1 and a_prep.shape[0] == 32
            assert [c[6] for c in calls] == [4]  # the exact count bucket


# ------------------------------------------------- (b)-(d) the plan follows


@pytest.fixture
def pinned(tmp_path):
    """A Store with one EC volume, fourteen shards pinned and warm."""
    v, _blobs = make_volume(tmp_path, vid=31, count=24)
    base = encode_volume(v)
    v.close()
    os.remove(base + ".dat")
    os.remove(base + ".idx")
    shards = {}
    for sid in range(14):
        with open(f"{base}.ec{sid:02d}", "rb") as f:
            shards[sid] = f.read()
    cache = rs_resident.DeviceShardCache(
        budget_bytes=1 << 30, shard_quantum=1 << 20, layout="blockdiag")
    cache.warm_sizes = (4096,)
    cache.warm_counts = (1, 4)
    store = Store([DiskLocation(str(tmp_path), max_volume_count=8)],
                  ec_backend="cpu", ec_device_cache=cache)
    try:
        for t in list(store._pin_threads):
            t.join(timeout=300)
        assert cache.shard_ids(31) == list(range(14))
        assert cache.aot_state(31) == "done"
        yield store, cache, shards
    finally:
        store.close()


class Gate:
    """Hold the one AOT compile worker, so that what is queued behind
    it stays queued until the test lets go."""

    def __enter__(self):
        self.event = threading.Event()
        self.held = rs_resident._aot_executor().submit(self.event.wait, 120)
        return self

    def __exit__(self, *exc):
        self.event.set()
        self.held.result()


def _join(store):
    for t in list(store._pin_threads):
        t.join(timeout=300)
    assert not any(t.is_alive() for t in store._pin_threads)
    assert store._ec_replan_latest == {}


def _reads(subset):
    return [(sid, 128 * (3 + i), 4000) for i, sid in enumerate(subset)]


def test_done_warming_done_and_the_shed_between(pinned):
    store, cache, shards = pinned
    shed0 = _sample(SHED)
    replan0 = _sample(PIN_SECONDS, volume="31", phase="replan")
    with Gate():
        store.delete_ec_shards(31, list(LOST))
        # back in "warming" before the RPC's thread returns
        assert cache.aot_state(31) == "warming"
        assert cache.shard_ids(31) == [s for s in range(14) if s not in LOST]
        deadline = time.monotonic() + 60
        while not rs_resident.aot_stats()["pending"]:  # the plan's thread
            assert time.monotonic() < deadline
            time.sleep(0.01)
        with pytest.raises(rs_resident.ColdShape):
            rs_resident.reconstruct_intervals(cache, 31, _reads((0, 4)))
        assert _sample(SHED) == shed0 + 2
        # one lost shard wanted: the pin-time family serves it meanwhile
        (got,) = rs_resident.reconstruct_intervals(cache, 31, _reads((8,)))
        assert got == shards[8][384:4384]
    _join(store)
    assert cache.aot_state(31) == "done"
    assert rs_resident.aot_stats()["pending"] == 0
    assert _sample(
        PIN_SECONDS, volume="31", phase="replan") > replan0
    miss0 = _sample(COMPILE, result="miss")
    hit0 = _sample(COMPILE, result="hit")
    for subset in SUBSETS:
        reqs = _reads(subset)
        out = rs_resident.reconstruct_intervals(cache, 31, reqs)
        for (sid, off, size), got in zip(reqs, out):
            assert got == shards[sid][off:off + size], subset
    assert _sample(COMPILE, result="miss") == miss0
    assert _sample(COMPILE, result="hit") == hit0 + 7
    assert _sample(SHED) == shed0 + 2


def test_losing_one_data_and_one_parity_shard_queues_nothing(pinned):
    store, cache, shards = pinned
    before = rs_resident.aot_stats()
    with Gate():
        store.delete_ec_shards(31, [3, 11])
        assert cache.aot_state(31) == "done"
        assert rs_resident.aot_stats() == before
        assert store._ec_replan_latest == {}
        assert not any(
            t.name.startswith("ec-replan") for t in store._pin_threads)
    (got,) = rs_resident.reconstruct_intervals(cache, 31, _reads((3,)))
    assert got == shards[3][384:4384]


def test_a_second_loss_during_a_replan(pinned):
    store, cache, _shards = pinned
    with Gate():
        store.delete_ec_shards(31, [0, 4])
        assert cache.aot_state(31) == "warming"
        first = store._ec_replan_latest[31]
        store.delete_ec_shards(31, [8, 12])
        assert cache.aot_state(31) == "warming"
        assert store._ec_replan_latest[31] > first
    _join(store)
    assert cache.aot_state(31) == "done"
    assert rs_resident.aot_stats()["pending"] == 0
    miss0 = _sample(COMPILE, result="miss")
    rs_resident.reconstruct_intervals(cache, 31, _reads((0, 4, 8)))
    assert _sample(COMPILE, result="miss") == miss0


def test_the_last_shard_unmounted_during_a_replan(pinned):
    store, cache, _shards = pinned
    with Gate():
        store.delete_ec_shards(31, [0, 4])
        assert cache.aot_state(31) == "warming"
        store.unmount_ec_shards(31, [s for s in range(14) if s not in (0, 4)])
        assert cache.shard_ids(31) == []
        assert cache.aot_state(31) == "none"
        assert store._ec_replan_latest == {}
    _join(store)
    assert cache.aot_state(31) == "none"
    assert rs_resident.aot_stats()["pending"] == 0


# ------------------------------------------------------- (e) the counters


@pytest.mark.parametrize("subset,wanted,computed", [
    ((4,), 1, 1),
    ((0, 4), 2, 3),
    ((0, 4, 8), 3, 3),
])
def test_rows_wanted_and_rows_computed_of_a_known_batch(
        seeded, subset, wanted, computed):
    _shards, cache = seeded
    w0, c0 = _sample(ROWS, kind="wanted"), _sample(ROWS, kind="computed")
    calls0 = (_sample(COMPILE, result="hit")
              + _sample(COMPILE, result="miss"))
    # one size bucket, so one call
    reqs = [(sid, 4096 * i, 3000) for i, sid in enumerate(subset * 2)]
    rs_resident.reconstruct_intervals(cache, 21, reqs)
    assert (_sample(COMPILE, result="hit")
            + _sample(COMPILE, result="miss")) == calls0 + 1
    assert _sample(ROWS, kind="wanted") == w0 + wanted
    assert _sample(ROWS, kind="computed") == c0 + computed


def test_the_row_counters_read_zero_before_any_call():
    from prometheus_client import generate_latest

    text = generate_latest(stats_metrics.REGISTRY).decode()
    for kind in ("wanted", "computed"):
        assert f'{ROWS}{{kind="{kind}"}}' in text
