"""The four-chip cell's share of the benchmark's own tests: the two-tier
reference on hand-worked cases, the layout-aware pool, the set-up check's
windows, the mesh reducer on a synthetic four-plane trace, and the cell
rehearsed on the CPU (one device, a 12 MiB volume: the mesh itself is
tests/test_ec_two_tier_layout.py's and tests/test_mesh_serving.py's) —
sound, with the control's stale shard, and with a fault planted.
"""
import os
import sys
from types import SimpleNamespace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import declarations  # noqa: E402
from benchmark import peaks, trace, work_counts  # noqa: E402
from benchmark.cluster import BenchFailure  # noqa: E402
from benchmark.generators.closed_loop_get_lb import pick_pool  # noqa: E402
from benchmark.reducers import mesh  # noqa: E402
from benchmark.reference import rs_layout_plain as ref  # noqa: E402
from benchmark.steps.check_encode_windows import pick_windows  # noqa: E402
from declarations import MESH_CELL  # noqa: E402
from test_benchmark_harness import BENCH, rehearse  # noqa: E402

GIB, MIB = 1 << 30, 1 << 20


def test_two_tier_reference_on_hand_worked_cases():
    dat = 16 * GIB + 8  # the cell's volume: one large row, 615 small rows
    assert ref.n_large_rows(dat) == 1
    assert ref.shard_size_of(dat) == GIB + 615 * MIB
    assert ref.shard_size_of(30_000 * MIB) == 2 * GIB + 952 * MIB
    # block 3 of the large row is shard 3's first GiB
    assert ref.locate(dat, 3 * GIB + 5, 100) == [(3, 5, 100, True)]
    assert ref.bytes_on_shard(dat, 3 * GIB - 10, 30, 3) == 20
    # the last bytes of the large row and the first of the small rows
    assert ref.locate(dat, 10 * GIB - 4, 10) == [
        (9, GIB - 4, 4, True), (0, GIB, 6, False)]
    # small block 13 is row 1 of shard 3, past the large block
    assert ref.locate(dat, 10 * GIB + 13 * MIB, MIB + 1) == [
        (3, GIB + MIB, MIB, False), (4, GIB + MIB, 1, False)]
    # below 10 GB it is work_counts' 1 MB rule
    for off, ln, shard in ((3 * MIB - 100, 300, 3), (0, 20 * MIB, 3),
                           (4 * MIB, 9 * MIB + 5, 3)):
        assert ref.bytes_on_shard(4 * GIB, off, ln, shard) == (
            work_counts.bytes_on_shard(off, ln, shard))


def groups_of(n_sizes=2, per_group=40):
    """{size: {in_large_row: (lost keys, healthy keys)}}, a quarter of
    every group with bytes on the lost shard."""
    groups, key = {}, 0
    for size in range(n_sizes):
        for in_large in (True, False):
            keys = list(range(key, key + per_group))
            key += per_group
            groups.setdefault(size, {})[in_large] = (
                keys[: per_group // 4], keys[per_group // 4:])
    return groups


def test_pool_takes_regions_and_lost_share_exactly():
    groups = groups_of()
    lost = {k for g in groups.values() for l, _ in g.values() for k in l}
    large = {k for g in groups.values() for k in sum(g[True], [])}
    pool = pick_pool(groups, 16, 0.5, 0.5, seed=7)
    assert len(pool) == len(set(pool)) == 32
    assert sum(k in large for k in pool) == 16
    assert sum(k in lost for k in pool) == 16
    assert pick_pool(groups, 16, 0.5, 0.5, seed=7) == pool
    # a share of 0 means none, where closed_loop_get keeps one a size
    assert not lost & set(pick_pool(groups, 16, 0.5, 0.0, seed=7))
    # a volume below 10 GB has no large row: everything from the rest
    assert not large & set(pick_pool(groups, 16, 0.0, 0.5, seed=7))
    # what the volume cannot give ends the run on the chip, and is made
    # up from the other kind in a rehearsal
    with pytest.raises(BenchFailure):
        pick_pool(groups, 32, 0.5, 0.75, seed=7)  # 12 of a group's 10
    short = pick_pool(groups, 32, 0.5, 0.75, seed=7, strict=False)
    assert len(short) == len(set(short)) == 64
    assert sum(k in lost for k in short) == 40


def test_encode_windows_cover_both_tiers_and_their_boundary():
    shard, edge = GIB + 615 * MIB, GIB
    windows = pick_windows(shard, edge, 32, MIB, seed=5)
    assert windows == pick_windows(shard, edge, 32, MIB, seed=5)
    assert all(0 <= s and s + n <= shard for s, n in windows)
    assert sum(s + n <= edge for s, n in windows) == 16
    assert sum(s >= edge for s, n in windows) == 15
    assert windows[-1] == (edge - MIB // 2, MIB)
    # a rehearsal's 2 MiB shard has one tier
    small = pick_windows(2 * MIB, 0, 32, MIB, seed=5)
    assert all(0 <= s <= MIB and n == MIB for s, n in small)


def _event(name, start_us, dur_us):
    return SimpleNamespace(name=name, start_ns=start_us * 1e3,
                           duration_ns=dur_us * 1e3)


def _mesh_profile(durations):
    """Four device planes running the same two executions of the sharded
    program, plane d taking durations[d] microseconds for each."""
    planes = []
    for d, dur in enumerate(durations):
        events = [_event("jit__sharded_gather_reconstruct(7)", 0, dur),
                  _event("jit__sharded_gather_reconstruct(7)", 1000, dur)]
        planes.append(SimpleNamespace(name=f"/device:TPU:{d}", lines=[
            SimpleNamespace(name="XLA Modules", events=events),
            SimpleNamespace(name="XLA Ops", events=[
                _event("fusion", e.start_ns / 1e3, e.duration_ns / 1e3)
                for e in events])]))
    return SimpleNamespace(planes=planes)


def test_mesh_reducer_counts_an_execution_once():
    chip = peaks.lookup("TPU v5 lite")
    tr = trace.read(_mesh_profile([100, 100, 100, 100]))
    assert tr.chips == 4
    params = {"mode": "roofline", "programs": ["sharded_gather_reconstruct"],
              "work": "reconstruct_bytes",
              "work_args": ["reconstruct_lost_bytes"]}
    # 11 x lost bytes take 200 us on four chips' HBM: the two executions
    # held the mesh 200 us
    lost = 4 * 819 * 200 * 1000 // 11
    facts = {"reconstruct_lost_bytes": lost}
    assert mesh.reduce(tr, facts, params, chip) == pytest.approx(
        100.0, rel=1e-3)
    # reducers/roofline.py on the same capture: a quarter
    from benchmark.reducers import roofline
    assert roofline.reduce(tr, facts, params, chip) == pytest.approx(
        25.0, rel=1e-3)
    balance = {"mode": "lane_imbalance"}
    assert mesh.reduce(tr, {}, balance, chip) == pytest.approx(0.0)
    # one lane twice as long: the mesh waits for it
    skew = trace.read(_mesh_profile([200, 100, 100, 100]))
    assert mesh.reduce(skew, facts, params, chip) == pytest.approx(
        50.0, rel=1e-3)
    assert mesh.reduce(skew, {}, balance, chip) == pytest.approx(60.0)
    # nothing to read: no fact, one chip, another program
    assert mesh.reduce(tr, {}, params, chip) is None
    one = trace.read(SimpleNamespace(planes=_mesh_profile([100]).planes))
    assert mesh.reduce(one, facts, params, chip) is None
    assert mesh.reduce(one, {}, balance, chip) is None
    assert mesh.reduce(tr, facts, {**params, "programs": ["absent"]},
                       chip) is None


def test_mesh_cell_is_declared_on_four_chips_with_its_readers():
    declarations.check_named_cells(BENCH, REPO)
    declarations.check_mesh_cell(BENCH, REPO)


def test_mesh_cell_rehearsed_with_trace():
    line = rehearse(MESH_CELL, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    # every client completed a GET: how many more, a loaded host decides
    assert line["attempted"] >= 16
    assert line["compared"] == {
        "failed_gets": {"value": 0, "limit": 0},
        "wrong_bodies": {"value": 0, "limit": 0},
        "encode_windows_differing": {"value": 0, "limit": 0}}
    counted = {n for n, m in line["metrics"].items() if m["value"] is not None}
    # one CPU device: no mesh call, so no wire ratio; a 12 MiB volume has
    # no large row
    assert counted == {"batch_size_mean", "device_calls_per_get",
                       "host_route_pct", "large_row_interval_pct"}
    assert line["metrics"]["large_row_interval_pct"]["value"] == 0.0


@pytest.mark.parametrize("how, number", [
    (("--control", "stale_shard"), "failed_gets+wrong_bodies"),
    (("--fault", "get_flip_byte"), "wrong_bodies"),
])
def test_mesh_cell_control_and_fault_come_out_incorrect(how, number):
    line = rehearse(MESH_CELL, "--trace", "0", *how)
    assert line["correct"] is False
    assert sum(line["compared"][n]["value"] for n in number.split("+")) > 0
    assert line["compared"]["encode_windows_differing"]["value"] == 0
