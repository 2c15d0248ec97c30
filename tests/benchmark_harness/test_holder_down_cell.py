"""The holder-down cell and the healthy-read control: how they are
declared, their readers on the program's own /metrics text and on a
text without the family, and both cells rehearsed on the CPU (a 12 MiB
volume, the XLA fallback kernel: bytes and counts, no device number) —
sound, and with the control's stale shard.
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import declarations  # noqa: E402
from benchmark import metrics_eval  # noqa: E402
from benchmark.cluster import parse_metrics  # noqa: E402
from benchmark.generators.closed_loop_get import pick_pool  # noqa: E402
from declarations import HEALTHY, HOLDER_DOWN, WIDE_READERS  # noqa: E402
from test_benchmark_harness import BENCH, rehearse  # noqa: E402

ROWS = "SeaweedFS_volumeServer_ec_reconstruct_rows_total"
CALLS = "SeaweedFS_volumeServer_ec_device_compile_total"


def entry(group, name):
    return declarations.entry(BENCH, group, name)


def test_both_cells_are_declared_on_one_chip_with_their_readers():
    declarations.check_named_cells(BENCH, REPO)
    declarations.check_get_cells(BENCH, REPO)


def test_the_configuration_is_the_first_of_four_holders_lost():
    from seaweedfs_tpu.shell.command_ec import balanced_ec_distribution
    from seaweedfs_tpu.shell.command_env import TopoNode

    decl = entry("configs", "ec-holder-down-4g")
    assert len(decl["source"]) <= 200 and decl["reduced"] == ["volume_bytes"]
    cfg = json.load(open(os.path.join(REPO, decl["file"])))
    nodes = [TopoNode(url=f"n{i}:8080", grpc_port=0, data_center="dc",
                      rack="r", max_volume_counts={"hdd": 10})
             for i in range(4)]
    assert cfg["lost_shards"] == balanced_ec_distribution(nodes)[0][1]
    assert cfg["lost_shards"] == [0, 4, 8, 12]
    sibling = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "ec-degraded-4g.json")))
    # the sibling's deployment but for the loss: same flags, sizes,
    # set-up, guarantees word for word, the same cut
    for key in ("layout", "volumes", "volume_bytes", "size_mix",
                "master_flags", "volume_flags", "setup", "guarantees",
                "rehearse"):
        assert cfg[key] == sibling[key], key
    assert cfg["reduced"] == sibling["reduced"]
    assert [s["step"] for s in cfg["setup"]] == [
        "load", "encode", "wait_resident", "lose_shards"]
    assert set(cfg["assumed"]) == {
        "layout_on_one_chip", "lost_shards", "lost_shard_share"}


def test_the_healthy_mix_is_the_mixed_one_with_no_lost_share():
    mixed = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "get-mixed-c16.json")))
    healthy = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "get-healthy-c16.json")))
    assert healthy["params"].pop("lost_shard_share") == 0
    assert mixed["params"].pop("lost_shard_share") == 0.75
    assert healthy == mixed
    # a share of 0 keeps one lost key a size: the traced window runs a
    # device program, which the harness asks of every traced run
    sizes = [4096, 16384] * 50
    lost_bytes = {k: (100 if k % 5 == 0 else 0) for k in range(1, 101)}
    pool = pick_pool(sizes, lost_bytes, 20, 0, seed=3)
    assert len(pool) == len(set(pool)) == 40
    by_size = {4096: 0, 16384: 0}
    for k in pool:
        by_size[sizes[k - 1]] += lost_bytes[k] > 0
    assert by_size == {4096: 1, 16384: 1}


def metrics_text(wanted, computed, hits, misses, with_family=True):
    rows = [f'{CALLS}{{result="hit"}} {hits}',
            f'{CALLS}{{result="miss"}} {misses}']
    if with_family:
        rows += [f'{ROWS}{{kind="wanted"}} {wanted}',
                 f'{ROWS}{{kind="computed"}} {computed}']
    return parse_metrics("\n".join(rows))


@pytest.mark.parametrize("name, want", [
    ("wanted_rows_per_call", 1.8),
    ("reconstruct_rows_computed_ratio", 3.0 / 1.8),
])
def test_the_readers_divide_the_window_s_deltas(name, want):
    reader = metrics_eval.load_reader(name)["ratio"]
    before = metrics_text(100, 100, 90, 10)
    after = metrics_text(100 + 180, 100 + 300, 190, 10)
    assert metrics_eval.ratio(reader, before, after, {}) == pytest.approx(
        want)


@pytest.mark.parametrize("name", sorted(WIDE_READERS))
def test_a_program_without_the_family_reads_nothing_and_does_not_raise(name):
    """The parent of this PR has no such counters: the wanted rows sum to
    0 there, so the ratio over them has nothing to divide by, and the
    rows per call read 0."""
    reader = metrics_eval.load_reader(name)["ratio"]
    before = metrics_text(0, 0, 90, 10, with_family=False)
    after = metrics_text(0, 0, 190, 10, with_family=False)
    value = metrics_eval.ratio(reader, before, after, {})
    assert value in (None, 0.0)
    # and a window that made no call reads nothing at all
    assert metrics_eval.ratio(reader, before, before, {}) is None


def test_the_row_counters_are_registered_with_help_text():
    from seaweedfs_tpu.stats import metrics as stats_metrics

    family = stats_metrics.VOLUME_SERVER_EC_RECONSTRUCT_ROWS
    assert family._name + "_total" == ROWS
    assert "wanted" in family._documentation
    readme = open(os.path.join(REPO, "README.md")).read()
    assert ROWS in readme and "warm_replan" in readme
    assert 'phase="replan"' in readme or "`replan`" in readme


def counted(line):
    return {n for n, m in line["metrics"].items() if m["value"] is not None}


def test_holder_down_cell_rehearsed_with_trace():
    line = rehearse(HOLDER_DOWN, "--trace", "1")
    assert line["correct"] is True and line["failed"] == 0
    # three seconds of the interpreted fallback kernel on two cores,
    # beside five other test workers: a few dozen GETs
    assert line["attempted"] > 16
    assert line["compared"] == {
        "failed_gets": {"value": 0, "limit": 0},
        "wrong_bodies": {"value": 0, "limit": 0}}
    assert counted(line) == {"batch_size_mean", "device_calls_per_get",
                             "host_route_pct"} | WIDE_READERS
    # the plan that followed the loss covers every shape of the window
    assert line["metrics"]["host_route_pct"]["value"] == 0
    assert line["metrics"]["wanted_rows_per_call"]["value"] >= 1
    assert 1 <= line["metrics"]["reconstruct_rows_computed_ratio"][
        "value"] <= 3


def test_holder_down_cell_rehearsed_untraced():
    line = rehearse(HOLDER_DOWN, "--trace", "0")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"degraded_get_rate", "setup_s"}


def test_holder_down_control_comes_out_incorrect():
    # no spare survivor: the needle checksum is all that stands between
    # a stale shard and the client
    line = rehearse(HOLDER_DOWN, "--trace", "0", "--control", "stale_shard")
    assert line["correct"] is False and line["failed"] > 0
    assert (line["compared"]["failed_gets"]["value"]
            + line["compared"]["wrong_bodies"]["value"]) == line["failed"]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_healthy_cell_rehearsed(trace):
    line = rehearse(HEALTHY, "--trace", trace)
    assert line["correct"] is True and line["failed"] == 0
    # every client completed a GET: how many more, a loaded host decides
    assert line["attempted"] >= 16
    if trace == "1":
        assert counted(line) == {"batch_size_mean", "device_calls_per_get",
                                 "host_route_pct"}
        assert "reconstruct_roofline" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"degraded_get_rate", "setup_s"}
