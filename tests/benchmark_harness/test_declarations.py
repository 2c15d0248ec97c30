"""The guard of declarations.py: every declaration check, which the
other test files run on the live BENCHMARK.json, passes as it stands on
a copy of the tree to which a sixth one-chip cell was added as files and
entries the way a later PR adds one.  In the copy the cell is one more
cell of the GET rate and of the holder-down readers, and the GET cells
gain two readers, so each count, position and whole list that these
tests once held to a literal differs from the live file's; the copy's
cell then rehearses on the CPU from the copy's own declaration.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import declarations as decl  # noqa: E402
from test_benchmark_harness import rehearse  # noqa: E402

CONFIG = "ec-holder-down-4g-second"
TRAFFIC = "get-mixed-c16-pool2"
CELL = f"{CONFIG}.{TRAFFIC}"
# one reader of counters, which the CPU reads, and one of spans, which
# only a capture on the chip gives: (reader, entry)
NEW_GET_READERS = {
    "batched_route_pct": (
        {"ratio": {"num": [{"series": "ec_read_route_total",
                            "labels": {"route": "batched"}}],
                   "den": [{"fact": "gets"}], "scale": 100}},
        {"unit": "%", "better": "higher", "source": "program_counter",
         "layer": "admission and batching"}),
    "get_shard_read_ms": (
        {"reducer": "host_spans", "mode": "self_ms_per",
         "spans": ["shard_read"], "per": "gets"},
        {"unit": "ms", "better": "lower", "source": "program_span",
         "layer": "resident cache and reconstruct"}),
}


def dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the tree with the cell of the second holder of four lost
    (shards 1, 5, 9 and 13: `ec-holder-down-4g`'s `assumed` names it the
    same case) under the mixed loop with another key pool."""
    tree = tmp_path_factory.mktemp("declarations") / "tree"
    shutil.copytree(os.path.join(REPO, "benchmark"), tree / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "seaweedfs_tpu"), tree / "seaweedfs_tpu")
    bench = decl.load(REPO)
    cfg = decl.load_json(REPO, "benchmark", "configs",
                         "ec-holder-down-4g.json")
    cfg["name"], cfg["lost_shards"] = CONFIG, [1, 5, 9, 13]
    dump(cfg, tree / "benchmark" / "configs" / f"{CONFIG}.json")
    mix = decl.load_json(REPO, "benchmark", "traffic", "get-mixed-c16.json")
    mix["params"]["pool_seed"] += 1
    dump(mix, tree / "benchmark" / "traffic" / f"{TRAFFIC}.json")
    for name, (reader, _) in NEW_GET_READERS.items():
        dump(reader, tree / "benchmark" / "layer_metrics" / f"{name}.json")

    bench["configs"].append({
        **decl.entry(bench, "configs", "ec-holder-down-4g"), "name": CONFIG,
        "file": f"benchmark/configs/{CONFIG}.json"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "closed loop, 16 clients, the second holder of four down"})
    decl.entry(bench, "end_to_end", "degraded_get_rate")["workloads"].append(
        CELL)
    for m in bench["per_layer"]:
        if decl.HOLDER_DOWN in m["workloads"]:
            m["workloads"].append(CELL)
    for name, (_, fields) in NEW_GET_READERS.items():
        bench["per_layer"].append({
            "name": name, **fields, "moves": "degraded_get_rate",
            "workloads": [decl.GET_CELL, decl.HOLDER_DOWN, decl.HEALTHY,
                          CELL]})
    dump(bench, tree / "BENCHMARK.json")
    return str(tree)


def test_the_copy_moves_every_former_pin(copy):
    live, added = decl.load(REPO), decl.load(copy)
    assert len(added["workloads"]) == len(live["workloads"]) + 1
    rate = decl.entry(added, "end_to_end", "degraded_get_rate")["workloads"]
    assert rate[-1] == CELL
    for name in decl.WIDE_READERS:
        wide = decl.entry(added, "per_layer", name)["workloads"]
        assert wide == decl.entry(live, "per_layer", name)["workloads"] + [
            CELL]
    assert (decl.readers_of(added, decl.GET_CELL)
            == decl.readers_of(live, decl.GET_CELL) | set(NEW_GET_READERS))

    def spans(bench, root):
        return [m for m in bench["per_layer"] if decl.reader(
            root, m["name"]).get("reducer") == "host_spans"]
    assert len(spans(added, copy)) == len(spans(live, REPO)) + 1


@pytest.mark.parametrize("check", decl.CHECKS, ids=lambda c: c.__name__)
def test_every_check_passes_with_a_cell_added_as_files(copy, check):
    check(decl.load(copy), copy)


def test_the_added_cell_rehearses_from_the_copy(copy):
    line = rehearse(CELL, "--trace", "1", cwd=copy)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 16
    assert line["compared"] == {
        "failed_gets": {"value": 0, "limit": 0},
        "wrong_bodies": {"value": 0, "limit": 0}}
    counted = {n for n, m in line["metrics"].items() if m["value"] is not None}
    assert counted == {"batch_size_mean", "device_calls_per_get",
                       "host_route_pct", "batched_route_pct"} | (
                           decl.WIDE_READERS)
    # three lost data shards: a call can want more than one of them
    assert line["metrics"]["wanted_rows_per_call"]["value"] >= 1
    assert line["metrics"]["host_route_pct"]["value"] == 0
