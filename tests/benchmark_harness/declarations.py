"""What every cell and per-layer reader of a BENCHMARK.json must be, as
functions of the declaration (`bench`, the parsed file) and the tree it
lies in (`root`).  They check properties and relations: that a named cell
exists as it was declared, that a reader lists the cells it reads, that a
cell's readers include what its path needs.  They never count the cells
or the readers, nor look at where one sits in a list, so a cell added as
files and entries passes them as they stand
(test_declarations.py holds them to such a copy of the tree).
"""
from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

GET_CELL = "ec-degraded-4g.get-mixed-c16"
HOLDER_DOWN = "ec-holder-down-4g.get-mixed-c16"
HEALTHY = "ec-degraded-4g.get-healthy-c16"
MESH_CELL = "ec-degraded-16g-x4.get-mixed-lb-c16"

# cell: (config, traffic, chips) as it was declared
CELLS = {
    GET_CELL: ("ec-degraded-4g", "get-mixed-c16", 1),
    HOLDER_DOWN: ("ec-holder-down-4g", "get-mixed-c16", 1),
    HEALTHY: ("ec-degraded-4g", "get-healthy-c16", 1),
    MESH_CELL: ("ec-degraded-16g-x4", "get-mixed-lb-c16", 4),
}
# what the one-chip GET path is read by (PR 23, 24)
GET_READERS = {
    "front_door_get_ms", "batch_queue_wait_ms", "batch_size_mean",
    "device_calls_per_get", "host_route_pct", "reconstruct_roofline",
    "device_idle_pct.get", "get_admit_ms", "get_respond_ms",
    "get_resolve_ms", "get_pack_ms", "get_device_wait_ms", "get_d2h_ms",
    "get_assemble_ms", "idle_attributed_pct.get", "idle_no_request_pct.get",
}
# what tells a reconstruct call of several wanted rows apart (PR 33)
WIDE_READERS = {"wanted_rows_per_call", "reconstruct_rows_computed_ratio"}
# what the four-chip mesh path is read by (PR 26)
MESH_READERS = {"mesh_reconstruct_roofline", "mesh_d2h_wire_ratio",
                "mesh_lane_imbalance_pct", "mesh_pack_ms", "mesh_fetch_ms",
                "large_row_interval_pct"}


def load_json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, *parts), encoding="utf-8") as f:
        return json.load(f)


def load(root: str) -> dict:
    return load_json(root, "BENCHMARK.json")


def reader(root: str, name: str) -> dict:
    return load_json(root, "benchmark", "layer_metrics", name + ".json")


def entry(bench: dict, group: str, name: str) -> dict:
    return next(e for e in bench[group] if e["name"] == name)


def readers_of(bench: dict, cell: str) -> set[str]:
    # a metric without `workloads` is read in every cell
    return {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])}


def config_of(bench: dict, root: str, cell: str) -> dict:
    decl = entry(bench, "configs", entry(bench, "workloads", cell)["config"])
    return load_json(root, decl["file"])


def check_names_units_and_files(bench: dict, root: str) -> None:
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [c["name"] for c in bench["configs"] + bench["workloads"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in bench["workloads"]]:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved), m["name"]
        # every per-layer metric has a reader of its own
        assert reader(root, m["name"])
    configs = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        cfg = load_json(root, c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            root, "benchmark", "traffic", w["traffic"] + ".json"))
    # every cell reports setup_s, another end-to-end metric and a layer's
    for cell in cells:
        own = [m for m in bench["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert len(own) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])


def check_named_cells(bench: dict, root: str) -> None:
    """Each cell this module names is there as it was declared, and the
    four-chip rule holds: at most half the cells, rounded down, or one."""
    for name, declared in CELLS.items():
        w = entry(bench, "workloads", name)
        assert (w["config"], w["traffic"], w["chips"]) == declared, name
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


def check_get_cells(bench: dict, root: str) -> None:
    rate = entry(bench, "end_to_end", "degraded_get_rate")
    assert {GET_CELL, HOLDER_DOWN, HEALTHY} <= set(rate["workloads"])
    of_get = readers_of(bench, GET_CELL)
    assert GET_READERS <= of_get
    # the holder-down cell runs the GET cell's path and loses more
    assert of_get | WIDE_READERS <= readers_of(bench, HOLDER_DOWN)
    # the healthy cell's device does a few milliseconds of work a window
    assert of_get - {"reconstruct_roofline"} <= readers_of(bench, HEALTHY)
    for name in WIDE_READERS:
        m = entry(bench, "per_layer", name)
        assert HOLDER_DOWN in m["workloads"]
        assert m["source"] == "program_counter"
        assert m["layer"] == "resident cache and reconstruct"
        assert m["moves"] == "degraded_get_rate"
        assert "ratio" in reader(root, name)
        # a call can want more than one lost row only where a volume has
        # lost two data shards or more
        for cell in m["workloads"]:
            cfg = config_of(bench, root, cell)
            data = cfg["layout"]["data_shards"]
            assert sum(s < data for s in cfg["lost_shards"]) >= 2, (name, cell)


def check_mesh_cell(bench: dict, root: str) -> None:
    assert MESH_READERS <= readers_of(bench, MESH_CELL)
    # a trace of one chip holds no mesh execution: the mesh reducer's
    # readers list only cells on four chips
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        if reader(root, m["name"]).get("reducer") == "mesh":
            assert all(chips[c] == 4 for c in m.get("workloads", chips)), (
                m["name"])


def check_host_spans_readers(bench: dict, root: str) -> None:
    """Every reader that names the `host_spans` reducer names a mode of it
    and stages that the program's spans or a capture hold."""
    from benchmark.reducers import host_spans
    from seaweedfs_tpu.stats import TRACE_STAGES

    # what is in a capture only: pairs, and the bulk pipelines' events
    sections = {"get", "get_queued", "batch_window", "bulk_run"}
    events = {"bulk_read", "bulk_write", "bulk_stage", "bulk_enqueue",
              "bulk_fetch", "bulk_unstack"}
    mine = [(m, reader(root, m["name"])) for m in bench["per_layer"]]
    mine = [(m, r) for m, r in mine if r.get("reducer") == "host_spans"]
    # the filter finds the readers PR 24 added, and any added since
    assert len(mine) >= 10
    for m, r in mine:
        assert r["mode"] in host_spans.MODES
        assert m["source"] == "program_span"
        named = (r.get("spans", []) + r.get("minus", [])
                 + r.get("precedence", []))
        assert named and set(named) <= (
            set(TRACE_STAGES) | sections | events), m["name"]
        if r["mode"] == "idle":
            assert r["open"] in sections


CHECKS = (check_names_units_and_files, check_named_cells, check_get_cells,
          check_mesh_cell, check_host_spans_readers)
