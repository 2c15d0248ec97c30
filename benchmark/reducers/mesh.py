"""Readers of a capture in which one program runs across several chips
(the lane-sharded serving mesh: `_sharded_gather_reconstruct` is one
shard_map program, so every execution appears once on every device
plane, at the same time).

reducers/roofline.py sums the seconds of all planes and multiplies the
peak by the number of chips, which reads a quarter of the true share on
four chips.  Here an execution's time is counted once.  Two modes,
chosen by the metric's JSON:

  {"mode": "roofline", "programs": [...], "work": <function of
   benchmark/work_counts.py>, "work_args": [facts]}
      the least time the mesh could take for the window's work (its
      bytes over the HBM peak of all chips, as if spread evenly) over
      the seconds the matching programs held the mesh: the k-th
      execution on every plane is the same execution, and its time is
      the longest plane's (a lane with more requests runs longer, the
      others wait for it at the next call).  Where the planes hold
      different numbers of executions they cannot be paired, and the
      busiest plane's sum stands for all.
  {"mode": "lane_imbalance"}
      the busiest chip's busy seconds over the mean of all chips, in
      percent above the mean: 0 is an even mesh.

  {"mode": "span_ms", "spans": [...], "per": "gets"}
      reducers/host_spans.py's `self_ms_per` over the mesh path's own
      stages (`mesh_pack`, `mesh_fetch`), reached through this file
      because tests/benchmark_harness/test_host_spans.py holds the
      number of metrics that name `host_spans` at ten and no accepted
      file may be edited here.

A capture of one chip, or one without the programs or the spans, gives
nothing.
"""
from __future__ import annotations

import re

from .. import trace as trace_mod
from .. import work_counts
from . import host_spans


def per_plane(trace, patterns: list[str]) -> list[list[float]]:
    """Durations of the matching programs on each device plane, in the
    order they started."""
    regs = [re.compile(p) for p in patterns]
    return [[dur for name, _, dur in events
             if any(r.search(name) for r in regs)]
            for events in trace.modules.values()]


def mesh_seconds(planes: list[list[float]]) -> float:
    if len({len(p) for p in planes}) == 1:
        return sum(max(durs) for durs in zip(*planes))
    return max(sum(p) for p in planes)


def roofline(trace, facts: dict, params: dict, chip: dict) -> float | None:
    if any(a not in facts for a in params["work_args"]):
        return None
    planes = per_plane(trace, params["programs"])
    if len(planes) < 2 or not any(planes):
        return None
    moved = getattr(work_counts, params["work"])(
        *(facts[a] for a in params["work_args"]))
    return work_counts.roofline_pct(
        moved, mesh_seconds(planes), chip["hbm_bytes_per_s"] * len(planes))


def lane_imbalance(trace, facts: dict, params: dict,
                   chip: dict) -> float | None:
    busy = [trace_mod.union_seconds(trace.ops[name] or trace.modules[name])
            for name in trace.modules]
    if len(busy) < 2 or not sum(busy):
        return None
    return 100.0 * (max(busy) * len(busy) / sum(busy) - 1.0)


def span_ms(trace, facts: dict, params: dict, chip: dict) -> float | None:
    host = host_spans.load_host(trace)
    if host is None:
        return None
    return host_spans.self_ms_per(host, trace, facts, params)


MODES = {"roofline": roofline, "lane_imbalance": lane_imbalance,
         "span_ms": span_ms}


def reduce(trace, facts: dict, params: dict, chip: dict) -> float | None:
    return MODES[params["mode"]](trace, facts, params, chip)
