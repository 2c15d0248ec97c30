"""Per-layer metrics, each read by the small reader its own file names.

benchmark/layer_metrics/<name>.json is one of

  {"ratio": {"num": [term...], "den": [term...], "scale": 1000}}
      the window's delta of the numerator's sum over the denominator's,
      times `scale`.  A term is {"series": <name without the
      SeaweedFS_volumeServer_ prefix>, "labels": {...}, "not_labels":
      {...}} (every matching sample summed) or {"fact": <a number the
      generator reports about its window>}.
  {"reducer": "<module of benchmark/reducers/>", ...its parameters}
      a reduction of the profiler trace.

A reader that finds nothing to read (a zero denominator, no trace, no
matching program) returns None and the metric is left out of the line.
"""
from __future__ import annotations

import importlib
import json
import math
import os

from . import peaks
from .cluster import series_sum

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reader(name: str) -> dict:
    path = os.path.join(HERE, "layer_metrics", name + ".json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def term_sum(terms: list[dict], before: dict, after: dict,
             facts: dict) -> float | None:
    total = 0.0
    for term in terms:
        if "fact" in term:
            if term["fact"] not in facts:
                return None
            total += facts[term["fact"]]
        else:
            args = (term["series"], term.get("labels"),
                    term.get("not_labels"))
            total += series_sum(after, *args) - series_sum(before, *args)
    return total


def ratio(spec: dict, before: dict, after: dict, facts: dict) -> float | None:
    num = term_sum(spec["num"], before, after, facts)
    den = term_sum(spec["den"], before, after, facts)
    if num is None or not den:
        return None
    return spec.get("scale", 1.0) * num / den


def layer_values(specs: list[dict], before: dict, after: dict, facts: dict,
                 trace, device_kind: str) -> dict:
    """{metric name: value or None}.  `trace` is None off the chip: no
    trace reducer runs there, and no table of peaks is consulted."""
    chip = peaks.lookup(device_kind) if trace is not None else None
    values = {}
    for spec in specs:
        reader = load_reader(spec["name"])
        if "ratio" in reader:
            value = ratio(reader["ratio"], before, after, facts)
        elif trace is None:
            value = None
        else:
            module = importlib.import_module(
                f"benchmark.reducers.{reader['reducer']}")
            value = module.reduce(trace, facts, reader, chip)
        values[spec["name"]] = value
    return values


def device_bytes_held(dev: dict, before: dict, after: dict) -> int:
    """A lower bound of the fullest chip's peak memory, from what the
    program itself reports: the bytes its shard cache holds on the
    fullest device plus the mean bytes one dispatch of the window moved
    across the device boundary.  The allocator's own peak
    (memory_stats) is not exposed by the server (PERF.md, open
    questions)."""
    resident = max(
        (d["used_bytes"] for d in dev.get("cache", {}).get("per_device", [])),
        default=0)
    moved = (series_sum(after, "device_dispatch_bytes_total", None,
                        {"device": "host"})
             - series_sum(before, "device_dispatch_bytes_total", None,
                          {"device": "host"}))
    calls = (series_sum(after, "device_dispatches_total", None,
                        {"device": "host"})
             - series_sum(before, "device_dispatches_total", None,
                          {"device": "host"}))
    return int(resident + (math.ceil(moved / calls) if calls else 0))
