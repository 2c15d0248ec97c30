"""A program's share of its roofline: the least time the chip could take
for the work the window asked for, over the summed device time of the
programs that did it.

Parameters: "programs" (regular expressions over the names on the trace's
"XLA Modules" line), "work" (a function of benchmark/work_counts.py) and
"work_args" (the generator's facts it is called with).  Where two verbs
run the same program ("verb": the bulk pipelines' encode and rebuild both
run apply_matrix_device_flat), the generator's fact "verb_batches" gives
the window's device batches in the order they ran, and the matching
executions are dealt out to the verbs in that order; a trace that holds
another number of them than the server counted cannot be attributed and
gives nothing.  The bound is memory traffic over the HBM peak.
"""
from __future__ import annotations

import re

from .. import work_counts


def matching(trace, patterns: list[str]) -> list[tuple[str, float, float]]:
    regs = [re.compile(p) for p in patterns]
    return sorted(
        (e for events in trace.modules.values() for e in events
         if any(r.search(e[0]) for r in regs)),
        key=lambda e: e[1])


def reduce(trace, facts: dict, params: dict, chip: dict) -> float | None:
    if any(a not in facts for a in params["work_args"]):
        return None
    moved = getattr(work_counts, params["work"])(
        *(facts[a] for a in params["work_args"]))
    events = matching(trace, params["programs"])
    if "verb" in params:
        order = facts.get("verb_batches", [])
        if sum(n for _, n in order) != len(events):
            return None
        mine, at = [], 0
        for verb, n in order:
            if verb == params["verb"]:
                mine += events[at:at + n]
            at += n
        events = mine
    seconds = sum(dur for _, _, dur in events)
    return work_counts.roofline_pct(
        moved, seconds, chip["hbm_bytes_per_s"] * trace.chips)
