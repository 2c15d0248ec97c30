"""The share of the window in which no operation ran on the device: 1
minus the union of the device's operation intervals over the window's
length, in percent, averaged over the chips that ran anything."""
from __future__ import annotations

from .. import trace as trace_mod


def reduce(trace, facts: dict, params: dict, chip: dict) -> float | None:
    busy = trace_mod.busy_seconds(trace)
    if busy is None or not facts.get("window_s"):
        return None
    return 100.0 * (1.0 - busy / facts["window_s"])
