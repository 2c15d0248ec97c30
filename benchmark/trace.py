"""From a profiler trace (.xplane.pb) to device busy time, per-program
and per-operation sums, and the longest idle gaps.

Works on anything shaped like jax.profiler.ProfileData: planes with a
`name` and `lines`, lines with a `name` and `events`, events with a
`name`, `start_ns` and `duration_ns`.  Device planes are the ones named
"/device:TPU:<n>"; on such a plane the line "XLA Modules" holds one event
per executed program (named "jit_<function>(<fingerprint>)") and the line
"XLA Ops" one per HLO operation inside it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


@dataclass
class DeviceTrace:
    # per device plane: [(name, start_s, duration_s)]
    modules: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    ops: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)

    @property
    def chips(self) -> int:
        return len(self.modules)


def program_name(event_name: str) -> str:
    """"jit_foo(1234567)" -> "jit_foo": fingerprints change with shapes."""
    return event_name.split("(", 1)[0]


def read(profile) -> DeviceTrace:
    out = DeviceTrace()
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = sorted(
                ((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                 for e in line.events),
                key=lambda e: e[1])
            target = out.modules if line.name == MODULES_LINE else out.ops
            target[plane.name] = events
    for name in out.modules:
        out.ops.setdefault(name, [])
    return out


def describe(profile, path: str) -> None:
    """Every plane and line of a trace with its event count and its most
    frequent event names: what a builder looks at by hand first."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for plane in profile.planes:
            f.write(f"plane {plane.name!r}\n")
            for line in plane.lines:
                events = list(line.events)
                names: dict[str, int] = {}
                for e in events:
                    names[e.name] = names.get(e.name, 0) + 1
                first = min((e.start_ns for e in events), default=0)
                f.write(f"  line {line.name!r}: {len(events)} events, "
                        f"first start_ns {first}\n")
                for name, n in sorted(names.items(), key=lambda kv: -kv[1])[:8]:
                    f.write(f"    {n:8d} x {name[:150]}\n")


def load(trace_dir: str, describe_to: str = "") -> DeviceTrace:
    """Read the one .xplane.pb under a capture directory.  Imports JAX
    for its reader only, held to the CPU: the harness never takes the
    chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {files}")
    profile = ProfileData.from_file(files[0])
    if describe_to:
        describe(profile, describe_to)
        with open(describe_to, "a", encoding="utf-8") as f:
            f.write(f"file {files[0]}: {os.path.getsize(files[0])} bytes\n")
    return read(profile)


def union_seconds(events: list[tuple[str, float, float]]) -> float:
    """Seconds covered by at least one of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_seconds(trace: DeviceTrace) -> float | None:
    """Seconds in which an operation ran on the device, averaged over the
    chips that ran any.  None where no device plane was traced."""
    per_chip = [
        union_seconds(trace.ops[name] or trace.modules[name])
        for name in trace.modules
    ]
    per_chip = [s for s in per_chip if s > 0]
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip)


def top_programs(trace: DeviceTrace, limit: int = 10) -> list[list]:
    sums: dict[str, float] = {}
    for events in trace.modules.values():
        for name, _, dur in events:
            key = program_name(name)
            sums[key] = sums.get(key, 0.0) + dur
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(trace: DeviceTrace, limit: int = 10) -> list[list]:
    """Idle seconds between consecutive programs of the fullest chip,
    summed by the program that ended each gap: all that can be said of a
    gap while the host's spans are not on the profiler's clock."""
    if not trace.modules:
        return []
    events = max(trace.modules.values(), key=len)
    sums: dict[str, float] = {}
    end = None
    for name, start, dur in events:
        if end is not None and start > end:
            key = "before " + program_name(name)
            sums[key] = sums.get(key, 0.0) + (start - end)
        end = max(end or 0.0, start + dur)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, seconds] for name, seconds in ranked]


def dump(trace: DeviceTrace, path: str) -> None:
    """What a builder reads by hand: per plane and line, the programs and
    operations with their counts and summed seconds."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for kind, planes in (("modules", trace.modules), ("ops", trace.ops)):
            for plane, events in planes.items():
                sums: dict[str, list] = {}
                for name, _, dur in events:
                    rec = sums.setdefault(name, [0, 0.0])
                    rec[0] += 1
                    rec[1] += dur
                f.write(f"== {plane} {kind}: {len(events)} events, union "
                        f"{union_seconds(events):.6f} s\n")
                for name, (n, s) in sorted(
                        sums.items(), key=lambda kv: -kv[1][1])[:60]:
                    f.write(f"{s:12.6f} s {n:8d} x  {name}\n")
