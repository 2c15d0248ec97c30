"""What the host was doing while the device idled: the program's own
spans, read from the host plane of the capture the run just took.

The server writes every span of obs/trace.py into the profiler's capture
while one is live (seaweedfs_tpu/obs/profile.py): a section with no
`await` inside as one event named for its stage on the line of the
thread that ran it; a section that spans awaits as two instant events
`<name>:begin` and `<name>:end` whose stat `id` joins them.  They are on
`/host:CPU` of the same `.xplane.pb` as the device's programs, so on
the same clock.

benchmark/trace.py `load()` keeps the device planes only, so this
reducer finds the file itself: the newest `*.xplane.pb` under
`<tempfile.gettempdir()>/swfs_bench_*/tmp/swfs_device_profiles/` (the
run's scratch directory still exists when reducers run), parsed once a
process, and used only if its device plane holds as many programs as
the `DeviceTrace` it is handed (same file, same zero).  A capture of a
program without these spans, or no file, gives nothing.

The window is the first device program's start to the last one's end
on the fullest chip.  Two modes, chosen by the metric's JSON:

  {"mode": "self_ms_per", "spans": [...], "minus": [...], "per": "gets"}
      the spans' self time inside the window, summed, over the fact
      `per`, in milliseconds.  Self time of an event is its length minus
      what events named in `minus` cover of it on the same line (its
      children: events nest per thread); a begin/end pair has no thread
      of its own and counts whole.
  {"mode": "idle", "precedence": [...], "open": "get",
   "closed_label": "no_request", "label": "unspanned", "complement": true}
      every instant of the window at which no program runs on the chip
      gets one label: the first name of `precedence` open on any
      thread; else `closed_label` if no `open` section is open; else
      "unspanned".  Returns `label`'s share of the idle seconds in
      percent (100 minus it with `complement`) and writes the whole
      table to standard error, once a process.
"""
from __future__ import annotations

import glob
import os
import sys
import tempfile
from dataclasses import dataclass, field

from ..trace import DEVICE_PLANE, MODULES_LINE

HOST_PLANE = "/host:CPU"
UNSPANNED = "unspanned"

Interval = tuple[float, float]


@dataclass
class Host:
    # stage -> [(line index, start_s, end_s)]
    events: dict[str, list[tuple[int, float, float]]] = field(
        default_factory=dict)
    # section -> {"begin": {id: t}, "end": {id: t}}
    marks: dict[str, dict[str, dict]] = field(default_factory=dict)
    # per device plane, the number of events on its "XLA Modules" line
    programs: dict[str, int] = field(default_factory=dict)

    def sections(self, name: str, window: Interval) -> list[Interval]:
        """The begin/end pairs of `name`; one whose other end lies
        outside the capture is open to that side of the window."""
        marks = self.marks.get(name, {"begin": {}, "end": {}})
        out = [(t, marks["end"].get(i, window[1]))
               for i, t in marks["begin"].items()]
        out += [(window[0], t) for i, t in marks["end"].items()
                if i not in marks["begin"]]
        return out

    def intervals(self, name: str, window: Interval) -> list[Interval]:
        return ([(s, e) for _, s, e in self.events.get(name, [])]
                + self.sections(name, window))


def read_host(profile) -> Host:
    host = Host()
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    host.programs[plane.name] = sum(1 for _ in line.events)
        if plane.name != HOST_PLANE:
            continue
        for index, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                if name.startswith("$"):
                    continue  # JAX's Python tracer, where it is still on
                start = e.start_ns * 1e-9
                stem, _, side = name.rpartition(":")
                if side in ("begin", "end") and stem:
                    pair = dict(e.stats).get("id")
                    if pair is not None:
                        host.marks.setdefault(
                            stem, {"begin": {}, "end": {}})[side][pair] = start
                        continue
                host.events.setdefault(name, []).append(
                    (index, start, start + e.duration_ns * 1e-9))
    return host


# ----------------------------------------------------- interval arithmetic


def union(intervals: list[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def clip(intervals: list[Interval], window: Interval) -> list[Interval]:
    return [(max(s, window[0]), min(e, window[1])) for s, e in intervals
            if min(e, window[1]) > max(s, window[0])]


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """`a` minus `b`, both sorted and disjoint."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > start:
                out.append((start, b[k][0]))
            start = max(start, b[k][1])
            k += 1
        if start < end:
            out.append((start, end))
    return out


def seconds(intervals: list[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def device_window(trace) -> tuple[Interval, list[Interval]] | None:
    """(window, busy intervals) of the fullest chip's programs."""
    if not trace.modules:
        return None
    events = max(trace.modules.values(), key=len)
    if not events:
        return None
    busy = union([(s, s + d) for _, s, d in events])
    return (busy[0][0], busy[-1][1]), busy


# ------------------------------------------------------------- the modes


def self_ms_per(host: Host, trace, facts: dict, params: dict) -> float | None:
    found = device_window(trace)
    per = facts.get(params.get("per", "gets"))
    if found is None or not per:
        return None
    window, _ = found
    names = params["spans"]
    if not any(n in host.events or n in host.marks for n in names):
        return None
    children: dict[int, list[Interval]] = {}
    for child in params.get("minus", []):
        for line, start, end in host.events.get(child, []):
            children.setdefault(line, []).append((start, end))
    children = {line: union(ivs) for line, ivs in children.items()}
    total = 0.0
    for name in names:
        for line, start, end in host.events.get(name, []):
            own = clip([(start, end)], window)
            total += seconds(subtract(own, children.get(line, [])))
        total += seconds(clip(host.sections(name, window), window))
    return 1e3 * total / per


_TABLES_SHOWN: set = set()


def idle_table(host: Host, trace, params: dict) -> list[tuple] | None:
    """[(label, seconds)] over the window's idle seconds, or None where
    the capture holds none of the spans asked for."""
    found = device_window(trace)
    if found is None:
        return None
    window, busy = found
    names = list(params["precedence"]) + [params["open"]]
    if not any(n in host.events or n in host.marks for n in names):
        return None
    left = subtract([window], busy)
    table = []
    for name in params["precedence"]:
        held = union(clip(host.intervals(name, window), window))
        rest = subtract(left, held)
        table.append((name, seconds(left) - seconds(rest)))
        left = rest
    asked = union(clip(host.intervals(params["open"], window), window))
    rest = subtract(left, subtract([window], asked))
    table.append((params["closed_label"], seconds(left) - seconds(rest)))
    table.append((UNSPANNED, seconds(rest)))
    return table


def idle(host: Host, trace, facts: dict, params: dict) -> float | None:
    table = idle_table(host, trace, params)
    if table is None:
        return None
    total = sum(s for _, s in table)
    if total <= 0:
        return None
    key = (tuple(params["precedence"]), params["open"])
    if key not in _TABLES_SHOWN:
        _TABLES_SHOWN.add(key)
        window, busy = device_window(trace)
        sys.stderr.write(
            f"host_spans idle table: window {window[1] - window[0]:.3f} s, "
            f"device busy {seconds(busy):.3f} s, idle {total:.3f} s\n")
        for label, s in table:
            sys.stderr.write(
                f"  idle {label:18s} {s:10.4f} s {100 * s / total:6.2f} %\n")
    share = 100.0 * dict(table)[params["label"]] / total
    return 100.0 - share if params.get("complement") else share


MODES = {"self_ms_per": self_ms_per, "idle": idle}

# ------------------------------------------------------- finding the file

_PARSED: dict[str, Host] = {}


def newest_capture() -> str | None:
    files = glob.glob(os.path.join(
        tempfile.gettempdir(), "swfs_bench_*", "tmp", "swfs_device_profiles",
        "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load_host(trace) -> Host | None:
    path = newest_capture()
    if path is None:
        return None
    if path not in _PARSED:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the reader only, as trace.load
        from jax.profiler import ProfileData

        _PARSED[path] = read_host(ProfileData.from_file(path))
    host = _PARSED[path]
    same = {name: len(events) for name, events in trace.modules.items()}
    return host if same == host.programs else None


def reduce(trace, facts: dict, params: dict, chip: dict) -> float | None:
    host = load_host(trace)
    if host is None:
        return None
    return MODES[params["mode"]](host, trace, facts, params)
