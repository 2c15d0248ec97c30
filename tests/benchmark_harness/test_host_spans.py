"""benchmark/reducers/host_spans.py on a synthetic profile, as
test_trace_reduction_on_a_synthetic_trace does for the device side: self
time with nested children, precedence between two threads, `no_request`
against `unspanned`, sections open across the window's edges, a capture
without the program's spans, and the capture found by its place on disk.
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
from benchmark import trace  # noqa: E402
from benchmark.reducers import host_spans  # noqa: E402


def _event(name, start_us, dur_us=0, **stats):
    return SimpleNamespace(name=name, start_ns=start_us * 1e3,
                           duration_ns=dur_us * 1e3,
                           stats=list(stats.items()))


def _profile(host_lines):
    """Two programs on the chip, 0-100 us and 900-1000 us: an 800 us
    idle stretch inside a 1000 us window."""
    modules = SimpleNamespace(name="XLA Modules", events=[
        _event("jit_reconstruct(11)", 0, 100),
        _event("jit_reconstruct(11)", 900, 100)])
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[
            SimpleNamespace(name="python", events=events)
            for events in host_lines]),
        SimpleNamespace(name="/device:TPU:0", lines=[modules]),
    ])


LOOP = [
    _event("get:begin", 50, id=1), _event("get:begin", 60, id=2),
    _event("get_admit", 100, 50),
    _event("batch_dispatch:begin", 150, id=7),
    # the loop resolves while the worker already packs the next batch
    _event("batch_resolve", 560, 40),
    _event("batch_dispatch:end", 555, id=7),
    _event("get:end", 700, id=1), _event("get:end", 700, id=2),
    # a request that began before the capture: only its end is there
    _event("get:end", 20, id=99),
    # and one still open when it stopped
    _event("get:begin", 850, id=3),
    _event("$server.py:12 handler", 0, 1000),
]
WORKER = [
    _event("device_execute", 200, 300),
    _event("h2d_copy", 210, 20), _event("d2h_copy", 400, 80),
    _event("PjitFunction(_fused)", 230, 50),  # JAX's own: no child of ours
    _event("batch_pack", 550, 100),
]
IDLE = {"reducer": "host_spans", "mode": "idle",
        "precedence": ["d2h_copy", "batch_pack", "device_execute",
                       "batch_resolve", "get_admit", "batch_dispatch"],
        "open": "get", "closed_label": "no_request"}


def _read(lines):
    profile = _profile(lines)
    return host_spans.read_host(profile), trace.read(profile)


def test_self_time_takes_the_children_of_the_same_thread_out():
    host, tr = _read([LOOP, WORKER])
    facts = {"gets": 2}

    def ms(spans, minus=()):
        return host_spans.self_ms_per(host, tr, facts, {
            "spans": spans, "minus": list(minus), "per": "gets"})

    # 300 us less the two copies nested in it, over two GETs
    assert ms(["device_execute"], ["h2d_copy", "d2h_copy"]) == pytest.approx(
        (300 - 20 - 80) / 2 / 1e3)
    assert ms(["device_execute"]) == pytest.approx(0.150)
    assert ms(["d2h_copy"]) == pytest.approx(0.040)
    # a child of another thread takes nothing out
    assert ms(["get_admit"], ["device_execute"]) == pytest.approx(0.025)
    # a begin/end pair counts whole: 150 -> 555
    assert ms(["batch_dispatch"]) == pytest.approx(0.405 / 2)
    # nothing of that name, no such fact: nothing, never zero
    assert ms(["needle_assemble"]) is None
    assert host_spans.self_ms_per(host, tr, {}, {"spans": ["d2h_copy"]}) is None


def test_self_time_is_cut_to_the_device_window():
    late = [_event("needle_assemble", 950, 200)]  # window ends at 1000
    host, tr = _read([late])
    got = host_spans.self_ms_per(host, tr, {"gets": 1},
                                 {"spans": ["needle_assemble"]})
    assert got == pytest.approx(0.050)


def test_idle_labels_by_precedence_across_threads():
    host, tr = _read([LOOP, WORKER])
    table = dict(host_spans.idle_table(host, tr, IDLE))
    us = {k: round(v * 1e6, 3) for k, v in table.items()}
    assert us == {
        "d2h_copy": 80,            # 400-480
        "batch_pack": 100,         # 550-650, over the loop's resolve
        "device_execute": 220,     # 200-500 less the d2h inside it
        "batch_resolve": 0,        # 560-600 lies under batch_pack
        "get_admit": 50,           # 100-150
        "batch_dispatch": 100,     # 150-200 and 500-550: a batch in
                                   # flight, the worker in no stage
        "no_request": 150,         # 700-850: no GET in the server
        "unspanned": 100,          # 650-700 and 850-900: a GET open,
                                   # nothing named
    }
    assert sum(us.values()) == 800
    attributed = host_spans.idle(host, tr, {}, {
        **IDLE, "label": "unspanned", "complement": True})
    assert attributed == pytest.approx(100 - 100 * 100 / 800)
    no_request = host_spans.idle(host, tr, {}, {**IDLE, "label": "no_request"})
    assert no_request == pytest.approx(100 * 150 / 800)


def test_a_section_open_across_an_edge_reaches_to_that_edge():
    host, tr = _read([LOOP])
    window = (0.0, 1000e-6)
    spans = sorted(host.sections("get", window))
    assert spans[0] == (0.0, pytest.approx(20e-6))       # id 99: end only
    assert spans[-1] == (pytest.approx(850e-6), 1000e-6)  # id 3: begin only
    assert len(spans) == 4


def test_a_capture_without_the_programs_spans_gives_nothing():
    # the parent's capture: JAX's Python tracer and nothing of ours
    python_only = [[_event("$server.py:12 handler", 0, 1000),
                    _event("PjitFunction(_fused)", 230, 50)]]
    host, tr = _read(python_only)
    assert host_spans.idle(host, tr, {}, {**IDLE, "label": "unspanned"}) is None
    assert host_spans.self_ms_per(
        host, tr, {"gets": 5}, {"spans": ["get_admit"]}) is None
    # no host plane at all, and no device plane
    device_only = SimpleNamespace(planes=_profile([]).planes[1:])
    host = host_spans.read_host(device_only)
    assert host_spans.idle_table(host, trace.read(device_only), IDLE) is None
    host, _ = _read([LOOP, WORKER])
    no_device = trace.read(SimpleNamespace(planes=_profile([LOOP]).planes[:1]))
    assert host_spans.idle_table(host, no_device, IDLE) is None


def test_the_capture_is_found_by_its_place_and_held_to_the_trace(
        tmp_path, monkeypatch):
    monkeypatch.setattr(host_spans.tempfile, "tempdir", str(tmp_path))
    _, tr = _read([LOOP, WORKER])
    # no capture on disk: every metric of the reducer is left out
    assert host_spans.reduce(tr, {"gets": 2}, {
        "mode": "self_ms_per", "spans": ["d2h_copy"]}, {}) is None
    old, new = (tmp_path / f"swfs_bench_x/tmp/swfs_device_profiles/capture_{i}"
                / "plugins/profile/2026_10_01" for i in "ab")
    for d in (old, new):
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
    os.utime(old / "vm.xplane.pb", (1, 1))
    assert host_spans.newest_capture() == str(new / "vm.xplane.pb")
    # parsed once a process; a file of another capture (another number of
    # programs on the chip) is not read against this trace
    host, _ = _read([LOOP, WORKER])
    monkeypatch.setitem(host_spans._PARSED, str(new / "vm.xplane.pb"), host)
    params = {"mode": "self_ms_per", "spans": ["d2h_copy"]}
    assert host_spans.reduce(tr, {"gets": 2}, params, {}) == pytest.approx(0.04)
    host.programs["/device:TPU:0"] += 1
    assert host_spans.reduce(tr, {"gets": 2}, params, {}) is None


def test_every_reader_of_this_reducer_names_a_mode_and_known_stages():
    declarations.check_host_spans_readers(declarations.load(REPO), REPO)
