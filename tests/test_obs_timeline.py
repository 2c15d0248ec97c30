"""The program's spans on the profiler's clock (obs/trace.py TIMELINE,
obs/profile.py): off means off and imports no JAX; in one real CPU
capture of /debug/profile a sync span, a span under a stage sink, an
await-spanning span and a timeline-only interval each appear once, on
the right thread, nested as the code nests them, and JAX's Python tracer
writes nothing; the bulk codec leg's four parts sum to the leg; /status
reports the allocator's bytes where the backend has them."""
import ast
import asyncio
import dis
import glob
import os
import subprocess
import sys
import tempfile
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from seaweedfs_tpu import obs
from seaweedfs_tpu.obs import profile as obs_profile
from seaweedfs_tpu.obs import trace as obs_trace
from seaweedfs_tpu.stats import metrics
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.ec.layout import to_ext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ------------------------------------------------------------ off is off


def test_span_without_a_capture_imports_no_jax_and_opens_nothing():
    code = (
        "import sys\n"
        "from seaweedfs_tpu.obs import trace\n"
        "assert trace.TIMELINE is None\n"
        "with trace.span('batch_pack', requests=3) as s:\n"
        "    with trace.await_span('batch_dispatch') as a:\n"
        "        with trace.interval('get') as i:\n"
        "            pass\n"
        "assert s._close is None and a._close is None and i._close is None\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def _branches_on(fn, names) -> int:
    """Conditional jumps of `fn` that test one of `names` (a local or an
    attribute) straight after loading it."""
    ins = list(dis.get_instructions(fn))
    return sum(
        cur.opname.startswith("POP_JUMP_IF") and prev.argval in names
        for prev, cur in zip(ins, ins[1:]))


@pytest.mark.parametrize("cls", [obs_trace.span, obs_trace.await_span,
                                 obs_trace.interval, obs_trace.event])
def test_the_timeline_costs_one_branch_on_entry_and_one_on_exit(cls):
    # `hook = TIMELINE`, then one test of it; `self._close`, one test
    assert _branches_on(cls.__enter__, {"hook"}) == 1
    assert _branches_on(cls.__exit__, {"_close"}) == 1
    # and nothing else of the module looks at the hook
    for fn in (obs_trace.record_span, obs_trace.start_trace,
               obs_trace.finish_trace):
        assert "TIMELINE" not in {i.argval for i in dis.get_instructions(fn)}


def test_no_plain_span_or_event_has_an_await_inside():
    """Events nest per thread: a block that awaits lets other work onto
    its thread, so it has to be an await_span or an interval (a
    begin/end pair)."""
    bad = []
    pkg = os.path.join(REPO, "seaweedfs_tpu")
    for root, _, files in os.walk(pkg):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not isinstance(node, (ast.With, ast.AsyncWith)):
                    continue
                for item in node.items:
                    call = item.context_expr
                    if not (isinstance(call, ast.Call) and isinstance(
                            call.func, (ast.Attribute, ast.Name))):
                        continue
                    fn = getattr(call.func, "attr", None) or call.func.id
                    if fn not in ("span", "event"):
                        continue
                    if any(isinstance(n, (ast.Await, ast.AsyncWith,
                                          ast.AsyncFor))
                           for stmt in node.body for n in ast.walk(stmt)):
                        bad.append(f"{path}:{node.lineno}")
    assert not bad, bad


# ----------------------------------------------------- one real capture


async def _one_round(i: int) -> None:
    """What a GET's way through the server looks like to the timeline:
    an interval around everything, a sync span on the loop under the
    request's trace, and in the drain lane's detached context an
    await-spanning span around a worker that nests sync spans under a
    stage sink, then the sink's replay onto the member traces."""

    def worker():
        with obs.span("device_execute", it=i):
            with obs.span("d2h_copy", it=i, bytes=4096):
                pass
        with obs.span("batch_pack", it=i):
            pass

    # (None, None) in the rounds that run as under -obs.disable
    trace, token = obs.start_trace(f"GET /1,{i:02x}", "volume")
    with obs.interval("get", it=i):
        with obs.span("get_admit", it=i):
            pass
        member = obs.current()
        with obs.detached():
            with obs.stage_sink() as sink:
                with obs.await_span("batch_dispatch", it=i):
                    await asyncio.to_thread(worker)
            assert set(sink) == {"device_execute", "d2h_copy", "batch_pack",
                                 "batch_dispatch"}
            with obs.span("batch_resolve", it=i):
                for ctx in (member, member):  # a batch of two
                    for stage, (dur, calls, ann) in sink.items():
                        obs.record_span(ctx, stage, 0.0, dur, observe=False,
                                        annotations={"calls": calls, **ann})
    obs.finish_trace(trace, token, 200)
    if trace is not None:
        # the ring's view: the stage replayed onto both members
        names = [sp.name for sp in trace.spans]
        assert names.count("batch_pack") == 2 and "get_admit" in names


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One bounded capture of /debug/profile on the CPU, with rounds of
    spans running for its whole length -> the parsed host lines:
    [[(name, start_ns, end_ns, {stat: value})]] one list per thread."""
    tmp = tmp_path_factory.mktemp("capture")
    old_tmp = tempfile.tempdir
    tempfile.tempdir = str(tmp)
    was_enabled = obs_trace.CONFIG.enabled
    rounds = 0

    async def run():
        nonlocal rounds
        request = SimpleNamespace(query={"seconds": "0.6"})
        handler = asyncio.ensure_future(obs_profile.profile_handler(request))
        while not handler.done():
            # every second round as `-obs.disable` has it
            obs_trace.CONFIG.enabled = rounds % 2 == 0
            await _one_round(rounds)
            rounds += 1
            await asyncio.sleep(0.005)
        assert obs_trace.TIMELINE is None  # cleared after stop_trace
        return await handler

    try:
        resp = asyncio.new_event_loop().run_until_complete(run())
    finally:
        tempfile.tempdir = old_tmp
        obs_trace.CONFIG.enabled = was_enabled
    assert resp.status == 200
    files = glob.glob(os.path.join(
        str(tmp), "swfs_device_profiles", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    assert len(files) == 1
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(files[0])
    lines = []
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            lines.append([
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events])
    return SimpleNamespace(lines=lines, rounds=rounds)


def _events(capture, name, it=None):
    return [(li, ev) for li, line in enumerate(capture.lines) for ev in line
            if ev[0] == name and (it is None or ev[3].get("it") == it)]


def _whole_rounds(capture) -> list[int]:
    """Rounds that began and ended inside the capture."""
    ends = {ev[3]["id"] for _, ev in _events(capture, "get:end")}
    return sorted(ev[3]["it"] for _, ev in _events(capture, "get:begin")
                  if ev[3]["id"] in ends)


def test_capture_holds_each_span_once_on_its_thread(capture):
    whole = _whole_rounds(capture)
    assert len(whole) >= 3, (capture.rounds, whole)
    # a round with tracing on and one as under -obs.disable
    for it in (whole[1], whole[2]):
        (loop_line, admit), = _events(capture, "get_admit", it)
        (l2, resolve), = _events(capture, "batch_resolve", it)
        assert l2 == loop_line
        (worker_line, execute), = _events(capture, "device_execute", it)
        (l3, d2h), = _events(capture, "d2h_copy", it)
        # the sink's replay onto two member traces added no event
        (l4, pack), = _events(capture, "batch_pack", it)
        assert worker_line == l3 == l4 and worker_line != loop_line
        # nesting as the code nests: d2h inside execute, pack after it
        assert execute[1] <= d2h[1] and d2h[2] <= execute[2]
        assert pack[1] >= execute[2]
        assert d2h[3]["bytes"] == 4096
        # the await-spanning span: one begin, one end, same id, on the
        # loop's thread, around the worker's events
        (l5, begin), = _events(capture, "batch_dispatch:begin", it)
        ends = [ev for _, ev in _events(capture, "batch_dispatch:end")
                if ev[3]["id"] == begin[3]["id"]]
        assert l5 == loop_line and len(ends) == 1
        assert admit[2] <= begin[1] <= execute[1]
        assert pack[2] <= ends[0][1] <= resolve[1]
        assert not _events(capture, "batch_dispatch", it)
        # the interval around the round
        (_, g0), = _events(capture, "get:begin", it)
        g1 = [ev for _, ev in _events(capture, "get:end")
              if ev[3]["id"] == g0[3]["id"]]
        assert len(g1) == 1 and g0[1] <= admit[1] and resolve[2] <= g1[0][1]


def test_capture_holds_no_python_tracer_event(capture):
    """The Python tracer names its events `$<file>:<line> <function>`
    (or `$<module> <builtin>`), one per call: none is there."""
    names = {ev[0] for line in capture.lines for ev in line}
    assert "get_admit" in names
    python_calls = sorted(n for n in names if n.startswith("$"))
    assert not python_calls, python_calls[:5]


# ------------------------------------------------- the codec leg's parts


def _codec_seconds(pipeline: str) -> tuple[float, float]:
    parts = sum(
        metrics.VOLUME_SERVER_EC_BULK_CODEC_SECONDS.labels(
            pipeline=pipeline, part=part)._value.get()
        for part in metrics.EC_BULK_CODEC_PARTS)
    leg = metrics.VOLUME_SERVER_EC_BULK_SECONDS.labels(
        pipeline=pipeline, leg="device")._value.get()
    return parts, leg


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_codec_parts_sum_to_the_device_leg(tmp_path, backend):
    """Rehearsal size: a 12 MiB volume, the backend the benchmark's
    rehearsal runs (Pallas, interpreted) and the plain XLA kernel."""
    base = str(tmp_path / "1")
    payload = np.random.default_rng(11).integers(
        0, 256, size=12 << 20, dtype=np.uint8)
    with open(base + ".dat", "wb") as f:
        f.write(b"\x03" + bytes(7) + payload.tobytes())
    for pipeline, verb in (
        ("encode", lambda: ec.write_ec_files(base, backend=backend)),
        ("rebuild", lambda: ec.rebuild_ec_files(base, backend=backend)),
    ):
        if pipeline == "rebuild":
            for sid in (3, 11):
                os.remove(base + to_ext(sid))
        parts0, leg0 = _codec_seconds(pipeline)
        verb()
        parts1, leg1 = _codec_seconds(pipeline)
        parts, leg = parts1 - parts0, leg1 - leg0
        # the parts lie inside the leg's clock and share their
        # boundaries: what they leave of it is the leg's own few lines
        # (5 %, or at this size a few milliseconds of scheduler noise)
        assert 0 < parts <= leg, (pipeline, parts, leg)
        assert leg - parts <= max(0.05 * leg, 0.005), (pipeline, parts, leg)
    # a CPU codec has no parts: the leg is counted, the parts are not
    parts0, leg0 = _codec_seconds("verify")
    ec.verify_ec_files(base, backend="numpy")
    parts1, leg1 = _codec_seconds("verify")
    assert leg1 > leg0 and parts1 == parts0


def test_bulk_spans_per_batch_and_one_run_interval(tmp_path):
    """On the timeline a pipeline run is one bulk_run pair around
    per-batch bulk_read / bulk_write events on their legs' threads and
    the four codec parts on the codec worker's."""
    seen: list[tuple[str, str, bool]] = []

    def hook(name, annotations, paired):
        seen.append((name, threading.current_thread().name, paired))
        return lambda: seen.append(
            (name + "/end", threading.current_thread().name, paired))

    base = str(tmp_path / "2")
    with open(base + ".dat", "wb") as f:
        f.write(b"\x03" + bytes(7) + bytes(range(256)) * (9 << 12))
    obs_trace.TIMELINE = hook
    try:
        stats: dict = {}
        ec.write_ec_files(base, backend="xla", stats=stats)
    finally:
        obs_trace.TIMELINE = None
    batches = stats["batches"]
    assert batches >= 1
    opened = [s for s in seen if not s[0].endswith("/end")]
    assert len(seen) == 2 * len(opened)
    count = {n: sum(s[0] == n for s in opened) for n, _, _ in opened}
    assert count == {"bulk_run": 1, "bulk_read": batches,
                     "bulk_write": batches, "bulk_stage": batches,
                     "bulk_enqueue": batches, "bulk_fetch": batches,
                     "bulk_unstack": batches}
    assert [p for n, _, p in opened if n == "bulk_run"] == [True]
    assert not any(p for n, _, p in opened if n != "bulk_run")
    # an event closes on the thread that opened it
    assert all((n + "/end", t, p) in seen for n, t, p in opened)
    threads = {n: {t for m, t, _ in opened if m == n} for n in count}
    me = threading.current_thread().name
    assert threads == {
        "bulk_run": {me}, "bulk_read": {"ec-bulk-encode-read"},
        "bulk_write": {"ec-bulk-encode-write"}, "bulk_stage": {"ec-dev_0"},
        "bulk_enqueue": {"ec-dev_0"}, "bulk_fetch": {"ec-dev_0"},
        "bulk_unstack": {"ec-dev_0"}}


# ----------------------------------------------------------- /status


def test_device_status_reports_allocator_bytes_where_there_are_any(
        monkeypatch):
    from seaweedfs_tpu.ops import rs_resident
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    # the CPU's allocator reports nothing: the key is absent
    assert "memory" not in rs_resident.device_status("auto")
    stats = {"bytes_in_use": 5_637_144_576, "peak_bytes_in_use": 5_700_000_000,
             "bytes_limit": 16_909_336_576, "num_allocs": 7}
    fake = [SimpleNamespace(id=0, memory_stats=lambda: stats),
            SimpleNamespace(id=1, memory_stats=lambda: None)]
    monkeypatch.setattr(mesh_mod, "local_devices", lambda: fake)
    assert rs_resident.device_status("auto")["memory"] == [{
        "device": 0, "bytes_in_use": 5_637_144_576,
        "peak_bytes_in_use": 5_700_000_000, "bytes_limit": 16_909_336_576}]
