"""Tail-latency forensics plane (obs/critpath.py + obs/tailstore.py):
tail-based retention under churn, cross-node assembly with clock-skew
reconciliation, client-anchored critical-path attribution, and the
end-to-end degraded read crossing filer -> volume -> remote-shard hops.

Reference: the Dapper trace model in obs/trace.py.
"""
import asyncio
import time

import aiohttp
import pytest

from seaweedfs_tpu import obs, stats
from seaweedfs_tpu.obs import critpath, tailstore
from seaweedfs_tpu.obs import trace as obs_trace


def run(coro):
    return asyncio.run(coro)


def _finish_one(name="GET /1,aabbcc", dur_s=0.0, trace_id=None,
                flag_store=None, flag_kind=None):
    """Finish one root trace with a faked duration (t0 rewound so the
    perf-counter delta IS the duration — finish_trace stamps end)."""
    t, tok = obs.start_trace(name, "volume", "vs1", trace_id=trace_id)
    t.t0 -= dur_s
    if flag_store is not None:
        flag_store.flag(t.trace_id, flag_kind or "qos_shed")
    obs.finish_trace(t, tok, 200)
    return t.trace_id


# ------------------------------------------------------------- retention


def test_tail_ring_retention_under_churn():
    """A pinned slow tree survives hundreds of fast requests: fast
    requests never pass the pin gate, so they can never evict it — and
    the pin's FROZEN entries outlive the main ring's churn too."""
    store = tailstore.TailStore(node="vs1", capacity=8, floor_ms=50.0)
    store.install()
    try:
        slow_id = _finish_one(dur_s=0.2)
        pins = store.snapshot(trace_id=slow_id)
        assert len(pins) == 1 and pins[0]["reason"] == "floor"
        assert pins[0]["entries"], "pin froze no span tree"

        # churn: enough fast roots to wrap the MAIN trace ring many
        # times over — none is slow enough to enter the tail ring
        for _ in range(max(obs_trace.CONFIG.trace_ring, 256) * 2):
            _finish_one(dur_s=0.0)

        assert not obs_trace.RING.snapshot(trace_id=slow_id), (
            "churn was not enough to evict the slow trace from the "
            "main ring — the retention half of this test needs that"
        )
        pins = store.snapshot(trace_id=slow_id)
        assert len(pins) == 1, "fast churn evicted the pinned slow tree"
        assert pins[0]["entries"]
        # the module-level resolver (what /debug/traces?id= falls back
        # to) and the assembler's local view both still find it
        assert tailstore.pinned(slow_id)
        assert critpath.local_entries(slow_id)
    finally:
        store.uninstall()


def test_route_segments_sum_to_the_route_total():
    """Per route, the critical-path segment counters
    (SeaweedFS_critpath_seconds{route,segment}) add up to the route's
    own total (SeaweedFS_critpath_route_seconds{route}): every second a
    pinned trace spent is in exactly one segment."""

    def counted(name, **labels):
        return stats.REGISTRY.get_sample_value(name, labels) or 0.0

    def sums(route):
        return (
            counted("SeaweedFS_critpath_route_seconds_total", route=route),
            sum(
                counted(
                    "SeaweedFS_critpath_seconds_total",
                    route=route, segment=seg,
                )
                for seg in critpath.SEGMENTS
            ),
        )

    store = tailstore.TailStore(node="vs1", capacity=8, floor_ms=50.0)
    store.install()
    try:
        names = ("GET /1,aabbcc", "POST /2,ddeeff")
        before = {critpath.route_of(n): sums(critpath.route_of(n))
                  for n in names}
        for i, name in enumerate(names):
            t, tok = obs.start_trace(name, "volume", "vs1")
            t.t0 -= 0.2 + 0.1 * i
            with obs.span("shard_read"):
                pass
            obs.finish_trace(t, tok, 200)
        routes = store.routes()
        assert set(before) <= set(routes)
        for route, (total0, segs0) in before.items():
            total, segs = sums(route)
            assert total - total0 >= 0.2, route
            assert total - total0 == pytest.approx(segs - segs0, rel=1e-6)
    finally:
        store.uninstall()


def test_tail_ring_bounded_newest_pins_win():
    store = tailstore.TailStore(node="vs1", capacity=4, floor_ms=10.0)
    store.install()
    try:
        ids = [_finish_one(dur_s=0.05) for _ in range(9)]
        pins = store.snapshot()
        assert len(pins) == 4, "tail ring exceeded its capacity"
        assert [p["trace_id"] for p in pins] == list(reversed(ids[-4:]))
    finally:
        store.uninstall()


def test_incident_flag_pins_a_fast_trace():
    """A QoS-shaped request pins regardless of latency — the decision
    itself is the evidence — while non-trigger kinds are ignored."""
    store = tailstore.TailStore(node="vs1", capacity=4, floor_ms=1e9)
    store.install()
    try:
        fast_id = _finish_one(dur_s=0.0, flag_store=store,
                              flag_kind="hedge")
        pins = store.snapshot(trace_id=fast_id)
        assert len(pins) == 1 and pins[0]["reason"] == "incident:hedge"

        # flag_ambient: trigger kinds fan to installed stores, others no-op
        t, tok = obs.start_trace("GET /2,dd", "volume", "vs1")
        tailstore.flag_ambient("compile_storm", t.trace_id)  # not a trigger
        obs.finish_trace(t, tok, 200)
        assert not store.snapshot(trace_id=t.trace_id)
    finally:
        store.uninstall()


def test_set_floor_ms_validation():
    store = tailstore.TailStore(node="vs1", capacity=4, floor_ms=0.0)
    with pytest.raises(ValueError):
        store.set_floor_ms(-1.0)
    store.install()
    try:
        no_pin = _finish_one(dur_s=0.05)
        assert not store.snapshot(trace_id=no_pin)  # floor 0 = off
        store.set_floor_ms(10.0)
        pinned_id = _finish_one(dur_s=0.05)
        assert store.snapshot(trace_id=pinned_id)
    finally:
        store.uninstall()


# -------------------------------------------------------------- assembly


def _parent_child_entries(child_wall_skew_ms=0.0):
    """A two-node trace: filerA's root with a chunk_fetch call span,
    and volB's child entry hanging off that span id.  The child truly
    started 15ms into the parent; its wall clock reads
    `child_wall_skew_ms` AHEAD of true time."""
    parent = {
        "trace_id": "T1", "role": "filer", "server": "filerA",
        "name": "GET /blob.bin", "parent_span_id": "",
        "root_span_id": "R", "start_unix_ms": 1_000_000,
        "duration_us": 100_000, "status": "200",
        "spans": [{
            "name": "chunk_fetch", "span_id": "S1", "parent_id": "R",
            "offset_us": 10_000, "duration_us": 80_000,
        }],
    }
    child = {
        "trace_id": "T1", "role": "volume", "server": "volB",
        "name": "GET /1,aa", "parent_span_id": "S1",
        "root_span_id": "C",
        "start_unix_ms": 1_000_015 + int(child_wall_skew_ms),
        "duration_us": 60_000, "status": "200",
        "spans": [{
            "name": "device_execute", "span_id": "D1", "parent_id": "C",
            "offset_us": 5_000, "duration_us": 50_000,
        }],
    }
    return parent, child


def test_clock_skew_reconciliation():
    """The heartbeat skew estimate places a deliberately skewed child
    where it actually ran; without the estimate, the parent-side call
    span window clamps the child so it can never appear to run outside
    the RPC that invoked it."""
    parent, child = _parent_child_entries(child_wall_skew_ms=5_000.0)

    doc = critpath.assemble([parent, child],
                            skew_ms={"volB": 5_000.0})
    vol = next(p for p in doc["participants"] if p["role"] == "volume")
    assert vol["offset_us"] == 15_000  # skew-corrected true start
    assert doc["total_us"] == 100_000

    # no estimate: the raw 5s-ahead wall clock would place the child
    # AFTER its parent ended — the clamp pins it to the latest start
    # that still fits inside the chunk_fetch window
    doc = critpath.assemble([parent, child])
    vol = next(p for p in doc["participants"] if p["role"] == "volume")
    assert vol["offset_us"] == 30_000  # 10_000 + (80_000 - 60_000)
    assert vol["offset_us"] + 60_000 <= 10_000 + 80_000

    # either way the six segments sum exactly to the root total, and
    # the child's device time outranks the covering network-call span
    assert sum(doc["segments_us"].values()) == doc["total_us"]
    assert doc["segments_us"]["device_execute"] == 50_000
    assert doc["segments_us"]["network_gap"] == 30_000  # 80k - 50k
    assert doc["segments_us"]["untraced"] == 20_000


def test_client_anchored_assembly_puts_wire_legs_in_network_gap():
    """Anchoring on the client-measured total classifies the slice of
    wall time outside the root handler span as network_gap — wire +
    handoff legs no server span can see — never as untraced."""
    parent, child = _parent_child_entries()
    doc = critpath.assemble([parent, child], skew_ms={},
                            client_total_us=120_000)
    assert doc["total_us"] == 120_000
    assert doc["server_total_us"] == 100_000
    assert sum(doc["segments_us"].values()) == 120_000
    assert doc["segments_us"]["network_gap"] == 30_000 + 20_000
    assert doc["segments_us"]["untraced"] == 20_000  # unchanged

    # a client total BELOW the server span is clock noise, not a leg:
    # the anchor never shrinks the timeline
    doc = critpath.assemble([parent, child], skew_ms={},
                            client_total_us=90_000)
    assert doc["total_us"] == 100_000


def test_assemble_dedupes_ring_and_pin_copies():
    """The same entry arriving via the live ring AND a tail pin (or two
    node urls of a co-hosted process) must not double its spans."""
    parent, child = _parent_child_entries()
    doc = critpath.assemble([parent, child, dict(parent), dict(child)])
    assert len(doc["participants"]) == 2
    assert doc["segments_us"]["device_execute"] == 50_000


# ------------------------------------------------------------ end-to-end


def test_degraded_read_assembly_across_hops(tmp_path):
    """A degraded EC read through the filer, resolved via the
    /debug/critpath front door: the assembled DAG spans the filer hop,
    the volume's dispatcher pipeline, and the remote-shard fetches; the
    client-anchored segments sum to the client-measured total; a bogus
    id gets the 404 contract on both forensics endpoints."""
    from degraded_cluster import build_degraded_cluster

    async def go():
        # host reconstruct path (no device cache): a read touching a
        # DESTROYED shard must try the remote-shard lane before it
        # reconstructs — that hop is the span under test, and it is
        # deterministic here where the device-resident path may serve
        # everything from cache depending on compile warmth
        cluster, vs, blobs, _vid = await build_degraded_cluster(
            str(tmp_path), n_blobs=6, blob_size=lambda i: 4096,
            device_cache=False, drop_shards=(0, 11), with_filer=True,
        )
        try:
            fs = cluster.filer
            from seaweedfs_tpu.filer import Attr, Entry
            from seaweedfs_tpu.pb import filer_pb2

            now = int(time.time())
            for i, (fid, data) in enumerate(blobs.items()):
                await fs.filer.create_entry(
                    Entry(
                        full_path=f"/blob{i}.bin",
                        attr=Attr(
                            mtime=now, crtime=now, file_size=len(data)
                        ),
                        chunks=[
                            filer_pb2.FileChunk(
                                file_id=fid, offset=0, size=len(data)
                            )
                        ],
                    )
                )

            def names(n):
                yield from (sp["name"] for sp in n["spans"])
                for c in n["children"]:
                    yield from names(c)

            async with aiohttp.ClientSession() as sess:
                # read every blob; at least one lives on a destroyed
                # shard and must cross the remote-shard lane before it
                # reconstructs — THAT assembled trace is under test
                hop_doc = None
                for i, (fid, data) in enumerate(blobs.items()):
                    t0 = time.perf_counter()
                    async with sess.get(
                        f"http://{fs.url}/blob{i}.bin"
                    ) as r:
                        assert r.status == 200
                        assert await r.read() == data
                        hdr = r.headers.get(obs.TRACE_HEADER, "")
                    client_us = int((time.perf_counter() - t0) * 1e6)
                    trace_id, _ = obs.parse_trace_header(hdr)
                    assert trace_id

                    async with sess.get(
                        f"http://{cluster.master.url}/debug/critpath",
                        params={"id": trace_id,
                                "client_total_us": str(client_us)},
                        allow_redirects=True,
                    ) as r:
                        assert r.status == 200, await r.text()
                        doc = await r.json()

                    roles = {p["role"] for p in doc["participants"]}
                    assert {"filer", "volume"} <= roles, (
                        doc["participants"]
                    )
                    assert doc["tree"]["children"], "hops did not link"
                    # client-anchored arithmetic on every read: the six
                    # segments sum to the client-visible total, exactly
                    assert doc["total_us"] == max(
                        client_us, doc["server_total_us"]
                    )
                    assert (
                        sum(doc["segments_us"].values()) == doc["total_us"]
                    )
                    assert doc["route"] == f"GET /blob{i}.bin"
                    if hop_doc is None and (
                        "remote_shard_read" in set(names(doc["tree"]))
                    ):
                        hop_doc = doc

                assert hop_doc is not None, (
                    "no degraded read crossed the remote-shard lane"
                )
                vol = next(p for p in hop_doc["participants"]
                           if p["role"] == "volume")
                assert vol["spans"] > 0

                # not-found contract, both front doors (satellite: a
                # miss is a 404 JSON error, not an empty 200)
                for path in ("/debug/critpath", "/debug/traces"):
                    async with sess.get(
                        f"http://{vs.url}{path}",
                        params={"id": "feedfacefeedface"},
                    ) as r:
                        assert r.status == 404
                        err = await r.json()
                        assert "not found" in err["error"]
                async with sess.get(
                    f"http://{vs.url}/debug/tail",
                    params={"id": "feedfacefeedface"},
                ) as r:
                    assert r.status == 404
        finally:
            await cluster.stop()

    run(go())
