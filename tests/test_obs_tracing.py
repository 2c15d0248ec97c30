"""End-to-end request tracing (seaweedfs_tpu/obs/): one trace id spans
the filer's inbound request, its chunk fan-out to the volume server, and
the volume server's EC serving stages (dispatcher queue hop included),
all visible in /debug/traces; the per-stage histograms ride /metrics.

The degraded cluster comes from degraded_cluster.build_degraded_cluster
(the one choreography the tests share; no warm plan, so the XLA-fallback
kernels compile in milliseconds at first use).
"""
import asyncio
import time
from types import SimpleNamespace

import aiohttp

from seaweedfs_tpu import obs, stats


def run(coro):
    return asyncio.run(coro)


# ----------------------------------------------------------------- units


def test_obs_config_validation():
    import pytest

    from seaweedfs_tpu.obs import ObsConfig

    assert ObsConfig().validated().trace_ring == 256
    with pytest.raises(ValueError):
        ObsConfig(trace_ring=0).validated()
    with pytest.raises(ValueError):
        ObsConfig(slow_ms=-1).validated()


def test_trace_header_roundtrip():
    assert obs.parse_trace_header("") == (None, "")
    assert obs.parse_trace_header("abc") == ("abc", "")
    assert obs.parse_trace_header("abc-def") == ("abc", "def")
    t, tok = obs.start_trace("GET /x", "volume", "srv")
    try:
        hdr = obs.outbound_headers()[obs.TRACE_HEADER]
        assert hdr == f"{t.trace_id}-{t.root_id}"
        md = dict(obs.grpc_metadata())
        assert md[obs.GRPC_TRACE_KEY] == hdr
    finally:
        obs.finish_trace(t, tok, 200)
    # outside a trace: nothing to propagate
    assert obs.outbound_headers() == {}
    assert obs.grpc_metadata() is None


def test_trace_ring_bounded_and_newest_first():
    from seaweedfs_tpu.obs.trace import Trace, TraceRing

    ring = TraceRing(capacity=3)
    for i in range(5):
        ring.add(Trace(f"id{i}", "volume", f"req{i}"))
    snap = ring.snapshot()
    assert [t["trace_id"] for t in snap] == ["id4", "id3", "id2"]
    assert ring.snapshot(limit=1)[0]["trace_id"] == "id4"


def test_span_nesting_and_stage_sink():
    # trace mode: spans nest via the contextvar
    t, tok = obs.start_trace("GET /y", "volume")
    with obs.span("shard_read", bytes=7):
        with obs.span("host_reconstruct"):
            pass
    obs.finish_trace(t, tok, 200)
    d = obs.RING.snapshot(1)[0]
    by_name = {s["name"]: s for s in d["spans"]}
    assert by_name["host_reconstruct"]["parent_span_id"] == \
        by_name["shard_read"]["span_id"]
    assert by_name["shard_read"]["annotations"]["bytes"] == 7
    # sink mode (no trace in context): durations/annotations accumulate
    with obs.stage_sink() as sink:
        for _ in range(3):
            with obs.span("device_execute", h2d_bytes=10):
                pass
    dur, calls, ann = sink["device_execute"]
    assert calls == 3 and dur > 0 and ann["h2d_bytes"] == 30


def test_slow_request_log(caplog):
    import logging

    from seaweedfs_tpu.obs import ObsConfig

    obs.configure(ObsConfig(slow_ms=0.0001))
    try:
        with caplog.at_level(logging.WARNING, logger="obs"):
            t, tok = obs.start_trace("GET /slow", "volume")
            with obs.span("shard_read"):
                time.sleep(0.002)
            obs.finish_trace(t, tok, 200)
        assert any(
            "slow request" in r.message and t.trace_id in r.message
            for r in caplog.records
        )
    finally:
        obs.configure(ObsConfig())


def test_mq_fence_conflict_counter():
    """The residual epoch-fence window is observed: an activation that
    finds the log tail moved after its resync bumps the conflict counter
    and resyncs next_offset past the interloper's records."""
    from seaweedfs_tpu.mq.broker import MessageQueueBroker, Partition

    async def go():
        broker = MessageQueueBroker(filer_address="127.0.0.1:1")
        p = Partition(broker, "default/t", 0)
        tails = iter([5, 7])  # resync sees 5; re-read sees 7 (conflict)

        async def fake_last_offset(part):
            return next(tails)

        async def fake_fence_read(part):
            return (0, b"")

        async def fake_fence_write(part, epoch):
            return None

        broker._last_offset = fake_last_offset
        broker._read_fence = fake_fence_read
        broker._write_fence = fake_fence_write
        before = stats.REGISTRY.get_sample_value(
            "SeaweedFS_mq_fence_conflict_total"
        )
        await broker._ensure_active(p)
        after = stats.REGISTRY.get_sample_value(
            "SeaweedFS_mq_fence_conflict_total"
        )
        assert after == before + 1
        assert p.next_offset == 8  # resynced over the interloper's tail
        assert p.active

    run(go())


def test_drain_lane_does_not_inherit_spawner_trace():
    """The dispatcher's drain lane is spawned from a traced request and
    asyncio copies that context into the task — the lane must be
    DETACHED, or every later request's batch spans would append to the
    spawner's finished trace.  Each request's trace must carry its own
    batch stages via the queue-hop replay, and only its own."""
    from seaweedfs_tpu.serving import EcReadDispatcher, ServingConfig

    class Store:
        def ec_volume_is_resident(self, vid):
            return True

        def read_ec_needles_batch(
            self, vid, requests, remote_read=None, zero_copy=False
        ):
            time.sleep(0.002)  # keep the lane alive across both reads
            return [b"x"] * len(requests)

    async def go():
        d = EcReadDispatcher(
            Store(), lambda vid: None,
            ServingConfig(max_batch=4, max_wait_us=500),
        )

        async def traced_read(nid):
            t, tok = obs.start_trace(f"GET /{nid}", "volume")
            await d.read(1, nid, None)
            obs.finish_trace(t, tok, 200)
            return t

        t1, t2 = await asyncio.gather(traced_read(1), traced_read(2))
        for t in (t1, t2):
            names = [s.name for s in t.spans]
            assert "queue_wait" in names, names
            assert "batch_dispatch" in names, names
        # a second round on the same (still-warm) dispatcher must not
        # grow the finished traces from round one
        n1 = len(t1.spans)
        await traced_read(3)
        assert len(t1.spans) == n1, "drain lane kept spawner's trace"

    run(go())


# ------------------------------------------------------------------- e2e


def test_trace_propagation_filer_to_volume(tmp_path):
    """One trace id spans filer -> volume -> dispatcher: a degraded EC
    read through the filer produces, in /debug/traces, a filer-role
    trace (chunk_fetch span) and a volume-role trace (queue_wait +
    device_execute + shard_read spans) under the SAME trace id, and
    /metrics exposes every stage histogram."""
    from degraded_cluster import build_degraded_cluster

    async def go():
        cluster, vs, blobs, _vid = await build_degraded_cluster(
            str(tmp_path), n_blobs=6, device_cache=True, with_filer=True,
        )
        try:
            fs = cluster.filer
            fid, data = next(iter(blobs.items()))
            from seaweedfs_tpu.filer import Attr, Entry
            from seaweedfs_tpu.pb import filer_pb2

            now = int(time.time())
            await fs.filer.create_entry(
                Entry(
                    full_path="/blob.bin",
                    attr=Attr(mtime=now, crtime=now, file_size=len(data)),
                    chunks=[
                        filer_pb2.FileChunk(
                            file_id=fid, offset=0, size=len(data)
                        )
                    ],
                )
            )
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"http://{fs.url}/blob.bin") as r:
                    assert r.status == 200
                    assert await r.read() == data
                    hdr = r.headers.get(obs.TRACE_HEADER, "")
                trace_id, _ = obs.parse_trace_header(hdr)
                assert trace_id, "filer response carries no trace id"

                # /debug/traces on the volume server (and the filer's
                # metrics port) serves the ring; in-process roles share
                # it like they share stats.REGISTRY
                async with sess.get(
                    f"http://{vs.url}/debug/traces"
                ) as r:
                    assert r.status == 200
                    traces = (await r.json())["traces"]
                async with sess.get(
                    f"http://{fs.ip}:{fs.metrics_port}/debug/traces"
                ) as r:
                    assert r.status == 200

                same_id = [t for t in traces if t["trace_id"] == trace_id]
                roles = {t["role"] for t in same_id}
                assert {"filer", "volume"} <= roles, (roles, same_id)

                filer_t = next(t for t in same_id if t["role"] == "filer")
                filer_spans = {s["name"] for s in filer_t["spans"]}
                assert "chunk_fetch" in filer_spans

                vol_t = next(t for t in same_id if t["role"] == "volume")
                vol_spans = {s["name"] for s in vol_t["spans"]}
                # acceptance: queue-wait, device-execute (resident
                # path), and shard-read stages on the volume trace
                assert {
                    "queue_wait", "batch_dispatch", "device_execute",
                    "shard_read",
                } <= vol_spans, vol_spans
                # device annotations made it through the queue hop
                dev = next(
                    s for s in vol_t["spans"]
                    if s["name"] == "device_execute"
                )
                ann = dev.get("annotations", {})
                assert ann.get("d2h_bytes", 0) > 0
                assert "compile_misses" in ann
                # the volume span is a child of the filer's outbound
                # span: its inbound parent id came off the header
                assert vol_t["parent_span_id"], vol_t

                # every stage histogram is scrapeable (pre-registered,
                # so even stages this read didn't exercise appear)
                async with sess.get(f"http://{vs.url}/metrics") as r:
                    text = await r.text()
                assert "SeaweedFS_request_stage_seconds_bucket" in text
                for stage in stats.TRACE_STAGES:
                    assert f'stage="{stage}"' in text, stage

                # the shell's operator view of the same ring
                from seaweedfs_tpu.shell.command_volume import (
                    cmd_volume_trace,
                )

                lines = []
                env = SimpleNamespace(write=lines.append)
                await cmd_volume_trace(env, ["-node", vs.url])
                out = "\n".join(lines)
                assert trace_id in out
                assert "device_execute" in out
        finally:
            await cluster.stop()

    run(go())
