"""Cluster telemetry plane (PR r08): volume servers ship device-cache /
dispatcher / stage-digest telemetry on every heartbeat pulse; the master
aggregates it into /cluster/health.json and SeaweedFS_cluster_* gauges,
flagging nodes that miss heartbeats as stale.

The e2e uses degraded_cluster.build_degraded_cluster (the canonical
degrade choreography, no warm plan), so the XLA-fallback kernels compile
in milliseconds at first use.
"""
import asyncio
import time

import aiohttp
import numpy as np

from seaweedfs_tpu import stats
from seaweedfs_tpu.pb import master_pb2
from seaweedfs_tpu.stats.cluster import quantile_from_buckets


def run(coro):
    return asyncio.run(coro)


# ----------------------------------------------------------------- units


def _cum_to_buckets(cum):
    return [cum[0]] + [cum[i] - cum[i - 1] for i in range(1, len(cum))]


def test_stage_digest_deltas():
    """Only stages with NEW observations ship, with per-bucket increments
    over the shared ladder (+Inf last)."""
    h = stats.REQUEST_STAGE_SECONDS.labels(stage="host_reconstruct")
    snap0 = stats.stage_histogram_snapshot()
    h.observe(0.0003)
    h.observe(0.0003)
    h.observe(5.0)  # overflow bucket
    snap1 = stats.stage_histogram_snapshot()
    deltas = {s: (b, c, ds) for s, b, c, ds in
              stats.stage_digest_deltas(snap0, snap1)}
    assert set(deltas) == {"host_reconstruct"}
    buckets, count, dsum = deltas["host_reconstruct"]
    assert count == 3 and sum(buckets) == 3
    assert len(buckets) == len(stats.STAGE_SECONDS_BUCKETS) + 1
    assert buckets[-1] == 1  # the 5s observation rode the +Inf bucket
    assert 5.0 < dsum < 5.01
    # idle pulse: nothing to ship
    assert stats.stage_digest_deltas(snap1, snap1) == []


def test_quantile_from_buckets():
    edges = stats.STAGE_SECONDS_BUCKETS
    assert quantile_from_buckets([0] * (len(edges) + 1), 0.5) is None
    # all mass in one bucket: interpolates within its edges
    counts = [0] * (len(edges) + 1)
    counts[1] = 10
    q = quantile_from_buckets(counts, 0.5)
    assert edges[0] < q <= edges[1]
    # overflow-only mass reports the last finite edge (a floor, flagged
    # by the caller via the overflow count)
    counts = [0] * (len(edges) + 1)
    counts[-1] = 4
    assert quantile_from_buckets(counts, 0.99) == edges[-1]


def test_cluster_telemetry_staleness_and_merge():
    ct = stats.ClusterTelemetry(pulse_seconds=1)
    assert ct.stale_after == 2.0  # flagged within 2 missed intervals

    def tel(used, shed, stage_counts):
        t = master_pb2.VolumeServerTelemetry(
            device_budget_bytes=100, device_used_bytes=used,
            dispatcher_shed=shed,
        )
        d = t.stage_digests.add()
        d.stage = "queue_wait"
        d.bucket_counts.extend(stage_counts)
        d.count = sum(stage_counts)
        d.sum_seconds = 0.001
        return t

    n_b = len(stats.STAGE_SECONDS_BUCKETS) + 1
    ct.observe("a:1", tel(10, 1, [2] + [0] * (n_b - 1)), now=100.0)
    ct.observe("b:2", tel(20, 2, [0, 2] + [0] * (n_b - 2)), now=101.5)
    h = ct.health(now=102.5)
    assert not h["nodes"]["b:2"]["stale"]
    assert h["nodes"]["a:1"]["stale"]  # 2.5s > 2.0s stale_after
    assert h["cluster"]["nodes_stale"] == 1
    # stale nodes drop out of the fresh-cluster scalar aggregates
    assert h["cluster"]["device_used_bytes"] == 20
    # ... but their merged digest contributions persist (history)
    assert h["cluster"]["stages"]["queue_wait"]["count"] == 4
    # a broken stream keeps the last snapshot, marked disconnected
    ct.disconnect("a:1")
    h = ct.health(now=102.5)
    assert h["nodes"]["a:1"]["connected"] is False
    assert h["nodes"]["a:1"]["device"]["used_bytes"] == 10
    # merged quantile spans both nodes' buckets
    q = ct.stage_quantile("queue_wait", 0.99)
    assert q is not None and q <= stats.STAGE_SECONDS_BUCKETS[1]


def test_device_cache_telemetry_counters():
    """Budget-pressure evictions and pin-source claims are counted (the
    heartbeat's HBM-pressure signals)."""
    from seaweedfs_tpu.ops.rs_resident import DeviceShardCache

    cache = DeviceShardCache(budget_bytes=1, shard_quantum=1024)
    cache.put(1, 0, b"x" * 64)
    assert cache.evictions == 0
    cache.put(1, 1, b"y" * 64)  # busts the 1-byte budget: evicts shard 0
    assert cache.evictions == 1
    assert cache.claim_pin_source(1, "/d0") == "/d0"
    assert cache.claim_pin_source(1, "/d1") == "/d0"  # loser keeps winner
    assert cache.pin_claims == 1
    cache.clear()


def test_dispatcher_shutdown_zeroes_gauges():
    from seaweedfs_tpu.serving import EcReadDispatcher

    d = EcReadDispatcher(object(), lambda vid: None)
    stats.VOLUME_SERVER_EC_BATCH_INFLIGHT.set(3)
    stats.VOLUME_SERVER_EC_QUEUE_DEPTH.set(7)
    d.shutdown()
    g = stats.REGISTRY.get_sample_value
    assert g("SeaweedFS_volumeServer_ec_batch_inflight") == 0
    assert g("SeaweedFS_volumeServer_ec_queue_depth") == 0


def test_trace_ring_id_filter():
    from seaweedfs_tpu.obs.trace import Trace, TraceRing

    ring = TraceRing(capacity=8)
    for i in range(4):
        ring.add(Trace("tid-even" if i % 2 == 0 else f"tid-{i}", "volume",
                       f"req{i}"))
    got = ring.snapshot(trace_id="tid-even")
    assert len(got) == 2
    assert all(t["trace_id"] == "tid-even" for t in got)
    # filter applies BEFORE the limit: one entry of the wanted trace,
    # not "the newest entry happens to match"
    assert len(ring.snapshot(limit=1, trace_id="tid-even")) == 1
    assert ring.snapshot(trace_id="nope") == []


def test_digest_ladder_drift_preserves_overflow():
    """A sender on a shorter bucket ladder: its LAST bucket is its +Inf
    overflow and must land in the receiver's +Inf, never in a finite
    mid-ladder bucket (which would fake fast observations)."""
    ct = stats.ClusterTelemetry(pulse_seconds=1)
    tel = master_pb2.VolumeServerTelemetry()
    d = tel.stage_digests.add()
    d.stage = "queue_wait"
    d.bucket_counts.extend([1, 0, 3])  # 3-bucket sender: last is +Inf
    d.count = 4
    ct.observe("a:1", tel, now=100.0)
    with ct._lock:
        buckets = list(ct._stages["queue_wait"].buckets)
    assert buckets[0] == 1 and buckets[-1] == 3 and sum(buckets) == 4
    # overflow surfaces as the health doc's p99-is-a-floor flag
    assert ct.health(now=100.0)["cluster"]["stages"]["queue_wait"]["overflow"] == 3
    # longer-than-ours ladder: extras fold into +Inf, nothing vanishes
    tel2 = master_pb2.VolumeServerTelemetry()
    d2 = tel2.stage_digests.add()
    d2.stage = "shard_read"
    d2.bucket_counts.extend([1] * (len(stats.STAGE_SECONDS_BUCKETS) + 5))
    d2.count = len(stats.STAGE_SECONDS_BUCKETS) + 5
    ct.observe("a:1", tel2, now=100.0)
    with ct._lock:
        buckets = list(ct._stages["shard_read"].buckets)
    assert sum(buckets) == d2.count and buckets[-1] == 5


def test_disconnected_node_retention():
    """Departed nodes keep their last snapshot for the retention window
    (post-mortem view), then drop — rolling restarts on dynamic ports
    must not grow the node set without bound."""
    ct = stats.ClusterTelemetry(pulse_seconds=1, retention_seconds=60)
    ct.observe("a:1", master_pb2.VolumeServerTelemetry(), now=100.0)
    ct.disconnect("a:1")
    assert "a:1" in ct.health(now=150.0)["nodes"]  # within retention
    assert "a:1" not in ct.health(now=161.0)["nodes"]  # pruned
    # a CONNECTED node is never pruned, however stale — a live stream
    # that stopped pulsing is exactly what the stale flag reports
    ct.observe("b:2", master_pb2.VolumeServerTelemetry(), now=100.0)
    h = ct.health(now=1000.0)
    assert h["nodes"]["b:2"]["stale"]


def test_digest_shipping_ack_gated(tmp_path):
    """Stage digests survive heartbeat stream breaks: a pulse's delta
    stays in the backlog until its heartbeat is acked, ships exactly
    once on the happy path, and re-ships after an un-acked stream
    teardown instead of being silently dropped."""
    from seaweedfs_tpu.server.volume import VolumeServer

    vs = VolumeServer(
        masters=[], directories=[str(tmp_path)], port=0, grpc_port=0
    )
    h = stats.REQUEST_STAGE_SECONDS.labels(stage="chunk_fetch")

    def counts(tel):
        return {d.stage: d.count for d in tel.stage_digests}

    h.observe(0.001)
    tel1 = vs._build_telemetry()  # ships (backlog drains prior tests too)
    first = counts(tel1)["chunk_fetch"]
    assert first >= 1
    vs._hb_sent += 1  # pulses() would bump after the build
    h.observe(0.001)
    tel2 = vs._build_telemetry()  # outstanding shipment un-acked: defer
    vs._hb_sent += 1
    assert "chunk_fetch" not in counts(tel2)
    vs._hb_acked = 2  # both heartbeats answered
    tel3 = vs._build_telemetry()  # retire shipment, ship the deferred obs
    vs._hb_sent += 1
    assert counts(tel3)["chunk_fetch"] == 1
    vs._hb_acked = 3
    tel4 = vs._build_telemetry()  # nothing new: empty digest
    vs._hb_sent += 1
    assert counts(tel4) == {}
    # stream break with the shipment un-acked: backlog retains it
    h.observe(0.001)
    tel5 = vs._build_telemetry()
    assert counts(tel5)["chunk_fetch"] == 1
    vs._hb_sent, vs._hb_acked = 0, 0  # _heartbeat_stream's finally
    vs._digest_shipped = {}
    vs._digest_inflight_at = None
    tel6 = vs._build_telemetry()  # re-ships on the new stream
    assert counts(tel6)["chunk_fetch"] == 1


# ------------------------------------------------------------------- e2e


def test_cluster_health_e2e(tmp_path):
    """The acceptance choreography: a degraded device-cached cluster
    serves reads; /cluster/health.json shows per-node HBM used/budget,
    dispatcher occupancy, the residency map, and a merged stage digest
    whose p99 estimate matches the per-server request_stage_seconds
    histogram; a node that stops heartbeating flags stale within 2
    intervals; the shell renders the same view."""
    from degraded_cluster import build_degraded_cluster

    def _device_failures():
        from seaweedfs_tpu.ops import rs_resident

        with rs_resident._device_failures_lock:
            return {
                k: v["count"] for k, v in rs_resident._device_failures.items()
            }

    async def go():
        from seaweedfs_tpu.repair import RepairConfig

        failures_before = _device_failures()
        cluster, vs, blobs, vid = await build_degraded_cluster(
            str(tmp_path), n_blobs=8, device_cache=True,
            # the master's autonomous repair rebuilds the two dropped
            # shards within its 5 s scan: the 12-resident-shard
            # assertions below would race it on a loaded box
            master_kwargs={"ec_repair": RepairConfig(enabled=False)},
        )
        master_http = cluster.master.url
        try:
            async with aiohttp.ClientSession() as sess:
                trace_id = None
                for fid, data in blobs.items():
                    async with sess.get(f"http://{vs.url}/{fid}") as r:
                        assert r.status == 200
                        assert await r.read() == data
                        trace_id = trace_id or r.headers.get(
                            "X-Seaweed-Trace-Id", ""
                        ).partition("-")[0]

                # /debug/traces?id= fetches ONE trace, not the ring
                assert trace_id
                async with sess.get(
                    f"http://{vs.url}/debug/traces", params={"id": trace_id}
                ) as r:
                    got = (await r.json())["traces"]
                assert got and all(
                    t["trace_id"] == trace_id for t in got
                ), got

                # wait for a post-read telemetry pulse to land: the
                # master's merged digest must cover every stage sample
                # the registry holds (vs._stage_snapshot starts empty,
                # so digests are cumulative-complete per stage)
                async def fetch_health():
                    async with sess.get(
                        f"http://{master_http}/cluster/health.json"
                    ) as r:
                        assert r.status == 200
                        return await r.json()

                reg_snap = stats.stage_histogram_snapshot()
                stage = "batch_dispatch"
                reg_cum, _ = reg_snap[stage]
                deadline = time.time() + 15
                health = await fetch_health()

                def landed(health):
                    # the pulse that carries the reads' stage samples AND
                    # the cache as the dropped shards left it: a pulse
                    # built before drop_shards still says 14
                    stages = health["cluster"]["stages"]
                    dev = health["nodes"].get(vs.url, {}).get("device", {})
                    residency = health["cluster"]["ec_volume_residency"]
                    return (
                        stages.get(stage, {}).get("count", 0) >= reg_cum[-1]
                        and dev.get("resident_shards") == 12
                        and residency.get(str(vid), {}).get(vs.url) == 12
                    )

                while time.time() < deadline and not landed(health):
                    await asyncio.sleep(0.5)
                    health = await fetch_health()

                node = health["nodes"][vs.url]
                assert not node["stale"] and node["connected"]
                dev = node["device"]
                assert dev["budget_bytes"] == 1 << 30
                assert dev["used_bytes"] > 0
                assert dev["resident_shards"] == 12  # 14 - 2 dropped
                assert dev["pin_claims"] >= 1
                # the residency map names the degraded volume
                assert dev["resident_shards_by_volume"][str(vid)] == 12
                residency = health["cluster"]["ec_volume_residency"]
                assert residency[str(vid)][vs.url] == 12
                disp = node["dispatcher"]
                assert {"queue_depth", "inflight", "shed_total"} <= set(disp)

                # merged digest p99 vs the per-server histogram: the
                # digests shipped are deltas of the SAME histogram, so
                # with all pulses landed the estimates must agree
                sdoc = health["cluster"]["stages"][stage]
                assert sdoc["count"] == reg_cum[-1], (
                    "digest pulses did not cover the registry histogram"
                )
                expected = quantile_from_buckets(
                    _cum_to_buckets(reg_cum), 0.99
                )
                assert sdoc["p99_seconds"] is not None
                assert abs(sdoc["p99_seconds"] - expected) <= max(
                    1e-9, expected * 1e-6
                ), (sdoc["p99_seconds"], expected)

                # master /metrics re-exports the per-node view
                async with sess.get(f"http://{master_http}/metrics") as r:
                    text = await r.text()
                assert "SeaweedFS_cluster_device_used_bytes" in text
                assert f'node="{vs.url}"' in text
                assert "SeaweedFS_cluster_stage_p99_seconds" in text

                # shell: cluster.health table + -json, volume.device.status
                from types import SimpleNamespace

                from seaweedfs_tpu.shell.command_cluster import (
                    cmd_cluster_health,
                )
                from seaweedfs_tpu.shell.command_volume import (
                    cmd_volume_device_status,
                )

                lines = []
                env = SimpleNamespace(
                    masters=[cluster.master.advertise_url],
                    write=lines.append,
                )
                await cmd_cluster_health(env, [])
                out = "\n".join(str(l) for l in lines)
                assert vs.url in out and "hbm used/budget" in out
                assert stage in out
                lines.clear()
                await cmd_cluster_health(env, ["-json"])
                assert '"nodes"' in "\n".join(str(l) for l in lines)
                lines.clear()
                await cmd_volume_device_status(env, ["-node", vs.url])
                out = "\n".join(str(l) for l in lines)
                assert f"ec volume {vid}: 12 resident shards" in out
                # the node's /status Device block: which accelerator,
                # what the backend resolved to, swallowed failures
                assert "device: platform=cpu kind='cpu'" in out
                # (process-lifetime counters, and under xdist another
                # file's synthetic compile failure may have run in this
                # process: this cluster must have added none)
                assert _device_failures() == failures_before
                assert "device failures: " + " ".join(
                    f"{k}={n}" for k, n in failures_before.items()
                ) in out

                # node goes silent: heartbeats stop, the master flags it
                # stale within 2 intervals (pulse=1s -> stale_after=2s)
                assert health["stale_after_seconds"] == 2.0
                for t_ in vs._tasks:
                    t_.cancel()
                deadline = time.time() + 10
                while time.time() < deadline:
                    health = await fetch_health()
                    if health["nodes"][vs.url]["stale"]:
                        break
                    await asyncio.sleep(0.5)
                assert health["nodes"][vs.url]["stale"], health["nodes"]
                # the dead node's last device snapshot is preserved
                assert health["nodes"][vs.url]["device"]["resident_shards"] == 12
        finally:
            await cluster.stop()

    run(go())
