"""Device-time attribution plane (r21): the per-workload accelerator
ledger (obs/devledger.py) + the cluster flight timeline
(obs/timeline.py).

Contracts pinned here:
  1. conservation — the ledger's per-class busy sums reconcile against
     the wall clocks that already existed (DevicePipeline.total_busy_s,
     bulk Codec.busy_s): attribution can never invent or lose device
     time;
  2. the timeline ring is bounded and its counter DELTAS are correct,
     including across heartbeat stream breaks (the r08 ACK-gated
     shipping protocol, mirrored for timeline samples) with idempotent
     reships (master dedupes by (node, whole-second t));
  3. exemplars resolve — a sample's slowest-trace link points at a
     trace actually present in /debug/traces' ring;
  4. incident bundles embed the trailing timeline window;
  5. the -obs.timeline.* config validates its edges.
"""
import asyncio
import json
import threading
import time

import pytest

from seaweedfs_tpu import stats
from seaweedfs_tpu.obs import devledger
from seaweedfs_tpu.obs import timeline as timeline_mod
from seaweedfs_tpu.obs import trace as obs_trace
from seaweedfs_tpu.obs.config import ObsConfig
from seaweedfs_tpu.pb import master_pb2
from seaweedfs_tpu.stats.cluster import RETENTION_SECONDS, ClusterTelemetry


@pytest.fixture(autouse=True)
def _fresh_ledger():
    devledger.LEDGER.reset_for_tests()
    yield
    devledger.LEDGER.reset_for_tests()
    devledger.LEDGER.enabled = True


# -------------------------------------------------------------- tagging


def test_workload_context_tagging_and_defaults():
    assert devledger.current_workload() == devledger.UNTAGGED
    assert devledger.current_device() == "default"
    with devledger.workload("scrub"):
        assert devledger.current_workload() == "scrub"
        with devledger.device("mesh"):
            assert devledger.current_device() == "mesh"
        assert devledger.current_device() == "default"
    assert devledger.current_workload() == devledger.UNTAGGED
    # an invalid class is the escape hatch, never a new label value
    with devledger.workload("not-a-class"):
        assert devledger.current_workload() == devledger.UNTAGGED


def test_context_survives_to_thread_hop():
    """The dispatcher tags at the edge; the ops layer records from a
    to_thread worker — the contextvar must ride along."""
    async def go():
        with devledger.workload("serving_bulk", device="3"):
            return await asyncio.to_thread(
                lambda: (
                    devledger.current_workload(),
                    devledger.current_device(),
                )
            )

    assert asyncio.run(go()) == ("serving_bulk", "3")


def test_record_accumulates_and_mirrors_prometheus():
    base = stats.REGISTRY.get_sample_value(
        "SeaweedFS_volumeServer_device_busy_seconds_total",
        {"workload": "ingest", "device": "default"},
    ) or 0.0
    with devledger.workload("ingest"):
        devledger.record(busy_s=0.25, dispatches=2, nbytes=100)
        devledger.record(busy_s=0.75, dispatches=1, nbytes=50,
                         queue_wait_s=0.1)
    snap = devledger.LEDGER.snapshot()
    assert snap["ingest"]["busy_s"] == pytest.approx(1.0)
    assert snap["ingest"]["dispatches"] == 3
    assert snap["ingest"]["bytes"] == 150
    assert snap["ingest"]["queue_wait_s"] == pytest.approx(0.1)
    assert snap["ingest"]["devices"]["default"]["busy_s"] == pytest.approx(1.0)
    got = stats.REGISTRY.get_sample_value(
        "SeaweedFS_volumeServer_device_busy_seconds_total",
        {"workload": "ingest", "device": "default"},
    )
    assert got == pytest.approx(base + 1.0)


def test_disabled_ledger_records_nothing():
    devledger.configure(enabled=False)
    devledger.record(workload="scrub", busy_s=1.0, dispatches=1)
    assert devledger.LEDGER.snapshot() == {}
    devledger.configure(enabled=True)


# --------------------------------------------------------- conservation


@pytest.mark.parametrize(
    "wl", [w for w in devledger.WORKLOADS if w != devledger.UNTAGGED]
)
def test_pipeline_slot_conserves_into_ledger(wl):
    """slot() records the identical duration into total_busy_s and the
    ledger, so the per-class sum equals the pipeline clock exactly —
    under every named class: none falls to `untagged`."""
    from seaweedfs_tpu.ops.rs_resident import DevicePipeline

    pipe = DevicePipeline(slots=2)
    with devledger.workload(wl, device="default"):
        for _ in range(3):
            with pipe.slot():
                time.sleep(0.002)
    busy = devledger.LEDGER.busy_by_workload()
    assert set(busy) == {wl}
    assert busy[wl] == pytest.approx(pipe.total_busy_s, rel=1e-9)
    assert pipe.total_busy_s > 0
    # and total_busy_s is cumulative across overlap windows (never the
    # windowed _busy_s the gauge resets)
    before = pipe.total_busy_s
    other = "scrub" if wl != "scrub" else "repair"
    with devledger.workload(other):
        with pipe.slot():
            time.sleep(0.001)
    assert pipe.total_busy_s > before
    busy = devledger.LEDGER.busy_by_workload()
    assert busy[wl] + busy[other] == pytest.approx(
        pipe.total_busy_s, rel=1e-9
    )


def test_bulk_codec_leg_conserves_into_ledger():
    """The codec leg thread never sees the submitter's context — the
    class rides as a Codec attribute, and the leg records the same
    duration into busy_s and the ledger."""
    import numpy as np

    from seaweedfs_tpu.storage.ec.bulk import Codec

    matrix = np.eye(4, dtype=np.uint8)
    codec = Codec(matrix, backend="numpy", workload="repair")
    shards = np.arange(4 * 64, dtype=np.uint8).reshape(4, 64)
    out = codec.resolve(codec.submit(shards))
    assert out.shape == (4, 64)
    busy = devledger.LEDGER.busy_by_workload()
    assert set(busy) == {"repair"}
    assert busy["repair"] == pytest.approx(codec.busy_s, rel=1e-9)
    snap = devledger.LEDGER.snapshot()
    assert snap["repair"]["devices"] == {
        "host": snap["repair"]["devices"]["host"]
    }
    codec.shutdown()


# ------------------------------------------------------------- timeline


def test_timeline_ring_bounded_and_deltas_correct():
    s = timeline_mod.TimelineSampler(node="n1", window=4)
    assert s.capacity == 4
    s.sample(now=100)  # baseline
    devledger.record(workload="scrub", busy_s=0.5, dispatches=2)
    smp = s.sample(now=101)
    assert smp["busy_ms"] == {"scrub": 500.0}
    assert smp["disp"] == {"scrub": 2}
    # no new work -> empty deltas, not repeated cumulative values
    smp2 = s.sample(now=102)
    assert smp2["busy_ms"] == {} and smp2["disp"] == {}
    for t in range(103, 110):
        s.sample(now=t)
    snap = s.snapshot()
    assert len(snap) == 4  # bounded by the ring
    assert [x["t"] for x in snap] == [106, 107, 108, 109]
    # trailing-window trim
    assert [x["t"] for x in s.snapshot(window_s=1)] == [108, 109]


def test_take_new_hands_each_sample_once_and_survives_overrun():
    s = timeline_mod.TimelineSampler(node="n1", window=3)
    s.sample(now=1)
    s.sample(now=2)
    assert [x["t"] for x in s.take_new()] == [1, 2]
    assert s.take_new() == []
    # shipper stalls past a full ring: only a ring's worth survives
    for t in range(3, 9):
        s.sample(now=t)
    assert [x["t"] for x in s.take_new()] == [6, 7, 8]


def test_timeline_heartbeat_shipping_ack_gated(tmp_path):
    """Timeline samples ride the same ACK-gated heartbeat protocol as
    the stage digests: ship once, defer while un-acked, retire on ack,
    re-ship after an un-acked stream teardown — and the master's
    (node, t) dedupe makes the reship idempotent."""
    from seaweedfs_tpu.server.volume import VolumeServer

    vs = VolumeServer(
        masters=[], directories=[str(tmp_path)], port=0, grpc_port=0
    )
    vs.timeline = timeline_mod.TimelineSampler(node="vs:1", window=8)

    def shipped(tel):
        return [json.loads(s)["t"] for s in tel.timeline_samples_json]

    vs.timeline.sample(now=100)
    tel1 = vs._build_telemetry()
    assert shipped(tel1) == [100]
    vs._hb_sent += 1
    vs.timeline.sample(now=101)
    tel2 = vs._build_telemetry()  # outstanding shipment un-acked: defer
    vs._hb_sent += 1
    assert shipped(tel2) == []
    vs._hb_acked = 2
    tel3 = vs._build_telemetry()  # retire, ship the deferred sample
    vs._hb_sent += 1
    assert shipped(tel3) == [101]
    vs._hb_acked = 3
    tel4 = vs._build_telemetry()
    vs._hb_sent += 1
    assert shipped(tel4) == []
    # stream break with a shipment un-acked: the new stream re-ships
    vs.timeline.sample(now=102)
    tel5 = vs._build_telemetry()
    assert shipped(tel5) == [102]
    vs._hb_sent, vs._hb_acked = 0, 0  # _heartbeat_stream's finally
    vs._digest_shipped = {}
    vs._digest_inflight_at = None
    vs._timeline_shipped = 0
    vs._timeline_inflight_at = None
    tel6 = vs._build_telemetry()
    assert shipped(tel6) == [102]

    # master side: the duplicate 102 folds into one row per (node, t)
    ct = ClusterTelemetry(pulse_seconds=1)
    ct.observe("vs:1", tel5, now=200.0)
    ct.observe("vs:1", tel6, now=201.0)
    doc = ct.timeline()
    assert [row["t"] for row in doc["samples"]] == [102]
    assert doc["nodes"] == ["vs:1"]


def test_cluster_timeline_clock_aligned_assembly():
    """Samples from different nodes at the same whole second land in
    ONE row — cluster-wide 'what was everyone doing at t' is a lookup."""
    ct = ClusterTelemetry(pulse_seconds=1)

    def tel(samples):
        t = master_pb2.VolumeServerTelemetry()
        t.timeline_samples_json.extend(
            json.dumps(s, separators=(",", ":")) for s in samples
        )
        return t

    ct.observe("a:1", tel([
        {"t": 100, "node": "a:1", "busy_ms": {"ingest": 10.0}},
        {"t": 101, "node": "a:1", "busy_ms": {}},
    ]), now=101.0)
    ct.observe("b:2", tel([
        {"t": 100, "node": "b:2", "busy_ms": {"scrub": 5.0}},
    ]), now=101.0)
    doc = ct.timeline()
    assert doc["nodes"] == ["a:1", "b:2"]
    rows = {row["t"]: row["nodes"] for row in doc["samples"]}
    assert set(rows) == {100, 101}
    assert rows[100]["a:1"]["busy_ms"] == {"ingest": 10.0}
    assert rows[100]["b:2"]["busy_ms"] == {"scrub": 5.0}
    assert "b:2" not in rows[101]
    # window trim keeps only the trailing seconds
    doc = ct.timeline(window_s=0.5)
    assert [row["t"] for row in doc["samples"]] == [101]
    # malformed rows are skipped, never fatal
    bad = master_pb2.VolumeServerTelemetry()
    bad.timeline_samples_json.append("not json")
    bad.timeline_samples_json.append(json.dumps({"no_t": 1}))
    ct.observe("a:1", bad, now=102.0)
    assert len(ct.timeline()["samples"]) == 2


def test_timeline_retention_shares_stale_node_window():
    """Micro-fix r21: node-timeline retention at the master IS the
    stale-node retention window — one constant, not two clocks."""
    ct = ClusterTelemetry(pulse_seconds=1)
    assert ct.retention_seconds == RETENTION_SECONDS
    t = master_pb2.VolumeServerTelemetry()
    t.timeline_samples_json.append(json.dumps({"t": 100, "node": "a:1"}))
    ct.observe("a:1", t, now=100.0)
    later = master_pb2.VolumeServerTelemetry()
    ct.observe("a:1", later, now=100.0 + RETENTION_SECONDS + 1)
    assert ct.timeline()["samples"] == []


def test_exemplar_links_resolve_against_trace_ring():
    """A spike sample's exemplar names a trace the /debug/traces ring
    can actually serve, with the slowest span attached."""
    s = timeline_mod.TimelineSampler(node="n1", window=4).install()
    try:
        tr, tok = obs_trace.start_trace("GET /7,aa", "volume")
        assert tr is not None
        tr.add_span("device_execute", tr.t0, 0.040)
        tr.add_span("queue_wait", tr.t0, 0.001)
        time.sleep(0.002)
        obs_trace.finish_trace(tr, tok, status=200)
        smp = s.sample(now=500)
        ex = smp["exemplar"]
        assert ex["trace_id"] == tr.trace_id
        assert ex["span"] == "device_execute"
        assert ex["ms"] > 0
        resolved = obs_trace.RING.snapshot(trace_id=ex["trace_id"])
        assert resolved and resolved[0]["trace_id"] == ex["trace_id"]
        # the exemplar is consumed with its sample — the next sample
        # does not repeat a stale slowest trace
        assert "exemplar" not in s.sample(now=501)
    finally:
        s.uninstall()
    assert s._on_trace not in obs_trace.FINISH_OBSERVERS


def test_observer_exception_never_breaks_finish_trace():
    def boom(_t):
        raise RuntimeError("observer bug")

    obs_trace.FINISH_OBSERVERS.append(boom)
    try:
        tr, tok = obs_trace.start_trace("GET /x", "volume")
        obs_trace.finish_trace(tr, tok, status=200)  # must not raise
    finally:
        obs_trace.FINISH_OBSERVERS.remove(boom)


# ------------------------------------------------------------- incident


def test_incident_bundle_embeds_timeline_window(tmp_path):
    """An SLO-fired bundle carries the trailing cluster timeline — the
    r17 'what happened' snapshot gains the 'what led into it' window."""
    from seaweedfs_tpu.obs import incident as obs_incident

    old = obs_incident.CONFIG
    obs_incident.configure(obs_incident.IncidentConfig(
        dir=str(tmp_path), min_interval_seconds=0.0,
    ))
    try:
        captured: list[float] = []

        def timeline_fn(window_s):
            captured.append(window_s)
            return {
                "window_seconds": window_s,
                "nodes": ["a:1"],
                "samples": [
                    {"t": 100, "nodes": {"a:1": {"busy_ms": {"scrub": 9.0}}}}
                ],
            }

        b = obs_incident.IncidentBundler(
            lambda: [], lambda: {"cluster": {}}, timeline_fn=timeline_fn,
        )
        summary = asyncio.run(
            b.capture({"slo": "read_p99"}, window_s=30.0)
        )
        assert summary is not None
        assert captured == [30.0]
        with open(summary["path"], encoding="utf-8") as f:
            bundle = json.load(f)
        assert bundle["timeline"]["samples"][0]["nodes"]["a:1"][
            "busy_ms"] == {"scrub": 9.0}
        assert bundle["timeline"]["window_seconds"] == 30.0
    finally:
        obs_incident.configure(old)


def test_incident_bundle_survives_timeline_failure(tmp_path):
    from seaweedfs_tpu.obs import incident as obs_incident

    old = obs_incident.CONFIG
    obs_incident.configure(obs_incident.IncidentConfig(
        dir=str(tmp_path), min_interval_seconds=0.0,
    ))
    try:
        def broken(_w):
            raise RuntimeError("assembly bug")

        b = obs_incident.IncidentBundler(
            lambda: [], lambda: {}, timeline_fn=broken,
        )
        summary = asyncio.run(b.capture({"slo": "x"}, window_s=10.0))
        assert summary is not None  # the bundle still lands
        with open(summary["path"], encoding="utf-8") as f:
            assert json.load(f)["timeline"] is None
    finally:
        obs_incident.configure(old)


# --------------------------------------------------------------- config


def test_obs_config_timeline_validation():
    assert ObsConfig().validated().timeline_window == 120
    with pytest.raises(ValueError, match="interval"):
        ObsConfig(timeline_interval_seconds=0.0).validated()
    with pytest.raises(ValueError, match="timeline_window"):
        ObsConfig(timeline_window=1).validated()
    cfg = ObsConfig(
        timeline_interval_seconds=0.25, timeline_window=2
    ).validated()
    assert cfg.timeline_interval_seconds == 0.25


def test_timeline_sampler_threadsafe_under_concurrent_records():
    """Sampling while dispatch sites record concurrently must neither
    crash nor lose counts (the ledger lock + snapshot-under-lock)."""
    s = timeline_mod.TimelineSampler(node="n1", window=16)
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            devledger.record(workload="bulk", busy_s=0.001, dispatches=1)

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for i in range(30):
            s.sample(now=1000 + i)
    finally:
        stop.set()
        for t in threads:
            t.join()
    total_disp = sum(
        smp["disp"].get("bulk", 0) for smp in s.snapshot()
    )
    # deltas across samples sum to (at most) the ledger's cumulative
    # count — nothing double-counted
    assert total_disp <= devledger.LEDGER.dispatches_by_workload()["bulk"]
