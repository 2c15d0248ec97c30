"""Incident plane end to end (ISSUE r17 tentpole): the flight recorder
records trace-stamped decision events on every role, /debug/incident
serves them, the master's SLO engine burns against live telemetry, and
a violation writes ONE correlated incident bundle — plus the on-demand
device endpoints (/debug/device/hot, SWFS_DEBUG-gated /debug/profile).

The e2e rides degraded_cluster's LocalCluster + EC spread choreography
(warm-free native backend: no device compiles) with second-scale
SLO windows so the burn fires within a few pulses.
"""
from __future__ import annotations

import asyncio
import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu import obs
from seaweedfs_tpu.obs import incident as obs_incident


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _restore_incident_config():
    """The incident config is process-global (like the trace ring);
    every test gets the defaults back."""
    yield
    obs_incident.configure(obs_incident.IncidentConfig())
    obs_incident.EVENTS.clear()


# ------------------------------------------------------------------ units


def test_incident_config_validation():
    with pytest.raises(ValueError):
        obs_incident.IncidentConfig(events=0).validated()
    with pytest.raises(ValueError):
        obs_incident.IncidentConfig(keep=0).validated()
    with pytest.raises(ValueError):
        obs_incident.IncidentConfig(min_interval_seconds=-1).validated()
    with pytest.raises(ValueError):
        obs_incident.IncidentConfig(profile_seconds=-1).validated()
    assert obs_incident.IncidentConfig().validated().events == 512


def test_record_stamps_ambient_trace_id():
    obs_incident.EVENTS.clear()
    t, tok = obs.start_trace("GET /x", "volume", "srv")
    try:
        obs_incident.record("qos_shed", tier="interactive", reason="t")
    finally:
        obs.finish_trace(t, tok, 200)
    obs_incident.record("tier_promote", vid=7)  # outside any trace
    ev = obs_incident.EVENTS.snapshot()
    assert ev[0]["kind"] == "tier_promote" and ev[0]["trace_id"] == ""
    assert ev[1]["kind"] == "qos_shed"
    assert ev[1]["trace_id"] == t.trace_id
    assert ev[1]["details"]["tier"] == "interactive"


def test_record_disabled_is_a_noop():
    obs_incident.configure(obs_incident.IncidentConfig(enabled=False))
    obs_incident.EVENTS.clear()
    obs_incident.record("qos_shed", tier="bulk", reason="x")
    assert obs_incident.EVENTS.snapshot() == []


def test_event_ring_since_kind_limit_filters():
    obs_incident.EVENTS.clear()
    base_s = 1_700_000_000  # exact integer epoch: no float truncation
    for i in range(6):
        obs_incident.EVENTS.add(
            {
                "unix_ms": (base_s + i) * 1000,
                "kind": "a" if i % 2 else "b",
                "trace_id": "",
                "details": {"i": i},
            }
        )
    # since: only events at/after the cutoff, newest-first
    got = obs_incident.EVENTS.snapshot(since_unix=base_s + 3)
    assert [e["details"]["i"] for e in got] == [5, 4, 3]
    # kind filter before limit
    got = obs_incident.EVENTS.snapshot(kind="a", limit=2)
    assert [e["details"]["i"] for e in got] == [5, 3]


def test_qos_shed_and_breaker_transitions_are_recorded():
    from seaweedfs_tpu.serving.qos import (
        INTERACTIVE,
        QosController,
        TierPolicy,
    )

    obs_incident.EVENTS.clear()
    q = QosController(
        {INTERACTIVE: TierPolicy(INTERACTIVE, 1, 0.0)},
        trip_after=2, cooldown_s=60.0,
    )
    q.enqueued(INTERACTIVE)  # budget (1) now full
    assert q.admit(INTERACTIVE, 1, 4) == "queue_budget"
    assert q.admit(INTERACTIVE, 1, 4) == "queue_budget"  # trips breaker
    assert q.admit(INTERACTIVE, 1, 4) == "breaker_open"
    kinds = [e["kind"] for e in obs_incident.EVENTS.snapshot()]
    assert kinds.count("qos_shed") == 3
    # the open transition was recorded (newest-first: it precedes the
    # breaker_open shed)
    br = [
        e for e in obs_incident.EVENTS.snapshot(kind="qos_breaker")
        if e["details"]["state"] == "open"
    ]
    assert len(br) == 1


# -------------------------------------------------------------------- e2e


async def _encode_spread(cluster, vid):
    """EC-encode `vid` and push its LEADING shard group (shard 0 — a
    small volume's every needle) to the OTHER volume server, so reads
    against the holder must fetch remote shards over gRPC: the genuine
    cross-server trace the correlation check wants."""
    from degraded_cluster import chaos_encode_spread

    holder = next(
        vs for vs in cluster.volume_servers if vs.store.has_volume(vid)
    )
    victim_idx = next(
        i for i, vs in enumerate(cluster.volume_servers)
        if vs is not holder
    )
    await chaos_encode_spread(cluster, vid, victim_idx=victim_idx)
    return holder


async def _incident_e2e(tmp_path, monkeypatch):
    import aiohttp

    from seaweedfs_tpu.operation import assign, upload_data
    from seaweedfs_tpu.server.cluster import LocalCluster

    # /debug/profile is SWFS_DEBUG-gated at server START
    monkeypatch.setenv("SWFS_DEBUG", "1")
    inc_dir = str(tmp_path / "incidents")
    cluster = LocalCluster(
        base_dir=str(tmp_path / "data"), n_volume_servers=2,
        pulse_seconds=1, ec_backend="native",
        master_kwargs=dict(
            # every shard_read observation is slower than 0.1us: the
            # read-latency SLO burns as soon as real reads flow, and
            # second-scale windows make fast-trip + slow-confirm land
            # within a few pulses
            obs_slo=obs.SloConfig(
                read_p99_ms=1e-4, read_stage="shard_read",
                fast_window_seconds=1.0, slow_window_seconds=2.0,
            ),
            obs_incident=obs_incident.IncidentConfig(
                dir=inc_dir, min_interval_seconds=0.0,
                profile_seconds=0.2,
            ),
        ),
    )
    await cluster.start()
    try:
        master = cluster.master.advertise_url
        rng = np.random.default_rng(11)
        blobs, vid = {}, None
        for i in range(200):
            if len(blobs) >= 10:
                break
            a = await assign(master)
            v = int(a.fid.split(",")[0])
            vid = vid if vid is not None else v
            if v != vid:
                continue
            data = rng.integers(0, 256, 2000 + i * 37, dtype=np.uint8)
            await upload_data(f"http://{a.url}/{a.fid}", data.tobytes())
            blobs[a.fid] = data.tobytes()
        assert len(blobs) >= 10
        front = await _encode_spread(cluster, vid)
        await asyncio.sleep(1.2)  # shard mounts reach the master

        async with aiohttp.ClientSession() as sess:
            deadline = time.monotonic() + 30
            burned = None
            while time.monotonic() < deadline and burned is None:
                # keep reads flowing so the stage digests keep landing
                for fid in blobs:
                    async with sess.get(
                        f"http://{front.url}/{fid}"
                    ) as r:
                        body = await r.read()
                        assert r.status == 200 and body == blobs[fid]
                async with sess.get(
                    f"http://{cluster.master.ip}:{cluster.master.port}"
                    "/cluster/health.json"
                ) as r:
                    health = await r.json()
                slo = health["slo"]["objectives"]["read_p99"]
                if slo["violations_total"] >= 1:
                    burned = slo
                await asyncio.sleep(0.3)
            assert burned is not None, "SLO never burned under load"
            assert burned["last_verdict"]["slo"] == "read_p99"

            # the violation wrote an incident bundle (rate limit 0).
            # The wait budget must EXCEED the bundler's own
            # device-profile capture timeout (30s): the capture runs
            # before the write by design, and a warmed full-suite
            # process pays 20s+ of jax profiler init + trace dump —
            # a 20s test bound raced the component's 30s contract
            bundle_path = None
            deadline = time.monotonic() + 40
            while time.monotonic() < deadline and bundle_path is None:
                files = sorted(os.listdir(inc_dir)) if os.path.isdir(
                    inc_dir
                ) else []
                files = [f for f in files if f.endswith(".json")]
                if files:
                    bundle_path = os.path.join(inc_dir, files[-1])
                await asyncio.sleep(0.2)
            assert bundle_path, "no incident bundle written"
            from seaweedfs_tpu.utils.aiofile import read_file_text

            bundle = json.loads(await read_file_text(bundle_path))
            assert bundle["trigger"] == "slo"
            assert bundle["reason"]["slo"] == "read_p99"
            # both volume servers + the master's own ring are in there
            urls = {vs.url for vs in cluster.volume_servers}
            assert urls <= set(bundle["nodes"]) - {"<master>"}
            assert "<master>" in bundle["nodes"]
            # the master recorded the violation event itself
            master_kinds = {
                e["kind"] for e in bundle["nodes"]["<master>"]["events"]
            }
            assert "slo_violation" in master_kinds
            # cross-server correlation: at least one trace id whose
            # entries were recorded at 2+ capture points (the front's
            # HTTP entry + the peer's grpc VolumeEcShardRead entry)
            corr = bundle["correlation"]
            assert corr["trace_ids_multi_node"], corr
            assert corr["trace_ids_cross_server"], corr
            # latency SLO + profileSeconds>0: a device capture rode along
            # (or recorded its failure — never silently absent)
            assert bundle["profile"] is not None
            assert (
                bundle["profile"].get("trace_dir")
                or bundle["profile"].get("error")
            )
            # the health doc embedded in the bundle carries the slo block
            assert "slo" in bundle["health"]

            # /debug/incident on a node: events+traces, since filter
            async with sess.get(
                f"http://{front.url}/debug/incident",
                params={"since": "60"},
            ) as r:
                assert r.status == 200
                doc = await r.json()
            assert "events" in doc and "traces" in doc
            assert doc["traces"], "no traces in the burn window"
            async with sess.get(
                f"http://{front.url}/debug/incident",
                params={"since": "0.0001"},
            ) as r:
                tiny = await r.json()
            assert len(tiny["traces"]) <= len(doc["traces"])

            # /debug/traces gained ?since= (filter before limit)
            async with sess.get(
                f"http://{front.url}/debug/traces",
                params={"since": "60", "limit": "3"},
            ) as r:
                assert r.status == 200
                assert len((await r.json())["traces"]) <= 3

            # /debug/device/hot: the per-shape dispatch view (no device
            # cache here, so shapes may be empty — the schema holds)
            async with sess.get(
                f"http://{front.url}/debug/device/hot"
            ) as r:
                assert r.status == 200
                hot = await r.json()
            assert "shapes" in hot and "aot" in hot

            # /debug/profile (SWFS_DEBUG on): a short capture succeeds
            # or reports profiler unavailability — never a 500.  The
            # bundler's OWN capture may still be draining on this node
            # (it writes the bundle after a 30s timeout even if the
            # node-side profiler is still initialising), so wait out
            # the single-flight 409 before judging the manual capture
            deadline = time.monotonic() + 45
            while True:
                async with sess.get(
                    f"http://{front.url}/debug/profile",
                    params={"seconds": "0.2"},
                ) as r:
                    if r.status == 409 and time.monotonic() < deadline:
                        await asyncio.sleep(1.0)
                        continue
                    assert r.status in (200, 503), await r.text()
                    if r.status == 200:
                        prof = await r.json()
                        assert prof["trace_dir"] and "hot_shapes" in prof
                    break

            # operator dump: POST /cluster/incident/dump forces a
            # second bundle past the rate limit
            async with sess.post(
                f"http://{cluster.master.ip}:{cluster.master.port}"
                "/cluster/incident/dump", params={"window": "60"},
            ) as r:
                assert r.status == 200
                dump = await r.json()
            assert os.path.exists(dump["path"])
            assert dump["correlation"]["trace_ids_multi_node"]
    finally:
        await cluster.stop()
        from seaweedfs_tpu.pb.rpc import close_all_channels

        await close_all_channels()


def test_incident_plane_e2e(tmp_path, monkeypatch):
    run(_incident_e2e(tmp_path, monkeypatch))
