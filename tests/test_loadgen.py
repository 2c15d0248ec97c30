"""Load-harness suite (seaweedfs_tpu/loadgen, what `weed loadtest`
drives): the workload math without sockets, and the three drivers
(`run_http_load`, `run_mixed_http_load`, `run_s3_load`) in process against
the tests' degraded cluster.  Counts and bytes only: a CPU run gives no
rate and no percentile, so none is compared."""
import asyncio

import aiohttp
import numpy as np
import pytest

from degraded_cluster import build_degraded_cluster
from seaweedfs_tpu import stats
from seaweedfs_tpu.loadgen import (
    LoadResult,
    LoadScenario,
    run_http_load,
    run_mixed_http_load,
    run_s3_load,
    zipf_ranks,
)
from seaweedfs_tpu.loadgen.workload import percentile_ms, plan_keys
from seaweedfs_tpu.repair import RepairConfig


# ----------------------------------------------------------- workload math


def test_zipf_ranks_skew_and_determinism():
    rng = np.random.default_rng(7)
    a = zipf_ranks(100, 5000, 1.1, np.random.default_rng(7))
    b = zipf_ranks(100, 5000, 1.1, np.random.default_rng(7))
    np.testing.assert_array_equal(a, b)  # deterministic under the seed
    counts = np.bincount(a, minlength=100)
    # rank 0 must dominate the tail decisively under s=1.1
    assert counts[0] > 5 * counts[50:].mean()
    assert a.min() >= 0 and a.max() < 100
    # s=0 is uniform: no rank may dominate
    u = zipf_ranks(100, 5000, 0.0, rng)
    uc = np.bincount(u, minlength=100)
    assert uc.max() < 3 * max(uc.min(), 1)


def test_zipf_ranks_rejects_empty_keyspace():
    with pytest.raises(ValueError):
        zipf_ranks(0, 10, 1.0, np.random.default_rng(0))


def test_plan_keys_hot_volume_pinning():
    # keys across three "volumes"; volume b holds the most keys and must
    # absorb ~the configured fraction of reads when pinning is on
    keys = [f"a,{i}" for i in range(3)] + [f"b,{i}" for i in range(9)] + [
        f"c,{i}" for i in range(3)
    ]
    sc = LoadScenario(
        connections=4, reads=2000, zipf_s=0.0, hot_volume_frac=0.9, seed=3
    )
    picks = plan_keys(keys, sc, volume_of=lambda k: k.split(",")[0])
    hot = sum(1 for p in picks if p.startswith("b,"))
    assert hot / len(picks) > 0.85
    sc2 = LoadScenario(connections=4, reads=2000, zipf_s=0.0, seed=3)
    picks2 = plan_keys(keys, sc2, volume_of=lambda k: k.split(",")[0])
    hot2 = sum(1 for p in picks2 if p.startswith("b,"))
    assert hot2 / len(picks2) < 0.8  # without pinning, ~9/15


def test_percentile_ms():
    assert percentile_ms([], 50) is None
    xs = [i / 1000 for i in range(1, 101)]  # 1..100 ms
    assert percentile_ms(xs, 50) == pytest.approx(51.0, abs=2)
    assert percentile_ms(xs, 99) == pytest.approx(100.0, abs=2)


# ------------------------------------------------- drivers, over sockets

BIG = 192 * 1024  # over the 64 KB streaming threshold of the front door


def _counter(name, labels=None):
    return stats.REGISTRY.get_sample_value(name, labels or {}) or 0.0


def _degraded_cluster(tmp_path, **kwargs):
    """One volume of twelve 4 KB blobs (the first two BIG), EC-encoded,
    pinned in the device cache, shards 0 and 11 destroyed: every read is
    a degraded read the resident dispatcher may take."""
    return build_degraded_cluster(
        str(tmp_path), n_blobs=12, device_cache=True,
        blob_size=lambda i: BIG if i < 2 else 4096,
        # the master would rebuild the destroyed shards by itself
        master_kwargs={"ec_repair": RepairConfig(enabled=False)},
        **kwargs,
    )


@pytest.mark.parametrize(
    "qos,zero_copy", [(False, False), (True, True)],
    ids=["copying", "qos_zero_copy"],
)
def test_run_http_load_in_both_front_door_modes(tmp_path, qos, zero_copy):
    """Closed-loop readers over real sockets, zipf keys on a hot volume:
    every planned read comes back byte-equal, and
    response_copy_bytes_total says which body path served them: it
    stays put with zero-copy responses and grows with the
    bytes-materializing ones."""

    async def go():
        cluster, vs, blobs, _vid = await _degraded_cluster(tmp_path)
        try:
            cfg = vs.ec_dispatcher.cfg
            cfg.qos, cfg.zero_copy = qos, zero_copy
            copied0 = _counter(
                "SeaweedFS_volumeServer_response_copy_bytes_total"
            )
            res = await run_http_load(
                vs.url, dict(blobs),
                LoadScenario(connections=4, reads=48, hot_volume_frac=0.5),
            )
            copied = _counter(
                "SeaweedFS_volumeServer_response_copy_bytes_total"
            ) - copied0
        finally:
            await cluster.stop()
        assert (res.reads_ok, res.errors, res.verify_failures) == (48, 0, 0)
        assert res.bytes_read >= 48 * 4096
        assert len(res.latencies_s) == 48
        assert res.slow_connections == 0 and res.churns == 0
        assert (copied == 0) if zero_copy else (copied > 0), copied

    asyncio.run(go())


def test_run_http_load_adversarial_clients_still_verify(tmp_path):
    """Half of the connections dribble their bodies and a quarter of the
    reads reconnect first, with the streamed BIG bodies on the hot
    ranks: the adversaries ran, a read the server cut short is an error
    and never a wrong byte, and every read is accounted for."""

    async def go():
        cluster, vs, blobs, _vid = await _degraded_cluster(tmp_path)
        try:
            res = await run_http_load(
                vs.url, dict(blobs),
                LoadScenario(
                    connections=4, reads=48, slow_client_frac=0.5,
                    churn=0.25, dribble_delay_s=0.002,
                ),
            )
        finally:
            await cluster.stop()
        assert res.slow_connections == 2 and res.churns >= 1
        assert res.verify_failures == 0
        assert res.reads_ok > 0 and res.reads_ok + res.errors == 48

    asyncio.run(go())


def test_run_mixed_http_load_reads_back_every_written_byte(tmp_path):
    """Half of the ops are uploads of fresh fids that join the read key
    stream: no write is refused, no read is wrong, the written bytes are
    counted by the ingest plane, and every written payload reads back
    byte-equal from its holder afterwards."""

    async def go():
        cluster, vs, blobs, _vid = await _degraded_cluster(tmp_path)
        written: dict = {}
        try:
            ingested0 = _counter("SeaweedFS_volumeServer_ingest_bytes_total")
            res = await run_mixed_http_load(
                cluster.master.advertise_url, vs.url, dict(blobs),
                LoadScenario(
                    connections=4, reads=64, write_frac=0.5,
                    write_sizes=[1024, 4096, 70_000],
                ),
                written=written,
            )
            ingested = _counter(
                "SeaweedFS_volumeServer_ingest_bytes_total"
            ) - ingested0
            async with aiohttp.ClientSession() as sess:
                for fid, (holder, data) in written.items():
                    async with sess.get(f"http://{holder}/{fid}") as r:
                        assert r.status == 200, fid
                        assert await r.read() == data, fid
        finally:
            await cluster.stop()
        assert res.writes_ok > 0 and res.write_errors == 0
        assert res.writes_ok == len(written)
        assert res.bytes_written == sum(len(d) for _, d in written.values())
        # the writes went through the ingest plane's door, not around it
        assert ingested >= res.bytes_written
        assert (res.errors, res.verify_failures) == (0, 0)
        assert res.reads_ok + res.writes_ok == 64

    asyncio.run(go())


def test_run_s3_load_rides_the_resident_route(tmp_path):
    """The S3 leg `weed loadtest -s3` drives: GetObject through the
    gateway against one-chunk objects on the degraded EC volumes, every
    body byte-equal, and each read attributed to the device-resident
    path under the s3 origin (ec_read_route_total{route="s3_batched"})."""
    rng = np.random.default_rng(5)
    objects = {
        f"o{i:03d}": rng.integers(0, 256, 4096 + 977 * i, dtype=np.uint8)
        .tobytes()
        for i in range(4)
    }

    async def put_objects(cluster):
        async with aiohttp.ClientSession() as sess:
            base = f"http://{cluster.s3.url}/loadtest"
            async with sess.put(base) as r:
                assert r.status == 200
            for key, data in objects.items():
                async with sess.put(f"{base}/{key}", data=data) as r:
                    assert r.status == 200

    def s3_batched():
        return _counter(
            "SeaweedFS_volumeServer_ec_read_route_total",
            {"route": "s3_batched"},
        )

    async def go():
        cluster, _vs, _blobs, _vid = await _degraded_cluster(
            tmp_path, with_s3=True, fill=put_objects
        )
        try:
            routed0 = s3_batched()
            res = await run_s3_load(
                cluster.s3.url, "loadtest", dict(objects),
                LoadScenario(connections=4, reads=32),
            )
            routed = s3_batched() - routed0
        finally:
            await cluster.stop()
        assert (res.reads_ok, res.errors, res.verify_failures) == (32, 0, 0)
        assert routed == 32

    asyncio.run(go())


def test_load_result_summary_carries_the_counts_loadtest_prints():
    """`weed loadtest` prints `summary()` a level: the read counts
    always, the write block only for a mixed run, the slowest trace ids
    by worker, slowest first."""
    res = LoadResult(
        connections=2, reads_ok=3, errors=1, verify_failures=0,
        slow_connections=1, churns=2, bytes_read=12288, wall_s=1.5,
        latencies_s=[0.001, 0.002, 0.003],
    )
    res.note_trace(res.slow_read_trace, 0, 0.002, "aaaa-01")
    res.note_trace(res.slow_read_trace, 0, 0.001, "bbbb-02")  # faster: kept out
    res.note_trace(res.slow_read_trace, 1, 0.003, "cccc-03")
    res.note_trace(res.slow_read_trace, 1, 0.009, "")  # no header: kept out
    d = res.summary()
    assert {k: d[k] for k in (
        "connections", "reads_ok", "errors", "verify_failures",
        "slow_connections", "churns", "bytes_read", "reads_per_s",
    )} == {
        "connections": 2, "reads_ok": 3, "errors": 1, "verify_failures": 0,
        "slow_connections": 1, "churns": 2, "bytes_read": 12288,
        "reads_per_s": 2.0,
    }
    assert [t["trace_id"] for t in d["slowest_read_traces"]] == [
        "cccc", "aaaa",
    ]
    assert not any(k.startswith("write") for k in d)
    res.writes_ok, res.bytes_written = 2, 3 << 20
    res.write_latencies_s = [0.004, 0.005]
    d = res.summary()
    assert (d["writes_ok"], d["write_errors"], d["bytes_written"]) == (
        2, 0, 3 << 20,
    )
    assert d["ingest_mb_per_s"] == 2.0
