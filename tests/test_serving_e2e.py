"""Integrated degraded-read serving e2e: HTTP reads through the volume
server's continuous-batching EcReadDispatcher (seaweedfs_tpu/serving/)
-> Store.read_ec_needles_batch -> EcVolume resident cache -> batched
reconstruct calls, with two shards destroyed so every read MUST
reconstruct.

This is the CI-scaled promotion of the round-4 hardware drive
(experiments/r4_serving_e2e.py): same cluster wiring, same
encode/mount/pin/degrade sequence, byte-exactness asserted for
sequential reads, coalesced concurrent bursts, and the no-cache native
path — on the CPU backend (tests/conftest.py forces JAX cpu; the device
cache runs the XLA fallback kernels).  The benchmark's GET cells
(benchmark/, BENCHMARK.json) run the same path on the chip.

Reference path being matched: weed/storage/store_ec.go:136-393.
"""
import asyncio

import aiohttp
import pytest


def run(coro):
    return asyncio.run(coro)


async def _build_degraded_cluster(
    tmp_path, n_blobs=10, device_cache=True, drop_shards=(0, 11)
):
    """Cluster with one volume EC-encoded, mounted, and `drop_shards`
    destroyed; returns (cluster, vs, blobs dict fid->bytes).  Thin CI
    wrapper over degraded_cluster.build_degraded_cluster — ONE
    implementation of the degrade choreography for every test."""
    from degraded_cluster import build_degraded_cluster

    cluster, vs, blobs, _vid = await build_degraded_cluster(
        str(tmp_path),
        n_blobs=n_blobs,
        device_cache=device_cache,
        drop_shards=drop_shards,
    )
    return cluster, vs, blobs


@pytest.mark.parametrize("device_cache", [True, False])
def test_degraded_http_serving_byte_exact(tmp_path, device_cache):
    """Every blob reads back byte-exact over plain HTTP with two shards
    destroyed — through the batcher + resident cache when enabled, and
    through the per-read native reconstruct path when not."""

    async def go():
        cluster, vs, blobs = await _build_degraded_cluster(
            tmp_path, device_cache=device_cache
        )
        try:
            async with aiohttp.ClientSession() as sess:

                async def read(fid):
                    async with sess.get(f"http://{vs.url}/{fid}") as r:
                        assert r.status == 200, (fid, r.status)
                        return await r.read()

                # sequential correctness pass
                for fid, want in blobs.items():
                    got = await read(fid)
                    assert got == want, f"{fid}: degraded read corrupt"

                # concurrent burst: the batcher coalesces (device-cache
                # mode) or fans out per-read (native mode); both must
                # stay byte-exact under concurrency
                fids = list(blobs) * 3
                results = await asyncio.gather(*(read(f) for f in fids))
                for f, got in zip(fids, results):
                    assert got == blobs[f]

                # missing needle still 404s cleanly through the batcher
                bad_fid = next(iter(blobs)).split(",")[0] + ",ffffffffffffffff"
                async with sess.get(f"http://{vs.url}/{bad_fid}") as r:
                    assert r.status == 404
        finally:
            await cluster.stop()

    run(go())


def test_degraded_serving_batcher_coalesces(tmp_path):
    """The concurrent burst actually rides the batch path: after the
    burst, the dispatcher has seen multi-needle batches (not 1-by-1),
    repeated bursts return stable results (compile caches warm), and the
    new serving series are scrapeable from the live /metrics endpoint."""

    async def go():
        cluster, vs, blobs = await _build_degraded_cluster(
            tmp_path, n_blobs=8, device_cache=True
        )
        try:
            seen_widths = []
            store = vs.store
            orig = store.read_ec_needles_batch

            def spying(vid, requests, remote_read=None, zero_copy=False):
                seen_widths.append(len(requests))
                return orig(vid, requests, remote_read, zero_copy)

            store.read_ec_needles_batch = spying
            async with aiohttp.ClientSession() as sess:

                async def read(fid):
                    async with sess.get(f"http://{vs.url}/{fid}") as r:
                        assert r.status == 200
                        return await r.read()

                for _ in range(2):
                    fids = list(blobs) * 4
                    results = await asyncio.gather(*(read(f) for f in fids))
                    for f, got in zip(fids, results):
                        assert got == blobs[f]

                # the batching decisions must be dashboard-visible: scrape
                # the real /metrics endpoint for the new serving series
                async with sess.get(f"http://{vs.url}/metrics") as r:
                    assert r.status == 200
                    text = await r.text()
            assert max(seen_widths) > 1, (
                f"burst never coalesced: widths={seen_widths}"
            )
            for series in (
                "SeaweedFS_volumeServer_ec_batch_size_bucket",
                "SeaweedFS_volumeServer_ec_batch_queue_wait_seconds_bucket",
                "SeaweedFS_volumeServer_ec_batch_inflight",
                "SeaweedFS_volumeServer_ec_batch_fallback_total",
                'SeaweedFS_volumeServer_ec_read_route_total{route="batched"}',
            ):
                assert series in text, f"missing metrics series: {series}"
            # the burst rode the batched route, and it was counted
            batched_line = next(
                l for l in text.splitlines()
                if l.startswith(
                    'SeaweedFS_volumeServer_ec_read_route_total{route="batched"}'
                )
            )
            assert float(batched_line.split()[-1]) > 0
        finally:
            await cluster.stop()

    run(go())


def test_degraded_serving_batched_equals_unbatched(tmp_path):
    """Concurrency consistency self-check on the REAL path: a concurrent
    burst served through the coalescer/pipeline returns bytes identical
    to the same needles read one-by-one through the unbatched native
    reconstruct.  The baseline passes use_device=False (the dispatcher's
    shed path), so it exercises the independent CPU reconstruct — a
    kernel bug that corrupts both resident paths identically cannot
    pass."""

    async def go():
        cluster, vs, blobs = await _build_degraded_cluster(
            tmp_path, n_blobs=8, device_cache=True
        )
        try:
            from seaweedfs_tpu.storage import types as t

            async with aiohttp.ClientSession() as sess:

                async def read(fid):
                    async with sess.get(f"http://{vs.url}/{fid}") as r:
                        assert r.status == 200
                        return await r.read()

                fids = list(blobs) * 3
                batched = await asyncio.gather(*(read(f) for f in fids))
            for fid, got in zip(fids, batched):
                vid, nid, cookie = t.parse_fid(fid)
                direct = vs.store.read_ec_needle(
                    vid, nid, cookie, use_device=False
                )
                assert got == direct.data, (
                    f"{fid}: batched read differs from unbatched"
                )
        finally:
            await cluster.stop()

    run(go())
