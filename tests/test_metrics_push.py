"""Prometheus pushgateway loop e2e: master, volume and filer servers
push their metric registries to a configured gateway address on an
interval (reference weed/stats/metrics.go:263-283 LoopPushingMetric),
in addition to serving /metrics locally.

The gateway here is an in-repo aiohttp receiver speaking the
pushgateway wire protocol (PUT /metrics/job/<job>/instance/<instance>,
text exposition body) — external services are unreachable on this rig.
"""
import asyncio

import aiohttp
from aiohttp import web

from seaweedfs_tpu.operation.ready import wait_cluster_ready
from seaweedfs_tpu.s3api import S3ApiServer
from seaweedfs_tpu.server.filer import FilerServer
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer


def run(coro):
    return asyncio.run(coro)


class PushReceiver:
    """Minimal pushgateway: records (job, instance, body) per PUT."""

    def __init__(self):
        self.pushes: list[tuple[str, str, bytes]] = []
        self._runner = None
        self.port = 0

    async def start(self):
        app = web.Application()
        app.router.add_put(
            "/metrics/job/{job}/instance/{instance}", self._handle
        )
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def _handle(self, request):
        self.pushes.append(
            (
                request.match_info["job"],
                request.match_info["instance"],
                await request.read(),
            )
        )
        return web.Response(status=200)

    async def stop(self):
        if self._runner:
            await self._runner.cleanup()


def test_all_server_roles_push_metrics(tmp_path):
    async def go():
        gw = PushReceiver()
        await gw.start()
        addr = f"127.0.0.1:{gw.port}"
        master = MasterServer(
            port=0, metrics_address=addr, metrics_interval_seconds=1
        )
        await master.start()
        vs = VolumeServer(
            masters=[master.advertise_url],
            directories=[str(tmp_path / "v")],
            port=0,
            grpc_port=0,
            metrics_address=addr,
            metrics_interval_seconds=1,
        )
        await vs.start()
        fs = FilerServer(
            masters=[master.advertise_url],
            port=0,
            grpc_port=0,
            metrics_address=addr,
            metrics_interval_seconds=1,
        )
        await fs.start()
        s3 = S3ApiServer(
            filer_address=fs.url,
            filer_grpc_address=f"{fs.ip}:{fs.grpc_port}",
            port=0,
            metrics_address=addr,
            metrics_interval_seconds=1,
        )
        await s3.start()
        try:
            # the volume server registers on its first heartbeat, some
            # time after its ports answer: a write sent before that
            # fails at assign with a 500
            await wait_cluster_ready(master.url)
            # generate some traffic so counters are non-empty
            async with aiohttp.ClientSession() as s:
                async with s.put(
                    f"http://{fs.url}/hello.txt", data=b"metrics!"
                ) as r:
                    assert r.status < 300
                async with s.get(f"http://{fs.url}/hello.txt") as r:
                    assert await r.read() == b"metrics!"

            want = {"master", "volumeServer", "filer", "s3"}
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                jobs = {j for j, _, _ in gw.pushes}
                if want <= jobs:
                    break
                await asyncio.sleep(0.2)
            jobs = {j for j, _, _ in gw.pushes}
            assert want <= jobs, jobs

            # instances are the servers' own urls; bodies are the text
            # exposition of the shared registry with real series
            by_job = {j: (i, b) for j, i, b in gw.pushes}
            assert by_job["master"][0] == master.url
            assert by_job["volumeServer"][0] == vs.url
            assert by_job["filer"][0] == fs.url
            assert by_job["s3"][0] == s3.url
            body = by_job["filer"][1]
            assert b"SeaweedFS_filer_request_total" in body
            assert b"SeaweedFS_volumeServer_volumes" in by_job["volumeServer"][1]
        finally:
            await s3.stop()
            await fs.stop()
            await vs.stop()
            await master.stop()
            await gw.stop()

    run(go())


def test_final_push_on_cancellation():
    """Stopping a server flushes one final best-effort push, so a
    short-lived run (benchmark, CI job) doesn't silently drop the last
    interval's samples.  The interval is set far beyond the test's
    lifetime: any push beyond the startup one must be the final flush."""

    async def go():
        gw = PushReceiver()
        await gw.start()
        master = MasterServer(
            port=0,
            metrics_address=f"127.0.0.1:{gw.port}",
            metrics_interval_seconds=3600,
        )
        await master.start()
        # the loop pushes once at startup, then sleeps the full hour
        deadline = asyncio.get_event_loop().time() + 10
        while asyncio.get_event_loop().time() < deadline:
            if gw.pushes:
                break
            await asyncio.sleep(0.05)
        assert gw.pushes, "startup push never arrived"
        n_before = len(gw.pushes)
        await master.stop()  # cancels the push task mid-sleep
        assert len(gw.pushes) > n_before, (
            "cancellation dropped the final interval's samples"
        )
        assert gw.pushes[-1][0] == "master"
        await gw.stop()

    run(go())


def test_push_survives_gateway_outage(tmp_path):
    """A down gateway must not kill the push loop: pushes resume when
    the receiver comes back (the reference logs and keeps looping)."""

    async def go():
        gw = PushReceiver()
        await gw.start()
        addr = f"127.0.0.1:{gw.port}"
        await gw.stop()  # gateway down at server start

        master = MasterServer(
            port=0, metrics_address=addr, metrics_interval_seconds=1
        )
        await master.start()
        try:
            await asyncio.sleep(1.5)  # at least one failed push attempt
            # bring the gateway back on the SAME port
            gw2 = PushReceiver()
            app = web.Application()
            app.router.add_put(
                "/metrics/job/{job}/instance/{instance}", gw2._handle
            )
            gw2._runner = web.AppRunner(app)
            await gw2._runner.setup()
            site = web.TCPSite(gw2._runner, "127.0.0.1", gw.port)
            await site.start()
            try:
                deadline = asyncio.get_event_loop().time() + 10
                while asyncio.get_event_loop().time() < deadline:
                    if gw2.pushes:
                        break
                    await asyncio.sleep(0.2)
                assert gw2.pushes, "push loop died during the outage"
                assert gw2.pushes[0][0] == "master"
            finally:
                await gw2._runner.cleanup()
        finally:
            await master.stop()

    run(go())
