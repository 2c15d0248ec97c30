"""Real multi-process cluster: master + volume server + filer launched as
separate `python -m seaweedfs_tpu ...` OS processes (the deployment
story, not LocalCluster), then driven end-to-end: upload through the
filer, admin shell over gRPC, S3 gateway, graceful teardown.
"""
import asyncio
import io
import os
import signal
import socket
import sys

import aiohttp
import pytest

from seaweedfs_tpu.operation.ready import wait_cluster_ready
from seaweedfs_tpu.shell import CommandEnv, run_command

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


async def spawn(*argv):
    return await asyncio.create_subprocess_exec(
        sys.executable, "-m", "seaweedfs_tpu", *argv,
        cwd=REPO,
        stdout=asyncio.subprocess.DEVNULL,
        stderr=asyncio.subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "SWFS_NO_NATIVE_BUILD": "1"},
    )


async def wait_http(url, timeout=90.0):
    # generous default: a 1-core CI box imports jax serially in each
    # subprocess and can take >30s to bind the first port
    deadline = asyncio.get_event_loop().time() + timeout
    async with aiohttp.ClientSession() as s:
        while asyncio.get_event_loop().time() < deadline:
            try:
                async with s.get(url):
                    return
            except aiohttp.ClientError:
                await asyncio.sleep(0.25)
    raise TimeoutError(url)


def test_multiprocess_cluster(tmp_path):
    async def go():
        mp, mg, vp, vg, fp, fg = free_ports(6)
        os.makedirs(tmp_path / "meta")
        os.makedirs(tmp_path / "vol")
        procs = []
        try:
            procs.append(
                await spawn(
                    "master", "-port", str(mp), "-port.grpc", str(mg),
                    "-mdir", str(tmp_path / "meta"),
                    "-volumeSizeLimitMB", "64",
                    # the telemetry plane's staleness window is derived
                    # from the master's OWN pulse flag — match the
                    # volume server's 1s pulse or stale_after is 10s
                    "-pulseSeconds", "1",
                )
            )
            await wait_http(f"http://127.0.0.1:{mp}/cluster/status")
            master = f"127.0.0.1:{mp}.{mg}"
            procs.append(
                await spawn(
                    "volume", "-port", str(vp), "-port.grpc", str(vg),
                    "-dir", str(tmp_path / "vol"), "-mserver", master,
                    "-pulseSeconds", "1",
                )
            )
            procs.append(
                await spawn(
                    "filer", "-port", str(fp), "-port.grpc", str(fg),
                    "-master", master,
                    "-store", "sqlite", "-db", str(tmp_path / "filer.db"),
                )
            )
            await wait_http(f"http://127.0.0.1:{fp}/?limit=1")
            # answering HTTP is not registered: an upload before the
            # volume server's first heartbeat fails at assign
            await wait_cluster_ready(f"127.0.0.1:{mp}")

            # data plane: upload + range read through the filer process
            data = os.urandom(512 * 1024)
            async with aiohttp.ClientSession() as s:
                async with s.put(
                    f"http://127.0.0.1:{fp}/docs/blob.bin", data=data
                ) as r:
                    assert r.status in (200, 201), await r.text()
                async with s.get(
                    f"http://127.0.0.1:{fp}/docs/blob.bin"
                ) as r:
                    assert await r.read() == data
                async with s.get(
                    f"http://127.0.0.1:{fp}/docs/blob.bin",
                    headers={"Range": "bytes=1000-1999"},
                ) as r:
                    assert await r.read() == data[1000:2000]

            # admin shell against the real processes
            env = CommandEnv([master], out=io.StringIO())
            await env.acquire_lock()
            await run_command(env, "volume.list")
            assert "total" in env.out.getvalue()
            env.out = io.StringIO()
            await run_command(env, "cluster.ps")
            assert "filers:" in env.out.getvalue()
            env.out = io.StringIO()
            await run_command(env, "fs.ls /docs")
            assert "blob.bin" in env.out.getvalue()
            await env.release_lock()

            # CLI tools against the processes
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "seaweedfs_tpu", "upload",
                "-master", master, __file__,
                cwd=REPO, stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
                env={**os.environ, "JAX_PLATFORMS": "cpu",
                     "SWFS_NO_NATIVE_BUILD": "1"},
            )
            out, err = await asyncio.wait_for(proc.communicate(), 60)
            assert proc.returncode == 0, err.decode()
            assert b'"fid"' in out

            # telemetry round-trip: the volume process's heartbeat
            # payload surfaces in the master process's health plane
            vs_url = f"127.0.0.1:{vp}"
            async with aiohttp.ClientSession() as s:

                async def health():
                    async with s.get(
                        f"http://127.0.0.1:{mp}/cluster/health.json"
                    ) as r:
                        assert r.status == 200
                        return await r.json()

                deadline = asyncio.get_event_loop().time() + 15
                doc = await health()
                while asyncio.get_event_loop().time() < deadline:
                    node = doc["nodes"].get(vs_url)
                    if node and node["telemetry"] and not node["stale"]:
                        break
                    await asyncio.sleep(0.25)
                    doc = await health()
                node = doc["nodes"][vs_url]
                assert node["telemetry"] and not node["stale"], node
                assert "dispatcher" in node and "device" in node

                # node goes silent (SIGKILL: no goodbye): flagged stale
                # within 2 pulse intervals (pulse=1s -> 2s)
                procs[1].kill()
                assert doc["stale_after_seconds"] == 2.0
                deadline = asyncio.get_event_loop().time() + 15
                while asyncio.get_event_loop().time() < deadline:
                    doc = await health()
                    if doc["nodes"][vs_url]["stale"]:
                        break
                    await asyncio.sleep(0.5)
                assert doc["nodes"][vs_url]["stale"], doc["nodes"]
        finally:
            for p in procs:
                if p.returncode is None:
                    p.send_signal(signal.SIGINT)
            for p in procs:
                try:
                    await asyncio.wait_for(p.wait(), 10)
                except asyncio.TimeoutError:
                    p.kill()
                    await p.wait()

    asyncio.run(go())
