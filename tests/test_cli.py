"""CLI command registry + TOML config layering.

Reference: weed/command/command.go:11-45 (registry), util/config.go
(<name>.toml discovery in ./, ~/.seaweedfs/, /etc/seaweedfs/).  The
two-process launch path (master + volume from separate shells, benchmark +
admin shell against them) is exercised in test_cli_two_process below at
reduced scale.
"""
import asyncio
import os
import subprocess
import sys
import time

import pytest

from seaweedfs_tpu.command import COMMANDS
from seaweedfs_tpu.utils import config as config_util

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_registry_covers_core_commands():
    for name in ("master", "volume", "filer", "s3", "server", "shell",
                 "benchmark", "scaffold", "version"):
        assert name in COMMANDS
        mod = COMMANDS[name]
        assert mod.HELP and callable(mod.add_args) and callable(mod.run)


def test_argparse_surfaces():
    import argparse

    for name, mod in COMMANDS.items():
        p = argparse.ArgumentParser(prog=name)
        mod.add_args(p)  # must not raise


def test_config_discovery(tmp_path):
    pytest.importorskip("tomllib")  # py3.11+ stdlib; config gates without it
    sec = tmp_path / "security.toml"
    sec.write_text('[jwt.signing]\nkey = "abc123"\nexpires_after_seconds = 9\n')
    assert config_util.find_config("security", dirs=(str(tmp_path),)) == str(sec)
    cfg = config_util.load_config("security", dirs=(str(tmp_path),))
    assert config_util.get_path(cfg, "jwt.signing.key") == "abc123"
    assert config_util.get_path(cfg, "jwt.signing.expires_after_seconds") == 9
    assert config_util.get_path(cfg, "nope.nope", "dflt") == "dflt"
    assert config_util.jwt_signing_key(dirs=(str(tmp_path),)) == "abc123"
    # first hit wins across the search path
    assert config_util.jwt_signing_key(dirs=("/nonexistent", str(tmp_path))) == "abc123"
    assert config_util.jwt_signing_key(dirs=("/nonexistent",)) == ""


def test_scaffold_templates_parse(capsys):
    tomllib = pytest.importorskip("tomllib")

    from seaweedfs_tpu.command import scaffold

    for which in scaffold.TEMPLATES:
        tomllib.loads(scaffold.TEMPLATES[which])


def _free_ports(n):
    """Distinct ephemeral ports: fixed numbers collide on busy hosts (this
    suite runs while benchmarks and sibling tests hold sockets)."""
    import socket

    socks, ports = [], []
    for _ in range(n):
        sk = socket.socket()
        sk.bind(("127.0.0.1", 0))
        socks.append(sk)
        ports.append(sk.getsockname()[1])
    for sk in socks:
        sk.close()
    return ports


def _spawn(args, cwd):
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu", *args],
        cwd=cwd,
        env=env,
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )


def _wait_http(url, timeout=15.0):
    import urllib.request

    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(url, timeout=1) as r:
                return r.read()
        except Exception:  # noqa: BLE001
            time.sleep(0.3)
    raise TimeoutError(url)


def test_cli_two_process(tmp_path):
    """Launch master and volume as real separate processes from the CLI,
    write/read through them, and drive the admin shell over a pipe."""
    vol_dir = tmp_path / "v1"
    vol_dir.mkdir()
    mport, vport = _free_ports(2)
    master = _spawn(["master", "-port", str(mport)], str(tmp_path))
    volume = None
    try:
        _wait_http(f"http://127.0.0.1:{mport}/cluster/status")
        volume = _spawn(
            [
                "volume", "-port", str(vport), "-dir", str(vol_dir),
                "-mserver", f"127.0.0.1:{mport}", "-ec.backend", "cpu",
                "-max", "2",
            ],
            str(tmp_path),
        )
        _wait_http(f"http://127.0.0.1:{vport}/status")

        async def roundtrip():
            from seaweedfs_tpu.operation import assign, upload_data
            import aiohttp

            deadline = time.time() + 15
            while True:
                try:
                    a = await assign(f"127.0.0.1:{mport}")
                    break
                except RuntimeError:
                    if time.time() > deadline:
                        raise
                    await asyncio.sleep(0.5)
            await upload_data(f"http://{a.url}/{a.fid}", b"cli-e2e", "f.txt", jwt=a.auth)
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://{a.url}/{a.fid}") as r:
                    assert r.status == 200
                    assert await r.read() == b"cli-e2e"

        asyncio.run(roundtrip())

        shell = _spawn(["shell", "-master", f"127.0.0.1:{mport}"], str(tmp_path))
        out, _ = shell.communicate(b"", timeout=30)
        # repl banner proves the shell connected and exited cleanly on EOF
        assert b"seaweedfs-tpu shell" in out
        assert shell.returncode == 0
    finally:
        for p in (volume, master):
            if p is not None:
                p.terminate()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()


def test_cli_shell_runs_commands(tmp_path):
    """cluster.ps / volume.list through the piped REPL."""
    mport, vport = _free_ports(2)
    vol_dir = tmp_path / "v1"
    vol_dir.mkdir()
    master = _spawn(["master", "-port", str(mport)], str(tmp_path))
    volume = None
    try:
        _wait_http(f"http://127.0.0.1:{mport}/cluster/status")
        volume = _spawn(
            ["volume", "-port", str(vport), "-dir", str(vol_dir),
             "-mserver", f"127.0.0.1:{mport}", "-ec.backend", "cpu",
             "-pulseSeconds", "1"],
            str(tmp_path),
        )
        _wait_http(f"http://127.0.0.1:{vport}/status")
        # wait until the heartbeat registered the node at the master
        deadline = time.time() + 15
        while time.time() < deadline:
            body = _wait_http(f"http://127.0.0.1:{mport}/dir/status")
            if f"127.0.0.1:{vport}".encode() in body:
                break
            time.sleep(0.3)
        shell = _spawn(["shell", "-master", f"127.0.0.1:{mport}"], str(tmp_path))
        out, _ = shell.communicate(b"cluster.ps\n", timeout=30)
        assert f"127.0.0.1:{vport}".encode() in out
    finally:
        for p in (volume, master):
            if p is not None:
                p.terminate()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()


def test_status_probe_does_not_initialise_jax(tmp_path):
    """/status is a readiness probe: on a server that does not use the
    device (-ec.backend=cpu, no device cache, ingest plane off) its
    Device block says so and JAX is never imported — a probe must not
    be what takes the chip.  Once the process has resolved an `auto`
    backend (the default ingest plane does), the identity is there."""
    script = f"""
import sys
from seaweedfs_tpu.ingest import IngestConfig
from seaweedfs_tpu.server.volume import VolumeServer

vs = VolumeServer(["127.0.0.1:1"], [{str(tmp_path)!r}], ec_backend="cpu",
                  ec_ingest=IngestConfig(enabled=False))
assert vs._device_status() == {{"initialised": False, "ec_backend": "cpu"}}
assert "jax" not in sys.modules
vs.store.close()
vs = VolumeServer(["127.0.0.1:1"], [{str(tmp_path)!r}], ec_backend="cpu")
dev = vs._device_status()
assert dev["initialised"] and dev["platform"] == "cpu", dev
assert dev["ec_backend"] in ("native", "numpy") and dev["device_count"] >= 1
assert set(dev["failures"]) == {{"pin", "warm", "aot"}}
assert dev["compile_cache"]["hits"] == 0
"""
    r = subprocess.run(
        [sys.executable, "-c", script], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert r.returncode == 0, r.stderr[-2000:]
