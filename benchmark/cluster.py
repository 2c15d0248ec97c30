"""One master and one volume server as real OS processes, and what the
harness asks of them: status, counters, shell verbs, shard RPCs.

Copied from chip_smoke.py at commit acf9d01 (Cluster, wait_http,
device_status, scrape, metric, device_dispatches, check_on_chip,
check_no_failures, wait_resident, wait_master_sees_shards, ec_shards_rpc,
shell) so that later changes to the smoke cannot move the yardstick.
This module never imports JAX: the volume server is the only process
that touches the chip.
"""
from __future__ import annotations

import asyncio
import io
import json
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIB = 1 << 20
PREFIX = "SeaweedFS_" + "volumeServer_"  # every series of the volume server


class BenchFailure(Exception):
    """The run cannot be reported: no chip, a host fallback, a compile in
    the window, a dead child.  Exit code 1, no result line."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise BenchFailure(message)


def say(line: str) -> None:
    print(line, flush=True)


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


class Cluster:
    def __init__(self, work: str, master_flags: list[str],
                 volume_flags: list[str], env: dict[str, str],
                 launcher: list[str] | None = None):
        self.work = work
        self.launcher = launcher or [sys.executable, "-m", "seaweedfs_tpu"]
        self.master_flags = master_flags
        self.volume_flags = volume_flags
        self.data_dir = os.path.join(work, "vol")
        self.meta_dir = os.path.join(work, "meta")
        self.keep_dir = os.path.join(work, "keep")
        self.tmp_dir = os.path.join(work, "tmp")
        for d in (self.data_dir, self.meta_dir, self.keep_dir, self.tmp_dir):
            os.makedirs(d)
        # the children's scratch (profile captures) stays inside `work`
        self.env = {**os.environ, **env, "TMPDIR": self.tmp_dir}
        # JAX's persistent compile cache: where the environment says, else
        # at the program's own fixed path in the checkout.  A server with
        # no device shard cache never configures it in code, so it is
        # given here, with the program's own "cache everything" threshold
        self.env.setdefault(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(REPO, ".jax_compile_cache"))
        self.env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        self.mp, self.mg, self.vp, self.vg = free_ports(4)
        self.master = f"127.0.0.1:{self.mp}.{self.mg}"
        self.master_http = f"127.0.0.1:{self.mp}"
        self.volume_http = f"127.0.0.1:{self.vp}"
        self.volume_grpc = f"127.0.0.1:{self.vg}"
        self.procs: dict[str, subprocess.Popen] = {}

    def _spawn(self, name: str, *argv: str) -> None:
        with open(os.path.join(self.work, f"{name}.log"), "ab") as log:
            self.procs[name] = subprocess.Popen(
                [*self.launcher, *argv],
                cwd=REPO, env=self.env, stdout=log, stderr=subprocess.STDOUT,
            )

    def start_master(self) -> None:
        self._spawn(
            "master", "master", "-port", str(self.mp),
            "-port.grpc", str(self.mg), "-mdir", self.meta_dir,
            *self.master_flags,
        )

    def start_volume(self) -> None:
        self._spawn(
            "volume", "volume", "-port", str(self.vp),
            "-port.grpc", str(self.vg), "-dir", self.data_dir,
            "-mserver", self.master, *self.volume_flags,
        )

    def stop(self, name: str) -> None:
        p = self.procs.pop(name, None)
        if p is None or p.poll() is not None:
            return
        p.send_signal(signal.SIGINT)
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()

    def stop_all(self) -> None:
        for name in list(self.procs):
            self.stop(name)

    def assert_alive(self) -> None:
        for name, p in self.procs.items():
            check(
                p.poll() is None,
                f"{name} process exited with code {p.returncode}:\n"
                + self.log_tail(name),
            )

    def log_tail(self, name: str, nbytes: int = 6000) -> str:
        try:
            with open(os.path.join(self.work, f"{name}.log"), "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"(no log: {e})"

    def base(self, vid: int) -> str:
        return os.path.join(self.data_dir, str(vid))


async def wait_http(session, url: str, cluster: Cluster, timeout: float):
    import aiohttp

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        cluster.assert_alive()
        try:
            async with session.get(url):
                return
        except aiohttp.ClientError:
            await asyncio.sleep(0.25)
    raise BenchFailure(f"{url} did not answer within {timeout:.0f}s")


# ---------------------------------------------------- status and counters


async def volume_status(session, cluster: Cluster) -> dict:
    async with session.get(f"http://{cluster.volume_http}/status") as r:
        check(r.status == 200, f"/status answered HTTP {r.status}")
        return await r.json()


async def device_status(session, cluster: Cluster) -> dict:
    return (await volume_status(session, cluster))["Device"]


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {(name, (label pairs...)): value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = ()
        if rest:
            labels = tuple(
                sorted(
                    (k, v.strip('"'))
                    for k, v in (
                        pair.split("=", 1)
                        for pair in rest.rstrip("}").split(",")
                        if pair
                    )
                )
            )
        out[(name, labels)] = float(value)
    return out


async def scrape(session, cluster: Cluster) -> dict:
    async with session.get(f"http://{cluster.volume_http}/metrics") as r:
        return parse_metrics(await r.text())


def series_sum(samples: dict, name: str, labels: dict | None = None,
               not_labels: dict | None = None) -> float:
    """Sum of every sample of `PREFIX + name` whose labels include
    `labels` and carry none of `not_labels`."""
    total = 0.0
    for (n, pairs), v in samples.items():
        if n != PREFIX + name:
            continue
        have = dict(pairs)
        if any(have.get(k) != v2 for k, v2 in (labels or {}).items()):
            continue
        if any(have.get(k) == v2 for k, v2 in (not_labels or {}).items()):
            continue
        total += v
    return total


def check_on_chip(dev: dict, enforce: bool, chips: int) -> None:
    """The resolved device identity and EC backend; with `enforce` a run
    that is not on a TPU with the Pallas kernels compiled is a failure."""
    check(dev.get("initialised", False), "the volume server could not "
          f"report its device: {dev.get('error', dev)}")
    say(
        f"device: platform={dev['platform']} kind={dev['device_kind']!r} "
        f"count={dev['device_count']} ec_backend={dev['ec_backend']} "
        f"serving_kernel={dev['serving_kernel']} "
        f"interpret={dev['interpret']} "
        f"compile_cache={dev['compile_cache']['path']!r}"
    )
    if not enforce:
        return
    check(
        dev["platform"] == "tpu",
        f"no TPU: JAX found platform {dev['platform']!r} "
        f"({dev['device_kind']}); the benchmark needs the chip",
    )
    check(dev["device_count"] == chips, f"JAX found {dev['device_count']} "
          f"device(s); the cell asks for {chips}")
    check(dev["ec_backend"] == "pallas", f"-ec.backend=auto resolved to "
          f"{dev['ec_backend']!r}, not pallas")
    check(dev["serving_kernel"] == "pallas" and not dev["interpret"],
          "the resident kernels would run interpreted or on the xla kernel")
    # a server without a device shard cache leaves the cache to JAX's
    # environment variable (Cluster sets it) and reports it as not its own
    check(dev["compile_cache"]["enabled"] or "cache" not in dev,
          f"persistent compile cache is off: {dev['compile_cache']}")


def check_no_failures(dev: dict) -> None:
    for kind, rec in dev["failures"].items():
        check(
            rec["count"] == 0,
            f"{rec['count']} {kind} failure(s) on the device path, "
            f"last: {rec['last']}",
        )
    check(dev["aot"]["failed"] == 0,
          f"{dev['aot']['failed']} AOT compile(s) failed")


def compile_cache_counts(dev: dict) -> str:
    cc = dev["compile_cache"]
    return (f"persistent compile cache: {cc['requests']} requests, "
            f"{cc['hits']} hits, {cc['misses']} misses")


async def wait_resident(
    session, cluster: Cluster, vid: int, shards: list[int], shard_size: int,
    timeout: float,
) -> dict:
    """Wait until exactly `shards` of `vid` are resident on the one device
    and its warm plan is done; hold the residency bytes to what the
    server itself reports.  -> status."""
    deadline = time.monotonic() + timeout
    dev = {}
    while time.monotonic() < deadline:
        cluster.assert_alive()
        dev = await device_status(session, cluster)
        check("cache" in dev, "the volume server has no device shard cache")
        check_no_failures(dev)
        vol = dev["cache"]["volumes"].get(str(vid), {})
        if (
            vol.get("resident_shards") == shards
            and vol.get("aot_state") == "done"
            and dev["aot"]["pending"] == 0
        ):
            break
        await asyncio.sleep(0.5)
    else:
        raise BenchFailure(
            f"volume {vid} not resident+warm after {timeout:.0f}s: "
            f"{json.dumps(dev.get('cache', {}).get('volumes'))} "
            f"aot={dev.get('aot')}"
        )
    per_device = [d["used_bytes"] for d in dev["cache"]["per_device"]]
    total = sum(per_device)
    check(total % len(shards) == 0, f"{total} resident bytes do not divide "
          f"into {len(shards)} equal shards")
    padded = total // len(shards)
    # DeviceShardCache pads a shard by one 2 MiB tile of slack, then up
    # to its 64 MiB quantum
    check(
        shard_size + 2 * MIB <= padded < shard_size + 66 * MIB + 64 * 1024,
        f"resident bytes {total} are not {len(shards)} padded shards of "
        f"{shard_size} bytes",
    )
    check(dev["aot"]["compiled"] > 0, "the AOT registry holds no executable")
    return dev


async def ec_shards_rpc(env, cluster, verb: str, vid: int, sids) -> None:
    from seaweedfs_tpu.pb import volume_server_pb2 as pb

    stub = env.volume_stub(cluster.volume_grpc)
    req = getattr(pb, f"VolumeEcShards{verb}Request")
    kw = {} if verb == "Unmount" else {"collection": ""}
    await getattr(stub, f"VolumeEcShards{verb}")(
        req(volume_id=vid, shard_ids=list(sids), **kw), timeout=300.0
    )


async def shell(env, line: str) -> str:
    from seaweedfs_tpu.shell import run_command

    env.out = io.StringIO()
    await run_command(env, line)
    out = env.out.getvalue()
    for row in out.splitlines():
        say(f"  shell> {row}")
    return out


async def wait_master_sees_shards(session, cluster, vid, n: int) -> None:
    """The shell verbs plan from the master's topology, which follows the
    volume server's heartbeats: wait until it has shown `n` shards of
    `vid` for two pulses running."""
    from seaweedfs_tpu.operation.ready import topology_nodes

    deadline = time.monotonic() + 60
    seen_since = None
    while time.monotonic() < deadline:
        url = f"http://{cluster.master_http}/dir/status"
        async with session.get(url) as r:
            nodes = topology_nodes((await r.json())["Topology"])
        bits = 0
        for node in nodes:
            for s in node["ec_shards"]:
                if s["id"] == vid:
                    bits |= s["ec_index_bits"]
        if bin(bits).count("1") != n:
            seen_since = None
        elif seen_since is None:
            seen_since = time.monotonic()
        elif time.monotonic() - seen_since >= 2.5:
            return
        await asyncio.sleep(0.25)
    raise BenchFailure(f"master never settled on {n} shards of volume {vid}")
