#!/usr/bin/env python3
"""Run the erasure-coding main path end to end on the TPU, once.

    python chip_smoke.py            # one chip, every phase
    python chip_smoke.py --chips 4  # four-chip serving mesh vs one device

This script never imports JAX.  It starts the real launchers — `python -m
seaweedfs_tpu master` and `python -m seaweedfs_tpu volume` — and the
volume server is the ONLY process that touches the chip.  The volume
server gets the flags an operator would give it (`-ec.backend=auto
-ec.deviceCacheMB=<budget>`, serving defaults: blockdiag layout, AOT
warm, cold-shape shed on) plus `-ec.ingest.disable`, so that `ec.encode`
is the offline bulk encode of storage/ec/bulk.py and not the seal of a
parity stream computed while the volume filled.  The master runs with
`-ec.repair.disable`: its autonomous repair would otherwise rebuild the
lost shards before the degraded reads and the `ec.rebuild` verb.

Phases (one chip), each timed on its own line, first failure exits:

  load      fill one volume to >= 4 GiB of .dat over the volume server's
            HTTP front door, needles mixed 4 KB - 1 MB, made from --seed
  encode    `ec.encode` through the shell verb; all 14 shards compared
            byte for byte with the host codec (ops/rs_cpu.py, native
            library built fresh) over the whole volume
  pin+warm  wait for the 14 shards to be resident in HBM and the AOT warm
            plan to finish; compile seconds reported
  degraded  delete one data and one parity shard, then GETs at c=16
            across the size mix, every body compared with what was written
  rebuild   `ec.rebuild -force`; the two rebuilt files equal the originals
  scrub     `ec.scrub` clean (per-volume and megakernel), then one byte
            flipped in a parity file is found
  restart   a second volume-server process on the same data: pin + warm
            again, compile seconds reported, and by JAX's own count every
            compile request of that process must be a persistent-cache
            hit (at least one per warm shape, no miss)

Correct bytes are not enough: after each phase the server's own status
and counters must show that the chip did the work (platform tpu, backend
pallas with interpret off, every read on the batched resident route, no
cold-shape shed, no batch fallback, no native-route read, no pin / warm /
AOT failure, device dispatches behind encode, rebuild and scrub).

The last line of standard output is the result object and nothing else.
With no TPU the script exits non-zero before any phase and prints no
result.
"""
from __future__ import annotations

import argparse
import asyncio
import hashlib
import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
NATIVE_DIR = os.path.join(REPO, "seaweedfs_tpu", "native")

SIZE_MIX = (4 << 10, 16 << 10, 64 << 10, 256 << 10, 1 << 20)
# the floor a deployment holds (ISSUE 21): shards of ~410 MiB, twelve
# survivors ~4.8 GiB resident; only the CPU rehearsal goes below it
VOLUME_BYTES = 4 << 30
DEVICE_CACHE_MB = 8192  # -ec.deviceCacheMB: 14 padded shards are 6.1 GiB
READ_CONCURRENCY = 16
LOST_DATA_SHARD, LOST_PARITY_SHARD = 3, 11
FLIPPED_PARITY_SHARD = 12
COOKIE = 0x5EED5EED
MIB = 1 << 20


class SmokeFailure(Exception):
    pass


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(line: str) -> None:
    print(line, flush=True)


# --------------------------------------------------------------- data set


def needle_bytes(seed: int, key: int, size: int) -> bytes:
    import numpy as np

    return np.random.Generator(np.random.PCG64([seed, key])).bytes(size)


def plan_needles(target_bytes: int) -> list[int]:
    """Needle sizes (key i+1 has sizes[i]) cycling through SIZE_MIX until
    the payload alone reaches the target."""
    sizes, total = [], 0
    while total < target_bytes:
        size = SIZE_MIX[len(sizes) % len(SIZE_MIX)]
        sizes.append(size)
        total += size
    return sizes


def fid_of(vid: int, key: int) -> str:
    return f"{vid},{key:x}{COOKIE:08x}"


# ------------------------------------------------------------ the cluster


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
    """One master and one volume server as real OS processes."""

    def __init__(self, work: str, ec_backend: str):
        self.work = work
        self.ec_backend = ec_backend
        self.data_dir = os.path.join(work, "vol")
        self.meta_dir = os.path.join(work, "meta")
        self.keep_dir = os.path.join(work, "keep")
        for d in (self.data_dir, self.meta_dir, self.keep_dir):
            os.makedirs(d)
        self.mp, self.mg, self.vp, self.vg = free_ports(4)
        self.master = f"127.0.0.1:{self.mp}.{self.mg}"
        self.master_http = f"127.0.0.1:{self.mp}"
        self.volume_http = f"127.0.0.1:{self.vp}"
        self.volume_grpc = f"127.0.0.1:{self.vg}"
        self.procs: dict[str, subprocess.Popen] = {}

    def _spawn(self, name: str, *argv: str) -> None:
        log = open(os.path.join(self.work, f"{name}.log"), "ab")
        self.procs[name] = subprocess.Popen(
            [sys.executable, "-m", "seaweedfs_tpu", *argv],
            cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
        )
        log.close()

    def start_master(self) -> None:
        self._spawn(
            "master", "master", "-port", str(self.mp),
            "-port.grpc", str(self.mg), "-mdir", self.meta_dir,
            "-volumeSizeLimitMB", "30000", "-pulseSeconds", "1",
            # the master's autonomous repair would rebuild the two lost
            # shards by itself, under the degraded reads and ahead of
            # the `ec.rebuild` verb this script is here to run
            "-ec.repair.disable",
        )

    def start_volume(self, *extra: str) -> None:
        self._spawn(
            "volume", "volume", "-port", str(self.vp),
            "-port.grpc", str(self.vg), "-dir", self.data_dir,
            "-mserver", self.master, "-pulseSeconds", "1",
            f"-ec.backend={self.ec_backend}",
            f"-ec.deviceCacheMB={DEVICE_CACHE_MB}",
            "-ec.ingest.disable", *extra,
        )

    def stop(self, name: str) -> None:
        p = self.procs.pop(name, None)
        if p is None or p.poll() is not None:
            return
        p.send_signal(signal.SIGINT)
        try:
            p.wait(timeout=90)
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
    raise SmokeFailure(f"{url} did not answer within {timeout:.0f}s")


# ---------------------------------------------------- status and counters


async def device_status(session, cluster: Cluster) -> dict:
    async with session.get(f"http://{cluster.volume_http}/status") as r:
        check(r.status == 200, f"/status answered HTTP {r.status}")
        return (await r.json())["Device"]


async def scrape(session, cluster: Cluster) -> dict:
    """The volume server's /metrics as {(name, (label pairs...)): value}."""
    async with session.get(f"http://{cluster.volume_http}/metrics") as r:
        text = await r.text()
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


def metric(samples: dict, name: str, **labels) -> float:
    return samples.get(
        (f"SeaweedFS_volumeServer_{name}", tuple(sorted(labels.items()))),
        0.0,
    )


def device_dispatches(samples: dict, workload: str) -> float:
    """Dispatches of `workload` that ran on the accelerator: every
    device label except "host" (the CPU-kernel legs)."""
    return sum(
        v for (name, labels), v in samples.items()
        if name == "SeaweedFS_volumeServer_device_dispatches_total"
        and dict(labels).get("workload") == workload
        and dict(labels).get("device") != "host"
    )


def check_on_chip(dev: dict, enforce: bool) -> None:
    """The resolved device identity and EC backend; with `enforce` a run
    that is not on a TPU with the Pallas kernels compiled is a failure."""
    check(dev["initialised"], "the volume server could not report its "
          f"device: {dev.get('error', dev)}")
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
        f"({dev['device_kind']}); chip_smoke.py needs the chip",
    )
    check(dev["ec_backend"] == "pallas", f"-ec.backend=auto resolved to "
          f"{dev['ec_backend']!r}, not pallas")
    check(dev["serving_kernel"] == "pallas" and not dev["interpret"],
          "the resident kernels would run interpreted or on the xla kernel")
    check(dev["compile_cache"]["enabled"],
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


async def wait_resident(
    session, cluster: Cluster, vid: int, shards: list[int], shard_size: int,
    spread_over: int, timeout: float,
) -> tuple[dict, dict]:
    """Wait until exactly `shards` of `vid` are resident and its warm
    plan is done; then hold the residency bytes and the AOT counters to
    what the server itself reports.  -> (status, metrics)."""
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
        raise SmokeFailure(
            f"volume {vid} not resident+warm after {timeout:.0f}s: "
            f"{json.dumps(dev.get('cache', {}).get('volumes'))} "
            f"aot={dev.get('aot')}"
        )
    samples = await scrape(session, cluster)
    per_device = [d["used_bytes"] for d in dev["cache"]["per_device"]]
    check(len(per_device) == spread_over,
          f"cache spans {len(per_device)} device(s), expected {spread_over}")
    gauges = [
        int(metric(samples, "ec_device_cache_bytes", device=str(d)))
        for d in range(spread_over)
    ]
    check(gauges == per_device,
          f"ec_device_cache_bytes {gauges} != status {per_device}")
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
    check(
        len(set(per_device)) == 1,
        f"resident bytes are not spread evenly over the devices: "
        f"{per_device}",
    )
    compiled = int(metric(samples, "ec_aot_compiled_total"))
    check(
        compiled == dev["aot"]["compiled"] and compiled > 0,
        f"ec_aot_compiled_total={compiled} but the AOT registry holds "
        f"{dev['aot']['compiled']} executables",
    )
    return dev, samples


# ----------------------------------------------------------------- phases


async def phase_load(session, cluster, vid, seed, sizes) -> None:
    """POST every needle as a raw body (the volume server's multipart
    parse costs ~10x the append and would turn the load into a
    measurement of email.parser)."""
    next_key = iter(range(1, len(sizes) + 1))
    headers = {"Content-Type": "application/octet-stream"}

    async def worker():
        for key in next_key:
            data = await asyncio.to_thread(
                needle_bytes, seed, key, sizes[key - 1]
            )
            url = f"http://{cluster.volume_http}/{fid_of(vid, key)}"
            async with session.post(url, data=data, headers=headers) as r:
                check(r.status in (200, 201), f"POST {url}: HTTP {r.status} "
                      f"{await r.text()}")

    await asyncio.gather(*(worker() for _ in range(READ_CONCURRENCY)))
    cluster.assert_alive()


def verify_against_host_codec(dat_path: str, base: str) -> int:
    """Every byte of the 14 shard files against the plain reference: the
    .dat striped into 1 MB blocks by hand and parity recomputed by the
    host codec.  -> bytes compared."""
    import numpy as np

    from seaweedfs_tpu.ops import gf256, rs_cpu

    check(rs_cpu.native_available(),
          "native host codec did not load after a fresh build")
    parity_m = gf256.build_matrix(10, 14)[10:]
    dat_size = os.path.getsize(dat_path)
    check(dat_size <= 10 << 30, "volume has 1 GB large-block rows; the "
          "smoke's reference stripes 1 MB rows only")
    row_bytes = 10 * MIB
    n_rows = -(-dat_size // row_bytes)
    shard_size = n_rows * MIB
    paths = [f"{base}.ec{i:02d}" for i in range(14)]
    for p in paths:
        check(os.path.getsize(p) == shard_size,
              f"{p} is {os.path.getsize(p)} bytes, expected {shard_size}")
    rows_per_task = 8

    def compare(first_row: int) -> int:
        n = min(rows_per_task, n_rows - first_row)
        with open(dat_path, "rb") as f:
            f.seek(first_row * row_bytes)
            raw = f.read(n * row_bytes)
        rows = np.zeros(n * row_bytes, dtype=np.uint8)
        rows[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        # [n rows, 10 shards, 1 MiB] -> shard-major [10, n MiB]
        data = np.ascontiguousarray(
            rows.reshape(n, 10, MIB).transpose(1, 0, 2).reshape(10, n * MIB)
        )
        want = np.concatenate(
            [data, rs_cpu.apply_matrix_native(parity_m, data)]
        )
        for i, p in enumerate(paths):
            with open(p, "rb") as f:
                f.seek(first_row * MIB)
                got = np.frombuffer(f.read(n * MIB), dtype=np.uint8)
            if not np.array_equal(got, want[i]):
                raise SmokeFailure(
                    f"{p} differs from the host codec in rows "
                    f"{first_row}..{first_row + n - 1}"
                )
        return 14 * n * MIB

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        return sum(ex.map(compare, range(0, n_rows, rows_per_task)))


def needles_touching_shard(ecx_path: str, dat_size: int, shard: int) -> set:
    """Keys of needles with at least one interval on data shard `shard`
    (only to aim part of the read sample at the lost shard; every body
    is verified whatever it touches)."""
    from seaweedfs_tpu.storage import idx
    from seaweedfs_tpu.storage.ec.layout import locate_data

    with open(ecx_path, "rb") as f:
        ids, offs, sizes = idx.parse_buffer(f.read())
    hit = set()
    for key, off, size in zip(ids.tolist(), offs.tolist(), sizes.tolist()):
        if size <= 0:
            continue
        for iv in locate_data(dat_size, off, size + 32):
            if iv.to_shard_and_offset()[0] == shard:
                hit.add(key)
                break
    return hit


def pick_reads(sizes, on_lost: set, n_reads: int, seed: int) -> list[int]:
    """`n_reads` distinct keys, every size of the mix equally often, as
    many as possible (up to three in four) needing the lost shard."""
    import random

    rng = random.Random(seed)
    by_size: dict[int, tuple[list[int], list[int]]] = {}
    for key, size in enumerate(sizes, start=1):
        by_size.setdefault(size, ([], []))[key not in on_lost].append(key)
    per_size = max(1, n_reads // len(by_size))
    picked = []
    for size in sorted(by_size):
        lost, healthy = by_size[size]
        rng.shuffle(lost)
        rng.shuffle(healthy)
        take = lost[: per_size * 3 // 4 or 1]
        take += healthy[: per_size - len(take)]
        take += lost[len(take):][: per_size - len(take)]
        picked += take[:per_size]
    rng.shuffle(picked)
    return picked


async def phase_reads(session, cluster, vid, seed, sizes, keys) -> dict:
    """GET every key at c=16, compare each body with what was written.
    -> {key: sha256 of the body} for cross-run comparison."""
    digests = {}
    pending = iter(keys)

    async def worker():
        for key in pending:
            url = f"http://{cluster.volume_http}/{fid_of(vid, key)}"
            async with session.get(url) as r:
                body = await r.read()
                check(r.status == 200, f"GET {url}: HTTP {r.status}")
            want = await asyncio.to_thread(
                needle_bytes, seed, key, sizes[key - 1]
            )
            check(body == want, f"GET {url}: body differs from what was "
                  f"written ({len(body)} vs {len(want)} bytes)")
            digests[key] = hashlib.sha256(body).hexdigest()

    await asyncio.gather(*(worker() for _ in range(READ_CONCURRENCY)))
    cluster.assert_alive()
    return digests


def check_read_counters(before, after, n_reads: int, enforce: bool) -> None:
    delta = {
        "batched": metric(after, "ec_read_route_total", route="batched")
        - metric(before, "ec_read_route_total", route="batched"),
        "native": metric(after, "ec_read_route_total", route="native")
        - metric(before, "ec_read_route_total", route="native"),
        "shed_cold_shape": metric(after, "ec_shed_cold_shape_total")
        - metric(before, "ec_shed_cold_shape_total"),
        "batch_fallback": metric(after, "ec_batch_fallback_total")
        - metric(before, "ec_batch_fallback_total"),
        "device_calls": sum(
            metric(after, "ec_device_compile_total", result=r)
            - metric(before, "ec_device_compile_total", result=r)
            for r in ("hit", "miss")
        ),
        "inline_compiles": metric(
            after, "ec_device_compile_total", result="miss"
        ) - metric(before, "ec_device_compile_total", result="miss"),
        "d2h_bytes": metric(after, "ec_device_d2h_bytes_total")
        - metric(before, "ec_device_d2h_bytes_total"),
    }
    say("read counters: " + " ".join(
        f"{k}=+{int(v)}" for k, v in delta.items()))
    check(delta["batched"] == n_reads, f"{int(delta['batched'])} of "
          f"{n_reads} reads took the batched resident route")
    check(delta["native"] == 0,
          f"{int(delta['native'])} reads took the native route")
    check(delta["batch_fallback"] == 0,
          f"{int(delta['batch_fallback'])} batch fallbacks")
    check(delta["device_calls"] > 0,
          "no reconstruct call was dispatched to the device")
    if enforce:
        # off the chip the xla fallback kernel serves, whose fetch
        # ladder the warm plan does not enumerate: sheds are reported
        check(delta["shed_cold_shape"] == 0,
              f"{int(delta['shed_cold_shape'])} intervals shed to the "
              "host codec on a cold shape")
        check(delta["inline_compiles"] == 0,
              f"{int(delta['inline_compiles'])} reconstruct shapes "
              "compiled inline on the serving path")


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
    """The shell verbs plan from the master's topology, which follows
    the volume server's heartbeats: wait until it has shown `n` shards
    of `vid` for two pulses running."""
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
    raise SmokeFailure(f"master never settled on {n} shards of volume {vid}")


def files_equal(a: str, b: str) -> bool:
    if os.path.getsize(a) != os.path.getsize(b):
        return False
    with open(a, "rb") as fa, open(b, "rb") as fb:
        while True:
            ca, cb = fa.read(8 * MIB), fb.read(8 * MIB)
            if ca != cb:
                return False
            if not ca:
                return True


def compile_cache_counts(dev: dict) -> str:
    cc = dev["compile_cache"]
    return (f"persistent compile cache: {cc['requests']} requests, "
            f"{cc['hits']} hits, {cc['misses']} misses")


def warmup_seconds(samples: dict) -> float:
    """Seconds the server spent in AOT warm-plan compiles, by its own
    device ledger (workload "warmup")."""
    return sum(
        v for (name, labels), v in samples.items()
        if name == "SeaweedFS_volumeServer_device_busy_seconds_total"
        and dict(labels).get("workload") == "warmup"
    )


# ------------------------------------------------------------- the script


class Run:
    def __init__(self, args):
        self.args = args
        self.enforce = args.rehearse_mib is None
        self.target = (
            VOLUME_BYTES if self.enforce else args.rehearse_mib * MIB
        )
        self.sizes = plan_needles(self.target)
        self.n_reads = args.reads
        self.timings: dict[str, float] = {}

    async def timed(self, name: str, coro):
        t0 = time.monotonic()
        result = await coro
        self.timings[name] = time.monotonic() - t0
        say(f"phase {name}: {self.timings[name]:.1f} s")
        return result

    async def bring_up(self, session, cluster, *volume_flags) -> dict:
        """Start (or restart) the volume server and report what device
        it found — before any phase, a missing chip ends the run."""
        cluster.start_volume(*volume_flags)
        await wait_http(
            session, f"http://{cluster.volume_http}/status", cluster, 180
        )
        dev = await device_status(session, cluster)
        check_on_chip(dev, self.enforce)
        check(
            dev["device_count"] == self.args.chips or not self.enforce,
            f"JAX found {dev['device_count']} device(s); this run was "
            f"asked for --chips {self.args.chips}",
        )
        return dev

    async def load_and_encode(self, session, cluster, env) -> tuple:
        from seaweedfs_tpu.operation.ready import wait_cluster_ready

        assign = await wait_cluster_ready(cluster.master_http, timeout=120)
        vid = int(assign["fid"].split(",")[0])
        await self.timed("load", phase_load(
            session, cluster, vid, self.args.seed, self.sizes))
        dat_path = os.path.join(cluster.data_dir, f"{vid}.dat")
        dat_size = os.path.getsize(dat_path)
        check(dat_size >= self.target,
              f"{dat_path} holds {dat_size} bytes, target {self.target}")
        say(f"loaded: {len(self.sizes)} needles, {dat_size} bytes of .dat "
            f"({dat_size / (1 << 30):.2f} GiB), sizes "
            f"{'/'.join(str(s >> 10) + 'K' for s in SIZE_MIX)}; "
            "1 MB stripe rows only (the 1 GB large-block row needs "
            "> 10 GiB of .dat and was not run)")
        # ec.encode deletes the .dat: keep its bytes under another name
        kept_dat = os.path.join(cluster.keep_dir, f"{vid}.dat")
        os.link(dat_path, kept_dat)

        before = await scrape(session, cluster)
        await env.acquire_lock()
        out = await self.timed(
            "encode", shell(env, f"ec.encode -volumeId {vid}"))
        check(f"ec encoded volume {vid}" in out, "ec.encode did not finish")
        after = await scrape(session, cluster)
        batches = (
            metric(after, "ec_bulk_batches_total", pipeline="encode")
            - metric(before, "ec_bulk_batches_total", pipeline="encode")
        )
        on_device = (
            device_dispatches(after, "bulk")
            - device_dispatches(before, "bulk")
        )
        say(f"encode counters: bulk_batches=+{int(batches)} "
            f"device_dispatches{{bulk}}=+{int(on_device)}")
        check(batches > 0, "ec.encode ran no bulk pipeline batch")
        check(on_device == batches, f"{int(on_device)} of {int(batches)} "
              "encode batches ran on the device")
        base = os.path.join(cluster.data_dir, str(vid))
        compared = await self.timed(
            "encode_verify",
            asyncio.to_thread(verify_against_host_codec, kept_dat, base),
        )
        say(f"encode verified: {compared} shard bytes byte-equal to the "
            f"host codec ({dat_size} bytes of volume)")
        shard_size = os.path.getsize(base + ".ec00")
        return vid, dat_size, shard_size, base

    async def lose_two_shards(self, session, cluster, env, vid, base,
                              shard_size, spread_over):
        lost = (LOST_DATA_SHARD, LOST_PARITY_SHARD)
        for sid in lost:
            os.link(f"{base}.ec{sid:02d}",
                    os.path.join(cluster.keep_dir, f"ec{sid:02d}"))
        await ec_shards_rpc(env, cluster, "Delete", vid, lost)
        for sid in lost:
            check(not os.path.exists(f"{base}.ec{sid:02d}"),
                  f"shard {sid} file survived its delete")
        survivors = [s for s in range(14) if s not in lost]
        await wait_resident(
            session, cluster, vid, survivors, shard_size, spread_over, 120)
        say(f"lost shards {list(lost)}: {len(survivors)} survivors resident")
        return survivors

    async def degraded_reads(self, session, cluster, vid, keys) -> dict:
        before = await scrape(session, cluster)
        digests = await self.timed("degraded_read", phase_reads(
            session, cluster, vid, self.args.seed, self.sizes, keys))
        after = await scrape(session, cluster)
        say(f"degraded reads: {len(digests)} GETs at c={READ_CONCURRENCY}, "
            f"{sum(self.sizes[k - 1] for k in keys)} bytes, all byte-equal "
            "to what was written")
        check_read_counters(before, after, len(keys), self.enforce)
        return digests

    async def one_chip(self, session, cluster, env) -> None:
        args = self.args
        t_up = time.monotonic()
        await self.bring_up(session, cluster)
        vid, dat_size, shard_size, base = await self.load_and_encode(
            session, cluster, env)

        all_shards = list(range(14))
        dev, samples = await self.timed("pin_warm", wait_resident(
            session, cluster, vid, all_shards, shard_size, 1, 900))
        cold_plan = dev["aot"]["compiled"]
        cold_s = warmup_seconds(samples)
        resident = sum(d["used_bytes"] for d in dev["cache"]["per_device"])
        say(f"warm plan (first process): {cold_plan} shapes AOT-compiled, "
            f"{cold_s:.1f} s of trace+lower+compile; "
            f"{compile_cache_counts(dev)}; {resident} bytes resident for "
            "14 shards")

        on_lost = await asyncio.to_thread(
            needles_touching_shard, base + ".ecx", dat_size, LOST_DATA_SHARD)
        keys = pick_reads(self.sizes, on_lost, self.n_reads, args.seed)
        say(f"read sample: {len(keys)} needles, "
            f"{sum(k in on_lost for k in keys)} of them with bytes on the "
            f"lost data shard {LOST_DATA_SHARD}")
        await self.lose_two_shards(
            session, cluster, env, vid, base, shard_size, 1)
        await self.degraded_reads(session, cluster, vid, keys)

        # rebuild
        await wait_master_sees_shards(session, cluster, vid, 12)
        before = await scrape(session, cluster)
        out = await self.timed("rebuild", shell(env, "ec.rebuild -force"))
        rebuilt = [LOST_DATA_SHARD, LOST_PARITY_SHARD]
        check(f"rebuilt {rebuilt}" in out, "ec.rebuild did not rebuild "
              f"{rebuilt}")
        after = await scrape(session, cluster)
        batches = (
            metric(after, "ec_bulk_batches_total", pipeline="rebuild")
            - metric(before, "ec_bulk_batches_total", pipeline="rebuild")
        )
        on_device = (
            device_dispatches(after, "repair")
            - device_dispatches(before, "repair")
        )
        say(f"rebuild counters: bulk_batches=+{int(batches)} "
            f"device_dispatches{{repair}}=+{int(on_device)}")
        check(batches > 0 and on_device == batches, f"{int(on_device)} of "
              f"{int(batches)} rebuild batches ran on the device")
        for sid in rebuilt:
            check(
                await asyncio.to_thread(
                    files_equal, f"{base}.ec{sid:02d}",
                    os.path.join(cluster.keep_dir, f"ec{sid:02d}")),
                f"rebuilt shard {sid} differs from the original",
            )
        say(f"rebuild verified: shards {rebuilt} byte-equal to the "
            f"originals ({2 * shard_size} bytes)")

        # scrub (the shell verb asks the master who holds all 14 shards)
        await wait_resident(
            session, cluster, vid, all_shards, shard_size, 1, 300)
        await wait_master_sees_shards(session, cluster, vid, 14)
        before = await scrape(session, cluster)
        t0 = time.monotonic()
        out = await shell(env, f"ec.scrub -volumeId {vid}")
        check("OK backend=device_resident" in out,
              "per-volume scrub was not clean on the device backend")
        out = await shell(env, "ec.scrub")
        check("OK backend=device_megakernel" in out,
              "megakernel scrub was not clean on the device backend")
        path = f"{base}.ec{FLIPPED_PARITY_SHARD:02d}"
        flip_at = (args.seed * 2654435761) % shard_size
        with open(path, "r+b") as f:
            f.seek(flip_at)
            byte = f.read(1)[0]
            f.seek(flip_at)
            f.write(bytes([byte ^ 0x40]))
        # the resident copy is what scrub reads: pin the file again
        await ec_shards_rpc(
            env, cluster, "Unmount", vid, [FLIPPED_PARITY_SHARD])
        await wait_master_sees_shards(session, cluster, vid, 13)
        await ec_shards_rpc(
            env, cluster, "Mount", vid, [FLIPPED_PARITY_SHARD])
        await wait_resident(
            session, cluster, vid, all_shards, shard_size, 1, 300)
        await wait_master_sees_shards(session, cluster, vid, 14)
        out = await shell(env, f"ec.scrub -volumeId {vid}")
        want = [0] * 4
        want[FLIPPED_PARITY_SHARD - 10] = 1
        check(f"CORRUPT: {want} mismatch bytes backend=device_resident"
              in out, f"scrub did not find exactly the flipped byte of "
              f"shard {FLIPPED_PARITY_SHARD} on the device backend")
        self.timings["scrub"] = time.monotonic() - t0
        say(f"phase scrub: {self.timings['scrub']:.1f} s")
        after = await scrape(session, cluster)
        scrub_calls = {
            mode: int(
                metric(after, "ec_scrub_device_dispatch_total", mode=mode)
                - metric(before, "ec_scrub_device_dispatch_total", mode=mode)
            )
            for mode in ("per_volume", "megakernel")
        }
        say(f"scrub counters: device dispatches {scrub_calls}; clean twice, "
            f"then byte {flip_at} of shard {FLIPPED_PARITY_SHARD} found")
        check(all(scrub_calls.values()), "a scrub mode dispatched nothing")
        check_no_failures(await device_status(session, cluster))
        say(f"first process: {time.monotonic() - t_up:.1f} s from launch")

        # restart: the same data, the persistent compile cache warm
        cluster.stop("volume")
        await self.bring_up(session, cluster)
        dev, samples = await self.timed("restart_pin_warm", wait_resident(
            session, cluster, vid, all_shards, shard_size, 1, 900))
        warm_s = warmup_seconds(samples)
        say(f"warm plan (restarted process): {dev['aot']['compiled']} "
            f"shapes, {warm_s:.1f} s of trace+lower+cache load (first "
            f"process: {cold_s:.1f} s); {compile_cache_counts(dev)}")
        check(dev["aot"]["compiled"] == cold_plan, "the restarted server's "
              f"warm plan has {dev['aot']['compiled']} shapes, the first "
              f"had {cold_plan}")
        # a time would not do (the machine may come with the cache
        # already filled, and then the first process is warm too), nor
        # would a count of files (a read-side miss rewrites the same
        # key): JAX's own hit and miss events of the restarted process
        cc = dev["compile_cache"]
        check(cc["hits"] >= cold_plan, f"{cc['hits']} persistent-cache hits "
              f"in the restarted process for a warm plan of {cold_plan}")
        check(cc["misses"] == 0 and cc["requests"] == cc["hits"],
              f"the restarted process compiled {cc['requests'] - cc['hits']} "
              f"program(s) again ({cc['misses']} written back): the "
              "persistent compile cache did not hit")

    async def four_chips(self, session, cluster, env) -> None:
        """The lane-sharded serving mesh users get by default on a
        four-chip host, against -ec.serving.mesh.disable on one device
        of the same host."""
        args = self.args
        n_dev = args.chips
        dev = await self.bring_up(session, cluster)
        if not self.enforce:
            n_dev = dev["device_count"]
        vid, dat_size, shard_size, base = await self.load_and_encode(
            session, cluster, env)
        on_lost = await asyncio.to_thread(
            needles_touching_shard, base + ".ecx", dat_size, LOST_DATA_SHARD)
        keys = pick_reads(self.sizes, on_lost, self.n_reads, args.seed)

        dev, samples = await self.timed("mesh_pin_warm", wait_resident(
            session, cluster, vid, list(range(14)), shard_size, n_dev, 1500))
        check(dev["cache"]["volumes"][str(vid)]["placement"] == "mesh",
              f"volume {vid} is not lane-sharded: placement "
              f"{dev['cache']['volumes'][str(vid)]['placement']}")
        say(f"mesh: 14 shards lane-sharded over {n_dev} devices, per-device "
            f"bytes {[d['used_bytes'] for d in dev['cache']['per_device']]}; "
            f"{dev['aot']['compiled']} shapes AOT-compiled in "
            f"{warmup_seconds(samples):.1f} s of compile; "
            f"{compile_cache_counts(dev)}")
        await self.lose_two_shards(
            session, cluster, env, vid, base, shard_size, n_dev)
        mesh_digests = await self.degraded_reads(session, cluster, vid, keys)

        cluster.stop("volume")
        await self.bring_up(session, cluster, "-ec.serving.mesh.disable")
        survivors = [s for s in range(14)
                     if s not in (LOST_DATA_SHARD, LOST_PARITY_SHARD)]
        dev, samples = await self.timed("one_device_pin_warm", wait_resident(
            session, cluster, vid, survivors, shard_size, 1, 1500))
        say(f"control: 12 shards whole on one device "
            f"({dev['cache']['per_device'][0]['used_bytes']} bytes); "
            f"{dev['aot']['compiled']} shapes AOT-compiled in "
            f"{warmup_seconds(samples):.1f} s of compile; "
            f"{compile_cache_counts(dev)}")
        one_digests = await self.degraded_reads(session, cluster, vid, keys)
        check(mesh_digests == one_digests,
              "mesh and one-device reads returned different bytes")
        say(f"mesh vs one device: {len(keys)} reads byte-equal")

    async def run(self) -> dict:
        import aiohttp

        from seaweedfs_tpu.shell import CommandEnv

        work = tempfile.mkdtemp(prefix="chip_smoke_")
        # off the chip `auto` resolves to the host codec: the rehearsal
        # names the Pallas backend (interpreted there) so that the bulk
        # device legs run and their counters are checked on the CPU too
        cluster = Cluster(work, "auto" if self.enforce else "pallas")
        try:
            timeout = aiohttp.ClientTimeout(total=600)
            async with aiohttp.ClientSession(timeout=timeout) as session:
                cluster.start_master()
                await wait_http(
                    session, f"http://{cluster.master_http}/cluster/status",
                    cluster, 60,
                )
                env = CommandEnv([cluster.master], out=io.StringIO())
                if self.args.chips == 1:
                    await self.one_chip(session, cluster, env)
                else:
                    await self.four_chips(session, cluster, env)
                dev = await device_status(session, cluster)
                check_no_failures(dev)
                return dev
        except BaseException:
            sys.stderr.write(
                "---- volume server log tail ----\n"
                + cluster.log_tail("volume") + "\n"
            )
            # the chip tool brings chiprun_out/ back: keep more of the
            # logs than fits the end of the output
            out_dir = os.path.join(REPO, "chiprun_out")
            os.makedirs(out_dir, exist_ok=True)
            for name in ("master", "volume"):
                with open(os.path.join(
                        out_dir, f"chip_smoke_{name}.log"), "w") as f:
                    f.write(cluster.log_tail(name, 1 << 20))
            raise
        finally:
            cluster.stop_all()
            shutil.rmtree(work, ignore_errors=True)


def build_native() -> None:
    """Build libswfs_native.so fresh from the committed sources: the
    comparison side must be the C++ host codec, never whatever .so was
    lying in the checkout and never the numpy fallback.  Built under
    another name and renamed into place, so that a process loading the
    library meanwhile never sees half a file."""
    t0 = time.monotonic()
    tmp = f"libswfs_native.so.build.{os.getpid()}"
    subprocess.run(
        ["make", "-C", NATIVE_DIR, "-B", f"lib={tmp}"], check=True,
        stdout=subprocess.DEVNULL,
    )
    os.replace(
        os.path.join(NATIVE_DIR, tmp),
        os.path.join(NATIVE_DIR, "libswfs_native.so"),
    )
    say(f"native host codec built in {time.monotonic() - t0:.1f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the four-chip serving mesh and its "
                    "one-device control")
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--reads", type=int, default=400,
                    help="degraded GETs")
    # the tier-1 CPU rehearsal's option (tests/test_chip_smoke.py): a
    # tiny volume, device identity reported and not enforced
    ap.add_argument("--rehearse-mib", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not os.path.isdir(NATIVE_DIR):
        sys.stderr.write(
            f"chip_smoke.py: {NATIVE_DIR} not found; run it from the root "
            "of a checkout of the repository\n"
        )
        return 2
    sys.path.insert(0, REPO)
    build_native()
    t0 = time.monotonic()
    try:
        dev = asyncio.run(Run(args).run())
    except SmokeFailure as e:
        sys.stderr.write(f"chip_smoke.py: FAILED: {e}\n")
        return 1
    say(f"total: {time.monotonic() - t0:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev["platform"],
            "kind": dev["device_kind"],
            "count": dev["device_count"],
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
