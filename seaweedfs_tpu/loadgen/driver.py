"""Async load drivers: closed-loop HTTP/S3 readers with adversarial
client behaviors (dribble, churn), byte verification, and latency
collection.

Connection model: each of `scenario.connections` workers owns ONE
aiohttp session with a single-connection pool, so N workers are N real
TCP connections to the front door (not N coroutines multiplexed over a
shared pool) — churn tears the socket down and reconnects, dribble
drains the response body slower than the server's stall budget allows.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

import numpy as np

from ..obs.trace import TRACE_HEADER
from .workload import LoadScenario, ZipfPicker, percentile_ms, plan_keys


@dataclass
class LoadResult:
    """One load level's outcome (all reads byte-verified when asked)."""

    connections: int
    reads_ok: int = 0
    errors: int = 0
    verify_failures: int = 0
    slow_connections: int = 0
    churns: int = 0
    bytes_read: int = 0
    wall_s: float = 0.0
    latencies_s: list = field(default_factory=list)
    # mixed read/write leg (scenario.write_frac > 0)
    writes_ok: int = 0
    write_errors: int = 0
    bytes_written: int = 0
    write_latencies_s: list = field(default_factory=list)
    # forensics hooks: per-worker slowest op's server-assigned trace id
    # (wid -> (latency_s, trace_id)) — each id resolves via
    # /debug/critpath or `volume.trace.why -id` while the tail ring
    # still pins it, so a bad level in a sweep names its own culprits
    slow_read_trace: dict = field(default_factory=dict)
    slow_write_trace: dict = field(default_factory=dict)

    def note_trace(self, table: dict, wid: int, lat_s: float, header: str):
        """Keep the slowest op's trace id per worker.  `header` is the
        raw X-Seaweed-Trace-Id response value ('<trace_id>-<span_id>')."""
        tid = header.partition("-")[0]
        if tid and (wid not in table or lat_s > table[wid][0]):
            table[wid] = (lat_s, tid)

    @staticmethod
    def _trace_exemplars(table: dict) -> list:
        return [
            {"worker": w, "ms": round(lat * 1e3, 3), "trace_id": tid}
            for w, (lat, tid) in sorted(
                table.items(), key=lambda kv: -kv[1][0]
            )
        ]

    @property
    def reads_per_s(self) -> float:
        return round(self.reads_ok / self.wall_s, 1) if self.wall_s else 0.0

    @property
    def writes_per_s(self) -> float:
        return round(self.writes_ok / self.wall_s, 1) if self.wall_s else 0.0

    @property
    def ingest_mb_per_s(self) -> float:
        if not self.wall_s:
            return 0.0
        return round(self.bytes_written / self.wall_s / 2**20, 3)

    def summary(self) -> dict:
        d = {
            "connections": self.connections,
            "reads_ok": self.reads_ok,
            "errors": self.errors,
            "verify_failures": self.verify_failures,
            "slow_connections": self.slow_connections,
            "churns": self.churns,
            "bytes_read": self.bytes_read,
            "wall_s": round(self.wall_s, 3),
            "reads_per_s": self.reads_per_s,
            "p50_ms": percentile_ms(self.latencies_s, 50),
            "p99_ms": percentile_ms(self.latencies_s, 99),
        }
        if self.slow_read_trace:
            d["slowest_read_traces"] = self._trace_exemplars(
                self.slow_read_trace
            )
        if self.writes_ok or self.write_errors:
            d.update({
                "writes_ok": self.writes_ok,
                "write_errors": self.write_errors,
                "bytes_written": self.bytes_written,
                "writes_per_s": self.writes_per_s,
                "ingest_mb_per_s": self.ingest_mb_per_s,
                "write_p50_ms": percentile_ms(self.write_latencies_s, 50),
                "write_p99_ms": percentile_ms(self.write_latencies_s, 99),
            })
            if self.slow_write_trace:
                d["slowest_write_traces"] = self._trace_exemplars(
                    self.slow_write_trace
                )
        return d


async def _run_load(
    url_of,
    expected,
    scenario: LoadScenario,
    headers: dict,
    volume_of=None,
) -> LoadResult:
    """Shared closed-loop engine: `url_of(key) -> url`, `expected(key) ->
    bytes|None` (None = skip verification for that key)."""
    import aiohttp

    keys = scenario.extra.get("keys")
    if keys is None:
        raise ValueError("scenario.extra['keys'] must list the key space")
    picks = plan_keys(list(keys), scenario, volume_of=volume_of)
    result = LoadResult(connections=scenario.connections)
    n_slow = int(scenario.connections * scenario.slow_client_frac)
    result.slow_connections = n_slow
    # shard the planned sequence across workers without reordering the
    # skew (worker w takes picks[w::N])
    shards = [picks[w :: scenario.connections] for w in range(scenario.connections)]

    def new_session():
        return aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=1),
            timeout=aiohttp.ClientTimeout(total=120),
        )

    async def worker(wid: int, my_picks: list) -> None:
        slow = wid < n_slow
        rng = np.random.default_rng(scenario.seed * 7919 + wid)
        session = new_session()
        try:
            for key in my_picks:
                if scenario.churn > 0 and rng.random() < scenario.churn:
                    await session.close()
                    session = new_session()
                    result.churns += 1
                t0 = time.perf_counter()
                try:
                    async with session.get(url_of(key), headers=headers) as r:
                        trace_hdr = r.headers.get(TRACE_HEADER, "")
                        if slow:
                            parts = []
                            while True:
                                c = await r.content.read(
                                    scenario.dribble_chunk
                                )
                                if not c:
                                    break
                                parts.append(c)
                                await asyncio.sleep(scenario.dribble_delay_s)
                            body = b"".join(parts)
                        else:
                            body = await r.read()
                        if r.status != 200:
                            result.errors += 1
                            continue
                        clen = r.headers.get("Content-Length")
                        if clen is not None and len(body) != int(clen):
                            # truncated transfer (stall abort, server
                            # reset): an ERROR, not a corruption — the
                            # verify counter must only mean wrong BYTES
                            result.errors += 1
                            continue
                except Exception:  # noqa: BLE001 — a failed read is the
                    # datum (sheds, stall disconnects, churn races)
                    result.errors += 1
                    continue
                lat = time.perf_counter() - t0
                result.latencies_s.append(lat)
                result.note_trace(result.slow_read_trace, wid, lat, trace_hdr)
                result.bytes_read += len(body)
                if scenario.verify:
                    want = expected(key)
                    if want is not None and body != want:
                        result.verify_failures += 1
                        continue
                result.reads_ok += 1
        finally:
            await session.close()

    t0 = time.perf_counter()
    # named, retained tasks + return_exceptions: a crashing worker must
    # not leave the other N-1 connections running unawaited behind an
    # early-raising gather (graftlint GL111's leak class) — every worker
    # finishes (or fails) before the sweep's wall clock stops, then the
    # first real error is re-raised with its worker attributed
    workers = [
        asyncio.ensure_future(worker(w, shards[w]))
        for w in range(scenario.connections)
    ]
    outcomes = await asyncio.gather(*workers, return_exceptions=True)
    result.wall_s = time.perf_counter() - t0
    for wid, out in enumerate(outcomes):
        if isinstance(out, BaseException):
            raise RuntimeError(
                f"load worker {wid}/{scenario.connections} crashed"
            ) from out
    return result


async def run_http_load(
    volume_url: str,
    blobs: dict,
    scenario: LoadScenario,
) -> LoadResult:
    """Drive the volume server's HTTP data plane directly: `blobs` maps
    fid -> expected payload bytes (or None to skip verification).  The
    QoS tier rides the X-Seaweed-QoS header."""
    scenario.extra.setdefault("keys", list(blobs))
    headers = {"X-Seaweed-QoS": scenario.tier}
    return await _run_load(
        lambda fid: f"http://{volume_url}/{fid}",
        blobs.get,
        scenario,
        headers,
        volume_of=lambda fid: fid.split(",")[0],
    )


async def run_mixed_http_load(
    master: str,
    volume_url: str,
    blobs: dict,
    scenario: LoadScenario,
    collection: str = "",
    written: dict | None = None,
) -> LoadResult:
    """Closed-loop MIXED read/write against the volume data plane (the
    reference `weed benchmark` shape, interleaved instead of
    write-phase-then-read-phase): each op is an upload with probability
    `scenario.write_frac`, else a read.  Writes assign fresh fids from
    the master, ride the scenario's X-Seaweed-QoS tier into ingest
    admission, and feed the written key straight back into the SHARED
    read key stream — so reads increasingly land on volumes whose
    stripe rows are being encoded under them, which is exactly the
    contention the ingest plane must not let bleed into read p99.

    `blobs` seeds the key space (fid -> bytes, all served by
    `volume_url`); every write's payload is deterministic from the
    worker rng and byte-verified on later reads like any seed key.
    `written`, when passed, collects every successful write as
    fid -> (holder_url, payload) so the caller can read back EVERY
    written byte after the run (a caller's readback check)."""
    import aiohttp

    from ..operation import assign, upload_data

    result = LoadResult(connections=scenario.connections)
    # shared mutable key space: list for rank order, dicts for payload
    # and holder; appends only, under the event loop (no lock needed)
    keys: list[str] = list(blobs)
    store: dict[str, bytes] = dict(blobs)
    holder: dict[str, str] = {}
    sizes = [int(s) for s in (scenario.write_sizes or [4096])]
    if any(s <= 0 for s in sizes):
        raise ValueError("write_sizes must be positive")
    headers = {"X-Seaweed-QoS": scenario.tier}
    # shard the op budget like _run_load shards picks
    ops_of = [
        len(range(w, scenario.reads, scenario.connections))
        for w in range(scenario.connections)
    ]

    def new_session():
        return aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=1),
            timeout=aiohttp.ClientTimeout(total=120),
        )

    async def do_write(wid: int, seq: int, rng, session) -> None:
        size = sizes[int(rng.integers(0, len(sizes)))]
        data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        t0 = time.perf_counter()
        try:
            a = await assign(master, collection=collection)
            up = await upload_data(
                f"http://{a.url}/{a.fid}", data, f"mix{wid}_{seq}",
                compress=False, jwt=a.auth, session=session,
                headers=headers,
            )
        except Exception:  # noqa: BLE001 — a refused write (429/504
            # ingest shed, dead server) is the datum
            result.write_errors += 1
            return
        lat = time.perf_counter() - t0
        result.write_latencies_s.append(lat)
        result.note_trace(
            result.slow_write_trace, wid, lat, up.get("traceId", "")
        )
        result.bytes_written += len(data)
        result.writes_ok += 1
        store[a.fid] = data
        holder[a.fid] = a.url
        keys.append(a.fid)
        if written is not None:
            written[a.fid] = (a.url, data)

    async def do_read(wid: int, key: str, rng, session) -> None:
        url = holder.get(key, volume_url)
        t0 = time.perf_counter()
        try:
            async with session.get(
                f"http://{url}/{key}", headers=headers
            ) as r:
                trace_hdr = r.headers.get(TRACE_HEADER, "")
                body = await r.read()
                if r.status != 200:
                    result.errors += 1
                    return
                clen = r.headers.get("Content-Length")
                if clen is not None and len(body) != int(clen):
                    result.errors += 1
                    return
        except Exception:  # noqa: BLE001
            result.errors += 1
            return
        lat = time.perf_counter() - t0
        result.latencies_s.append(lat)
        result.note_trace(result.slow_read_trace, wid, lat, trace_hdr)
        result.bytes_read += len(body)
        if scenario.verify and body != store[key]:
            result.verify_failures += 1
            return
        result.reads_ok += 1

    async def worker(wid: int, n_ops: int) -> None:
        rng = np.random.default_rng(scenario.seed * 7919 + wid)
        picker = ZipfPicker(scenario.zipf_s)
        session = new_session()
        try:
            for seq in range(n_ops):
                if scenario.churn > 0 and rng.random() < scenario.churn:
                    await session.close()
                    session = new_session()
                    result.churns += 1
                if keys and rng.random() >= scenario.write_frac:
                    await do_read(
                        wid, keys[picker.pick(len(keys), rng)], rng, session
                    )
                else:
                    await do_write(wid, seq, rng, session)
        finally:
            await session.close()

    t0 = time.perf_counter()
    workers = [
        asyncio.ensure_future(worker(w, ops_of[w]))
        for w in range(scenario.connections)
    ]
    outcomes = await asyncio.gather(*workers, return_exceptions=True)
    result.wall_s = time.perf_counter() - t0
    for wid, out in enumerate(outcomes):
        if isinstance(out, BaseException):
            raise RuntimeError(
                f"mixed load worker {wid}/{scenario.connections} crashed"
            ) from out
    return result


async def run_s3_load(
    s3_url: str,
    bucket: str,
    objects: dict,
    scenario: LoadScenario,
) -> LoadResult:
    """Drive the S3 gateway's GetObject path: `objects` maps key ->
    expected bytes (or None).  Anonymous requests (the harness targets
    an IAM-less test gateway; a signed driver belongs to the client SDK
    tests, not the load path).  The scenario tier rides X-Seaweed-QoS —
    the gateway forwards it onto its direct volume reads."""
    scenario.extra.setdefault("keys", list(objects))
    return await _run_load(
        lambda key: f"http://{s3_url}/{bucket}/{key}",
        objects.get,
        scenario,
        headers={"X-Seaweed-QoS": scenario.tier},
        volume_of=None,
    )


