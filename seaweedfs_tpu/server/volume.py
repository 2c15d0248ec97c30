"""VolumeServer: storage engine host — HTTP data plane + gRPC admin/EC.

Reference: weed/server/volume_server.go (23-53), volume_server_handlers*.go,
volume_grpc_admin.go (351), volume_grpc_vacuum.go (111), volume_grpc_copy.go
(401), volume_grpc_erasure_coding.go (446), volume_grpc_client_to_master.go.

One asyncio process per storage node:
  - aiohttp: GET/HEAD/POST/PUT/DELETE on /vid,fid — reads serve normal
    volumes, EC volumes (with remote-shard + degraded reconstruction
    fallbacks), or redirect to a peer; writes fan out to replicas
    (store_replicate.go:24-120)
  - grpc.aio `VolumeServer` service: volume lifecycle, the 4-step vacuum
    protocol, file copy streams, and all nine EC RPCs (SURVEY.md §2.2)
  - a heartbeat task streaming full + delta state to the master
    (volume_grpc_client_to_master.go:50-92)

Blocking storage/kernel work runs via asyncio.to_thread; the degraded EC
read's remote-shard hook uses synchronous gRPC stubs since it already runs
on a worker thread.
"""
from __future__ import annotations

import asyncio
import json
import logging
import os
import random
import time

import grpc
from aiohttp import web

from ..pb import Stub, generic_handler, master_pb2, volume_server_pb2
from ..pb.rpc import GRPC_OPTIONS, channel
from ..storage import types as t
from ..storage import vacuum as vacuum_mod
from ..storage.disk_location import DiskLocation
from ..storage.ec import (
    TOTAL_SHARDS,
    ec_base_name,
    find_dat_file_size,
    to_ext,
    write_dat_file,
    write_idx_file_from_ec_index,
)
from .. import obs, stats
from ..serving import EcReadDispatcher, ServingConfig
from ..security import verify_volume_write_jwt
from ..security import tls as tls_mod
from ..security import guard as guard_mod
from ..storage.needle import CrcError, Needle
from ..storage.store import Store
from ..utils import faultpolicy
from ..utils.tasks import spawn_logged
from ..storage.volume import CookieMismatch, NotFoundError, Volume, VolumeReadOnly
from .conversions import ec_msg_to_pb, volume_msg_to_pb

log = logging.getLogger("volume")

_EC_LOCATION_TTL = 10.0  # seconds; reference refreshes at 11s (store_ec.go:254)
# per-call bounds for the degraded-read fan-out when no request budget
# is tighter: one shard interval off a healthy peer is milliseconds, so
# these are generous — but FINITE, which is the whole point (r18)
_SHARD_READ_TIMEOUT_S = 10.0
_EC_LOOKUP_TIMEOUT_S = 5.0


class ByteLimiter:
    """Bound total in-flight bytes (the reference's inFlightUploadData /
    inFlightDownloadData cond-var throttles, volume_server.go:23-53).
    Admission is FIFO so an oversize request (> limit, which runs alone)
    can't be starved by a stream of small ones.  limit<=0 disables."""

    def __init__(self, limit_bytes: int, timeout: float = 30.0):
        self.limit = limit_bytes
        self.timeout = timeout
        self.in_flight = 0
        self._cond = asyncio.Condition()
        from collections import deque

        self._queue: deque = deque()

    def __call__(self, n: int) -> "_ByteLease":
        return _ByteLease(self, n)


class _ByteLease:
    def __init__(self, limiter: ByteLimiter, n: int):
        self.limiter = limiter
        self.n = n

    async def __aenter__(self):
        lim = self.limiter
        if lim.limit <= 0:
            return self
        ticket = object()
        async with lim._cond:
            lim._queue.append(ticket)

            def my_turn():
                return lim._queue[0] is ticket and (
                    lim.in_flight + self.n <= lim.limit
                    or lim.in_flight == 0  # oversize requests run alone
                )

            try:
                await asyncio.wait_for(
                    lim._cond.wait_for(my_turn), lim.timeout
                )
            except asyncio.TimeoutError:
                lim._queue.remove(ticket)
                lim._cond.notify_all()
                raise web.HTTPTooManyRequests(
                    text="too many in-flight bytes; retry later"
                )
            lim._queue.popleft()
            lim.in_flight += self.n
            lim._cond.notify_all()  # the next ticket may also fit
        return self

    async def __aexit__(self, *exc):
        lim = self.limiter
        if lim.limit <= 0:
            return
        async with lim._cond:
            lim.in_flight -= self.n
            lim._cond.notify_all()


class VolumeServer:
    def __init__(
        self,
        masters: list[str],
        directories: list[str],
        ip: str = "127.0.0.1",
        port: int = 8080,
        grpc_port: int = 0,
        public_url: str = "",
        max_volume_counts: int | list[int] = 8,
        data_center: str = "",
        rack: str = "",
        pulse_seconds: int = 5,
        ec_backend: str = "auto",
        read_mode: str = "proxy",  # local | proxy | redirect
        jwt_signing_key: str = "",
        tier_backends: dict | None = None,  # storage/backend.py configure()
        index_kind: str = "memory",  # memory | sqlite (ref -index flag)
        client_max_size_mb: int = 256,
        concurrent_upload_limit_mb: int = 0,  # 0 = unlimited
        concurrent_download_limit_mb: int = 0,
        disk_types: list[str] | None = None,  # per-directory (ref -disk flag)
        ec_device_cache_mb: int = 0,  # >0: pin mounted EC shards in HBM
        white_list: list[str] | None = None,  # [access] white_list guard
        fix_jpg_orientation: bool = False,  # ref -images.fix.orientation
        metrics_address: str = "",  # pushgateway host:port (ref -metrics.address)
        metrics_interval_seconds: int = 15,  # ref -metrics.intervalSeconds
        ec_scrub_interval_seconds: int = 0,  # >0: periodic parity scrub
        ec_serving=None,  # serving.ServingConfig | None (-ec.serving.* flags)
        ec_ingest=None,  # ingest.IngestConfig | None (-ec.ingest.* flags)
        ec_scrub_megakernel: bool = True,  # fuse resident scrubs into one
        # device pass per cycle (-ec.scrub.megakernel.disable)
    ):
        self.metrics_address = metrics_address
        self.metrics_interval_seconds = metrics_interval_seconds
        self.ec_scrub_interval_seconds = ec_scrub_interval_seconds
        self.ec_scrub_megakernel = ec_scrub_megakernel
        self.fix_jpg_orientation = fix_jpg_orientation
        self.guard = guard_mod.Guard(white_list)
        if tier_backends:
            from ..storage import backend as backend_mod

            backend_mod.configure(tier_backends)
        # validate the serving config BEFORE the Store exists: the cache
        # must carry the configured layout/pipeline shape from birth —
        # Store.__init__ spawns pin/warm threads for on-disk EC volumes
        # immediately, and a warm racing a late layout assignment would
        # burn its 20-40s/shape budget compiling the wrong ladder
        ec_serving = (ec_serving or ServingConfig()).validated()
        self.ec_serving = ec_serving
        device_cache = None
        if ec_device_cache_mb > 0:
            from ..ops.rs_resident import DeviceShardCache

            device_cache = DeviceShardCache(
                budget_bytes=ec_device_cache_mb << 20,
                layout=ec_serving.layout,
                # pod-scale mesh residency (-ec.serving.mesh.*): lane-
                # shard resident volumes across the local device mesh;
                # None keeps the single-device layout
                mesh_devices=(
                    ec_serving.mesh_devices if ec_serving.mesh else None
                ),
                mesh_min_shard_bytes=ec_serving.mesh_min_shard_mb << 20,
                # multi-controller pod mesh (-ec.mesh.*): residency
                # spans every process's devices; the caller already ran
                # parallel.mesh.initialize_distributed before the first
                # jax touch (command/volume.py)
                global_mesh=ec_serving.multiprocess,
            )
            device_cache.pipeline.set_slots(ec_serving.pipeline_slots)
            # -ec.serving.aot.disable: inline compiles instead of the
            # cold-shape shed (warm() also keys its mode off this)
            device_cache.shed_cold = ec_serving.aot
        if isinstance(max_volume_counts, int):
            max_volume_counts = [max_volume_counts] * len(directories)
        if disk_types is None:
            disk_types = ["hdd"] * len(directories)
        if len(disk_types) != len(directories) or len(max_volume_counts) != len(
            directories
        ):
            raise ValueError(
                "disk_types / max_volume_counts must match directories 1:1"
            )
        self.store = Store(
            [
                DiskLocation(
                    d, max_volume_count=c, disk_type=dt,
                    needle_map_kind=(
                        {"sqlite": "persistent", "native": "native"}.get(
                            index_kind
                        )
                    ),
                )
                for d, c, dt in zip(directories, max_volume_counts, disk_types)
            ],
            ip=ip,
            port=port,
            public_url=public_url,
            ec_backend=ec_backend,
            ec_device_cache=device_cache,
        )
        self.masters = masters
        self.ip = ip
        self.port = port
        self.grpc_port = grpc_port or (port + 10000 if port else 0)
        self.data_center = data_center
        self.rack = rack
        self.pulse_seconds = pulse_seconds
        self.read_mode = read_mode
        self.jwt_signing_key = jwt_signing_key
        self.current_master = masters[0] if masters else ""
        self.client_max_size_mb = client_max_size_mb
        self.upload_limiter = ByteLimiter(concurrent_upload_limit_mb << 20)
        self.download_limiter = ByteLimiter(concurrent_download_limit_mb << 20)
        self._pending_compacts: dict[int, tuple[str, str, int, str | None]] = {}
        self._ec_locations: dict[int, tuple[float, dict[int, list[str]]]] = {}
        # peer grpc addr -> mesh pod id, refreshed with _ec_locations:
        # the hedged gather's pod anti-affinity signal (r20)
        self._ec_location_pods: dict[int, dict[str, str]] = {}
        self.ec_dispatcher = EcReadDispatcher(
            self.store, self._remote_shard_reader, ec_serving
        )
        # heat-tiered residency ladder (serving/tiering.py, -ec.tier.*):
        # only meaningful with a device cache; the dispatcher feeds the
        # heat signal, the QoS controller gates swap churn under
        # overload, and the tier loop below runs the rebalance cycles
        self.tiering = None
        if device_cache is not None and ec_serving.tier:
            from ..serving.tiering import TieringController

            self.tiering = TieringController(self.store, ec_serving)
            self.tiering.attach_qos(self.ec_dispatcher.qos)
            self.ec_dispatcher.tiering = self.tiering
        # streaming ingest plane (ingest/, -ec.ingest.*): QoS write-tier
        # admission + whole-upload deadline doom at the door, per-volume
        # pipelines stream-encoding stripe rows as appends land, group-
        # commit fsync.  Write heat feeds the same HeatTracker the read
        # path feeds, so a freshly written volume enters the tiering
        # ladder already warm.
        from ..ingest import IngestConfig, IngestPlane

        ec_ingest = (ec_ingest or IngestConfig()).validated()
        self.ingest = None
        if ec_ingest.enabled:
            self.ingest = IngestPlane(
                ec_ingest,
                heat=self.tiering.heat if self.tiering is not None else None,
            )
        self.store.ingest = self.ingest
        # stage-digest shipping state: deltas against _stage_snapshot
        # accrue in _digest_backlog until the heartbeat that carried
        # them is ACKED (the master answers every heartbeat in order),
        # so a stream break re-ships instead of silently dropping the
        # lost pulse's observations from the cluster's merged digests
        self._stage_snapshot: dict = {}
        self._digest_backlog: dict = {}  # stage -> [buckets, count, sum_s]
        self._digest_shipped: dict = {}  # the outstanding shipment's content
        self._digest_inflight_at: int | None = None  # its heartbeat seq
        self._hb_sent = 0  # per-stream counters (reset on reconnect)
        self._hb_acked = 0
        # flight-timeline shipping state (obs/timeline.py): samples
        # accrue in the backlog until the heartbeat that carried them is
        # ACKed — same protocol as the stage digests above; reships
        # after a stream break are safe because the master dedupes
        # samples by (node, t)
        self.timeline = None  # TimelineSampler, built in start()
        # tail-forensics retention (obs/tailstore.py), built in start():
        # pins the full span tree of p99-exceeding / incident-flagged
        # requests and feeds SeaweedFS_critpath_seconds per route
        self.tailstore = None
        self._timeline_backlog: list[dict] = []
        self._timeline_shipped = 0  # leading backlog entries in flight
        self._timeline_inflight_at: int | None = None
        self._grpc_server: grpc.aio.Server | None = None
        self._http_runner: web.AppRunner | None = None
        self._tasks: list[asyncio.Task] = []
        self._stopping = False
        # chaos-harness hook (loadgen/chaos.py): True stops the pulse
        # loop from sending WITHOUT breaking the stream — the master
        # sees missed heartbeats and flags the node stale, which is the
        # partition signal the repair scheduler watches (a broken
        # stream would instead unregister the node immediately)
        self.heartbeat_pause = False
        # chaos-harness NETWORK faults on the VolumeEcShardRead servicer
        # (loadgen/chaos.py; r18 tail-tolerance sweep): the gray-failure
        # injectors fast faults can't model.  hang = accept the RPC then
        # never answer; stall_after_chunks = answer N chunks then hang
        # mid-stream; delay_s = fixed added latency before the first
        # byte; fail_pct = probability of an immediate UNAVAILABLE (the
        # flaky-dial model).  Never set outside tests/bench.
        self.fault_shard_read_hang = False
        self.fault_shard_read_stall_after: int | None = None
        self.fault_shard_read_delay_s = 0.0
        self.fault_shard_read_fail_pct = 0.0

    @property
    def url(self) -> str:
        return f"{self.ip}:{self.port}"

    @property
    def grpc_url(self) -> str:
        return f"{self.ip}:{self.grpc_port}"

    # ------------------------------------------------------------------ lifecycle

    async def start(self, heartbeat: bool = True) -> None:
        self._grpc_server = grpc.aio.server(options=GRPC_OPTIONS)
        self._grpc_server.add_generic_rpc_handlers(
            [generic_handler(volume_server_pb2, "VolumeServer", self)]
        )
        self.grpc_port = tls_mod.add_port(
            self._grpc_server, f"{self.ip}:{self.grpc_port}"
        )
        await self._grpc_server.start()

        app = web.Application(
            client_max_size=self.client_max_size_mb * 1024 * 1024,
            middlewares=(
                [guard_mod.middleware(self.guard)] if self.guard.enabled else []
            ) + [obs.middleware("volume")],
        )
        app.router.add_get("/status", self.h_status)
        app.router.add_get("/metrics", stats.metrics_handler)
        app.router.add_get("/debug/traces", obs.traces_handler)
        # tail-forensics plane: this node's own critical-path view
        # (local ring + tail pins only — cross-node assembly lives on
        # the master) and the tail ring's route stats / pinned trees
        app.router.add_get("/debug/critpath", self.h_debug_critpath)
        app.router.add_get("/debug/tail", self.h_debug_tail)
        # incident plane: this node's flight-recorder ring + trace
        # window (the master's bundle fan-out target) and the live
        # per-shape device dispatch view (volume.device.status -hot)
        app.router.add_get("/debug/incident", obs.incident.incident_handler)
        app.router.add_get("/debug/device/hot", obs.device_hot_handler)
        # this node's flight-timeline ring (obs/timeline.py): the local
        # view of what the master assembles cluster-wide
        app.router.add_get("/debug/timeline", self.h_debug_timeline)
        # this node's device-time ledger: per-workload busy/dispatch/
        # bytes attribution (shell volume.device.attribution)
        app.router.add_get(
            "/debug/device/attribution", self.h_debug_device_attribution
        )
        if os.environ.get("SWFS_DEBUG") == "1":
            # stack dumps reveal internals; opt-in only (the reference
            # gates pprof handlers the same way)
            from ..utils.profiling import debug_stacks_handler

            app.router.add_get("/debug/stacks", debug_stacks_handler)
            # on-demand device profiling (obs/profile.py): wraps
            # jax.profiler around the live serving loop — same opt-in
            # gate as the stack dumps (it reveals internals AND costs
            # device attention)
            app.router.add_get("/debug/profile", obs.profile_handler)
        app[stats.metrics.metrics_collect_key()] = self._collect_metrics
        app.router.add_route("*", "/{fid:.*}", self.h_needle)
        self._http_runner = web.AppRunner(app)
        await self._http_runner.setup()
        site = web.TCPSite(self._http_runner, self.ip, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        self.store.port = self.port
        if self.store.public_url == f"{self.ip}:0":
            self.store.public_url = self.url

        # spawn_logged: a heartbeat/sweep/scrub loop dying early must
        # log AT death with its spawn trace, not sit silent until stop()
        # gathers the corpse (GL111 hardening)
        if heartbeat and self.masters:
            self._tasks.append(
                spawn_logged(self._heartbeat_forever(), log, "heartbeat loop")
            )
        self._tasks.append(
            spawn_logged(self._ttl_sweep_forever(), log, "ttl sweep loop")
        )
        if self.ec_scrub_interval_seconds > 0:
            self._tasks.append(
                spawn_logged(self._ec_scrub_forever(), log, "ec scrub loop")
            )
        if (
            self.tiering is not None
            and self.ec_dispatcher.cfg.tier_interval_seconds > 0
        ):
            self._tasks.append(
                spawn_logged(self._tier_loop_forever(), log, "ec tier loop")
            )
        from ..obs import timeline as timeline_mod
        from ..obs import trace as obs_trace_mod

        if obs_trace_mod.CONFIG.timeline_enabled:
            self.timeline = timeline_mod.TimelineSampler(
                node=self.url
            ).install()
            self._tasks.append(
                spawn_logged(
                    self._timeline_forever(), log, "timeline sampler loop"
                )
            )
        if obs_trace_mod.CONFIG.tail_enabled:
            from ..obs import tailstore as tailstore_mod

            self.tailstore = tailstore_mod.TailStore(node=self.url).install()
        push = stats.start_push_loop(
            "volumeServer", self.url, self.metrics_address,
            self.metrics_interval_seconds, collect=self._collect_metrics,
        )
        if push is not None:
            self._tasks.append(push)
        log.info("volume server up http=%s grpc=%s", self.url, self.grpc_url)

    async def _timeline_forever(self) -> None:
        """~1s flight-timeline sampling (-obs.timeline.intervalSeconds):
        each tick snapshots the ledger/QoS/ingest counters into one
        clock-aligned sample; the heartbeat builder drains the ring's
        new suffix into its ACK-gated backlog."""
        from ..obs import trace as obs_trace_mod

        interval = obs_trace_mod.CONFIG.timeline_interval_seconds
        while not self._stopping:
            await asyncio.sleep(interval)
            try:
                self.timeline.sample()
            except Exception:  # noqa: BLE001 — sampling must never die
                log.exception("timeline sample failed")

    async def h_debug_timeline(self, request: web.Request) -> web.Response:
        window = request.query.get("window")
        samples = (
            self.timeline.snapshot(float(window) if window else None)
            if self.timeline is not None
            else []
        )
        return web.json_response({"node": self.url, "samples": samples})

    async def h_debug_critpath(self, request: web.Request) -> web.Response:
        """GET /debug/critpath?id=: critical-path attribution from THIS
        node's local view (ring + tail pins).  No cluster fan-out here —
        a volume server only ever holds its own hops; the master's
        endpoint stitches the cross-node DAG."""
        return await obs.critpath_handler()(request)

    async def h_debug_tail(self, request: web.Request) -> web.Response:
        """GET /debug/tail: the tail ring's per-route stats + pinned
        slow/incident span trees (?id= resolves one full tree)."""
        if self.tailstore is None:
            return web.json_response(
                {"error": "tail retention disabled (-obs.tail.disable)"},
                status=404,
            )
        return await obs.tail_handler(self.tailstore)(request)

    async def h_debug_device_attribution(
        self, request: web.Request
    ) -> web.Response:
        """GET /debug/device/attribution: the device-time ledger — busy
        seconds / dispatches / bytes / queue-wait per workload class,
        with the per-device breakdown (shell volume.device.attribution)."""
        from ..obs import devledger

        return web.json_response({
            "node": self.url,
            "enabled": devledger.LEDGER.enabled,
            "total_busy_seconds": devledger.LEDGER.total_busy_s(),
            "workloads": devledger.LEDGER.snapshot(),
        })

    async def _ec_scrub_forever(self) -> None:
        """Periodic parity scrub of every locally-complete EC volume
        (-ec.scrub.intervalSeconds): the background repair loop around
        VolumeEcShardsVerify.  Device-resident volumes scrub in HBM at
        ~zero payload cost; file-backed volumes stream through the CPU
        kernel.  Corruption is logged loudly and surfaced as a gauge —
        detection, not auto-repair (ec.rebuild is the repair verb)."""
        from ..storage.ec.layout import TOTAL_SHARDS

        # (location dir, vid) -> last KNOWN verdict.  A scrub that ERRORS
        # keeps the previous verdict: a transiently unreadable volume
        # that was corrupt last cycle must not auto-resolve the alert.
        verdicts: dict[tuple[str, int], bool] = {}

        def _record(key: tuple[str, int], r: dict) -> None:
            # ONE home for the verdict+alert bookkeeping so the
            # megakernel and per-volume branches can never report
            # corruption differently
            bad = sum(r["parity_mismatch_bytes"])
            verdicts[key] = bool(bad)
            if bad:
                log.error(
                    "ec volume %d FAILED parity scrub: %s mismatch "
                    "bytes (backend=%s) — run ec.rebuild",
                    key[1], r["parity_mismatch_bytes"], r["backend"],
                )

        while not self._stopping:
            await asyncio.sleep(self.ec_scrub_interval_seconds)
            seen: set[tuple[str, int]] = set()
            # megakernel pre-pass: every fully resident volume scrubs in
            # ONE fused device pass (block-diagonally stacked parity
            # systems) instead of one dispatch per volume; the loop
            # below consumes its verdicts and only scrubs the rest
            # (file-backed or unpinned copies) individually
            mega: dict = {}
            if self.ec_scrub_megakernel:
                try:
                    mega = await asyncio.to_thread(
                        self.store.scrub_all_resident
                    )
                except Exception:  # noqa: BLE001 — fall back per-volume
                    log.exception("ec scrub megakernel pass failed")
            for loc in self.store.locations:
                # per-location EcVolume objects: a vid mounted in two
                # locations is two independent shard sets, each scrubbed
                for vid, ev in list(loc.ec_volumes.items()):
                    key = (loc.directory, vid)
                    seen.add(key)
                    if len(ev.shards) < TOTAL_SHARDS:
                        # locally incomplete (normal spread placement):
                        # nothing to verify here; don't burn a thread
                        # hop per cycle finding that out
                        verdicts.pop(key, None)
                        continue
                    r = mega.get(vid)
                    if r is not None and r["dir"] == loc.directory:
                        # the fused pass already verified THIS location's
                        # pinned bytes
                        _record(key, r)
                        continue
                    try:
                        r = await asyncio.to_thread(self.store.scrub_ec, ev)
                    except FileNotFoundError:
                        verdicts.pop(key, None)  # shards went away
                        continue
                    except Exception:  # noqa: BLE001 — transient IO /
                        # unmount mid-scrub: keep the last verdict
                        log.exception("ec scrub failed for volume %d", vid)
                        continue
                    _record(key, r)
            for key in list(verdicts):
                if key not in seen:  # unmounted since last cycle
                    del verdicts[key]
            stats.VOLUME_SERVER_SCRUB_CORRUPT_GAUGE.set(
                sum(verdicts.values())
            )

    async def _tier_loop_forever(self) -> None:
        """The residency ladder's rebalance loop
        (-ec.tier.intervalSeconds): each cycle re-ranks volumes by
        decayed read heat and makes at most a couple of ladder moves —
        promotion pins (host-RAM bytes first) + AOT pre-warm, demotion
        through the claim/evict release path, host-tier staging.  The
        blocking pin/stage IO runs on a worker thread so the event loop
        keeps serving."""
        interval = self.ec_dispatcher.cfg.tier_interval_seconds
        while not self._stopping:
            await asyncio.sleep(interval)
            try:
                moves = await asyncio.to_thread(self.tiering.rebalance)
                if moves:
                    log.info(
                        "tier rebalance: %s",
                        " ".join(f"{kind}:{vid}" for kind, vid in moves),
                    )
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — one failed cycle must
                # not end the ladder; the next cycle retries
                log.exception("tier rebalance failed")

    async def _ttl_sweep_forever(self, interval: float = 60.0) -> None:
        while not self._stopping:
            await asyncio.sleep(interval)
            try:
                await asyncio.to_thread(self.sweep_expired_ttl_volumes)
            except Exception:  # noqa: BLE001
                log.exception("ttl sweep failed")

    def sweep_expired_ttl_volumes(self, grace: float = 0.1) -> list[int]:
        """Delete volumes whose TTL fully lapsed since their last write
        (the reference expires whole TTL volumes the same way,
        store_vacuum/volume ttl handling).  Returns deleted vids."""
        deleted = []
        now = time.time()
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                ttl_min = v.super_block.ttl.minutes
                if not ttl_min or v.is_tiered:
                    # tiered guard must be is_tiered: keep_local tiering
                    # leaves remote_dat None but still owns a remote copy
                    continue
                try:
                    last_write = os.path.getmtime(v.dat_path)
                except OSError:
                    continue
                if last_write + ttl_min * 60 * (1 + grace) >= now:
                    continue
                # close the write window before deleting: mark readonly
                # (pushed to the master immediately so assigns stop), then
                # re-check — a write that raced the first mtime read keeps
                # the volume for its records' full TTL
                try:
                    self.store.mark_volume_readonly(vid, True)
                except Exception:  # noqa: BLE001 — volume may be mid-delete
                    continue
                if os.path.getmtime(v.dat_path) != last_write:
                    continue
                log.info("ttl volume %d expired; deleting", vid)
                self.store.delete_volume(vid)
                deleted.append(vid)
        return deleted

    async def kill(self) -> None:
        """Abrupt stop — the in-process analogue of SIGKILL for the
        chaos harness (loadgen/chaos.py): the HTTP/gRPC endpoints
        vanish and the heartbeat stream breaks mid-pulse (so the master
        unregisters the node's shards), but the store stays OPEN — a
        SIGKILLed process doesn't get to flush or unmount either, and
        `revive()` must bring the same on-disk state back."""
        self._stopping = True
        for t_ in self._tasks:
            t_.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        self._tasks.clear()
        if self._grpc_server:
            await self._grpc_server.stop(0)
            self._grpc_server = None
        if self._http_runner:
            await self._http_runner.cleanup()
            self._http_runner = None

    async def revive(self) -> None:
        """Restart after `kill()` on the same ports (fids cached by
        clients keep resolving) with the same store."""
        self._stopping = False
        self.heartbeat_pause = False
        await self.start()

    async def stop(self) -> None:
        self._stopping = True
        for t_ in self._tasks:
            t_.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
        if self._grpc_server:
            await self._grpc_server.stop(0.1)
        if self._http_runner:
            await self._http_runner.cleanup()
        # zero the occupancy/queue gauges: the registry outlives this
        # server (co-hosted roles, in-process restarts), and a restarted
        # server must not report the dead instance's last occupancy
        # until its first batch
        self.ec_dispatcher.shutdown()
        if self.timeline is not None:
            # unhook the finished-trace tap: the process-global observer
            # list outlives this server (co-hosted roles, test restarts)
            self.timeline.uninstall()
        if self.tailstore is not None:
            self.tailstore.uninstall()
        if self.ingest is not None:
            # joins encode workers + the group-commit flusher
            await asyncio.to_thread(self.ingest.close)
        # off the loop: close() joins pin/warm threads that may sit in a
        # 20-40s jit compile — blocking here would freeze every other
        # coroutine in the process (co-hosted servers, in-flight HTTP)
        await asyncio.to_thread(self.store.close)

    # ------------------------------------------------------------------ heartbeat

    @staticmethod
    def _fold_digest(dst: dict, stage, buckets, count, dsum, sign=1) -> None:
        rec = dst.setdefault(stage, [[0] * len(buckets), 0, 0.0])
        rec[0] = [a + sign * b for a, b in zip(rec[0], buckets)]
        rec[1] += sign * count
        rec[2] += sign * dsum

    def _build_telemetry(self) -> master_pb2.VolumeServerTelemetry:
        """One pulse's telemetry payload: device-cache occupancy, the
        serving dispatcher's live state, and the stage-histogram delta
        since the previous pulse (pb StageDigest — fixed buckets, so the
        master merges without raw samples).

        Digest delivery is ack-gated: each pulse's delta joins the
        backlog, the backlog ships only while no earlier shipment is
        unconfirmed, and a shipment is confirmed (removed from the
        backlog) once its heartbeat's response arrives — responses are
        1:1 and ordered.  A broken stream re-ships the unconfirmed
        backlog on reconnect, so observations survive blips; the rare
        cost is one pulse's digest double-counted when the master
        applied a heartbeat whose response the break ate (and a
        follower's hint response during leader churn can false-ack one
        shipment) — a bounded skew, versus guaranteed loss."""
        tel = master_pb2.VolumeServerTelemetry()
        # wall clock at build time: the master differences it against
        # its own receive time for the per-node skew estimate the
        # tail-forensics assembler reconciles span timestamps with
        tel.wall_clock_unix_ms = int(time.time() * 1e3)
        # pod rank (r20): which member of the multi-controller mesh this
        # node is — cluster.health keys its per-host pod rows on it
        tel.mesh_process_id = self.ec_serving.mesh_process_id
        tel.mesh_process_count = self.ec_serving.mesh_process_count
        cache = self.store.ec_device_cache
        if cache is not None:
            n_resident, n_bytes = cache.stats()
            tel.device_budget_bytes = cache.budget
            tel.device_used_bytes = n_bytes
            tel.device_resident_shards = n_resident
            tel.device_evictions = cache.evictions
            tel.device_pin_claims = cache.pin_claims
            # per-device breakdown (r19 mesh layout): index-ordered so
            # the master can show which chip a lopsided mesh is full on
            tel.device_bytes_per_device.extend(
                d["used_bytes"] for d in cache.device_stats()
            )
            for vid, sids in cache.resident_by_vid().items():
                tel.resident_shards_by_volume[vid] = len(sids)
        g = stats.REGISTRY.get_sample_value
        tel.compile_hits = int(
            g("SeaweedFS_volumeServer_ec_device_compile_total",
              {"result": "hit"}) or 0
        )
        tel.compile_misses = int(
            g("SeaweedFS_volumeServer_ec_device_compile_total",
              {"result": "miss"}) or 0
        )
        # persistent-compile-cache outcome: a node silently recompiling
        # every restart is an operator-visible column, not a lost log
        from ..ops import rs_resident

        tel.compile_cache_enabled = bool(
            rs_resident.compile_cache_status()["enabled"]
        )
        # residency-ladder state (serving/tiering.py): census from the
        # last rebalance + cumulative promotion/demotion counters, so
        # cluster.health can show where each node's working set lives
        # and whether its ladder is thrashing
        if self.tiering is not None:
            tel.tier_hbm_volumes = self.tiering.last_sizes.get("hbm", 0)
            tel.tier_host_volumes = self.tiering.last_sizes.get("host", 0)
            tel.tier_promotions = sum(self.tiering.promotions.values())
            tel.tier_demotions = sum(self.tiering.demotions.values())
            hc = self.tiering.host_cache
            tel.tier_host_bytes = hc.bytes_used if hc is not None else 0
        tel.dispatcher_queue_depth = self.ec_dispatcher.queue_depth
        tel.dispatcher_inflight = self.ec_dispatcher.inflight
        # INTERACTIVE admission breaker state: the master's repair
        # scheduler defers bulk repair traffic while any node reports
        # an open front-door breaker (serving/qos.py Breaker.OPEN)
        from ..serving import qos as qos_mod

        tel.qos_breaker_open = bool(
            self.ec_dispatcher.qos.breaker_state(qos_mod.INTERACTIVE)
            == qos_mod.Breaker.OPEN
        )
        tel.dispatcher_shed = int(
            g("SeaweedFS_volumeServer_ec_batch_fallback_total") or 0
        )
        # error-rate SLO raw counters (obs/slo.py): admitted EC reads
        # (batched+native partitions admissions — the re-route counts
        # like shed_cold_shape ride on top and must not double-count)
        # and total sheds.  With QoS enabled, every coalescer-saturation
        # fallback ALSO lands in qos_shed{queue_budget} via saturated(),
        # so the qos series alone is the complete shed count — adding
        # dispatcher_shed on top would double-count saturation and
        # inflate the error-rate burn; only the -ec.qos.disable config
        # (fixed at construction) leaves the fallback counter as the
        # sole record.
        tel.ec_reads_total = sum(
            int(
                g("SeaweedFS_volumeServer_ec_read_route_total",
                  {"route": r}) or 0
            )
            for r in ("batched", "native")
        )
        qos_sheds = sum(
            int(
                g("SeaweedFS_volumeServer_ec_qos_shed_total",
                  {"tier": t_, "reason": r_}) or 0
            )
            for t_ in ("interactive", "bulk")
            for r_ in ("queue_budget", "deadline", "breaker_open")
        )
        tel.ec_reads_shed_total = (
            qos_sheds if self.ec_dispatcher.cfg.qos
            else tel.dispatcher_shed
        )
        # double-buffered batch pipeline: last window's device-busy /
        # wall ratio + cumulative staged bytes, so cluster.health can
        # show per-node overlap next to queue/occupancy
        tel.overlap_fraction = float(
            g("SeaweedFS_volumeServer_ec_overlap_fraction") or 0.0
        )
        tel.ec_h2d_bytes = int(
            g("SeaweedFS_volumeServer_ec_h2d_bytes_total") or 0
        )
        tel.ec_d2h_bytes = int(
            g("SeaweedFS_volumeServer_ec_d2h_bytes_total") or 0
        )
        # streaming ingest plane (ingest/): write bytes admitted, rows
        # encoded online split device/host, door sheds, group-commit
        # fsyncs, live pipelines, and seals that skipped the offline
        # encode — cluster.health rolls these up next to the read plane
        if self.ingest is not None:
            ing = self.ingest.snapshot()
            tel.ingest_bytes_total = int(
                g("SeaweedFS_volumeServer_ingest_bytes_total") or 0
            )
            tel.ingest_rows_device = int(ing["rows_device"])
            tel.ingest_rows_host = int(ing["rows_host"])
            tel.ingest_shed_total = sum(ing["sheds"].values())
            tel.ingest_fsyncs_total = int(
                g("SeaweedFS_volumeServer_ingest_fsyncs_total") or 0
            )
            tel.ingest_active_pipelines = int(ing["pipelines"])
            tel.ingest_streamed_seals = int(
                g("SeaweedFS_volumeServer_ingest_seals_total",
                  {"path": "streamed"}) or 0
            )
        snap = stats.metrics.stage_histogram_snapshot()
        for stage, buckets, count, dsum in stats.metrics.stage_digest_deltas(
            self._stage_snapshot, snap
        ):
            self._fold_digest(self._digest_backlog, stage, buckets, count, dsum)
        self._stage_snapshot = snap
        if (
            self._digest_inflight_at is not None
            and self._hb_acked >= self._digest_inflight_at
        ):
            # the shipment's heartbeat was answered: the master applied
            # it — retire exactly what was shipped from the backlog
            for stage, (buckets, count, dsum) in self._digest_shipped.items():
                self._fold_digest(
                    self._digest_backlog, stage, buckets, count, dsum, sign=-1
                )
            self._digest_backlog = {
                s: rec for s, rec in self._digest_backlog.items() if rec[1] > 0
            }
            self._digest_shipped = {}
            self._digest_inflight_at = None
        if self._digest_inflight_at is None and self._digest_backlog:
            for stage, (buckets, count, dsum) in sorted(
                self._digest_backlog.items()
            ):
                d = tel.stage_digests.add()
                d.stage = stage
                d.bucket_counts.extend(buckets)
                d.count = count
                d.sum_seconds = dsum
            self._digest_shipped = {
                s: (list(b), c, ds)
                for s, (b, c, ds) in self._digest_backlog.items()
            }
            # pulses() bumps _hb_sent right after this build, so the
            # heartbeat carrying this shipment is number _hb_sent + 1
            self._digest_inflight_at = self._hb_sent + 1
        # flight-timeline samples ride the same ACK gate: fold the
        # ring's new suffix into the backlog, retire the in-flight
        # shipment once its heartbeat is answered, ship the backlog only
        # while nothing is unconfirmed.  The backlog is capped at one
        # ring's worth — a long partition drops the OLDEST unshipped
        # samples (bounded memory; the local /debug/timeline ring still
        # has them until they age out).
        if self.timeline is not None:
            self._timeline_backlog.extend(self.timeline.take_new())
            drop = len(self._timeline_backlog) - self.timeline.capacity
            if drop > 0:
                del self._timeline_backlog[:drop]
                self._timeline_shipped = max(0, self._timeline_shipped - drop)
            if (
                self._timeline_inflight_at is not None
                and self._hb_acked >= self._timeline_inflight_at
            ):
                del self._timeline_backlog[: self._timeline_shipped]
                self._timeline_shipped = 0
                self._timeline_inflight_at = None
            if self._timeline_inflight_at is None and self._timeline_backlog:
                tel.timeline_samples_json.extend(
                    json.dumps(s, separators=(",", ":"))
                    for s in self._timeline_backlog
                )
                self._timeline_shipped = len(self._timeline_backlog)
                self._timeline_inflight_at = self._hb_sent + 1
        return tel

    def _identity_heartbeat(self) -> master_pb2.Heartbeat:
        """Who-am-i header + this pulse's telemetry, no volume state:
        what keeps the master's health plane fresh when nothing about
        the volumes changed between pulses."""
        hb = master_pb2.Heartbeat(
            ip=self.ip, port=self.port,
            public_url=self.store.public_url, grpc_port=self.grpc_port,
            data_center=self.data_center, rack=self.rack,
            # pod membership: the coordinator address IS the pod id —
            # every member of one jax.distributed job shares it, and the
            # master treats it as a rack-like failure domain
            mesh_pod=(
                self.ec_serving.mesh_coordinator
                if self.ec_serving.multiprocess else ""
            ),
        )
        hb.telemetry.CopyFrom(self._build_telemetry())
        return hb

    def _full_heartbeat(self) -> master_pb2.Heartbeat:
        hs = self.store.collect_heartbeat()
        hb = self._identity_heartbeat()
        hb.has_no_volumes = hs.has_no_volumes
        hb.has_no_ec_shards = hs.has_no_ec_shards
        hb.offset_bytes = t.OFFSET_SIZE
        for k, v in hs.max_volume_counts.items():
            hb.max_volume_counts[k] = v
        hb.volumes.extend(volume_msg_to_pb(v) for v in hs.volumes)
        hb.ec_shards.extend(ec_msg_to_pb(e) for e in hs.ec_shards)
        return hb

    def _delta_heartbeat(self) -> master_pb2.Heartbeat | None:
        new_v, del_v, new_ec, del_ec = self.store.drain_deltas()
        if not (new_v or del_v or new_ec or del_ec):
            return None
        hb = self._identity_heartbeat()
        hb.new_volumes.extend(volume_msg_to_pb(v) for v in new_v)
        hb.deleted_volumes.extend(volume_msg_to_pb(v) for v in del_v)
        hb.new_ec_shards.extend(ec_msg_to_pb(e) for e in new_ec)
        hb.deleted_ec_shards.extend(ec_msg_to_pb(e) for e in del_ec)
        return hb

    async def _heartbeat_forever(self) -> None:
        i = 0
        while not self._stopping:
            master = self.masters[i % len(self.masters)]
            i += 1
            try:
                await self._heartbeat_stream(master)
            except asyncio.CancelledError:
                # stop() cancelled us: propagate so the awaited task
                # reads CANCELLED instead of silently "done"
                raise
            except Exception as e:
                log.debug("heartbeat to %s failed: %s", master, e)
            await asyncio.sleep(min(self.pulse_seconds, 1))

    async def _heartbeat_stream(self, master: str) -> None:
        """One connected session: full heartbeat, then deltas + periodic
        re-sync (doHeartbeat volume_grpc_client_to_master.go:92+)."""
        from ..pb import server_address

        stub = Stub(channel(server_address.grpc_address(master)), master_pb2, "Seaweed")

        async def pulses():
            hb = self._full_heartbeat()
            self._hb_sent += 1
            yield hb
            n = 0
            while not self._stopping:
                await asyncio.sleep(
                    0.05 if not self.store.new_volumes.empty()
                    or not self.store.new_ec_shards.empty()
                    else self.pulse_seconds
                )
                while self.heartbeat_pause and not self._stopping:
                    # chaos partition: stay connected, stop pulsing —
                    # the master's staleness window does the rest
                    await asyncio.sleep(0.05)
                hb = self._delta_heartbeat()
                n += 1
                if hb is None:
                    # no state deltas: periodic full re-sync, otherwise a
                    # telemetry-only pulse — the master's health plane
                    # (staleness marking, HBM headroom, stage digests)
                    # needs EVERY pulse, not just state changes
                    hb = (
                        self._full_heartbeat() if n % 4 == 0
                        else self._identity_heartbeat()
                    )
                self._hb_sent += 1
                yield hb

        try:
            # graftlint: allow(unbounded-rpc): the heartbeat stream IS
            # the liveness signal — deliberately unbounded; a wedged
            # master surfaces as a broken stream and a redial
            async for resp in stub.SendHeartbeat(pulses()):
                self._hb_acked += 1
                if resp.volume_size_limit:
                    self.store.volume_size_limit = resp.volume_size_limit
                if resp.leader:
                    self.current_master = resp.leader
        finally:
            # per-stream bookkeeping dies with the stream; an
            # unconfirmed digest shipment stays in the backlog and
            # re-ships on the next connection
            self._hb_sent = 0
            self._hb_acked = 0
            self._digest_shipped = {}
            self._digest_inflight_at = None
            # unconfirmed timeline samples stay in the backlog and
            # re-ship whole on the next connection (master dedupes by t)
            self._timeline_shipped = 0
            self._timeline_inflight_at = None

    # ------------------------------------------------------------------ HTTP data plane

    async def h_status(self, request: web.Request) -> web.Response:
        infos = await asyncio.to_thread(self.store.volume_infos)
        from . import ui

        if ui.wants_html(request):
            # operator page (reference volume_server_ui/ index.html)
            disks = [
                {
                    "dir": loc.directory,
                    "disk_type": loc.disk_type,
                    "max_volume_count": loc.max_volume_count,
                    "volumes": len(loc.volumes),
                    "ec_shards": sum(
                        len(ev.shards) for ev in loc.ec_volumes.values()
                    ),
                }
                for loc in self.store.locations
            ]
            cache = self.store.ec_device_cache
            resident = (
                cache.resident_by_vid() if cache is not None else {}
            )
            ec = [
                {
                    "id": ev.id,
                    "collection": ev.collection,
                    "shard_ids": ",".join(
                        str(s) for s in sorted(ev.shards)
                    ),
                    "resident": ",".join(
                        str(s) for s in resident.get(ev.id, [])
                    ),
                }
                for loc in self.store.locations
                for ev in loc.ec_volumes.values()
            ]
            return web.Response(
                text=ui.render_volume(
                    self.url, disks, [vars(i) for i in infos], ec
                ),
                content_type="text/html",
            )
        return web.json_response(
            {
                "Version": "seaweedfs-tpu",
                "Volumes": [vars(i) for i in infos],
                "Device": await asyncio.to_thread(self._device_status),
            }
        )

    def _device_status(self) -> dict:
        """Device identity, resolved EC backend, residency and swallowed
        pin/warm/AOT failures (rs_resident.device_status) — what tells a
        run that used the chip from one that fell back to the host.

        Only for a process that already uses the device: one with a
        device shard cache, one whose -ec.backend names a device codec,
        or one that has resolved an `auto` backend (the ingest plane
        does at start-up, -ec.backend=auto on its first EC operation) —
        that resolution is what initialises the JAX backend.  Any other
        server answers {"initialised": False} and initialises nothing:
        /status is a readiness probe, and a probe must not be what
        takes the chip."""
        from ..ops import rs

        cache = self.store.ec_device_cache
        backend = self.store.ec_backend
        if not (
            cache is not None
            or backend in ("pallas", "xla")
            or rs.auto_resolved()
        ):
            return {"initialised": False, "ec_backend": backend}
        try:
            from ..ops import rs_resident

            return {
                "initialised": True,
                **rs_resident.device_status(backend, cache),
            }
        except Exception as e:  # noqa: BLE001 — no usable backend: the
            # probe still answers, and says why
            return {
                "initialised": False,
                "ec_backend": backend,
                "error": repr(e)[:500],
            }

    async def h_needle(self, request: web.Request) -> web.StreamResponse:
        if request.method in ("GET", "HEAD"):
            with stats.time_request(
                stats.VOLUME_SERVER_REQUEST_COUNTER,
                stats.VOLUME_SERVER_REQUEST_HISTOGRAM,
                "get",
            ), obs.interval("get"):
                # one GET's stay in the handler, on the profiler's
                # timeline: a capture tells the seconds in which the
                # device idled with no GET to serve from the rest
                return await self.h_read(request)
        if request.method in ("POST", "PUT"):
            self._check_write_jwt(request)
            with stats.time_request(
                stats.VOLUME_SERVER_REQUEST_COUNTER,
                stats.VOLUME_SERVER_REQUEST_HISTOGRAM,
                "post",
            ):
                return await self.h_write(request)
        if request.method == "DELETE":
            self._check_write_jwt(request)
            with stats.time_request(
                stats.VOLUME_SERVER_REQUEST_COUNTER,
                stats.VOLUME_SERVER_REQUEST_HISTOGRAM,
                "delete",
            ):
                return await self.h_delete(request)
        raise web.HTTPMethodNotAllowed(request.method, ["GET", "POST", "PUT", "DELETE"])

    def _check_write_jwt(self, request: web.Request) -> None:
        """Reject unauthorized writes/deletes when a signing key is
        configured (volume_server_handlers.go:33-120 write guard)."""
        raw_fid = request.match_info["fid"].strip("/").split(".")[0]
        if not verify_volume_write_jwt(self.jwt_signing_key, request, raw_fid):
            raise web.HTTPUnauthorized(text="missing or invalid write jwt")

    def _collect_metrics(self) -> None:
        """Refresh volume/EC gauges from store state at scrape time
        (reference gauges set on mount/unmount, ec_shard.go:46).  The gauge
        is cleared first so deleted collections drop to absent instead of
        reporting stale counts; with several in-process volume servers
        sharing the registry (LocalCluster), a scrape reflects the server
        that answered it — separate processes (the deployed shape) each
        have their own registry, like the reference."""
        stats.VOLUME_SERVER_VOLUME_GAUGE.clear()
        by_key: dict[tuple[str, str], int] = {}
        for loc in self.store.locations:
            for v in loc.volumes.values():
                key = (v.collection, "volume")
                by_key[key] = by_key.get(key, 0) + 1
            for ev in loc.ec_volumes.values():
                key = (ev.collection, "ec_shards")
                by_key[key] = by_key.get(key, 0) + len(ev.shards)
        for (collection, kind), count in by_key.items():
            stats.VOLUME_SERVER_VOLUME_GAUGE.labels(
                collection=collection, type=kind
            ).set(count)
        cache = self.store.ec_device_cache
        # always set (zero when cache-less): on a shared registry
        # (LocalCluster) a skipped set would leave another server's
        # resident counts standing as if they were this server's
        n_resident, n_bytes = cache.stats() if cache is not None else (0, 0)
        stats.VOLUME_SERVER_RESIDENT_SHARD_GAUGE.set(n_resident)
        stats.VOLUME_SERVER_RESIDENT_BYTES_GAUGE.set(n_bytes)

    def _parse_fid(self, request: web.Request) -> tuple[int, int, int]:
        fid = request.match_info["fid"].strip("/")
        return t.parse_fid(fid)  # raises ValueError

    async def h_read(self, request: web.Request) -> web.StreamResponse:
        """(GetOrHeadHandler volume_server_handlers_read.go:31-235)"""
        try:
            vid, nid, cookie = self._parse_fid(request)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        v = self.store.find_volume(vid)
        ev = self.store.find_ec_volume(vid) if v is None else None
        if v is None and ev is None:
            return await self._read_remote(request, vid)
        # lease BEFORE the disk read so the throttle bounds memory; the
        # index knows the size up front for normal volumes (EC locates
        # during the read itself — those lease 0 and stay unthrottled)
        read_deleted = request.query.get("readDeleted") == "true"
        size_hint = 0
        if v is not None:
            loc = v.nm.get(nid)
            size_hint = loc[1] if loc else 0
            if loc is None and read_deleted:
                # forensic reads must stay under the memory throttle too
                # (a 16-byte header pread on a rare path)
                size_hint = (
                    await asyncio.to_thread(v.deleted_needle_size, nid) or 0
                )
        serving_cfg = self.ec_dispatcher.cfg
        async with self.download_limiter(size_hint):
            try:
                if v is not None:
                    n = await asyncio.to_thread(
                        self.store.read_needle,
                        vid,
                        nid,
                        cookie,
                        read_deleted,
                        serving_cfg.zero_copy,
                    )
                else:
                    # the serving dispatcher routes per volume: resident
                    # volumes coalesce into pipelined device-resident
                    # reconstruct batches; unpinned/cache-less volumes
                    # (whose concurrent disk reads must not serialize
                    # behind a batch queue) take the native path inside.
                    # QoS tier + origin ride in on headers (the S3
                    # gateway's direct path and the load harness set
                    # them; absent = interactive front-door traffic)
                    n = await self.ec_dispatcher.read(
                        vid, nid, cookie,
                        tier=request.headers.get("X-Seaweed-QoS", ""),
                        origin=request.headers.get(
                            "X-Seaweed-Read-Origin", ""
                        ),
                    )
            except (NotFoundError, KeyError):
                raise web.HTTPNotFound()
            except CookieMismatch:
                raise web.HTTPForbidden()
            except CrcError:
                raise web.HTTPInternalServerError(
                    text="data corruption: CRC mismatch"
                )
            except ValueError:
                # the volume was destroyed under us (TTL sweep / admin
                # delete closed the dat file mid-read)
                raise web.HTTPNotFound(text="volume is gone")
            # TTL'd needles expire at read time even before the volume
            # sweep removes the whole volume (GetOrHeadHandler's ttl check)
            if v is not None:
                ttl_min = v.super_block.ttl.minutes
                if (
                    ttl_min
                    and n.last_modified
                    and n.last_modified + ttl_min * 60 < time.time()
                ):
                    raise web.HTTPNotFound(text="needle expired")
            # headers, range and the body as far as this handler writes
            # it: a streamed body whole, a small one only up to the
            # web.Response (aiohttp writes that after the handler
            # returned, where no span of the program can reach)
            with obs.await_span("response_write", bytes=len(n.data)):
                return await self._respond_needle(request, n)

    async def _respond_needle(
        self, request: web.Request, n: Needle
    ) -> web.StreamResponse:
        headers = {"Etag": f'"{n.etag}"', "Accept-Ranges": "bytes"}
        if n.last_modified:
            from .conditional import format_http_date

            headers["Last-Modified"] = format_http_date(n.last_modified)
        ct = n.mime.decode() if n.mime else "application/octet-stream"
        is_image = ct.startswith("image/")
        resize = is_image and (
            "width" in request.query or "height" in request.query
        )
        crop = is_image and any(
            f"crop_{k}" in request.query for k in ("x1", "y1", "x2", "y2")
        )
        if resize or crop:
            try:
                rw = int(request.query.get("width") or 0)
                rh = int(request.query.get("height") or 0)
                cx1 = int(request.query.get("crop_x1") or 0)
                cy1 = int(request.query.get("crop_y1") or 0)
                cx2 = int(request.query.get("crop_x2") or 0)
                cy2 = int(request.query.get("crop_y2") or 0)
            except ValueError:
                raise web.HTTPBadRequest(
                    text="width/height/crop_* must be integers"
                )
            rmode = request.query.get("mode", "")
            # processed variants must not share the original's cache
            # identity; the crop suffix only appears when cropping so
            # resize-only Etags stay stable across versions
            variant = f"{n.etag}-{rw}x{rh}{rmode}"
            if crop:
                variant += f"-{cx1},{cy1},{cx2},{cy2}"
            headers["Etag"] = f'"{variant}"'
        from .conditional import content_disposition, not_modified

        cd = content_disposition(
            request, n.name.decode("utf-8", "replace") if n.name else ""
        )
        if cd:
            headers["Content-Disposition"] = cd
        if not_modified(request, headers["Etag"], n.last_modified):
            # BEFORE decompress/resize: a 304 exists to skip the body work;
            # keep the validators so caches can refresh their entry
            return web.Response(status=304, headers=headers)
        copied = 0  # response-path bytes COPIED serving this request
        body = n.data  # memoryview on the zero-copy parse, else bytes
        if isinstance(body, bytes) and body:
            # the copying parse already materialized the payload once —
            # that copy is exactly what the zero-copy path removes, so
            # it is what the counter measures
            copied += len(body)
        if n.is_compressed:
            # transforms need pixels: never hand gzip bytes to crop/resize
            # (they would pass through untouched yet carry the variant
            # Etag, poisoning caches with the original under that identity)
            if not (resize or crop) and "gzip" in request.headers.get(
                "Accept-Encoding", ""
            ):
                headers["Content-Encoding"] = "gzip"
            else:
                import gzip as _gz

                body = _gz.decompress(body)
                copied += len(body)
        if crop:
            # reference order: crop first, then resize (volume_server_
            # handlers_read.go shouldCropImages + shouldResizeImages)
            from ..images import cropped

            body = await asyncio.to_thread(
                cropped, bytes(body), cx1, cy1, cx2, cy2
            )
            copied += len(body)
        if resize:
            from ..images import resized

            body = await asyncio.to_thread(resized, bytes(body), rw, rh, rmode)
            copied += len(body)
        if request.method == "HEAD":
            stats.VOLUME_SERVER_RESPONSE_COPY_BYTES.inc(copied)
            return web.Response(
                status=200, headers={**headers, "Content-Length": str(len(body))},
                content_type=ct,
            )
        # range support
        status = 200
        rng = request.http_range
        if rng.start is not None or rng.stop is not None:
            start = rng.start or 0
            if start < 0:  # suffix range "bytes=-N": last N bytes
                start, stop = max(len(body) + start, 0), len(body)
            else:
                stop = min(
                    rng.stop if rng.stop is not None else len(body),
                    len(body),
                )
            if start >= stop:
                # a 206 with an empty body and end<start Content-Range
                # would read as "object ends here" to resuming clients
                raise web.HTTPRequestRangeNotSatisfiable(
                    headers={"Content-Range": f"bytes */{len(body)}"}
                )
            # memoryview slice = zero-copy window; a bytes slice copies
            part = memoryview(body)[start:stop] if isinstance(
                body, memoryview
            ) else body[start:stop]
            if isinstance(part, bytes):
                copied += len(part)
            headers["Content-Range"] = f"bytes {start}-{start + len(part) - 1}/{len(body)}"
            body = part
            status = 206
        stats.VOLUME_SERVER_RESPONSE_COPY_BYTES.inc(copied)
        return await self._send_body(request, status, body, headers, ct)

    # streamed-write chunk; also the threshold below which a body rides
    # web.Response (a small body sits in the socket buffer regardless of
    # how slowly the client drains — nothing worth bounding)
    _STREAM_CHUNK = 64 * 1024

    async def _send_body(
        self,
        request: web.Request,
        status: int,
        body,
        headers: dict,
        ct: str,
    ) -> web.StreamResponse:
        """Write a read response body.  Large bodies stream in chunks
        (memoryview windows — no further copies) under a per-response
        stall budget scaled by size, the way r06 bounded mount reads: a
        dribbling client that can't drain within the budget is
        disconnected (counted in response_stall_aborts_total) instead of
        holding the download byte-lease and the needle buffers open."""
        cfg = self.ec_dispatcher.cfg
        budget = cfg.stall_budget_for(len(body))
        if len(body) <= self._STREAM_CHUNK or budget <= 0:
            return web.Response(
                status=status, body=body, headers=headers, content_type=ct
            )
        resp = web.StreamResponse(
            status=status,
            headers={**headers, "Content-Length": str(len(body))},
        )
        resp.content_type = ct
        loop = asyncio.get_running_loop()
        deadline = loop.time() + budget
        mv = memoryview(body)
        try:
            await resp.prepare(request)
            for off in range(0, len(mv), self._STREAM_CHUNK):
                remaining = deadline - loop.time()
                if remaining <= 0:
                    raise asyncio.TimeoutError
                # write() returns once the chunk is buffered; it only
                # awaits when the transport is above its high-water mark
                # — i.e. exactly when the client is the bottleneck
                await asyncio.wait_for(
                    resp.write(mv[off : off + self._STREAM_CHUNK]),
                    timeout=remaining,
                )
            await resp.write_eof()
        except ConnectionResetError:
            # the client went away on its own (churn, cancel): not a
            # stall — nothing to abort, nothing to count as dribbling
            log.debug("client disconnected mid-response")
        except asyncio.TimeoutError:
            stats.VOLUME_SERVER_RESPONSE_STALL_ABORTS.inc()
            # flight recorder: the abort decision, trace-stamped — an
            # incident bundle joins "this client got cut off" with the
            # request trace that was dribbling
            obs.incident.record(
                "stall_abort", bytes=len(mv), budget_s=round(budget, 1)
            )
            log.warning(
                "read response stalled past its %.1fs budget "
                "(%d bytes); disconnecting slow client", budget, len(mv),
            )
            if request.transport is not None:
                # abort, not close: close() flushes the transport's
                # buffered backlog first, which a dribbling client would
                # keep draining for minutes — the budget's whole point
                # is to stop paying for this socket NOW
                request.transport.abort()
        return resp

    async def _read_remote(self, request: web.Request, vid: int) -> web.StreamResponse:
        """Volume not local: proxy to or redirect at a peer holding it
        (volume_server_handlers_read.go:65-120)."""
        locations = await self._lookup_volume_locations(vid)
        locations = [u for u in locations if u != self.url]
        if not locations:
            raise web.HTTPNotFound(text=f"volume {vid} not found anywhere")
        target = locations[0]
        if self.read_mode == "redirect":
            raise web.HTTPMovedPermanently(
                f"http://{target}{request.path_qs}"
            )
        import aiohttp

        # forward the read-semantics headers (conditionals, Range) and hand
        # the peer's validators back, so proxied reads revalidate exactly
        # like local ones
        fwd = {
            k: request.headers[k]
            for k in (
                "Range",
                "If-None-Match",
                "If-Modified-Since",
                "Accept-Encoding",
            )
            if k in request.headers
        }
        # the peer records its own spans under the same trace id
        fwd.update(obs.outbound_headers())
        # auto_decompress=False: the relay must pass the holder's bytes
        # VERBATIM — transparent gunzip would serve decompressed data
        # still labeled Content-Encoding: gzip
        async with aiohttp.ClientSession(auto_decompress=False) as s:
            async with s.get(
                f"http://{target}{request.path_qs}", headers=fwd
            ) as r:
                body = await r.read()
                back = {
                    k: r.headers[k]
                    for k in (
                        "Etag",
                        "Last-Modified",
                        "Accept-Ranges",
                        "Content-Range",
                        "Content-Encoding",
                        "Content-Disposition",
                    )
                    if k in r.headers
                }
                return web.Response(
                    status=r.status, body=body, headers=back,
                    content_type=r.content_type or "application/octet-stream",
                )

    async def _lookup_volume_locations(self, vid: int) -> list[str]:
        if not self.masters:
            return []
        from ..pb import server_address

        stub = Stub(
            channel(server_address.grpc_address(self.current_master)),
            master_pb2,
            "Seaweed",
        )
        try:
            resp = await stub.LookupVolume(
                master_pb2.LookupVolumeRequest(volume_or_file_ids=[str(vid)]),
                timeout=10.0,  # master metadata round-trip (GL114)
            )
        except grpc.aio.AioRpcError:
            return []
        out = []
        for e in resp.volume_id_locations:
            out.extend(l.url for l in e.locations)
        return out

    async def h_write(self, request: web.Request) -> web.Response:
        """(PostHandler volume_server_handlers_write.go) — parse upload,
        append locally, fan out to replicas unless this IS a replica write.

        The ingest plane's front door: the write rides one deadline
        budget end to end (r18 request_scope), and admission happens
        BEFORE any body byte is buffered — a QoS write-tier shed or a
        doomed upload (content_length at the floor rate overruns the
        remaining budget) is refused at the door instead of discovered
        at fsync."""
        try:
            vid, nid, cookie = self._parse_fid(request)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        if not self.store.has_volume(vid):
            raise web.HTTPNotFound(text=f"volume {vid} not local")
        tier = request.headers.get("X-Seaweed-QoS", "")
        # The doom projection only binds against a deadline the CLIENT
        # propagated: the server-stamped default budget is a backstop
        # for in-flight work, not a contract the uploader agreed to —
        # dooming an undeadlined large body against it would refuse
        # uploads the client is happy to wait for.
        client_ms = faultpolicy.parse_deadline_ms(
            request.headers.get(faultpolicy.DEADLINE_HEADER, "")
        )
        with faultpolicy.request_scope(request.headers):
            if self.ingest is None:
                return await self._h_write_admitted(request, vid, nid, cookie, tier)
            shed = self.ingest.admit(
                tier,
                request.content_length or 0,
                faultpolicy.remaining_s() if client_ms is not None else None,
            )
            if shed == "deadline":
                raise web.HTTPGatewayTimeout(
                    text="upload cannot finish within its deadline budget"
                )
            if shed is not None:
                err = web.HTTPTooManyRequests(
                    text=f"write admission shed ({shed})"
                )
                err.headers["Retry-After"] = "1"
                raise err
            t0 = time.monotonic()
            try:
                return await self._h_write_admitted(
                    request, vid, nid, cookie, tier
                )
            finally:
                self.ingest.complete(tier, time.monotonic() - t0)

    async def _h_write_admitted(
        self, request: web.Request, vid: int, nid: int, cookie: int, tier: str
    ) -> web.Response:
        # lease BEFORE buffering the body, or the throttle bounds nothing;
        # chunked uploads (no Content-Length) pass a 0 lease
        async with self.upload_limiter(request.content_length or 0):
            body = await request.read()
            name, mime, data, compressed = self._parse_upload(
                request.headers.get("Content-Type", ""), body
            )
            if (
                self.fix_jpg_orientation
                and not compressed
                and (
                    mime == b"image/jpeg"
                    or (name or b"").lower().endswith((b".jpg", b".jpeg"))
                )
            ):
                # turn pixels upright at ingest (reference needle.go:104
                # images.FixJpgOrientation, behind -images.fix.orientation)
                from ..images.orientation import fix_orientation

                data = await asyncio.to_thread(fix_orientation, data)
            from ..storage.needle import FLAG_IS_COMPRESSED

            n = Needle(
                id=nid,
                cookie=cookie,
                data=data,
                name=name,
                mime=mime,
                last_modified=int(time.time()),
                flags=FLAG_IS_COMPRESSED if compressed else 0,
            )
            is_replicate = request.query.get("type") == "replicate"
            v = self.store.find_volume(vid)
            existed = v is not None and v.has(nid)
            try:
                size = await asyncio.to_thread(self.store.write_needle, vid, n)
            except VolumeReadOnly:
                raise web.HTTPConflict(text=f"volume {vid} is read-only")
            if self.ingest is not None and v is not None:
                # post-append hook on the worker thread: write heat,
                # stage newly completed stripe rows (the arena wait is
                # the plane's backpressure, landing on THIS writer), and
                # park on the group commit when durability is on
                await asyncio.to_thread(self.ingest.on_write, v, size, tier)
            if not is_replicate:
                err, acked = await self._replicate(
                    request, vid, body_override=body
                )
                if err:
                    # un-commit so replicas can't diverge silently
                    # (store_replicate.go deletes on fan-out failure):
                    # tombstone the fresh needle locally AND on peers that
                    # acked — but only for CREATES; rolling back an
                    # overwrite would destroy the prior durable version,
                    # so overwrite divergence is left to fix.replication
                    if not existed:
                        try:
                            await asyncio.to_thread(
                                self.store.delete_needle, vid, nid, cookie
                            )
                        except Exception:  # noqa: BLE001 — best effort
                            log.exception("rollback of %d,%x failed", vid, nid)
                        await self._rollback_acked(request, acked)
                    raise web.HTTPInternalServerError(
                        text=f"replication failed: {err}"
                    )
        return web.json_response({"name": name.decode() or "", "size": size, "eTag": n.etag})

    @staticmethod
    def _parse_upload(
        content_type: str, body: bytes
    ) -> tuple[bytes, bytes, bytes, bool]:
        """multipart/form-data or raw body -> (filename, mime, data,
        is_gzipped) (needle_parse_upload.go).  Parses from the cached raw
        bytes so the identical body can be re-posted to replicas."""
        if content_type.startswith("multipart/"):
            import email
            import email.policy

            msg = email.message_from_bytes(
                b"Content-Type: " + content_type.encode() + b"\r\n\r\n" + body,
                policy=email.policy.HTTP,
            )
            for part in msg.iter_parts():
                data = part.get_payload(decode=True) or b""
                fname = (part.get_filename() or "").encode()
                pmime = (part.get_content_type() or "").encode()
                if part.get("Content-Type") is None or pmime == b"application/octet-stream":
                    pmime = b""
                gz = part.get("Content-Encoding") == "gzip"
                return fname, pmime, data, gz
            return b"", b"", b"", False
        ct = content_type.split(";")[0].strip()
        mime = ct.encode() if ct and ct != "application/octet-stream" else b""
        return b"", mime, body, False

    async def _replicate(
        self, request: web.Request, vid: int, body_override
    ) -> tuple[str | None, list[str]]:
        """Fan the original request out to every replica
        (DistributedOperation store_replicate.go:60).  Returns
        (error_summary_or_None, peers_that_acked)."""
        v = self.store.find_volume(vid)
        if v is None or v.super_block.replica_placement.copy_count <= 1:
            return None, []
        locations = await self._lookup_volume_locations(vid)
        peers = [u for u in locations if u != self.url]
        if not peers:
            return "no replica locations known", []
        import aiohttp

        body = body_override if body_override is not None else await request.read()
        sep = "&" if request.query_string else ""
        qs = f"?{request.query_string}{sep}type=replicate"
        errors = []
        acked: list[str] = []

        headers = {"Content-Type": request.headers.get("Content-Type", "")}
        if request.headers.get("Authorization"):
            # replicas validate the same master-issued write jwt
            headers["Authorization"] = request.headers["Authorization"]

        async def one(peer):
            try:
                async with aiohttp.ClientSession() as s:
                    async with s.request(
                        request.method,
                        f"http://{peer}{request.path}{qs}",
                        data=body,
                        headers=headers,
                    ) as r:
                        if r.status >= 300:
                            errors.append(f"{peer}: HTTP {r.status}")
                        else:
                            acked.append(peer)
            except Exception as e:
                errors.append(f"{peer}: {e}")

        await asyncio.gather(*(one(p) for p in peers))
        return ("; ".join(errors) if errors else None), acked

    async def _rollback_acked(
        self, request: web.Request, acked: list[str]
    ) -> None:
        """Best-effort delete of the fresh needle on replicas that took
        the failed fan-out's write."""
        if not acked:
            return
        import aiohttp

        headers = {}
        if request.headers.get("Authorization"):
            headers["Authorization"] = request.headers["Authorization"]
        async with aiohttp.ClientSession() as s:
            for peer in acked:
                try:
                    await s.delete(
                        f"http://{peer}{request.path}?type=replicate",
                        headers=headers,
                    )
                except Exception:  # noqa: BLE001
                    log.warning("rollback delete on %s failed", peer)

    async def h_delete(self, request: web.Request) -> web.Response:
        try:
            vid, nid, cookie = self._parse_fid(request)
        except ValueError as e:
            raise web.HTTPBadRequest(text=str(e))
        is_replicate = request.query.get("type") == "replicate"
        v = self.store.find_volume(vid)
        if v is None:
            ev = self.store.find_ec_volume(vid)
            if ev is None:
                raise web.HTTPNotFound()
            await asyncio.to_thread(self.store.delete_ec_needle, vid, nid)
            return web.json_response({"size": 0})
        try:
            size = await asyncio.to_thread(self.store.delete_needle, vid, nid, cookie)
        except CookieMismatch:
            raise web.HTTPForbidden()
        if not is_replicate:
            await self._replicate(request, vid, body_override=b"")
        return web.json_response({"size": size})

    # ------------------------------------------------------------------ EC remote reads

    def _remote_shard_reader(self, vid: int):
        """Sync hook: shard_id, offset, size -> bytes|None, fetching from a
        peer found via master LookupEcVolume (store_ec.go:238-337).  Both the
        location lookup and the shard fetch happen lazily INSIDE the hook,
        which runs on a to_thread worker — sync gRPC on the event-loop
        thread would deadlock against our own servers.  Every fetch
        carries a hard per-call timeout (the remaining deadline budget,
        capped at _SHARD_READ_TIMEOUT_S): a peer that accepts the RPC
        and never answers — the gray failure ChaosInjector's
        hang_shard_reads plants — frees this worker at the timeout instead of
        pinning it forever.  `read.peer_of` exposes the shard's primary
        holder so the hedged gather can key its latency EWMAs per peer."""

        def read(shard_id: int, offset: int, size: int):
            try:
                timeout = faultpolicy.rpc_timeout_s(
                    _SHARD_READ_TIMEOUT_S, what="remote_shard_read"
                )
            except faultpolicy.DeadlineExceeded:
                return None  # doomed: the gather's verdict tells the truth
            locations = self._cached_ec_locations(vid)
            for addr in locations.get(shard_id, []):
                try:
                    # cached per-address channel: the survivor gather
                    # hits up to 10 peers per degraded read, and a
                    # fresh dial per shard was the p99 cliff the chaos
                    # sweep measured (channels are thread-safe; never
                    # closed here)
                    from ..pb.rpc import sync_channel_cached

                    ch = sync_channel_cached(addr)
                    stub = Stub(ch, volume_server_pb2, "VolumeServer")
                    chunks = []
                    for resp in stub.VolumeEcShardRead(
                        volume_server_pb2.VolumeEcShardReadRequest(
                            volume_id=vid, shard_id=shard_id, offset=offset, size=size
                        ),
                        timeout=timeout,
                    ):
                        if resp.is_deleted:
                            return None
                        chunks.append(resp.data)
                    return b"".join(chunks)
                except grpc.RpcError:
                    continue
            return None

        def peer_of(shard_id: int):
            return next(
                iter(self._cached_ec_locations(vid).get(shard_id, ())), None
            )

        def pod_of(shard_id: int):
            # the primary holder's mesh pod ("" = not in a pod): the
            # hedged gather prefers spares OUTSIDE a slow peer's pod —
            # pod members serve one SPMD mesh and stall together, so a
            # same-pod hedge buys nothing (r20)
            peer = peer_of(shard_id)
            if peer is None:
                return ""
            return self._ec_location_pods.get(vid, {}).get(peer, "")

        read.peer_of = peer_of
        read.pod_of = pod_of
        return read

    def _cached_ec_locations(self, vid: int) -> dict[int, list[str]]:
        now = time.time()
        cached = self._ec_locations.get(vid)
        if cached and now - cached[0] < _EC_LOCATION_TTL:
            return cached[1]
        locs: dict[int, list[str]] = {}
        if self.masters:
            from ..pb import server_address

            try:
                from ..pb.rpc import sync_channel_cached

                ch = sync_channel_cached(
                    server_address.grpc_address(self.current_master)
                )
                stub = Stub(ch, master_pb2, "Seaweed")
                # FIXED timeout, not the ambient budget: this refresh
                # fills a process-level cache serving MANY requests, so
                # it must not ride (or be refused by) whichever dying
                # request happened to trigger it
                resp = stub.LookupEcVolume(
                    master_pb2.LookupEcVolumeRequest(volume_id=vid),
                    timeout=_EC_LOOKUP_TIMEOUT_S,
                )
                pods: dict[str, str] = {}
                for e in resp.shard_id_locations:
                    addrs = []
                    for l in e.locations:
                        if l.url == self.url:
                            continue
                        addr = f"{l.url.rsplit(':', 1)[0]}:{l.grpc_port}"
                        addrs.append(addr)
                        if l.mesh_pod:
                            pods[addr] = l.mesh_pod
                    locs[e.shard_id] = addrs
                self._ec_location_pods[vid] = pods
            except grpc.RpcError:
                # unreachable master: keep serving the STALE snapshot
                # rather than poisoning the cache with an empty map for
                # a full TTL (no remote candidates = every degraded
                # read fails for 2s — the netchaos sweep caught this).
                # Re-stamp the timestamp so a down master costs ONE
                # blocking lookup per TTL, not one per call.
                if cached:
                    self._ec_locations[vid] = (now, cached[1])
                    return cached[1]
        self._ec_locations[vid] = (now, locs)
        return locs

    # ------------------------------------------------------------------ gRPC: lifecycle

    async def AllocateVolume(self, request, context):
        await asyncio.to_thread(
            self.store.add_volume,
            request.volume_id,
            request.collection,
            request.replication or "000",
            request.ttl or "",
            3,
            request.disk_type or "",
        )
        return volume_server_pb2.AllocateVolumeResponse()

    async def VolumeMount(self, request, context):
        await asyncio.to_thread(self.store.mount_volume, request.volume_id)
        return volume_server_pb2.VolumeMountResponse()

    async def VolumeUnmount(self, request, context):
        await asyncio.to_thread(self.store.unmount_volume, request.volume_id)
        return volume_server_pb2.VolumeUnmountResponse()

    async def VolumeDelete(self, request, context):
        try:
            await asyncio.to_thread(self.store.delete_volume, request.volume_id)
        except NotFoundError:
            pass
        return volume_server_pb2.VolumeDeleteResponse()

    async def VolumeMarkReadonly(self, request, context):
        self.store.mark_volume_readonly(request.volume_id, True)
        return volume_server_pb2.VolumeMarkReadonlyResponse()

    async def VolumeMarkWritable(self, request, context):
        self.store.mark_volume_readonly(request.volume_id, False)
        return volume_server_pb2.VolumeMarkWritableResponse()

    async def VolumeConfigure(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return volume_server_pb2.VolumeConfigureResponse(error="not found")
        try:
            await asyncio.to_thread(
                v.update_replica_placement,
                t.ReplicaPlacement.parse(request.replication),
            )
        except (ValueError, VolumeReadOnly) as e:
            return volume_server_pb2.VolumeConfigureResponse(error=str(e))
        return volume_server_pb2.VolumeConfigureResponse()

    async def VolumeStatus(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        info = v.info()
        return volume_server_pb2.VolumeStatusResponse(
            is_read_only=v.read_only,
            volume_size=info.size,
            file_count=info.file_count,
            file_deleted_count=info.delete_count,
            compact_revision=v.super_block.compaction_revision,
            version=v.version,
            ttl=str(v.super_block.ttl),
            replication=str(v.super_block.replica_placement),
        )

    async def DeleteCollection(self, request, context):
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                if v.collection == request.collection:
                    await asyncio.to_thread(self.store.delete_volume, vid)
        return volume_server_pb2.DeleteCollectionResponse()

    async def VolumeServerStatus(self, request, context):
        return volume_server_pb2.VolumeServerStatusResponse(
            data_dirs=[l.directory for l in self.store.locations],
            volume_count=sum(len(l.volumes) for l in self.store.locations),
            ec_shard_count=sum(
                ev.shard_bits().count()
                for l in self.store.locations
                for ev in l.ec_volumes.values()
            ),
        )

    async def VolumeServerLeave(self, request, context):
        self._stopping = True
        for t_ in self._tasks:
            t_.cancel()
        return volume_server_pb2.VolumeServerLeaveResponse()

    # ------------------------------------------------------------------ gRPC: vacuum

    async def VacuumVolumeCheck(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        return volume_server_pb2.VacuumVolumeCheckResponse(
            # tiered volumes must not be vacuum candidates: compaction
            # would clash with the remote .dat the .vif records
            garbage_ratio=0.0 if v.is_tiered else v.garbage_ratio
        )

    async def VacuumVolumeCompact(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        cpd, cpx, snap, shadow = await asyncio.to_thread(vacuum_mod.compact, v)
        self._pending_compacts[request.volume_id] = (cpd, cpx, snap, shadow)
        yield volume_server_pb2.VacuumVolumeCompactResponse(
            processed_bytes=os.path.getsize(cpd)
        )

    async def VacuumVolumeCommit(self, request, context):
        v = self.store.find_volume(request.volume_id)
        pending = self._pending_compacts.pop(request.volume_id, None)
        if v is None or pending is None:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, "no pending compact")
        await asyncio.to_thread(vacuum_mod.commit, v, *pending)
        return volume_server_pb2.VacuumVolumeCommitResponse(is_read_only=v.read_only)

    async def VacuumVolumeCleanup(self, request, context):
        pending = self._pending_compacts.pop(request.volume_id, None)
        if pending:
            cpd, cpx, _, shadow = pending
            for p in (cpd, cpx, shadow):
                if p and os.path.exists(p):
                    os.remove(p)
        return volume_server_pb2.VacuumVolumeCleanupResponse()

    # ------------------------------------------------------------------ gRPC: tail sync

    async def VolumeTierMoveDatToRemote(self, request, context):
        """Upload the .dat to a backend, keep serving reads from it
        (volume_grpc_tier.go)."""
        try:
            size = await asyncio.to_thread(
                self.store.tier_move_to_remote,
                request.volume_id,
                request.destination_backend_name,
                request.keep_local_dat_file,
            )
        except (NotFoundError, ValueError, KeyError, OSError) as e:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        yield volume_server_pb2.VolumeTierMoveDatToRemoteResponse(
            processed=size, processedPercentage=100.0
        )

    async def VolumeTierMoveDatFromRemote(self, request, context):
        try:
            size = await asyncio.to_thread(
                self.store.tier_move_from_remote,
                request.volume_id,
                request.keep_remote_dat_file,
            )
        except (NotFoundError, ValueError, KeyError, OSError) as e:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        yield volume_server_pb2.VolumeTierMoveDatFromRemoteResponse(
            processed=size, processedPercentage=100.0
        )

    async def VolumeTailSender(self, request, context):
        """Stream records appended after since_ns; with a nonzero idle
        timeout, drain that many idle seconds then end the stream
        (volume_grpc_tail.go VolumeTailSender)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            await context.abort(
                grpc.StatusCode.NOT_FOUND, f"volume {request.volume_id} not found"
            )
        chunk_limit = 256 * 1024
        since_ns = request.since_ns
        draining = request.idle_timeout_seconds
        # position once by timestamp, then follow appends by byte offset —
        # a cursor that advances even for v1/v2 records (no timestamps)
        # and never re-reads the index per poll
        pos = await asyncio.to_thread(v.find_offset_since, since_ns)
        while True:
            advanced = False
            for offset, hdr, rest, n in v.scan_records(pos):
                pos = offset + len(hdr) + len(rest)
                advanced = True
                if 0 < n.append_at_ns <= since_ns:
                    continue  # initial positioning backs up one record
                for i in range(0, max(len(rest), 1), chunk_limit):
                    part = rest[i : i + chunk_limit]
                    yield volume_server_pb2.VolumeTailSenderResponse(
                        needle_header=hdr,
                        needle_body=part,
                        is_last_chunk=i + chunk_limit >= len(rest),
                    )
            if not advanced:
                # no new data: keepalive + drain countdown
                yield volume_server_pb2.VolumeTailSenderResponse(is_last_chunk=True)
                if request.idle_timeout_seconds > 0:
                    draining -= 1
                    if draining <= 0:
                        return
            else:
                draining = request.idle_timeout_seconds
            await asyncio.sleep(1)

    async def VolumeTailReceiver(self, request, context):
        """Pull another server's appends into the local volume — how a new
        or stale replica catches up (volume_grpc_tail.go
        VolumeTailReceiver)."""
        from ..operation.tail_volume import tail_volume_from_source

        v = self.store.find_volume(request.volume_id)
        if v is None:
            await context.abort(
                grpc.StatusCode.NOT_FOUND, f"volume {request.volume_id} not found"
            )

        async def write(n):
            await asyncio.to_thread(self.store.write_needle, request.volume_id, n)

        await tail_volume_from_source(
            request.source_volume_server,
            request.volume_id,
            request.since_ns,
            int(request.idle_timeout_seconds),
            write,
            version=v.version,
        )
        return volume_server_pb2.VolumeTailReceiverResponse()

    # ------------------------------------------------------------------ gRPC: copy

    async def CopyFile(self, request, context):
        """Stream a volume/EC file to a puller (volume_grpc_copy.go
        CopyFile)."""
        v = self.store.find_volume(request.volume_id)
        if v is not None:
            base = Volume.base_name(v.dir, v.id, v.collection)
        else:
            base = await asyncio.to_thread(
                self.store._ec_base, request.volume_id, request.collection
            )
            if base is None:
                if request.ignore_source_file_not_found:
                    return
                await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        path = base + request.ext
        if not os.path.exists(path):
            if request.ignore_source_file_not_found:
                return
            await context.abort(grpc.StatusCode.NOT_FOUND, f"{path} not found")
        stop = request.stop_offset or os.path.getsize(path)
        chunk = 1024 * 1024
        # open + the 1MB reads go through to_thread: a multi-GB shard
        # copy must not stall the event loop (heartbeats, EC reads)
        # between its disk reads
        from ..utils.aiofile import open_in_thread

        async with open_in_thread(path, "rb") as f:
            sent = 0
            while sent < stop:
                buf = await asyncio.to_thread(f.read, min(chunk, stop - sent))
                if not buf:
                    break
                sent += len(buf)
                yield volume_server_pb2.CopyFileResponse(file_content=buf)

    async def _pull_file(self, source_grpc: str, vid: int, collection: str, ext: str,
                         dest_path: str, ignore_missing: bool = False) -> bool:
        stub = Stub(channel(source_grpc), volume_server_pb2, "VolumeServer")
        tmp = dest_path + ".tmp"
        got_any = False
        from ..utils.aiofile import open_in_thread

        try:
            async with open_in_thread(tmp, "wb") as f:
                async for resp in stub.CopyFile(
                    volume_server_pb2.CopyFileRequest(
                        volume_id=vid,
                        collection=collection,
                        ext=ext,
                        ignore_source_file_not_found=ignore_missing,
                    ),
                    # whole-shard pulls ship tens of MB: heavy but
                    # FINITE, so a hung source frees the copier (GL114)
                    timeout=600.0,
                ):
                    got_any = True
                    await asyncio.to_thread(f.write, resp.file_content)
        except grpc.aio.AioRpcError:
            if os.path.exists(tmp):
                os.remove(tmp)
            if ignore_missing:
                return False
            raise
        if got_any or not ignore_missing:
            os.replace(tmp, dest_path)
            return True
        os.remove(tmp)
        return False

    async def VolumeCopy(self, request, context):
        """Pull .dat/.idx of a volume from a peer and mount it
        (volume_grpc_copy.go VolumeCopy).  `disk_type` pins the copy onto a
        matching DiskLocation (volume.tier.move's hdd→ssd path)."""
        loc = self.store._pick_location(request.disk_type or "")
        if loc is None:
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, "no free slots")
        base = Volume.base_name(loc.directory, request.volume_id, request.collection)
        n = 0
        for ext in (".dat", ".idx"):
            await self._pull_file(
                request.source_data_node, request.volume_id, request.collection,
                ext, base + ext,
            )
            n += os.path.getsize(base + ext)
        await asyncio.to_thread(self.store.mount_volume, request.volume_id)
        yield volume_server_pb2.VolumeCopyResponse(processed_bytes=n)

    async def ReadNeedleBlob(self, request, context):
        try:
            n = await asyncio.to_thread(
                self.store.read_needle, request.volume_id, request.needle_id
            )
        except (NotFoundError, KeyError):
            await context.abort(grpc.StatusCode.NOT_FOUND, "needle not found")
        return volume_server_pb2.ReadNeedleBlobResponse(
            needle_blob=n.data, cookie=n.cookie,
            last_modified=n.last_modified,
        )

    async def WriteNeedleBlob(self, request, context):
        """Append one needle to a local replica — volume.check.disk's sync
        path (reference volume_grpc_read_write.go WriteNeedleBlob)."""
        n = Needle(
            id=request.needle_id,
            cookie=request.cookie,
            data=request.needle_blob,
            last_modified=request.last_modified or int(time.time()),
        )
        try:
            await asyncio.to_thread(self.store.write_needle, request.volume_id, n)
        except NotFoundError:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        except VolumeReadOnly as e:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        return volume_server_pb2.WriteNeedleBlobResponse()

    # ------------------------------------------------------------------ gRPC: erasure coding

    async def VolumeEcShardsGenerate(self, request, context):
        """volume_grpc_erasure_coding.go:38-81 — the TPU encode entry."""
        try:
            await asyncio.to_thread(self.store.ec_generate, request.volume_id)
        except NotFoundError:
            await context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        return volume_server_pb2.VolumeEcShardsGenerateResponse()

    async def VolumeEcShardsRebuild(self, request, context):
        try:
            rebuilt = await asyncio.to_thread(
                self.store.ec_rebuild, request.volume_id, request.collection,
                request.fsync,
            )
        except (NotFoundError, ValueError) as e:
            await context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        return volume_server_pb2.VolumeEcShardsRebuildResponse(
            rebuilt_shard_ids=rebuilt
        )

    async def VolumeEcShardsCopy(self, request, context):
        """Pull shard files (+ sidecars) from source_data_node
        (volume_grpc_erasure_coding.go:126-177)."""
        loc = self.store._pick_location()
        if loc is None:
            await context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, "no free slots")
        base = ec_base_name(loc.directory, request.volume_id, request.collection)
        for sid in request.shard_ids:
            await self._pull_file(
                request.source_data_node, request.volume_id, request.collection,
                to_ext(sid), base + to_ext(sid),
            )
        if request.copy_ecx_file:
            await self._pull_file(
                request.source_data_node, request.volume_id, request.collection,
                ".ecx", base + ".ecx",
            )
        if request.copy_ecj_file:
            await self._pull_file(
                request.source_data_node, request.volume_id, request.collection,
                ".ecj", base + ".ecj", ignore_missing=True,
            )
        if request.copy_vif_file:
            await self._pull_file(
                request.source_data_node, request.volume_id, request.collection,
                ".vif", base + ".vif", ignore_missing=True,
            )
        return volume_server_pb2.VolumeEcShardsCopyResponse()

    async def VolumeEcShardsDelete(self, request, context):
        await asyncio.to_thread(
            self.store.delete_ec_shards,
            request.volume_id,
            list(request.shard_ids),
            request.collection,
        )
        return volume_server_pb2.VolumeEcShardsDeleteResponse()

    async def VolumeEcShardsMount(self, request, context):
        try:
            await asyncio.to_thread(
                self.store.mount_ec_shards,
                request.volume_id,
                list(request.shard_ids),
                request.collection,
            )
        except NotFoundError as e:
            await context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        return volume_server_pb2.VolumeEcShardsMountResponse()

    async def VolumeEcShardsUnmount(self, request, context):
        await asyncio.to_thread(
            self.store.unmount_ec_shards, request.volume_id, list(request.shard_ids)
        )
        return volume_server_pb2.VolumeEcShardsUnmountResponse()

    async def VolumeEcShardRead(self, request, context):
        """Stream raw shard bytes (volume_grpc_erasure_coding.go:309-375)."""
        # chaos network faults (loadgen/chaos.py): the gray failures the
        # r18 fault-policy layer exists to survive — callers must carry
        # per-call timeouts (graftlint GL114) and hedge around us
        if self.fault_shard_read_fail_pct > 0 and (
            random.random() < self.fault_shard_read_fail_pct
        ):
            await context.abort(
                grpc.StatusCode.UNAVAILABLE, "chaos: flaky dial"
            )
        if self.fault_shard_read_delay_s > 0:
            await asyncio.sleep(self.fault_shard_read_delay_s)
        if self.fault_shard_read_hang:
            await asyncio.Event().wait()  # hold until the caller times out
        ev = self.store.find_ec_volume(request.volume_id)
        if ev is None or request.shard_id not in ev.shards:
            await context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"ec volume {request.volume_id} shard {request.shard_id} not here",
            )
        if request.file_key:
            from ..storage.ec.volume import NeedleNotFound, search_sorted_index

            try:
                _, _, size = await asyncio.to_thread(
                    search_sorted_index, ev._ecx.fileno(), ev.ecx_size, request.file_key
                )
                if t.size_is_deleted(size):
                    yield volume_server_pb2.VolumeEcShardReadResponse(is_deleted=True)
                    return
            except NeedleNotFound:
                pass
        remaining = request.size
        offset = request.offset
        chunk = 1024 * 1024
        sent_chunks = 0
        while remaining > 0:
            stall_after = self.fault_shard_read_stall_after
            if stall_after is not None and sent_chunks >= stall_after:
                # chaos: mid-stream stall — bytes stop flowing but the
                # stream stays open (the half-answered gray failure)
                await asyncio.Event().wait()
            buf = await asyncio.to_thread(
                self.store.read_ec_shard_interval,
                request.volume_id,
                request.shard_id,
                offset,
                min(chunk, remaining),
            )
            if not buf:
                break
            yield volume_server_pb2.VolumeEcShardReadResponse(data=buf)
            sent_chunks += 1
            offset += len(buf)
            remaining -= len(buf)

    async def VolumeEcBlobDelete(self, request, context):
        try:
            await asyncio.to_thread(
                self.store.delete_ec_needle, request.volume_id, request.file_key
            )
        except NotFoundError:
            pass
        return volume_server_pb2.VolumeEcBlobDeleteResponse()

    async def VolumeEcShardsVerify(self, request, context):
        """Parity scrub of a mounted EC volume (device-resident when the
        shard cache holds the whole volume, else the CPU kernel over the
        shard files) — the repair-loop verify pass as a first-class RPC.

        `all_resident=True` ignores volume_id and scrubs EVERY fully
        device-resident volume on this node in one fused megakernel pass
        (per-volume parity systems stacked block-diagonally — a handful
        of device dispatches for the whole cache); the per-volume
        verdicts come back in `volumes`."""
        if getattr(request, "all_resident", False):
            results = await asyncio.to_thread(self.store.scrub_all_resident)
            # per-volume seconds are span-apportioned slices of the one
            # shared pass, so their sum IS the pass wall
            wall = sum(r["seconds"] for r in results.values())
            return volume_server_pb2.VolumeEcShardsVerifyResponse(
                backend="device_megakernel",
                seconds=wall,
                volumes=[
                    volume_server_pb2.EcVolumeScrubResult(
                        volume_id=vid,
                        parity_mismatch_bytes=r["parity_mismatch_bytes"],
                        backend=r["backend"],
                        bytes_verified=r["bytes_verified"],
                        seconds=r["seconds"],
                    )
                    for vid, r in sorted(results.items())
                ],
            )
        try:
            result = await asyncio.to_thread(
                self.store.scrub_ec_volume, request.volume_id
            )
        except NotFoundError as e:
            await context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        except FileNotFoundError as e:
            # degraded volume (missing shard files) and not fully
            # resident: scrub needs all 14 inputs — tell the caller
            # cleanly instead of an UNKNOWN traceback
            await context.abort(
                grpc.StatusCode.FAILED_PRECONDITION, str(e)
            )
        return volume_server_pb2.VolumeEcShardsVerifyResponse(
            parity_mismatch_bytes=result["parity_mismatch_bytes"],
            backend=result["backend"],
            seconds=result["seconds"],
            bytes_verified=result["bytes_verified"],
        )

    async def VolumeEcShardsToVolume(self, request, context):
        """Decode EC shards back into a normal .dat/.idx volume
        (volume_grpc_erasure_coding.go:407-446)."""
        ev = self.store.find_ec_volume(request.volume_id)
        if ev is None:
            await context.abort(grpc.StatusCode.NOT_FOUND, "ec volume not found")
        base = ev.base_name

        def decode():
            dat_size = find_dat_file_size(base)
            write_dat_file(base, dat_size)
            write_idx_file_from_ec_index(base)

        await asyncio.to_thread(decode)
        await asyncio.to_thread(self.store.mount_volume, request.volume_id)
        return volume_server_pb2.VolumeEcShardsToVolumeResponse()
