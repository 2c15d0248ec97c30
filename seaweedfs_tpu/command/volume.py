"""`volume` — run a volume server (reference: weed/command/volume.go)."""
from __future__ import annotations

import asyncio

from . import common_args
from ..serving.config import ServingConfig
from ..utils import config as config_util
from ..security import guard as guard_mod

NAME = "volume"
HELP = "start a volume server"


def add_args(p) -> None:
    p.add_argument("-ip", default="127.0.0.1")
    p.add_argument("-port", type=int, default=8080)
    p.add_argument(
        "-port.grpc", dest="grpc_port", type=int, default=0,
        help="grpc port (default: port+10000)",
    )
    p.add_argument(
        "-dir", default=".", help="comma-separated data directories"
    )
    p.add_argument(
        "-max", dest="max_volume_counts", default="8",
        help="max volumes per dir (comma-separated to match -dir)",
    )
    p.add_argument(
        "-mserver", dest="masters", default="127.0.0.1:9333",
        help="comma-separated master servers",
    )
    p.add_argument("-publicUrl", dest="public_url", default="")
    p.add_argument("-dataCenter", dest="data_center", default="")
    p.add_argument("-rack", default="")
    p.add_argument("-pulseSeconds", dest="pulse_seconds", type=int, default=5)
    p.add_argument(
        "-ec.backend", dest="ec_backend", default="auto",
        choices=["auto", "cpu", "native", "numpy", "xla", "pallas"],
        help="erasure-coding kernel backend (auto = pallas on TPU)",
    )
    p.add_argument(
        "-ec.deviceCacheMB", dest="ec_device_cache_mb", type=int, default=0,
        help="pin mounted EC shards in device HBM up to this budget so "
        "degraded reads/rebuilds reconstruct without per-call H2D "
        "(0 = disabled)",
    )
    p.add_argument(
        "-ec.scrub.intervalSeconds", dest="ec_scrub_interval_seconds",
        type=int, default=0,
        help="periodically verify EC parity of locally-complete volumes "
        "(device-resident when pinned; 0 = disabled)",
    )
    # continuous-batching EC serving dispatcher (serving/dispatcher.py):
    # ServingConfig is the single source of the defaults; the flags exist
    # so an operator can tune the batching curve without a rebuild
    serving_defaults = ServingConfig()
    p.add_argument(
        "-ec.serving.disable", dest="ec_serving_disable",
        action="store_true",
        help="serve every EC read on the native per-read path instead of "
        "the resident continuous-batching dispatcher",
    )
    p.add_argument(
        "-ec.serving.maxBatch", dest="ec_serving_max_batch", type=int,
        default=serving_defaults.max_batch,
        help="widest coalesced EC read batch (device needles per call)",
    )
    p.add_argument(
        "-ec.serving.maxWaitUs", dest="ec_serving_max_wait_us", type=int,
        default=serving_defaults.max_wait_us,
        help="admission window (µs) a hot dispatch lane holds open for a "
        "partial batch to fill; 0 disables",
    )
    p.add_argument(
        "-ec.serving.maxInflight", dest="ec_serving_max_inflight", type=int,
        default=serving_defaults.max_inflight,
        help="pipelined EC read batches in flight (batch N+1 dispatches "
        "while batch N's bytes return)",
    )
    p.add_argument(
        "-ec.serving.maxQueue", dest="ec_serving_max_queue", type=int,
        default=serving_defaults.max_queue,
        help="queued EC reads beyond this fall back to the native path "
        "(backpressure)",
    )
    p.add_argument(
        "-ec.serving.layout", dest="ec_serving_layout",
        default=serving_defaults.layout, choices=["flat", "blockdiag"],
        help="resident shard serving layout: blockdiag runs degraded "
        "reads and scrubs on the block-diagonal g=4 kernel (~157 vs "
        "~121 GB/s flat on v5e; the host stages the segment layout for "
        "free at pin time), flat keeps the plain kernel",
    )
    p.add_argument(
        "-ec.serving.overlap.disable", dest="ec_serving_overlap_disable",
        action="store_true",
        help="serialize the device batch pipeline (one staging slot) "
        "instead of double-buffering pack/H2D of batch N+1 under batch "
        "N's execute",
    )
    p.add_argument(
        "-ec.serving.aot.disable", dest="ec_serving_aot_disable",
        action="store_true",
        help="compile reconstruct shapes inline on first use instead of "
        "ahead-of-time on the warm executor; also disarms the "
        "cold-shape shed (a cold shape then stalls the read 20-40s "
        "instead of routing to host reconstruct)",
    )
    p.add_argument(
        "-ec.serving.mesh.disable", dest="ec_serving_mesh_disable",
        action="store_true",
        help="pin resident EC volumes whole onto the default device "
        "instead of lane-sharding them across the local device mesh "
        "(pod-scale residency: sharded volumes size to the WHOLE "
        "mesh's HBM and split reconstruct lane work across devices)",
    )
    p.add_argument(
        "-ec.serving.mesh.devices", dest="ec_serving_mesh_devices",
        type=int, default=serving_defaults.mesh_devices,
        help="devices the serving mesh may span (0 = every local "
        "device, n = the first n)",
    )
    p.add_argument(
        "-ec.serving.mesh.minShardMB", dest="ec_serving_mesh_min_shard_mb",
        type=int, default=serving_defaults.mesh_min_shard_mb,
        help="volumes with shard files below this pin whole onto the "
        "least-loaded mesh device instead of lane-sharding (a tiny "
        "volume spread across the mesh buys no capacity and pays "
        "cross-device dispatch per batch)",
    )
    p.add_argument(
        "-ec.mesh.coordinator", dest="ec_mesh_coordinator",
        default=serving_defaults.mesh_coordinator,
        help="host:port of the jax.distributed coordinator this volume "
        "server rendezvouses at when joining a multi-controller pod "
        "mesh (required when -ec.mesh.processCount > 1; ignored at 1)",
    )
    p.add_argument(
        "-ec.mesh.processId", dest="ec_mesh_process_id",
        type=int, default=serving_defaults.mesh_process_id,
        help="this process's rank in the multi-controller pod mesh "
        "(0 <= processId < processCount; one process per host)",
    )
    p.add_argument(
        "-ec.mesh.processCount", dest="ec_mesh_process_count",
        type=int, default=serving_defaults.mesh_process_count,
        help="processes in the multi-controller pod mesh; 1 (default) "
        "stays single-controller — resident volumes then shard over "
        "this host's devices only and no coordinator is contacted",
    )
    p.add_argument(
        "-ec.serving.zerocopy.disable", dest="ec_serving_zerocopy_disable",
        action="store_true",
        help="materialize needle payloads as bytes on the HTTP read path "
        "instead of streaming memoryview windows of the reconstruct/"
        "pread buffers (the copying pre-r13 behavior; "
        "response_copy_bytes_total measures the difference)",
    )
    # QoS admission control in front of the serving queue (serving/qos.py)
    p.add_argument(
        "-ec.qos.disable", dest="ec_qos_disable", action="store_true",
        help="disable QoS admission control (tier budgets, deadline "
        "shedding, breaker) — the single shared queue with only the "
        "maxQueue backstop",
    )
    p.add_argument(
        "-ec.qos.interactiveQueue", dest="ec_qos_interactive_queue",
        type=int, default=serving_defaults.qos_interactive_queue,
        help="max interactive-tier reads queued at once (front-door "
        "traffic; X-Seaweed-QoS header absent or 'interactive')",
    )
    p.add_argument(
        "-ec.qos.bulkQueue", dest="ec_qos_bulk_queue", type=int,
        default=serving_defaults.qos_bulk_queue,
        help="max bulk-tier reads queued at once (X-Seaweed-QoS: bulk) — "
        "a narrow slice so background load can't crowd out the front door",
    )
    p.add_argument(
        "-ec.qos.interactiveDeadlineMs",
        dest="ec_qos_interactive_deadline_ms", type=int,
        default=serving_defaults.qos_interactive_deadline_ms,
        help="shed an interactive read to the host path at admission when "
        "its estimated queue wait already exceeds this (0 disables)",
    )
    p.add_argument(
        "-ec.qos.bulkDeadlineMs", dest="ec_qos_bulk_deadline_ms", type=int,
        default=serving_defaults.qos_bulk_deadline_ms,
        help="deadline budget for bulk-tier reads (0 disables)",
    )
    p.add_argument(
        "-ec.qos.tripAfter", dest="ec_qos_trip_after", type=int,
        default=serving_defaults.qos_trip_after,
        help="consecutive sheds that trip a tier's breaker into "
        "fast-fail (host path) until the recover cooldown's probe",
    )
    p.add_argument(
        "-ec.qos.recoverSeconds", dest="ec_qos_recover_seconds", type=float,
        default=serving_defaults.qos_recover_seconds,
        help="breaker cooldown before a half-open probe may re-admit",
    )
    p.add_argument(
        "-ec.qos.stallBudgetSeconds", dest="ec_qos_stall_budget_seconds",
        type=float, default=serving_defaults.stall_budget_seconds,
        help="base seconds a streamed read response may stall on a slow "
        "client before it is disconnected (plus bytes/minRate; 0 "
        "disables the guard)",
    )
    p.add_argument(
        "-ec.qos.stallMinRateKBps", dest="ec_qos_stall_min_rate_kbps",
        type=int, default=serving_defaults.stall_min_rate_kbps,
        help="minimum drain rate a client must sustain for large read "
        "responses (sizes the per-response stall budget)",
    )
    # heat-tiered residency ladder (serving/tiering.py): HBM -> host RAM
    # -> disk, driven by decayed per-volume read heat
    p.add_argument(
        "-ec.tier.disable", dest="ec_tier_disable", action="store_true",
        help="disable the automatic residency ladder (residency falls "
        "back to manual pin/unpin + blind LRU budget eviction)",
    )
    p.add_argument(
        "-ec.tier.intervalSeconds", dest="ec_tier_interval_seconds",
        type=float, default=serving_defaults.tier_interval_seconds,
        help="tier-loop rebalance cadence; 0 disables the loop",
    )
    p.add_argument(
        "-ec.tier.hostCacheMB", dest="ec_tier_host_cache_mb", type=int,
        default=serving_defaults.tier_host_cache_mb,
        help="pinned host-RAM warm-tier budget: demoted volumes' shard "
        "bytes stage here and serve reconstructs without disk reads "
        "(0 disables the host tier)",
    )
    p.add_argument(
        "-ec.tier.halfLifeSeconds", dest="ec_tier_half_life_seconds",
        type=float, default=serving_defaults.tier_half_life_seconds,
        help="decay half-life of the per-volume read-heat counters",
    )
    p.add_argument(
        "-ec.tier.promoteRatio", dest="ec_tier_promote_ratio", type=float,
        default=serving_defaults.tier_promote_ratio,
        help="hysteresis margin: a promotion swap needs the candidate "
        "to out-heat the coldest eligible resident by this factor",
    )
    p.add_argument(
        "-ec.tier.minResidencySeconds",
        dest="ec_tier_min_residency_seconds", type=float,
        default=serving_defaults.tier_min_residency_seconds,
        help="a promoted volume is not swap-eligible before this age "
        "(over-budget pressure demotions ignore it)",
    )
    p.add_argument(
        "-ec.tier.bulkWeight", dest="ec_tier_bulk_weight", type=float,
        default=serving_defaults.tier_bulk_weight,
        help="QoS weight of bulk-tier reads in the heat signal, so "
        "background scans cannot evict the interactive hot set",
    )
    # tail-tolerant RPC plane (utils/faultpolicy.py): deadline budgets,
    # hedged shard gathers, per-peer retry budgets
    p.add_argument(
        "-ec.rpc.deadlineMs", dest="ec_rpc_deadline_ms", type=int,
        default=30000,
        help="default deadline budget stamped on requests arriving "
        "without an X-Seaweed-Deadline-Ms header; every cross-node hop "
        "subtracts elapsed time and refuses doomed work (0 = no "
        "default stamp)",
    )
    p.add_argument(
        "-ec.rpc.hedgeQuantile", dest="ec_rpc_hedge_quantile", type=float,
        default=0.95,
        help="per-peer latency EWMA quantile a survivor-shard fetch "
        "must exceed before a hedge is armed to a spare parity holder",
    )
    p.add_argument(
        "-ec.rpc.hedgeBudgetPct", dest="ec_rpc_hedge_budget_pct",
        type=float, default=10.0,
        help="hedge token budget as a percentage of primary fetches — "
        "hedging can add at most this much cluster load (0 disables "
        "hedging)",
    )
    p.add_argument(
        "-ec.rpc.retryBudgetPct", dest="ec_rpc_retry_budget_pct",
        type=float, default=10.0,
        help="per-peer retry token budget as a percentage of first "
        "attempts — a sick peer degrades into fast-fail instead of a "
        "retry storm (0 disables retries)",
    )
    # streaming ingest plane (ingest/): writes EC-encode on the device
    # as they land; IngestConfig is the single source of the defaults
    from ..ingest import IngestConfig

    ingest_defaults = IngestConfig()
    p.add_argument(
        "-ec.ingest.disable", dest="ec_ingest_disable",
        action="store_true",
        help="disable the streaming write-path EC encode: every volume "
        "reverts to the after-the-fact bulk encode at ec.encode time",
    )
    p.add_argument(
        "-ec.ingest.backend", dest="ec_ingest_backend",
        default=ingest_defaults.backend,
        choices=["auto", "cpu", "native", "numpy", "xla", "pallas"],
        help="codec backend for the streaming row encode (auto = device "
        "when one is visible, else the native/numpy host kernel)",
    )
    p.add_argument(
        "-ec.ingest.arenaSlots", dest="ec_ingest_arena_slots", type=int,
        default=ingest_defaults.arena_slots,
        help="staged 10MB row buffers per actively-written volume; the "
        "pool is the ingest backpressure — a writer that cannot stage "
        "blocks until the encode leg drains",
    )
    p.add_argument(
        "-ec.ingest.backpressureMs", dest="ec_ingest_backpressure_ms",
        type=int, default=ingest_defaults.backpressure_ms,
        help="how long a writer may block on a free staging row before "
        "the volume falls back to the offline encode at seal",
    )
    p.add_argument(
        "-ec.ingest.fsync", dest="ec_ingest_fsync", action="store_true",
        help="group-commit durability: writers ack from a batched fsync "
        "instead of the page cache",
    )
    p.add_argument(
        "-ec.ingest.fsyncMaxBatch", dest="ec_ingest_fsync_max_batch",
        type=int, default=ingest_defaults.fsync_max_batch,
        help="writers per group-commit fsync batch before it fires",
    )
    p.add_argument(
        "-ec.ingest.fsyncMaxDelayMs", dest="ec_ingest_fsync_max_delay_ms",
        type=float, default=ingest_defaults.fsync_max_delay_ms,
        help="longest a group-commit writer lingers for batch-mates "
        "before the fsync fires anyway",
    )
    p.add_argument(
        "-ec.ingest.minRateKBps", dest="ec_ingest_min_rate_kbps",
        type=int, default=ingest_defaults.min_rate_kbps,
        help="deadline doom check: refuse an upload at the door when its "
        "size over this floor rate exceeds the request's remaining "
        "X-Seaweed-Deadline-Ms budget (0 disables)",
    )
    p.add_argument(
        "-ec.ingest.interactiveQueue", dest="ec_ingest_interactive_queue",
        type=int, default=ingest_defaults.interactive_queue,
        help="max interactive-tier writes queued at admission "
        "(X-Seaweed-QoS header absent or 'interactive')",
    )
    p.add_argument(
        "-ec.ingest.bulkQueue", dest="ec_ingest_bulk_queue", type=int,
        default=ingest_defaults.bulk_queue,
        help="max bulk-tier writes queued at admission (multipart parts, "
        "batch loaders) — a narrow slice so loader floods can't crowd "
        "out interactive PUTs",
    )
    p.add_argument(
        "-ec.ingest.deadlineMs", dest="ec_ingest_deadline_ms", type=int,
        default=ingest_defaults.deadline_ms,
        help="per-tier write admission deadline when the client sent no "
        "deadline header of its own (0 disables)",
    )
    p.add_argument(
        "-ec.scrub.megakernel.disable", dest="ec_scrub_megakernel_disable",
        action="store_true",
        help="scrub resident EC volumes one device call per volume "
        "instead of fusing the whole HBM cache into one block-diagonal "
        "megakernel pass per cycle",
    )
    # staged bulk EC pipelines (storage/ec/bulk.py): encode/rebuild/verify
    # overlap host read, device matmul, and shard write by default
    p.add_argument(
        "-ec.bulk.overlap.disable", dest="ec_bulk_overlap_disable",
        action="store_true",
        help="run the bulk EC pipelines (encode/rebuild/verify) serially "
        "on one thread instead of overlapping the read/device/write legs",
    )
    p.add_argument(
        "-ec.bulk.prefetch", dest="ec_bulk_prefetch", type=int, default=3,
        help="stripe batches the bulk pipelines' reader leg may run "
        "ahead of the codec (bounded queue depth)",
    )
    p.add_argument(
        "-ec.bulk.strideMB", dest="ec_bulk_stride_mb", type=int, default=0,
        help="per-shard bytes per bulk codec call (0 = built-in 4MB "
        "default; smaller strides trade kernel efficiency for pipeline "
        "granularity)",
    )
    p.add_argument(
        "-readMode", dest="read_mode", default="proxy",
        choices=["local", "proxy", "redirect"],
    )
    p.add_argument(
        "-images.fix.orientation", dest="fix_jpg_orientation",
        action="store_true",
        help="rotate JPEG pixels per EXIF orientation at upload",
    )
    p.add_argument(
        "-offset.bytes", dest="offset_bytes", type=int, default=4,
        choices=[4, 5],
        help="needle-map offset width: 5 raises the volume cap from 32GB "
        "to 8TB (reference 5BytesOffset build tag; must match the whole "
        "deployment — .idx/.ecx files are not readable across modes)",
    )
    p.add_argument(
        "-tier.dir", dest="tier_dir", default="",
        help="directory backing the 'local.default' tier storage backend",
    )
    p.add_argument(
        "-index", dest="index_kind", default="memory",
        choices=["memory", "sqlite", "native"],
        help="needle map kind: memory (CompactMap), sqlite (persistent, "
        "O(1) RAM per volume), or native (embedded C++ KV, "
        "native/kvstore.cpp — the reference's leveldb index role)",
    )
    p.add_argument(
        "-fileSizeLimitMB", dest="client_max_size_mb", type=int, default=256,
        help="reject uploads larger than this",
    )
    p.add_argument(
        "-concurrentUploadLimitMB", dest="concurrent_upload_limit_mb",
        type=int, default=0, help="total in-flight upload bytes (0 = off)",
    )
    p.add_argument(
        "-concurrentDownloadLimitMB", dest="concurrent_download_limit_mb",
        type=int, default=0, help="total in-flight download bytes (0 = off)",
    )
    common_args.add_metrics_args(p)
    common_args.add_obs_args(p)


async def run(args) -> None:
    common_args.apply_obs_args(args)
    from ..ingest import IngestConfig
    from ..server.volume import VolumeServer
    from ..storage.ec import bulk as ec_bulk

    # bulk pipelines are store-level maintenance verbs; the config is
    # process-global like the obs flags
    ec_bulk.configure(
        ec_bulk.BulkConfig(
            overlap=not args.ec_bulk_overlap_disable,
            prefetch=args.ec_bulk_prefetch,
            stride=args.ec_bulk_stride_mb << 20,
        )
    )
    from ..utils import faultpolicy

    faultpolicy.configure(
        faultpolicy.FaultPolicyConfig(
            deadline_ms=args.ec_rpc_deadline_ms,
            hedge_quantile=args.ec_rpc_hedge_quantile,
            hedge_budget_pct=args.ec_rpc_hedge_budget_pct,
            retry_budget_pct=args.ec_rpc_retry_budget_pct,
        )
    )

    if args.offset_bytes != 4:
        from ..storage import types as storage_types

        storage_types.set_offset_size(args.offset_bytes)
    dirs = [d.strip() for d in args.dir.split(",") if d.strip()]
    counts = [int(c) for c in str(args.max_volume_counts).split(",")]
    ec_serving = ServingConfig(
        enabled=not args.ec_serving_disable,
        max_batch=args.ec_serving_max_batch,
        max_wait_us=args.ec_serving_max_wait_us,
        max_inflight=args.ec_serving_max_inflight,
        max_queue=args.ec_serving_max_queue,
        layout=args.ec_serving_layout,
        overlap=not args.ec_serving_overlap_disable,
        aot=not args.ec_serving_aot_disable,
        mesh=not args.ec_serving_mesh_disable,
        mesh_devices=args.ec_serving_mesh_devices,
        mesh_min_shard_mb=args.ec_serving_mesh_min_shard_mb,
        mesh_coordinator=args.ec_mesh_coordinator,
        mesh_process_id=args.ec_mesh_process_id,
        mesh_process_count=args.ec_mesh_process_count,
        zero_copy=not args.ec_serving_zerocopy_disable,
        qos=not args.ec_qos_disable,
        qos_interactive_queue=args.ec_qos_interactive_queue,
        qos_bulk_queue=args.ec_qos_bulk_queue,
        qos_interactive_deadline_ms=args.ec_qos_interactive_deadline_ms,
        qos_bulk_deadline_ms=args.ec_qos_bulk_deadline_ms,
        qos_trip_after=args.ec_qos_trip_after,
        qos_recover_seconds=args.ec_qos_recover_seconds,
        stall_budget_seconds=args.ec_qos_stall_budget_seconds,
        stall_min_rate_kbps=args.ec_qos_stall_min_rate_kbps,
        tier=not args.ec_tier_disable,
        tier_interval_seconds=args.ec_tier_interval_seconds,
        tier_host_cache_mb=args.ec_tier_host_cache_mb,
        tier_half_life_seconds=args.ec_tier_half_life_seconds,
        tier_promote_ratio=args.ec_tier_promote_ratio,
        tier_min_residency_seconds=args.ec_tier_min_residency_seconds,
        tier_bulk_weight=args.ec_tier_bulk_weight,
    ).validated()  # startup fast-fail: a bad -ec.mesh.* config dies HERE
    if ec_serving.multiprocess:
        # multi-controller rendezvous must precede the first jax backend
        # touch (the compile-cache warm below initializes the backend)
        from ..parallel import mesh as mesh_mod

        mesh_mod.initialize_distributed(
            ec_serving.mesh_coordinator,
            ec_serving.mesh_process_id,
            ec_serving.mesh_process_count,
        )
    if args.ec_device_cache_mb > 0:
        # process entry point: persist kernel compiles (at
        # JAX_COMPILATION_CACHE_DIR, else one fixed path in the checkout)
        # so restarts don't recompile every reconstruct shape
        from ..ops.rs_resident import enable_persistent_compile_cache

        enable_persistent_compile_cache()
    if len(counts) == 1:
        counts = counts * len(dirs)
    vs = VolumeServer(
        masters=[m.strip() for m in args.masters.split(",") if m.strip()],
        directories=dirs,
        ip=args.ip,
        port=args.port,
        grpc_port=args.grpc_port,
        public_url=args.public_url,
        max_volume_counts=counts,
        data_center=args.data_center,
        rack=args.rack,
        pulse_seconds=args.pulse_seconds,
        ec_backend=args.ec_backend,
        read_mode=args.read_mode,
        jwt_signing_key=config_util.jwt_signing_key(),
        tier_backends={
            # master.toml [storage.backend.*] + the -tier.dir shorthand
            **config_util.storage_backends(),
            **(
                {"local.default": {"type": "local", "dir": args.tier_dir}}
                if args.tier_dir
                else {}
            ),
        }
        or None,
        index_kind=args.index_kind,
        client_max_size_mb=args.client_max_size_mb,
        concurrent_upload_limit_mb=args.concurrent_upload_limit_mb,
        concurrent_download_limit_mb=args.concurrent_download_limit_mb,
        ec_device_cache_mb=args.ec_device_cache_mb,
        white_list=guard_mod.from_security_toml(),
        fix_jpg_orientation=args.fix_jpg_orientation,
        ec_scrub_interval_seconds=args.ec_scrub_interval_seconds,
        ec_scrub_megakernel=not args.ec_scrub_megakernel_disable,
        ec_serving=ec_serving,
        ec_ingest=IngestConfig(
            enabled=not args.ec_ingest_disable,
            backend=args.ec_ingest_backend,
            arena_slots=args.ec_ingest_arena_slots,
            backpressure_ms=args.ec_ingest_backpressure_ms,
            fsync=args.ec_ingest_fsync,
            fsync_max_batch=args.ec_ingest_fsync_max_batch,
            fsync_max_delay_ms=args.ec_ingest_fsync_max_delay_ms,
            min_rate_kbps=args.ec_ingest_min_rate_kbps,
            interactive_queue=args.ec_ingest_interactive_queue,
            bulk_queue=args.ec_ingest_bulk_queue,
            deadline_ms=args.ec_ingest_deadline_ms,
        ),
        **common_args.metrics_kwargs(args),
    )
    await vs.start()
    await asyncio.Event().wait()
