"""Prometheus metrics, mirroring the reference's key series.

Reference: /root/reference/weed/stats/metrics.go:30-300 — namespace
"SeaweedFS", per-subsystem counters/gauges/histograms, exposed by every
server on a /metrics endpoint.  The series kept here are the ones its
dashboards and the EC inventory rely on:

  SeaweedFS_master_received_heartbeats{type}        metrics.go:57-64
  SeaweedFS_volumeServer_request_total{type}        metrics.go:206-213
  SeaweedFS_volumeServer_request_seconds{type}      metrics.go:215-223
  SeaweedFS_volumeServer_volumes{collection,type}   metrics.go:225-232
                                                    (type="volume" |
                                                    "ec_shards", set from
                                                    store state at scrape —
                                                    ec_shard.go:46,
                                                    store_ec.go:41)
  SeaweedFS_filer_request_total{type}               metrics.go:81-88
  SeaweedFS_filer_request_seconds{type}             metrics.go:89-97
  SeaweedFS_s3_request_total{type,code,bucket}      metrics.go:248-255

One process-wide registry: in-process clusters (server/cluster.py) run all
roles in one interpreter, so the roles share a registry exactly like the
reference's shared default Gatherer when roles share a `weed server`
process.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.exposition import CONTENT_TYPE_LATEST

REGISTRY = CollectorRegistry()


def metrics_collect_key():
    """aiohttp AppKey for a per-server gauge-refresh callback, created
    lazily so importing stats never pulls in aiohttp."""
    global _COLLECT_KEY
    try:
        return _COLLECT_KEY
    except NameError:
        from aiohttp import web

        _COLLECT_KEY = web.AppKey("metrics_collect", object)
        return _COLLECT_KEY

MASTER_RECEIVED_HEARTBEATS = Counter(
    "SeaweedFS_master_received_heartbeats",
    "Counter of master received heartbeats.",
    ["type"],
    registry=REGISTRY,
)

# self-healing repair plane (repair/scheduler.py): the master's
# autonomous ec.rebuild loop.  queued/completed/failed/backoff are
# lifecycle counters per repair JOB (one EC volume's gather -> rebuild
# -> remount choreography); inflight is the live job gauge; the
# time-to-healthy histogram is the recovery SLO itself — wall seconds
# from first observing the cluster under-replicated to full redundancy
MASTER_REPAIR_QUEUED = Counter(
    "SeaweedFS_master_repair_queued_total",
    "Repair jobs admitted to the scheduler's queue (one per EC volume "
    "per detection; re-queues after backoff count again).",
    registry=REGISTRY,
)
MASTER_REPAIR_INFLIGHT = Gauge(
    "SeaweedFS_master_repair_inflight",
    "Repair jobs currently executing their gather/rebuild fan-out.",
    registry=REGISTRY,
)
MASTER_REPAIR_COMPLETED = Counter(
    "SeaweedFS_master_repair_completed_total",
    "Repair jobs that restored their volume's shards.",
    registry=REGISTRY,
)
MASTER_REPAIR_FAILED = Counter(
    "SeaweedFS_master_repair_failed_total",
    "Repair jobs parked after exhausting -ec.repair.maxAttempts.",
    registry=REGISTRY,
)
MASTER_REPAIR_BACKOFF = Counter(
    "SeaweedFS_master_repair_backoff_total",
    "Repair deferrals, by reason: 'retry' = a failed job entering "
    "exponential backoff; 'breaker_open' = a whole scheduling cycle "
    "deferred because a fresh node reported an open interactive QoS "
    "breaker (repair yields to the front door).",
    ["reason"],
    registry=REGISTRY,
)
for _r in ("retry", "breaker_open"):
    MASTER_REPAIR_BACKOFF.labels(reason=_r)
MASTER_REPAIR_TIME_TO_HEALTHY = Histogram(
    "SeaweedFS_master_repair_time_to_healthy_seconds",
    "Wall seconds from first observing missing/corrupt EC shards to "
    "the cluster reaching full redundancy again (the recovery SLO).",
    registry=REGISTRY,
    buckets=(0.5, 1, 2, 5, 10, 30, 60, 120, 300, 600, 1800),
)

VOLUME_SERVER_REQUEST_COUNTER = Counter(
    "SeaweedFS_volumeServer_request_total",
    "Counter of volume server requests.",
    ["type"],
    registry=REGISTRY,
)
VOLUME_SERVER_REQUEST_HISTOGRAM = Histogram(
    "SeaweedFS_volumeServer_request_seconds",
    "Bucketed histogram of volume server request processing time.",
    ["type"],
    registry=REGISTRY,
    # sub-100µs floor: the 0.0001 floor lumped every device-resident EC
    # read (µs-scale once batched) into one bucket
    buckets=(0.000005, 0.00001, 0.000025, 0.00005, 0.0001, 0.001, 0.01,
             0.1, 1.0, 10.0),
)
VOLUME_SERVER_VOLUME_GAUGE = Gauge(
    "SeaweedFS_volumeServer_volumes",
    "Number of volumes or EC shards.",
    ["collection", "type"],
    registry=REGISTRY,
)
VOLUME_SERVER_RESIDENT_SHARD_GAUGE = Gauge(
    "SeaweedFS_volumeServer_ec_resident_shards",
    "EC shards pinned in device HBM (the degraded-read fast path).",
    registry=REGISTRY,
)
VOLUME_SERVER_RESIDENT_BYTES_GAUGE = Gauge(
    "SeaweedFS_volumeServer_ec_resident_bytes",
    "Device memory held by the EC shard cache (padded bytes).",
    registry=REGISTRY,
)
VOLUME_SERVER_SCRUB_CORRUPT_GAUGE = Gauge(
    "SeaweedFS_volumeServer_ec_scrub_corrupt_volumes",
    "EC volumes whose last parity scrub found mismatching bytes.",
    registry=REGISTRY,
)

# continuous-batching EC serving dispatcher (serving/dispatcher.py): these
# four series make the dispatch-software gap measurable on a dashboard —
# round 5's 417 reads/s vs a 3259 ceiling was only visible in bench logs
VOLUME_SERVER_EC_BATCH_SIZE = Histogram(
    "SeaweedFS_volumeServer_ec_batch_size",
    "Coalesced EC read batch width (needles per device call).",
    registry=REGISTRY,
    # COUNT_BUCKETS ladder: each bucket edge is a compiled device shape
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
VOLUME_SERVER_EC_BATCH_QUEUE_WAIT = Histogram(
    "SeaweedFS_volumeServer_ec_batch_queue_wait_seconds",
    "Time an EC read waited in the coalescer before its batch dispatched.",
    registry=REGISTRY,
    # µs-scale admission window up to saturated-queue milliseconds
    buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.05,
             0.25, 1.0),
)
VOLUME_SERVER_EC_BATCH_INFLIGHT = Gauge(
    "SeaweedFS_volumeServer_ec_batch_inflight",
    "EC read batches currently in flight on the device (occupancy; "
    "bounded by -ec.serving.maxInflight).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_QUEUE_DEPTH = Gauge(
    "SeaweedFS_volumeServer_ec_queue_depth",
    "EC reads waiting in the serving coalescer right now (bounded by "
    "-ec.serving.maxQueue; zeroed on clean dispatcher shutdown).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_BATCH_FALLBACK = Counter(
    "SeaweedFS_volumeServer_ec_batch_fallback_total",
    "EC reads shed to the native per-read path because the dispatch "
    "queue was saturated.",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_READ_ROUTE = Counter(
    "SeaweedFS_volumeServer_ec_read_route_total",
    "EC reads by serving route (batched = resident continuous-batching "
    "path, native = per-read host path, shed_cold_shape = interval "
    "requests re-routed to host reconstruct because their device shape "
    "was still AOT-cold — counted per reconstruct interval, not per "
    "needle, and IN ADDITION to the admitting batched/native count: "
    "batched+native partitions admissions, shed_cold_shape marks which "
    "of those were re-routed after admission).  s3_batched/s3_native are "
    "attribution counts IN ADDITION to the admitting route for reads the "
    "S3 gateway sent down its direct volume path — s3_batched rising "
    "means S3 GETs are riding the device-resident dispatcher.",
    ["route"],
    registry=REGISTRY,
)
for _route in (
    "batched", "native", "shed_cold_shape", "s3_batched", "s3_native"
):
    VOLUME_SERVER_EC_READ_ROUTE.labels(route=_route)
VOLUME_SERVER_RESPONSE_COPY_BYTES = Counter(
    "SeaweedFS_volumeServer_response_copy_bytes_total",
    "Bytes COPIED while assembling volume-server HTTP read responses "
    "(needle-buffer materialization, range slices of bytes bodies, "
    "decompress/transform output).  The zero-copy serving path "
    "(-ec.serving.zerocopy.disable off) streams memoryview slices of the "
    "reconstruct/needle buffers instead, so this stays 0 for its reads — "
    "a nonzero rate under zero-copy means a request fell onto a copying "
    "branch (transforms, gzip, tombstones).",
    registry=REGISTRY,
)
VOLUME_SERVER_RESPONSE_STALL_ABORTS = Counter(
    "SeaweedFS_volumeServer_response_stall_aborts_total",
    "HTTP read responses aborted because the client drained the body "
    "slower than the per-response stall budget (-ec.qos.stallBudget "
    "Seconds + bytes/minRate): a dribbling reader is disconnected "
    "instead of holding the download byte-lease and its needle buffers "
    "open indefinitely.",
    registry=REGISTRY,
)

# QoS admission control on the EC serving dispatcher (serving/qos.py):
# per-tier queue budgets + deadline-aware shedding + a breaker that
# fast-fails while overload persists.  These series are how an operator
# sees WHICH tier is being shed and WHY before queues collapse.
VOLUME_SERVER_EC_QOS_ADMITTED = Counter(
    "SeaweedFS_volumeServer_ec_qos_admitted_total",
    "EC reads admitted to the serving queue by QoS tier (interactive = "
    "front-door reads, bulk = background/batch traffic).",
    ["tier"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_QOS_SHED = Counter(
    "SeaweedFS_volumeServer_ec_qos_shed_total",
    "EC reads the QoS admission controller re-routed to the host path "
    "before they could queue, by tier and reason: queue_budget = the "
    "tier's queue slice is full, deadline = the estimated queue wait "
    "already exceeds the tier's deadline, breaker_open = the tier's "
    "breaker tripped on sustained shedding and is fast-failing until "
    "its cooldown probe succeeds.",
    ["tier", "reason"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_QOS_QUEUE_DEPTH = Gauge(
    "SeaweedFS_volumeServer_ec_qos_queue_depth",
    "EC reads currently queued in the serving coalescer, by QoS tier "
    "(the tier budgets partition -ec.serving.maxQueue).",
    ["tier"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_QOS_BREAKER_STATE = Gauge(
    "SeaweedFS_volumeServer_ec_qos_breaker_state",
    "QoS admission breaker state by tier: 0 closed (admitting), 1 "
    "half-open (cooldown elapsed, probing), 2 open (fast-failing to "
    "the host path).",
    ["tier"],
    registry=REGISTRY,
)
for _tier in ("interactive", "bulk"):
    VOLUME_SERVER_EC_QOS_ADMITTED.labels(tier=_tier)
    VOLUME_SERVER_EC_QOS_QUEUE_DEPTH.labels(tier=_tier)
    VOLUME_SERVER_EC_QOS_BREAKER_STATE.labels(tier=_tier)
    for _reason in ("queue_budget", "deadline", "breaker_open"):
        VOLUME_SERVER_EC_QOS_SHED.labels(tier=_tier, reason=_reason)
VOLUME_SERVER_EC_SHED_COLD_SHAPE = Counter(
    "SeaweedFS_volumeServer_ec_shed_cold_shape_total",
    "Resident reconstruct interval requests shed to the host path "
    "because a device call shape was not AOT-compiled yet (the shed "
    "schedules the background compile; the read never blocks on a "
    "20-40s compile cliff).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_COMPILE_CACHE_ENABLED = Gauge(
    "SeaweedFS_volumeServer_ec_compile_cache_enabled",
    "1 when the persistent XLA compile cache is active (reconstruct "
    "kernel compiles survive restarts), 0 when configuration failed — "
    "a 0 here means every restart re-pays tens of seconds per shape.",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_AOT_COMPILED = Counter(
    "SeaweedFS_volumeServer_ec_aot_compiled_total",
    "Reconstruct-kernel shapes compiled ahead-of-time on the background "
    "executor (warm plans + cold-shape sheds) — compiles the serving "
    "path never paid inline.",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_SCRUB_DISPATCH = Counter(
    "SeaweedFS_volumeServer_ec_scrub_device_dispatch_total",
    "Device dispatches spent scrubbing resident EC volumes, by mode: "
    "per_volume = one call per volume (scrub_volume), megakernel = one "
    "block-diagonal pass covering a whole stack of pinned volumes "
    "(scrub_all_resident) — the megakernel winning means the same "
    "parity coverage for a fraction of the dispatch/RTT bill.",
    ["mode"],
    registry=REGISTRY,
)
for _mode in ("per_volume", "megakernel"):
    VOLUME_SERVER_EC_SCRUB_DISPATCH.labels(mode=_mode)

# request tracing stages (obs/trace.py spans): one histogram family,
# labeled by stage, µs-resolution buckets — the per-stage view that lets
# a tail regression name its stage instead of hiding in the aggregate
# request histogram.  Stages are pre-registered so /metrics always
# exposes every stage series (and the README drift check sees them)
# even before the first request exercises a path.
TRACE_STAGES = (
    "queue_wait",        # coalescer admission -> batch take (dispatcher)
    "batch_dispatch",    # one coalesced batch through the store call
    "batch_pack",        # host-side planning + vector staging of a batch
    "h2d_copy",          # shipping the packed vectors host -> device
    "device_execute",    # rs_resident reconstruct (device dispatch+fetch)
    "d2h_copy",          # fetching reconstructed bytes device -> host
    "host_reconstruct",  # CPU-kernel GF(256) reconstruct fallback
    "shard_read",        # .ecx index lookups + local shard preads
    "remote_shard_read", # peer shard interval fetch (VolumeEcShardRead)
    "chunk_fetch",       # filer -> volume server chunk read
    "bulk_read",         # bulk EC pipeline reader leg (stripe preads)
    "bulk_device",       # bulk EC pipeline codec leg (stage+H2D+kernel+D2H)
    "bulk_write",        # bulk EC pipeline writer leg (shard writes/compare)
    "get_admit",         # dispatcher admission of a read, up to the queue
    "batch_resolve",     # loop side of a finished batch: replay, futures
    "needle_assemble",   # pieces joined + parsed + CRC-checked, per batch
    "response_write",    # headers, range and the body the handler writes
    "mesh_pack",         # lane-sharded planning: stripe split + owner routing
    "mesh_fetch",        # a sharded call's per-device rows fetched to the host
    "warm_replan",       # the warm plan made again after a pinned volume's loss
)
# the FIXED bucket ladder the heartbeat stage digests ride on: volume
# servers ship per-bucket count deltas over exactly these edges (+Inf
# appended), so the master can merge per-server histograms into one
# cluster digest without raw samples (pb StageDigest, stats/cluster.py)
STAGE_SECONDS_BUCKETS = (0.000005, 0.00001, 0.000025, 0.00005, 0.0001,
                         0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                         0.05, 0.25, 1.0)
REQUEST_STAGE_SECONDS = Histogram(
    "SeaweedFS_request_stage_seconds",
    "Per-stage serving time from the request-tracing spans "
    "(obs/trace.py); stage names cover the EC read path end to end.",
    ["stage"],
    registry=REGISTRY,
    buckets=STAGE_SECONDS_BUCKETS,
)
for _stage in TRACE_STAGES:
    REQUEST_STAGE_SECONDS.labels(stage=_stage)

# critical-path attribution (obs/critpath.py): every finished ROOT trace
# has its client-visible wall time bucketed into exactly these six
# segments (trace stages map onto the first five; whatever no span
# covers is `untraced`), so the per-route composition — "reads on this
# route spend 60% in device_execute, 30% in disk" — is a counter ratio.
# The segment label universe is fixed here; routes register lazily (the
# route space is a runtime property, like the mesh width above).
CRITPATH_SEGMENTS = ("queue_wait", "device_execute", "host_reconstruct",
                     "disk", "network_gap", "untraced")
CRITPATH_SECONDS = Counter(
    "SeaweedFS_critpath_seconds",
    "Client-visible request seconds attributed to each critical-path "
    "segment per route (obs/tailstore.py feeds every finished root "
    "trace through obs/critpath.py's bucketing); the six segments of "
    "one route sum to that route's SeaweedFS_critpath_route_seconds.",
    ["route", "segment"],
    registry=REGISTRY,
)
CRITPATH_ROUTE_SECONDS = Counter(
    "SeaweedFS_critpath_route_seconds",
    "Total client-visible request seconds per route — the denominator "
    "the per-segment SeaweedFS_critpath_seconds composition is read "
    "against (segments sum to this by construction).",
    ["route"],
    registry=REGISTRY,
)

# device-call accounting for the resident EC reconstruct path
# (ops/rs_resident.py): the bytes moved and the compile-cache behavior
# per shape are what decide whether a batch was cheap or a compile cliff
VOLUME_SERVER_EC_DEVICE_H2D_BYTES = Counter(
    "SeaweedFS_volumeServer_ec_device_h2d_bytes",
    "Host->device bytes shipped by resident EC reconstruct calls "
    "(offset/row vectors only — survivor bytes stay pinned).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_DEVICE_D2H_BYTES = Counter(
    "SeaweedFS_volumeServer_ec_device_d2h_bytes",
    "Device->host bytes fetched by resident EC reconstruct calls "
    "(the reconstructed intervals).",
    registry=REGISTRY,
)
# the lane-sharded (mesh) reconstruct path's own accounting: a sharded
# call fetches all n_bucket rows of `fetch` bytes of every device that
# holds an asked-for row, so wire / useful is what the padding costs;
# the per-lane request count shows whether the interleaved stripes keep
# the devices evenly loaded
VOLUME_SERVER_EC_MESH_D2H_BYTES = Counter(
    "SeaweedFS_volumeServer_ec_mesh_d2h_bytes",
    "Device->host bytes of lane-sharded reconstruct calls: wire = the "
    "padded rows of every shard that was fetched, useful = the interval "
    "bytes the requests asked for.",
    ["kind"],
    registry=REGISTRY,
)
for _k in ("wire", "useful"):
    VOLUME_SERVER_EC_MESH_D2H_BYTES.labels(kind=_k)
# how the device section of a degraded-read batch moved its transfers
# (ops/rs_resident.py reconstruct_intervals): a call's vector put left
# asynchronous or issued only after the oldest call was collected, and a
# sharded call's per-device result shards fetched or left on the device
VOLUME_SERVER_EC_DEVICE_TRANSFERS = Counter(
    "SeaweedFS_volumeServer_ec_device_transfers",
    "Transfers of resident EC reconstruct calls by how they were issued: "
    "h2d_async = a call's vector put left in flight behind its program, "
    "h2d_waited = the staging arena was full and the oldest call was "
    "collected first, d2h_shard_fetched / d2h_shard_skipped = a sharded "
    "call's per-device result shards with / without asked-for rows.",
    ["kind"],
    registry=REGISTRY,
)
for _k in ("h2d_async", "h2d_waited", "d2h_shard_fetched",
           "d2h_shard_skipped"):
    VOLUME_SERVER_EC_DEVICE_TRANSFERS.labels(kind=_k)
# how wide the reconstruct calls' systems are: a volume one data shard
# down asks every call for one row and computes one; a volume several
# data shards down is answered with the one matrix of all its lost data
# shards whenever a batch wants more than one of them, and computes rows
# that no request of the call asked for
VOLUME_SERVER_EC_RECONSTRUCT_ROWS = Counter(
    "SeaweedFS_volumeServer_ec_reconstruct_rows",
    "Rows of the reconstruction matrix by resident EC reconstruct call: "
    "wanted = the distinct lost shards the call's requests asked for, "
    "computed = the rows the call's program multiplied (its static "
    "wanted-set width).",
    ["kind"],
    registry=REGISTRY,
)
for _k in ("wanted", "computed"):
    VOLUME_SERVER_EC_RECONSTRUCT_ROWS.labels(kind=_k)
VOLUME_SERVER_EC_MESH_LANE_REQUESTS = Counter(
    "SeaweedFS_volumeServer_ec_mesh_lane_requests",
    "Sub-requests of lane-sharded reconstruct batches by the mesh device "
    "that owns their stripe (device = mesh index).",
    ["device"],
    registry=REGISTRY,
)
# which tier of the two-tier striping a served interval lay in: volumes
# above 10 GB keep their first rows in 1 GB large blocks
VOLUME_SERVER_EC_INTERVAL_ROWS = Counter(
    "SeaweedFS_volumeServer_ec_interval_rows",
    "Shard intervals located for EC needle reads by the kind of stripe "
    "row they lie in (large = a 1 GB large-block row, small = a 1 MB "
    "row).",
    ["kind"],
    registry=REGISTRY,
)
for _k in ("large", "small"):
    VOLUME_SERVER_EC_INTERVAL_ROWS.labels(kind=_k)
VOLUME_SERVER_EC_PIN_SECONDS = Counter(
    "SeaweedFS_volumeServer_ec_pin_seconds",
    "Seconds the pin thread spent bringing a volume's shards into the "
    "device cache, by phase: stage = shard file (or host-tier array) "
    "into the padded staging buffer, in the mesh layout's owner-major "
    "stripe order, h2d = the transfer, warm = the AOT warm plan, replan = "
    "the plan made again off the pin thread after shards of the pinned "
    "volume went.",
    ["volume", "phase"],
    registry=REGISTRY,
)
# per-device residency of the shard cache (r19 mesh layout): one series
# per mesh device, so a lopsided mesh — whole-pins crowding one chip
# while lane-sharded volumes spread evenly — is visible as a device-axis
# breakdown instead of hiding inside the aggregate used-bytes gauge.
# Labels are device indices within the serving mesh ("0".."n-1"),
# registered lazily at cache construction (the mesh width is a runtime
# property, not an import-time constant).
VOLUME_SERVER_EC_DEVICE_CACHE_BYTES = Gauge(
    "SeaweedFS_volumeServer_ec_device_cache_bytes",
    "Padded EC shard-cache bytes resident per serving-mesh device "
    "(device = mesh index; the sum over devices is device_used_bytes).",
    ["device"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_DEVICE_COMPILE = Counter(
    "SeaweedFS_volumeServer_ec_device_compile",
    "Resident EC reconstruct device calls by compile-cache outcome: "
    "miss = first use of a (kernel, tile, fetch, count, k) shape in "
    "this process (a jit compile, tens of seconds on remote-compile "
    "rigs), hit = an already-compiled shape.",
    ["result"],
    registry=REGISTRY,
)
for _r in ("hit", "miss"):
    VOLUME_SERVER_EC_DEVICE_COMPILE.labels(result=_r)

# double-buffered batch pipeline (ops/rs_resident.DevicePipeline): the
# explicit pack->H2D->execute->D2H staging of the serving path.  The
# byte counters are the stage-level view of the same transfers the
# ec_device_* counters account per device call (measured at the copy
# sites, so a pipeline-stage regression can be read off directly); the
# overlap gauge is what proves the double buffer actually overlaps.
VOLUME_SERVER_EC_H2D_BYTES = Counter(
    "SeaweedFS_volumeServer_ec_h2d_bytes",
    "Host->device bytes staged by the double-buffered EC batch "
    "pipeline's h2d_copy stage (packed offset/row vectors; survivor "
    "bytes stay pinned).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_D2H_BYTES = Counter(
    "SeaweedFS_volumeServer_ec_d2h_bytes",
    "Device->host bytes fetched by the pipeline's d2h_copy stage "
    "(reconstructed interval rows, fetch-width padding included).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_OVERLAP_FRACTION = Gauge(
    "SeaweedFS_volumeServer_ec_overlap_fraction",
    "Device-busy time / wall time over the double-buffered EC "
    "pipeline's current batch window, refreshed at every batch "
    "completion (1.0 = the device section was busy the whole window; "
    ">1 = staging slots overlapped, up to the slot count).",
    registry=REGISTRY,
)

# staged bulk EC pipelines (storage/ec/bulk.py): the per-leg decomposition
# behind every encode/rebuild/verify overlap claim — read leg, codec leg,
# and writer leg active seconds accumulate per pipeline so a dashboard can
# read off which leg bounds bulk wall-clock, and the overlap gauge proves
# the legs actually ran concurrently (the stats-contract inequality
# read_s + write_s + device_busy_s > wall_s, as a ratio)
VOLUME_SERVER_EC_BULK_SECONDS = Counter(
    "SeaweedFS_volumeServer_ec_bulk_seconds",
    "Cumulative active seconds of the staged bulk EC pipelines by leg "
    "(read = stripe/shard preads, device = codec stage+H2D+kernel+D2H "
    "or CPU kernel, write = shard writes / parity compare).",
    ["pipeline", "leg"],
    registry=REGISTRY,
)
# the device leg told apart at the boundaries the host crosses, per
# batch as it runs (Codec._enqueue, Codec._fetch): on a device backend the
# four parts of a pipeline sum to its ec_bulk_seconds{leg="device"}; a
# CPU codec has no parts and leaves them at zero
EC_BULK_CODEC_PARTS = ("stage", "enqueue", "fetch", "unstack")
VOLUME_SERVER_EC_BULK_CODEC_SECONDS = Counter(
    "SeaweedFS_volumeServer_ec_bulk_codec_seconds",
    "Cumulative seconds of the bulk EC pipelines' device leg by part, "
    "each named for what the host waits on (stage = the batch laid out "
    "in one flat host buffer: nothing where the reader leg delivered it "
    "laid out, enqueue = device_put + the kernel call, "
    "which both return before the device is done, fetch = the blocking "
    "copy back: what is left of H2D and kernel once the next batch, "
    "where one was submitted, is enqueued, and the D2H, unstack = the "
    "layout undone); zero under a CPU codec.",
    ["pipeline", "part"],
    registry=REGISTRY,
)
# every batch-sized host buffer the pipelines' pool hands out (the reader
# leg's payload, the codec worker's staging buffer): "fresh" is a new
# allocation whose pages fault on first touch, "reused" a kept buffer
EC_BULK_PIPELINES = ("encode", "rebuild", "verify")
VOLUME_SERVER_EC_BULK_BUFFERS = Counter(
    "SeaweedFS_volumeServer_ec_bulk_buffers",
    "Batch-sized host buffers handed out by the bulk EC pipelines' pool "
    "(storage/ec/bulk.py POOL), by where they came from: reused = a "
    "kept buffer whose pages were touched before, fresh = a new "
    "allocation (an empty pool, or a batch larger than what was kept).",
    ["pipeline", "source"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_BULK_BATCHES = Counter(
    "SeaweedFS_volumeServer_ec_bulk_batches",
    "Stripe batches pushed through the bulk EC pipelines' codec leg.",
    ["pipeline"],
    registry=REGISTRY,
)
# over ec_bulk_batches: the share of a pipeline's batches that reached
# the device without a staging copy (rebuild's; 0 where a second reader
# holds the payload to plain rows, and under a CPU codec, which puts
# nothing)
VOLUME_SERVER_EC_BULK_DIRECT_BATCHES = Counter(
    "SeaweedFS_volumeServer_ec_bulk_direct_batches",
    "Stripe batches the bulk EC pipelines' device leg put on the device "
    "as the reader leg delivered them: read from the shard files "
    "straight into the row order the codec's program takes, no staging "
    "copy on the host.",
    ["pipeline"],
    registry=REGISTRY,
)
# over ec_bulk_batches: the share of a pipeline's batches whose wait for
# the device was shared with the next batch's transfer (the codec worker
# keeps two on the device where a successor has been submitted; 0 under
# a CPU codec and in the serial mode, which never has a successor)
VOLUME_SERVER_EC_BULK_PIPELINED_BATCHES = Counter(
    "SeaweedFS_volumeServer_ec_bulk_pipelined_batches",
    "Stripe batches whose fetch from the device began with their "
    "successor already enqueued (its device_put and program issued): "
    "the bulk EC pipelines' codec worker keeps up to two batches on "
    "the device.",
    ["pipeline"],
    registry=REGISTRY,
)
# over ec_bulk_batches: the share of a pipeline's batches whose shard
# rows the writer leg wrote side by side (bulk.write_shard_rows; 0 in
# the serial mode, which writes inline, and for a batch of fewer than
# two rows that are not holes)
VOLUME_SERVER_EC_BULK_PARALLEL_WRITE_BATCHES = Counter(
    "SeaweedFS_volumeServer_ec_bulk_parallel_write_batches",
    "Stripe batches whose shard rows the bulk EC pipelines' writer leg "
    "handed to its writer threads with two or more rows written at "
    "once, each to its own shard file from the row's own buffer.",
    ["pipeline"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_BULK_OVERLAP_FRACTION = Gauge(
    "SeaweedFS_volumeServer_ec_bulk_overlap_fraction",
    "Leg-active seconds / wall seconds of the last bulk EC pipeline run "
    "per pipeline (fsync tail excluded; 1.0 = one leg busy the whole "
    "wall, >1 = legs genuinely overlapped, up to 3.0).",
    ["pipeline"],
    registry=REGISTRY,
)
for _p in EC_BULK_PIPELINES:
    for _leg in ("read", "device", "write"):
        VOLUME_SERVER_EC_BULK_SECONDS.labels(pipeline=_p, leg=_leg)
    for _source in ("reused", "fresh"):
        VOLUME_SERVER_EC_BULK_BUFFERS.labels(pipeline=_p, source=_source)
    for _part in EC_BULK_CODEC_PARTS:
        VOLUME_SERVER_EC_BULK_CODEC_SECONDS.labels(pipeline=_p, part=_part)
    VOLUME_SERVER_EC_BULK_BATCHES.labels(pipeline=_p)
    VOLUME_SERVER_EC_BULK_DIRECT_BATCHES.labels(pipeline=_p)
    VOLUME_SERVER_EC_BULK_PIPELINED_BATCHES.labels(pipeline=_p)
    VOLUME_SERVER_EC_BULK_PARALLEL_WRITE_BATCHES.labels(pipeline=_p)
    VOLUME_SERVER_EC_BULK_OVERLAP_FRACTION.labels(pipeline=_p)

# heat-tiered residency ladder (serving/tiering.py): HBM -> host RAM ->
# disk, driven by the decayed per-volume read heat.  The census gauge
# shows where the working set lives; the promotion/demotion counters are
# the thrash signal (hysteresis exists to keep them low under a flash
# crowd); host_reads proves the warm tier actually serves from RAM.
VOLUME_SERVER_EC_TIER_VOLUMES = Gauge(
    "SeaweedFS_volumeServer_ec_tier_volumes",
    "EC volumes by residency tier after the last tier rebalance (hbm = "
    "device-resident serving, host = shard bytes pinned in host RAM, "
    "disk = served from shard files / remote).",
    ["tier"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_TIER_PROMOTIONS = Counter(
    "SeaweedFS_volumeServer_ec_tier_promotions",
    "Tier-ladder promotions by destination tier (hbm = pinned into the "
    "device cache with an AOT pre-warm, host = shard bytes staged into "
    "the pinned host-RAM reconstruct cache).",
    ["tier"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_TIER_DEMOTIONS = Counter(
    "SeaweedFS_volumeServer_ec_tier_demotions",
    "Tier-ladder demotions by source tier (hbm = heat-chosen device "
    "eviction under budget pressure or a hotter candidate's swap, host "
    "= host-RAM bytes dropped for a warmer volume).  A high rate means "
    "the ladder is thrashing — widen -ec.tier.promoteRatio or "
    "-ec.tier.minResidencySeconds.",
    ["tier"],
    registry=REGISTRY,
)
VOLUME_SERVER_EC_TIER_HOST_BYTES = Gauge(
    "SeaweedFS_volumeServer_ec_tier_host_bytes",
    "Host RAM held by the warm-tier shard cache (-ec.tier.hostCacheMB "
    "budget).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_DEGRADED_MEMO = Counter(
    "SeaweedFS_volumeServer_ec_degraded_memo",
    "Degraded-read reconstructed-interval memo outcomes: a 'hit' "
    "serves a previously reconstructed interval without re-gathering "
    ">=10 survivor shards (the repair-window hot-needle fast path, "
    "tests/test_ec.py holds it); 'miss' pays the full gather + "
    "reconstruct and populates the memo.",
    ["result"],
    registry=REGISTRY,
)
for _r in ("hit", "miss"):
    VOLUME_SERVER_EC_DEGRADED_MEMO.labels(result=_r)

VOLUME_SERVER_EC_TIER_HOST_READS = Counter(
    "SeaweedFS_volumeServer_ec_tier_host_reads",
    "Shard interval reads served from the pinned host-RAM tier "
    "(zero-copy memoryview slices of the staged shard bytes — no disk "
    "pread).",
    registry=REGISTRY,
)
for _tier in ("hbm", "host", "disk"):
    VOLUME_SERVER_EC_TIER_VOLUMES.labels(tier=_tier)
for _tier in ("hbm", "host"):
    VOLUME_SERVER_EC_TIER_PROMOTIONS.labels(tier=_tier)
    VOLUME_SERVER_EC_TIER_DEMOTIONS.labels(tier=_tier)

# -- fault policy (utils/faultpolicy.py): the tail-tolerant RPC plane's
# decision counters.  hedge_sent/hedge_wins/hedge_cancelled bound and
# prove the hedged survivor gather (a win = the spare shard beat a
# tail-slow holder); deadline_exceeded counts doomed work refused
# early; retry_budget_exhausted counts fast-fails where the per-peer
# retry budget said "stop retrying a sick node".
VOLUME_SERVER_EC_HEDGE_SENT = Counter(
    "SeaweedFS_volumeServer_ec_hedge_sent",
    "Hedge fetches armed by the degraded-read survivor gather: a "
    "pending shard fetch exceeded its peer's latency-EWMA quantile "
    "(-ec.rpc.hedgeQuantile) and a spare parity holder was asked for a "
    "different shard instead of waiting.  Bounded by the hedge token "
    "budget (-ec.rpc.hedgeBudgetPct), so this can never exceed that "
    "fraction of primary fetches.",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_HEDGE_WINS = Counter(
    "SeaweedFS_volumeServer_ec_hedge_wins",
    "Hedge fetches whose bytes completed a reconstruct before the "
    "tail-slow primary they covered — each one is a read that did NOT "
    "ride a slow peer's tail.",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_HEDGE_CANCELLED = Counter(
    "SeaweedFS_volumeServer_ec_hedge_cancelled",
    "Hedge fetches cancelled or abandoned because the gather was "
    "satisfied first (the loser side of the race; their per-call RPC "
    "timeout frees the worker thread).",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_DEADLINE_EXCEEDED = Counter(
    "SeaweedFS_volumeServer_ec_deadline_exceeded",
    "Work refused or abandoned because the request's propagated "
    "deadline budget (X-Seaweed-Deadline-Ms) was already spent — "
    "admission sheds, doomed RPCs, and survivor gathers that ran out "
    "of budget.",
    registry=REGISTRY,
)
VOLUME_SERVER_EC_RETRY_BUDGET_EXHAUSTED = Counter(
    "SeaweedFS_volumeServer_ec_retry_budget_exhausted",
    "RPC retries refused because the peer's token-bucket retry budget "
    "(-ec.rpc.retryBudgetPct) was drained — the fast-fail that keeps a "
    "sick node from turning into a cluster-wide retry storm.",
    registry=REGISTRY,
)

# streaming ingest plane (seaweedfs_tpu/ingest/): writes land in bounded
# staging arenas and EC-encode per stripe row as the .dat grows, instead
# of the after-the-fact bulk encode.  bytes/rows split by where the row
# encoded (device vs host-shed) is the plane's health headline; the
# backpressure counter is the honest "writers outran the codec" signal;
# shed splits by reason so QoS write-tier sheds, deadline dooms and
# arena overflows are distinguishable at a glance.
VOLUME_SERVER_INGEST_BYTES = Counter(
    "SeaweedFS_volumeServer_ingest_bytes",
    "Payload bytes accepted into per-volume streaming ingest pipelines "
    "(staged toward stripe rows; every byte here is EC-encoded online "
    "or swept into the offline fallback at seal).",
    registry=REGISTRY,
)
VOLUME_SERVER_INGEST_ROWS = Counter(
    "SeaweedFS_volumeServer_ingest_rows",
    "Completed stripe rows encoded by the streaming ingest plane, by "
    "where the parity was computed (device = AOT-warmed accelerator "
    "call, host = CPU codec after a shed-cold or on a CPU backend).",
    ["path"],
    registry=REGISTRY,
)
VOLUME_SERVER_INGEST_BACKPRESSURE = Counter(
    "SeaweedFS_volumeServer_ingest_backpressure",
    "Ingest arena stage() calls that had to BLOCK for a free staging "
    "row — each one is a writer stalled because the encode leg hasn't "
    "drained; a steady rate means the arena (-ec.ingest.arenaSlots) or "
    "the device is undersized for the write load.",
    registry=REGISTRY,
)
VOLUME_SERVER_INGEST_SHED = Counter(
    "SeaweedFS_volumeServer_ingest_shed",
    "Writes refused at the door by the ingest plane, by reason "
    "(qos = write-tier admission shed, deadline = the r18 budget says "
    "the upload cannot finish in time, arena = no staging row freed "
    "within the backpressure budget).",
    ["reason"],
    registry=REGISTRY,
)
for _reason in ("qos", "deadline", "arena"):
    VOLUME_SERVER_INGEST_SHED.labels(reason=_reason)
VOLUME_SERVER_INGEST_FSYNCS = Counter(
    "SeaweedFS_volumeServer_ingest_fsyncs",
    "Group-commit fsync batches issued by ingest pipelines — many "
    "writes acknowledged per fsync is the point; compare against "
    "SeaweedFS_volumeServer_ingest_fsync_writes for the batching "
    "factor.",
    registry=REGISTRY,
)
VOLUME_SERVER_INGEST_FSYNC_WRITES = Counter(
    "SeaweedFS_volumeServer_ingest_fsync_writes",
    "Writes whose durability was covered by a group-commit fsync batch "
    "(fsync_writes / fsyncs = achieved group-commit factor).",
    registry=REGISTRY,
)
VOLUME_SERVER_INGEST_PIPELINES = Gauge(
    "SeaweedFS_volumeServer_ingest_pipelines",
    "Per-volume streaming ingest pipelines currently live (streaming "
    "state valid: rows encoded so far remain byte-identical to an "
    "offline re-encode of the final .dat).",
    registry=REGISTRY,
)
VOLUME_SERVER_INGEST_STREAMED_SEALS = Counter(
    "SeaweedFS_volumeServer_ingest_seals",
    "Volume EC seals by provenance (streamed = parity rows were "
    "already encoded online and only the zero-padded tail row remained "
    "at ec.encode time; offline = the pipeline had been invalidated — "
    "vacuum, large-row boundary, restart — and the bulk executor "
    "re-encoded from scratch).",
    ["path"],
    registry=REGISTRY,
)
for _path in ("streamed", "offline"):
    VOLUME_SERVER_INGEST_STREAMED_SEALS.labels(path=_path)
for _path in ("device", "host"):
    VOLUME_SERVER_INGEST_ROWS.labels(path=_path)

# device-time attribution ledger (obs/devledger.py): every device
# dispatch — serving reconstruct, ingest row encode, scrub megakernel,
# repair re-encode, AOT pre-warm compiles, bulk executor legs — is
# tagged with a workload class and lands here per class per device, so
# "who is burning the accelerator" is a PromQL query instead of a
# per-subsystem spelunk.  The class busy sums reconcile against the
# DevicePipeline/bulk wall clocks (tests pin the conservation).
DEVICE_WORKLOADS = (
    "serving_interactive", "serving_bulk", "ingest", "scrub", "repair",
    "warmup", "bulk", "untagged",
)
VOLUME_SERVER_DEVICE_BUSY_SECONDS = Counter(
    "SeaweedFS_volumeServer_device_busy_seconds",
    "Accelerator busy seconds attributed per workload class and device "
    "(device = mesh for lane-sharded calls, a device index for pinned "
    "calls, default/host for unplaced or CPU-kernel legs; untagged = a "
    "dispatch that escaped the workload tagging — should stay ~0).",
    ["workload", "device"],
    registry=REGISTRY,
)
VOLUME_SERVER_DEVICE_DISPATCHES = Counter(
    "SeaweedFS_volumeServer_device_dispatches",
    "Device dispatches (kernel calls / codec legs / background "
    "compiles) per workload class and device.",
    ["workload", "device"],
    registry=REGISTRY,
)
VOLUME_SERVER_DEVICE_DISPATCH_BYTES = Counter(
    "SeaweedFS_volumeServer_device_dispatch_bytes",
    "Bytes moved across the device boundary (H2D + D2H) per workload "
    "class and device.",
    ["workload", "device"],
    registry=REGISTRY,
)
VOLUME_SERVER_DEVICE_QUEUE_WAIT_SECONDS = Counter(
    "SeaweedFS_volumeServer_device_queue_wait_seconds",
    "Seconds workloads spent queued for a device pipeline slot per "
    "workload class and device — who is queued behind whom.",
    ["workload", "device"],
    registry=REGISTRY,
)

MQ_FENCE_CONFLICT = Counter(
    "SeaweedFS_mq_fence_conflict",
    "Partition activations that found the durable log tail moved after "
    "the fence was written (a fenced-out owner's append landed in the "
    "KvGet->append window; offsets were resynced).",
    registry=REGISTRY,
)


def stage_histogram_snapshot() -> dict:
    """{stage: (cumulative per-le counts incl +Inf, sum_seconds)} from the
    stage histogram — the raw material of the heartbeat stage digests.
    Counts are cumulative in `le` order (the Prometheus exposition shape);
    stage_digest_deltas() turns two snapshots into per-bucket increments."""
    out: dict = {}
    for family in REQUEST_STAGE_SECONDS.collect():
        cums: dict = {}
        sums: dict = {}
        for s in family.samples:
            stage = s.labels.get("stage")
            if s.name.endswith("_bucket"):
                cums.setdefault(stage, []).append(
                    (float(s.labels["le"]), s.value)
                )
            elif s.name.endswith("_sum"):
                sums[stage] = s.value
        for stage, pairs in cums.items():
            pairs.sort(key=lambda p: p[0])
            out[stage] = (
                [int(v) for _, v in pairs], float(sums.get(stage, 0.0))
            )
    return out


def stage_digest_deltas(before: dict, after: dict) -> list:
    """[(stage, per-bucket increments, count, sum_seconds_delta)] accrued
    between two stage_histogram_snapshot() calls; stages with no new
    observations are dropped so an idle pulse ships an empty digest."""
    out = []
    for stage, (cum_b, sum_b) in after.items():
        cum_a, sum_a = before.get(stage, ([0] * len(cum_b), 0.0))
        dcum = [b - a for a, b in zip(cum_a, cum_b)]
        count = dcum[-1] if dcum else 0
        if count <= 0:
            continue
        buckets = [dcum[0]] + [
            dcum[i] - dcum[i - 1] for i in range(1, len(dcum))
        ]
        out.append((stage, buckets, count, max(0.0, sum_b - sum_a)))
    return out


FILER_REQUEST_COUNTER = Counter(
    "SeaweedFS_filer_request_total",
    "Counter of filer requests.",
    ["type"],
    registry=REGISTRY,
)
FILER_REQUEST_HISTOGRAM = Histogram(
    "SeaweedFS_filer_request_seconds",
    "Bucketed histogram of filer request processing time.",
    ["type"],
    registry=REGISTRY,
    buckets=(0.0001, 0.001, 0.01, 0.1, 1.0, 10.0),
)

S3_REQUEST_COUNTER = Counter(
    "SeaweedFS_s3_request_total",
    "Counter of s3 requests.",
    ["type", "code", "bucket"],
    registry=REGISTRY,
)


@contextmanager
def time_request(counter: Counter, histogram: Histogram, kind: str):
    """Count + time one request under the given label."""
    counter.labels(type=kind).inc()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        histogram.labels(type=kind).observe(time.perf_counter() - t0)


def start_push_loop(
    job: str,
    instance: str,
    address: str,
    interval_seconds: int,
    collect=None,
):
    """Background task PUSHING the registry to a Prometheus pushgateway
    (reference metrics.go:263-283 LoopPushingMetric): PUT the text
    exposition to /metrics/job/<job>/instance/<instance> every
    `interval_seconds`.  Returns the asyncio.Task (cancel on server
    stop), or None when no address/interval is configured — serving
    /metrics locally is unaffected either way."""
    import asyncio

    # interval 0 = pushing disabled even with an address, matching the
    # reference's early return (metrics.go:264-266)
    if not address or interval_seconds == 0:
        return None
    if interval_seconds < 0:
        # misconfigured negative interval would busy-loop; the reference
        # clamps to its 15s default the same way (metrics.go:277-279)
        interval_seconds = 15
    return asyncio.create_task(
        _push_loop(job, instance, address, interval_seconds, collect)
    )


async def _push_loop(job, instance, address, interval_seconds, collect):
    import asyncio
    import logging
    import urllib.parse

    import aiohttp

    log = logging.getLogger("stats")
    base = address if "://" in address else f"http://{address}"
    url = (
        f"{base}/metrics/job/{urllib.parse.quote(job, safe='')}"
        f"/instance/{urllib.parse.quote(instance, safe='')}"
    )
    log.info("pushing metrics to %s every %ds", url, interval_seconds)

    async def push_once(sess):
        if collect is not None:
            collect()
        async with sess.put(
            url,
            data=generate_latest(REGISTRY),
            headers={"Content-Type": CONTENT_TYPE_LATEST},
        ) as r:
            if r.status >= 300:
                log.warning(
                    "pushgateway %s returned HTTP %d", url, r.status
                )

    async with aiohttp.ClientSession() as sess:
        try:
            while True:
                try:
                    await push_once(sess)
                except asyncio.CancelledError:
                    raise
                except Exception as e:  # noqa: BLE001 — the gateway being
                    # down must not kill the server's push loop
                    log.warning("could not push metrics to %s: %s", url, e)
                await asyncio.sleep(interval_seconds)
        except asyncio.CancelledError:
            # final best-effort push so a short-lived run (benchmark, CI
            # job) doesn't silently drop the last interval's samples —
            # bounded, so a dead gateway can't stall server shutdown
            try:
                await asyncio.wait_for(push_once(sess), timeout=2.0)
            except Exception:  # noqa: BLE001
                log.debug("final metrics push to %s failed", url)
            raise


async def metrics_handler(request):
    """aiohttp GET /metrics handler (the reference's per-server metrics
    listener, metrics.go StartMetricsServer)."""
    from aiohttp import web

    collect = request.app.get(metrics_collect_key())
    if collect is not None:
        collect()
    return web.Response(
        body=generate_latest(REGISTRY), content_type=CONTENT_TYPE_LATEST.split(";")[0]
    )
