"""Device-resident EC shard cache + batched degraded-read reconstruction.

A naive device degraded read ships 10x the payload (the survivor
intervals) host->device per needle before the kernel can run, so the call
is transfer-bound.  The fix is to keep hot shards *resident in HBM*: then a degraded
read sends only (offset, row) scalars up and the reconstructed interval
bytes down, and any number of concurrent needle reconstructions batch into
ONE device call that gathers survivor slices from the resident buffers.

This is the TPU answer to the reference's per-needle goroutine fan-in
(/root/reference/weed/storage/store_ec.go:339-393): instead of fetching
interval bytes from >=10 peers per needle, the rebuilder/reader node pins
the survivor shards once (mount time or first read) and serves every
degraded needle from device memory.

Shapes and compile hygiene:
  * shard buffers are padded to SHARD_QUANTUM so volumes of similar size
    share jit caches, plus MAX_TILE slack so slices never clamp;
  * request sizes quantize to SIZE_BUCKETS, request counts to
    COUNT_BUCKETS, offsets align down to LANE (128) with the residual
    sliced off on host — a handful of compiles total, warmable up front.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import functools
import json
import os
import threading
import time
import warnings
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from . import gf256, rs_tpu
from ..parallel import mesh as mesh_mod
from ..obs import devledger
from ..obs import incident as obs_incident
from ..obs import trace as obs_trace
from ..stats import metrics as stats_metrics

DATA_SHARDS = 10
TOTAL_SHARDS = 14

LANE = 128  # TPU lane tile: device slices start lane-aligned
# The fused kernel's DMA source is a (1024)-tiled 1-D HBM memref: Mosaic
# must PROVE slice starts divisible by 1024, so fused offsets align down
# to this and the <=1023-byte residual joins the host-trimmed delta.
FUSED_ALIGN = 1024
SIZE_BUCKETS = (2048, 8192, 32768, 131072, 524288, 2 * 1024 * 1024)
# a 256-wide bucket amortizes the per-call dispatch over whole read
# bursts (padding past the true count costs only device compute: the
# in-jit [:n] trim keeps padded rows off the wire).  The ladder jumps
# 64 -> 256 on purpose: every bucket is a compiled shape warm() must
# pay for, and a 65-request batch padded to 256 wastes only
# microseconds of MXU time
COUNT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 256)
MAX_TILE = SIZE_BUCKETS[-1]
# split oversized intervals into chunks that fit the largest bucket even
# after the <=FUSED_ALIGN-1 alignment residual
CHUNK = MAX_TILE - FUSED_ALIGN
SHARD_QUANTUM = 64 * 1024 * 1024

# The fused kernels' per-call staging is ONE packed [N] int32 vector:
# (offset in FUSED_ALIGN units) << META_ROW_BITS | wanted matrix row.
# Rows index the wanted-shard list (<= TOTAL_SHARDS = 14, 5 bits with
# margin), leaving 26 bits of offset units = 64GB of addressable shard —
# far past SHARD_QUANTUM padding.  Halving the r09 meta ([2, N] -> [N])
# halves serving H2D bytes per fused batch; the XLA fallback's three
# vectors collapse into one [3, N] array for the same reason (one
# device_put, one dispatch RTT, instead of three).
META_ROW_BITS = 5
_META_ROW_MASK = (1 << META_ROW_BITS) - 1
# the staging vectors are DONATED to their kernels (donate_argnums): a
# consumed batch's meta buffer frees as soon as the kernel reads it
# instead of surviving until the pipelined call's D2H.  XLA warns when a
# donated buffer cannot ALSO alias an output — always true here (int32
# staging in, uint8 bytes out), so the advisory is noise by construction.
# Applied per compile site via _quiet_donation too: pytest re-arms the
# global filter around every test, so the module-level form alone leaks
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


@contextlib.contextmanager
def _quiet_donation():
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


class CacheMiss(LookupError):
    """Not enough resident shards to serve the request."""


class ColdShape(CacheMiss):
    """A serving reconstruct would dispatch a device shape that is not
    compiled yet (the volume's AOT warm plan hasn't reached it): the
    caller must serve the read on the host path instead of stalling the
    dispatcher behind an inline compile.  Raised BEFORE any device
    work, and only for caches with an AOT warm plan + shed_cold set —
    direct callers and never-warmed volumes keep inline compiles."""


_COMPILE_CACHE_SET = False
# observable cache state: the outcome is a gauge, a telemetry field, and
# a volume.device.status column (compile_cache_status())
_COMPILE_CACHE_STATE = {"enabled": False, "path": "", "error": ""}
# JAX's own count of this process's compile requests that consulted the
# persistent cache, how many of them were served from it, and how many
# executables it wrote back (jax.monitoring events): requests == hits
# is what proves that a restarted process compiled nothing
_COMPILE_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_compile_cache_counts = dict.fromkeys(_COMPILE_CACHE_EVENTS.values(), 0)
_compile_cache_counts_lock = threading.Lock()


def _count_compile_cache_event(event: str, **_kw) -> None:
    name = _COMPILE_CACHE_EVENTS.get(event)
    if name is not None:
        with _compile_cache_counts_lock:
            _compile_cache_counts[name] += 1


jax.monitoring.register_event_listener(_count_compile_cache_event)

# Where compiled kernels persist when JAX_COMPILATION_CACHE_DIR does not
# say: ONE fixed directory inside the checkout (git-ignored), shared by
# `volume`, `server`, chip_smoke.py and benchmark/.  The directory is part
# of the cache key, so a path that moved with -dir, a temporary name, a
# pid or a time would never hit.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)

# name of the observed-(size, count)-frequency sidecar persisted next to
# the compile cache, so warm()'s observed-buckets-first priority order
# survives process restarts instead of resetting to ladder order
OBSERVED_SHAPES_FILE = "observed_shapes.json"


def compile_cache_dir() -> str:
    """The persistent compile cache's directory: the environment's when
    JAX_COMPILATION_CACHE_DIR is set, else the fixed in-checkout path."""
    return os.environ.get(COMPILE_CACHE_ENV) or COMPILE_CACHE_DIR


def enable_persistent_compile_cache() -> bool:
    """Turn on XLA's persistent compilation cache so the reconstruct
    kernel's per-(size, count)-shape compiles survive process restarts,
    and load the observed-shape frequency state persisted next to it.

    Where JAX_COMPILATION_CACHE_DIR is set JAX has already adopted it
    and this function sets NO directory in code; otherwise the cache
    goes to COMPILE_CACHE_DIR.  Every compile persists (no minimum
    compile time: several warm shapes compile in under a second).

    The setting is PROCESS-GLOBAL, so call this once from the process
    entry point (the volume CLI does, next to -ec.deviceCacheMB); later
    calls no-op.  Returns True when the cache was enabled; the outcome
    either way is visible via compile_cache_status() and the
    SeaweedFS_volumeServer_ec_compile_cache_enabled gauge."""
    global _COMPILE_CACHE_SET
    if _COMPILE_CACHE_SET:
        return False
    path = compile_cache_dir()
    try:
        # probe writability up front: jax.config.update accepts any
        # string and the failure would otherwise surface as a per-shape
        # cache-write warning long after the operator stopped looking
        os.makedirs(path, exist_ok=True)
        # pid-suffixed probe: two servers sharing a cache dir must not
        # race on one filename (the loser's os.remove would read as
        # "bad path" and silently disable ITS persistent cache)
        probe = os.path.join(path, f".write_probe.{os.getpid()}")
        with open(probe, "w"):
            pass
        os.remove(probe)
        if not os.environ.get(COMPILE_CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    except Exception as e:  # noqa: BLE001 — bad path
        import logging

        logging.getLogger(__name__).warning(
            "persistent compile cache unavailable at %s (%s): every "
            "restart will recompile the reconstruct kernel shapes", path, e,
        )
        _COMPILE_CACHE_STATE.update(enabled=False, path=path, error=str(e))
        stats_metrics.VOLUME_SERVER_EC_COMPILE_CACHE_ENABLED.set(0)
        return False
    _COMPILE_CACHE_SET = True
    _COMPILE_CACHE_STATE.update(enabled=True, path=path, error="")
    stats_metrics.VOLUME_SERVER_EC_COMPILE_CACHE_ENABLED.set(1)
    load_observed_shapes(os.path.join(path, OBSERVED_SHAPES_FILE))
    return True


def compile_cache_status() -> dict:
    """{"enabled", "path", "error"} — the persistent-compile-cache
    outcome, shipped in heartbeat telemetry and volume.device.status —
    plus JAX's own {"requests", "hits", "misses"} for this process."""
    with _compile_cache_counts_lock:
        return {**_COMPILE_CACHE_STATE, **_compile_cache_counts}


# --- device identity + swallowed-failure record -------------------------------
# A serving process SURVIVES a pin, warm or AOT-compile failure (the read
# falls to the host codec), so the failure must be countable from the
# outside: volume.device.status and the volume server's /status report
# each kind's count and last message next to the device's identity.

DEVICE_FAILURE_KINDS = ("pin", "warm", "aot")
_device_failures = {
    kind: {"count": 0, "last": ""} for kind in DEVICE_FAILURE_KINDS
}
_device_failures_lock = threading.Lock()


def note_device_failure(kind: str, message: str) -> None:
    with _device_failures_lock:
        rec = _device_failures[kind]
        rec["count"] += 1
        rec["last"] = message[:500]


def device_status(ec_backend: str, cache=None) -> dict:
    """What the accelerator is and how the EC paths resolved against it:
    {"platform", "device_kind", "device_count"} as JAX reports them, the
    resolved EC backend of `ec_backend` (the -ec.backend flag) with the
    serving kernel and interpret mode the entry points will pick, AOT
    registry, every swallowed pin / warm / AOT-compile failure (count +
    last message), the allocator's bytes per local device ("memory") and
    (with a `cache`) what is resident where and each warm plan's state."""
    from . import rs

    devices = mesh_mod.global_devices()  # == jax.devices(), pod order
    with _device_failures_lock:
        failures = {k: dict(v) for k, v in _device_failures.items()}
    kernel, interpret = _kernel_mode()
    out = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "ec_backend": rs.resolve_backend(ec_backend),
        "serving_kernel": kernel,
        "interpret": interpret,
        "compile_cache": compile_cache_status(),
        "aot": aot_stats(),
        "failures": failures, **_device_memory(),
    }
    if cache is not None:
        resident = cache.resident_by_vid()
        out["cache"] = {
            "budget_bytes": cache.budget,
            "layout": cache.layout,
            "mesh_devices": cache.n_devices,
            "per_device": cache.device_stats(),
            "volumes": {
                str(vid): {
                    "resident_shards": sids,
                    "placement": str(cache.placement(vid)),
                    "aot_state": cache.aot_state(vid),
                }
                for vid, sids in sorted(resident.items())
            },
        }
    return out


# --- observed-shape persistence ---------------------------------------------
# warm() walks the (size, count) grid observed-buckets-first; persisting
# the frequency map next to the compile cache means a RESTARTED process
# warms the live workload's shapes first too, not just a re-pin.

_OBSERVED_SAVE_INTERVAL_S = 5.0
_observed_path: str | None = None
_observed_dirty = False
_observed_last_save = 0.0


def load_observed_shapes(path: str) -> int:
    """Merge a persisted observed-shape frequency file into this
    process's ranking and adopt `path` for future saves.  Returns the
    number of (size, count) buckets loaded (0 when absent/corrupt —
    either way the path is adopted so the state starts persisting)."""
    global _observed_path
    _observed_path = path
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        # parse fully BEFORE touching shared state: a syntactically
        # valid JSON file with the wrong shape (bad row arity, non-list
        # buckets) is just as corrupt as unparseable JSON
        rows = [
            (int(size), int(count), int(hits))
            for size, count, hits in data["buckets"]
        ]
    except FileNotFoundError:
        return 0
    except Exception as e:  # noqa: BLE001 — corrupt file must not stop boot
        import logging

        logging.getLogger(__name__).warning(
            "ignoring corrupt observed-shapes file %s: %s", path, e
        )
        return 0
    with _shapes_lock:
        for size, count, hits in rows:
            key = (size, count)
            _observed_buckets[key] = _observed_buckets.get(key, 0) + hits
    return len(rows)


def persist_observed_shapes(path: str | None = None) -> bool:
    """Atomically write the observed-shape frequency map (tmp + rename)
    to `path` (default: the path adopted by load_observed_shapes).
    Returns True when written."""
    global _observed_dirty, _observed_last_save
    path = path or _observed_path
    if path is None:
        return False
    with _shapes_lock:
        buckets = [
            [s, c, n] for (s, c), n in sorted(_observed_buckets.items())
        ]
        _observed_dirty = False
    tmp = path + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"buckets": buckets}, f)
        os.replace(tmp, path)
    except OSError:
        # the observations are still unsaved: re-arm the dirty flag so
        # the hook retries once the dir is writable again — but stamp
        # the attempt so a persistently broken dir costs one failed
        # open per save interval, not one per batch
        with _shapes_lock:
            _observed_dirty = True
        _observed_last_save = time.monotonic()
        return False
    _observed_last_save = time.monotonic()
    return True


def _maybe_persist_observed() -> None:
    """Throttled save hook on the dispatch path: cheap no-op unless a
    new observation landed and the last save is older than the
    interval (the file is tiny — a handful of bucket rows)."""
    if (
        _observed_path is not None
        and _observed_dirty
        and time.monotonic() - _observed_last_save > _OBSERVED_SAVE_INTERVAL_S
    ):
        persist_observed_shapes()


def _bucket(values: tuple[int, ...], need: int) -> int:
    for v in values:
        if need <= v:
            return v
    raise ValueError(f"{need} exceeds largest bucket {values[-1]}")


# bound per-call output (count * size bucket) so a wide batch of large
# intervals can't balloon device/host buffers; small-needle batches (the
# dominant serving shape) still ride the widest counts
_MAX_CALL_OUT = 32 * 1024 * 1024
# bound AGGREGATE un-fetched output across pipelined calls: each pending
# call parks its [n, fetch] result in HBM until the fetch loop reaches it
_MAX_PENDING_OUT = 128 * 1024 * 1024


def _max_count(size_bucket: int) -> int:
    return max(1, min(COUNT_BUCKETS[-1], _MAX_CALL_OUT // size_bucket))


# resident shard layouts.  "flat": the round-5/6 layout — one 1-D padded
# buffer per shard, reconstructed with the plain [8m,8k] bit matrix.
# "blockdiag": the same resident bytes SERVED through the block-diagonal
# g-group system (rs_tpu round-3: A_blk [128, 320] fills the MXU's M
# dimension, ~157 vs ~121 GB/s flat).  The host stages the layout for
# free: a request's tile (or scrub's shard span) splits into g
# CONTIGUOUS segments — segment-stacked [g*k, B/g] input rows are just
# g slices per survivor, so the gather reads them straight out of the
# flat resident buffers and no device restack (58 GB/s byte transposes,
# the round-3 dealbreaker) ever happens.
LAYOUTS = ("flat", "blockdiag")


class StagingArena:
    """Per-slot preallocated host staging rows for a batch's packed
    offset/row vectors: BLOCKS row-blocks of [3, COUNT_BUCKETS[-1]]
    int32, each wide enough for the widest device call of either kernel
    family (fused uses one packed row, the XLA fallback all three).
    Every call of a batch in flight stages into a block of its own
    (`take`), so its put can be left asynchronous: the transfer may
    read the rows at any time until the call's result is ready, and
    reconstruct_intervals gives a block back (`give`) only after it
    has waited for that result.  A batch with more calls than blocks
    collects its oldest call first and stages into the rows that call
    gave back.  The pipeline slot, and with it the arena, is held until
    every result of the batch is fetched, and a released slot frees all
    blocks (`reset`), so the other slot's in-flight batch never touches
    these rows.  Only safe where device_put COPIES (TPU/GPU): the CPU
    PJRT client zero-copies aligned numpy, so an arena there would
    alias (and corrupt) an asynchronously executing call's input —
    reconstruct_intervals gates arena use on on_tpu()."""

    # rows of a block, by kernel family
    ROWS_FUSED = 1   # packed (offset_units << META_ROW_BITS | row)
    ROWS_XLA = 3     # offsets / rows / deltas
    # a batch is one call a size bucket present (more only where a group
    # outgrows its count bucket, _max_count): one block a bucket lets
    # every put of such a batch fly
    BLOCKS = len(SIZE_BUCKETS)

    def __init__(self, width: int | None = None):
        self.width = width or COUNT_BUCKETS[-1]
        buf = np.empty(
            (self.BLOCKS, self.ROWS_XLA, self.width), dtype=np.int32
        )
        # one [3, width] view a block, kept: every staged view of a block
        # derives from the same object
        self.blocks = list(buf)
        self.reset()

    def reset(self) -> None:
        """Every block is free again (the slot was released)."""
        self._free = list(range(len(self.blocks) - 1, -1, -1))

    def take(self) -> int | None:
        """-> a free block's index, None while all are in flight."""
        return self._free.pop() if self._free else None

    def give(self, block: int) -> None:
        """The call staged in `block` has its result: rows reusable."""
        self._free.append(block)

    def stage_fused(
        self, packed: list[int], pad: int, block: int = 0
    ) -> np.ndarray:
        """-> [n] int32 view of `block` holding the packed meta."""
        n = len(packed) + pad
        view = self.blocks[block][0, :n]
        view[: len(packed)] = packed
        view[len(packed):] = 0
        return view

    def stage_xla(
        self, offsets: list[int], rows: list[int], deltas: list[int],
        pad: int, block: int = 0,
    ) -> np.ndarray:
        """-> [3, n] int32 view of `block` (offsets/rows/deltas)."""
        n = len(offsets) + pad
        view = self.blocks[block][:, :n]
        for i, col in enumerate((offsets, rows, deltas)):
            view[i, : len(col)] = col
            view[i, len(col):] = 0
        return view


class PipelineSlot:
    """What DevicePipeline.slot() yields: the slot-acquisition wait (for
    the device span's saturation attribution) plus this slot's private
    staging arena."""

    __slots__ = ("wait_s", "arena")

    def __init__(self, wait_s: float, arena: StagingArena):
        self.wait_s = wait_s
        self.arena = arena


class DevicePipeline:
    """Double-buffered staging gate for the device leg of batched
    reconstruct calls: `slots=2` lets batch N+1 pack (outside the slot)
    and ship+execute (inside it) while batch N drains its D2H — only
    N's fetch blocks N's completion.  `slots=1` is the serial baseline
    (-ec.serving.overlap.disable).  Each slot owns a preallocated
    StagingArena so a held slot's host vectors stage into reused
    memory, a row-block a call in flight (no per-batch np alloc churn;
    the r11 donation work).  The overlap-fraction gauge is device-busy
    seconds / wall seconds over the current batch window (a window
    opens when the pipeline leaves idle; the ratio refreshes at EVERY
    batch completion — a drain-only update would go stale under exactly
    the sustained load it exists to measure), so 1.0 means the device
    section ran the whole window and >1 means the staging slots
    genuinely overlapped."""

    def __init__(self, slots: int = 2):
        self._cond = threading.Condition()
        self._slots = max(1, slots)
        self._active = 0
        self._busy_s = 0.0
        # cumulative (never-reset) busy clock — the conservation anchor
        # the devledger per-class sums reconcile against; _busy_s stays
        # windowed because the overlap gauge needs the window semantics
        self.total_busy_s = 0.0
        self._window_t0 = 0.0
        self.last_overlap = 0.0
        # arena pool: one per concurrently held slot, grown on demand so
        # set_slots() widening never reallocates under the lock-holder
        self._arenas: list[StagingArena] = []
        self._free_arenas: list[int] = []

    @property
    def slots(self) -> int:
        return self._slots

    def set_slots(self, n: int) -> None:
        with self._cond:
            self._slots = max(1, int(n))
            self._cond.notify_all()

    @contextlib.contextmanager
    def slot(self):
        """Hold one staging slot for a device section; yields a
        PipelineSlot carrying the time spent waiting for the slot
        (annotated on the device span so a saturated pipeline is
        attributable) and the slot's staging arena."""
        t_req = time.perf_counter()
        with self._cond:
            while self._active >= self._slots:
                self._cond.wait()
            self._active += 1
            if self._active == 1:
                self._window_t0 = time.perf_counter()
                self._busy_s = 0.0
            if self._free_arenas:
                arena_idx = self._free_arenas.pop()
            else:
                self._arenas.append(StagingArena())
                arena_idx = len(self._arenas) - 1
        t0 = time.perf_counter()
        try:
            yield PipelineSlot(t0 - t_req, self._arenas[arena_idx])
        finally:
            dur = time.perf_counter() - t0
            with self._cond:
                self._active -= 1
                self._arenas[arena_idx].reset()
                self._free_arenas.append(arena_idx)
                self._busy_s += dur
                self.total_busy_s += dur
                wall = time.perf_counter() - self._window_t0
                if wall > 0:
                    self.last_overlap = self._busy_s / wall
                    stats_metrics.VOLUME_SERVER_EC_OVERLAP_FRACTION.set(
                        self.last_overlap
                    )
                self._cond.notify()
            # slot duration IS the device section's busy time, so the
            # ledger's per-class sum conserves against total_busy_s by
            # construction (workload/device ride the caller's context)
            devledger.record(busy_s=dur, queue_wait_s=t0 - t_req)


class DeviceShardCache:
    """LRU cache of EC shard bytes pinned in device memory.

    Keyed by (vid, shard_id).  `budget_bytes` bounds device-padded bytes;
    inserting past the budget evicts least-recently-used shards (whole
    shards — a partially resident volume simply fails over to the host
    path via CacheMiss).

    Mesh-sharded residency (r19, -ec.serving.mesh.*): with
    `mesh_devices` set (0 = every local device) the cache lays volumes
    out ACROSS the serving mesh instead of whole onto the default
    device.  A volume whose shard files reach `mesh_min_shard_bytes`
    is lane-sharded: each shard's padded buffer is staged with
    `jax.device_put(x, NamedSharding(mesh, P("shard")))`, so device d
    holds byte-chunk d of every shard and the volume's resident
    capacity is the WHOLE mesh's budget, not one chip's.  Smaller
    volumes pin whole onto the least-loaded device (spreading a tiny
    volume across 8 chips buys no capacity and pays mesh dispatch).
    Budgets are accounted PER DEVICE (`budget_bytes / n_devices`
    each): eviction pressure targets the device that is actually full,
    and the tiering ladder's fit arithmetic follows the same per-device
    vectors (serving/tiering.py).

    Pod scale (r20, -ec.mesh.*): with `global_mesh=True` the mesh spans
    EVERY process of a multi-controller job (parallel.mesh.
    global_serving_mesh) and the cache becomes one member of an SPMD
    group.  Three rules keep the group consistent without any cache-to-
    cache coordination channel:

      * the mesh/whole placement decision is a pure function of
        (shard_bytes, mesh_min_shard_bytes) — identical on every host —
        so one volume can never straddle layouts across hosts; only the
        least-loaded pick for a whole pin is host-local (a whole pin IS
        host-local: it lands on one of THIS process's devices);
      * mesh-placed arrays are staged with
        `jax.make_array_from_process_local_data`, each host providing
        exactly its devices' stripes (no survivor byte ever crosses the
        host boundary at pin time either);
      * eviction is PARTITIONED: mesh puts evict only mesh-placed
        victims (pressure from mesh bytes alone) and host-local puts
        never evict mesh-placed arrays — the mesh-array set stays a
        pure function of the SPMD put sequence, so no host can evict a
        lane of an array its peers still serve (a collective against a
        half-evicted array deadlocks the pod).
    """

    def __init__(
        self,
        budget_bytes: int = 8 << 30,
        shard_quantum: int = SHARD_QUANTUM,
        layout: str = "flat",
        groups: int = rs_tpu.BLOCKDIAG_GROUPS,
        mesh_devices: int | None = None,
        mesh_min_shard_bytes: int = 8 << 20,
        global_mesh: bool = False,
    ):
        if layout not in LAYOUTS:
            raise ValueError(f"unknown resident layout {layout!r}")
        if groups < 1 or SIZE_BUCKETS[0] % (groups * LANE):
            # every size bucket is a multiple of the smallest, so this
            # one check guarantees lane-aligned tile/groups segments on
            # the XLA path (the fused path re-derives its own
            # groups*FUSED_ALIGN-aligned ladder)
            raise ValueError(
                f"groups={groups} must split the {SIZE_BUCKETS[0]}-byte "
                "size bucket into lane-aligned segments"
            )
        self.budget = budget_bytes
        self.quantum = shard_quantum
        # the serving mesh (parallel/mesh.py — the one home shared with
        # the bulk plane): None = the pre-r19 single-device layout.
        # mesh_devices=None keeps it off; 0 = all local devices; n = the
        # first n.  A resolved 1-wide mesh degrades to None (shard_map
        # overhead with no capacity win).
        self.mesh = (
            (
                mesh_mod.global_serving_mesh(mesh_devices)
                if global_mesh
                else mesh_mod.serving_mesh(mesh_devices)
            )
            if mesh_devices is not None else None
        )
        self.n_devices = (
            int(self.mesh.devices.size) if self.mesh is not None else 1
        )
        # pod-scale bookkeeping: which hosts (process indices) the mesh
        # spans, and which global lane indices are THIS process's.  A
        # single-process global mesh degrades to n_hosts == 1 and
        # _local_dev_indices == range(n_devices) — every multiprocess
        # branch below collapses to the r19 behavior.
        self.n_hosts = max(1, len(mesh_mod.mesh_hosts(self.mesh)))
        self.multiprocess = self.n_hosts > 1
        if self.mesh is not None:
            me = mesh_mod.process_index()
            self._local_dev_indices = [
                i
                for i, d in enumerate(self.mesh.devices.reshape(-1))
                if mesh_mod.device_host(d) == me
            ]
        else:
            self._local_dev_indices = [0]
        self.mesh_min_shard_bytes = mesh_min_shard_bytes
        # interleaved stripe width of the lane-sharded layout: stripe c
        # of a padded buffer lives on device c % n (the host permutes
        # the buffer owner-major at put time so NamedSharding's
        # contiguous split lands each device exactly its stripes).
        # Interleaving is what keeps ownership EVEN at any volume size:
        # a contiguous chunk-per-device split would park all of a
        # small-ish volume's data (and every zipf-hot byte range) on
        # the first chunks' owners while the padding tail's owners sat
        # idle, and the per-device count padding of a skewed batch
        # multiplies compute.  Each stripe must fit the largest gather
        # window placeable in it (>= SIZE_BUCKETS[0]) and stay
        # FUSED_ALIGN-aligned.
        self.stripe = 0
        if self.mesh is not None:
            q = self.n_devices * max(FUSED_ALIGN, SIZE_BUCKETS[0])
            self.quantum = -(-self.quantum // q) * q
            self.stripe = self.quantum // self.n_devices
        # which reconstruct/scrub kernel family serves this cache's bytes
        # (-ec.serving.layout); mutable at runtime — the bytes are
        # layout-agnostic (blockdiag segments are contiguous slices of
        # the same flat buffers), only the compiled shapes differ
        self.layout = layout
        self.groups = groups
        # the double-buffered device staging gate shared by every
        # reconstruct call against this cache (-ec.serving.overlap)
        self.pipeline = DevicePipeline()
        # the (size, count) shapes the store's pin thread pre-compiles
        # after pinning a volume (warm()): by default one probe per size
        # bucket and count bucket, so with warm()'s fetch-rung expansion
        # the plan covers EVERY shape the fused serving path can
        # dispatch — a read of a warmed volume never sheds cold.
        # Deployments with a known workload shape can narrow these to
        # cut mount-time compile cost.
        self.warm_sizes: tuple[int, ...] = SIZE_BUCKETS
        self.warm_counts: tuple[int, ...] = COUNT_BUCKETS
        # AOT shed policy (-ec.serving.aot.disable): when True AND a
        # volume has an AOT warm plan (aot_state != "none"), a serving
        # reconstruct that would hit a still-cold device shape raises
        # ColdShape (host fallback + background compile) instead of
        # paying an inline compile.  Volumes never warmed (empty
        # warm plan — the CI convention warm_sizes=()) keep the legacy
        # inline-compile behavior so direct callers are unaffected.
        self.shed_cold = True
        self._lock = threading.Lock()
        # the staging buffer a TPU's puts share (see _put), and its turn
        self._stage_lock = threading.Lock()
        self._stage_buf: np.ndarray | None = None
        # vid -> "none" | "warming" | "done": whether an AOT warm plan
        # was started/finished for this volume (warm() maintains it)
        self._aot_states: dict[int, str] = {}
        self._arrays: OrderedDict[tuple[int, int], object] = OrderedDict()
        self._true_sizes: dict[tuple[int, int], int] = {}
        # vid -> the disk-location directory whose shard files were
        # pinned.  The cache is keyed by (vid, shard) only, so a vid
        # mounted in several locations is ambiguous without this: scrub
        # and read verdicts must be attributed to the location whose
        # bytes are actually resident (ADVICE r5).
        self._pin_source: dict[int, str] = {}
        # vid -> resident shard count, maintained on put/evict so the
        # serving path's per-read routing predicate is O(1) instead of
        # a scan-and-sort of the whole key set under the lock
        self._vid_counts: dict[int, int] = {}
        # per-device padded bytes held (len 1 without a mesh): the
        # accounting the per-device budget/eviction/tiering all share.
        # bytes_used (the pre-r19 scalar every caller reads) is the sum.
        self._dev_bytes: list[int] = [0] * self.n_devices
        # per-device MESH-PLACED padded bytes only: the pressure signal
        # of the multiprocess eviction partition (mesh puts may only
        # evict mesh victims, so their budget check must not see
        # host-local whole-pins another host knows nothing about)
        self._mesh_dev_bytes: list[int] = [0] * self.n_devices
        # vid -> "mesh" | device index: where this volume's arrays
        # live, decided at first put (claimed like the pin source so a
        # partially pinned volume can never interleave placements)
        self._vid_place: dict[int, object] = {}
        # key -> (place, padded size): what evicting the key frees, per
        # device
        self._foot: dict[tuple[int, int], tuple[object, int]] = {}
        # cumulative telemetry counters, reported up the heartbeat
        # (pb VolumeServerTelemetry): budget-pressure evictions are the
        # "HBM is too small for the working set" signal, pin claims the
        # "how many volumes ever went resident here" one
        self.evictions = 0
        self.pin_claims = 0

    def _padded_len(self, n: int) -> int:
        need = n + MAX_TILE
        return -(-need // self.quantum) * self.quantum

    # ------------------------------------------------- per-device accounting

    @property
    def bytes_used(self) -> int:
        """Total padded device bytes held (sum over the mesh) — the
        pre-r19 scalar every status/telemetry caller reads."""
        return sum(self._dev_bytes)

    @property
    def device_budget(self) -> int:
        """Per-device byte budget: the total budget split evenly over
        the mesh (the whole budget on a single-device cache)."""
        return self.budget // self.n_devices

    def _shares(self, place, size: int) -> list[tuple[int, int]]:
        """(device index, padded bytes) pairs one array of `size` costs
        under placement `place` ("mesh" = an even split — NamedSharding
        over the byte axis gives every device exactly size/n)."""
        if place == "mesh":
            per = size // self.n_devices
            return [(d, per) for d in range(self.n_devices)]
        return [(int(place), size)]

    def _publish_dev_gauges(self) -> None:
        for d, used in enumerate(self._dev_bytes):
            stats_metrics.VOLUME_SERVER_EC_DEVICE_CACHE_BYTES.labels(
                device=str(d)
            ).set(used)

    def _claim_place_locked(self, vid: int, shard_bytes: int):
        """First put of a vid decides (and pins) its placement: mesh
        lane-sharding for volumes worth spreading, else whole onto the
        least-loaded device.  Later puts of the same vid follow the
        claim — one volume must never straddle placements (the
        reconstruct kernels assume a uniform survivor layout)."""
        place = self._vid_place.get(vid)
        if place is None:
            if self.mesh is None:
                place = 0
            elif shard_bytes >= self.mesh_min_shard_bytes:
                # deterministic across processes: a pure function of the
                # shard size, so every host of a pod mesh claims the
                # same layout for the same volume (host-aware placement
                # invariant — one volume never straddles layouts)
                place = "mesh"
            else:
                # a whole pin is HOST-LOCAL: only this process's lanes
                # are addressable landing spots (== range(n) when
                # single-process)
                place = min(
                    self._local_dev_indices,
                    key=lambda d: self._dev_bytes[d],
                )
            self._vid_place[vid] = place
        return place

    def placement(self, vid: int):
        """"mesh" | device index | None (nothing of `vid` was ever
        placed) — the layout the serving path must dispatch for."""
        with self._lock:
            return self._vid_place.get(vid)

    def vid_sharded(self, vid: int) -> bool:
        with self._lock:
            return self._vid_place.get(vid) == "mesh"

    def device_stats(self) -> list[dict]:
        """Per-device [{"used_bytes", "budget_bytes"}] — the telemetry
        breakdown behind volume.device.status and cluster.health."""
        budget = self.device_budget
        with self._lock:
            return [
                {"used_bytes": used, "budget_bytes": budget}
                for used in self._dev_bytes
            ]

    def pressure_devices(self) -> list[int]:
        """Devices currently over their per-device budget, fullest
        first — what the tiering ladder's pressure demotion targets."""
        budget = self.device_budget
        with self._lock:
            over = [
                (used - budget, d)
                for d, used in enumerate(self._dev_bytes)
                if used > budget
            ]
        return [d for _, d in sorted(over, reverse=True)]

    def vid_device_bytes(self, vid: int) -> dict[int, int]:
        """device -> padded bytes held by `vid` (what demoting it
        frees, per device)."""
        out: dict[int, int] = {}
        with self._lock:
            for key, (place, size) in self._foot.items():
                if key[0] != vid:
                    continue
                for d, share in self._shares(place, size):
                    out[d] = out.get(d, 0) + share
        return out

    def device_bytes_by_vid(self) -> dict[int, dict[int, int]]:
        """vid -> {device -> padded bytes} in ONE locked pass over the
        footprint map — the rebalance-cycle bulk form of
        vid_device_bytes (a per-vid call rescans the whole map under
        the serving-path lock once per volume per cycle)."""
        out: dict[int, dict[int, int]] = {}
        with self._lock:
            for (vid, _sid), (place, size) in self._foot.items():
                dev = out.setdefault(vid, {})
                for d, share in self._shares(place, size):
                    dev[d] = dev.get(d, 0) + share
        return out

    def plan_pin(
        self, n_shards: int, shard_bytes: int, vid: int | None = None
    ) -> dict[int, int]:
        """device -> padded bytes a full pin of (n_shards x shard_bytes)
        WOULD add, previewing the placement rule — the tiering ladder's
        per-device fit arithmetic.  Pass `vid` so an existing placement
        claim wins over the least-loaded preview: budget-pressure
        eviction deliberately RETAINS a vid's claim, so a re-pin lands
        back on the claimed device — the fit check must judge the
        device the pin will ACTUALLY land on, not where a fresh volume
        would go."""
        padded = self._padded_len(shard_bytes)
        with self._lock:
            place = self._vid_place.get(vid) if vid is not None else None
            if place is None:
                if self.mesh is None:
                    place = 0
                elif shard_bytes >= self.mesh_min_shard_bytes:
                    place = "mesh"
                else:
                    place = min(
                        self._local_dev_indices,
                        key=lambda i: self._dev_bytes[i],
                    )
        if place == "mesh":
            per = padded // self.n_devices
            return {d: n_shards * per for d in range(self.n_devices)}
        return {int(place): n_shards * padded}

    def _device_of(self, place):
        """The jax device (or sharding) one placement stages through."""
        if place == "mesh":
            return NamedSharding(self.mesh, P(mesh_mod.SHARD_AXIS))
        if self.mesh is not None:
            return self.mesh.devices.reshape(-1)[int(place)]
        return mesh_mod.default_device()

    def put(self, vid: int, shard_id: int, data) -> None:
        host = np.frombuffer(bytes(data), dtype=np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)
        ) else np.asarray(data, dtype=np.uint8)

        def copy(out, start):
            out[:] = host[start : start + out.size]

        self._put(vid, shard_id, host.size, copy)

    def put_file(self, vid: int, shard_id: int, path: str) -> None:
        """put() of a shard file's bytes, read straight into their places
        in the staging buffer: the pin of a 1.6 GiB shard pays one host
        pass (file -> staging) where `put(np.fromfile(path))` pays the
        read into a new array and the copy out of it."""
        with open(path, "rb") as f:
            size = os.fstat(f.fileno()).st_size

            def read(out, start):
                view = memoryview(out)
                while len(view):
                    got = os.preadv(f.fileno(), [view], start)
                    if not got:
                        raise OSError(f"{path}: short read at {start}")
                    view, start = view[got:], start + got

            self._put(vid, shard_id, size, read)

    def release_staging(self) -> None:
        """Give the kept staging buffer back (the pin loop's last act)."""
        with self._stage_lock:
            self._stage_buf = None

    def _lay_out(self, padded, size: int, place, fill) -> None:
        """`size` source bytes into the padded buffer, zeros after them.
        For a lane-sharded volume in owner-major stripe order:
        NamedSharding splits the 1-D buffer into n contiguous blocks, so
        stripe c goes to position (c % n major, c // n minor) and device
        d gets exactly its interleaved stripes {d, d+n, d+2n, ...}; the
        bytes go straight to their permuted places, one host pass."""
        if place != "mesh":
            fill(padded[:size], 0)
            padded[size:] = 0
            return
        n, stripe = self.n_devices, self.stripe
        per_device = padded.size // n
        for c in range(padded.size // stripe):
            at = (c % n) * per_device + (c // n) * stripe
            take = max(0, min(stripe, size - c * stripe))
            if take:
                fill(padded[at : at + take], c * stripe)
            if take < stripe:
                padded[at + take : at + stripe] = 0

    def _put(self, vid: int, shard_id: int, size: int, fill) -> None:
        """Stage `size` bytes (`fill(out, start)` writes the source's
        bytes from `start` on into `out`) and ship them to the volume's
        placement."""
        with self._lock:
            place = self._claim_place_locked(vid, size)
        n_padded = self._padded_len(size)
        # np.empty + zeroing of what the source does not cover: np.zeros
        # would memset the WHOLE padded buffer first.  Where device_put
        # copies (a TPU) one staging buffer is kept between puts and
        # reused once the transfer has ended: a new 1.6 GiB array pays
        # the first touch of every page.  The CPU PJRT client zero-copies
        # aligned numpy arrays into jax Arrays, so there reuse would
        # alias (and corrupt) previously pinned shards and every put
        # takes a fresh buffer.  The padded buffer doubles as the
        # blockdiag segment-stacked layout: its g segments are
        # contiguous slices, staged by the host for free.
        kept = rs_tpu.on_tpu()
        with self._stage_lock if kept else contextlib.nullcontext():
            t_stage = time.perf_counter()
            if not kept:
                padded = np.empty(n_padded, dtype=np.uint8)
            else:
                if self._stage_buf is None or self._stage_buf.size < n_padded:
                    self._stage_buf = np.empty(n_padded, dtype=np.uint8)
                padded = self._stage_buf[:n_padded]
            self._lay_out(padded, size, place, fill)
            t_h2d = time.perf_counter()
            arr = self._ship(padded, place)
            # the transfer is asynchronous: wait it out, so that the
            # staging buffer is free again and the phase's seconds are
            # the transfer's
            arr.block_until_ready()
        for phase, dt in (("stage", t_h2d - t_stage),
                          ("h2d", time.perf_counter() - t_h2d)):
            stats_metrics.VOLUME_SERVER_EC_PIN_SECONDS.labels(
                volume=str(vid), phase=phase
            ).inc(dt)
        self._insert(vid, shard_id, place, arr, size)

    def _ship(self, padded, place):
        # the H2D lands directly on the owning device(s): an explicit
        # sharding/device for every put (mesh puts split host-side and
        # ship each device its stripes; whole pins ship to the claimed
        # device) — also what graftlint GL115 enforces in this scope.
        # Multiprocess mesh puts can't device_put against a global
        # sharding (most of its devices aren't addressable here):
        # each process provides exactly ITS lanes' contiguous slice of
        # the owner-major buffer via make_array_from_process_local_data
        # — the pin path's no-survivor-byte-crosses-hosts rule.
        if place == "mesh" and self.multiprocess:
            chunk = padded.size // self.n_devices
            lo = self._local_dev_indices[0] * chunk
            hi = (self._local_dev_indices[-1] + 1) * chunk
            return jax.make_array_from_process_local_data(
                self._device_of(place), padded[lo:hi], (padded.size,)
            )
        return jax.device_put(padded, self._device_of(place))

    def _insert(self, vid: int, shard_id: int, place, arr, size: int) -> None:
        """Take the shipped array into the cache's books, evicting what
        its devices' budgets ask for."""
        key = (vid, shard_id)
        shares = self._shares(place, int(arr.size))
        budget = self.device_budget
        with self._lock:
            if self._vid_place.get(vid) != place:
                # the claim this array was staged/permuted for vanished
                # (evict()/clear() between the claim read and here —
                # tiering demoting a vid whose pin thread is mid-upload
                # is a supported race): inserting would let one vid's
                # shards straddle placements, turning later reads into
                # jit device-mismatch errors instead of the documented
                # clean CacheMiss.  Drop the array; the pin loop's next
                # put re-claims fresh.
                return
            if key in self._arrays:
                self._drop_key_locked(key)
            # evict while any device the incoming array lands on would
            # exceed ITS budget: LRU order, restricted to keys that
            # actually hold bytes on an over-budget device — pressure
            # on a full device never flushes a whole-pin parked on a
            # device with headroom (mesh-sharded arrays touch every
            # device, so they stay evictable under any pressure).  ONE
            # forward pass suffices: dropping victims only shrinks the
            # over set, so a key skipped as off-pressure can never
            # match later — rescanning from the LRU head per victim
            # would cost O(victims x resident keys) under this lock.
            # Multiprocess eviction PARTITION: a pod cache's mesh-array
            # set must stay a pure function of the SPMD put sequence
            # (a lane evicted on one host deadlocks its peers' next
            # collective), so mesh puts judge pressure by mesh bytes
            # alone and evict only mesh victims, while host-local puts
            # may never touch a mesh victim (they break over budget
            # instead — tiering pressure demotion drains the rest).
            mesh_only = self.multiprocess and place == "mesh"
            skip_mesh = self.multiprocess and place != "mesh"
            pressure = self._mesh_dev_bytes if mesh_only else self._dev_bytes
            lru = iter(list(self._arrays))
            while self._arrays:
                over = {
                    d
                    for d, share in shares
                    if pressure[d] + share > budget
                }
                if not over:
                    break
                victim = next(
                    (
                        k
                        for k in lru
                        if (
                            not (mesh_only and self._foot[k][0] != "mesh")
                            and not (
                                skip_mesh and self._foot[k][0] == "mesh"
                            )
                            and any(
                                d in over
                                for d, _ in self._shares(*self._foot[k])
                            )
                        )
                    ),
                    None,
                )
                if victim is None:
                    break  # pressure is on devices nothing else holds
                self._drop_key_locked(victim)
                self.evictions += 1
                # deliberately KEEP the evicted vid's pin-source claim
                # (and placement): budget pressure can evict a volume's
                # own oldest shards while its pin thread is still
                # uploading, and dropping the claim here would leave
                # the remaining pins unclaimed (never routed resident)
                # or let a second location interleave its shard set.  A
                # stale claim is conservative: scrub/serving just see
                # too few resident shards and stay on the file path;
                # explicit evict()/clear() (unmount, destroy) release
                # the claim.
            self._arrays[key] = arr
            self._true_sizes[key] = size
            self._foot[key] = (place, int(arr.size))
            self._vid_counts[vid] = self._vid_counts.get(vid, 0) + 1
            for d, share in shares:
                self._dev_bytes[d] += share
                if place == "mesh":
                    self._mesh_dev_bytes[d] += share
            self._publish_dev_gauges()

    def _drop_key_locked(self, key: tuple[int, int]) -> None:
        """Remove one key's array + every piece of its accounting
        (caller holds the lock and owns claim/placement policy)."""
        self._arrays.pop(key)
        self._true_sizes.pop(key, None)
        place, size = self._foot.pop(key)
        for d, share in self._shares(place, size):
            self._dev_bytes[d] -= share
            if place == "mesh":
                self._mesh_dev_bytes[d] -= share
        self._vid_counts[key[0]] -= 1
        if not self._vid_counts[key[0]]:
            del self._vid_counts[key[0]]

    def resident_count(self, vid: int) -> int:
        """O(1) resident shard count for `vid` (the serving dispatcher's
        per-read routing predicate — shard_ids() would scan the whole
        key set under the lock on every read)."""
        with self._lock:
            return self._vid_counts.get(vid, 0)

    def aot_state(self, vid: int) -> str:
        """"none" | "warming" | "done" — whether warm() started/finished
        an AOT compile plan for this volume.  Anything but "none" arms
        the cold-shape shed (shed_cold): the plan's shapes are coming,
        so a read must not compile inline ahead of it."""
        with self._lock:
            return self._aot_states.get(vid, "none")

    def _set_aot_state(self, vid: int, state: str) -> None:
        with self._lock:
            if state == "none":
                # "none" == absent: pop instead of storing, so an
                # aborted plan leaves no entry behind for a never
                # re-pinned vid
                self._aot_states.pop(vid, None)
            elif state == "done" and vid not in self._aot_states:
                # the volume was evicted mid-warm (_forget_if_gone
                # dropped the entry): a straggling compile future's
                # done-callback must not resurrect it, or a later
                # re-pin starts shed-armed against a plan that never
                # covered its (possibly different) shapes
                return
            else:
                self._aot_states[vid] = state

    def _forget_if_gone(self, vid: int) -> None:
        """Drop per-vid bookkeeping once no shard of `vid` remains
        (caller holds the lock; _vid_counts already knows, no key scan)."""
        if not self._vid_counts.get(vid):
            self._vid_counts.pop(vid, None)
            self._pin_source.pop(vid, None)
            self._aot_states.pop(vid, None)  # a re-pin re-plans
            self._vid_place.pop(vid, None)  # a re-pin re-places

    def claim_pin_source(self, vid: int, source: str) -> str:
        """Atomically claim which disk location's shard files back this
        vid's resident bytes; returns the winning source (first claimant
        keeps it — two locations' pin threads racing must not interleave
        their shard sets under one key space)."""
        with self._lock:
            if vid not in self._pin_source:
                self.pin_claims += 1
            return self._pin_source.setdefault(vid, source)

    def release_pin_source(self, vid: int, source: str) -> None:
        """Release `source`'s claim if nothing of `vid` is resident: a
        pin attempt that failed before uploading anything (unreadable
        shard file, aborted thread) must not block another location's
        healthy copy until process restart.  A partially pinned claim is
        kept — those bytes are still the vid's resident identity."""
        with self._lock:
            if (
                self._pin_source.get(vid) == source
                and not self._vid_counts.get(vid)
            ):
                del self._pin_source[vid]

    def pin_source(self, vid: int) -> str | None:
        with self._lock:
            return self._pin_source.get(vid)

    def get(self, vid: int, shard_id: int):
        with self._lock:
            key = (vid, shard_id)
            arr = self._arrays.get(key)
            if arr is not None:
                self._arrays.move_to_end(key)
            return arr

    def shard_size(self, vid: int, shard_id: int) -> int | None:
        return self._true_sizes.get((vid, shard_id))

    def stats(self) -> tuple[int, int]:
        """(resident shard count, padded device bytes held)."""
        with self._lock:
            return len(self._arrays), self.bytes_used

    def resident_by_vid(self) -> dict[int, list[int]]:
        """One locked snapshot of vid -> sorted resident shard ids (status
        pages render many volumes; per-vid shard_ids() calls would scan
        the key set once per volume under the serving path's lock)."""
        out: dict[int, list[int]] = {}
        with self._lock:
            for v, s in self._arrays:
                out.setdefault(v, []).append(s)
        for ids in out.values():
            ids.sort()
        return out

    def shard_ids(self, vid: int) -> list[int]:
        with self._lock:
            return sorted(s for (v, s) in self._arrays if v == vid)

    def evict(self, vid: int, shard_id: int | None = None) -> None:
        with self._lock:
            keys = [
                k
                for k in self._arrays
                if k[0] == vid and (shard_id is None or k[1] == shard_id)
            ]
            for k in keys:
                self._drop_key_locked(k)
            if keys:
                self._publish_dev_gauges()
            if shard_id is None or keys:
                # a whole-vid evict (unmount/destroy) always releases
                # the claim — even when budget pressure already removed
                # the shards, the claim must not outlive the volume.  A
                # PARTIAL evict that matched nothing must not drop a
                # mid-pin claim (the pin thread claimed before its first
                # put) and open the two-location interleave window.
                self._forget_if_gone(vid)

    def clear(self) -> None:
        with self._lock:
            self._arrays.clear()
            self._true_sizes.clear()
            self._pin_source.clear()
            self._vid_counts.clear()
            self._aot_states.clear()
            self._vid_place.clear()
            self._foot.clear()
            self._dev_bytes = [0] * self.n_devices
            self._publish_dev_gauges()


@functools.lru_cache(maxsize=64)
def _prepared_matrix(matrix_bytes: bytes, m: int, k: int):
    return rs_tpu.prepare_matrix(
        np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k)
    )


# block-diagonal prepared matrices share rs_tpu's cache (the bulk
# encoder prepares the same parity system — one cached device copy)
_prepared_blockdiag_matrix = rs_tpu._prepared_blockdiag


# --- fused gather+reconstruct kernel ----------------------------------------
#
# The round-3 serving path ran FOUR chained XLA ops per call (vmap
# dynamic_slice gather -> stack/reshape -> pallas matmul -> take_along_axis
# -> vmap slice): every stage round-trips HBM and the chain costs several
# dispatches of fixed overhead per 4KB needle.  The fused kernel does the
# whole thing in ONE pallas program: per grid step it DMAs each survivor's
# slice HBM->VMEM at a scalar-prefetched offset, unpacks to GF(2) bit
# planes, runs the MXU dot, packs, and row-selects the wanted shard — no
# gathered intermediate ever touches HBM.  The sub-lane `delta` trim
# happens on host after D2H (<=127 bytes per needle of extra wire).
#
# Mosaic layout constraints (probed on v5e, experiments/r4_fused_probe.py +
# the memref_slice divisibility errors that followed):
#   * output/VMEM blocks need their second-minor dim divisible by 8 (or
#     equal to the array dim) — so each grid step serves a GROUP of 8
#     requests, output block (8, tile);
#   * DMA slice starts must be PROVABLY divisible by the memref tiling
#     (1024 for 1-D u8) — offsets travel in FUSED_ALIGN units and multiply
#     in-kernel, and every destination offset is a static multiple of tile;
#   * single-row slices of 2-D VMEM scratch are rejected (sublane tile 8),
#     and 1-D->2-D reshapes relayout — so the gather lands in a FLAT 1-D
#     HBM buffer laid out so a free XLA reshape yields [chunks, G, k, W],
#     which a second, regular-BlockSpec kernel consumes (block (1,1,k,W):
#     leading dims are unconstrained, trailing dims equal the array's);
#   * jax.lax.dynamic_slice has no Mosaic lowering — the per-request row
#     select is an iota-mask reduction.
# Both pallas calls live in ONE jit: a single host dispatch, and the only
# intermediate (the gathered slices) never rides the host link.

FUSED_GROUP = 8  # requests per grid step (output sublane tile)
FUSED_TILE = 4096  # per-request lane chunk; x8 group = 32768-lane compute
                   # width (bits 4MB + counts 4MB int32 in VMEM)


def _make_gather_body(k: int, g_n: int, tile: int, n_groups: int):
    w = g_n * tile

    def body(offs_ref, *rest):
        surv = rest[:k]
        o_ref = rest[k]
        sems = rest[k + 1]
        g = pl.program_id(0)
        j = pl.program_id(1)
        # the grid-step terms are traced ONCE and every copy adds a
        # Python-int remainder: tracing this body is most of what a warm
        # shape costs once the compile itself comes from the cache
        g0 = g * g_n
        j_off = j * tile
        dst0 = (j * n_groups + g) * (k * w)
        copies = []
        for r in range(g_n):
            # unpack the offset units from the packed meta word; the
            # explicit multiply is what lets Mosaic PROVE alignment
            src = (
                (offs_ref[g0 + r] >> META_ROW_BITS) * FUSED_ALIGN + j_off
            )
            for i in range(k):
                dst = dst0 + (i * w + r * tile)
                copies.append(
                    pltpu.make_async_copy(
                        surv[i].at[pl.ds(src, tile)],
                        o_ref.at[pl.ds(dst, tile)],
                        sems.at[i, r],
                    )
                )
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    return body


def _make_select_body(k: int, k_pad: int, m_pad: int, g_n: int, tile: int):
    w = g_n * tile

    def body(rows_ref, a_ref, x_ref, o_ref):
        g = pl.program_id(0)
        xv = x_ref[0, 0]  # (k, w); leading unit dims index away for free
        if k < k_pad:
            xv = jnp.concatenate(
                [xv, jnp.zeros((k_pad - k, w), jnp.uint8)], axis=0
            )
        bits = rs_tpu._unpack_bits_bitmajor(xv)
        counts = jnp.dot(a_ref[:], bits, preferred_element_type=jnp.int32)
        packed = rs_tpu._pack_bits_bitmajor(counts, m_pad)  # (m_pad, w)
        ridx = jax.lax.broadcasted_iota(jnp.int32, (m_pad, tile), 0)
        outs = []
        for r in range(g_n):
            row = rows_ref[g * g_n + r] & _META_ROW_MASK
            blk = packed[:, r * tile : (r + 1) * tile]
            sel = jnp.where(ridx == row, blk, jnp.uint8(0)).astype(jnp.int32)
            outs.append(jnp.sum(sel, axis=0, keepdims=True).astype(jnp.uint8))
        o_ref[:] = jnp.concatenate(outs, axis=0)

    return body


@functools.partial(
    jax.jit,
    static_argnames=("tile", "fetch", "k_true", "interpret"),
    donate_argnums=(2,),
)
def _fused_reconstruct(
    a_bm, survivors, meta, *, tile, fetch, k_true, interpret
):
    """survivors: tuple of [L] u8 resident shards (HBM) in matrix column
    order; meta [N] int32 — each word packs (offset in FUSED_ALIGN
    units) << META_ROW_BITS | wanted matrix row, so the call ships ONE
    scalar vector of half the r09 width.  The meta buffer is DONATED
    (staging dies with the call).  -> [N, fetch] u8 of raw reconstructed
    bytes starting at each aligned offset (caller trims the delta head).
    N pads to the 8-request group internally.  Returns the [N, fetch]
    result FLATTENED (1-D, true-N rows only); callers reshape
    host-side."""
    k = len(survivors)
    if k_true is not None and k != k_true:
        raise ValueError(f"{k} survivors but matrix was built for {k_true}")
    m_pad8, k_pad8 = a_bm.shape
    m_pad, k_pad = m_pad8 // 8, k_pad8 // 8
    n = meta.shape[0]
    pad = (-n) % FUSED_GROUP
    if pad:
        meta = jnp.pad(meta, (0, pad))
    # both pallas bodies consume the same packed word: the gather
    # shifts the offset units out, the select masks the row bits
    offsets = row_idx = meta
    n_pad = n + pad
    tile = min(tile, fetch)
    chunks = max(1, fetch // tile)
    n_groups = n_pad // FUSED_GROUP
    w = FUSED_GROUP * tile

    gathered = pl.pallas_call(
        _make_gather_body(k, FUSED_GROUP, tile, n_groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_groups, chunks),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * k,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((k, FUSED_GROUP))],
        ),
        out_shape=jax.ShapeDtypeStruct((chunks * n_groups * k * w,), jnp.uint8),
        cost_estimate=pl.CostEstimate(
            flops=0,
            bytes_accessed=2 * chunks * n_groups * k * w,
            transcendentals=0,
        ),
        interpret=interpret,
    )(offsets, *survivors)
    x4 = gathered.reshape(chunks, n_groups, k, w)  # contiguous: free

    out = pl.pallas_call(
        _make_select_body(k, k_pad, m_pad, FUSED_GROUP, tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_groups, chunks),
            in_specs=[
                pl.BlockSpec(
                    a_bm.shape, lambda *_: (0, 0), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec(
                    (1, 1, k, w),
                    lambda gi, ji, *_: (ji, gi, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (FUSED_GROUP, tile),
                lambda gi, ji, *_: (gi, ji),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, fetch), jnp.uint8),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad8 * k_pad8 * n_pad * fetch,
            bytes_accessed=(k + 1) * n_pad * fetch,
            transcendentals=0,
        ),
        interpret=interpret,
    )(row_idx, a_bm, x4)
    return (out[:n] if pad else out).reshape(-1)


# --- block-diagonal variants -------------------------------------------------
#
# Same fused two-kernel structure, but the reconstruction system is the
# block-diagonal [g*w, g*k] expansion (rs_tpu.blockdiag_system): each
# request's tile splits into g contiguous segments, group jg's input
# rows are the survivors' slices of segment jg, and group jg's output
# row is the wanted shard's bytes of that segment — concatenating the
# groups along lanes reassembles the contiguous tile.  The fatter
# contraction (8*pad16(g*k) = 384 vs 128 bits for k=10, g=4) is what
# lifts the MXU roof from ~121 to ~157 GB/s (rs_tpu.py round 3/4).
# Mosaic constraints inherited from the flat kernel: every DMA slice
# start must stay provably FUSED_ALIGN-divisible, so per-chunk segments
# are tile/groups wide and the blockdiag fetch ladder rounds up to a
# multiple of groups*FUSED_ALIGN (a coarser ladder — the caller pays at
# most one extra 4KB step of D2H per request, against a ~30% MXU win).


def _make_gather_body_blockdiag(k, groups, g_n, tile, n_groups):
    seg = tile // groups
    w = g_n * seg
    gk = groups * k

    def body(offs_ref, *rest):
        surv = rest[:k]
        o_ref = rest[k]
        sems = rest[k + 1]
        g = pl.program_id(0)
        j = pl.program_id(1)
        # grid-step terms traced once, Python-int remainders per copy
        # (see _make_gather_body)
        g0 = g * g_n
        j_off = j * tile
        dst0 = (j * n_groups + g) * (gk * w)
        copies = []
        for r in range(g_n):
            base = (
                (offs_ref[g0 + r] >> META_ROW_BITS) * FUSED_ALIGN + j_off
            )
            for jg in range(groups):
                # seg is a multiple of FUSED_ALIGN (caller-enforced), so
                # base + jg*seg keeps the alignment proof intact
                src = base + jg * seg
                for i in range(k):
                    dst = dst0 + ((jg * k + i) * w + r * seg)
                    copies.append(
                        pltpu.make_async_copy(
                            surv[i].at[pl.ds(src, seg)],
                            o_ref.at[pl.ds(dst, seg)],
                            sems.at[i, jg * g_n + r],
                        )
                    )
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    return body


def _make_select_body_blockdiag(k, groups, w_true, k_pad, m_pad, g_n, tile):
    seg = tile // groups
    w = g_n * seg
    gk = groups * k

    def body(rows_ref, a_ref, x_ref, o_ref):
        g = pl.program_id(0)
        xv = x_ref[0, 0]  # (g*k, w)
        if gk < k_pad:
            xv = jnp.concatenate(
                [xv, jnp.zeros((k_pad - gk, w), jnp.uint8)], axis=0
            )
        bits = rs_tpu._unpack_bits_bitmajor(xv)
        counts = jnp.dot(a_ref[:], bits, preferred_element_type=jnp.int32)
        packed = rs_tpu._pack_bits_bitmajor(counts, m_pad)  # (m_pad, w)
        ridx = jax.lax.broadcasted_iota(jnp.int32, (m_pad, seg), 0)
        outs = []
        for r in range(g_n):
            row = rows_ref[g * g_n + r] & _META_ROW_MASK
            blk = packed[:, r * seg : (r + 1) * seg]  # (m_pad, seg)
            segs = []
            for jg in range(groups):
                # group jg's wanted row sits at jg*w_true + row in the
                # block-diagonal system; its seg lanes are the request's
                # bytes [jg*seg, (jg+1)*seg) of this chunk's tile
                sel = jnp.where(
                    ridx == jg * w_true + row, blk, jnp.uint8(0)
                ).astype(jnp.int32)
                segs.append(
                    jnp.sum(sel, axis=0, keepdims=True).astype(jnp.uint8)
                )
            outs.append(jnp.concatenate(segs, axis=1))  # (1, tile)
        o_ref[:] = jnp.concatenate(outs, axis=0)

    return body


@functools.partial(
    jax.jit,
    static_argnames=("tile", "fetch", "k_true", "w_true", "groups", "interpret"),
    donate_argnums=(2,),
)
def _fused_reconstruct_blockdiag(
    a_blk, survivors, meta, *, tile, fetch, k_true, w_true, groups, interpret
):
    """Block-diagonal twin of _fused_reconstruct: same packed-[N]-meta
    (donated) and flat 1-D output contract; `w_true` is the
    reconstruction system's pre-expansion row count (len(wanted)) so the
    per-group row select can address jg*w_true + row.  Caller guarantees
    tile % (groups * FUSED_ALIGN) == 0 and fetch % tile == 0."""
    k = len(survivors)
    if k_true is not None and k != k_true:
        raise ValueError(f"{k} survivors but matrix was built for {k_true}")
    m_pad8, k_pad8 = a_blk.shape
    m_pad, k_pad = m_pad8 // 8, k_pad8 // 8
    n = meta.shape[0]
    pad = (-n) % FUSED_GROUP
    if pad:
        meta = jnp.pad(meta, (0, pad))
    offsets = row_idx = meta
    n_pad = n + pad
    chunks = fetch // tile
    n_groups = n_pad // FUSED_GROUP
    seg = tile // groups
    w = FUSED_GROUP * seg
    gk = groups * k

    gathered = pl.pallas_call(
        _make_gather_body_blockdiag(k, groups, FUSED_GROUP, tile, n_groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_groups, chunks),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * k,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((k, groups * FUSED_GROUP))
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (chunks * n_groups * gk * w,), jnp.uint8
        ),
        cost_estimate=pl.CostEstimate(
            flops=0,
            bytes_accessed=2 * chunks * n_groups * gk * w,
            transcendentals=0,
        ),
        interpret=interpret,
    )(offsets, *survivors)
    x4 = gathered.reshape(chunks, n_groups, gk, w)  # contiguous: free

    out = pl.pallas_call(
        _make_select_body_blockdiag(
            k, groups, w_true, k_pad, m_pad, FUSED_GROUP, tile
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_groups, chunks),
            in_specs=[
                pl.BlockSpec(
                    a_blk.shape, lambda *_: (0, 0), memory_space=pltpu.VMEM
                ),
                pl.BlockSpec(
                    (1, 1, gk, w),
                    lambda gi, ji, *_: (ji, gi, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (FUSED_GROUP, tile),
                lambda gi, ji, *_: (gi, ji),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, fetch), jnp.uint8),
        cost_estimate=pl.CostEstimate(
            flops=2 * m_pad8 * k_pad8 * n_pad * (fetch // groups),
            bytes_accessed=(k + 1) * n_pad * fetch,
            transcendentals=0,
        ),
        interpret=interpret,
    )(row_idx, a_blk, x4)
    return (out[:n] if pad else out).reshape(-1)


@functools.partial(
    jax.jit,
    static_argnames=("tile", "fetch", "kernel", "interpret", "k_true"),
    donate_argnums=(2,),
)
def _gather_reconstruct(
    a_bm,
    survivors,
    vecs,
    *,
    tile,
    fetch,
    kernel,
    interpret,
    k_true,
):
    """survivors: tuple of [L] u8 resident shards in matrix column order;
    vecs [3, N] int32 (donated) — row 0 the lane-aligned offsets, row 1
    each request's wanted matrix row, row 2 the sub-lane alignment
    residual.  One array = ONE device_put and one dispatch RTT where the
    r09 path paid three.  -> [N, fetch] u8.

    `tile` is the compute width (size bucket); `fetch` <= tile is the D2H
    width (power-of-two cover of the largest actual request): the result
    is delta-shifted and narrowed ON DEVICE so the transfer back
    carries only useful bytes.  Returns the [N, fetch] result FLATTENED
    (1-D); callers reshape host-side."""
    offsets, row_idx, deltas = vecs[0], vecs[1], vecs[2]
    cols = [
        jax.vmap(
            lambda off, arr=arr: jax.lax.dynamic_slice(arr, (off,), (tile,))
        )(offsets)
        for arr in survivors
    ]  # k x [N, tile]
    x = jnp.stack(cols, axis=0)  # [k, N, tile]
    k, n, _ = x.shape
    out = rs_tpu.apply_matrix_device(
        a_bm,
        x.reshape(k, n * tile),
        kernel=kernel,
        interpret=interpret,
        k_true=k_true,
    )  # [m_pad, n*tile]
    out3 = out.reshape(out.shape[0], n, tile).transpose(1, 0, 2)
    sel = jnp.take_along_axis(out3, row_idx[:, None, None], axis=1)[:, 0, :]
    if fetch < tile:
        sel = jax.vmap(
            lambda row, d: jax.lax.dynamic_slice(row, (d,), (fetch,))
        )(sel, deltas)
    return sel.reshape(-1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "tile", "fetch", "groups", "w_true", "kernel", "interpret", "k_true",
    ),
    donate_argnums=(2,),
)
def _gather_reconstruct_blockdiag(
    a_blk,
    survivors,
    vecs,
    *,
    tile,
    fetch,
    groups,
    w_true,
    kernel,
    interpret,
    k_true,
):
    """Block-diagonal twin of _gather_reconstruct (the XLA fallback,
    what the CPU mesh runs), same single donated [3, N] vecs contract: each
    request's tile splits into `groups` contiguous segments gathered
    into segment-stacked [g*k, N*seg] rows, one apply of the
    block-diagonal matrix reconstructs every segment, and the per-group
    wanted rows (jg*w_true + row) concatenate back into the contiguous
    [N, tile] before the same on-device delta/narrow."""
    offsets, row_idx, deltas = vecs[0], vecs[1], vecs[2]
    seg = tile // groups
    cols = []
    for jg in range(groups):
        for arr in survivors:
            cols.append(
                jax.vmap(
                    lambda off, arr=arr, jg=jg: jax.lax.dynamic_slice(
                        arr, (off + jg * seg,), (seg,)
                    )
                )(offsets)
            )
    x = jnp.stack(cols, axis=0)  # [g*k, N, seg]
    gk, n, _ = x.shape
    out = rs_tpu.apply_matrix_device(
        a_blk,
        x.reshape(gk, n * seg),
        kernel=kernel,
        interpret=interpret,
        k_true=None if k_true is None else groups * k_true,
    )  # [m_pad >= groups*w_true, n*seg]
    out3 = out.reshape(out.shape[0], n, seg).transpose(1, 0, 2)
    segs = []
    for jg in range(groups):
        rows = row_idx + jg * w_true
        segs.append(
            jnp.take_along_axis(out3, rows[:, None, None], axis=1)[:, 0, :]
        )
    sel = jnp.concatenate(segs, axis=-1)  # [N, tile], contiguous bytes
    if fetch < tile:
        sel = jax.vmap(
            lambda row, d: jax.lax.dynamic_slice(row, (d,), (fetch,))
        )(sel, deltas)
    return sel.reshape(-1)


# --- mesh-sharded twins ------------------------------------------------------
#
# With the cache's mesh layout (r19), a volume's shard buffers are
# lane-sharded in INTERLEAVED STRIPES: stripe c (cache.stripe bytes) of
# every shard lives on device c % n — the host permutes each padded
# buffer owner-major at put time, so NamedSharding(mesh, P("shard"))'s
# contiguous split hands device d exactly its stripes.  Interleaving
# keeps ownership even at any volume size (a contiguous
# chunk-per-device split parks all of a small volume's data — and any
# zipf-hot byte range — on the first chunks' owners, and the uniform
# per-device count padding then multiplies compute).  The planner
# routes each sub-request to the device owning its gather window
# (splitting requests that straddle a stripe boundary, backward-
# aligning windows that would overhang one), so the whole batch
# reconstructs in ONE shard_map program across the mesh: each device
# gathers its own requests' survivor slices locally, runs the (flat or
# block-diagonal) GF(2) matmul over its ~1/n of the batch, and
# row-selects its wanted bytes — lane work genuinely parallelizes
# across devices instead of queueing on one chip, and no survivor byte
# ever crosses the interconnect (only the per-device request vectors
# go up and the reconstructed rows come down).  The staged vec is
# [n_dev, 2, N] int32 (per-device LOCAL offsets + wanted rows),
# sharded P("shard") so each device receives exactly its own requests
# — the sharding-aware H2D.  Host trims the alignment delta after D2H
# (the fused kernels' contract), so the kernel never shifts.


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "tile", "groups", "w_true", "kernel", "interpret", "k_true",
        "replicate_out",
    ),
    donate_argnums=(2,),
)
def _sharded_gather_reconstruct(
    a_prep, survivors, vecs, *, mesh, tile, groups, w_true, kernel,
    interpret, k_true, replicate_out=False,
):
    """survivors: tuple of [L_pad] u8 shards sharded P("shard") over
    `mesh`; vecs [n_dev, 2, N] int32 (donated), sharded P("shard") —
    row 0 each request's CHUNK-LOCAL aligned offset, row 1 its wanted
    matrix row.  `tile` is both the gather width and the D2H width
    (the planner sizes it to cover every request's delta+take, so the
    host-side delta trim needs no wider window).  groups > 1 applies
    the block-diagonal system exactly like _gather_reconstruct_blockdiag
    (g contiguous segments per window, per-group row select at
    jg*w_true + row).  -> [n_dev, N, tile] u8 sharded P("shard").

    `replicate_out` (the multi-controller mode): each lane all-gathers
    the RESULT rows over the shard axis so the output is fully
    replicated — same [n_dev, N, tile] global shape, but every process
    can np.asarray it locally.  Only the small result vecs cross the
    host boundary; survivor bytes never do (each lane still gathers
    exclusively from its own resident chunk)."""
    k = len(survivors)
    if k_true is not None and k != k_true:
        raise ValueError(f"{k} survivors but matrix was built for {k_true}")
    seg = tile // groups

    def kern(vecs_l, a_l, *surv_l):
        offsets, rows = vecs_l[0, 0], vecs_l[0, 1]
        n = offsets.shape[0]
        cols = []
        for jg in range(groups):
            for arr in surv_l:
                cols.append(
                    jax.vmap(
                        lambda o, arr=arr, jg=jg: jax.lax.dynamic_slice(
                            arr, (o + jg * seg,), (seg,)
                        )
                    )(offsets)
                )
        x = jnp.stack(cols, axis=0)  # [g*k, N, seg]
        gk = x.shape[0]
        out = rs_tpu.apply_matrix_device(
            a_l,
            x.reshape(gk, n * seg),
            kernel=kernel,
            interpret=interpret,
            k_true=None if k_true is None else groups * k_true,
        )  # [m_pad, N*seg]
        out3 = out.reshape(out.shape[0], n, seg).transpose(1, 0, 2)
        segs = []
        for jg in range(groups):
            want = rows + jg * w_true if groups > 1 else rows
            segs.append(
                jnp.take_along_axis(out3, want[:, None, None], axis=1)[
                    :, 0, :
                ]
            )
        sel = segs[0] if groups == 1 else jnp.concatenate(segs, axis=-1)
        if replicate_out:
            # [n_dev, N, tile] on EVERY lane: result rows (not survivor
            # bytes) cross the ICI/DCN once, so each host can read the
            # whole batch's answers without a second collective
            return jax.lax.all_gather(sel, mesh_mod.SHARD_AXIS)
        return sel[None]  # [1, N, tile]: this device's chunk of the out

    return _shard_map(
        kern,
        mesh=mesh,
        in_specs=(
            P(mesh_mod.SHARD_AXIS, None, None),
            P(None, None),
            *([P(mesh_mod.SHARD_AXIS)] * k),
        ),
        out_specs=(
            P(None, None, None)
            if replicate_out
            else P(mesh_mod.SHARD_AXIS, None, None)
        ),
        # the all_gather above really does replicate the output, but
        # shard_map's static varying-axes checker types all_gather's
        # result as still varying over the shard axis — disable the
        # check only for the replicated (multi-controller) variant
        check_vma=not replicate_out,
    )(vecs, a_prep, *survivors)


def _plan(requests: list[tuple[int, int, int]], l_loc: int = 0):
    """Split/align requests into device sub-requests.

    Each request (wanted_shard, offset, size) becomes >=1 sub-requests
    (req_index, aligned_off, delta, take, bucket) with delta+take <= bucket.

    `l_loc` > 0 is the mesh layout's stripe width: every sub-request's
    whole bucket window [aligned, aligned+bucket) must then sit inside
    ONE stripe (each stripe lives whole on its owner device), so
    requests additionally split at stripe boundaries and a window that
    would overhang its boundary is backward-aligned to END there
    instead (the delta grows up to bucket - take; the host trims it
    after D2H like any other delta).  Stripe starts are LANE-aligned by
    construction (the stripe is a multiple of FUSED_ALIGN), so
    backward-aligned offsets stay lane-aligned.
    """
    cap = SIZE_BUCKETS[-1]
    if l_loc:
        cap = max(v for v in SIZE_BUCKETS if v <= l_loc)
    subs = []
    for idx, (_, off, size) in enumerate(requests):
        pos = off
        remaining = size
        while remaining > 0:
            take = min(remaining, CHUNK)
            if l_loc:
                boundary = (pos // l_loc + 1) * l_loc
                take = min(take, boundary - pos)
                aligned = pos - (pos % LANE)
                delta = pos - aligned
                if delta + take > cap:
                    take = cap - delta
                bucket = _bucket(SIZE_BUCKETS, delta + take)
                if aligned + bucket > boundary:
                    # overhang: end the window AT the boundary (bucket
                    # <= cap <= l_loc keeps it inside the chunk); the
                    # residual pos - aligned joins the trimmed delta
                    aligned = boundary - bucket
                    delta = pos - aligned
            else:
                aligned = pos - (pos % LANE)
                delta = pos - aligned
                bucket = _bucket(SIZE_BUCKETS, delta + take)
            subs.append((idx, aligned, delta, take, bucket))
            pos += take
            remaining -= take
    return subs


@functools.lru_cache(maxsize=64)
def _prepared_matrix_placed(
    matrix_bytes, m, k, groups, mesh, place, multiprocess=False
):
    """Prepared (flat or blockdiag) matrix staged where the placement's
    kernels need it: replicated over the mesh for lane-sharded volumes,
    committed to the owning device for whole-pins — jit refuses to mix
    committed inputs across device sets, so the matrix must follow the
    survivors.  Cached per (system, placement) like _prepared_matrix.
    A multi-controller mesh can't device_put against the replicated
    sharding (non-addressable devices): every process holds the same
    matrix bytes, so each provides its full copy as the process-local
    data of the replicated global array."""
    if groups > 1:
        base = _prepared_blockdiag_matrix(matrix_bytes, m, k, groups)
    else:
        base = _prepared_matrix(matrix_bytes, m, k)
    if place == "mesh":
        sharding = NamedSharding(mesh, P(None, None))
        if multiprocess:
            return jax.make_array_from_process_local_data(
                # graftlint: allow(device-sync): `base` is host numpy —
                # asarray is a no-copy view, not a device sync
                sharding, np.asarray(base), base.shape
            )
        return jax.device_put(base, sharding)
    return jax.device_put(base, mesh.devices.reshape(-1)[int(place)])


def _resolve_codec(cache, vid, requests, data_shards, total_shards, layout):
    """Shared preamble: reconstruction matrix (flat or block-diagonal,
    per the active layout, staged on the vid's placement) + resident
    survivor tuple + the system's pre-expansion row count + the vid's
    placement ("mesh" | device index | 0 for the legacy default)."""
    resident = cache.shard_ids(vid)
    wanted = _batch_wanted(requests, resident, data_shards)
    present = [s for s in resident if s not in wanted]
    if len(present) < data_shards:
        raise CacheMiss(
            f"vid {vid}: {len(present)} resident survivors, need {data_shards}"
        )
    rmat, use = gf256.reconstruction_matrix(
        data_shards, total_shards, present, wanted
    )
    place = cache.placement(vid)
    if place is None:
        place = 0
    groups = cache.groups if layout == "blockdiag" else 1
    if cache.mesh is not None:
        a_prep = _prepared_matrix_placed(
            rmat.tobytes(), *rmat.shape, groups, cache.mesh, place,
            cache.multiprocess,
        )
    elif layout == "blockdiag":
        a_prep = _prepared_blockdiag_matrix(
            rmat.tobytes(), *rmat.shape, cache.groups
        )
    else:
        a_prep = _prepared_matrix(rmat.tobytes(), *rmat.shape)
    survivors = tuple(cache.get(vid, s) for s in use)
    if any(s is None for s in survivors):  # evicted between listing and get
        raise CacheMiss(f"vid {vid}: survivor shard evicted mid-request")
    if place == "mesh" and len({int(s.size) for s in survivors}) != 1:
        # the sharded planner derives ONE per-device chunk length for
        # the whole batch; mixed padded lengths cannot serve sharded
        raise CacheMiss(f"vid {vid}: sharded survivors differ in size")
    row_of = {sid: i for i, sid in enumerate(wanted)}
    return a_prep, survivors, row_of, use, rmat.shape[0], place


def _group_vectors(part, requests, row_of):
    """HOST-side offset/row/delta COLUMNS (plain lists): numpy staging
    happens at dispatch time — into the held slot's arena on TPU, a
    fresh array elsewhere — so packing allocates nothing per batch and
    the H2D transfer lands under the pipeline's h2d_copy stage."""
    offsets = [s[1] for _, s in part]
    rows = [row_of[requests[s[0]][0]] for _, s in part]
    deltas = [s[2] for _, s in part]
    return offsets, rows, deltas


def _fetch_cover(span: int) -> int:
    """Smallest of {2^n, 3*2^(n-1)} covering span (min 2048).  A pure
    power-of-two ladder wastes ~2x D2H whenever the alignment delta pushes
    a power-of-two-sized request just past the boundary (the common case:
    any unaligned 1MB needle); the 1.5x steps cap the waste at ~50% while
    adding at most one compiled shape per size class."""
    p = max(1 << (span - 1).bit_length(), 2048)
    three_halves = 3 * (p >> 2)
    return three_halves if three_halves >= max(span, 2048) else p


def _sharded_fetch_rungs(fetch: int) -> list[int]:
    """Every fetch a live sharded sub-request in `fetch`'s size bucket
    can produce.  A sharded call's fetch is min(bucket, _fetch_cover(span))
    with span anywhere in (0, bucket]: _plan's stripe-boundary backward
    alignment grows delta up to bucket - take, so the reachable set is
    the whole {2^n, 3*2^(n-1)} cover ladder from 2048 up to the bucket —
    not just the aligned / off-by-one spans warm's probes enumerate.
    The smaller rungs double as cover for the boundary-SPLIT halves of a
    probe-sized read, whose takes land in buckets no probe size maps to."""
    bucket = _bucket(SIZE_BUCKETS, fetch)
    rungs, f = [], 2048
    while f <= bucket:
        rungs.append(f)
        if f + (f >> 1) <= bucket:
            rungs.append(f + (f >> 1))
        f <<= 1
    return rungs


def _fused_fetch_rungs(bucket: int) -> list[int]:
    """Every fetch a live fused sub-request of size bucket `bucket` can
    produce.  Its lane-aligned delta+take lies in (previous bucket,
    bucket]; re-aligning down to FUSED_ALIGN adds up to FUSED_ALIGN -
    LANE more, and the call's fetch is _fetch_cover of the LARGEST such
    span in its group — so the reachable set is the cover ladder from
    just above the previous bucket to the rung covering bucket +
    FUSED_ALIGN - LANE, not only the aligned / off-by-one spans warm's
    probes enumerate."""
    i = SIZE_BUCKETS.index(bucket)
    lo = _fetch_cover(SIZE_BUCKETS[i - 1] + 1) if i else 2048
    hi = min(MAX_TILE, _fetch_cover(bucket + FUSED_ALIGN - LANE))
    rungs, f = [], 2048
    while f <= hi:
        for rung in (f, f + (f >> 1)):
            if lo <= rung <= hi:
                rungs.append(rung)
        f <<= 1
    return rungs


def _fused_tile_for(fetch: int) -> int:
    """Largest per-chunk tile <= FUSED_TILE dividing fetch (fetch is
    2^n or 3*2^(n-1), so halving always lands on a divisor >= 1024)."""
    t = FUSED_TILE
    while fetch % t:
        t //= 2
    return t


def _fused_fetch_tile(fetch: int, groups: int) -> tuple[int, int]:
    """(fetch, tile) a fused call of cover-ladder width `fetch` runs
    with: the blockdiag kernel rounds fetch to its segment quantum, the
    flat kernel keeps it and picks the widest dividing tile."""
    if groups > 1:
        return _blockdiag_fetch_tile(fetch, groups)
    return fetch, _fused_tile_for(fetch)


def _fused_vectors(part, requests, row_of):
    """Re-align each sub-request down to FUSED_ALIGN: offsets become unit
    counts, the residual joins the host-trimmed delta.  -> (packed,
    deltas, fetch): `packed` is the [N] list of single int32 meta words
    ((units << META_ROW_BITS) | row — HALF the r09 [2, N] wire width,
    still one H2D transfer) and fetch covers the largest delta+take
    (CHUNK keeps it <= MAX_TILE).  Stays host-side lists here — numpy
    staging (arena or fresh) and the ship happen under h2d_copy."""
    packed, deltas = [], []
    for _, s in part:
        extra = s[1] % FUSED_ALIGN
        units = (s[1] - extra) // FUSED_ALIGN
        if units >= 1 << (31 - META_ROW_BITS):  # 64GB shard: unreachable
            raise ValueError(f"offset {s[1]} exceeds packed meta range")
        packed.append(
            (units << META_ROW_BITS) | row_of[requests[s[0]][0]]
        )
        deltas.append(s[2] + extra)
    span = max(d + s[3] for d, (_, s) in zip(deltas, part))
    fetch = _fetch_cover(span)
    return packed, deltas, fetch


def _kernel_mode(
    kernel: str | None = None, interpret: bool | None = None
) -> tuple[str, bool]:
    """(kernel, interpret) with each None resolved to what the device
    allows: the Pallas kernels compiled on a TPU, the xla kernel (and
    interpret mode for any Pallas call) everywhere else.  The one home
    of that choice — device_status reports exactly this."""
    on_tpu = rs_tpu.on_tpu()
    if kernel is None:
        kernel = "pallas" if on_tpu else "xla"
    if interpret is None:
        interpret = not on_tpu
    return kernel, interpret


def _use_fused(kernel: str, interpret: bool) -> bool:
    """The fused DMA kernel is the serving path on real TPUs; interpret
    mode also supports it (tests), but the XLA fallback kernel cannot."""
    return kernel == "pallas"


# shapes this process has already dispatched: first use of a shape is a
# jit compile — the trace
# annotation + compile counter are what let a tail spike be attributed
# to "hit an unwarmed shape" instead of guessed at
_dispatched_shapes: set = set()
_shapes_lock = threading.Lock()


# (size_bucket, count_bucket) -> dispatch count, recorded per device
# call: warm() compiles the observed buckets FIRST, so a re-pin (budget
# churn, volume move) reaches serving-readiness for the live workload's
# shapes before burning compiles on ladder corners nobody hits
_observed_buckets: dict[tuple[int, int], int] = {}


def _note_observed(size_bucket: int, count_bucket: int) -> None:
    global _observed_dirty
    with _shapes_lock:
        key = (size_bucket, count_bucket)
        _observed_buckets[key] = _observed_buckets.get(key, 0) + 1
        _observed_dirty = True


def observed_buckets() -> list[tuple[int, int]]:
    """(size_bucket, count_bucket) pairs this process has dispatched,
    most-frequent first — warm()'s compile-priority order."""
    with _shapes_lock:
        items = sorted(_observed_buckets.items(), key=lambda kv: -kv[1])
    return [k for k, _ in items]


# per-_call_key dispatch accounting for the live "what shape is hot
# right now" view (/debug/device/hot, volume.device.status -hot): the
# observed-bucket ranking above orders COMPILES, this answers the
# operator's runtime question — which compiled shape the device is
# actually spending its time in, and how long one dispatch of it takes.
# key -> [dispatch count, latency EWMA seconds, last dispatch unix]
_call_stats: dict[tuple, list] = {}
# EWMA weight: ~last 10 dispatches of the shape, same horizon as the
# QoS deadline estimator
_CALL_EWMA_ALPHA = 0.2


def _note_call_latency(key: tuple, seconds: float) -> None:
    """Record one device call's dispatch->fetch-complete wall seconds.
    Measured across the async pipeline (overlapped calls include their
    queue time behind siblings), so it is an OBSERVED service latency,
    not a pure kernel time — exactly what a tail investigation wants."""
    now = time.time()
    with _shapes_lock:
        rec = _call_stats.get(key)
        if rec is None:
            _call_stats[key] = [1, seconds, now]
            return
        rec[0] += 1
        rec[1] += _CALL_EWMA_ALPHA * (seconds - rec[1])
        rec[2] = now


def hot_shapes(limit: int = 10) -> list[dict]:
    """The hottest compiled call shapes, most-dispatched first:
    dispatch counts, per-dispatch latency EWMA, last-seen age — the
    `volume.device.status -hot` / /debug/device/hot payload."""
    with _shapes_lock:
        items = sorted(
            _call_stats.items(), key=lambda kv: -kv[1][0]
        )[: max(0, limit)]
    now = time.time()
    out = []
    for key, (count, ewma_s, last) in items:
        (
            family, groups, w_true, tile, fetch, n_bucket, k, a_shape,
            surv_len, interpret, place,
        ) = key
        out.append(
            {
                "kernel": family,
                "groups": groups,
                "w_true": w_true,
                "tile": tile,
                "fetch": fetch,
                "count_bucket": n_bucket,
                "k": k,
                "a_shape": list(a_shape),
                "survivor_len": surv_len,
                "interpret": bool(interpret),
                # 0 = default device; n = lane-sharded over n devices;
                # ["dev", d] = whole-pin on mesh device d; ["pod", n, h]
                # = lane-sharded over an n-device h-host global mesh;
                # ["podev", d] = whole-pin on global lane d of a pod
                "placement": list(place) if isinstance(place, tuple)
                else place,
                "dispatches": count,
                "ewma_ms": round(ewma_s * 1e3, 3),
                "last_dispatch_age_s": round(max(0.0, now - last), 3),
            }
        )
    return out


def _blockdiag_fetch_tile(fetch: int, groups: int) -> tuple[int, int]:
    """(fetch, tile) for the fused blockdiag kernel: per-chunk segments
    must stay FUSED_ALIGN-provable, so fetch rounds UP to a multiple of
    groups*FUSED_ALIGN and tile is the fixed groups*FUSED_ALIGN-aligned
    chunk (= FUSED_TILE for g=4).  Coarser D2H ladder than flat — at
    most one extra step per request, traded for the blockdiag MXU win."""
    q = groups * FUSED_ALIGN
    fetch = -(-fetch // q) * q
    tile = FUSED_TILE if FUSED_TILE % q == 0 and fetch % FUSED_TILE == 0 else q
    return fetch, tile


def _note_shape(key: tuple) -> bool:
    """Record one device call's shape; True when it was a compile miss
    (first use).  Locked: concurrent drain lanes dispatching the same
    first-ever shape must count ONE miss, or the hit/miss ratio skews
    exactly under the load it exists to diagnose."""
    with _shapes_lock:
        if key in _dispatched_shapes:
            miss = False
        else:
            _dispatched_shapes.add(key)
            miss = True
    stats_metrics.VOLUME_SERVER_EC_DEVICE_COMPILE.labels(
        result="miss" if miss else "hit"
    ).inc()
    return miss


# --- AOT serving grid --------------------------------------------------------
#
# warm() used to TRACE-AND-EXECUTE every ladder shape through
# reconstruct_intervals; now it lowers each device-call shape with
# jax.jit(...).lower(...).compile() on a background executor and parks
# the Compiled executable here.  _dispatch_call routes a matching call
# straight through the executable (the jit wrapper's own cache never
# sees it, so there is no second compile), and a serving read that would
# dispatch a shape neither AOT-compiled nor inline-compiled raises
# ColdShape instead of stalling on a compile — the dispatcher serves it on the
# host path while the executor compiles the shape for the next read.

_aot_executables: dict[tuple, object] = {}  # call key -> jax Compiled
_aot_pending: set = set()  # keys queued/being compiled on the executor
# keys whose AOT compile RAISED: never re-queued (a deterministic
# compile failure would otherwise burn the single-worker executor
# one compile per matching read, forever) — the shape keeps shedding to the
# host path, which serves it fine
_aot_failed: set = set()
_AOT_EXECUTOR: concurrent.futures.Executor | None = None


def _call_key(
    kind, kernel, groups, w_true, tile, fetch, n_bucket, k, a_shape,
    surv_len, interpret, place=0,
) -> tuple:
    """Canonical identity of ONE device call's compiled shape — every
    static arg plus every aval dim of the reconstruct kernels.
    Shared by the miss counter, the AOT registry, and the shed check so
    the three can never disagree about what 'warm' means.  w_true only
    shapes the blockdiag kernels (the flat kernels' row select is purely
    data); normalizing it to 0 for flat keeps a warm plan's w_true=1
    probes valid for any wanted-set width with the same matrix shape.

    `place` is the r19 placement axis of the identity: 0 = the legacy
    default device, n >= 2 = lane-sharded over an n-device mesh (the
    sharded twin, compiled against NamedSharding avals), ("dev", d) = a
    whole-pin on mesh device d (an executable compiled for device 0
    cannot serve arrays committed to device d, so each owning device is
    its own compiled shape).  r20 grows the PROCESS dimension:
    ("pod", n_dev, n_hosts) = lane-sharded over a multi-controller
    global mesh (compiled with replicated output — a different program
    than the single-process n-wide twin), ("podev", d) = a whole-pin on
    GLOBAL lane d of a pod cache."""
    return (
        "fused" if kind == "fused" else kernel,
        groups,
        w_true if groups > 1 else 0,
        tile,
        fetch,
        n_bucket,
        k,
        tuple(a_shape),
        surv_len,
        bool(interpret),
        place,
    )


def _key_place(cache, place):
    """Map a cache placement to the _call_key placement element: the
    mesh width for lane-sharded vids, ("dev", d) for whole-pins on a
    mesh cache, 0 for the legacy single-device cache.  A multiprocess
    (pod) cache gets its own placement atoms — the SPMD executable with
    replicated output is a different program than the single-process
    sharded twin, and a pod whole-pin's owning device is a GLOBAL lane
    index resolved through the global mesh."""
    if place == "mesh":
        if cache.multiprocess:
            return ("pod", cache.n_devices, cache.n_hosts)
        return cache.n_devices
    if cache.mesh is not None:
        if cache.multiprocess:
            return ("podev", int(place))
        return ("dev", int(place))
    return 0


def _aot_executor() -> concurrent.futures.Executor:
    """Single-worker compile executor: AOT jobs run one at a time in
    submission order, so warm()'s observed-buckets-first priority IS the
    compile order even when several volumes pin at once."""
    global _AOT_EXECUTOR
    with _shapes_lock:
        if _AOT_EXECUTOR is None:
            _AOT_EXECUTOR = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ec-aot-compile"
            )
        return _AOT_EXECUTOR


def _compile_shape(key: tuple) -> None:
    """Build the Compiled executable for one call key (runs on the AOT
    executor).  Lowers against abstract avals only — no resident buffer
    is held while a compile runs.  Placement rides in the avals:
    lane-sharded keys lower against NamedSharding'd ShapeDtypeStructs
    (the executable spans the mesh), whole-pin keys against the owning
    device, so a sharded volume's first read can hit a parked
    executable exactly like a single-device one."""
    (
        family, groups, w_true, tile, fetch, n_bucket, k, a_shape,
        surv_len, interpret, place,
    ) = key
    pod = isinstance(place, tuple) and place[0] == "pod"
    if (isinstance(place, int) and place >= 2) or pod:
        n_dev = place[1] if pod else place
        mesh = (
            mesh_mod.global_serving_mesh(n_dev)
            if pod
            else mesh_mod.serving_mesh(n_dev)
        )
        if mesh is None or int(mesh.devices.size) != n_dev:
            raise RuntimeError(
                f"serving mesh of {n_dev} devices unavailable"
            )
        if pod and len(mesh_mod.mesh_hosts(mesh)) != place[2]:
            raise RuntimeError(
                f"pod mesh spans {len(mesh_mod.mesh_hosts(mesh))} hosts, "
                f"key compiled for {place[2]}"
            )
        a_aval = jax.ShapeDtypeStruct(
            a_shape, jnp.int8, sharding=NamedSharding(mesh, P(None, None))
        )
        sv = NamedSharding(mesh, P(mesh_mod.SHARD_AXIS))
        survivors = tuple(
            jax.ShapeDtypeStruct((surv_len,), jnp.uint8, sharding=sv)
            for _ in range(k)
        )
        vec = jax.ShapeDtypeStruct(
            (n_dev, 2, n_bucket), jnp.int32,
            sharding=NamedSharding(mesh, P(mesh_mod.SHARD_AXIS, None, None)),
        )
        with _quiet_donation():
            exe = _sharded_gather_reconstruct.lower(
                a_aval, survivors, vec, mesh=mesh, tile=tile,
                groups=groups, w_true=w_true if groups > 1 else 1,
                kernel=family, interpret=interpret, k_true=k,
                replicate_out=pod,
            ).compile()
        _register_compiled(key, exe)
        return
    if isinstance(place, tuple):
        # whole-pin on mesh device place[1]: the avals commit there.
        # ("podev", d) resolves d through the GLOBAL mesh — a pod
        # cache's whole-pin lives on one global lane.
        mesh = (
            mesh_mod.global_serving_mesh(0)
            if place[0] == "podev"
            else mesh_mod.serving_mesh(0)
        )
        dev = mesh.devices.reshape(-1)[place[1]]
        from jax.sharding import SingleDeviceSharding

        sds = SingleDeviceSharding(dev)
        a_aval = jax.ShapeDtypeStruct(a_shape, jnp.int8, sharding=sds)
        survivors = tuple(
            jax.ShapeDtypeStruct((surv_len,), jnp.uint8, sharding=sds)
            for _ in range(k)
        )
        vec_sharding = sds
    else:
        a_aval = jax.ShapeDtypeStruct(a_shape, jnp.int8)
        survivors = tuple(
            jax.ShapeDtypeStruct((surv_len,), jnp.uint8) for _ in range(k)
        )
        vec_sharding = None

    def _vec_aval(shape):
        if vec_sharding is None:
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=vec_sharding)

    with _quiet_donation():
        if family == "fused":
            vec = _vec_aval((n_bucket,))
            if groups > 1:
                lowered = _fused_reconstruct_blockdiag.lower(
                    a_aval, survivors, vec, tile=tile, fetch=fetch,
                    k_true=k, w_true=w_true, groups=groups,
                    interpret=interpret,
                )
            else:
                lowered = _fused_reconstruct.lower(
                    a_aval, survivors, vec, tile=tile, fetch=fetch,
                    k_true=k, interpret=interpret,
                )
        else:
            vec = _vec_aval((3, n_bucket))
            if groups > 1:
                lowered = _gather_reconstruct_blockdiag.lower(
                    a_aval, survivors, vec, tile=tile, fetch=fetch,
                    groups=groups, w_true=w_true, kernel=family,
                    interpret=interpret, k_true=k,
                )
            else:
                lowered = _gather_reconstruct.lower(
                    a_aval, survivors, vec, tile=tile, fetch=fetch,
                    kernel=family, interpret=interpret, k_true=k,
                )
        exe = lowered.compile()
    _register_compiled(key, exe)


def _register_compiled(key: tuple, exe) -> None:
    with _shapes_lock:
        _aot_executables[key] = exe
        # the shape is warm: a dispatch through the executable never
        # compiles, so the miss counter and shed check must see it
        _dispatched_shapes.add(key)
        _aot_pending.discard(key)
    stats_metrics.VOLUME_SERVER_EC_AOT_COMPILED.inc()


def _compile_shape_logged(key: tuple) -> None:
    # the compile executor's worker thread never inherits the caller's
    # tagging context, so warmup attribution is explicit here; a compile
    # occupies the (single) compile stream, not a serving slot, hence
    # its own class rather than folding into the requester's
    place = key[-1]
    dev_label = (
        "mesh" if isinstance(place, int) and place >= 2
        else str(place[1]) if isinstance(place, tuple)
        else "default"
    )
    t0 = time.perf_counter()
    try:
        with devledger.workload("warmup", device=dev_label):
            _compile_shape(key)
        devledger.record(
            workload="warmup", device=dev_label,
            busy_s=time.perf_counter() - t0, dispatches=1,
        )
    except Exception as e:  # noqa: BLE001 — a failed AOT compile must
        # not kill the executor; the shape stays cold and falls back to
        # the inline-compile path on a later non-shedding caller
        import logging

        logging.getLogger(__name__).exception(
            "AOT compile failed for shape %s", key
        )
        note_device_failure("aot", f"shape {key}: {e!r}")
        with _shapes_lock:
            _aot_pending.discard(key)
            _aot_failed.add(key)


def _schedule_aot_compiles(keys) -> list:
    """Queue cold call keys on the compile executor (dedup against the
    registry, the pending set, and inline-compiled shapes); returns the
    futures for callers that want to wait (warm)."""
    jobs = []
    with _shapes_lock:
        for key in keys:
            if (
                key in _aot_executables
                or key in _aot_pending
                or key in _dispatched_shapes
                or key in _aot_failed
            ):
                continue
            _aot_pending.add(key)
            jobs.append(key)
    if not jobs:
        return []
    ex = _aot_executor()
    return [ex.submit(_compile_shape_logged, key) for key in jobs]


def _shape_is_warm(key: tuple) -> bool:
    with _shapes_lock:
        return key in _dispatched_shapes or key in _aot_executables


def aot_stats() -> dict:
    """{"compiled", "pending", "failed"} — registry occupancy for
    status pages and tests."""
    with _shapes_lock:
        return {
            "compiled": len(_aot_executables),
            "pending": len(_aot_pending),
            "failed": len(_aot_failed),
        }


def _pack_calls_sharded(cache, requests, row_of, record_observed):
    """PACK stage for a lane-sharded volume: plan against the stripe
    width (requests split at stripe boundaries), partition each
    size-bucket group by OWNER DEVICE (stripe c lives on device c % n —
    the interleaving is what keeps ownership even at any volume size),
    and build per-device column lists of DEVICE-LOCAL offsets — device
    d's slots carry only d's requests, so the mesh does ~1/n of the
    batch's lane work per device.  Returns (calls, subs) with each call
    ("sharded", part, (dev_cols, width), 0, fetch, fetch, n_bucket,
    None): part entries are (sub_idx, sub, flat_row) where flat_row
    indexes the call's [n_dev * n_bucket, fetch] output (device-major),
    and fetch both gathers and ships — it covers every member's
    delta+take (backward-aligned deltas included), and the host trims
    the delta like the fused kernels' contract."""
    n_dev = cache.n_devices
    stripe = cache.stripe
    subs = _plan(requests, stripe)
    calls = []
    for bucket in SIZE_BUCKETS:
        group = [(i, s) for i, s in enumerate(subs) if s[4] == bucket]
        if not group:
            continue
        by_dev: list[list] = [[] for _ in range(n_dev)]
        for i, s in group:
            by_dev[(s[1] // stripe) % n_dev].append((i, s))
        if record_observed:  # live traffic only, as the observed shapes
            for d, mine in enumerate(by_dev):
                stats_metrics.VOLUME_SERVER_EC_MESH_LANE_REQUESTS.labels(
                    device=str(d)
                ).inc(len(mine))
        widest = max(len(b) for b in by_dev)
        n_bucket = _bucket(COUNT_BUCKETS, min(widest, _max_count(bucket)))
        for start in range(0, widest, n_bucket):
            part = []
            dev_cols = []
            span = 0
            for d in range(n_dev):
                chunk = by_dev[d][start : start + n_bucket]
                # device-local offset of a global aligned offset o in
                # stripe c = o // stripe: the device holds its stripes
                # owner-major, so stripe c sits at local stripe index
                # c // n_dev
                offs = [
                    (s[1] // stripe // n_dev) * stripe + s[1] % stripe
                    for _, s in chunk
                ]
                rows = [row_of[requests[s[0]][0]] for _, s in chunk]
                dev_cols.append((offs, rows))
                for j, (i, s) in enumerate(chunk):
                    part.append((i, s, d * n_bucket + j))
                    span = max(span, s[2] + s[3])
            if record_observed:
                _note_observed(bucket, n_bucket)
            fetch = min(bucket, _fetch_cover(span))
            calls.append(
                ("sharded", part, (dev_cols, n_bucket), 0, fetch, fetch,
                 n_bucket, None)
            )
    return calls, subs


def _pack_calls(
    cache, vid, requests, kernel, interpret, layout, data_shards,
    total_shards, record_observed=True,
):
    """PACK stage: resolve the codec, split/align the requests, group
    them into device calls, and build every call's HOST-side columns
    (plain lists — numpy staging waits for the slot's arena).  Returns
    (calls, subs, survivors, a_prep, use, w_true) with each call a
    (kind, part, cols, pad, fetch, tile, n_bucket, deltas) tuple —
    nothing has touched the device yet, so a double-buffered caller can
    pack batch N+1 while batch N still owns a staging slot.
    `record_observed=False` keeps synthetic probes (warm's ladder walk)
    out of the observed-shape ranking, which must reflect live traffic
    only."""
    a_prep, survivors, row_of, use, w_true, place = _resolve_codec(
        cache, vid, requests, data_shards, total_shards, layout
    )
    groups = cache.groups if layout == "blockdiag" else 1
    if place == "mesh":
        # lane-sharded volume: one cross-device program per call — the
        # planner routes every sub-request to the device owning its
        # gather window, so the fused single-device DMA kernels do not
        # apply (the sharded twin IS the batched gather)
        with obs_trace.span("mesh_pack", requests=len(requests)):
            calls, subs = _pack_calls_sharded(
                cache, requests, row_of, record_observed
            )
        return calls, subs, survivors, a_prep, use, w_true, place
    fused = _use_fused(kernel, interpret)
    subs = _plan(requests)
    calls = []
    for bucket in SIZE_BUCKETS:
        group = [(i, s) for i, s in enumerate(subs) if s[4] == bucket]
        if not group:
            continue
        wide = groups > 1 and w_true > 1
        n_bucket = _call_count(bucket, len(group), wide)
        for start in range(0, len(group), n_bucket):
            part = group[start : start + n_bucket]
            pad = n_bucket - len(part)
            if record_observed:
                _note_observed(bucket, n_bucket)
            if fused:
                # fetch covers the realigned delta+take (the host trims
                # the delta head after D2H; no in-kernel shift needed)
                packed, deltas, fetch = _fused_vectors(
                    part, requests, row_of
                )
                fetch, tile = _call_fetch_tile(fetch, groups, wide)
                calls.append(
                    ("fused", part, packed, pad, fetch, tile, n_bucket,
                     deltas)
                )
            else:
                cols = _group_vectors(part, requests, row_of)
                # D2H width: power-of-two cover of the largest actual
                # request in this call, never wider than the compute tile
                # nor narrower than the smallest one (so that the rungs
                # a plan has to hold stay few: _xla_fetch_rungs)
                max_take = max(s[3] for _, s in part)
                fetch = min(bucket, max(
                    SIZE_BUCKETS[0], 1 << (max_take - 1).bit_length()
                ))
                calls.append(
                    ("xla", part, cols, pad, fetch, bucket, n_bucket,
                     None)
                )
    return calls, subs, survivors, a_prep, use, w_true, place


def _stage_call_vec(kind, cols, pad, arena=None, block=0) -> np.ndarray:
    """Materialize one call's host staging vector — [n] packed int32
    (fused) or [3, n] int32 (xla fallback) — into row-block `block` of
    the held slot's arena when one is supplied (TPU: device_put copies,
    so the arena's rows are reused batch after batch with zero host
    allocs; the caller took the block and keeps it until the call's
    result is ready) or a fresh array otherwise (CPU PJRT zero-copies
    aligned numpy into the jax Array, so a reused buffer would alias an
    asynchronously executing call's input)."""
    if kind == "sharded":
        # [n_dev, 2, width] per-device (local offset, wanted row)
        # slots: the NamedSharding put splits this host-side and ships
        # each device exactly its own requests — never through the
        # arena (one block cannot back a device-sharded put), so a
        # fresh array a call, which the asynchronous put keeps alive
        dev_cols, width = cols
        vec = np.zeros((len(dev_cols), 2, width), dtype=np.int32)
        for d, (offs, rows) in enumerate(dev_cols):
            vec[d, 0, : len(offs)] = offs
            vec[d, 1, : len(rows)] = rows
        return vec
    if kind == "fused":
        if arena is not None:
            return arena.stage_fused(cols, pad, block)
        return np.array(cols + [0] * pad, dtype=np.int32)
    offsets, rows, deltas = cols
    if arena is not None:
        return arena.stage_xla(offsets, rows, deltas, pad, block)
    return np.array(
        [col + [0] * pad for col in (offsets, rows, deltas)],
        dtype=np.int32,
    )


def _dispatch_call(
    kind, vec, a_prep, survivors, n_use, w_true, groups, tile,
    fetch, kernel, interpret, key=None, mesh=None, replicate_out=False,
):
    """Route one packed call's staged vector to its kernel — the single
    home of the fused/xla x flat/blockdiag dispatch for
    reconstruct_intervals' drain loop, so that the shape a call
    dispatches and the shape _call_key names (the shed gate's, the warm
    plan's) cannot drift apart.  An AOT-compiled executable for
    the call's shape takes precedence: the jit wrappers' caches never
    see AOT-warmed shapes, so routing through the registry is what makes
    the background compile actually serve.  `key` is the call's
    _call_key when the caller already computed it (the serving drain
    loop shares one key list between the shed gate, the miss counter,
    and this lookup — recomputing here from the staged vec could drift
    from the gate's notion of "warm")."""
    if key is None:
        key = _call_key(
            kind, kernel, groups, w_true, tile, fetch, vec.shape[-1],
            n_use, a_prep.shape, int(survivors[0].size), interpret,
        )
    exe = _aot_executables.get(key)
    if exe is not None:
        return exe(a_prep, survivors, vec)
    with _quiet_donation():
        if kind == "sharded":
            return _sharded_gather_reconstruct(
                a_prep, survivors, vec, mesh=mesh, tile=tile,
                groups=groups, w_true=w_true if groups > 1 else 1,
                kernel=kernel, interpret=interpret, k_true=n_use,
                replicate_out=replicate_out,
            )
        if kind == "fused":
            if groups > 1:
                return _fused_reconstruct_blockdiag(
                    a_prep, survivors, vec, tile=tile, fetch=fetch,
                    k_true=n_use, w_true=w_true, groups=groups,
                    interpret=interpret,
                )
            return _fused_reconstruct(
                a_prep, survivors, vec, tile=tile, fetch=fetch,
                k_true=n_use, interpret=interpret,
            )
        if groups > 1:
            return _gather_reconstruct_blockdiag(
                a_prep, survivors, vec, tile=tile, fetch=fetch,
                groups=groups, w_true=w_true, kernel=kernel,
                interpret=interpret, k_true=n_use,
            )
        return _gather_reconstruct(
            a_prep, survivors, vec, tile=tile, fetch=fetch,
            kernel=kernel, interpret=interpret, k_true=n_use,
        )


def reconstruct_intervals(
    cache: DeviceShardCache,
    vid: int,
    requests: list[tuple[int, int, int]],
    kernel: str | None = None,
    interpret: bool | None = None,
    data_shards: int = DATA_SHARDS,
    total_shards: int = TOTAL_SHARDS,
    layout: str | None = None,
    record_observed: bool = True,
) -> list[bytes]:
    """Reconstruct interval bytes for a batch of degraded reads in as few
    device calls as possible (one per size bucket actually present).

    requests: [(wanted_shard_id, shard_offset, size)].  All gather inputs
    are resident shards; per-call H2D is just the offset/row vectors and
    D2H is exactly the reconstructed bytes.  Raises CacheMiss when fewer
    than `data_shards` non-wanted shards of `vid` are resident.

    `layout` (None = the cache's active layout) picks the kernel family:
    "blockdiag" serves through the block-diagonal g-group system,
    "flat" the plain one.  Packing runs before a staging slot is taken
    (cache.pipeline, 2 slots = double buffering), so a concurrent batch
    packs while the previous one holds the device section.  Inside the
    section every call is enqueued before any is collected: its vector
    put is issued and not waited for (a staging row-block of its own on
    a TPU, a fresh vector elsewhere), its program dispatched, and its
    result's copy to the host asked for at dispatch; a lane-sharded
    call asks only for the shards of devices that hold asked-for rows.
    The calls go out largest output first, so that the longest program
    and copy run behind the staging of all the others.  The section
    then collects in that order: it blocks once a call, for that call's
    result, and reads a host copy that has been streaming out since the
    program ended.  It waits earlier only where a batch has more calls
    than the arena has row-blocks, or more un-fetched output than
    _MAX_PENDING_OUT: then the oldest call is collected first.
    Every stage is a trace span feeding SeaweedFS_request_stage_seconds;
    ec_device_transfers_total{kind} says how the transfers went."""
    if not requests:
        return []
    kernel, interpret = _kernel_mode(kernel, interpret)
    if layout is None:
        layout = cache.layout
    if layout not in LAYOUTS:
        raise ValueError(f"unknown resident layout {layout!r}")
    groups = cache.groups if layout == "blockdiag" else 1
    fused = _use_fused(kernel, interpret)
    with obs_trace.span(
        "batch_pack", requests=len(requests), layout=layout
    ):
        calls, subs, survivors, a_prep, use, w_true, place = _pack_calls(
            cache, vid, requests, kernel, interpret, layout,
            data_shards, total_shards, record_observed,
        )
    surv_len = int(survivors[0].size)
    key_place = _key_place(cache, place)
    call_keys = [
        _call_key(
            kind, kernel, groups, w_true, tile, fetch, n_bucket,
            len(use), a_prep.shape, surv_len, interpret, key_place,
        )
        for kind, _part, _cols, _pad, fetch, tile, n_bucket, _d in calls
    ]
    # AOT shed gate: a volume with a warm plan must never pay an inline
    # compile on the serving path — a still-cold shape goes BACK to the
    # caller (host reconstruct) before any device work, and the compile
    # runs on the background executor so the next read finds it warm
    if cache.shed_cold and cache.aot_state(vid) != "none":
        cold = [key for key in call_keys if not _shape_is_warm(key)]
        if cold:
            _schedule_aot_compiles(cold)
            stats_metrics.VOLUME_SERVER_EC_SHED_COLD_SHAPE.inc(
                len(requests)
            )
            stats_metrics.VOLUME_SERVER_EC_READ_ROUTE.labels(
                route="shed_cold_shape"
            ).inc(len(requests))
            # flight recorder: the shed decision, trace-stamped — an
            # incident bundle can say "this tail read hit a cold shape"
            obs_incident.record(
                "cold_shape_shed", vid=vid, requests=len(requests),
                cold_shapes=len(cold),
            )
            raise ColdShape(
                f"vid {vid}: {len(cold)} device shape(s) still AOT-cold"
            )
    # the device-execute stage of the request trace: every dispatched
    # call's H2D/D2H bytes and compile-cache outcome annotate the span
    # (and the SeaweedFS_volumeServer_ec_device_* counters), so a slow
    # read can say "compile cliff" or "transfer-bound fetch" by itself
    dev_span = obs_trace.span(
        "device_execute", requests=len(requests), layout=layout,
        kernel=(("sharded_" if place == "mesh" else
                 "fused_" if fused else "")
                + ("blockdiag" if groups > 1 else kernel)),
    )
    dev_calls = dev_misses = dev_h2d = dev_d2h = rows_wanted = 0
    sub_out: list[bytes | None] = [None] * len(subs)

    # PIPELINE: enqueue everything, then collect.  jax dispatch is
    # async: a call's vector put is issued and left in flight (its
    # program orders itself behind the transfer), the program is
    # dispatched, and the result's copy to the host is asked for at
    # once, so it streams out as soon as the program ends while later
    # calls are still being staged; the section waits for nothing until
    # it collects, in order.  Aggregate un-fetched output is bounded:
    # every pending call holds its [n, fetch] result in HBM, so a huge
    # batch must collect the oldest call before dispatching more.
    pending: list[tuple] = []
    pending_bytes = 0
    n_dev = cache.n_devices
    # ec_device_transfers_total{kind}, counted once a batch
    transfers = {
        "h2d_waited": 0, "d2h_shard_fetched": 0, "d2h_shard_skipped": 0,
    }

    def _finish(entry) -> int:
        (part, arr, fetch, n_bucket, deltas, key, t_dispatch, wire_bytes,
         block, shards) = entry
        # completion boundary BEFORE the d2h span: jax dispatch is
        # async, so without it the fetch would absorb the kernel's
        # remaining execute time and an MXU/compile regression would
        # read as "transfer-bound fetch" in the stage histogram — the
        # blocking wait lands in device_execute, where it belongs
        arr.block_until_ready()
        if block is not None:
            # the program has consumed its vector: the rows are free
            arena.give(block)
        # the hot-shape view's latency sample: dispatch -> result ready
        # (pipelined calls include their wait behind siblings)
        _note_call_latency(key, time.perf_counter() - t_dispatch)
        sharded = deltas is None and part and len(part[0]) == 3
        # a sharded call's fetch is its own stage inside d2h_copy: the
        # n_bucket rows, padded slots too, of every device that holds an
        # asked-for row (`shards`), or of every device, in one array,
        # from the replicated result of a multi-process mesh
        mesh_fetch = (
            obs_trace.span("mesh_fetch", bytes=wire_bytes) if sharded
            else contextlib.nullcontext()
        )
        # the copy was asked for at dispatch: what is paid here is what
        # of it is still on its way, and numpy's view of the host copy
        with obs_trace.span("d2h_copy", bytes=wire_bytes), mesh_fetch:
            if shards is not None:
                # device -> its [n_bucket, fetch] rows
                out = {d: np.asarray(shard)[0] for d, shard in shards.items()}
            else:
                out = np.asarray(arr).reshape(
                    (-1, n_bucket, fetch) if sharded else (-1, fetch)
                )
        stats_metrics.VOLUME_SERVER_EC_D2H_BYTES.inc(wire_bytes)
        if sharded:
            mesh_d2h = stats_metrics.VOLUME_SERVER_EC_MESH_D2H_BYTES
            mesh_d2h.labels(kind="wire").inc(wire_bytes)
            mesh_d2h.labels(kind="useful").inc(
                sum(sub[3] for _, sub, _ in part)
            )
        if deltas is not None:  # fused: host trims the alignment delta
            for j, (sub_idx, (_, _, _, take, _)) in enumerate(part):
                d = deltas[j]
                sub_out[sub_idx] = out[j, d : d + take].tobytes()
        elif sharded:
            # sharded: part entries carry their flat output row (the
            # call's [n_dev * n_bucket, fetch] layout is device-major,
            # with padded slots between devices), which names the
            # owner's shard and the row in it; the host trims the delta
            # — backward-aligned windows fold theirs into it
            for sub_idx, (_, _, delta, take, _), row in part:
                d, j = divmod(row, n_bucket)
                sub_out[sub_idx] = out[d][j, delta : delta + take].tobytes()
        else:  # XLA fallback: delta was shifted on device iff narrowed
            bucket = part[0][1][4]
            for j, (sub_idx, (_, _, delta, take, _)) in enumerate(part):
                lo = 0 if fetch < bucket else delta
                sub_out[sub_idx] = out[j, lo : lo + take].tobytes()
        return wire_bytes

    # the ledger's device label follows placement; the workload class is
    # whatever the caller tagged (devledger.current_workload()) — the
    # serving dispatcher / scrub loop / repair handler set it at the edge
    dev_label = (
        "mesh" if place == "mesh"
        else str(int(place)) if cache.mesh is not None
        else "default"
    )
    with devledger.device(dev_label), cache.pipeline.slot() as pslot, dev_span:
        slot_wait_s = pslot.wait_s
        # the slot's preallocated arena only where device_put COPIES
        # (TPU/GPU); the CPU PJRT client zero-copies aligned numpy, so a
        # reused block would alias an asynchronously executing call's
        # input (see StagingArena); a sharded call's vector is split
        # over the mesh and never rides the arena (_stage_call_vec)
        arena = (
            pslot.arena if rs_tpu.on_tpu() and place != "mesh" else None
        )
        # the calls with the most output first: their programs and
        # their copies to the host take longest, and run while the
        # smaller calls are staged and dispatched behind them
        by_output = sorted(
            zip(calls, call_keys), reverse=True,
            key=lambda ck: ck[0][6] * ck[0][4],  # n_bucket * fetch
        )
        for call, key in by_output:
            kind, part, cols, pad, fetch, tile, n_bucket, deltas = call
            # H2D: stage + ship this call's packed host vector (ONE
            # int32 array per call — fused meta is a single packed row,
            # the r09 [2, N]/three-vector forms are gone).  Tiny, but
            # making it a named stage is what lets the stage histogram
            # show whether h2d or execute owns a regression.
            block = None
            if arena is not None:
                block = arena.take()
                if block is None:
                    # more calls than row-blocks: every block backs a
                    # put that may still be read, so collect the oldest
                    # call (its result proves its put landed) and stage
                    # into the rows it gives back
                    pending_bytes -= _finish(pending.pop(0))
                    block = arena.take()
                    transfers["h2d_waited"] += 1
            vec_np = _stage_call_vec(kind, cols, pad, arena, block)
            h2d_bytes = int(vec_np.nbytes)
            with obs_trace.span("h2d_copy", bytes=h2d_bytes):
                # sharding-aware staging: the vector lands directly on
                # the owning device(s) — split across the mesh for a
                # sharded call (each device receives only its own
                # requests' slots), committed to the claimed device for
                # a whole-pin, default device otherwise
                if kind == "sharded":
                    vec_sharding = NamedSharding(
                        cache.mesh, P(mesh_mod.SHARD_AXIS, None, None)
                    )
                    if cache.multiprocess:
                        # pod mesh: only THIS process's request rows are
                        # addressable here — ship exactly our lanes'
                        # slice (the local rows are contiguous in the
                        # canonical device order).  This is the only
                        # payload that crosses toward remote lanes, and
                        # it is request metadata, never survivor bytes.
                        lo = cache._local_dev_indices[0]
                        hi = cache._local_dev_indices[-1] + 1
                        dev_vec = jax.make_array_from_process_local_data(
                            vec_sharding, vec_np[lo:hi], vec_np.shape
                        )
                    else:
                        dev_vec = jax.device_put(vec_np, vec_sharding)
                elif cache.mesh is not None:
                    dev_vec = jax.device_put(
                        vec_np, cache.mesh.devices.reshape(-1)[int(place)]
                    )
                else:
                    dev_vec = jnp.asarray(vec_np)
                # the put is left in flight: the span is its enqueue,
                # and the program that consumes dev_vec orders itself
                # behind the transfer.  The transfer may read vec_np
                # until then: an arena block stays taken until _finish
                # has the call's result, and a fresh vector is kept
                # alive by the put itself
            stats_metrics.VOLUME_SERVER_EC_H2D_BYTES.inc(h2d_bytes)
            dev_h2d += h2d_bytes
            # the call key tracks the prepared matrix's shape EXACTLY
            # as retracing does: blockdiag kernels take w_true static
            # (and a_blk rows = 8*pad4(g*w_true) moves with it), while
            # the flat kernels only retrace when pad4(w_true) changes
            # a_bm's shape — keying on the shape neither misses a real
            # compile nor counts phantom ones
            dev_misses += _note_shape(key)
            t_dispatch = time.perf_counter()
            arr = _dispatch_call(
                kind, dev_vec, a_prep, survivors, len(use), w_true,
                groups, tile, fetch, kernel, interpret, key=key,
                mesh=cache.mesh if kind == "sharded" else None,
                replicate_out=cache.multiprocess,
            )
            # D2H asked for at dispatch: the copy to the host starts
            # when the program ends, while later calls are still being
            # staged, and all calls' copies are in flight together
            shards = None
            wire_rows = n_bucket
            if kind == "sharded" and not cache.multiprocess:
                # one [1, n_bucket, fetch] shard a device: only the
                # devices that hold asked-for rows are fetched, each
                # into a host array of its own (a whole-array fetch
                # would bring every device's padded rows and copy them
                # once more into one block)
                dev_cols = cols[0]
                shards = {}
                for shard in arr.addressable_shards:
                    d = shard.index[0].start or 0
                    if dev_cols[d][0]:
                        shard.data.copy_to_host_async()
                        shards[d] = shard.data
                transfers["d2h_shard_fetched"] += len(shards)
                transfers["d2h_shard_skipped"] += n_dev - len(shards)
                wire_rows *= len(shards)
            else:
                # one array: a single device's result, or the result a
                # multi-process mesh replicates, of which every process
                # reads all rows
                arr.copy_to_host_async()
                if kind == "sharded":
                    wire_rows *= n_dev
            # the padded rows ride the wire too: count what the fetch
            # actually moves, not just the useful subset
            pending.append(
                (part, arr, fetch, n_bucket, deltas, key, t_dispatch,
                 wire_rows * fetch, block, shards)
            )
            pending_bytes += wire_rows * fetch
            dev_calls += 1
            # the lost shards this call's requests asked for, beside the
            # w_true rows its program multiplies
            rows_wanted += (
                len({requests[e[1][0]][0] for e in part}) if w_true > 1 else 1
            )
            dev_d2h += wire_rows * fetch
            while pending_bytes > _MAX_PENDING_OUT and len(pending) > 1:
                pending_bytes -= _finish(pending.pop(0))
        for entry in pending:
            _finish(entry)
        transfers["h2d_async"] = dev_calls - transfers["h2d_waited"]
        for how, n in transfers.items():
            if n:
                stats_metrics.VOLUME_SERVER_EC_DEVICE_TRANSFERS.labels(
                    kind=how
                ).inc(n)
        rows = stats_metrics.VOLUME_SERVER_EC_RECONSTRUCT_ROWS
        rows.labels(kind="wanted").inc(rows_wanted)
        rows.labels(kind="computed").inc(w_true * dev_calls)
        dev_span.annotate(
            device_calls=dev_calls, compile_misses=dev_misses,
            h2d_bytes=dev_h2d, d2h_bytes=dev_d2h,
            slot_wait_us=int(slot_wait_s * 1e6),
        )
        stats_metrics.VOLUME_SERVER_EC_DEVICE_H2D_BYTES.inc(dev_h2d)
        stats_metrics.VOLUME_SERVER_EC_DEVICE_D2H_BYTES.inc(dev_d2h)
        # busy/queue-wait are the slot's (recorded on exit); dispatches
        # and boundary bytes are this batch's
        devledger.record(dispatches=dev_calls, nbytes=dev_h2d + dev_d2h)
    outputs: list[list[bytes]] = [[] for _ in requests]
    for (idx, *_), piece in zip(subs, sub_out):
        outputs[idx].append(piece)  # subs are in offset order per request
    # throttled observed-shape save (satellite: the warm/AOT priority
    # order survives restarts) — off the device path, after the batch
    _maybe_persist_observed()
    return [b"".join(parts) for parts in outputs]


# Scrub runs over BOUNDED LANE WINDOWS: one compiled program verifies
# _SCRUB_WINDOW lanes of every shard starting at a traced offset, the
# host walks the windows and adds the per-window mismatch counts with
# Python ints.  The program's HBM need (the stacked [k, window] input,
# the recomputed parity and the compare temporaries) is therefore fixed
# by the window, not by the shard: a whole-span program needs a padded
# [k, L] copy next to the resident shards and stops fitting a 16 GB chip
# at a few hundred MB per shard.  A window's mismatch count is at most
# _SCRUB_WINDOW < 2^31, so the int32 sum cannot wrap.  At 16 MiB the
# TPU compiler reports 0.7 GiB (blockdiag) to 1.2 GiB (flat) of
# temporaries per program (tests/test_tpu_compile.py holds the bound).
_SCRUB_WINDOW = 16 << 20


def _scrub_windows(n_lanes: int, window: int):
    """(start, width) lane windows covering [0, n_lanes): full `window`
    steps, then one remainder window of whatever is left."""
    for start in range(0, n_lanes, window):
        yield start, min(window, n_lanes - start)


def _lane_window(arr, start, width):
    return jax.lax.dynamic_slice(arr, (start,), (width,))


@functools.partial(
    jax.jit, static_argnames=("width", "kernel", "interpret")
)
def _scrub_call(a_bm, data, parity, start, *, width, kernel, interpret):
    """data: tuple of 10 resident [L_pad] u8 shards; parity: tuple of 4;
    start: traced int32 lane offset.  Recompute parity over lanes
    [start, start+width) and count mismatching bytes per parity shard —
    the ONLY thing that leaves the device is the [p] int32 mismatch
    vector."""
    x = jnp.stack([_lane_window(d, start, width) for d in data])
    out = rs_tpu.apply_matrix_device(
        a_bm, x, kernel=kernel, interpret=interpret, k_true=len(data)
    )
    return jnp.stack(
        [
            jnp.sum(
                (out[j] != _lane_window(parity[j], start, width)).astype(
                    jnp.int32
                )
            )
            for j in range(len(parity))
        ]
    )


@functools.partial(
    jax.jit, static_argnames=("width", "groups", "kernel", "interpret")
)
def _scrub_call_blockdiag(
    a_blk, data, parity, start, *, width, groups, kernel, interpret
):
    """Block-diagonal scrub of one lane window: the window splits into
    `groups` contiguous segments per shard (the host-staged segment
    stacking — slices of the same resident buffers), one apply of the
    blockdiag parity system recomputes every segment's parity, and group
    jg's output rows compare against parity segment jg.  Same contract
    as _scrub_call: only the [p] int32 mismatch vector leaves the
    device."""
    k = len(data)
    p = len(parity)
    seg = width // groups
    x = jnp.stack(
        [
            _lane_window(data[i], start + jg * seg, seg)
            for jg in range(groups)
            for i in range(k)
        ]
    )  # [g*k, seg], segment-stacked
    out = rs_tpu.apply_matrix_device(
        a_blk, x, kernel=kernel, interpret=interpret, k_true=groups * k
    )
    return jnp.stack(
        [
            sum(
                jnp.sum(
                    (
                        out[jg * p + j]
                        != _lane_window(parity[j], start + jg * seg, seg)
                    ).astype(jnp.int32)
                )
                for jg in range(groups)
            )
            for j in range(p)
        ]
    )


def scrub_volume(
    cache: DeviceShardCache,
    vid: int,
    kernel: str | None = None,
    interpret: bool | None = None,
    data_shards: int = DATA_SHARDS,
    total_shards: int = TOTAL_SHARDS,
    layout: str | None = None,
) -> tuple[list[int], int]:
    """Parity scrub of a fully resident volume: -> (per-parity-shard
    mismatch byte counts, bytes verified per shard).  Raises CacheMiss
    unless ALL shards are resident.  The verified span rounds the true
    shard size UP to the lane tile (blockdiag: to groups lane tiles, so
    every segment slice stays lane-aligned) — cache buffers are
    zero-padded and parity-of-zeros is zero, so the extra lanes verify
    trivially instead of costing a per-shard tail fetch.  The span is
    verified in _SCRUB_WINDOW-lane windows whose mismatch counts are
    summed on the host.  `layout` (None = cache's active layout) picks
    the kernel: blockdiag runs the scrub matmul on the block-diagonal
    system."""
    kernel, interpret = _kernel_mode(kernel, interpret)
    if layout is None:
        layout = cache.layout
    resident = cache.shard_ids(vid)
    if len(resident) < total_shards:
        raise CacheMiss(
            f"vid {vid}: {len(resident)}/{total_shards} shards resident"
        )
    sizes = {cache.shard_size(vid, s) for s in range(total_shards)}
    if len(sizes) != 1:
        raise CacheMiss(f"vid {vid}: resident shard sizes differ: {sizes}")
    true_size = sizes.pop()
    parity_m = gf256.build_matrix(data_shards, total_shards)[data_shards:]
    data = tuple(cache.get(vid, s) for s in range(data_shards))
    parity = tuple(
        cache.get(vid, s) for s in range(data_shards, total_shards)
    )
    if any(s is None for s in data + parity):
        raise CacheMiss(f"vid {vid}: shard evicted mid-scrub")
    if cache.vid_sharded(vid):
        # lane-sharded buffers are stripe-PERMUTED on device: parity is
        # byte-wise, so verifying the permuted layout is positionally
        # consistent across shards — but a true_size-bounded span would
        # cover an arbitrary stripe subset, so scrub the WHOLE padded
        # buffer (the zero padding verifies trivially: parity of zeros
        # is zero, identically placed in every shard)
        true_size = int(data[0].size)
    # scrub is scrub no matter who invoked it (the background loop, the
    # shell verb, a repair preflight) — pin the ledger class here, where
    # the dispatch happens
    t0 = time.perf_counter()
    p = total_shards - data_shards
    mismatch = [0] * p
    if layout == "blockdiag":
        quant = cache.groups * LANE
        n_lanes = -(-true_size // quant) * quant
        a_prep = _prepared_blockdiag_matrix(
            parity_m.tobytes(), *parity_m.shape, cache.groups
        )
        call = functools.partial(
            _scrub_call_blockdiag, groups=cache.groups
        )
    else:
        n_lanes = -(-true_size // LANE) * LANE
        a_prep = _prepared_matrix(parity_m.tobytes(), *parity_m.shape)
        call = _scrub_call
    dispatches = 0
    with devledger.workload("scrub"):
        for start, width in _scrub_windows(n_lanes, _SCRUB_WINDOW):
            # graftlint: allow(device-sync): deliberate D2H of the tiny
            # [p] int32 mismatch vector — the whole point of scrub is
            # that only this verdict leaves the device
            counts = np.asarray(
                call(
                    a_prep, data, parity, np.int32(start),
                    width=width, kernel=kernel, interpret=interpret,
                )
            )
            dispatches += 1
            for j in range(p):
                mismatch[j] += int(counts[j])
    devledger.record(
        workload="scrub", busy_s=time.perf_counter() - t0,
        dispatches=dispatches, nbytes=4 * p * dispatches,
    )
    stats_metrics.VOLUME_SERVER_EC_SCRUB_DISPATCH.labels(
        mode="per_volume"
    ).inc(dispatches)
    return mismatch, n_lanes


# --- fused multi-volume scrub megakernel -------------------------------------
#
# Per-volume scrub re-pays its device dispatches per pinned volume even
# though every input already sits in HBM.  The megakernel walks the
# WHOLE resident cache in one pass: every volume shares the same
# block-diagonal parity system (the per-volume matrices stacked
# block-diagonally are just the SAME cached a_blk the per-volume scrub
# uses), so V volumes stack along the LANE axis — x is [g*k, V*seg] with
# volume v's segment-stacked rows occupying its seg lanes — and one
# matmul recomputes every volume's parity at the same per-byte MXU cost
# as the per-volume loop.  (Expanding the matrix to V*g blocks instead
# would multiply the dense contraction V-fold; the lane stack keeps
# compute linear and amortizes only what is actually per-call: dispatch
# and trace.)  The verdict reduction happens on device exactly as in
# _scrub_call: only the [V, p] int32 mismatch counts of each lane window
# come back, and the host sums them to a per-volume verdict.
#
# Stacks are padded to a power-of-two volume count (repeating the first
# volume) so the compile ladder stays a handful of shapes per n_lanes
# class, not one per cache occupancy; _SCRUB_STACK_CAP bounds the pow2
# padding waste, and the shared lane window (_SCRUB_WINDOW / V lanes per
# volume) bounds every call's transient HBM whatever the shard size.

_SCRUB_STACK_CAP = 32  # max volumes fused into one device call


@functools.partial(
    jax.jit,
    static_argnames=(
        "width", "groups", "vols", "k", "p", "kernel", "interpret",
    ),
)
def _scrub_all_call(
    a_blk, shards, start, *, width, groups, vols, k, p, kernel, interpret
):
    """shards: flat tuple of vols*(k+p) resident buffers, volume-major
    (k data then p parity per volume); a_blk the SAME per-volume
    blockdiag parity system scrub_volume applies; start: traced int32
    lane offset.  One matmul over the lane-stacked [g*k, vols*seg]
    input recomputes every volume's parity over lanes
    [start, start+width); -> [vols, p] int32 mismatch counts (the only
    D2H).  The host sizes `width` so vols*width stays one
    _SCRUB_WINDOW: the call's HBM need does not grow with the stack."""
    seg = width // groups
    x = jnp.stack(
        [
            # row jg*k + i: shard i's segment jg, all volumes
            # concatenated along lanes
            jnp.concatenate(
                [
                    _lane_window(
                        shards[v * (k + p) + i], start + jg * seg, seg
                    )
                    for v in range(vols)
                ]
            )
            for jg in range(groups)
            for i in range(k)
        ]
    )  # [groups*k, vols*seg]
    out = rs_tpu.apply_matrix_device(
        a_blk, x, kernel=kernel, interpret=interpret,
        k_true=groups * k,
    )
    return jnp.stack(
        [
            jnp.stack(
                [
                    sum(
                        jnp.sum(
                            (
                                out[jg * p + j][v * seg : (v + 1) * seg]
                                != _lane_window(
                                    shards[v * (k + p) + k + j],
                                    start + jg * seg, seg,
                                )
                            ).astype(jnp.int32)
                        )
                        for jg in range(groups)
                    )
                    for j in range(p)
                ]
            )
            for v in range(vols)
        ]
    )


def scrub_all_resident(
    cache: DeviceShardCache,
    kernel: str | None = None,
    interpret: bool | None = None,
    data_shards: int = DATA_SHARDS,
    total_shards: int = TOTAL_SHARDS,
    layout: str | None = None,
    vids: list[int] | None = None,
) -> tuple[dict[int, tuple[list[int], int]], dict]:
    """Parity-scrub EVERY fully resident volume (or the `vids` subset)
    in as few device passes as possible: volumes with equal verified
    spans stack into one block-diagonal megakernel call, amortizing
    dispatch + H2D over the whole cache.  -> ({vid: (per-parity-shard
    mismatch byte counts, bytes verified per shard)}, {"device_calls",
    "volumes"}).  Volumes that stop qualifying mid-pass (eviction, size
    mismatch) are silently absent from the result — the caller's
    per-volume path still owns them."""
    kernel, interpret = _kernel_mode(kernel, interpret)
    if layout is None:
        layout = cache.layout
    groups = cache.groups if layout == "blockdiag" else 1
    k = data_shards
    p = total_shards - data_shards
    quant = groups * LANE
    if vids is None:
        vids = sorted(cache.resident_by_vid())
    # ((n_lanes, placement), [(vid, shard tuple)]) stacks: only fully
    # resident, uniform-size volumes qualify (same rule as
    # scrub_volume).  Placement is part of the stack key: one
    # _scrub_all_call's inputs must share a device set — stacking a
    # device-0 whole-pin with a device-1 one (or a mesh-sharded volume)
    # is a jit device-mismatch ValueError, not a slow path
    stacks: dict[tuple[int, object], list[tuple[int, tuple]]] = {}
    for vid in vids:
        if cache.resident_count(vid) < total_shards:
            continue
        sizes = {cache.shard_size(vid, s) for s in range(total_shards)}
        if len(sizes) != 1 or None in sizes:
            continue
        shards = tuple(cache.get(vid, s) for s in range(total_shards))
        if any(s is None for s in shards):
            continue
        size = sizes.pop()
        if cache.vid_sharded(vid):
            # permuted stripe layout: scrub the whole padded buffer
            # (see scrub_volume — positional consistency holds, a
            # true_size-bounded span would cover an arbitrary subset)
            size = int(shards[0].size)
        n_lanes = -(-size // quant) * quant
        place = cache.placement(vid)
        stacks.setdefault((n_lanes, 0 if place is None else place), []).append(
            (vid, shards)
        )
    parity_m = gf256.build_matrix(data_shards, total_shards)[data_shards:]
    # the SAME prepared system scrub_volume uses (one cached device
    # copy): volumes stack along lanes, never into a bigger matrix
    a_blk = _prepared_blockdiag_matrix(
        parity_m.tobytes(), *parity_m.shape, groups
    )
    results: dict[int, tuple[list[int], int]] = {}
    device_calls = 0
    for (n_lanes, _place), members in sorted(
        stacks.items(), key=lambda kv: (kv[0][0], str(kv[0][1]))
    ):
        step = _SCRUB_STACK_CAP
        for start_v in range(0, len(members), step):
            chunk = members[start_v : start_v + step]
            # pad to the power-of-two volume bucket by repeating the
            # first volume: compile shapes quantize to the bucket
            # ladder, and the duplicate lanes' counts are dropped
            vols = 1 << (len(chunk) - 1).bit_length()
            padded = chunk + [chunk[0]] * (vols - len(chunk))
            flat = tuple(s for _vid, shards in padded for s in shards)
            # the stack shares ONE lane window: vols * window stays
            # _SCRUB_WINDOW (quant-aligned), so a call's transient HBM
            # is the same for one large volume as for 32 small ones
            window = max(quant, _SCRUB_WINDOW // vols // quant * quant)
            mismatch = [[0] * p for _ in chunk]
            t0 = time.perf_counter()
            calls = 0
            with devledger.workload("scrub"):
                for start, width in _scrub_windows(n_lanes, window):
                    # graftlint: allow(device-sync): deliberate D2H —
                    # the [V, p] mismatch counts are the megakernel's
                    # only output, host-summed to per-volume verdicts
                    counts = np.asarray(
                        _scrub_all_call(
                            a_blk, flat, np.int32(start), width=width,
                            groups=groups, vols=vols, k=k, p=p,
                            kernel=kernel, interpret=interpret,
                        )
                    )
                    calls += 1
                    for v in range(len(chunk)):
                        for j in range(p):
                            mismatch[v][j] += int(counts[v][j])
            devledger.record(
                workload="scrub", busy_s=time.perf_counter() - t0,
                dispatches=calls, nbytes=4 * vols * p * calls,
            )
            device_calls += calls
            stats_metrics.VOLUME_SERVER_EC_SCRUB_DISPATCH.labels(
                mode="megakernel"
            ).inc(calls)
            for (vid, _shards), counts_v in zip(chunk, mismatch):
                results[vid] = (counts_v, n_lanes)
    return results, {"device_calls": device_calls, "volumes": len(results)}


def _warm_key(size: int, count: int) -> tuple[int, int]:
    """Map a warm-plan (size, count) to the (size_bucket, count_bucket)
    shape its ALIGNED-offset request compiles — the key space
    observed_buckets() records.  Ranking by the off=0 class (not
    size+delta) keeps boundary sizes like 2048 in their own bucket."""
    b = _bucket(SIZE_BUCKETS, min(size, MAX_TILE))
    return b, _bucket(COUNT_BUCKETS, min(count, _max_count(b)))


def _warm_grid(cache, vid, sizes, counts, total_shards, observed):
    """(missing shard, observed-first ordered [(size, count)] grid), or
    (None, []) when the volume cannot serve a degraded read at all."""
    resident = cache.shard_ids(vid)
    non_resident = [s for s in range(total_shards) if s not in resident]
    if non_resident:
        missing = non_resident[0]
        if len(resident) < DATA_SHARDS:
            return None, []
    else:
        missing = resident[-1]
        if len(resident) - 1 < DATA_SHARDS:
            return None, []
    grid = [(size, count) for size in sizes for count in counts]
    if observed is None:
        observed = observed_buckets()
    if observed:
        rank = {b: i for i, b in enumerate(observed)}
        grid.sort(key=lambda sc: rank.get(_warm_key(*sc), len(rank)))
    return missing, grid


def warm(
    cache: DeviceShardCache,
    vid: int,
    sizes: tuple[int, ...] = (4096, 65536, 1 << 20),
    counts: tuple[int, ...] = (1, 8, 64),  # single read, a batcher
    # coalesce round, and a full burst — the serving path's count shapes
    total_shards: int = TOTAL_SHARDS,
    should_stop=None,  # callable -> bool: abort between compiles
    layout: str | None = None,
    observed: list[tuple[int, int]] | None = None,
    aot: bool = True,
    wait: bool = True,
    kernel: str | None = None,
    interpret: bool | None = None,
    **kw,
) -> None:
    """Make the bucket combinations a serving path will hit compiled
    BEFORE the first real degraded read, so none pays a TPU
    compile inline.  The wanted shard is a NON-resident one when any
    exists (the realistic degraded case), so a volume with exactly
    DATA_SHARDS survivors still warms.

    Default mode (`aot=True`) is ahead-of-time: every device-call shape
    of the grid is lowered + compiled (jax.jit(...).lower(...).compile())
    on the single-worker background executor, in observed-buckets-first
    priority order, and parked in the AOT registry _dispatch_call serves
    from — no synthetic read is ever executed.  Setting the plan also
    arms the cold-shape shed for this volume (cache.aot_state != "none"):
    a serving read racing the executor sheds to host instead of
    compiling inline.  `wait=False` returns as soon as the plan is
    queued; `wait=True` blocks until the grid is compiled and marks the
    volume "done".  `aot=False` is the legacy trace-and-execute walk
    (kept for the -ec.serving.aot.disable knob and as the
    compiled-shapes oracle in tests); it never arms the shed.

    Compiles the ACTIVE layout's ladder only (`layout`, None = the
    cache's — the other family's shapes would double the per-shape
    mount-time bill for a path the knob has switched off), and walks the
    grid OBSERVED-SHAPES-FIRST (`observed`, default this process's
    dispatch history): a re-pin under live traffic reaches
    serving-readiness for the workload's real (size, count) buckets
    before burning compiles on ladder corners nobody hits.

    When a plan is made: at the pin (storage/store.py), at a promotion
    (serving/tiering.py), both through this function, and after a loss
    (warm_replan, below).  The grid is the family of ONE wanted shard.
    A volume that is two or more data shards down when this runs gets
    the wide family of its loss as well (_wide_probes); one that loses
    them later, with its plan made, gets it from warm_replan."""
    if layout is None:
        layout = cache.layout
    kernel, interpret = _kernel_mode(kernel, interpret)
    missing, grid = _warm_grid(
        cache, vid, sizes, counts, total_shards, observed
    )
    if missing is None or not grid:
        # no plan (unservable volume, or the CI convention warm_sizes=())
        # — aot_state stays "none", so reads keep inline compiles
        return
    if not aot:
        for size, count in grid:
            # both alignment classes: an aligned offset keeps fetch at
            # cover(size); any other offset pushes the span past it onto
            # the next ladder step (usually the 3*2^(n-1) one, see
            # _fetch_cover) — each is its own compiled shape
            for off in (0, 1):
                if should_stop is not None and should_stop():
                    return
                reqs = [(missing, off, size)] * count
                # record_observed=False: warm's own ladder walk must not
                # feed the observed-shape ranking it consults
                reconstruct_intervals(
                    cache, vid, reqs, layout=layout, kernel=kernel,
                    interpret=interpret, record_observed=False, **kw,
                )
        return
    cache._set_aot_state(vid, "warming")
    futures = []
    probes = [
        [(missing, off, size)] * count
        for size, count in grid for off in (0, 1)
    ]
    # a volume that is pinned with two or more data shards down already
    # (a restart, a promotion) gets the wide family of its loss with the
    # plan; one that loses them later gets it from warm_replan
    for reqs in probes + _wide_probes(cache, vid, sizes):
        if should_stop is not None and should_stop():
            # aborted (pin teardown): no plan is coming, so the
            # volume must not stay shed-armed in "warming"
            cache._set_aot_state(vid, "none")
            return
        try:
            keys = _probe_keys(
                cache, vid, reqs, kernel, interpret, layout, total_shards
            )
        except CacheMiss:
            # evicted under the planner: nothing to warm — reset the
            # state so a later direct re-pin doesn't shed forever
            # against a plan that never ran
            cache._set_aot_state(vid, "none")
            return
        futures.extend(_schedule_aot_compiles(keys))
    if wait:
        for f in futures:
            f.result()
        cache._set_aot_state(vid, "done")
    elif futures:
        futures[-1].add_done_callback(
            lambda _f: cache._set_aot_state(vid, "done")
        )
    else:  # every shape already warm
        cache._set_aot_state(vid, "done")


def _probe_keys(cache, vid, reqs, kernel, interpret, layout, total_shards):
    """Every call key a live batch shaped like the probe `reqs` can
    dispatch.  Raises CacheMiss where the volume cannot serve it."""
    calls, _subs, survivors, a_prep, use, w_true, place = _pack_calls(
        cache, vid, reqs, kernel, interpret, layout, DATA_SHARDS,
        total_shards, record_observed=False,
    )
    groups = cache.groups if layout == "blockdiag" else 1
    surv_len = int(survivors[0].size)
    key_place = _key_place(cache, place)
    keys = []
    for kind, part, _c, _pad, fetch, tile, n_bucket, _d in calls:
        shapes = [(fetch, tile)]
        if kind == "fused":
            # a live group's fetch follows its LARGEST span, so
            # a batch in this probe's size bucket can land on
            # any rung of the bucket's ladder: compile them all,
            # or a warmed (size, count) still sheds cold
            bucket = part[0][1][4]
            shapes = dict.fromkeys(
                _call_fetch_tile(f, groups, groups > 1 and w_true > 1)
                for f in _fused_fetch_rungs(bucket)
            )
        elif kind == "xla":
            # the fallback kernel's fetch is the power of two that
            # covers the group's largest take, from the bucket below
            # up to its own
            shapes = [(f, tile) for f in _xla_fetch_rungs(tile)]
        keys.extend(
            _call_key(
                kind, kernel, groups, w_true, t, f, n_bucket,
                len(use), a_prep.shape, surv_len, interpret,
                key_place,
            )
            for f, t in shapes
        )
    if isinstance(key_place, int) and key_place >= 2:
        # lane-sharded: the key's count bucket is the PER-DEVICE
        # width — a live batch of `count` reads lands anywhere
        # between ceil(count/n_dev) (spread) and count (every
        # hot needle in one chunk) per device — and its
        # fetch(=tile) can be any cover-ladder rung up to the
        # probe's bucket (stripe-boundary splits shrink the
        # span, backward alignment grows it to the full
        # bucket).  Compile every (fetch rung, count rung at or
        # below the probe's) so no distribution or boundary
        # placement of a warmed batch width hits a cold shape
        # (tile/fetch are key[3:5], n_bucket key[5])
        keys = list(
            dict.fromkeys(
                key[:3] + (f, f, cb) + key[6:]
                for key in keys
                for f in _sharded_fetch_rungs(key[4])
                for cb in COUNT_BUCKETS
                if cb <= key[5]
            )
        )
    return keys


def _xla_fetch_rungs(bucket: int) -> list[int]:
    """Every fetch a live sub-request group of size bucket `bucket` can
    produce on the XLA fallback kernel: the power of two covering its
    largest take, held between the smallest bucket and its own; and a
    take lies within LANE - 1 of the bucket below."""
    i = SIZE_BUCKETS.index(bucket)
    f = SIZE_BUCKETS[i - 1] if i else bucket
    rungs = []
    while f <= bucket:
        rungs.append(f)
        f <<= 1
    return rungs


# --- the warm plan follows the loss -------------------------------------------
#
# The block-diagonal kernels take the wanted-set width w_true static, and
# the prepared matrix has 8*pad4(groups*w_true) rows, so every width is
# its own family of compiled shapes.  The plan made at pin time is the
# family of ONE wanted shard.  A volume that is two or more data shards
# down can be asked, in one batch, for any subset of them; it is answered
# with ONE more family: a batch that wants more than one lost data shard
# is given the matrix of ALL the volume's lost data shards (_batch_wanted;
# the row select is in the kernel, so no more bytes leave the device),
# and its calls take one count a size class (_WIDE_COUNTS) and the
# powers of two of the fetch ladder (_call_fetch_tile).  That family is
# twelve shapes on one chip, small enough to compile in the time a
# failed holder is noticed in (2-3 s a shape cold), and it is planned
# when the loss is seen: warm_replan.

# the count bucket of a wide call by size bucket: padded rows ride the
# wire, so the classes whose rows are large pad to few; a group beyond
# its count goes out as several calls
_WIDE_COUNTS = dict(zip(SIZE_BUCKETS, (8, 8, 8, 4, 4, 2)))


def _batch_wanted(requests, resident, data_shards) -> list[int]:
    """The wanted set a batch's matrix is built for: the shards its
    requests name; all the volume's lost data shards where they name
    more than one of them."""
    wanted = sorted({r[0] for r in requests})
    if len(wanted) > 1:
        lost = [s for s in range(data_shards) if s not in resident]
        if set(wanted) <= set(lost):
            return lost
    return wanted


def _call_count(size_bucket: int, n: int, wide: bool) -> int:
    """The count bucket of a group of `n` sub-requests of one size
    bucket; `wide` = a block-diagonal call of more than one wanted row."""
    if wide:
        return _WIDE_COUNTS[size_bucket]
    return _bucket(COUNT_BUCKETS, min(n, _max_count(size_bucket)))


def _call_fetch_tile(fetch: int, groups: int, wide: bool) -> tuple[int, int]:
    """(fetch, tile) of a fused call whose group's cover is `fetch`; a
    wide call takes the next power of two, so that its family has ten
    fetch rungs for the ladder's eighteen (at most a third more bytes
    fetched on a 3*2^(n-1) rung)."""
    if wide:
        fetch = 1 << (fetch - 1).bit_length()
    return _fused_fetch_tile(fetch, groups)


def _wide_lost(cache, vid) -> list[int]:
    """The lost data shards of `vid` where its loss asks for the wide
    family: two or more of them (the family of one wanted shard serves
    any other volume whole), and not on the mesh, whose plan does not
    follow a loss yet."""
    resident = cache.shard_ids(vid)
    lost = [s for s in range(DATA_SHARDS) if s not in resident]
    if len(lost) < 2 or cache.placement(vid) == "mesh":
        return []
    return lost


def _wide_probes(cache, vid, sizes) -> list[list[tuple[int, int, int]]]:
    """One probe a size and alignment class that wants every lost data
    shard of `vid`."""
    lost = _wide_lost(cache, vid)
    return [
        [(sid, off, size) for sid in lost]
        for size in sizes if lost for off in (0, 1)
    ]


def loss_owes_replan(cache: DeviceShardCache, vid: int) -> bool:
    """Shards of `vid` just went.  True, with the volume back in
    "warming", where it has a warm plan and its loss can now ask for
    the wide family; the caller then runs warm_replan off its thread."""
    if cache.aot_state(vid) == "none" or not _wide_lost(cache, vid):
        return False
    cache._set_aot_state(vid, "warming")
    return True


def warm_replan(cache: DeviceShardCache, vid: int) -> tuple[int, int]:
    """Compile what the volume's present loss can ask for and the
    registry lacks, on the AOT executor, and wait for it.  -> (shapes
    queued, shapes already warm).  The state is the caller's: it set
    "warming" (loss_owes_replan) and sets "done" (replan_done) unless a
    later loss has superseded this plan.  A read that races the executor
    is shed to the host and counted, as during the first plan."""
    kernel, interpret = _kernel_mode()
    with obs_trace.span("warm_replan", vid=vid) as sp:
        keys: list[tuple] = []
        try:
            for reqs in _wide_probes(cache, vid, cache.warm_sizes):
                keys += _probe_keys(
                    cache, vid, reqs, kernel, interpret, cache.layout,
                    TOTAL_SHARDS,
                )
        except CacheMiss:
            keys = []  # evicted under the planner: nothing to serve
        keys = list(dict.fromkeys(keys))
        warm_already = sum(map(_shape_is_warm, keys))
        futures = _schedule_aot_compiles(keys)
        sp.annotate(queued=len(futures), warm=warm_already)
        # one worker, in order: when this job has run, so has every
        # compile queued before it, a shed read's as well as this plan's
        futures.append(_aot_executor().submit(lambda: None))
        for f in futures:
            f.result()
    return len(futures) - 1, warm_already


def replan_done(cache: DeviceShardCache, vid: int) -> None:
    """The newest re-plan of `vid` is in.  (A volume evicted meanwhile
    has no state left, and gets none.)"""
    cache._set_aot_state(vid, "done")


# At the end of the file on purpose: a Pallas executable's persistent-cache
# key carries its kernel's source lines, so code added above the kernels
# costs every warm shape a recompile.


def _device_memory() -> dict:
    """{"memory": [{"device", "bytes_in_use", "peak_bytes_in_use",
    "bytes_limit"}, ...]} from each local device's allocator, or {} where
    the backend reports none (the CPU's `memory_stats()` is None)."""
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    memory = [
        {"device": d.id, **{k: int(m[k]) for k in keys if k in m}}
        for d in mesh_mod.local_devices()
        if (m := d.memory_stats())
    ]
    return {"memory": memory} if memory else {}
