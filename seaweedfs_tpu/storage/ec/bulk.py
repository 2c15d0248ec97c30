"""Staged executor shared by the bulk EC pipelines (encode / rebuild /
verify in encoder.py).

The three pipelines move the same shape of work: read stripe batches
from disk, push them through a GF(256) matrix multiply (device or CPU),
and write/compare the results.  Before this module each pipeline staged
every pread and every shard write on the caller thread between device
submits, so wall-clock was read + device + write even though the legs
touch disjoint resources.  Here the legs run on dedicated threads around
bounded queues, so wall-clock trends toward max(read, device, write):

  reader leg   -> bounded stripe queue ->  caller (submit/resolve)
                                             |  bounded result queue
                                             v
                                          writer leg

Reads use one vectored ``os.preadv`` per stripe where the platform has
it and the stripe's rows are contiguous on disk (full-block batches),
instead of DATA_SHARDS serial preads; every other row is one ``preadv``
straight into its row, no intermediate ``bytes``.  A batch out of
per-shard files (rebuild, verify) is one ``preadv`` a file, the files
read side by side on READ_THREADS threads (``read_shard_rows``).

The [k, stride]-sized host buffers of a batch — the reader leg's payload
and the codec worker's staging buffer — come from ``POOL`` and go back
to it: a rebuild batch's 40 MiB is above glibc's largest mmap threshold
(32 MiB), so a new array a batch is a new mapping a batch, every page
faulted on first touch (47 ms of a 68 ms batch on the chip host, PERF.md
PR 24/25).  A recycled buffer holds the previous batch's bytes, so what
keeps old bytes out of parity is the readers' zero fill past what was
read (``_read_row``, ``_zero_tail``) — never a memset of the whole
buffer, which was ~10% of the read leg at device speeds.

Rebuild's payload has no reader but the codec, so its reader leg fills
it in the row order the codec's program takes (``Codec.segments``: each
``preadv`` scatters a shard's segments to their stacked rows) and the
worker puts it on the device as it is: the payload IS the staged buffer,
and no second copy of the batch is made on the host.  Encode's payload
is also the writer leg's (the ten data rows go to their shard files) and
verify's is compared against, so both stay plain rows and the worker
stages its own copy.

The codec worker keeps up to ``DEVICE_DEPTH`` = 2 batches on the device:
it enqueues batch n+1 (``device_put`` and the program) before it fetches
batch n, so the H2D of one batch runs beside the program and the D2H of
the one before it and the wire is not idle while the host enqueues and
unstacks.  It clocks itself: it
enqueues a successor only where one has been submitted already, and
fetches the oldest batch at once where none has, so the serial mode, a
verb's last batch and a slow reader need no flush
(``Codec._advance``; ``ec_bulk_pipelined_batches`` counts the batches
whose fetch began with their successor enqueued).

Stats contract (the dict ``run()`` fills, same keys for all three
pipelines):

  read_s / submit_s / wait_s / write_s   per-leg active seconds
  device_busy_s                          codec worker active time (one
                                         thread: never two batches'
                                         lifetimes added up)
  wall_s, fsync_s, batches               caller-filled wall + tail
  overlap                                the mode the run used

With ``overlap=False`` every leg runs on the caller thread, so
``read_s + submit_s + wait_s + write_s (+ fsync_s) ~= wall_s``.  With
``overlap=True`` the legs overlap and
``read_s + write_s + device_busy_s > wall_s - fsync_s`` is the measured
proof (the fsync tail follows the last write by definition, so it is
excluded from the window on both sides of the claim) —
the per-pipeline ``SeaweedFS_volumeServer_ec_bulk_*`` series and the
``bulk_read`` / ``bulk_device`` / ``bulk_write`` trace stages publish
the same decomposition; the device leg's own four parts (stage /
enqueue / fetch / unstack, ``ec_bulk_codec_seconds``) are counted per
batch as it runs, and while a profiler capture is live every batch's
sections are events on their threads' lines.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor, wait
from dataclasses import dataclass

import numpy as np

from ...obs import devledger
from ...obs import trace as obs_trace
from ...ops import rs
from ...stats import metrics as _metrics
from .layout import DATA_SHARDS, LARGE_BLOCK_SIZE

# Per-shard stride fed to the codec in one device call.  4MB x 10 shards =
# 40MB input per batch: large enough to saturate the MXU kernel (tile sweep
# in ops/rs_tpu.py), small enough to double-buffer in HBM comfortably.
DEFAULT_STRIDE = 4 * 1024 * 1024
# In-flight codec batches: the caller may run this far ahead of the codec
# worker before blocking on a resolve.  3 keeps one batch staging, one on
# the wire, one landing, and a successor submitted by the time the worker
# looks for one (DEVICE_DEPTH).  NOTE the overlapped pipeline's peak host
# footprint is 2*prefetch + depth + 2 payloads — the stripe queue, the
# pending deque, the result queue (payloads ride along for the writer),
# and one in each leg's hands — plus, where the codec worker stages
# (encode, verify; not rebuild, whose payload is what it puts), its
# DEVICE_DEPTH staging buffers: 11 buffers in a rebuild (440MB of
# [10, 4MB] batches at the default stride), 13 in an encode or a scrub,
# vs the serial mode's 1 and 2.  POOL keeps as many as the widest run had
# in flight (see BufferPool), so the footprint is the process's from its
# first bulk verb on; size stride/prefetch down together on memory-tight
# volume servers.
PIPELINE_DEPTH = 3
# Batches the codec worker keeps on the device: it enqueues a submitted
# successor before it fetches the oldest, never a third (Codec._advance).
# Two is what the wire can use — one batch's H2D beside the other's
# program and D2H — and what a staged pipeline pays for in staging
# buffers.
DEVICE_DEPTH = 2
# Threads that read one shard-file batch's rows side by side
# (read_shard_rows).  A constant from a measurement, not a knob: alone on
# the chip tool's 13-core host ten 4MB preadv out of the page cache take
# 16.8ms one after another, 9.1 on 2 threads, 5.5 on 4, 4.0-5.4 on 5 and
# 2.8-3.7 on 10 (experiments/host_read_fanout.py, PERF.md PR 31).  5
# splits a rebuild's ten rows evenly and leaves the reader a quarter of
# the codec worker's batch; more would take the cores the worker's
# transfers, the writer leg and the server's loop run on for a
# millisecond that no leg waits for.
READ_THREADS = 5

# test seams / portability: the slow-IO fixtures in tests/test_ec_bulk.py
# wrap these, and platforms without preadv (none we target) fall back to
# per-row pread
_pread = os.pread
_preadv = getattr(os, "preadv", None)


@dataclass
class BulkConfig:
    """Knobs for the staged bulk pipelines (CLI: the -ec.bulk.* flags).

    Process-global like obs.CONFIG — bulk encode/rebuild/verify are
    store-level maintenance verbs, not per-request serving state."""

    # run the reader/writer legs on dedicated threads; False = the
    # serial baseline, every leg on the caller thread
    # (-ec.bulk.overlap.disable)
    overlap: bool = True
    # bounded stripe-queue depth: how many read batches the reader leg
    # may run ahead of the codec (and results ahead of the writer)
    # (-ec.bulk.prefetch)
    prefetch: int = 3
    # per-shard bytes per codec call; 0 = DEFAULT_STRIDE
    # (-ec.bulk.strideMB)
    stride: int = 0

    def validated(self) -> "BulkConfig":
        if self.prefetch < 1:
            raise ValueError("prefetch must be >= 1")
        if self.stride < 0:
            raise ValueError("stride must be >= 0")
        if (
            self.stride
            and self.stride < LARGE_BLOCK_SIZE
            and LARGE_BLOCK_SIZE % self.stride
        ):
            # a non-dividing stride silently falls back to whole-block
            # batches in the encode plan — a [10, 1GB] (~10GB) staging
            # array per batch on volumes with large-block rows.  Fail at
            # flag-parse time instead of OOM mid-encode.
            raise ValueError(
                "stride must divide the 1GB EC large block "
                "(use a power-of-two -ec.bulk.strideMB)"
            )
        return self


DEFAULT = BulkConfig()


def configure(cfg: BulkConfig) -> None:
    """Apply the -ec.bulk.* flags; process-global like stats.REGISTRY."""
    global DEFAULT
    DEFAULT = cfg.validated()


class BufferPool:
    """The batch-sized host buffers of the bulk pipelines, recycled: flat
    uint8 arrays whose pages have been touched, kept between batches and
    between verbs for the life of the process.

    take() never waits: an empty pool, or a kept buffer smaller than the
    batch, is a fresh allocation (the small one is let go, so the pool
    settles on the largest batch the process runs).  What bounds the
    buffers in flight is the pipeline (its queues hold 2*prefetch +
    depth + 2 payloads and DEVICE_DEPTH staging buffers at most); `keep`, which
    run() sets from those same numbers, bounds what stays here
    afterwards.  A buffer that never comes back — a batch dropped on the
    abort path — is the garbage collector's: nothing waits for it.
    deque.pop / append are the only shared steps, one each per leg per
    batch; neither takes a lock of this module's."""

    def __init__(self) -> None:
        self._free: deque = deque()
        self.keep = 0
        self._handed = {
            (pipeline, source): _metrics.VOLUME_SERVER_EC_BULK_BUFFERS.labels(
                pipeline=pipeline, source=source
            )
            for pipeline in _metrics.EC_BULK_PIPELINES
            for source in ("reused", "fresh")
        }

    def take(self, pipeline: str, rows: int, width: int) -> np.ndarray:
        """A C-contiguous [rows, width] uint8 view of a pooled buffer,
        holding whatever its last user left there."""
        need = rows * width
        try:
            flat = self._free.pop()
        except IndexError:
            flat = None
        source = "reused"
        if flat is None or flat.size < need:
            flat = np.empty(need, dtype=np.uint8)
            source = "fresh"
        self._handed[pipeline, source].inc()
        return flat[:need].reshape(rows, width)

    def give(self, batch: np.ndarray) -> None:
        """Return what take() handed out, once nothing reads or writes
        it any more (`batch.base` is the pooled buffer: numpy gives a
        view of a view its owner as base)."""
        if len(self._free) < self.keep:
            self._free.append(batch.base)


POOL = BufferPool()


@dataclass
class _Batch:
    """One submitted batch on its way through the device leg: what
    submit() was given, the handle it returned, and from the enqueue on
    what the fetch needs."""

    shards: np.ndarray
    direct: bool
    handle: Future
    staged: np.ndarray | None = None
    out: object = None
    busy_s: float = 0.0


class Codec:
    """Wraps RSCodec so the matrix-multiply leg can run pipelined.
    submit() returns an opaque handle; resolve() turns it into a numpy
    [m, stride] array.  `busy_s` accumulates the leg's active time — the
    device_busy_s term of the stats contract.

    Device path: one worker thread owns the whole device leg — stage the
    block-diagonal layout, jax.device_put, dispatch the kernel, fetch the
    result — so that the blocking transfers never serialize against the
    caller's file reads/writes, and keeps up to DEVICE_DEPTH batches on
    the device (_advance).  CPU
    backends get the same worker thread when `threaded` (the overlap
    mode): pread/pwrite and the native kernel all release the GIL, so the
    three legs genuinely overlap."""

    def __init__(
        self,
        matrix: np.ndarray,
        backend: str,
        threaded: bool = False,
        workload: str = "bulk",
        pipeline: str = "encode",
    ):
        self.backend = rs.resolve_backend(backend)
        self.matrix = np.asarray(matrix, dtype=np.uint8)
        self.rows = self.matrix.shape[0]
        self.device = self.backend in ("xla", "pallas")
        self.busy_s = 0.0
        # device-ledger class the legs record under: the dedicated leg
        # thread never sees the submitting pipeline's context, so tenancy
        # rides as an attribute (encode="bulk", rebuild="repair",
        # verify="scrub" — encoder.py sets it per pipeline)
        self.workload = workload
        # the device leg's four parts, counted per batch under the
        # pipeline's name (ec_bulk_codec_seconds): staging, enqueue,
        # fetch, unstack
        self.pipeline = pipeline
        self._part_seconds = [
            _metrics.VOLUME_SERVER_EC_BULK_CODEC_SECONDS.labels(
                pipeline=pipeline, part=part
            )
            for part in _metrics.EC_BULK_CODEC_PARTS
        ]
        self._direct_batches = (
            _metrics.VOLUME_SERVER_EC_BULK_DIRECT_BATCHES.labels(
                pipeline=pipeline
            )
        )
        self._pipelined_batches = (
            _metrics.VOLUME_SERVER_EC_BULK_PIPELINED_BATCHES.labels(
                pipeline=pipeline
            )
        )
        self._pool = None
        if self.device:
            from ...ops import rs_tpu

            self._tpu = rs_tpu
            self._a_bm = rs_tpu.prepare_matrix(self.matrix)
            self._a_blk = rs_tpu.prepare_matrix_blockdiag(self.matrix)
            self._interpret = not rs_tpu.on_tpu()
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ec-dev"
            )
            # submit() appends, the worker pops: one _advance a batch
            self._submitted: deque = deque()
            # the worker's own: enqueued and not fetched yet, oldest first
            self._on_device: deque = deque()
        else:
            self._codec = rs.RSCodec(backend=self.backend)
            if threaded:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ec-host"
                )

    def segments(self, b: int) -> int:
        """The row order this codec's program takes a [k, b] batch in:
        1 is plain rows; g > 1 is rs_tpu.stack_segments' order, the same
        bytes as [g*k, b/g] with shard i's segment s at row s*k + i (the
        block-diagonal fast path: the MXU runs with a full M dimension,
        ~152 vs ~123 GB/s, see ops/rs_tpu.py header).  A reader whose
        payload only the codec reads fills it in this order
        (read_shard_rows) and submits it `direct`."""
        if self.backend == "pallas":
            groups = self._tpu.BLOCKDIAG_GROUPS
            if b % (groups * 128) == 0:
                return groups
        return 1

    def submit(self, shards: np.ndarray, direct: bool = False):
        """Queue one [k, b] batch.  `direct` says that `shards` is a
        pooled payload already in segments(b)'s order which nothing else
        reads before resolve() has returned: the device leg puts it as it
        is, where otherwise it lays its own copy out in a staging
        buffer."""
        if self.device:
            batch = _Batch(shards, direct, Future())
            self._submitted.append(batch)
            self._pool.submit(self._advance)
            return batch.handle
        if self._pool is not None:
            return self._pool.submit(self._host_leg, shards)
        return self._host_leg(shards)

    def _host_leg(self, shards: np.ndarray) -> np.ndarray:
        t0 = time.perf_counter()
        with devledger.workload(self.workload, device="host"):
            out = self._codec.apply_matrix(self.matrix, shards)
        dur = time.perf_counter() - t0
        self.busy_s += dur
        devledger.record(
            workload=self.workload, device="host", busy_s=dur,
            dispatches=1, nbytes=int(shards.nbytes) + int(out.nbytes),
        )
        return out

    def _advance(self) -> None:
        """One turn of the device worker, queued once for every submitted
        batch: enqueue that batch, then fetch while DEVICE_DEPTH batches
        are on the device or no successor has been submitted — so a
        successor that is there goes on the wire before the oldest batch
        is waited for, and nothing ever waits for a successor that is
        not (the serial mode, a verb's last batch, a reader that is
        behind).  Handles complete oldest first; a batch whose enqueue or
        fetch raises fails its own handle and the worker goes on, so
        shutdown() leaves nothing on the device."""
        batch = self._submitted.popleft()
        try:
            self._timed(self._enqueue, batch)
            self._on_device.append(batch)
        except BaseException as e:  # noqa: BLE001 — the handle's to raise
            batch.handle.set_exception(e)
        while self._on_device and (
            len(self._on_device) >= DEVICE_DEPTH or not self._submitted
        ):
            batch = self._on_device.popleft()
            if self._on_device:
                self._pipelined_batches.inc()
            try:
                parity = self._timed(self._fetch, batch)
            except BaseException as e:  # noqa: BLE001 — as above
                batch.handle.set_exception(e)
                continue
            devledger.record(
                workload=self.workload, busy_s=batch.busy_s, dispatches=1,
                nbytes=int(batch.shards.nbytes) + int(parity.nbytes),
            )
            batch.handle.set_result(parity)

    def _timed(self, step, batch: _Batch):
        """`busy_s` is the worker's active time: the seconds of its steps
        one after another, a batch's two steps to that batch's ledger
        record, never the lifetimes of two batches that overlap."""
        t0 = time.perf_counter()
        try:
            return step(batch)
        finally:
            dur = time.perf_counter() - t0
            self.busy_s += dur
            batch.busy_s += dur

    def _enqueue(self, batch: _Batch) -> None:
        """The first two of the leg's four parts, _fetch the other two:
        each an event of a profiler capture and a term of
        ec_bulk_codec_seconds, named for what the HOST waits on (the
        device trace has the kernel's own time; nothing here
        synchronises to tell them apart).  `bulk_stage` lays the batch
        out in one flat pooled host buffer (nothing to do for a `direct`
        payload, which arrived laid out), `bulk_enqueue` is device_put
        plus the kernel call (both return before the device is done),
        `bulk_fetch` the blocking copy back — what is left of H2D and
        kernel once the successor, if one was there, is on the wire,
        and the D2H — and `bulk_unstack` the layout undone.  One thread
        runs them, so they are sequential events on its line and sum to
        the leg; both transfers ship FLAT 1-D buffers
        (apply_matrix_device_flat).  Asking for the copy back at
        dispatch (copy_to_host_async) bought nothing on the chip: the
        put's transfer is the wire's floor and the D2H runs beside the
        successor's (PERF.md PR 34)."""
        import jax

        shards, direct = batch.shards, batch.direct
        k, b = shards.shape
        groups = self.segments(b)
        blockdiag = groups > 1
        clock = time.perf_counter
        # the with-block tags the dispatch IN the leg thread — the pool
        # worker never inherits the submitter's ledger context (GL116's
        # lexical-tagging contract anchors here, not in _advance)
        with devledger.workload(self.workload):
            t0 = clock()
            with obs_trace.event("bulk_stage", bytes=int(shards.nbytes)):
                # device_put reads this memory until the transfer is done
                # (the CPU backend may alias it for the life of `x`), so
                # a staging buffer goes back to the pool only in _fetch,
                # after the blocking fetch of the program that consumed
                # `x`, and a direct payload where its pipeline gives it
                # back, after resolve() — so after that same fetch.  The
                # worker stages batch n+1 before it fetches n, so
                # DEVICE_DEPTH staging buffers circulate, by the same
                # take and give.
                if direct:
                    staged = shards
                    self._direct_batches.inc()
                else:
                    staged = POOL.take(self.pipeline, k, b)
                    if blockdiag:
                        self._tpu.stack_segments(shards, out=staged)
                    else:
                        np.copyto(staged, shards)
                batch.staged = staged
            t1 = clock()
            with obs_trace.event("bulk_enqueue"):
                x = jax.device_put(staged.reshape(-1))
                if blockdiag:
                    out = self._tpu.apply_matrix_device_flat(
                        self._a_blk,
                        x,
                        k=groups * k,
                        m=groups * self.rows,
                        tile=self._tpu.BLOCKDIAG_TILE,
                        interpret=self._interpret,
                    )
                else:
                    out = self._tpu.apply_matrix_device_flat(
                        self._a_bm,
                        x,
                        k=k,
                        m=self.rows,
                        kernel=self.backend,
                        interpret=self._interpret,
                    )
                batch.out = out
            t2 = clock()
        self._part_seconds[0].inc(t1 - t0)
        self._part_seconds[1].inc(t2 - t1)

    def _fetch(self, batch: _Batch) -> np.ndarray:
        b = batch.shards.shape[1]
        groups = self.segments(b)
        clock = time.perf_counter
        t2 = clock()
        with obs_trace.event("bulk_fetch"):
            # graftlint: allow(device-sync): the codec worker's own
            # D2H — fetched on the dedicated device leg, timed busy_s
            flat = np.asarray(batch.out)
        if not batch.direct:
            POOL.give(batch.staged)
        t3 = clock()
        with obs_trace.event("bulk_unstack"):
            if groups > 1:
                parity = self._tpu.unstack_segments(
                    flat.reshape(groups * self.rows, b // groups),
                    self.rows,
                )
            else:
                parity = flat.reshape(self.rows, b)
        t4 = clock()
        self._part_seconds[2].inc(t3 - t2)
        self._part_seconds[3].inc(t4 - t3)
        return parity

    def resolve(self, handle) -> np.ndarray:
        if isinstance(handle, Future):
            return handle.result()
        return handle

    def shutdown(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True)


# ----------------------------------------------------------------- reads


def _zero_tail(out: np.ndarray, filled: int) -> None:
    """Zero every byte of a [rows, width] batch past the first `filled`
    (row-major): with _read_row, what keeps a recycled buffer's old
    bytes out of a short batch."""
    rows, width = out.shape
    row, rem = divmod(filled, width)
    if rem:
        out[row, rem:] = 0
        row += 1
    if row < rows:
        out[row:] = 0


def _read_row(fd: int, pieces: list, n: int, off: int) -> None:
    """Fill `pieces` — one row's consecutive parts, in file order: the
    row itself, or its segments where they lie apart — with the file's
    bytes at [off, off+n) and zeros past what was read (EOF, n short of
    the row): one preadv scatters straight into them where the platform
    has it, no intermediate bytes."""
    got, buf = 0, None
    if n > 0 and _preadv is not None:
        iov, left = [], n
        for piece in pieces:
            if left <= 0:
                break
            iov.append(piece[:left])
            left -= len(piece)
        got = _preadv(fd, iov, off)
    elif n > 0:
        buf = np.frombuffer(_pread(fd, n, off), dtype=np.uint8)
        got = len(buf)
    at = 0
    for piece in pieces:
        have = min(max(got - at, 0), len(piece))
        if buf is not None:
            piece[:have] = buf[at:at + have]
        piece[have:] = 0
        at += len(piece)


def read_stripe(
    f, dat_size: int, row_start: int, block_size: int, stride_off: int,
    stride: int, out: np.ndarray | None = None,
) -> np.ndarray:
    """[DATA_SHARDS, stride] batch: shard i's bytes are the original volume
    at row_start + i*block_size + stride_off, zero-padded past EOF
    (encodeDataOneBatch's zero-fill, ec_encoder.go:165-177).  Filled into
    `out` (a pooled buffer: every byte of it is written) when given, else
    into a new array (the ingest plane's reads, which keep theirs).

    Full-block batches (stride == block_size) cover one CONTIGUOUS byte
    range of the .dat — the rows are just a reshape — so a single
    vectored preadv scatters the whole stripe into the row buffers in one
    syscall.  Sub-block batches (stride < block_size) have strided row
    offsets and fall back to one read per row."""
    if out is None:
        out = np.empty((DATA_SHARDS, stride), dtype=np.uint8)
    fd = f.fileno()
    if _preadv is not None and stride == block_size and stride_off == 0:
        want = min(DATA_SHARDS * stride, max(0, dat_size - row_start))
        got = _preadv(fd, list(out), row_start) if want > 0 else 0
        if got >= want:
            _zero_tail(out, got)
            return out
        # short read before the known EOF (signal/odd fs): retake the
        # whole stripe on the per-row path rather than resuming mid-iov
    for i in range(DATA_SHARDS):
        start = row_start + i * block_size + stride_off
        _read_row(fd, [out[i]], min(stride, dat_size - start), start)
    return out


def row_readers() -> ThreadPoolExecutor:
    """The threads one run's read_shard_rows calls share; the run that
    made them shuts them down."""
    return ThreadPoolExecutor(
        max_workers=READ_THREADS, thread_name_prefix="ec-bulk-row"
    )


def read_shard_rows(
    handles: dict, ids, off: int, out: np.ndarray,
    readers: ThreadPoolExecutor, segments: int = 1,
) -> np.ndarray:
    """Fill the [len(ids), n] batch `out` from per-shard FILES
    (rebuild/verify inputs) with shard ids[j]'s bytes at [off, off+n),
    zero-padded on a short read: as row j, or with `segments` g > 1 in
    the order Codec.segments names, `out`'s bytes as [g*k, n/g] with the
    shard's segment s at row s*k + j.  Separate files can't share a
    preadv, but each shard is one (its segments are the iovecs), and the
    calls release the interpreter lock: they run side by side on
    `readers` and all have ended, whatever any of them raised, before
    this returns or raises the first error."""
    k, n = out.shape
    rows = out.reshape(segments, k, n // segments)
    reads = [
        readers.submit(
            _read_row, handles[sid].fileno(), list(rows[:, j]), n, off
        )
        for j, sid in enumerate(ids)
    ]
    wait(reads)
    for read in reads:
        read.result()
    return out


# ---------------------------------------------------------------- writes

# Threads that write one batch's shard rows side by side
# (write_shard_rows).  A constant from a measurement, not a knob: alone on
# a 13-core TPU v5e host fourteen 1MB rows appended to fresh files
# take 6.8-7.5ms one after another through `tobytes`, 5.1 from their own
# buffers, and from their own buffers 3.3-3.5 on 2 threads, 2.6-2.7 on
# 3, 2.2 on 4, 1.9-2.1 on 5, 2.1 on 7 and 2.3 on 14; rebuild's two 4MB
# rows 3.4-3.7 inline through `tobytes`, 1.5-1.6 on 2 threads
# (experiments/host_write_fanout.py, PERF.md).  4 comes within a
# quarter of a millisecond of what more threads give, and leaves the
# cores the codec worker's transfers, the reader leg and the server's
# loop run on.
WRITE_THREADS = 4


def write_or_seek(fobj, row: np.ndarray) -> bool:
    """Sparse-aware shard write: an all-zero chunk becomes a hole (seek)
    instead of written zeros — byte-identical on read (holes read as
    zeros), but a mostly-empty volume encodes/rebuilds without
    materializing terabytes of zero blocks.  Final sizes are fixed by the
    caller's ftruncate.  The row is written from its own memory, no
    intermediate bytes; True where it was written, False for a hole."""
    if row.any():
        fobj.write(np.ascontiguousarray(row))
        return True
    fobj.seek(len(row), os.SEEK_CUR)
    return False


def row_writers() -> ThreadPoolExecutor:
    """The threads one run's write_shard_rows calls share; the run that
    made them shuts them down."""
    return ThreadPoolExecutor(
        max_workers=WRITE_THREADS, thread_name_prefix="ec-bulk-write"
    )


def write_shard_rows(
    pipeline: str, files, rows, writers: ThreadPoolExecutor | None,
) -> None:
    """Append rows[j] to the shard file files[j] at its position, a hole
    where the row is all zero (write_or_seek).  Without `writers` (the
    serial mode) the rows go one after another on the calling thread;
    with them, one task a row, side by side: separate files share
    nothing, and a buffered write of a row this size releases the
    interpreter lock for its whole length.  Every task has ended,
    whatever any of them raised, before this returns or raises the
    first error, so a file takes its rows in plan order as long as the
    caller writes batch after batch, and the buffers the rows lie in may
    be given back once it returns.  A batch of which two or more rows
    were written so counts in ec_bulk_parallel_write_batches."""
    if writers is None:
        for fobj, row in zip(files, rows):
            write_or_seek(fobj, row)
        return
    writes = [
        writers.submit(write_or_seek, fobj, row)
        for fobj, row in zip(files, rows)
    ]
    wait(writes)
    if sum(bool(write.result()) for write in writes) >= 2:
        _metrics.VOLUME_SERVER_EC_BULK_PARALLEL_WRITE_BATCHES.labels(
            pipeline=pipeline
        ).inc()


# -------------------------------------------------------------- executor

_DONE = object()


class _Leg(threading.Thread):
    """One pipeline leg: runs fn to completion, parks any exception for
    the orchestrator to re-raise."""

    def __init__(self, name: str, fn):
        super().__init__(name=name, daemon=True)
        self._fn = fn
        self.error: BaseException | None = None

    def run(self) -> None:  # pragma: no cover - trivial dispatch
        try:
            self._fn()
        except BaseException as e:  # noqa: BLE001 — parked for the caller
            self.error = e


def _put_checked(q: queue.Queue, item, leg: _Leg) -> None:
    """put() that cannot deadlock on a dead consumer: if the consuming
    leg died, raise its error instead of blocking on a full queue."""
    while True:
        if leg.error is not None:
            raise leg.error
        try:
            q.put(item, timeout=0.1)
            return
        except queue.Full:
            continue


def run(
    name: str,
    plan: list,
    read_batch,
    codec: Codec,
    write_batch,
    *,
    overlap: bool | None = None,
    prefetch: int | None = None,
    depth: int = PIPELINE_DEPTH,
    to_codec=None,
    direct: bool = False,
) -> dict:
    """Drive one bulk pipeline over `plan` and return its stats dict.

    `read_batch(desc) -> payload` runs on the reader leg,
    `codec.submit(to_codec(payload))` / `resolve` on the caller thread
    (device/CPU work lands on the codec's own worker), and
    `write_batch(desc, payload, result)` on the writer leg, in plan
    order.  With overlap disabled everything runs inline on the caller
    thread — the serial baseline of the stats contract.

    A payload that read_batch took from POOL is write_batch's to give
    back when it has finished with it; POOL keeps as many buffers as
    this run can have in flight (the PIPELINE_DEPTH note).  `direct`:
    the payload has no reader but the codec and read_batch filled it in
    the order codec.segments names, so the codec is handed it to put as
    it is (Codec.submit) and stages no copy of its own."""
    cfg = DEFAULT
    overlap = cfg.overlap if overlap is None else bool(overlap)
    prefetch = cfg.prefetch if prefetch is None else prefetch
    payloads = 2 * max(1, prefetch) + depth + 2 if overlap else 1
    # the codec worker's staging buffers: one a batch on the device,
    # and serial mode never submits a second before the first resolved
    staging = 0 if direct else (DEVICE_DEPTH if overlap else 1)
    POOL.keep = payloads + staging
    pick = to_codec if to_codec is not None else lambda payload: payload

    def submit(payload):
        return codec.submit(pick(payload), direct)

    t = {
        "read_s": 0.0, "submit_s": 0.0, "wait_s": 0.0, "write_s": 0.0,
        "fsync_s": 0.0, "batches": 0, "overlap": overlap,
    }
    # one run on the profiler's timeline: inside it an idle device is
    # this pipeline's to explain, outside it nothing asked for the device
    with obs_trace.interval("bulk_run", pipeline=name):
        if overlap:
            _run_overlapped(
                name, plan, read_batch, codec, write_batch, submit, prefetch,
                depth, t,
            )
        else:
            _run_serial(plan, read_batch, codec, write_batch, submit, t)
    t["device_busy_s"] = codec.busy_s
    return t


def _run_serial(plan, read_batch, codec, write_batch, submit, t: dict) -> None:
    """Every leg on the caller thread, batch after batch."""
    clock = time.perf_counter
    for desc in plan:
        t0 = clock()
        with obs_trace.event("bulk_read"):
            payload = read_batch(desc)
        t1 = clock()
        handle = submit(payload)
        t2 = clock()
        result = codec.resolve(handle)
        t3 = clock()
        with obs_trace.event("bulk_write"):
            write_batch(desc, payload, result)
        t["read_s"] += t1 - t0
        t["submit_s"] += t2 - t1
        t["wait_s"] += t3 - t2
        t["write_s"] += clock() - t3
        t["batches"] += 1


def _run_overlapped(
    name: str, plan, read_batch, codec, write_batch, submit, prefetch: int,
    depth: int, t: dict,
) -> None:
    """Reader and writer legs on their own threads around the caller's
    submit / resolve loop."""
    clock = time.perf_counter
    read_q: queue.Queue = queue.Queue(maxsize=max(1, prefetch))
    write_q: queue.Queue = queue.Queue(maxsize=max(1, prefetch))
    abort = threading.Event()

    def reader() -> None:
        try:
            for desc in plan:
                if abort.is_set():
                    return
                r0 = clock()
                with obs_trace.event("bulk_read"):
                    payload = read_batch(desc)
                t["read_s"] += clock() - r0
                read_q.put((desc, payload))
        finally:
            read_q.put(_DONE)

    def writer() -> None:
        while True:
            item = write_q.get()
            if item is _DONE:
                return
            desc, payload, result = item
            w0 = clock()
            with obs_trace.event("bulk_write"):
                write_batch(desc, payload, result)
            t["write_s"] += clock() - w0

    r_leg = _Leg(f"ec-bulk-{name}-read", reader)
    w_leg = _Leg(f"ec-bulk-{name}-write", writer)
    r_leg.start()
    w_leg.start()
    pending: deque = deque()

    def flush_one() -> None:
        desc, payload, handle = pending.popleft()
        q0 = clock()
        result = codec.resolve(handle)
        t["wait_s"] += clock() - q0
        _put_checked(write_q, (desc, payload, result), w_leg)

    try:
        while True:
            item = read_q.get()
            if item is _DONE:
                # the reader's finally puts _DONE while its exception is
                # still unwinding toward _Leg.run's handler — join before
                # reading .error or a reader failure could look like a
                # clean (truncated!) end of plan
                r_leg.join()
                if r_leg.error is not None:
                    raise r_leg.error
                break
            desc, payload = item
            s0 = clock()
            handle = submit(payload)
            t["submit_s"] += clock() - s0
            t["batches"] += 1
            pending.append((desc, payload, handle))
            if len(pending) >= depth:
                flush_one()
        while pending:
            flush_one()
        _put_checked(write_q, _DONE, w_leg)
        w_leg.join()
        if w_leg.error is not None:
            raise w_leg.error
    except BaseException:
        # unblock both legs before propagating: the reader may be parked
        # on a full stripe queue, the writer on an empty result queue
        abort.set()
        while True:
            try:
                if read_q.get(timeout=0.05) is _DONE:
                    break
            except queue.Empty:
                if not r_leg.is_alive():
                    break
        while w_leg.is_alive():
            try:
                write_q.put(_DONE, timeout=0.05)
                break
            except queue.Full:
                # aborting anyway: drop a queued result to make room for
                # the sentinel rather than stranding the writer on get()
                try:
                    write_q.get_nowait()
                except queue.Empty:
                    pass
        r_leg.join(timeout=5)
        w_leg.join(timeout=5)
        raise


def publish(name: str, t: dict) -> None:
    """Feed one finished run into the SeaweedFS_volumeServer_ec_bulk_*
    series and the bulk_read/bulk_device/bulk_write trace stages (the
    caller's active trace when the pipeline ran under a traced RPC, e.g.
    VolumeEcShardsGenerate).  Call after wall_s/fsync_s are filled."""
    wall = float(t.get("wall_s", 0.0))
    ctx = obs_trace.current()
    t0 = time.perf_counter() - wall
    # stage names spelled out per leg (not f"bulk_{leg}") so lint can tie
    # each TRACE_STAGES entry to a literal call site (GL117 stage-drift)
    anns = {"pipeline": name, "batches": t.get("batches", 0)}
    for leg, key in (
        ("read", "read_s"), ("device", "device_busy_s"), ("write", "write_s")
    ):
        _metrics.VOLUME_SERVER_EC_BULK_SECONDS.labels(
            pipeline=name, leg=leg
        ).inc(float(t.get(key, 0.0)))
    obs_trace.record_span(
        ctx, "bulk_read", t0, float(t.get("read_s", 0.0)), annotations=anns
    )
    obs_trace.record_span(
        ctx, "bulk_device", t0, float(t.get("device_busy_s", 0.0)),
        annotations=anns,
    )
    obs_trace.record_span(
        ctx, "bulk_write", t0, float(t.get("write_s", 0.0)), annotations=anns
    )
    _metrics.VOLUME_SERVER_EC_BULK_BATCHES.labels(pipeline=name).inc(
        int(t.get("batches", 0))
    )
    # overlap proof as a gauge: leg-active seconds over the wall they ran
    # in (fsync excluded — it follows the last write by definition).
    # >1 = the legs genuinely overlapped, up to 3.0 (three legs)
    window = wall - float(t.get("fsync_s", 0.0))
    if window > 0:
        _metrics.VOLUME_SERVER_EC_BULK_OVERLAP_FRACTION.labels(
            pipeline=name
        ).set(
            (
                float(t.get("read_s", 0.0))
                + float(t.get("write_s", 0.0))
                + float(t.get("device_busy_s", 0.0))
            )
            / window
        )
