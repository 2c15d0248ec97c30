"""Streaming ingest encode (r20): RS parity for stripe rows as they
complete on the WRITE path.

The bulk executor (storage/ec/bulk.py) encodes a finished `.dat` after
the fact; the ingest plane (seaweedfs_tpu/ingest/) encodes each stripe
row — [k, block] bytes of the still-growing `.dat` — the moment the row
fills.  This module is the device entry for that plane:

  * one jitted GF(2) bitsliced matmul per row, the SAME kernels the read
    path dispatches (rs_tpu.apply_matrix_device), so encode and
    reconstruct can never drift numerically;
  * the r11 AOT warm / shed-cold discipline, shared registry and
    counters with rs_resident: the live write path never inline-compiles
    — a cold row shape raises ColdShape, the row encodes on the host
    codec, and the background executor compiles the shape for the next
    row (`warm()` pre-compiles the volume block sizes at startup);
  * donation flows the OPPOSITE way from reads: the read path donates a
    tiny [N] request vector and keeps survivor shards resident; ingest
    donates the big [k, block] staged data block itself (its bytes are
    already on their way to the shard files — the device copy is
    dead after the multiply).  On a zero-copy PJRT client (CPU) the
    staged arena row is therefore NEVER handed to the donating call —
    `_donatable()` makes the defensive copy, and the viewguard harness
    patches it to enforce the discipline at test time;
  * IngestArena: the bounded pool of staged row buffers whose
    exhaustion IS the write path's backpressure (a writer that cannot
    stage blocks until the codec drains — bounded memory, bounded
    lag, no unbounded queue between the front door and the device).
"""
from __future__ import annotations

import queue
import threading

import time

import numpy as np

from ..obs import devledger
from ..stats import metrics as stats_metrics
from . import rs

DATA_SHARDS = rs.DATA_SHARDS
PARITY_SHARDS = rs.PARITY_SHARDS


class ArenaExhausted(RuntimeError):
    """No staging row freed within the backpressure budget."""


def _donatable(rows: np.ndarray, on_tpu: bool) -> np.ndarray:
    """The array actually handed to the donating device call.  On TPU the
    transfer copies, so donating the staged view is the designed fast
    path; on a zero-copy CPU client donation would hand the live arena
    row's memory to XLA — exactly the aliasing the arena pool exists to
    prevent — so the call gets a fresh copy.  Viewguard patches this
    boundary (tests/viewguard.py) to fail a gating regression at the
    dispatch, not as scribbled shard bytes."""
    if on_tpu:
        return rows
    return np.array(rows)


class IngestArena:
    """Bounded pool of [k, block] staged row buffers for ONE pipeline.

    stage() blocks (up to the backpressure budget) until a row buffer is
    free — that wait propagates through IngestPipeline.feed() to the
    HTTP writer as honest backpressure.  seal() marks a filled row
    immutable-until-reclaim (viewguard export point); reclaim() returns
    the buffer to the pool once its shard rows are on disk (viewguard
    verifies the bytes never drifted in between)."""

    def __init__(self, k: int, block: int, slots: int = 2):
        if slots < 1:
            raise ValueError(f"arena needs >= 1 slot, got {slots}")
        self.k = k
        self.block = block
        self.slots = slots
        self.waits = 0  # stage() calls that had to block
        self._free: queue.Queue = queue.Queue()
        for _ in range(slots):
            self._free.put(np.empty((k, block), dtype=np.uint8))

    def stage(self, timeout_s: float | None = None) -> np.ndarray:
        try:
            return self._free.get_nowait()
        except queue.Empty:
            pass
        self.waits += 1
        stats_metrics.VOLUME_SERVER_INGEST_BACKPRESSURE.inc()
        try:
            return self._free.get(timeout=timeout_s)
        except queue.Empty:
            raise ArenaExhausted(
                f"no ingest arena row freed in {timeout_s}s "
                f"({self.slots} slots of [{self.k}, {self.block}])"
            ) from None

    def seal(self, buf: np.ndarray) -> np.ndarray:
        """The row is full: its bytes are final until reclaim()."""
        return buf

    def reclaim(self, buf: np.ndarray) -> None:
        self._free.put(buf)

    @property
    def free_slots(self) -> int:
        return self._free.qsize()


class StreamEncoder:
    """RS(k, p) parity for one staged row, device-first with AOT
    shed-cold, host codec fallback.  Thread-safe: the per-volume
    pipeline workers share one encoder (one prepared matrix, one AOT
    registry entry per block size)."""

    def __init__(
        self,
        backend: str = "auto",
        shed_cold: bool = True,
        interpret: bool | None = None,
    ):
        self.backend = rs.resolve_backend(backend)
        self.device = self.backend in ("xla", "pallas")
        self.shed_cold = bool(shed_cold)
        self.k = DATA_SHARDS
        self.p = PARITY_SHARDS
        # host fallback/oracle: native kernel when built, numpy otherwise
        self._host = rs.RSCodec(backend="cpu")
        self.host_rows = 0  # rows encoded on the host (shed or CPU backend)
        self.device_rows = 0
        self._mu = threading.Lock()
        if self.device:
            from . import rs_tpu

            self._tpu = rs_tpu
            self.interpret = (
                (not rs_tpu.on_tpu()) if interpret is None else bool(interpret)
            )
            self._a_prep = rs_tpu.prepare_matrix(self._host.matrix[self.k :])
            self._a_shape = tuple(self._a_prep.shape)

    # ------------------------------------------------------------- AOT grid

    def _key(self, block: int) -> tuple:
        """Streaming-encode twin of rs_resident._call_key: one entry in
        the SAME registry/miss-counter/shed namespace (the leading
        "ingest_encode" family tag keeps it disjoint from every
        reconstruct key)."""
        return (
            "ingest_encode", self.backend, self._a_shape, self.k,
            int(block), bool(self.interpret),
        )

    def _compile_key(self, key: tuple) -> None:
        """Lower + compile one row shape (runs on the shared AOT
        executor, so ingest compiles queue behind/ahead of serving warms
        in one global submission order)."""
        import jax

        from . import rs_resident

        _, kernel, a_shape, k, block, interpret = key
        a_aval = jax.ShapeDtypeStruct(a_shape, np.int8)
        x_aval = jax.ShapeDtypeStruct((k, block), np.uint8)
        with rs_resident._quiet_donation():
            exe = _encode_entry().lower(
                a_aval, x_aval, kernel=kernel, interpret=interpret, k_true=k
            ).compile()
        rs_resident._register_compiled(key, exe)

    def warm(self, blocks, wait: bool = False) -> list:
        """Pre-compile the streaming-encode executable for each row
        width a volume can stage (the small/large block sizes), exactly
        like rs_resident.warm parks the serving ladder: first write
        traffic hits a parked executable or sheds cleanly — never an
        inline compile on the live path."""
        if not self.device:
            return []
        from . import rs_resident

        jobs = []
        with rs_resident._shapes_lock:
            for block in blocks:
                key = self._key(block)
                if (
                    key in rs_resident._aot_executables
                    or key in rs_resident._aot_pending
                    or key in rs_resident._dispatched_shapes
                    or key in rs_resident._aot_failed
                ):
                    continue
                rs_resident._aot_pending.add(key)
                jobs.append(key)
        ex = rs_resident._aot_executor()
        futs = [ex.submit(self._compile_logged, key) for key in jobs]
        if wait:
            import concurrent.futures

            concurrent.futures.wait(futs)
        return futs

    def _compile_logged(self, key: tuple) -> None:
        from . import rs_resident

        # explicit warmup attribution: the shared compile executor's
        # thread has no tagging context (see rs_resident._compile_shape_logged)
        t0 = time.perf_counter()
        try:
            with devledger.workload("warmup"):
                self._compile_key(key)
            devledger.record(
                workload="warmup",
                busy_s=time.perf_counter() - t0, dispatches=1,
            )
        except Exception as e:  # noqa: BLE001 — a failed ingest AOT
            # compile must not kill the shared executor; the shape keeps
            # encoding on the host codec, which serves it fine
            import logging

            logging.getLogger(__name__).exception(
                "ingest AOT compile failed for %s", key
            )
            rs_resident.note_device_failure("aot", f"shape {key}: {e!r}")
            with rs_resident._shapes_lock:
                rs_resident._aot_pending.discard(key)
                rs_resident._aot_failed.add(key)

    def shape_is_warm(self, block: int) -> bool:
        if not self.device:
            return True  # host codec: nothing to compile
        from . import rs_resident

        return rs_resident._shape_is_warm(self._key(block))

    # ------------------------------------------------------------- encoding

    def encode(self, rows: np.ndarray) -> np.ndarray:
        """rows [k, B] u8 -> parity [p, B] u8.  Device path: AOT
        executable when parked, shed-cold otherwise (the CALLER encodes
        the shed row via encode_host — raising keeps the shed explicit
        in the pipeline's stats)."""
        from . import rs_resident

        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        if not self.device:
            return self.encode_host(rows)
        key = self._key(rows.shape[1])
        if self.shed_cold and not rs_resident._shape_is_warm(key):
            self.warm((rows.shape[1],))  # arm the background compile
            raise rs_resident.ColdShape(
                f"ingest encode shape [{self.k}, {rows.shape[1]}] is cold"
            )
        rs_resident._note_shape(key)
        x = _donatable(rows, self._tpu.on_tpu())
        exe = rs_resident._aot_executables.get(key)
        # pipeline workers call encode() directly from their own threads,
        # so the ingest class is pinned here rather than inherited; the
        # busy window covers dispatch through the D2H np.asarray fetch —
        # the row's whole device occupancy
        t0 = time.perf_counter()
        with devledger.workload("ingest"), rs_resident._quiet_donation():
            if exe is not None:
                out = exe(self._a_prep, x)
            else:
                out = _encode_entry()(
                    self._a_prep, x, kernel=self.backend,
                    interpret=self.interpret, k_true=self.k,
                )
            parity = np.asarray(out)[: self.p]
        devledger.record(
            workload="ingest",
            busy_s=time.perf_counter() - t0, dispatches=1,
            nbytes=int(x.nbytes) + int(parity.nbytes),
        )
        with self._mu:
            self.device_rows += 1
        return parity

    def encode_host(self, rows: np.ndarray) -> np.ndarray:
        with self._mu:
            self.host_rows += 1
        return self._host.encode(rows)


def _encode_rows_impl(a_bm, x, kernel="xla", interpret=False, k_true=None):
    from . import rs_tpu

    return rs_tpu.apply_matrix_device(
        a_bm, x, kernel=kernel, interpret=interpret, k_true=k_true
    )


_ENCODE_JIT = None
_ENCODE_JIT_LOCK = threading.Lock()


def _encode_entry():
    """The jitted streaming-encode entry, built on first use: donate the
    staged data block (the big H2D buffer — dead after the multiply,
    unlike the read path where the survivors stay resident and only the
    request vec donates).  Both the live dispatch and the AOT
    lower().compile() go through this ONE jit wrapper so a warmed
    executable and an inline trace can never diverge."""
    global _ENCODE_JIT
    if _ENCODE_JIT is None:
        with _ENCODE_JIT_LOCK:
            if _ENCODE_JIT is None:
                import jax

                _ENCODE_JIT = jax.jit(
                    _encode_rows_impl,
                    static_argnames=("kernel", "interpret", "k_true"),
                    donate_argnums=(1,),
                )
    return _ENCODE_JIT
