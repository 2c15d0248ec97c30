"""EcVolume: serving needles out of mounted `.ecNN` shards.

Reference: /root/reference/weed/storage/erasure_coding/ec_volume.go,
ec_shard.go, ec_volume_delete.go and the volume-server read path
weed/storage/store_ec.go:136-393.  A needle read resolves the sorted `.ecx`
index (on-disk binary search), maps the (offset, size) run to shard
intervals, then serves each interval from a local shard, a caller-supplied
remote reader, or — the degraded path — by fetching the same interval from
>=10 surviving shards and reconstructing the missing bytes with one batched
GF(256) multiply (the reference's per-needle ReconstructData,
store_ec.go:339-393).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional

import numpy as np

from ...obs import trace as obs_trace
from ...ops import rs
from .. import idx as idx_mod
from .. import needle as needle_mod
from .. import types as t
from ..needle import Needle
from ..volume_info import load_volume_info, save_volume_info
from .encoder import ec_base_name
from .layout import (
    DATA_SHARDS,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    TOTAL_SHARDS,
    Interval,
    ShardBits,
    locate_data,
    to_ext,
)


class NeedleNotFound(KeyError):
    pass


class InsufficientShards(RuntimeError):
    pass


def search_sorted_index(fd: int, index_size: int, needle_id: int) -> tuple[int, int, int]:
    """Binary-search a sorted-entry index file -> (entry_offset,
    needle_offset, size); raises NeedleNotFound (SearchNeedleFromSortedIndex
    ec_volume.go:230-255).  The single home of the .ecx entry layout —
    delete, rebuild and lookup all go through here.  Entry width follows
    the process offset mode (16B, or 17B under t.set_offset_size(5))."""
    entry = t.NEEDLE_MAP_ENTRY_SIZE
    lo, hi = 0, index_size // entry
    while lo < hi:
        mid = (lo + hi) // 2
        buf = os.pread(fd, entry, mid * entry)
        key = int.from_bytes(buf[:8], "big")
        if key == needle_id:
            off = t.offset_from_bytes(buf[8 : 8 + t.OFFSET_SIZE])
            size = int.from_bytes(
                buf[8 + t.OFFSET_SIZE : entry], "big", signed=True
            )
            return mid * entry, off, size
        if key < needle_id:
            lo = mid + 1
        else:
            hi = mid
    raise NeedleNotFound(f"needle {needle_id:x} not in sorted index")


def mark_entry_deleted(fd: int, entry_offset: int) -> None:
    """Tombstone an index entry in place: size=-1 written over the size
    field (MarkNeedleDeleted ec_volume_delete.go:13-25)."""
    os.pwrite(
        fd,
        t.TOMBSTONE_FILE_SIZE.to_bytes(4, "big", signed=True),
        entry_offset + 8 + t.OFFSET_SIZE,
    )


def iter_ecj(path: str):
    """Yield journaled needle ids from a .ecj (8B big-endian each)."""
    if not os.path.exists(path):
        return
    with open(path, "rb") as f:
        buf = f.read()
    for i in range(0, len(buf) - len(buf) % 8, 8):
        yield int.from_bytes(buf[i : i + 8], "big")

# shard_id, shard file offset, size -> bytes (or None if unavailable);
# the remote-read hook corresponding to VolumeEcShardRead gRPC
# (store_ec.go:299-337)
RemoteReadFn = Callable[[int, int, int], Optional[bytes]]


# shared fetch pool for the degraded-read survivor gather: sized for a
# few concurrent degraded reads' waves; a per-read pool would spawn ~10
# threads per reconstruct, and thread churn IS tail latency under load
_GATHER_POOL = None
_GATHER_POOL_LOCK = threading.Lock()


# budget for the per-volume reconstructed-interval memo (bytes): sized
# for a hot needle set, far below one shard
RECONSTRUCT_MEMO_BUDGET = 8 << 20
# memo entry lifetime — the corruption-exposure bound.  A reconstruct
# whose gather included a corrupt survivor is wrong with or without the
# memo (the pre-memo code served the same wrong bytes on every read
# until the corrupt copy was dropped); the memo can only EXTEND that
# window, and only by this TTL, because no shard-lifecycle event is a
# reliable invalidation signal: the corrupt copy usually lives on a
# REMOTE peer whose drop this node never observes, and local
# delete_shard fires for content-fine moves too (repair's borrowed
# cleanup and spread-source unmounts — clearing on those measurably
# re-created the repair-window p99 cliff the memo removes)
RECONSTRUCT_MEMO_TTL_S = 15.0


def _gather_pool():
    global _GATHER_POOL
    with _GATHER_POOL_LOCK:
        if _GATHER_POOL is None:
            from concurrent.futures import ThreadPoolExecutor

            _GATHER_POOL = ThreadPoolExecutor(
                max_workers=32, thread_name_prefix="ec-gather"
            )
        return _GATHER_POOL


# chaos-harness hook (loadgen/chaos.py slow_disk): >0 sleeps this long
# before every shard pread, simulating a degraded spindle.  Module-level
# and process-wide — the in-process chaos harness targets reads of a
# specific server's shards by WHAT it reads, not by which server object
# executes the pread.  Never set outside tests/bench.
FAULT_READ_DELAY_S = 0.0


class EcVolumeShard:
    """One mounted .ecNN file (ec_shard.go:17-97)."""

    def __init__(self, dirname: str, vid: int, shard_id: int, collection: str = ""):
        self.dir = dirname
        self.id = vid
        self.shard_id = shard_id
        self.collection = collection
        self.path = ec_base_name(dirname, vid, collection) + to_ext(shard_id)
        self._f = open(self.path, "rb")
        self.size = os.path.getsize(self.path)

    def read_at(self, offset: int, size: int) -> bytes:
        if FAULT_READ_DELAY_S > 0:
            time.sleep(FAULT_READ_DELAY_S)
        return os.pread(self._f.fileno(), size, offset)

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def destroy(self) -> None:
        self.close()
        if os.path.exists(self.path):
            os.remove(self.path)


class EcVolume:
    """Mounted EC volume: `.ecx` + `.ecj` sidecars + any local shards."""

    def __init__(self, dirname: str, vid: int, collection: str = ""):
        self.dir = dirname
        self.id = vid
        self.collection = collection
        self.base_name = ec_base_name(dirname, vid, collection)
        self.ecx_path = self.base_name + ".ecx"
        self.ecj_path = self.base_name + ".ecj"
        self._ecx = open(self.ecx_path, "r+b")
        self.ecx_size = os.path.getsize(self.ecx_path)
        self._ecj = open(self.ecj_path, "ab")
        self._ecj_lock = threading.Lock()
        self.shards: dict[int, EcVolumeShard] = {}
        # the two-tier striping's block sizes, as write_ec_files takes
        # them: the upstream's constants, smaller only in tests
        self.large_block = LARGE_BLOCK_SIZE
        self.small_block = SMALL_BLOCK_SIZE
        info = load_volume_info(self.base_name + ".vif")
        if info:
            self.version = int(info.get("version", needle_mod.CURRENT_VERSION))
        else:
            # no .vif: derive the true version from the .ec00 superblock
            # (block 0 of the stripe is the head of the original .dat) the
            # way ec_decoder.go:120-138 does, then persist it
            try:
                from .decoder import read_ec_volume_version

                self.version = read_ec_volume_version(self.base_name)
            except OSError:
                self.version = needle_mod.CURRENT_VERSION
            save_volume_info(self.base_name + ".vif", {"version": self.version})
        # remote shard locations, refreshed by the store from master lookups
        # (store_ec.go:238-279)
        self.shard_locations: dict[int, list[str]] = {}
        self.shard_locations_refresh = 0.0
        # optional HBM shard cache (ops/rs_resident.py): when set and >=10
        # survivors of this volume are resident, degraded reads reconstruct
        # on-device without per-call H2D of survivor bytes
        self.device_cache = None
        # optional host-RAM warm tier (serving/tiering.HostShardCache):
        # when set and this volume's shard bytes are staged, interval
        # reads serve zero-copy memoryview slices of the staged arrays
        # instead of disk preads — the middle rung of the residency
        # ladder
        self.host_cache = None
        # reconstructed-interval memo: while a shard is missing, the
        # zipf-hot needles hit the SAME (sid, off, size) interval over
        # and over, and every reconstruct pays a >=10-shard survivor
        # gather (remote under spread placement), for the whole repair
        # window (an earlier rig's CPU sweep saw ~3x read p99; no ledger
        # line measures it).  Shard content is immutable once encoded
        # (deletes are .ecj tombstones, never byte rewrites), so ADDING
        # a shard never invalidates the memo — repair re-mounting a
        # shard mid-window must NOT wipe the hot set (the re-gather
        # spike was measurable), and once a shard is back, reads bypass
        # the memo entirely.  What CAN go stale-wrong is an entry whose
        # gather included a corrupt survivor — bounded by the entry TTL
        # (see RECONSTRUCT_MEMO_TTL_S for why time, not lifecycle
        # events, is the invalidation).  The budget keeps it to the hot
        # set.
        self._reconstruct_memo: dict[
            tuple[int, int, int], tuple[bytes, float]
        ] = {}
        self._reconstruct_memo_bytes = 0
        self._reconstruct_memo_lock = threading.Lock()

    # -- shard management ----------------------------------------------------

    def add_shard(self, shard_id: int) -> bool:
        if shard_id in self.shards:
            return False
        self.shards[shard_id] = EcVolumeShard(
            self.dir, self.id, shard_id, self.collection
        )
        return True

    def delete_shard(self, shard_id: int) -> EcVolumeShard | None:
        # only the pinning location's unmount evicts resident bytes: the
        # cache is keyed by (vid, shard), so a second location dropping
        # ITS copy must not wipe the owner's pinned shards
        if (
            self.device_cache is not None
            and self.device_cache.pin_source(self.id) == self.dir
        ):
            self.device_cache.evict(self.id, shard_id)
        return self.shards.pop(shard_id, None)

    def load_shards_to_device(self, cache=None, should_stop=None) -> int:
        """Pin every locally mounted shard of this volume into the device
        cache (the resident-serving setup: done at mount time or on first
        degraded read, so reconstruction gathers from HBM instead of
        re-shipping survivor bytes per call).  Returns shards pinned.
        `should_stop` (callable -> bool) aborts between shards so a
        closing server can join its pin thread promptly."""
        if cache is not None:
            self.device_cache = cache
        if self.device_cache is None:
            raise ValueError("no device cache configured")
        # the cache is keyed by (vid, shard) only, so a vid mounted in
        # two disk locations would interleave both locations' shard sets
        # under one key space: first pinner claims the vid; a different
        # location's copy stays file-backed (its scrub/read verdicts must
        # not be attributed to this location's bytes)
        if self.device_cache.claim_pin_source(self.id, self.dir) != self.dir:
            return 0
        n = 0
        # snapshot: mount RPCs may add shards while a pin thread iterates.
        # Sorted by shard id: puts claim the volume's mesh placement on
        # first touch (rs_resident r19) and budget pressure evicts in
        # LRU(=pin) order, so a deterministic order keeps restarts and
        # the tiering ladder's plan_pin previews reproducible instead
        # of following mount-RPC arrival order
        for sid, shard in sorted(self.shards.items()):
            if should_stop is not None and should_stop():
                break
            if self.device_cache.get(self.id, sid) is None:
                # promotion from the host tier never re-reads disk: the
                # staged bytes ARE the shard file's bytes (staged once
                # at demotion), so the ladder's hot path is RAM -> HBM
                staged = (
                    self.host_cache.shard_array(self.id, sid)
                    if self.host_cache is not None
                    else None
                )
                if staged is not None:
                    self.device_cache.put(self.id, sid, staged)
                else:
                    self.device_cache.put_file(self.id, sid, shard.path)
                n += 1
        self.device_cache.release_staging()
        return n

    def stage_host_shards(self) -> dict[int, np.ndarray]:
        """Read every locally mounted shard's bytes once (demotion-time
        staging for the host-RAM warm tier).  Raises OSError when a
        shard file is unreadable — the caller keeps the volume on its
        current tier rather than staging a partial set silently."""
        return {
            sid: np.fromfile(shard.path, dtype=np.uint8)
            for sid, shard in list(self.shards.items())
        }

    def is_device_resident(self) -> bool:
        """True when enough of THIS location's shards are pinned in HBM
        to reconstruct any missing interval on-device.  Checks the pin
        source — another location's resident copy of the same vid does
        not make this shard set resident, which is what keeps scrub
        verdicts attributed to the bytes actually verified.  (Read
        routing uses Store.ec_volume_is_resident instead, which accepts
        any resident copy: the encoded bytes are identical.)"""
        c = self.device_cache
        return (
            c is not None
            and c.pin_source(self.id) == self.dir
            and c.resident_count(self.id) >= DATA_SHARDS
        )

    def shard_bits(self) -> ShardBits:
        b = ShardBits(0)
        for sid in self.shards:
            b = b.add(sid)
        return b

    @property
    def shard_size(self) -> int:
        for s in self.shards.values():
            return s.size
        return 0

    def dat_size(self) -> int:
        """Original volume size implied by the shard size, the same
        DataShards*ecdFileSize the reference uses for interval math
        (ec_volume.go:218-223)."""
        return DATA_SHARDS * self.shard_size

    # -- .ecx lookup ---------------------------------------------------------

    def _search_ecx(self, needle_id: int) -> tuple[int, int, int]:
        """-> (entry_offset_in_ecx, needle_offset, size)."""
        return search_sorted_index(self._ecx.fileno(), self.ecx_size, needle_id)

    def find_needle(self, needle_id: int) -> tuple[int, int]:
        """-> (volume offset, size); raises NeedleNotFound (incl. deleted)."""
        _, off, size = self._search_ecx(needle_id)
        if not t.size_is_valid(size):
            raise NeedleNotFound(f"needle {needle_id:x} deleted")
        return off, size

    def locate_needle(self, needle_id: int) -> tuple[int, int, list[Interval]]:
        """(offset, size, shard intervals covering the whole record)
        (LocateEcShardNeedle ec_volume.go:206-223)."""
        off, size = self.find_needle(needle_id)
        total = needle_mod.actual_size(size, self.version)
        intervals = locate_data(
            self.dat_size(), off, total, self.large_block, self.small_block
        )
        return off, size, intervals

    def _shard_and_offset(self, interval: Interval) -> tuple[int, int]:
        return interval.to_shard_and_offset(self.large_block, self.small_block)

    @staticmethod
    def _count_row_kinds(large: int, total: int) -> None:
        """`total` located intervals, `large` of them in large-block rows."""
        from ... import stats as swfs_stats

        rows = swfs_stats.VOLUME_SERVER_EC_INTERVAL_ROWS
        if large:
            rows.labels(kind="large").inc(large)
        if total > large:
            rows.labels(kind="small").inc(total - large)

    # -- interval reads (store_ec.go:176-393) --------------------------------

    def read_interval(
        self,
        interval: Interval,
        remote_read: RemoteReadFn | None = None,
        backend: str = "cpu",
        use_device: bool = True,
    ) -> bytes:
        shard_id, off = self._shard_and_offset(interval)
        data = self._read_shard_interval(
            shard_id, off, interval.size, remote_read, backend, use_device
        )
        return data

    def _host_tier_read(self, shard_id: int, off: int, size: int):
        """Zero-copy slice of the host-RAM tier's staged shard bytes, or
        None when the shard is not staged (the single host-tier probe
        every interval-read path shares)."""
        hc = self.host_cache
        if hc is None:
            return None
        return hc.read(self.id, shard_id, off, size)

    def _read_shard_interval(
        self,
        shard_id: int,
        off: int,
        size: int,
        remote_read: RemoteReadFn | None,
        backend: str,
        use_device: bool = True,
    ) -> bytes:
        staged = self._host_tier_read(shard_id, off, size)
        if staged is not None and len(staged) == size:
            with obs_trace.span(
                "shard_read", shard=shard_id, bytes=size, source="host_tier"
            ):
                return staged
        shard = self.shards.get(shard_id)
        if shard is not None:
            with obs_trace.span("shard_read", shard=shard_id, bytes=size):
                return shard.read_at(off, size)
        if remote_read is not None:
            with obs_trace.span(
                "remote_shard_read", shard=shard_id, bytes=size
            ):
                data = remote_read(shard_id, off, size)
            if data is not None:
                return data
        return self._reconstruct_interval(
            shard_id, off, size, remote_read, backend, use_device
        )

    def _reconstruct_interval(
        self,
        missing_shard: int,
        off: int,
        size: int,
        remote_read: RemoteReadFn | None,
        backend: str,
        use_device: bool = True,
    ) -> bytes:
        """Degraded read: gather this interval from >=k other shards and
        recompute the missing rows (recoverOneRemoteEcShardInterval
        store_ec.go:339-393) — a single batched multiply on the selected
        backend rather than a goroutine fan-in.  When the survivors are
        pinned in HBM (device_cache), the gather happens on-device and the
        only per-call transfer is the reconstructed bytes themselves.
        `use_device=False` forces the host reconstruct — the serving
        dispatcher's shed path must not add width-1 device dispatches to
        a device that is already the bottleneck."""
        from ... import stats as swfs_stats

        memo_key = (missing_shard, off, size)
        hit = None
        with self._reconstruct_memo_lock:
            rec = self._reconstruct_memo.get(memo_key)
            if rec is not None:
                data_m, expires = rec
                if time.monotonic() < expires:
                    hit = data_m
                else:
                    self._reconstruct_memo_bytes -= len(data_m)
                    del self._reconstruct_memo[memo_key]
        if hit is not None:
            swfs_stats.VOLUME_SERVER_EC_DEGRADED_MEMO.labels(
                result="hit"
            ).inc()
            return hit
        swfs_stats.VOLUME_SERVER_EC_DEGRADED_MEMO.labels(
            result="miss"
        ).inc()
        if use_device and self.device_cache is not None:
            from ...ops import rs_resident

            try:
                return rs_resident.reconstruct_intervals(
                    self.device_cache, self.id, [(missing_shard, off, size)]
                )[0]
            except rs_resident.CacheMiss:
                # includes ColdShape (a CacheMiss subclass): an AOT-cold
                # device shape sheds here to the host reconstruct below
                # — counted in ..._ec_shed_cold_shape_total and the
                # shed_cold_shape read route — while the background
                # executor compiles it for the next read
                pass
        got: dict[int, np.ndarray] = {}
        n_remote = 0
        n_remote_ok = 0
        with obs_trace.span("shard_read", op="gather_survivors") as gather:
            remote_candidates: list[int] = []
            for sid in range(TOTAL_SHARDS):
                if sid == missing_shard:
                    continue
                shard = self.shards.get(sid)
                # host tier first: a warm volume's survivor gather must
                # not touch disk (the whole point of the middle rung)
                buf = self._host_tier_read(sid, off, size)
                if buf is not None and len(buf) != size:
                    buf = None
                if buf is None:
                    if shard is not None:
                        buf = shard.read_at(off, size)
                    elif remote_read is not None:
                        remote_candidates.append(sid)
                        continue
                if buf is not None and len(buf) == size:
                    got[sid] = np.frombuffer(buf, dtype=np.uint8)
                if len(got) >= DATA_SHARDS:
                    break
            # remote survivors fetch CONCURRENTLY through the hedged
            # gather (utils/faultpolicy.py): the `need` cheapest peers
            # (per-peer latency EWMAs) are asked first, a fetch that
            # exceeds its peer's EWMA-quantile threshold gets a hedge
            # to a spare parity holder (RS(10,4): ANY 10 of 14 shards
            # reconstruct, so a tail-slow peer is routed around, not
            # waited on), failed fetches are replaced from the spares,
            # and the first `need` completions win — all bounded by the
            # hedge token budget and the remaining deadline budget.
            # Each fetch runs under a copy of this worker's contextvars
            # (the r17 fix: the fan-out's VolumeEcShardRead RPCs must
            # carry the trace id so peers' entries correlate).
            if (
                len(got) < DATA_SHARDS
                and remote_candidates
                and remote_read is not None
            ):
                from ...utils import faultpolicy

                res = faultpolicy.hedged_gather(
                    DATA_SHARDS - len(got),
                    remote_candidates,
                    lambda sid: remote_read(sid, off, size),
                    pool=_gather_pool(),
                    validate=lambda b: b is not None and len(b) == size,
                    peer_of=getattr(remote_read, "peer_of", None),
                    pod_of=getattr(remote_read, "pod_of", None),
                    what=f"ec {self.id} survivor gather",
                )
                n_remote = res.sent
                for sid, buf in res.got.items():
                    got[sid] = np.frombuffer(buf, dtype=np.uint8)
                    n_remote_ok += 1
                gather.annotate(
                    hedges=res.hedges_sent, hedge_wins=res.hedge_wins,
                )
            gather.annotate(
                survivors=len(got), remote=n_remote,
                bytes=size * len(got),
            )
        if len(got) < DATA_SHARDS:
            raise InsufficientShards(
                f"ec volume {self.id}: {len(got)} shards reachable, "
                f"{DATA_SHARDS} needed to recover shard {missing_shard}"
            )
        with obs_trace.span(
            "host_reconstruct", backend=backend, bytes=size,
        ):
            codec = rs.RSCodec(backend=backend)
            out = codec.reconstruct(got, wanted=[missing_shard])
            data = out[missing_shard].tobytes()
        if n_remote_ok > 0:
            # memo ONLY results whose gather actually PULLED survivor
            # bytes off a peer: that is the cost the memo amortizes
            # (up to 10 peer round-trips per interval).  A reconstruct
            # from purely local bytes is near-disk speed — failed
            # remote ATTEMPTS at cluster-wide-missing shards don't
            # count — and its byte caching belongs to the residency
            # ladder (HBM/host tiers); memoing it here would shadow
            # the tiering policy's placement decisions.
            self._memo_reconstructed(memo_key, data)
        return data

    def _memo_reconstructed(
        self, key: tuple[int, int, int], data: bytes
    ) -> None:
        with self._reconstruct_memo_lock:
            if key in self._reconstruct_memo:
                return
            self._reconstruct_memo[key] = (
                data, time.monotonic() + RECONSTRUCT_MEMO_TTL_S,
            )
            self._reconstruct_memo_bytes += len(data)
            while (
                self._reconstruct_memo_bytes > RECONSTRUCT_MEMO_BUDGET
                and self._reconstruct_memo
            ):
                # dicts iterate in insertion order: drop the oldest
                old_key = next(iter(self._reconstruct_memo))
                self._reconstruct_memo_bytes -= len(
                    self._reconstruct_memo.pop(old_key)[0]
                )


    def read_needle_bytes(
        self,
        needle_id: int,
        remote_read: RemoteReadFn | None = None,
        backend: str = "cpu",
        use_device: bool = True,
    ) -> bytes:
        # the .ecx binary search is a real disk read serving the request
        with obs_trace.span("shard_read", op="locate"):
            _, _, intervals = self.locate_needle(needle_id)
        self._count_row_kinds(
            sum(iv.is_large_block for iv in intervals), len(intervals)
        )
        parts = [
            self.read_interval(iv, remote_read, backend, use_device)
            for iv in intervals
        ]
        # single-interval needles (the common small-object case) hand
        # their one buffer through untouched so the zero-copy parse can
        # view it instead of re-joining
        return parts[0] if len(parts) == 1 else b"".join(parts)

    def read_needles_batch(
        self,
        needle_ids: list[int],
        remote_read: RemoteReadFn | None = None,
        backend: str = "cpu",
        zero_copy: bool = False,
    ) -> list[Needle | Exception]:
        """Serve a burst of needle reads with all degraded-read
        reconstructions coalesced into (at most one-per-size-bucket)
        resident device calls — the batched counterpart of the reference's
        per-needle goroutine fan-in (store_ec.go:339-393).  Intervals whose
        shard is locally mounted are pread as usual; missing-shard
        intervals are reconstructed together.  Falls back to the per-call
        host path when no device cache is set or it lacks survivors.

        Returns one entry per requested id, in order; a failed needle
        (deleted, not found, corrupt) yields its exception in that slot
        rather than aborting the rest of the burst."""
        plans: list[tuple[int, list] | Exception] = []
        requests: list[tuple[int, int, int]] = []
        n_large = n_located = 0
        # locate = one .ecx binary search (disk preads) per needle: the
        # batch's index-lookup cost, visible as its own trace stage
        with obs_trace.span(
            "shard_read", op="locate", needles=len(needle_ids)
        ):
            for nid in needle_ids:
                try:
                    _, _, intervals = self.locate_needle(nid)
                except (NeedleNotFound, OSError) as e:
                    plans.append(e)
                    continue
                n_located += len(intervals)
                parts: list = []
                for iv in intervals:
                    n_large += iv.is_large_block
                    sid, off = self._shard_and_offset(iv)
                    shard = self.shards.get(sid)
                    if shard is not None:
                        parts.append(("local", sid, off, iv.size))
                    else:
                        parts.append(("recon", len(requests)))
                        requests.append((sid, off, iv.size))
                plans.append((nid, parts))
        self._count_row_kinds(n_large, n_located)

        recon: list[bytes] | None = None
        if requests and self.device_cache is not None:
            from ...ops import rs_resident

            try:
                recon = rs_resident.reconstruct_intervals(
                    self.device_cache, self.id, requests
                )
            except rs_resident.CacheMiss:
                # includes ColdShape: the whole batch's intervals shed
                # to the per-interval host path (recon=None) instead of
                # stalling the dispatcher behind a 20-40s inline compile
                recon = None

        results: list[Needle | Exception] = []
        # every needle of the batch put together from its pieces and
        # parsed (the CRC check is in the parse); local preads are
        # shard_read's, nested inside
        with obs_trace.span("needle_assemble", needles=len(plans)):
            for plan in plans:
                if isinstance(plan, Exception):
                    results.append(plan)
                    continue
                try:
                    results.append(self._assemble_needle(
                        *plan, requests, recon, remote_read, backend,
                        zero_copy,
                    ))
                except Exception as e:  # isolate per-needle failures
                    results.append(e)
        return results

    def _assemble_needle(
        self, nid: int, parts: list, requests: list, recon, remote_read,
        backend: str, zero_copy: bool,
    ) -> Needle:
        """One needle of a batch from its planned pieces: local preads,
        the batch's reconstructed intervals (`recon`), or where there
        are none the per-interval host path."""
        pieces: list = []
        for p in parts:
            if p[0] == "local":
                _, sid, off, size = p
                staged = self._host_tier_read(sid, off, size)
                if staged is not None and len(staged) == size:
                    pieces.append(staged)
                    continue
                with obs_trace.span("shard_read", shard=sid, bytes=size):
                    pieces.append(self.shards[sid].read_at(off, size))
            elif recon is not None:
                pieces.append(recon[p[1]])
            else:
                sid, off, size = requests[p[1]]
                pieces.append(self._read_shard_interval(
                    sid, off, size, remote_read, backend
                ))
        # zero_copy: the parse keeps `data` a memoryview over the single
        # source buffer (or the one join for multi-interval needles)
        # instead of materializing bytes twice — the response writer
        # streams it straight out
        raw = pieces[0] if len(pieces) == 1 else b"".join(pieces)
        n = Needle.from_bytes(raw, self.version, copy=not zero_copy)
        if n.id != nid:
            raise NeedleNotFound(
                f"ec batch read got needle {n.id:x}, expected {nid:x}"
            )
        return n

    def read_needle(
        self,
        needle_id: int,
        cookie: int | None = None,
        remote_read: RemoteReadFn | None = None,
        backend: str = "cpu",
        use_device: bool = True,
        zero_copy: bool = False,
    ) -> Needle:
        """Full needle with CRC verification (ReadEcShardNeedle
        store_ec.go:136-174)."""
        raw = self.read_needle_bytes(needle_id, remote_read, backend, use_device)
        n = Needle.from_bytes(raw, self.version, copy=not zero_copy)
        if n.id != needle_id:
            raise NeedleNotFound(
                f"ec read got needle {n.id:x}, expected {needle_id:x}"
            )
        if cookie is not None and n.cookie != cookie:
            from ..volume import CookieMismatch

            raise CookieMismatch(f"cookie mismatch for needle {needle_id:x}")
        return n

    # -- delete (ec_volume_delete.go) ----------------------------------------

    def delete_needle(self, needle_id: int) -> None:
        """Tombstone the .ecx entry in place + journal the id in .ecj
        (DeleteNeedleFromEcx ec_volume_delete.go:27-49)."""
        try:
            entry_off, _, _ = self._search_ecx(needle_id)
        except NeedleNotFound:
            return
        mark_entry_deleted(self._ecx.fileno(), entry_off)
        with self._ecj_lock:
            self._ecj.write(needle_id.to_bytes(8, "big"))
            self._ecj.flush()

    # -- lifecycle -----------------------------------------------------------

    def file_count(self) -> int:
        return self.ecx_size // t.NEEDLE_MAP_ENTRY_SIZE

    def close(self) -> None:
        for s in self.shards.values():
            s.close()
        if not self._ecx.closed:
            self._ecx.close()
        if not self._ecj.closed:
            self._ecj.close()

    def destroy(self) -> None:
        """Remove sidecars + local shards (ec_volume.go Destroy)."""
        if (
            self.device_cache is not None
            and self.device_cache.pin_source(self.id) == self.dir
        ):
            self.device_cache.evict(self.id)
        self.close()
        for p in [self.ecx_path, self.ecj_path, self.base_name + ".vif"]:
            if os.path.exists(p):
                os.remove(p)
        for s in self.shards.values():
            s.destroy()


def rebuild_ecx_file(base_name: str) -> None:
    """Replay .ecj tombstones into a (rebuilt) .ecx, then drop the journal
    (RebuildEcxFile ec_volume_delete.go:51-98)."""
    ecj_path = base_name + ".ecj"
    if not os.path.exists(ecj_path):
        return
    with open(base_name + ".ecx", "r+b") as ecx:
        size = os.fstat(ecx.fileno()).st_size
        for nid in iter_ecj(ecj_path):
            try:
                entry_off, _, _ = search_sorted_index(ecx.fileno(), size, nid)
            except NeedleNotFound:
                continue
            mark_entry_deleted(ecx.fileno(), entry_off)
    os.remove(ecj_path)
