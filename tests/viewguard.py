"""Runtime view-lifetime sanitizer — the dynamic complement of
graftlint's static GL109 (view-escape) and GL110 (use-after-donate).

The zero-copy serving path (r13) hands memoryviews of needle source
buffers all the way into HTTP body writes, and the staging arenas (r11)
hand numpy views of reused pinned blocks into donated device calls.
Static analysis proves views don't ESCAPE; it cannot prove the bytes a
still-outstanding view reads are the bytes that were exported.  This
harness closes that gap at test time:

  * every zero-copy `Needle.from_bytes(copy=False)` payload view is
    registered with a content fingerprint at export;
  * every `StagingArena.stage_*` view is registered against its
    row-block, and REUSING a block (the next `stage_*` on it) while a
    previous export is still outstanding is a violation — that is
    exactly the aliasing scribble the arena's blocks and the two-slot
    pipeline exist to prevent;
  * a block's export is released (and its bytes verified) when the
    block is given back (`StagingArena.give`: the call staged there has
    its result), and every block's when the `DevicePipeline` slot is
    returned (the device section that used them has completed);
  * `vacuum.commit` triggers an immediate re-verification of every
    outstanding view: a vacuum that mutated bytes under a live zero-copy
    response fails HERE, not as interleaved bytes on a client socket;
  * `release(view)` / watch-exit verify fingerprints: any drift means a
    stale-byte serve and raises ViewGuardViolation.

Usage:

    with viewguard.watch() as g:
        ... exercise zero-copy reads / vacuum / batches ...
    g.assert_clean()        # verifies every outstanding view too

Suite-wide sweep (opt-in, see tests/conftest.py):
    SWFS_VIEWGUARD=1 pytest tests/
"""
from __future__ import annotations

import contextlib
import threading
import zlib
from dataclasses import dataclass, field
from typing import Any, Iterator


class ViewGuardViolation(AssertionError):
    """A view outlived its buffer's reuse, or its bytes drifted."""


def _fingerprint(view: Any) -> int:
    """crc32 of the view's current bytes (cheap at test sizes)."""
    if isinstance(view, memoryview):
        return zlib.crc32(view)
    # numpy view (arena staging) — tobytes() copies, fine for tests
    return zlib.crc32(view.tobytes() if hasattr(view, "tobytes") else bytes(view))


@dataclass
class _Export:
    view: Any          # strong ref: id() stays valid while registered
    source_id: int     # id() of the buffer/arena the view derives from
    label: str
    crc: int


@dataclass
class ViewGuard:
    violations: list = field(default_factory=list)
    exports_total: int = 0
    releases_total: int = 0
    reuse_checks_total: int = 0
    _mu: threading.Lock = field(default_factory=threading.Lock)
    _exports: dict = field(default_factory=dict)  # id(view) -> _Export

    # ------------------------------------------------------- registration

    def export(self, view: Any, source: Any, label: str) -> None:
        with self._mu:
            self.exports_total += 1
            self._exports[id(view)] = _Export(
                view, id(source), label, _fingerprint(view)
            )

    def release(self, view: Any) -> None:
        """Verify-and-drop one export (call when the holder is done
        reading — response fully written, device call returned)."""
        with self._mu:
            exp = self._exports.pop(id(view), None)
        if exp is None:
            return
        self.releases_total += 1
        self._verify(exp)

    def release_source(self, source: Any) -> None:
        """Release every outstanding export derived from `source`."""
        sid = id(source)
        with self._mu:
            mine = [k for k, e in self._exports.items() if e.source_id == sid]
            exps = [self._exports.pop(k) for k in mine]
        for exp in exps:
            self.releases_total += 1
            self._verify(exp)

    # --------------------------------------------------------- enforcement

    def check_reuse(self, source: Any, what: str) -> None:
        """A guarded source is about to be reused/overwritten: any
        outstanding export over it is a use-after-reuse hazard."""
        sid = id(source)
        self.reuse_checks_total += 1
        with self._mu:
            live = [e for e in self._exports.values() if e.source_id == sid]
        for exp in live:
            self._fail(
                f"{what} while view {exp.label!r} is still outstanding — "
                "the holder would read scribbled bytes"
            )

    def check_donation(self, arr: Any, what: str) -> None:
        """An array is being donated to a device call: donating a
        still-outstanding exported view hands its memory to XLA."""
        with self._mu:
            exp = self._exports.get(id(arr))
        if exp is not None:
            self._fail(
                f"{what} donates view {exp.label!r} that is still "
                "outstanding — the kernel may alias its buffer as output"
            )

    def verify_outstanding(self, why: str) -> None:
        """Re-fingerprint every outstanding export (e.g. right after a
        vacuum commit): drift = stale bytes already served."""
        with self._mu:
            live = list(self._exports.values())
        for exp in live:
            self._verify(exp, why=why)

    # ------------------------------------------------------------ verdicts

    def _verify(self, exp: _Export, why: str = "release") -> None:
        try:
            now = _fingerprint(exp.view)
        except ValueError:
            # underlying buffer was resized/closed with the view live:
            # that is its own violation (BufferError normally guards it)
            self._fail(
                f"view {exp.label!r} lost its buffer before {why}"
            )
            return
        if now != exp.crc:
            self._fail(
                f"view {exp.label!r} bytes changed under the holder "
                f"(detected at {why}): exported crc {exp.crc:08x}, now "
                f"{now:08x} — stale/interleaved bytes would have been "
                "served"
            )

    def _fail(self, msg: str) -> None:
        with self._mu:
            self.violations.append(msg)
        raise ViewGuardViolation(msg)

    def assert_clean(self) -> None:
        self.verify_outstanding("watch exit")
        if self.violations:
            raise ViewGuardViolation("; ".join(self.violations))

    @property
    def outstanding(self) -> int:
        with self._mu:
            return len(self._exports)


# the innermost active watch, so a test that DELIBERATELY mutates a
# buffer under a zero-copy view (the CRC-corruption fixtures) can
# release its export first instead of tripping the suite-wide sweep
_ACTIVE: list[ViewGuard] = []


def current() -> ViewGuard | None:
    return _ACTIVE[-1] if _ACTIVE else None


@contextlib.contextmanager
def watch() -> Iterator[ViewGuard]:
    """Instrument the view sources for the duration of the context:

      Needle.from_bytes(copy=False)  -> export payload views
      StagingArena.stage_fused/xla   -> reuse check + export, a block
      StagingArena.give              -> release the block's export
      DevicePipeline.slot            -> auto-release the slot arena's
                                        exports when the slot returns
      vacuum.commit                  -> verify outstanding views after
    """
    from seaweedfs_tpu.ops import rs_ingest, rs_resident
    from seaweedfs_tpu.storage import needle as needle_mod
    from seaweedfs_tpu.storage import vacuum as vacuum_mod

    g = ViewGuard()

    real_from_bytes = needle_mod.Needle.from_bytes.__func__
    real_stage_fused = rs_resident.StagingArena.stage_fused
    real_stage_xla = rs_resident.StagingArena.stage_xla
    real_give = rs_resident.StagingArena.give
    real_slot = rs_resident.DevicePipeline.slot
    real_commit = vacuum_mod.commit
    real_dispatch = rs_resident._dispatch_call
    real_ing_stage = rs_ingest.IngestArena.stage
    real_ing_seal = rs_ingest.IngestArena.seal
    real_ing_reclaim = rs_ingest.IngestArena.reclaim
    real_ing_donatable = rs_ingest._donatable

    # nested watches stack their patches (a test's own watch() inside
    # the SWFS_VIEWGUARD session sweep): only the INNERMOST guard
    # registers, so a scoped test that deliberately scribbles under a
    # view (and verifies the violation itself) cannot leak an
    # already-poisoned export into the outer sweep's ledger
    def _mine() -> bool:
        return bool(_ACTIVE) and _ACTIVE[-1] is g

    def from_bytes(cls, buf, version=needle_mod.CURRENT_VERSION,
                   verify=True, copy=True):
        n = real_from_bytes(cls, buf, version, verify, copy)
        if (
            _mine() and not copy
            and isinstance(n.data, memoryview) and len(n.data)
        ):
            g.export(n.data, buf, f"needle {n.id:x} payload")
        return n

    # an arena's unit of reuse is the row-block: each call of a batch
    # in flight stages into a block of its own, so the guarded source is
    # the block (arena.blocks[b], a kept object), not the arena
    def stage_fused(self, packed, pad, block=0):
        if _mine():
            g.check_reuse(
                self.blocks[block],
                f"StagingArena.stage_fused reuses block {block}",
            )
        view = real_stage_fused(self, packed, pad, block)
        if _mine():
            g.export(
                view, self.blocks[block],
                f"arena fused meta [{len(packed)}+{pad}] block {block}",
            )
        return view

    def stage_xla(self, offsets, rows, deltas, pad, block=0):
        if _mine():
            g.check_reuse(
                self.blocks[block],
                f"StagingArena.stage_xla reuses block {block}",
            )
        view = real_stage_xla(self, offsets, rows, deltas, pad, block)
        if _mine():
            g.export(
                view, self.blocks[block],
                f"arena xla meta [{len(offsets)}+{pad}] block {block}",
            )
        return view

    def give(self, block):
        # the call staged in this block has its result: the export is
        # dead, and its bytes must be what was staged (a sibling call
        # of the same batch that scribbled on them fails HERE)
        g.release_source(self.blocks[block])
        real_give(self, block)

    @contextlib.contextmanager
    def slot(self):
        with real_slot(self) as s:
            try:
                yield s
            finally:
                # the device section holding this slot has returned:
                # its arena exports are dead (verified on the way out)
                for blk in s.arena.blocks:
                    g.release_source(blk)

    def dispatch_call(kind, vec, *args, **kw):
        # donation boundary: the staged vec rides donate_argnums into
        # the kernel.  On a COPYING client (TPU: device_put copies) a
        # live arena export at this position is the designed fast path;
        # on a zero-copy PJRT client (CPU) it would hand the export's
        # actual memory to XLA — exactly the aliasing the arena gating
        # in reconstruct_intervals exists to prevent, enforced here so
        # a gating regression fails the test at the dispatch boundary.
        from seaweedfs_tpu.ops import rs_tpu

        if not rs_tpu.on_tpu():
            g.check_donation(vec, f"_dispatch_call({kind})")
        return real_dispatch(kind, vec, *args, **kw)

    def ing_stage(self, timeout_s=None):
        buf = real_ing_stage(self, timeout_s)
        if _mine():
            # the pool just handed this row out for overwrite: a still-
            # outstanding seal export over it means reclaim was skipped
            g.check_reuse(buf, "IngestArena.stage reuses a staging row")
        return buf

    def ing_seal(self, buf):
        out = real_ing_seal(self, buf)
        if _mine():
            g.export(
                out, out, f"ingest row [{self.k}, {self.block}]"
            )
        return out

    def ing_reclaim(self, buf):
        if _mine():
            # verifies the fingerprint: the encode leg must only READ
            # the sealed row between seal() and here
            g.release_source(buf)
        real_ing_reclaim(self, buf)

    def ing_donatable(rows, on_tpu):
        out = real_ing_donatable(rows, on_tpu)
        if _mine() and out is rows and not on_tpu:
            # the defensive-copy gate was skipped on a zero-copy client:
            # donating the live arena row hands its memory to XLA
            g.check_donation(rows, "rs_ingest._donatable")
        return out

    def commit(v, cpd, cpx, idx_snapshot, shadow_db=None):
        out = real_commit(v, cpd, cpx, idx_snapshot, shadow_db)
        # the .dat was just swapped: every outstanding zero-copy view
        # must still read its exported bytes (old preads are immutable
        # `bytes` over the old inode — this is what PROVES it)
        g.verify_outstanding(f"vacuum commit of volume {v.id}")
        return out

    needle_mod.Needle.from_bytes = classmethod(from_bytes)
    rs_resident.StagingArena.stage_fused = stage_fused
    rs_resident.StagingArena.stage_xla = stage_xla
    rs_resident.StagingArena.give = give
    rs_resident.DevicePipeline.slot = slot
    vacuum_mod.commit = commit
    rs_resident._dispatch_call = dispatch_call
    rs_ingest.IngestArena.stage = ing_stage
    rs_ingest.IngestArena.seal = ing_seal
    rs_ingest.IngestArena.reclaim = ing_reclaim
    rs_ingest._donatable = ing_donatable
    _ACTIVE.append(g)
    try:
        yield g
    finally:
        _ACTIVE.remove(g)
        needle_mod.Needle.from_bytes = classmethod(real_from_bytes)
        rs_resident.StagingArena.stage_fused = real_stage_fused
        rs_resident.StagingArena.stage_xla = real_stage_xla
        rs_resident.StagingArena.give = real_give
        rs_resident.DevicePipeline.slot = real_slot
        vacuum_mod.commit = real_commit
        rs_resident._dispatch_call = real_dispatch
        rs_ingest.IngestArena.stage = real_ing_stage
        rs_ingest.IngestArena.seal = real_ing_seal
        rs_ingest.IngestArena.reclaim = real_ing_reclaim
        rs_ingest._donatable = real_ing_donatable
