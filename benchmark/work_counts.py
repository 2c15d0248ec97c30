"""Bytes the ALGORITHM has to move for the window's own requests.

RS(10,4) over GF(256), whatever implements it: these are functions of
what the window asked for (bytes of a lost shard read, bytes of .dat
encoded, bytes of shard rebuilt), never of the shapes a kernel was
compiled for, so padding, fetch rungs and count buckets all show up as a
lower share of the roofline.  GF(256) multiply-add has no published peak
on the chip; the bound is memory traffic over the HBM peak.
"""
from __future__ import annotations

DATA_SHARDS = 10
PARITY_SHARDS = 4
BLOCK = 1 << 20  # the 1 MB stripe row every volume below 10 GB uses


def reconstruct_bytes(lost_bytes: int) -> int:
    """A read of `lost_bytes` bytes of one lost shard: the same columns
    of 10 survivors read, the lost columns written."""
    return DATA_SHARDS * lost_bytes + lost_bytes


def encode_bytes(dat_bytes: int) -> int:
    """Per 10 bytes of .dat, 10 read and 4 parity bytes written."""
    return dat_bytes + dat_bytes * PARITY_SHARDS // DATA_SHARDS


def rebuild_bytes(shard_bytes: int, lost_shards: int) -> int:
    """Per column, 10 survivor bytes read and one written per lost
    shard."""
    return shard_bytes * (DATA_SHARDS + lost_shards)


def bytes_on_shard(offset: int, length: int, shard: int) -> int:
    """How many bytes of the .dat extent [offset, offset+length) lie on
    data shard `shard` under 1 MB striping (block b of the .dat is row
    b // 10 of shard b % 10)."""
    total, pos, end = 0, offset, offset + length
    while pos < end:
        block = pos // BLOCK
        stop = min(end, (block + 1) * BLOCK)
        if block % DATA_SHARDS == shard:
            total += stop - pos
        pos = stop
    return total


def roofline_pct(moved_bytes: int, device_seconds: float,
                 hbm_bytes_per_s: float) -> float | None:
    """The least time the chip could take for `moved_bytes` over the
    device time the programs took, in percent.  Nothing to read (no
    device time, no work) gives None, never 0."""
    if device_seconds <= 0 or moved_bytes <= 0:
        return None
    return 100.0 * (moved_bytes / hbm_bytes_per_s) / device_seconds
