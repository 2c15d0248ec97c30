"""Plain reference for the two-tier striping of a sealed volume, as the
upstream encoder writes it (ec_encoder.go:219-230, ec_locate.go:15-52).

While MORE than one row of ten large blocks remains of the .dat, a row of
ten large blocks (1 GB each) is written, block i of the row to shard i;
what is left goes into rows of ten small blocks (1 MB), the last one zero
padded.  Every shard file is therefore `n_large_rows` large blocks
followed by the small rows' blocks, and the four parity shards are the
coding matrix applied column by column, whatever the tier.  A volume
below 10 GB has no large row and this is rs_plain's 1 MB striping.

Independent of the program under test: nothing is imported from
seaweedfs_tpu; the field, the matrix and the row encoder are rs_plain's.
Block sizes are parameters so that tests can run both tiers at small
sizes.
"""
from __future__ import annotations

import numpy as np

from . import rs_plain

DATA_SHARDS, TOTAL_SHARDS = rs_plain.DATA_SHARDS, rs_plain.TOTAL_SHARDS
LARGE_BLOCK = 1 << 30
SMALL_BLOCK = 1 << 20


def n_large_rows(dat_size: int, large: int = LARGE_BLOCK) -> int:
    """Rows of large blocks the encoder writes: one for every whole
    `10 * large` bytes but the last (its loop runs while MORE than a
    large row remains)."""
    return max(0, (dat_size - 1) // (DATA_SHARDS * large))


def shard_size_of(dat_size: int, large: int = LARGE_BLOCK,
                  small: int = SMALL_BLOCK) -> int:
    """Every shard file's length: the large rows, then whole small rows
    for the rest."""
    rows = n_large_rows(dat_size, large)
    rest = dat_size - rows * DATA_SHARDS * large
    return rows * large + -(-rest // (DATA_SHARDS * small)) * small


def locate(dat_size: int, offset: int, length: int,
           large: int = LARGE_BLOCK, small: int = SMALL_BLOCK
           ) -> list[tuple[int, int, int, bool]]:
    """The .dat extent [offset, offset+length) as pieces (shard, offset
    in the shard file, length, lies in a large row), in .dat order."""
    rows = n_large_rows(dat_size, large)
    large_end = rows * DATA_SHARDS * large
    pieces, pos, end = [], offset, offset + length
    while pos < end:
        if pos < large_end:
            block, inner = divmod(pos, large)
            take = min(end, (block + 1) * large) - pos
            at = (block // DATA_SHARDS) * large + inner
        else:
            block, inner = divmod(pos - large_end, small)
            take = min(end - pos, small - inner)
            at = rows * large + (block // DATA_SHARDS) * small + inner
        pieces.append((block % DATA_SHARDS, at, take, pos < large_end))
        pos += take
    return pieces


def bytes_on_shard(dat_size: int, offset: int, length: int, shard: int,
                   large: int = LARGE_BLOCK, small: int = SMALL_BLOCK) -> int:
    """How many bytes of the .dat extent lie on data shard `shard`."""
    return sum(n for s, _, n, _ in locate(dat_size, offset, length,
                                          large, small) if s == shard)


def data_window(read_dat, dat_size: int, start: int, length: int,
                large: int = LARGE_BLOCK, small: int = SMALL_BLOCK
                ) -> np.ndarray:
    """Columns [start, start+length) of the ten data shards -> [10,
    length].  `read_dat(offset, n)` returns up to n bytes of the .dat at
    `offset` (fewer past its end: the tail is zero padded)."""
    rows = n_large_rows(dat_size, large)
    out = np.zeros((DATA_SHARDS, length), dtype=np.uint8)
    pos, end = start, start + length
    while pos < end:
        if pos < rows * large:
            row, inner = divmod(pos, large)
            row_start, block = row * DATA_SHARDS * large, large
        else:
            row, inner = divmod(pos - rows * large, small)
            row_start = (rows * large + row * small) * DATA_SHARDS
            block = small
        take = min(end - pos, block - inner)
        for shard in range(DATA_SHARDS):
            at = row_start + shard * block + inner
            raw = read_dat(at, max(0, min(take, dat_size - at)))
            out[shard, pos - start: pos - start + len(raw)] = (
                np.frombuffer(raw, dtype=np.uint8))
        pos += take
    return out


def encode_window(read_dat, dat_size: int, start: int, length: int,
                  parity_rows: list[list[int]], large: int = LARGE_BLOCK,
                  small: int = SMALL_BLOCK) -> np.ndarray:
    """Columns [start, start+length) of all 14 shard files -> [14,
    length], in either tier or across their boundary."""
    data = data_window(read_dat, dat_size, start, length, large, small)
    return np.concatenate([data, rs_plain.apply_rows(parity_rows, data)])
