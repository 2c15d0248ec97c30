"""Plain reference for RS(10,4) over GF(256) as SeaweedFS lays it out.

Independent of the program under test: its own field tables, its own
coding matrix (the systematic Vandermonde construction of
klauspost/reedsolomon that the upstream Go code uses: rows r^c, times the
inverse of the top square), its own striping of a .dat into 1 MB rows.
Nothing is imported from seaweedfs_tpu and nothing the program computed
is read here except the bytes it was asked to encode.
"""
from __future__ import annotations

import numpy as np

DATA_SHARDS, TOTAL_SHARDS = 10, 14
BLOCK = 1 << 20
_POLY = 0x11D


def _tables():
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[:255]
    mul = np.zeros((256, 256), dtype=np.uint8)
    a = np.arange(1, 256)
    for c in range(1, 256):
        mul[c, 1:] = exp[log[c] + log[a]]
    return exp, log, mul


EXP, LOG, MUL = _tables()


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(256)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for k, v in enumerate(row):
                acc ^= gf_mul(v, b[k][j])
            out[i][j] = acc
    return out


def mat_inv(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(256)."""
    n = len(m)
    work = [list(row) + [int(i == j) for j in range(n)]
            for i, row in enumerate(m)]
    for col in range(n):
        pivot = next(r for r in range(col, n) if work[r][col])
        work[col], work[pivot] = work[pivot], work[col]
        inv = gf_inv(work[col][col])
        work[col] = [gf_mul(v, inv) for v in work[col]]
        for r in range(n):
            if r != col and work[r][col]:
                f = work[r][col]
                work[r] = [v ^ gf_mul(f, p)
                           for v, p in zip(work[r], work[col])]
    return [row[n:] for row in work]


def coding_matrix() -> list[list[int]]:
    """[14][10]: identity on top, four parity rows below."""
    vm = [[gf_pow(r, c) for c in range(DATA_SHARDS)]
          for r in range(TOTAL_SHARDS)]
    return mat_mul(vm, mat_inv(vm[:DATA_SHARDS]))


def apply_rows(rows: list[list[int]], data: np.ndarray) -> np.ndarray:
    """rows [r][c] times data [c, n] (uint8) -> [r, n], one table pass
    per non-zero coefficient."""
    out = np.zeros((len(rows), data.shape[1]), dtype=np.uint8)
    for i, row in enumerate(rows):
        for c, coef in enumerate(row):
            if coef == 1:
                out[i] ^= data[c]
            elif coef:
                out[i] ^= MUL[coef][data[c]]
    return out


def shard_size_of(dat_size: int) -> int:
    """Every shard's length for a .dat below 10 GB: whole 1 MB rows."""
    return -(-dat_size // (DATA_SHARDS * BLOCK)) * BLOCK


def stripe(dat: bytes | memoryview, first_row: int, n_rows: int) -> np.ndarray:
    """Rows [first_row, first_row+n_rows) of the .dat bytes `dat` (which
    start at row `first_row`), zero padded -> data shards [10, n_rows MiB]."""
    flat = np.zeros(n_rows * DATA_SHARDS * BLOCK, dtype=np.uint8)
    raw = np.frombuffer(dat, dtype=np.uint8)
    flat[: len(raw)] = raw
    return np.ascontiguousarray(
        flat.reshape(n_rows, DATA_SHARDS, BLOCK)
        .transpose(1, 0, 2)
        .reshape(DATA_SHARDS, n_rows * BLOCK)
    )


def encode_rows(dat: bytes | memoryview, first_row: int, n_rows: int,
                parity_rows: list[list[int]]) -> np.ndarray:
    """All 14 shards' bytes for those rows -> [14, n_rows MiB]."""
    data = stripe(dat, first_row, n_rows)
    return np.concatenate([data, apply_rows(parity_rows, data)])
