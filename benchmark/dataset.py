"""The data set, made from --seed: needles of a fixed size mix, loaded
through the volume server's HTTP front door.

needle_bytes / plan_needles / fid_of / the raw-body POST loader are
copied from chip_smoke.py at commit acf9d01; the index reader is the
benchmark's own (16-byte entries: key u64, offset u32 in units of 8
bytes, size i32, all big-endian — the upstream .idx/.ecx format).
"""
from __future__ import annotations

import asyncio

import numpy as np

from .cluster import Cluster, check

COOKIE = 0x5EED5EED
LOAD_CONCURRENCY = 16


def needle_bytes(seed: int, vid: int, key: int, size: int) -> bytes:
    return np.random.Generator(np.random.PCG64([seed, vid, key])).bytes(size)


def plan_needles(target_bytes: int, size_mix: list[int]) -> list[int]:
    """Needle sizes (key i+1 has sizes[i]) cycling through the mix until
    the payload alone reaches the target."""
    sizes, total = [], 0
    while total < target_bytes:
        size = size_mix[len(sizes) % len(size_mix)]
        sizes.append(size)
        total += size
    return sizes


def fid_of(vid: int, key: int) -> str:
    return f"{vid},{key:x}{COOKIE:08x}"


async def load_volume(session, cluster: Cluster, vid: int, seed: int,
                      sizes: list[int]) -> None:
    """POST every needle as a raw body (the volume server's multipart
    parse costs ~10x the append and would turn the load into a
    measurement of email.parser)."""
    next_key = iter(range(1, len(sizes) + 1))
    headers = {"Content-Type": "application/octet-stream"}

    async def worker():
        for key in next_key:
            data = await asyncio.to_thread(
                needle_bytes, seed, vid, key, sizes[key - 1]
            )
            url = f"http://{cluster.volume_http}/{fid_of(vid, key)}"
            async with session.post(url, data=data, headers=headers) as r:
                check(r.status in (200, 201), f"POST {url}: HTTP {r.status} "
                      f"{await r.text()}")

    await asyncio.gather(*(worker() for _ in range(LOAD_CONCURRENCY)))
    cluster.assert_alive()


def read_index(path: str) -> dict[int, tuple[int, int]]:
    """{key: (offset in the .dat, record length)} of the live needles of
    an .idx or .ecx file.  A record's length is the distance to the next
    record (needles are appended back to back), the last one's is taken
    from its size field rounded up to the 8-byte padding."""
    raw = np.fromfile(path, dtype=np.uint8)
    n = len(raw) // 16
    a = raw[: n * 16].reshape(n, 16)
    keys = a[:, :8].copy().view(">u8").reshape(n).astype(np.int64)
    offs = a[:, 8:12].copy().view(">u4").reshape(n).astype(np.int64) * 8
    sizes = a[:, 12:16].copy().view(">i4").reshape(n).astype(np.int64)
    live = sizes > 0
    keys, offs, sizes = keys[live], offs[live], sizes[live]
    order = np.argsort(offs)
    keys, offs, sizes = keys[order], offs[order], sizes[order]
    # header 16 + body + checksum 4 + timestamp 8, padded to 8
    last = (16 + int(sizes[-1]) + 12 + 7) // 8 * 8 if n else 0
    lengths = np.append(np.diff(offs), last)
    return {
        int(k): (int(o), int(ln)) for k, o, ln in zip(keys, offs, lengths)
    }
