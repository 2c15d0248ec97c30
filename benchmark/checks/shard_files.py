"""After the bulk verbs: every shard file of every volume the window
finished, against the plain reference.

For each volume the kept .dat (a hard link made before `ec.encode`
deleted it) is striped and encoded by benchmark/reference/rs_plain.py;
compared with it are the 14 files `ec.encode` wrote (the two that were
then lost under the names they were kept by) and the two files
`ec.rebuild` wrote.  Then a sample of needles drawn from the seed is
read back through the front door out of the rebuilt shard set and
compared with the bytes made from the seed.
"""
from __future__ import annotations

import asyncio
import os
import random
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..cluster import say
from ..dataset import fid_of, needle_bytes
from ..reference import rs_plain

ROWS_PER_TASK = 8


def compare_volume(vol, keep_dir: str, lost: list[int],
                   workers: int) -> tuple[set, set]:
    """-> (names of encode outputs that differ, of rebuild outputs)."""
    parity = rs_plain.coding_matrix()[rs_plain.DATA_SHARDS:]
    shard_size = rs_plain.shard_size_of(vol.dat_size)
    n_rows = shard_size // rs_plain.BLOCK
    encoded = {i: f"{vol.base}.ec{i:02d}" for i in range(14)}
    for sid in lost:
        encoded[sid] = os.path.join(keep_dir, f"{vol.vid}.encoded.ec{sid:02d}")
    rebuilt = {sid: f"{vol.base}.ec{sid:02d}" for sid in lost}
    bad_encode, bad_rebuild = set(), set()
    for paths, bad in ((encoded, bad_encode), (rebuilt, bad_rebuild)):
        for sid, p in paths.items():
            if not os.path.exists(p) or os.path.getsize(p) != shard_size:
                bad.add(sid)

    def compare(first_row: int) -> None:
        n = min(ROWS_PER_TASK, n_rows - first_row)
        with open(vol.kept_dat, "rb") as f:
            f.seek(first_row * rs_plain.DATA_SHARDS * rs_plain.BLOCK)
            raw = f.read(n * rs_plain.DATA_SHARDS * rs_plain.BLOCK)
        want = rs_plain.encode_rows(raw, first_row, n, parity)
        for paths, bad in ((encoded, bad_encode), (rebuilt, bad_rebuild)):
            for sid, p in paths.items():
                if sid in bad:
                    continue
                with open(p, "rb") as f:
                    f.seek(first_row * rs_plain.BLOCK)
                    got = np.frombuffer(
                        f.read(n * rs_plain.BLOCK), dtype=np.uint8)
                if not np.array_equal(got, want[sid]):
                    bad.add(sid)

    with ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(compare, range(0, n_rows, ROWS_PER_TASK)))
    return bad_encode, bad_rebuild


async def sample_gets(ctx, vol, n: int) -> int:
    """-> how many of `n` seeded needles did not read back byte-equal."""
    rng = random.Random(f"{ctx.seed}/readback/{vol.vid}")
    keys = rng.sample(range(1, len(vol.sizes) + 1), min(n, len(vol.sizes)))
    wrong = 0
    for key in keys:
        url = f"http://{ctx.cluster.volume_http}/{fid_of(vol.vid, key)}"
        async with ctx.session.get(url) as r:
            body = await r.read()
            if r.status != 200 or body != needle_bytes(
                    ctx.seed, vol.vid, key, vol.sizes[key - 1]):
                wrong += 1
    return wrong


async def run(ctx, result: dict, readback_per_volume: int = 50) -> dict:
    lost = ctx.config["lost_shards"]
    workers = min(8, os.cpu_count() or 1)
    t0 = time.monotonic()
    bad_encode = bad_rebuild = wrong = compared = 0
    for vol in result["done"]:
        be, br = await asyncio.to_thread(
            compare_volume, vol, ctx.cluster.keep_dir, lost, workers)
        if be or br:
            say(f"volume {vol.vid}: ec.encode outputs that differ from the "
                f"reference: {sorted(be)}; ec.rebuild outputs: {sorted(br)}")
        bad_encode += len(be)
        bad_rebuild += len(br)
        compared += (14 + len(lost)) * rs_plain.shard_size_of(vol.dat_size)
        wrong += await sample_gets(ctx, vol, readback_per_volume)
    say(f"check: {compared} shard bytes of {len(result['done'])} volume(s) "
        f"against the plain reference in {time.monotonic() - t0:.1f} s")
    return {
        "encode_files_differing": (bad_encode, 0),
        "rebuild_files_differing": (bad_rebuild, 0),
        "readback_wrong_bodies": (wrong, 0),
    }
