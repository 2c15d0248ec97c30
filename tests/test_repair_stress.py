"""Lockwatch + viewguard stress for the repair plane: the executor's
shard lifecycle (unmount/delete -> rebuilt re-mount, what a repair job
does to a holder) racing zero-copy batched reads and tier-style device
evict/re-pin cycles — the exact interleaving a repair creates when it
rebuilds a volume WHILE front-door load reads it.

Invariants under the race (the sanitizers earn their keep on a real
schedule, per ROADMAP item 3):
  * no observed lock acquisition-order cycle across the cache lock /
    pipeline condition / EcVolume shard map (lockwatch);
  * every read that SUCCEEDS is byte-exact against the oracle and its
    exported zero-copy view verifies at release (viewguard); a read
    that loses its shard mid-repair fails a clean CacheMiss /
    KeyError / FileNotFoundError, never stale bytes.

All device work runs on the CPU test mesh (conftest), mirroring
tests/test_lockwatch_stress.py / test_viewguard_stress.py.
"""
import random
import threading
import time

import lockwatch
import viewguard
from seaweedfs_tpu.ops import rs_resident
from seaweedfs_tpu.storage import ec
from seaweedfs_tpu.storage.volume import Volume

VID = 37
MISSING = 4  # destroyed data shard: every read must reconstruct
CYCLED = 12  # parity shard the "repair" thread unmounts/re-mounts


def _make_volume(tmp_path, count=20, seed=19):
    rng = random.Random(seed)
    v = Volume(str(tmp_path), VID)
    blobs = {}
    for i in range(1, count + 1):
        size = rng.choice([120, 1500, 4096, 30_000])
        data = rng.randbytes(size)
        v.write(i, rng.getrandbits(32), data, name=f"f{i}".encode())
        blobs[i] = data
    v.sync()
    return v, blobs


def test_repair_shard_cycle_races_reads_and_tier_swaps(tmp_path):
    v, blobs = _make_volume(tmp_path)
    base = Volume.base_name(v.dir, v.id, v.collection)
    ec.write_ec_files(base, backend="cpu")
    ec.write_sorted_file_from_idx(base)
    v.close()

    errors: list[BaseException] = []
    good_reads = 0
    clean_misses = 0
    repair_cycles = 0
    stop = threading.Event()
    lock = threading.Lock()

    with lockwatch.watch() as w, viewguard.watch() as g:
        ev = ec.EcVolume(str(tmp_path), VID)
        for sid in range(14):
            if sid != MISSING:
                ev.add_shard(sid)
        cache = rs_resident.DeviceShardCache(
            shard_quantum=1 << 20, layout="blockdiag"
        )
        cache.warm_sizes = ()  # CI convention: no AOT grid compile
        ev.load_shards_to_device(cache)
        nids = sorted(blobs)

        def reader(seed: int):
            nonlocal good_reads, clean_misses
            rng = random.Random(seed)
            deadline = time.time() + 20
            mine = 0
            while time.time() < deadline and mine < 8:
                batch = rng.sample(nids, 3)
                try:
                    out = ev.read_needles_batch(
                        batch, backend="cpu", zero_copy=True
                    )
                except (
                    rs_resident.CacheMiss, KeyError, FileNotFoundError
                ):
                    with lock:
                        clean_misses += 1
                    time.sleep(0.01)
                    continue
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                ok = True
                for nid, res in zip(batch, out):
                    if isinstance(
                        res,
                        (rs_resident.CacheMiss, KeyError,
                         FileNotFoundError),
                    ):
                        with lock:
                            clean_misses += 1
                        ok = False
                        continue
                    if isinstance(res, Exception):
                        errors.append(res)
                        return
                    if bytes(res.data) != blobs[nid]:
                        errors.append(
                            AssertionError(f"stale bytes for needle {nid}")
                        )
                        return
                    if isinstance(res.data, memoryview):
                        g.release(res.data)
                if ok:
                    mine += 1
                    with lock:
                        good_reads += 1

        def repairer():
            """The executor's holder-side choreography, in a loop:
            unmount the shard (close its file handle, evict resident
            copy), then 're-mount the rebuilt shard' — the file is the
            rebuilt output in a real repair."""
            nonlocal repair_cycles
            while not stop.is_set():
                try:
                    shard = ev.delete_shard(CYCLED)
                    if shard is not None:
                        shard.close()
                    cache.evict(VID, CYCLED)
                    time.sleep(0.002)
                    ev.add_shard(CYCLED)
                    with open(
                        ev.shards[CYCLED].path, "rb"
                    ) as f:
                        cache.put(
                            VID, CYCLED,
                            memoryview(f.read()),
                        )
                    with lock:
                        repair_cycles += 1
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return

        def tier_swapper():
            """Tier-style pressure: evict + re-pin survivor shards the
            way a demotion/promotion cycle does."""
            i = 0
            sids = [s for s in range(14) if s not in (MISSING, CYCLED)]
            while not stop.is_set():
                sid = sids[i % len(sids)]
                try:
                    with open(ev.shards[sid].path, "rb") as f:
                        cache.put(VID, sid, memoryview(f.read()))
                except KeyError:
                    pass  # shard between unmount and re-mount
                except BaseException as e:  # noqa: BLE001 — collected
                    errors.append(e)
                    return
                i += 1

        threads = [
            threading.Thread(target=reader, args=(1,), name="reader1"),
            threading.Thread(target=reader, args=(2,), name="reader2"),
            threading.Thread(target=repairer, name="repairer"),
            threading.Thread(target=tier_swapper, name="tier"),
        ]
        for t in threads:
            t.start()
        threads[0].join()
        threads[1].join()
        stop.set()
        threads[2].join()
        threads[3].join()
        ev.close()

    assert not errors, errors
    assert good_reads > 0, "no read ever succeeded under the race"
    assert repair_cycles > 0, "the repair cycle never ran"
    assert g.exports_total > 0, "no zero-copy views were ever tracked"
    g.assert_clean()
    w.assert_no_cycles()
