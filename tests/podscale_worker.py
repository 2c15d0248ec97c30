"""One member of a jax.distributed pod, run as a process of its own.

`tests/test_podscale.py` starts two of these (four forced CPU devices
each): a process that has touched JAX cannot join a second mesh, so the
worker is a script and not a function of the test.  It joins the mesh,
stages a seeded working set in SPMD lockstep, byte-verifies every lane
this process owns and prints ONE JSON line; with `hold` it then keeps its
lanes until the test kills it.
"""
import json
import os
import sys
import time

import numpy as np

POD_DROP = 3  # the "lost" shard every degraded read rebuilds
POD_LANES = 8  # full-pod lane count the per-chip budget assumes


def pod_volumes(n_volumes: int, shard_bytes: int, seed: int) -> dict:
    """vid -> encoded shard list, a pure function of the seed: every pod
    member stages identical bytes in identical order (SPMD lockstep)."""
    from seaweedfs_tpu.ops import rs

    rng = np.random.default_rng(seed)
    return {
        vid: rs.RSCodec(backend="numpy").encode_all(
            rng.integers(0, 256, size=(10, shard_bytes), dtype=np.uint8)
        )
        for vid in range(1, n_volumes + 1)
    }


def pod_stage(cache, volumes, n_staged: int):
    """Stage every volume's survivor shards (all but POD_DROP) in
    deterministic lockstep order under a per-chip budget sized so the
    FULL 8-lane pod holds EXACTLY the working set: per-chip capacity is
    a constant of the deployment, so pod capacity = per_chip x lanes
    scales with process count."""
    from seaweedfs_tpu.ops import rs_resident

    some_vid = next(iter(volumes))
    pad = cache._padded_len(int(volumes[some_vid][0].size))
    per_chip = -(-(len(volumes) * n_staged * pad) // POD_LANES)
    cache.budget = per_chip * cache.n_devices
    for vid in sorted(volumes):
        for sid in range(rs_resident.TOTAL_SHARDS):
            if sid != POD_DROP:
                cache.put(vid, sid, volumes[vid][sid].tobytes())
    return pad


def pod_worker(cfg: dict) -> None:
    """One pod member.  Joins the jax.distributed mesh
    (process_count=1 skips the join and degrades to the local mesh),
    stages the working set, byte-verifies its own lanes, prints ONE
    JSON line, then (cfg["hold"]) parks until the test kills it."""
    from seaweedfs_tpu.ops import rs_resident
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    mesh_mod.initialize_distributed(
        cfg["coordinator"], cfg["process_id"], cfg["process_count"]
    )
    shard_bytes = int(cfg["shard_kb"]) * 1024
    volumes = pod_volumes(
        int(cfg["n_volumes"]), shard_bytes, int(cfg["seed"])
    )
    cache = rs_resident.DeviceShardCache(
        shard_quantum=1 << 18,
        mesh_devices=0,
        mesh_min_shard_bytes=0,
        global_mesh=True,
    )
    cache.warm_sizes = ()  # the CI convention: no AOT warm plan
    n_staged = rs_resident.TOTAL_SHARDS - 1
    pad = pod_stage(cache, volumes, n_staged)
    # lane byte-verify: rebuild the owner-major permuted buffer the put
    # path shipped and compare every lane THIS process owns (its
    # addressable shards) slice-for-slice.  sh.index[0] is the lane's
    # slice of the GLOBAL buffer, so the check proves both bytes and
    # placement (each host holding exactly its interleaved stripes).
    lanes_checked = 0
    lane_mismatches = 0
    s_n = pad // cache.stripe
    perm = (
        np.arange(s_n)
        .reshape(s_n // cache.n_devices, cache.n_devices)
        .T.ravel()
    )
    for vid in sorted(volumes):
        if cache.resident_count(vid) != n_staged:
            continue  # W=1 sheds most volumes; verify what's resident
        for sid in (0, rs_resident.TOTAL_SHARDS - 1):
            arr = cache.get(vid, sid)
            if arr is None:
                continue
            padded = np.zeros(pad, dtype=np.uint8)
            padded[:shard_bytes] = volumes[vid][sid]
            exp = padded.reshape(s_n, cache.stripe)[perm].reshape(-1)
            for sh in arr.addressable_shards:
                lo = sh.index[0].start or 0
                piece = np.asarray(sh.data)
                lanes_checked += 1
                if not np.array_equal(piece, exp[lo : lo + piece.size]):
                    lane_mismatches += 1
    resident = sum(
        1 for vid in volumes if cache.resident_count(vid) == n_staged
    )
    print(
        json.dumps({
            "rank": int(cfg["process_id"]),
            "n_devices": int(cache.n_devices),
            "n_hosts": int(cache.n_hosts),
            "multiprocess": bool(cache.multiprocess),
            "local_lanes": list(cache._local_dev_indices),
            "resident_volumes": int(resident),
            "evictions": int(cache.evictions),
            "all_mesh_placed": all(
                cache.placement(vid) == "mesh"
                for vid in volumes
                if cache.resident_count(vid)
            ),
            "lanes_checked": int(lanes_checked),
            "lane_mismatches": int(lane_mismatches),
        }),
        flush=True,
    )
    if cfg["hold"]:
        deadline = time.time() + 180
        while time.time() < deadline:
            time.sleep(0.2)


if __name__ == "__main__":
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    )
    pod_worker(json.loads(sys.argv[1]))
