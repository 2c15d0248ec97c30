"""Mesh-sharded GF(256) linear algebra: encode/rebuild over many chips.

Two parallel axes (SURVEY.md §2.10 mapping):

  "shard" — the RS shard dimension (the reference's 10-way striping over
            volume servers becomes a sharded array axis).  The bitsliced
            matmul out = (A @ bits(x)) mod 2 decomposes over column groups:
            each device computes partial int32 bit-counts from its local
            shard rows, one `psum` over the shard axis sums counts
            (exact: counts <= 8k per output bit), mod-2 recovers the XOR.
            This turns the reference's per-shard gRPC interval streams
            (store_ec.go:299-337) into a single ICI collective.

  "batch" — the stripe/byte dimension, embarrassingly parallel (pure data
            parallelism; no collective).

Both compose in one mesh: a (S, D) mesh reconstructs S-sharded inputs in
D-way data parallel with one psum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf256
from ..ops.rs_tpu import _pack_bits_bitmajor, _unpack_bits_bitmajor

# mesh construction lives in parallel/mesh.py (ONE home for axis names
# and device ordering, shared with the r19 sharded serving layout);
# re-exported here because every bulk call site imports it from this
# module
from .mesh import make_mesh  # noqa: F401  (re-export)


def split_matrix_bitmajor(m_gf: np.ndarray, n_groups: int) -> jax.Array:
    """GF(256) matrix [m, k] -> per-group bit-major GF(2) blocks
    [n_groups, 8m, 8*(k/n_groups)] int8, group g covering input shards
    [g*k/n, (g+1)*k/n).  Each device's block is bit-major over its LOCAL
    k so the kernel's unpack/pack layout is unchanged."""
    m_gf = np.asarray(m_gf, dtype=np.uint8)
    m, k = m_gf.shape
    if k % n_groups:
        raise ValueError(f"k={k} not divisible by {n_groups} shard groups")
    k_loc = k // n_groups
    a_std = gf256.expand_to_gf2(m_gf)  # [8m, 8k], row p*8+i, col d*8+j
    # -> [8m(bit-major rows), bit j, d]
    a = a_std.reshape(m, 8, k, 8)  # [p, i, d, j]
    a_bm_rows = a.transpose(1, 0, 3, 2).reshape(8 * m, 8, k)  # [row, j, d]
    groups = []
    for g in range(n_groups):
        blk = a_bm_rows[:, :, g * k_loc : (g + 1) * k_loc]  # [8m, 8, k_loc]
        groups.append(blk.reshape(8 * m, 8 * k_loc))
    return jnp.asarray(np.stack(groups), dtype=jnp.int8)


@functools.partial(jax.jit, static_argnames=("mesh", "m_rows"))
def _distributed_apply(mesh: Mesh, a_groups: jax.Array, x: jax.Array, m_rows: int):
    """a_groups [S, 8m, 8k_loc] sharded on S; x [k, B] sharded (shard,
    batch); -> [m, B] u8 sharded on batch."""

    def kernel(a_loc, x_loc):
        bits = _unpack_bits_bitmajor(x_loc)  # [8k_loc, B_loc]
        partial = jnp.dot(
            a_loc[0], bits, preferred_element_type=jnp.int32
        )  # [8m, B_loc]
        # mod-2 BEFORE the collective: (Σ cᵢ) mod 2 == (Σ (cᵢ mod 2)) mod 2,
        # so psum'ing the int8 bit-planes is exact (sums ≤ n_shard < 128)
        # and moves 4x fewer bytes over ICI than the raw int32 counts
        pbits = (partial & 1).astype(jnp.int8)
        counts = jax.lax.psum(pbits, axis_name="shard")
        return _pack_bits_bitmajor(counts, m_rows)  # [m, B_loc]

    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", "batch")),
        out_specs=P(None, "batch"),
    )(a_groups, x)


def distributed_apply_matrix(
    mesh: Mesh, m_gf: np.ndarray, shards, pad_rows_to: int = 4
) -> jax.Array:
    """out[i] = XOR_j m_gf[i,j] ⊗ shards[j], computed over the mesh.

    `shards` is [k, B] uint8 (host or device); k must divide over the
    mesh's shard axis and B over its batch axis.  Output rows are padded
    to a sublane-friendly multiple and sliced back."""
    m_gf = np.asarray(m_gf, dtype=np.uint8)
    rows, k = m_gf.shape
    pad = (-rows) % pad_rows_to
    if pad:
        m_gf = np.concatenate([m_gf, np.zeros((pad, k), dtype=np.uint8)])
    n_shard = mesh.shape["shard"]
    a_groups = jax.device_put(
        split_matrix_bitmajor(m_gf, n_shard),
        NamedSharding(mesh, P("shard", None, None)),
    )
    x = jax.device_put(
        jnp.asarray(shards, dtype=jnp.uint8),
        NamedSharding(mesh, P("shard", "batch")),
    )
    out = _distributed_apply(mesh, a_groups, x, rows + pad)
    return out[:rows]


def shard_parallel_apply(
    mesh: Mesh, m_gf: np.ndarray, shards
) -> np.ndarray:
    """Host-convenience wrapper returning numpy."""
    return np.asarray(distributed_apply_matrix(mesh, m_gf, shards))


def distributed_encode_blockdiag(
    mesh: Mesh, parity_m: np.ndarray, shards, groups: int = 4
) -> jax.Array:
    """Block-diagonal bulk encode over the mesh: the same g-group packing
    the single-chip fast path ships (ops/rs_tpu.py header — fills the
    MXU's M dimension, ~152 vs ~123 GB/s) expressed as one block-diagonal
    GF system and run through the generic sharded apply.  Any column
    partition of a GF matrix is valid for the shard axis, so the
    block-diagonal system needs no special shard_map treatment — the host
    stages the segment-stacked layout exactly as the single-chip path
    does."""
    from ..ops import rs_tpu

    parity_m = np.asarray(parity_m, dtype=np.uint8)
    rows = parity_m.shape[0]
    shards = np.asarray(shards, dtype=np.uint8)
    blk = rs_tpu.blockdiag_system(parity_m, groups)
    stacked = rs_tpu.stack_segments(shards, groups)  # [g*k, B/g]
    out = np.asarray(distributed_apply_matrix(mesh, blk, stacked))
    return rs_tpu.unstack_segments(out, rows, groups)


def distributed_degraded_read(
    mesh: Mesh,
    survivors: np.ndarray,  # [k, L] survivor shard bytes (k = data_shards)
    survivor_ids: list[int],
    wanted: int,  # shard id to reconstruct
    requests: list[tuple[int, int]],  # (offset, size) within the shard
    data_shards: int = 10,
    total_shards: int = 14,
) -> list[bytes]:
    """Batched degraded read over the mesh: every requested interval's
    survivor slices batch along the byte axis into ONE sharded apply (the
    pod-scale analogue of ops/rs_resident.py's serving path; replaces the
    reference's per-needle goroutine fan-in, store_ec.go:339-393)."""
    from ..ops import gf256

    rmat, use = gf256.reconstruction_matrix(
        data_shards, total_shards, survivor_ids, [wanted]
    )
    order = [survivor_ids.index(s) for s in use]
    n_batch = mesh.shape["batch"]
    tile = 128 * n_batch
    # variable-width concatenation: each request contributes only its own
    # tile-rounded span (padding every request to the burst's largest span
    # would stage/transfer mostly zeros for mixed-size bursts)
    spans = []
    col = 0
    for off, size in requests:
        lo = off - off % 128
        span = -(-(off + size - lo) // tile) * tile
        spans.append((lo, span, col))
        col += span
    x = np.zeros((len(use), col), dtype=np.uint8)
    for lo, span, c in spans:
        seg = survivors[order, lo : lo + span]
        x[:, c : c + seg.shape[1]] = seg
    out = np.asarray(distributed_apply_matrix(mesh, rmat, x))
    return [
        out[0, c + (off - lo) : c + (off - lo) + size].tobytes()
        for (off, size), (lo, _, c) in zip(requests, spans)
    ]


# ---- multi-process host staging (BASELINE config 5 / SURVEY §2.10) ---------


def staged_apply_matrix(
    mesh: Mesh,
    m_gf: np.ndarray,
    local_x: np.ndarray,
    global_b: int,
    pad_rows_to: int = 4,
):
    """Multi-process variant of distributed_apply_matrix: each PROCESS
    contributes only the input slice its own host read from its own disks
    (`jax.make_array_from_process_local_data`), the global mesh assembles
    the [k, B] logical array across hosts, and the same shard_map step
    runs with its psum riding ICI/DCN.  This is the pod-scale rebuild
    staging story: volume-server hosts feed local shard bytes straight
    into the sharded step with no central gather.

    `local_x` is this process's [k_local, b_local] portion per the
    (shard, batch) sharding; returns the [m, B] output assembled from
    THIS process's addressable output shards (replicated over the shard
    axis, so every process can reassemble the full result)."""
    m_gf = np.asarray(m_gf, dtype=np.uint8)
    rows, k = m_gf.shape
    pad = (-rows) % pad_rows_to
    if pad:
        m_gf = np.concatenate([m_gf, np.zeros((pad, k), dtype=np.uint8)])
    n_shard = mesh.shape["shard"]
    a_all = np.asarray(split_matrix_bitmajor(m_gf, n_shard))
    a_groups = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("shard", None, None)),
        a_all[_local_shard_rows(mesh)],
        a_all.shape,
    )
    x = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("shard", "batch")),
        np.ascontiguousarray(local_x),
        (k, global_b),
    )
    out = _distributed_apply(mesh, a_groups, x, rows + pad)
    # reassemble from the output shards this process can address
    cols: dict[int, np.ndarray] = {}
    for s in out.addressable_shards:
        cols[s.index[1].start or 0] = np.asarray(s.data)
    assembled = np.concatenate(
        [cols[c] for c in sorted(cols)], axis=1
    )
    return assembled[:rows]


def _local_shard_rows(mesh: Mesh) -> slice:
    """Which rows of the [S, ...] per-group matrix stack this process
    owns: the shard-axis positions of its addressable devices."""
    rows = sorted(
        {
            int(np.argwhere(mesh.devices == d)[0][0])
            for d in mesh.local_devices
        }
    )
    return slice(rows[0], rows[-1] + 1)


def _staged_worker_main(argv) -> None:
    """Worker for the two-process host-staging validation: each process
    initializes jax.distributed, stages ITS half of the input via
    make_array_from_process_local_data, runs the sharded encode, and
    asserts the full result against the numpy oracle.  Spawned by
    tests/test_parallel.py and by `python -m seaweedfs_tpu.parallel.
    distributed --staged-worker ...`."""
    import argparse
    import os

    p = argparse.ArgumentParser()
    p.add_argument("--coordinator", required=True)
    p.add_argument("--nproc", type=int, required=True)
    p.add_argument("--pid", type=int, required=True)
    p.add_argument("--devices-per-proc", type=int, default=4)
    args = p.parse_args(argv)

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices_per_proc}"
    )
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    from . import mesh as mesh_mod

    mesh_mod.initialize_distributed(args.coordinator, args.pid, args.nproc)
    mesh = make_mesh(args.nproc, devices=mesh_mod.global_devices())

    from ..ops import rs_cpu
    from ..ops.rs import RSCodec

    rng = np.random.default_rng(42)
    k, b = 10, 1 << 20
    data = rng.integers(0, 256, size=(k, b), dtype=np.uint8)
    parity_m = np.asarray(RSCodec().matrix[k:], dtype=np.uint8)
    rows = _local_shard_rows(mesh)
    k_loc = k // args.nproc
    local = data[rows.start * k_loc : rows.stop * k_loc]
    out = staged_apply_matrix(mesh, parity_m, local, b)
    want = rs_cpu.apply_matrix_numpy(parity_m, data)
    np.testing.assert_array_equal(out, want)
    print(f"staged worker {args.pid}: ok {out.shape}")


if __name__ == "__main__":
    import sys

    if len(sys.argv) > 1 and sys.argv[1] == "--staged-worker":
        _staged_worker_main(sys.argv[2:])
