"""Every kernel of the EC main path compiles for a described TPU v5e.

No chip is attached: the TPU compiler installed with JAX lowers and
compiles each jitted entry for a `v5e:2x2` topology that is only
DESCRIBED (on-chip-measurement guide, section 2), with interpret=False,
on ShapeDtypeStructs at the shapes chip_smoke.py drives — a 410 MiB
shard (4 GiB volume) padded the way DeviceShardCache pads it.  What the
chip's compiler would refuse (a misaligned slice, too much VMEM, a
program that does not fit 16 GB of HBM, a kernel that cannot be
partitioned) fails here, at no chip time.  A compile that passes is not
a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist every
worker imports this file.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from seaweedfs_tpu.ops import rs_ingest, rs_resident, rs_tpu  # noqa: E402
from seaweedfs_tpu.parallel import mesh as mesh_mod  # noqa: E402

SHARD_BYTES = 410 << 20  # one shard of a 4 GiB volume
HBM_BYTES = 16 * 10**9  # one v5e chip
GROUPS = rs_tpu.BLOCKDIAG_GROUPS
K, PARITY = 10, 4


def _padded_len(n: int, quantum: int = rs_resident.SHARD_QUANTUM) -> int:
    # DeviceShardCache._padded_len without building a cache
    return -(-(n + rs_resident.MAX_TILE) // quantum) * quantum


L_PAD = _padded_len(SHARD_BYTES)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip: keep it off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), (mesh_mod.SHARD_AXIS,))


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_checked(lowered):
    """Compile, require a Mosaic kernel in the program and that program
    arguments + temporaries fit one chip's HBM; -> memory analysis."""
    exe = lowered.compile()
    assert "tpu_custom_call" in exe.as_text()
    mem = exe.memory_analysis()
    need = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert need < HBM_BYTES, f"program needs {need} bytes of HBM"
    return mem


def _recon_matrix(groups: int):
    m_gf = np.ones((1, K), dtype=np.uint8)  # one wanted shard
    if groups > 1:
        return rs_tpu.prepare_matrix_blockdiag(m_gf, groups).shape
    return rs_tpu.prepare_matrix(m_gf).shape


def _parity_matrix(groups: int):
    m_gf = np.ones((PARITY, K), dtype=np.uint8)
    if groups > 1:
        return rs_tpu.prepare_matrix_blockdiag(m_gf, groups).shape
    return rs_tpu.prepare_matrix(m_gf).shape


# --- bulk encode / rebuild (storage/ec/bulk.py device leg) -------------------


@pytest.mark.parametrize("stride", [1 << 20, 4 << 20])
def test_bulk_encode_plain(one_chip, stride):
    # [10, stride] flat in, [4, stride] flat out; 1 MiB is a small-block
    # row (what a 4 GiB volume encodes in), 4 MiB the bulk stride
    a = sds(_parity_matrix(1), jnp.int8, one_chip)
    x = sds((K * stride,), jnp.uint8, one_chip)
    compile_checked(
        rs_tpu.apply_matrix_device_flat.lower(
            a, x, k=K, m=PARITY, kernel="pallas", interpret=False
        )
    )


@pytest.mark.parametrize("stride", [1 << 20, 4 << 20])
def test_bulk_encode_blockdiag(one_chip, stride):
    a = sds(_parity_matrix(GROUPS), jnp.int8, one_chip)
    x = sds((K * stride,), jnp.uint8, one_chip)
    compile_checked(
        rs_tpu.apply_matrix_device_flat.lower(
            a, x, k=GROUPS * K, m=GROUPS * PARITY,
            tile=rs_tpu.BLOCKDIAG_TILE, interpret=False,
        )
    )


def test_ingest_row_encode(one_chip):
    # ops/rs_ingest.py streaming encode of one staged small-block row
    a = sds(_parity_matrix(1), jnp.int8, one_chip)
    x = sds((K, 1 << 20), jnp.uint8, one_chip)
    compile_checked(
        rs_ingest._encode_entry().lower(
            a, x, kernel="pallas", interpret=False, k_true=K
        )
    )


# --- resident degraded-read reconstruct --------------------------------------

# corners of SIZE_BUCKETS x COUNT_BUCKETS (the widest count a size
# bucket may batch is _max_count of it)
CORNERS = [
    (count, size)
    for size in (rs_resident.SIZE_BUCKETS[0], rs_resident.SIZE_BUCKETS[-1])
    for count in (rs_resident.COUNT_BUCKETS[0], rs_resident._max_count(size))
]


@pytest.mark.parametrize("count,fetch", CORNERS)
def test_fused_reconstruct(one_chip, count, fetch):
    fetch, tile = rs_resident._fused_fetch_tile(fetch, 1)
    a = sds(_recon_matrix(1), jnp.int8, one_chip)
    survivors = tuple(
        sds((L_PAD,), jnp.uint8, one_chip) for _ in range(K)
    )
    meta = sds((count,), jnp.int32, one_chip)
    with rs_resident._quiet_donation():
        compile_checked(
            rs_resident._fused_reconstruct.lower(
                a, survivors, meta, tile=tile, fetch=fetch, k_true=K,
                interpret=False,
            )
        )


@pytest.mark.parametrize("count,fetch", CORNERS)
def test_fused_reconstruct_blockdiag(one_chip, count, fetch):
    fetch, tile = rs_resident._fused_fetch_tile(fetch, GROUPS)
    a = sds(_recon_matrix(GROUPS), jnp.int8, one_chip)
    survivors = tuple(
        sds((L_PAD,), jnp.uint8, one_chip) for _ in range(K)
    )
    meta = sds((count,), jnp.int32, one_chip)
    with rs_resident._quiet_donation():
        compile_checked(
            rs_resident._fused_reconstruct_blockdiag.lower(
                a, survivors, meta, tile=tile, fetch=fetch, k_true=K,
                w_true=1, groups=GROUPS, interpret=False,
            )
        )


# the wide family: what a volume several data shards down is re-planned
# with (rs_resident.warm_replan): the matrix of all its lost data shards,
# one count bucket a size class; its smallest and largest shapes
WIDE_CORNERS = [
    (rs_resident._WIDE_COUNTS[size], fetch)
    for size in (rs_resident.SIZE_BUCKETS[0], rs_resident.SIZE_BUCKETS[-1])
    for fetch in (rs_resident._fused_fetch_rungs(size)[0],
                  rs_resident._fused_fetch_rungs(size)[-1])
]  # (8, 2048 -> 4096), (8, 3072 -> 4096), (2, 786432 -> 1 MiB), (2, 2 MiB)


@pytest.mark.parametrize("w_true", (2, 3, 4))
@pytest.mark.parametrize("count,fetch", WIDE_CORNERS)
def test_fused_reconstruct_blockdiag_wide(one_chip, count, fetch, w_true):
    fetch, tile = rs_resident._call_fetch_tile(fetch, GROUPS, True)
    m_gf = np.ones((w_true, K), dtype=np.uint8)
    a = sds(rs_tpu.prepare_matrix_blockdiag(m_gf, GROUPS).shape, jnp.int8,
            one_chip)
    survivors = tuple(
        sds((L_PAD,), jnp.uint8, one_chip) for _ in range(K)
    )
    meta = sds((count,), jnp.int32, one_chip)
    with rs_resident._quiet_donation():
        compile_checked(
            rs_resident._fused_reconstruct_blockdiag.lower(
                a, survivors, meta, tile=tile, fetch=fetch, k_true=K,
                w_true=w_true, groups=GROUPS, interpret=False,
            )
        )


# --- resident scrub ----------------------------------------------------------


def _scrub_args(one_chip, vols=1):
    shards = tuple(
        sds((L_PAD,), jnp.uint8, one_chip)
        for _ in range(vols * (K + PARITY))
    )
    return shards, sds((), jnp.int32, one_chip)


def _assert_window_bounded(mem):
    # the point of the lane windows: a scrub program's temporaries are
    # sized by the window, not by the 14 x 448 MiB it reads from
    assert mem.temp_size_in_bytes < 2 << 30


def test_scrub_volume_flat(one_chip):
    shards, start = _scrub_args(one_chip)
    a = sds(_parity_matrix(1), jnp.int8, one_chip)
    mem = compile_checked(
        rs_resident._scrub_call.lower(
            a, shards[:K], shards[K:], start,
            width=rs_resident._SCRUB_WINDOW, kernel="pallas",
            interpret=False,
        )
    )
    _assert_window_bounded(mem)


def test_scrub_volume_blockdiag(one_chip):
    shards, start = _scrub_args(one_chip)
    a = sds(_parity_matrix(GROUPS), jnp.int8, one_chip)
    mem = compile_checked(
        rs_resident._scrub_call_blockdiag.lower(
            a, shards[:K], shards[K:], start,
            width=rs_resident._SCRUB_WINDOW, groups=GROUPS,
            kernel="pallas", interpret=False,
        )
    )
    _assert_window_bounded(mem)


@pytest.mark.parametrize("vols", [1, 2])
def test_scrub_megakernel(one_chip, vols):
    shards, start = _scrub_args(one_chip, vols)
    a = sds(_parity_matrix(GROUPS), jnp.int8, one_chip)
    mem = compile_checked(
        rs_resident._scrub_all_call.lower(
            a, shards, start, width=rs_resident._SCRUB_WINDOW // vols,
            groups=GROUPS, vols=vols, k=K, p=PARITY, kernel="pallas",
            interpret=False,
        )
    )
    _assert_window_bounded(mem)


# --- four-chip serving mesh ---------------------------------------------------


@pytest.mark.parametrize("replicate_out", [False, True])
def test_sharded_gather_reconstruct_pallas(mesh4, replicate_out):
    n_dev = 4
    # DeviceShardCache rounds the quantum so each device's stripes are
    # whole; any multiple of n_dev * SIZE_BUCKETS[0] shards evenly
    l_pad = L_PAD
    assert l_pad % (n_dev * rs_resident.SIZE_BUCKETS[0]) == 0
    rep = NamedSharding(mesh4, P(None, None))
    lanes = NamedSharding(mesh4, P(mesh_mod.SHARD_AXIS))
    a = sds(_recon_matrix(GROUPS), jnp.int8, rep)
    survivors = tuple(sds((l_pad,), jnp.uint8, lanes) for _ in range(K))
    count, tile = 64, 8192
    vecs = sds(
        (n_dev, 2, count), jnp.int32,
        NamedSharding(mesh4, P(mesh_mod.SHARD_AXIS, None, None)),
    )
    with rs_resident._quiet_donation():
        lowered = rs_resident._sharded_gather_reconstruct.lower(
            a, survivors, vecs, mesh=mesh4, tile=tile, groups=GROUPS,
            w_true=1, kernel="pallas", interpret=False, k_true=K,
            replicate_out=replicate_out,
        )
    exe = lowered.compile()
    text = exe.as_text()
    assert "tpu_custom_call" in text
    # the replicated (multi-controller) variant all-gathers result rows
    assert ("all-gather" in text) == replicate_out
    mem = exe.memory_analysis()
    # per-device: each chip holds 1/4 of every survivor
    assert mem.argument_size_in_bytes < K * l_pad // n_dev + (64 << 20)
