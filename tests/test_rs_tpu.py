"""TPU backend parity tests: xla and pallas (interpret-mode on the CPU test
mesh) must match the numpy oracle bit-for-bit — the same test shape the
reference uses for its EC layer (encode then reconstruct from random shard
subsets, ec_test.go)."""
import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_cpu, rs_tpu
from seaweedfs_tpu.ops.rs import RSCodec


def _rand(k, b, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (k, b)).astype(np.uint8)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_apply_matrix_matches_numpy(kernel):
    m = gf256.parity_matrix(10, 14)
    x = _rand(10, 1000, 1)  # deliberately not a tile multiple
    want = rs_cpu.apply_matrix_numpy(m, x)
    got = rs_tpu.apply_matrix(m, x, kernel=kernel)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_arbitrary_matrix_rows(kernel):
    """Reconstruction matrices have 1..4 rows; row padding must slice off."""
    rng = np.random.default_rng(2)
    for rows in (1, 2, 3, 4, 5, 14):
        m = rng.integers(0, 256, (rows, 10)).astype(np.uint8)
        x = _rand(10, 256, rows)
        assert np.array_equal(
            rs_tpu.apply_matrix(m, x, kernel=kernel),
            rs_cpu.apply_matrix_numpy(m, x),
        )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_codec_roundtrip(backend):
    codec = RSCodec(backend=backend)
    data = _rand(10, 5000, 3)
    shards = codec.encode_all(data)
    assert codec.verify(shards)
    # 4 losses incl. parity
    lost = [0, 5, 11, 13]
    present = {i: shards[i] for i in range(14) if i not in lost}
    got = codec.reconstruct(present)
    for l in lost:
        assert np.array_equal(got[l], shards[l])


def test_cross_backend_identical():
    """numpy, xla, pallas parity bytes are identical -> shard files written
    by any backend are interchangeable."""
    data = _rand(10, 4096, 4)
    outs = [RSCodec(backend=b).encode(data) for b in ("numpy", "xla", "pallas")]
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], outs[2])


def test_large_batch_tiling():
    """B spanning multiple grid tiles incl. a ragged tail (explicit small
    tile so interpret mode stays fast; the real-TPU multi-tile path is
    compiled by tests/test_tpu_compile.py and runs in the bulk cell)."""
    m = gf256.parity_matrix(10, 14)
    x = _rand(10, 3 * 512 + 77, 5)
    assert np.array_equal(
        rs_tpu.apply_matrix(m, x, kernel="pallas", tile=512),
        rs_cpu.apply_matrix_numpy(m, x),
    )


def test_blockdiag_matches_numpy():
    """Block-diagonal fast path (segment-stacked host staging, ~152 GB/s
    on v5e) is bit-identical to the plain formulation."""
    m = gf256.parity_matrix(10, 14)
    for b in (4 * 512, 4 * 512 + 4):  # divisible by groups; uneven tile
        x = _rand(10, b, 6)
        got = rs_tpu.apply_matrix_blockdiag(m, x, tile=512)
        assert np.array_equal(got, rs_cpu.apply_matrix_numpy(m, x))


def test_blockdiag_reconstruction_matrix():
    """Rebuild matrices (arbitrary rows/cols) ride the same path."""
    rmat, use = gf256.reconstruction_matrix(
        10, 14, [i for i in range(14) if i not in (1, 4, 10, 12)],
        [1, 4, 10, 12],
    )
    codec = RSCodec(backend="numpy")
    data = _rand(10, 4 * 1024, 7)
    shards = codec.encode_all(data)
    got = rs_tpu.apply_matrix_blockdiag(rmat, shards[use], tile=1024)
    assert np.array_equal(got, shards[[1, 4, 10, 12]])


def test_blockdiag_indivisible_falls_back():
    m = gf256.parity_matrix(10, 14)
    x = _rand(10, 4 * 512 + 3, 8)  # not divisible by groups
    got = rs_tpu.apply_matrix_blockdiag(m, x, tile=512)
    assert np.array_equal(got, rs_cpu.apply_matrix_numpy(m, x))


def test_stack_unstack_inverse():
    x = _rand(10, 4 * 333, 9)
    st = rs_tpu.stack_segments(x)
    assert st.shape == (40, 333)
    # parity-shaped output round-trip (m_pad rows per group)
    out = _rand(16, 333, 10)
    flat = rs_tpu.unstack_segments(out, 4)
    assert flat.shape == (4, 4 * 333)
    for g in range(4):
        assert np.array_equal(flat[:, g * 333 : (g + 1) * 333], out[g * 4 : g * 4 + 4])
