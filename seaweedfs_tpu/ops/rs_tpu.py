"""TPU Reed-Solomon backends: bitsliced GF(2) matmul on the MXU.

The reference's hot loop is a CPU GF(256) SIMD multiply
(klauspost/reedsolomon AVX2 nibble shuffles, called from
/root/reference/weed/storage/erasure_coding/ec_encoder.go:162-192 and the
degraded-read reconstruct at /root/reference/weed/storage/store_ec.go:339-393).
TPUs have no byte-shuffle unit, so a table-lookup port would fight the
hardware.  Instead we use the GF(2) structure of the code:

  GF(256) is an 8-dim vector space over GF(2); multiply-by-constant is a
  GF(2)-linear map (an 8x8 bit matrix).  An RS code with generator G[m,k]
  over GF(256) is therefore one GF(2) matrix A[8m, 8k], and

      out_bits[8m, B] = A[8m, 8k] @ in_bits[8k, B]   (mod 2)

  — a plain matmul with a parity reduction.  Bits are 0/1 int8 values, the
  products accumulate exactly in int32 (counts <= 8k << 2^31), and
  `count & 1` recovers the XOR.  This maps the whole codec onto the MXU
  systolic array: encode, rebuild, and degraded-read reconstruction are the
  same kernel with different matrices.

Layout (kernel variants: experiments/kernel_variants*.py; the rates
quoted in this header are the builders' own from rounds 3-4 on an earlier
rig and are NOT measured on the current chip — see PERF.md):

  * int8 operands with int32 accumulation — the v5e MXU runs int8 at twice
    the bf16 MAC rate (394 vs 197 TOPS), and every element here is a 0/1
    bit, so the narrow type is exact.
  * rows/cols permuted *bit-major* (row = bit*k_pad + shard) so the kernel
    unpacks bytes to bits with a sublane concatenation of eight masked
    planes ((x & 2^i) != 0 — int8 end to end, no widening) and repacks
    with eight static row-slices — no gathers.  The permutation is folded
    into the matrix on the host.
  * matrix cols padded to k_pad = 16 shards (so the MXU contraction dim
    8*k_pad is an exact 128 tile and every unpacked bit-plane starts on a
    sublane-tile boundary).  The input stays [k, B] in HBM; the kernel
    concatenates the k_pad-k zero rows in VMEM, which costs ~5% vs a
    pre-padded input but avoids any HBM pad copy in the pipeline.
    Head-to-head on v5e-1 (same run, useful-byte GB/s): bf16 k=10: 49;
    int8 + per-batch HBM pad: 52; int8 + VMEM concat: 67; int8
    pre-padded: 70.  Roof for this shape: one 128x128 int8 MXU pass per
    128 lanes = 1638 MACs/useful-byte -> ~120 GB/s.

Two kernels:
  "xla"    — the formulation in plain jnp; XLA materialises the bit matrix
             in HBM (8x inflation) but needs no Pallas.
  "pallas" — fused kernel: unpack -> MXU dot -> pack entirely in VMEM, so
             HBM traffic is just the k input and m output byte planes.

Round-3 findings (experiments/kernel_roof_r3.py, profiler-measured on
a v5e-1 — the fori-loop differencing harness used in
earlier rounds charges its own per-iteration XOR pass and dispatch
jitter to the kernel, reading ~77 GB/s for a kernel whose device-stream
execution time is 0.81 ms for 96MB = ~123 GB/s, i.e. the plain kernel
already sits AT its documented ~120 GB/s MXU roof):

  * BLOCK-DIAGONAL g=4 packing lifts the roof itself: four independent
    stripe groups fill the MXU's M dimension (A_blk [128, 320] vs a
    mostly-padding [128, 128]), cutting MACs/useful-byte from 1638 to
    ~1229 -> measured 0.656 ms / 96MB = ~152 GB/s.  The catch: inputs
    must arrive segment-stacked ([g*k, B/g]); restacking ON DEVICE costs
    more than the win (byte transposes: 58 GB/s flat-to-flat), so the
    HOST stages the layout.  Not free: one copy of the batch per call,
    measured on the v5e's host at 6.7 ms of a 28.6 ms rebuild batch
    (40 MiB) and 1.6 ms of a 9.4 ms encode batch (10 MiB) into a kept
    buffer — and 47 ms / 2.5 ms into a new array every batch, which is
    what it cost until storage/ec/bulk.py pooled it (PERF.md, PR 24 and
    PR 25).  apply_matrix_blockdiag below.
  * g=8 regresses (95 GB/s): longer contraction padding + VMEM pressure.
  * Feeding the flat layout via a 3-D BlockSpec block (gather inside the
    kernel) is rejected by Mosaic (compile-helper 500) — dead end, like
    the int8-accumulate and u8-multiply routes before it.

Round 4 (bench.py reworked onto profiler device-stream timing):

  * blockdiag ~157 GB/s and plain ~121 GB/s by device-stream timing;
    the fori-loop differencing cross-check spreads widely and is
    published only as the conservative bound.
  * Every pipeline ships FLAT 1-D buffers (apply_matrix_device_flat)
    and reshapes on device.
  * The serving-side fused gather+reconstruct pair lives in
    rs_resident.py (its header documents the Mosaic layout rules that
    shaped it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gf256

# Lane tile for the batch dimension.  v5e sweep: 16384 is the knee for the
# int8 kernel (8192: 112, 16384: 115, 24576: 113 GB/s); VMEM footprint at
# 16384 is ~(16+4)*16K input/output + 128*16K bits ~= 2.4MB with headroom
# for double buffering.
BATCH_TILE = 16384

# Input-shard padding: k rounds up to a multiple of 16 so the unpacked bit
# planes are sublane-tile aligned and 8*k_pad is a multiple of the 128 MXU
# contraction tile.
K_ALIGN = 16


def _pad_rows(m_gf: np.ndarray) -> np.ndarray:
    """Pad the GF matrix to a multiple of 4 output rows (sublane alignment:
    8 bits * 4 rows = 32 = int8/u8 sublane tile). Zero rows produce zero
    shards that callers slice away."""
    rows = m_gf.shape[0]
    pad = (-rows) % 4
    if pad:
        m_gf = np.concatenate(
            [m_gf, np.zeros((pad, m_gf.shape[1]), dtype=np.uint8)]
        )
    return m_gf


def _pad_cols(m_gf: np.ndarray) -> np.ndarray:
    """Pad the GF matrix to a multiple of K_ALIGN input columns.  Zero
    columns multiply zero-padded input rows: no effect on the result."""
    cols = m_gf.shape[1]
    pad = (-cols) % K_ALIGN
    if pad:
        m_gf = np.concatenate(
            [m_gf, np.zeros((m_gf.shape[0], pad), dtype=np.uint8)], axis=1
        )
    return m_gf


def prepare_matrix(m_gf: np.ndarray) -> jax.Array:
    """GF(256) matrix [m,k] -> bit-major GF(2) int8 matrix
    [8*m_pad, 8*k_pad].

    a_bm[i*m_pad + p, j*k_pad + d] == bit i of (G[p,d] * 2^j), i.e.
    standard expand_to_gf2 with rows/cols permuted bit-major, rows padded
    to a multiple of 4 and cols to a multiple of K_ALIGN."""
    m_gf = _pad_cols(_pad_rows(np.asarray(m_gf, dtype=np.uint8)))
    m, k = m_gf.shape
    a_std = gf256.expand_to_gf2(m_gf)  # [8m, 8k], row p*8+i
    a_bm = (
        a_std.reshape(m, 8, k, 8).transpose(1, 0, 3, 2).reshape(8 * m, 8 * k)
    )
    return jnp.asarray(a_bm, dtype=jnp.int8)


def _unpack_bits_bitmajor(x: jax.Array, dtype=jnp.int8) -> jax.Array:
    """u8 [k, B] -> 0/1 bits [8k, B], row = bit*k + shard (concat of eight
    masked planes along sublanes).  Bit i extracts as (x & 2^i) != 0 — a
    bytewise AND + compare that stays 1-byte-wide end to end.  (The shift
    formulation needs int32 — Mosaic can't legalize sub-word shrui — and
    the 4x widening costs ~12% of kernel throughput: 65.9 -> 75.2 GB/s on
    v5e, builders' round-3 figure.)"""
    planes = [
        ((x & np.uint8(1 << i)) != 0).astype(dtype) for i in range(8)
    ]
    return jnp.concatenate(planes, axis=0)


def _pack_bits_bitmajor(counts: jax.Array, m: int) -> jax.Array:
    """int32/f32 counts [8m, B] -> u8 [m, B]: mod-2 then byte-pack via
    eight static row slices."""
    obits = counts.astype(jnp.int32) & 1
    acc = obits[0:m]
    for i in range(1, 8):
        acc = acc | (obits[i * m : (i + 1) * m] << i)
    return acc.astype(jnp.uint8)


def _check_x_rows(x: jax.Array, k_pad: int, k_true: int | None) -> None:
    """Guard matrix/input shard-count mismatches.  The matrix cols are
    padded to k_pad, so a wrong-but-smaller shard count would silently
    multiply zero columns; callers that know the matrix's true k pass it
    so the mismatch raises instead."""
    if k_true is not None and x.shape[0] != k_true:
        raise ValueError(
            f"input has {x.shape[0]} shards but matrix was built for {k_true}"
        )
    if x.shape[0] > k_pad:
        raise ValueError(
            f"input has {x.shape[0]} shards but matrix covers {k_pad}"
        )


# --- XLA kernel -------------------------------------------------------------


def _apply_xla(a_bm: jax.Array, x: jax.Array) -> jax.Array:
    m = a_bm.shape[0] // 8
    k_pad = a_bm.shape[1] // 8
    if x.shape[0] < k_pad:  # XLA fuses the row pad into the unpack
        x = jnp.pad(x, ((0, k_pad - x.shape[0]), (0, 0)))
    bits = _unpack_bits_bitmajor(x)
    counts = jnp.dot(a_bm, bits, preferred_element_type=jnp.int32)
    return _pack_bits_bitmajor(counts, m)


# --- Pallas kernel ----------------------------------------------------------


def _gf2_matmul_kernel(a_ref, x_ref, o_ref):
    m = o_ref.shape[0]
    k_pad = a_ref.shape[1] // 8
    xv = x_ref[:]
    if xv.shape[0] < k_pad:  # align shards to k_pad with a VMEM-local
        zeros = jnp.zeros((k_pad - xv.shape[0], xv.shape[1]), jnp.uint8)
        xv = jnp.concatenate([xv, zeros], axis=0)  # zero block (no HBM pad)
    bits = _unpack_bits_bitmajor(xv)
    counts = jnp.dot(a_ref[:], bits, preferred_element_type=jnp.int32)
    o_ref[:] = _pack_bits_bitmajor(counts, m)


def _tile_for(b: int) -> int:
    """Block tile: full BATCH_TILE for large batches, shrunk (128-aligned)
    for small ones so degraded reads of single needles don't pay for a 16K
    pad and interpret-mode tests stay fast."""
    return min(BATCH_TILE, max(128, -(-b // 128) * 128))


def _apply_pallas(
    a_bm: jax.Array, x: jax.Array, interpret: bool, tile: int
) -> jax.Array:
    m8, k8 = a_bm.shape
    k, b = x.shape
    m = m8 // 8
    grid = (pl.cdiv(b, tile),)
    return pl.pallas_call(
        _gf2_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m8, k8), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        # inside shard_map (the mesh-sharded reconstruct) the output
        # varies over the same mesh axes as the input shards
        out_shape=jax.ShapeDtypeStruct(
            (m, b), jnp.uint8, vma=jax.typeof(x).vma
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m8 * k8 * b, bytes_accessed=k * b + m * b, transcendentals=0
        ),
        interpret=interpret,
    )(a_bm, x)


# --- jitted entry points ----------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("kernel", "interpret", "tile", "k_true")
)
def apply_matrix_device(
    a_bm: jax.Array,
    x: jax.Array,
    kernel: str = "pallas",
    interpret: bool = False,
    tile: int | None = None,
    k_true: int | None = None,
) -> jax.Array:
    """Device-resident apply: bit-major matrix [8m,8k_pad] int8, shards
    [k,B] u8 (k <= k_pad; the missing rows are treated as zeros inside the
    kernel) -> [m,B] u8.  For the pallas kernel B is padded to the block
    tile (the pad region computes garbage that is sliced off).  `tile` is
    an explicit static override (tests, tuning) — by default it is derived
    from B so the jit cache stays consistent.  `k_true` is the matrix's
    pre-padding shard count; pass it to catch shard-count mismatches that
    the column padding would otherwise absorb silently."""
    _check_x_rows(x, a_bm.shape[1] // 8, k_true)
    if kernel == "pallas":
        b = x.shape[1]
        tile = tile or _tile_for(b)
        pad = (-b) % tile
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        out = _apply_pallas(a_bm, x, interpret, tile)
        return out[:, :b] if pad else out
    if kernel == "xla":
        return _apply_xla(a_bm, x)
    raise ValueError(f"unknown TPU kernel {kernel!r}")


# --- block-diagonal variant (the encode hot path) ---------------------------

BLOCKDIAG_GROUPS = 4
BLOCKDIAG_TILE = 32768


def blockdiag_system(
    m_gf: np.ndarray, groups: int = BLOCKDIAG_GROUPS
) -> np.ndarray:
    """GF(256) matrix [m,k] -> the [groups*m, groups*k] block-diagonal
    system that encodes `groups` independent stripe segments in one
    multiply.  Shared by the single-chip prepared-matrix path and the
    mesh-sharded encode so the two can never drift."""
    m_gf = np.asarray(m_gf, dtype=np.uint8)
    m, k = m_gf.shape
    blk = np.zeros((groups * m, groups * k), dtype=np.uint8)
    for g in range(groups):
        blk[g * m : (g + 1) * m, g * k : (g + 1) * k] = m_gf
    return blk


def prepare_matrix_blockdiag(
    m_gf: np.ndarray, groups: int = BLOCKDIAG_GROUPS
) -> jax.Array:
    """GF(256) matrix [m,k] -> the block-diagonal system's prepared bit
    matrix.  The block structure is applied at the GF(256) level and then
    expanded by the standard prepare_matrix, so the column order matches
    what _unpack_bits_bitmajor produces for the STACKED input (bit-major
    over all groups*k rows — a per-group bit-major layout would compute
    garbage)."""
    return prepare_matrix(blockdiag_system(m_gf, groups))


def apply_matrix_device_blockdiag(
    a_blk: jax.Array,
    x_stacked: jax.Array,  # [groups*k, seg] u8, segment-stacked
    groups: int = BLOCKDIAG_GROUPS,
    tile: int = BLOCKDIAG_TILE,
    interpret: bool = False,
) -> jax.Array:
    """-> [>=groups*m, seg] u8 (group g's true output rows at g*m..; any
    row padding sits at the tail).  Same fused kernel as the plain path —
    only the matrix and input layout differ."""
    return apply_matrix_device(
        a_blk,
        x_stacked,
        kernel="pallas",
        interpret=interpret,
        tile=tile,
        k_true=x_stacked.shape[0],
    )


@functools.lru_cache(maxsize=16)
def _prepared_blockdiag(matrix_bytes: bytes, m: int, k: int, groups: int):
    return prepare_matrix_blockdiag(
        np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k), groups
    )


def stack_segments(
    shards: np.ndarray,
    groups: int = BLOCKDIAG_GROUPS,
    out: np.ndarray | None = None,
) -> np.ndarray:
    """[k, B] -> [groups*k, B/groups]: segment g of every shard becomes
    rows g*k..g*k+k-1 (the host-side staging block-diagonal needs — same
    bytes, different row order, so one copy of the batch).  With `out`,
    a C-contiguous buffer of the batch's k*B bytes in any shape, the
    copy lands there and no array is allocated."""
    k, b = shards.shape
    seg = b // groups
    stacked = shards.reshape(k, groups, seg).transpose(1, 0, 2)
    if out is None:
        return stacked.reshape(groups * k, seg)
    np.copyto(out.reshape(groups, k, seg), stacked)
    return out.reshape(groups * k, seg)


def unstack_segments(out: np.ndarray, m: int, groups: int = BLOCKDIAG_GROUPS) -> np.ndarray:
    """[>=groups*m, seg] -> [m, groups*seg]: group g's true rows live at
    g*m..g*m+m-1 (row padding, if any, is beyond groups*m)."""
    seg = out.shape[1]
    return (
        out[: groups * m]
        .reshape(groups, m, seg)
        .transpose(1, 0, 2)
        .reshape(m, groups * seg)
    )


def apply_matrix_blockdiag(
    m_gf: np.ndarray,
    shards: np.ndarray,
    groups: int = BLOCKDIAG_GROUPS,
    tile: int = BLOCKDIAG_TILE,
) -> np.ndarray:
    """Host-convenience block-diagonal apply (numpy in/out) — the fast
    path for bulk encode/rebuild when B divides by `groups`.  Callers
    with indivisible batches use the plain apply_matrix."""
    m_gf = np.asarray(m_gf, dtype=np.uint8)
    rows, k = m_gf.shape
    b = shards.shape[1]
    if b % groups:
        return apply_matrix(m_gf, shards)
    a_blk = _prepared_blockdiag(m_gf.tobytes(), rows, k, groups)
    x = jnp.asarray(
        np.ascontiguousarray(stack_segments(np.asarray(shards, np.uint8), groups))
    )
    out = apply_matrix_device_blockdiag(
        a_blk, x, groups=groups, tile=tile, interpret=_interpret_default()
    )
    return unstack_segments(np.asarray(out), rows, groups)


@functools.partial(
    jax.jit, static_argnames=("k", "m", "kernel", "tile", "interpret")
)
def apply_matrix_device_flat(
    a_bm: jax.Array,
    x_flat: jax.Array,
    *,
    k: int,
    m: int,
    kernel: str = "pallas",
    tile: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """1-D in / 1-D out apply: pipelines ship flat buffers and reshape
    on device, where it's free under jit.  x_flat is the row-major
    [k, B] input flattened; the result is the row-major [m, B] output
    flattened."""
    b = x_flat.size // k
    x = x_flat.reshape(k, b)
    out = apply_matrix_device(
        a_bm, x, kernel=kernel, interpret=interpret, tile=tile, k_true=k
    )
    return out[:m].reshape(-1)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def _interpret_default() -> bool:
    # Pallas TPU kernels run interpreted off-TPU (CPU test mesh).
    return not on_tpu()


@functools.lru_cache(maxsize=64)
def _prepared(matrix_bytes: bytes, m: int, k: int) -> jax.Array:
    return prepare_matrix(np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k))


def apply_matrix(
    m_gf: np.ndarray,
    shards: np.ndarray,
    kernel: str = "pallas",
    tile: int | None = None,
) -> np.ndarray:
    """Host-convenience apply (numpy in/out). Pipelines that care about
    staging (storage/ec/encoder.py) use apply_matrix_device directly."""
    m_gf = np.asarray(m_gf, dtype=np.uint8)
    rows, k = m_gf.shape
    a_bm = _prepared(m_gf.tobytes(), *m_gf.shape)
    x = jnp.asarray(np.ascontiguousarray(shards, dtype=np.uint8))
    out = apply_matrix_device(
        a_bm,
        x,
        kernel=kernel,
        interpret=_interpret_default(),
        tile=tile,
        k_true=k,
    )
    return np.asarray(out)[:rows]
