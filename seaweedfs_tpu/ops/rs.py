"""Reed-Solomon codec front-end with pluggable CPU/TPU backends.

The reference calls reedsolomon.New(10,4) / Encode / Reconstruct /
ReconstructData (/root/reference/weed/storage/erasure_coding/ec_encoder.go:198,
/root/reference/weed/storage/store_ec.go:342-384).  This module is the
equivalent surface, except every operation is expressed through one linear
primitive — apply_matrix over GF(256) — so the TPU backend is a single
batched matmul kernel regardless of which shards are being produced.

Backends:
  "numpy"  — pure numpy table gathers (always available; oracle)
  "native" — C++ SSSE3/AVX2 nibble-shuffle kernel (the CPU baseline)
  "xla"    — bitsliced GF(2) matmul via jnp on the default JAX device
  "pallas" — fused Pallas TPU kernel (interpret-mode on CPU)
  "cpu"    — native if built else numpy
  "auto"   — pallas on TPU, cpu otherwise
"""
from __future__ import annotations

import functools

import numpy as np

from . import gf256, rs_cpu

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = 14


@functools.cache
def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def auto_resolved() -> bool:
    """Whether this process has resolved "auto" yet — the step that
    initialises the JAX backend (and on a TPU host takes the chip)."""
    return _on_tpu.cache_info().currsize > 0


def resolve_backend(name: str) -> str:
    if name == "cpu":
        return "native" if rs_cpu.native_available() else "numpy"
    if name == "auto":
        return "pallas" if _on_tpu() else resolve_backend("cpu")
    return name


class RSCodec:
    """RS(k, p) systematic erasure codec over GF(256).

    Shards are uint8 arrays of equal length B, stacked [k or total, B].
    Shard indices 0..k-1 are data, k..k+p-1 parity, matching the reference's
    .ec00-.ec13 file naming (ec_encoder.go:17-23).
    """

    def __init__(
        self,
        data_shards: int = DATA_SHARDS,
        parity_shards: int = PARITY_SHARDS,
        backend: str = "cpu",
    ):
        self.k = data_shards
        self.p = parity_shards
        self.n = data_shards + parity_shards
        self.backend = resolve_backend(backend)
        self.matrix = gf256.build_matrix(self.k, self.n)

    # -- primitive ----------------------------------------------------------

    def apply_matrix(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        """out[i] = XOR_j m[i,j] ⊗ shards[j] over GF(256)."""
        if self.backend == "numpy":
            return rs_cpu.apply_matrix_numpy(m, shards)
        if self.backend == "native":
            return rs_cpu.apply_matrix_native(m, shards)
        if self.backend in ("xla", "pallas"):
            from . import rs_tpu

            return rs_tpu.apply_matrix(m, shards, kernel=self.backend)
        raise ValueError(f"unknown backend {self.backend!r}")

    # -- RS surface ---------------------------------------------------------

    def encode(self, data: np.ndarray) -> np.ndarray:
        """data [k,B] -> parity [p,B]."""
        data = np.asarray(data, dtype=np.uint8)
        if data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} data shards, got {data.shape[0]}")
        return self.apply_matrix(self.matrix[self.k :], data)

    def encode_all(self, data: np.ndarray) -> np.ndarray:
        """data [k,B] -> all shards [n,B] (data rows are copies)."""
        parity = self.encode(data)
        return np.concatenate([np.asarray(data, dtype=np.uint8), parity], axis=0)

    def reconstruct(
        self, shards: dict[int, np.ndarray], wanted: list[int] | None = None
    ) -> dict[int, np.ndarray]:
        """Recompute missing shards from any >=k present ones.

        `shards` maps shard index -> [B] or [B]-like u8 array. Returns
        {wanted_index: array}; `wanted=None` means all missing indices
        (reference Reconstruct); pass only missing *data* indices for the
        ReconstructData fast path used by degraded reads (store_ec.go:384).
        """
        present = sorted(shards.keys())
        if wanted is None:
            wanted = [i for i in range(self.n) if i not in shards]
        if not wanted:
            return {}
        r, use = gf256.reconstruction_matrix(self.k, self.n, present, wanted)
        stack = np.stack([np.asarray(shards[i], dtype=np.uint8) for i in use])
        out = self.apply_matrix(r, stack)
        return {w: out[i] for i, w in enumerate(wanted)}

    def verify(self, shards: np.ndarray) -> bool:
        """shards [n,B]: recompute parity from data rows and compare."""
        shards = np.asarray(shards, dtype=np.uint8)
        parity = self.encode(shards[: self.k])
        return bool(np.array_equal(parity, shards[self.k :]))
