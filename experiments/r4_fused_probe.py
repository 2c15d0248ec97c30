"""Isolate which construct of the fused DMA kernel Mosaic rejects (the
same failure class round 3 hit with its 3-D BlockSpec gather).

Variants build up: scalar prefetch -> ANY input + static DMA -> dynamic
offset DMA -> u8 payloads -> the iota row-select -> the full fused body.
Each prints OK or the first 1500 chars of the error.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = 2048
    L = 1 << 20

    def report(name, fn):
        try:
            r = np.asarray(fn())
            print(f"{name}: OK {r.shape} {r.dtype}")
            return True
        except Exception as e:  # noqa: BLE001
            print(f"{name}: FAIL {repr(e)[:1500]}")
            return False

    # A: scalar prefetch only, block copy
    def a():
        def body(s_ref, x_ref, o_ref):
            o_ref[:] = x_ref[:] + s_ref[0]

        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2,),
            in_specs=[pl.BlockSpec((1, 128), lambda i, s: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i, s: (i, 0)),
        )
        return pl.pallas_call(
            body,
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((2, 128), jnp.int32),
        )(jnp.arange(4, dtype=jnp.int32), jnp.ones((2, 128), jnp.int32))

    # B: ANY input + DMA at static offset (int32 1-D)
    def b():
        def body(x_hbm, o_ref, scratch, sem):
            c = pltpu.make_async_copy(
                x_hbm.at[pl.ds(0, T)], scratch, sem
            )
            c.start()
            c.wait()
            o_ref[:] = scratch[:].reshape(1, T)

        return pl.pallas_call(
            body,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((1, T), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, T), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((T,), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ],
        )(jnp.arange(L, dtype=jnp.int32))

    # C: dynamic offset from prefetched scalar (int32 1-D)
    def c():
        def body(s_ref, x_hbm, o_ref, scratch, sem):
            off = s_ref[pl.program_id(0)]
            cpy = pltpu.make_async_copy(
                x_hbm.at[pl.ds(off, T)], scratch, sem
            )
            cpy.start()
            cpy.wait()
            o_ref[:] = scratch[:].reshape(1, T)

        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((1, T), lambda i, s: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((T,), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ],
        )
        return pl.pallas_call(
            body,
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((2, T), jnp.int32),
        )(
            jnp.array([128, 4096], dtype=jnp.int32),
            jnp.arange(L, dtype=jnp.int32),
        )

    # D: same but uint8 payload + (k, T) scratch rows
    def d():
        k = 3

        def body(s_ref, x_hbm, o_ref, scratch, sems):
            off = s_ref[pl.program_id(0)]
            cps = [
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(off + i, T)], scratch.at[i], sems.at[i]
                )
                for i in range(k)
            ]
            for cp in cps:
                cp.start()
            for cp in cps:
                cp.wait()
            o_ref[:] = jnp.sum(
                scratch[:].astype(jnp.int32), axis=0, keepdims=True
            ).astype(jnp.uint8)

        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((1, T), lambda i, s: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((k, T), jnp.uint8),
                pltpu.SemaphoreType.DMA((k,)),
            ],
        )
        return pl.pallas_call(
            body,
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((2, T), jnp.uint8),
        )(
            jnp.array([128, 4096], dtype=jnp.int32),
            jnp.arange(L, dtype=jnp.int32).astype(jnp.uint8),
        )

    # E: iota row select on u8
    def e():
        def body(s_ref, x_ref, o_ref):
            row = s_ref[pl.program_id(0)]
            ridx = jax.lax.broadcasted_iota(jnp.int32, (4, 128), 0)
            sel = jnp.where(ridx == row, x_ref[:], jnp.uint8(0)).astype(
                jnp.int32
            )
            o_ref[:] = jnp.sum(sel, axis=0, keepdims=True).astype(jnp.uint8)

        gs = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2,),
            in_specs=[pl.BlockSpec((4, 128), lambda i, s: (0, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i, s: (i, 0)),
        )
        return pl.pallas_call(
            body,
            grid_spec=gs,
            out_shape=jax.ShapeDtypeStruct((2, 128), jnp.uint8),
        )(
            jnp.array([1, 3], dtype=jnp.int32),
            jnp.arange(512, dtype=jnp.int32).astype(jnp.uint8).reshape(4, 128),
        )

    # F: the real fused kernel, small shapes
    def f():
        from seaweedfs_tpu.ops import rs_resident, rs_tpu

        rmat = np.eye(10, dtype=np.uint8)[:1]  # want shard 0 back
        a_bm = rs_tpu.prepare_matrix(rmat)
        survivors = tuple(
            jax.device_put(
                np.full(L, i + 1, dtype=np.uint8)
            )
            for i in range(10)
        )
        meta = jnp.array([[0, 4], [0, 0]], dtype=jnp.int32)  # [offs_units, rows]
        return rs_resident._fused_reconstruct(
            a_bm,
            survivors,
            meta,
            tile=2048,
            fetch=2048,
            k_true=10,
            interpret=False,
        )

    ok = True
    for name, fn in (("A", a), ("B", b), ("C", c), ("D", d), ("E", e), ("F", f)):
        ok = report(name, fn) and ok
    print("ALL OK" if ok else "SOME FAILED")


if __name__ == "__main__":
    main()
