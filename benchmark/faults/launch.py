"""Start a server of the program with one fault planted under the timed
path: `python launch.py <fault> <seaweedfs_tpu argv...>`.

Only the benchmark's own tests and its on-chip controls use this (run.py
--fault): a run with a fault planted has to come out `correct: false`.
The driver's command never does.  Nothing here touches a kernel, a
traced function or a frame of a compile, so the programs and their
compile-cache keys are the program's own.

  get_flip_byte    one bit of every 8th needle's body flipped after the
                   EC volume produced it (an answer altered where it is
                   produced; the needle's own CRC was checked before)
  bulk_flip_byte   one byte of the third batch the bulk codec returns
                   flipped (an answer altered where it is produced)
  bulk_drop_half   every second batch of a bulk pipeline never written
                   (half of the batches left out)
"""
from __future__ import annotations

import itertools
import runpy
import sys


def get_flip_byte() -> None:
    from seaweedfs_tpu.storage.ec import volume as ec_volume

    counter = itertools.count(1)
    original = ec_volume.EcVolume.read_needles_batch

    def read_needles_batch(self, *args, **kwargs):
        results = original(self, *args, **kwargs)
        for n in results:
            data = getattr(n, "data", None)
            if data is not None and len(data) and next(counter) % 8 == 0:
                flipped = bytearray(data)
                flipped[len(flipped) // 2] ^= 0x10
                n.data = bytes(flipped)
        return results

    ec_volume.EcVolume.read_needles_batch = read_needles_batch


def bulk_flip_byte() -> None:
    from seaweedfs_tpu.storage.ec import bulk

    counter = itertools.count(1)
    original = bulk.Codec.resolve

    def resolve(self, handle):
        out = original(self, handle)
        if next(counter) == 3:
            out = out.copy()
            out[0, out.shape[1] // 2] ^= 0x10
        return out

    bulk.Codec.resolve = resolve


def bulk_drop_half() -> None:
    from seaweedfs_tpu.storage.ec import bulk, encoder

    original = bulk.run

    def run(name, plan, read_batch, codec, write_batch, **kwargs):
        counter = itertools.count()

        def write_some(desc, payload, result):
            if next(counter) % 2 == 0:
                write_batch(desc, payload, result)

        return original(name, plan, read_batch, codec, write_some, **kwargs)

    bulk.run = run
    encoder.bulk.run = run


FAULTS = {f.__name__: f for f in (get_flip_byte, bulk_flip_byte,
                                  bulk_drop_half)}


if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    sys.argv = ["seaweedfs_tpu", *sys.argv[2:]]
    runpy.run_module("seaweedfs_tpu", run_name="__main__", alter_sys=True)
