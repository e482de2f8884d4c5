"""Object payloads, made from --seed alone: the generator writes them and
the verifier makes them again, so neither keeps a copy of what was sent."""

from __future__ import annotations

import struct

import numpy as np


class Payloads:
    """`pool_size` random buffers of `object_bytes` drawn from the seed;
    object i is buffer i % pool_size with i stamped into its first 8 bytes,
    so that no two objects are equal and any object can be made again."""

    def __init__(self, seed: int, object_bytes: int, pool_size: int,
                 prefix: str) -> None:
        if object_bytes < 8:
            raise ValueError("an object holds at least its 8-byte stamp")
        rng = np.random.default_rng(int(seed))
        self.seed = int(seed)
        self.object_bytes = int(object_bytes)
        self.prefix = prefix
        self._tails = [memoryview(rng.bytes(object_bytes))[8:]
                       for _ in range(pool_size)]

    def name(self, i: int) -> str:
        return f"{self.prefix}_{self.seed}_{i}"

    def data(self, i: int) -> bytes:
        return b"".join((struct.pack("<Q", i),
                         self._tails[i % len(self._tails)]))
