"""Plain reference for a block image: what a read of any byte range has to
return after a history of writes, and what the image's data objects hold.
It imports nothing of the program.

A block device's contract is short.  A write replaces the bytes of its
extent and no other byte; a read returns, for every byte, the newest write
that was acknowledged before the read was issued; bytes never written read
as zeros.  The model below is a `bytearray` of the image, cut into blocks
so that it keeps nothing it can make again: a block holds either nothing
(zeros), or a GENERATION, which stands for `block_payload(seed, block,
generation)`, or literal bytes where a write was not such a payload.  So a
4 GiB image written once and overwritten at random costs an int a block,
and `read`, `object_bytes` and a plain `bytearray` agree byte for byte
(`tests/test_rbd_randwrite_model.py` holds them to each other).

    block_payload(seed, block, generation)
        the block's `block_bytes` bytes at that generation: a 16-byte stamp
        (block, generation) and a run of one seeded pool at an offset the
        pair picks, so that no two (block, generation) are equal and a torn
        or stale block cannot pass for a whole, current one.
    BlockImage.stamp(block, generation) / stamp_run(first, count, generation)
        the writer says: this block now holds that payload.
    BlockImage.write(offset, data) / read(offset, length)
        the byte-granular device.
    BlockImage.object_bytes(index)
        data object `index` (bytes [index << order, (index + 1) << order)
        of the image, cut at the image's end): what the pool holds for it,
        whose k+m shards `reed_sol_van.shards` computes.

The model has no notion of time: the benchmark's stream writes no block
twice after the fill, every write is awaited before the comparison, and a
write that failed fails the run, so each block has exactly one admissible
content when the history ends.
"""

from __future__ import annotations

import functools
import struct

import numpy as np

STAMP = struct.Struct("<QQ")  # block, generation
ZEROS = -1  # the generation of a block never written
LITERAL = -2  # a block whose bytes are kept as written
_POOL_BYTES = 1 << 20


class Payloads:
    """`block_payload` for one seed and block size."""

    def __init__(self, seed: int, block_bytes: int = 4096) -> None:
        if block_bytes <= STAMP.size:
            raise ValueError("a block holds more than its 16-byte stamp")
        self.seed, self.block_bytes = int(seed), int(block_bytes)
        self._tail = self.block_bytes - STAMP.size
        self._pool = memoryview(np.random.default_rng(self.seed).bytes(
            _POOL_BYTES + self._tail))

    def block(self, block: int, generation: int) -> bytes:
        off = (block * 2654435761 + generation * 40503) % _POOL_BYTES
        return b"".join((STAMP.pack(block, generation),
                         self._pool[off:off + self._tail]))


@functools.lru_cache(maxsize=4)
def _payloads(seed: int, block_bytes: int) -> Payloads:
    return Payloads(seed, block_bytes)


def block_payload(seed: int, block: int, generation: int,
                  block_bytes: int = 4096) -> bytes:
    return _payloads(int(seed), int(block_bytes)).block(block, generation)


class BlockImage:
    def __init__(self, seed: int, image_bytes: int, block_bytes: int = 4096,
                 order: int = 22) -> None:
        if image_bytes % block_bytes or (1 << order) % block_bytes:
            raise ValueError("image and objects are whole blocks")
        self.image_bytes, self.block_bytes = int(image_bytes), int(block_bytes)
        self.object_size = 1 << int(order)
        self.payloads = Payloads(seed, block_bytes)
        self.n_blocks = self.image_bytes // self.block_bytes
        self.n_objects = -(-self.image_bytes // self.object_size)
        self._generation = np.full(self.n_blocks, ZEROS, dtype=np.int64)
        self._literal: dict = {}  # block -> bytearray

    # -- the writer's side -----------------------------------------------------

    def stamp(self, block: int, generation: int) -> None:
        if generation < 0:
            raise ValueError("a generation is 0 or more")
        self._generation[block] = generation
        self._literal.pop(block, None)

    def stamp_run(self, first: int, count: int, generation: int) -> None:
        """`stamp` for `count` blocks from `first` on (a fill)."""
        if self._literal:
            for block in range(first, first + count):
                self._literal.pop(block, None)
        self._generation[first:first + count] = generation

    def write(self, offset: int, data: bytes) -> None:
        if offset < 0 or offset + len(data) > self.image_bytes:
            raise ValueError("write beyond the image")
        size, pos = self.block_bytes, 0
        while pos < len(data):
            block, off_in = divmod(offset + pos, size)
            n = min(size - off_in, len(data) - pos)
            kept = self._literal.get(block)
            if kept is None:
                kept = self._literal[block] = bytearray(self._block(block))
                self._generation[block] = LITERAL
            kept[off_in:off_in + n] = data[pos:pos + n]
            pos += n

    # -- the reader's side -----------------------------------------------------

    def generation(self, block: int) -> int:
        return int(self._generation[block])

    def _block(self, block: int) -> bytes:
        gen = int(self._generation[block])
        if gen == ZEROS:
            return bytes(self.block_bytes)
        if gen == LITERAL:
            return bytes(self._literal[block])
        return self.payloads.block(block, gen)

    def read(self, offset: int, length: int) -> bytes:
        if offset < 0 or offset >= self.image_bytes:
            return b""
        end = min(offset + length, self.image_bytes)
        size = self.block_bytes
        first, last = offset // size, (end - 1) // size
        whole = b"".join(self._block(b) for b in range(first, last + 1))
        return whole[offset - first * size:end - first * size]

    def object_bytes(self, index: int) -> bytes:
        return self.read(index * self.object_size, self.object_size)
