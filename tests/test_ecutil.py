"""ECUtil stripe math + cumulative HashInfo + batched multi-stripe encode
(reference src/osd/ECUtil.{h,cc})."""

import os
import zlib

import numpy as np
import pytest

from ceph_tpu.ec.registry import registry
from ceph_tpu.rados.ecutil import HashInfo, StripeInfo, batched_encode


def codec(k=4, m=2):
    return registry.factory("jerasure", "", {
        "plugin": "jerasure", "technique": "reed_sol_van",
        "k": str(k), "m": str(m)})


class TestStripeInfo:
    def test_conversions(self):
        s = StripeInfo(k=4, stripe_width=16384)  # chunk 4096
        assert s.chunk_size == 4096
        assert s.logical_to_prev_chunk_offset(0) == 0
        assert s.logical_to_prev_chunk_offset(16384) == 4096
        assert s.logical_to_prev_chunk_offset(20000) == 4096
        assert s.logical_to_next_chunk_offset(1) == 4096
        assert s.logical_to_next_chunk_offset(16384) == 4096
        assert s.logical_to_prev_stripe_offset(20000) == 16384
        assert s.logical_to_next_stripe_offset(16385) == 32768
        assert s.aligned_logical_offset_to_chunk_offset(32768) == 8192
        assert s.aligned_chunk_offset_to_logical_offset(8192) == 32768

    def test_stripe_bounds_rmw_read_set(self):
        s = StripeInfo(k=2, stripe_width=8192)
        # a 100-byte overwrite at 5000 must read the whole first stripe
        assert s.offset_len_to_stripe_bounds(5000, 100) == (0, 8192)
        # spanning a boundary pulls in both stripes
        assert s.offset_len_to_stripe_bounds(8000, 400) == (0, 16384)
        assert s.offset_len_to_stripe_bounds(8192, 10) == (8192, 8192)

    def test_pad(self):
        s = StripeInfo(k=2, stripe_width=100)
        assert len(s.pad_to_stripe(b"x" * 150)) == 200
        assert len(s.pad_to_stripe(b"x" * 200)) == 200

    def test_invalid_width_rejected(self):
        with pytest.raises(AssertionError):
            StripeInfo(k=3, stripe_width=100)


class TestHashInfo:
    def test_cumulative_append_chaining(self):
        h = HashInfo(3)
        a1 = {0: b"one", 1: b"two", 2: b"par"}
        a2 = {0: b"ONE", 1: b"TWO", 2: b"PAR"}
        h.append(a1)
        h.append(a2)
        assert h.total_chunk_size == 6
        # chained crc == crc of the concatenation (the scrub comparison)
        # algorithm-agnostic: the store's checksum (hardware crc32c when
        # the native layer builds) must chain identically to one pass
        from ceph_tpu.utils.checksum import checksum

        assert h.shard_crc(0) == checksum(b"oneONE")
        assert h.shard_crc(2) == checksum(b"parPAR")

    def test_encode_decode_xattr_roundtrip(self):
        h = HashInfo(2)
        h.append({0: b"abcd", 1: b"efgh"})
        h2 = HashInfo.decode(h.encode())
        assert h2.crcs == h.crcs
        assert h2.total_chunk_size == 4

    def test_unequal_append_rejected(self):
        h = HashInfo(2)
        with pytest.raises(AssertionError):
            h.append({0: b"ab", 1: b"c"})


class TestBatchedEncode:
    def test_matches_per_stripe_loop(self):
        c = codec(k=4, m=2)
        s = StripeInfo(k=4, stripe_width=4 * 1024)
        data = os.urandom(10_000)  # 3 stripes, padded
        loop = batched_encode(c, s, data, queue=None)
        from ceph_tpu.parallel.service import BatchingQueue

        q = BatchingQueue(max_delay=0.001)
        try:
            batched = batched_encode(c, s, data, queue=q)
            assert q.dispatches >= 1
        finally:
            q.close()
        assert len(batched) == len(loop) == 6
        for a, b in zip(batched, loop):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                "batched dispatch diverged from the per-stripe loop"

    def test_single_stripe_short_circuit(self):
        c = codec(k=2, m=1)
        s = StripeInfo(k=2, stripe_width=1 << 16)
        data = os.urandom(1000)
        out = batched_encode(c, s, data, queue=None)
        assert len(out) == 3

    def test_one_dispatch_for_many_stripes(self):
        from ceph_tpu.parallel.service import BatchingQueue

        c = codec(k=4, m=2)
        s = StripeInfo(k=4, stripe_width=4 * 4096)  # reference default unit
        data = os.urandom(64 * 4 * 4096)  # 64 stripes
        q = BatchingQueue(max_delay=0.001)
        try:
            batched_encode(c, s, data, queue=q)
            # the reference would dispatch 64 times; we dispatch ONCE
            assert q.dispatches == 1, q.dispatches
        finally:
            q.close()


class TestQueuePaths:
    def test_single_stripe_rides_the_queue(self):
        """Small (single-stripe) objects must ALSO go through the queue —
        cross-object coalescing of small concurrent writes is the
        dispatch-latency win the design exists for."""
        from ceph_tpu.parallel.service import BatchingQueue

        c = codec(k=2, m=1)
        s = StripeInfo(k=2, stripe_width=4096)
        data = os.urandom(3000)  # one stripe after padding
        loop = batched_encode(c, s, data, queue=None)
        q = BatchingQueue(max_delay=0.001)
        try:
            out = batched_encode(c, s, data, queue=q)
            assert q.dispatches == 1
        finally:
            q.close()
        for a, b in zip(out, loop):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_decode_through_queue_matches_cpu(self):
        from ceph_tpu.parallel.service import BatchingQueue
        from ceph_tpu.rados.ecutil import decode_object

        c = codec(k=4, m=2)
        s = StripeInfo(k=4, stripe_width=4 * 2048)
        data = os.urandom(9 * 4 * 2048 - 777)
        blobs = batched_encode(c, s, data, queue=None)
        # lose two data shards: decode must reconstruct through the queue
        avail = {i: np.asarray(b) for i, b in enumerate(blobs)
                 if i not in (0, 2)}
        want = decode_object(c, s, dict(avail), len(data))
        q = BatchingQueue(max_delay=0.001)
        try:
            got = decode_object(c, s, dict(avail), len(data), queue=q)
            assert q.dispatches == 1
        finally:
            q.close()
        assert got == want == data

    def test_async_variants_coalesce_concurrent_ops(self):
        """N concurrent encodes from one event loop must land in ONE
        device dispatch (the await keeps the loop free to submit)."""
        import asyncio

        from ceph_tpu.parallel.service import BatchingQueue
        from ceph_tpu.rados.ecutil import batched_encode_async

        c = codec(k=2, m=1)
        s = StripeInfo(k=2, stripe_width=4096)
        q = BatchingQueue(max_delay=0.05)  # wide window: all N must land
        bufs = [os.urandom(4096) for _ in range(16)]

        async def go():
            outs = await asyncio.gather(
                *(batched_encode_async(c, s, b, queue=q) for b in bufs))
            return outs

        try:
            outs = asyncio.run(go())
            assert q.dispatches <= 2, \
                f"16 concurrent ops took {q.dispatches} dispatches"
        finally:
            q.close()
        for b, out in zip(bufs, outs):
            ref = batched_encode(c, s, b, queue=None)
            for a, r in zip(out, ref):
                assert np.array_equal(np.asarray(a), np.asarray(r))


class TestPackedbitQueuePaths:
    """The packed-bit production lane through the ecutil plans
    (ops/gf2.py lane promotion): w=8 codec dispatch routes to the
    XOR-schedule queue lanes, byte-identical to the CPU path, with the
    int8-plane lanes behind the CEPH_TPU_PACKEDBIT=0 kill switch."""

    def test_encode_plan_routes_packedbit(self):
        from ceph_tpu.parallel.service import BatchingQueue

        c = codec(k=4, m=2)
        s = StripeInfo(k=4, stripe_width=4 * 2048)
        data = os.urandom(16 * 4 * 2048 - 100)
        want = batched_encode(c, s, data, queue=None)
        q = BatchingQueue(max_delay=0.001)
        try:
            got = batched_encode(c, s, data, queue=q)
            assert q.perf.get("submit_packedbit") == 1, \
                "encode plan did not ride the packed-bit lane"
            assert q.dispatches == 1
        finally:
            q.close()
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_decode_plan_routes_packedbit(self):
        from ceph_tpu.parallel.service import BatchingQueue
        from ceph_tpu.rados.ecutil import decode_object

        c = codec(k=4, m=2)
        s = StripeInfo(k=4, stripe_width=4 * 2048)
        data = os.urandom(5 * 4 * 2048 - 333)
        blobs = batched_encode(c, s, data, queue=None)
        avail = {i: np.asarray(b) for i, b in enumerate(blobs)
                 if i not in (1, 3)}
        q = BatchingQueue(max_delay=0.001)
        try:
            got = decode_object(c, s, dict(avail), len(data), queue=q)
            assert q.perf.get("submit_packedbit") == 1, \
                "decode plan did not ride the packed-bit lane"
        finally:
            q.close()
        assert got == data

    def test_packedbit_kill_switch_pins_int8_lane(self, monkeypatch):
        from ceph_tpu.parallel.service import BatchingQueue

        monkeypatch.setenv("CEPH_TPU_PACKEDBIT", "0")
        c = codec(k=4, m=2)
        s = StripeInfo(k=4, stripe_width=4 * 2048)
        data = os.urandom(4 * 4 * 2048)
        want = batched_encode(c, s, data, queue=None)
        q = BatchingQueue(max_delay=0.001)
        try:
            got = batched_encode(c, s, data, queue=q)
            assert q.perf.get("submit_packedbit") == 0, \
                "packed-bit lane used while disabled"
            assert q.perf.get("submit_packed") == 1
        finally:
            q.close()
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_w16_stays_off_the_packedbit_lane(self):
        """Packed-bit is the w=8 byte-layout lane; w=16 pools must keep
        riding the int8-plane lanes."""
        from ceph_tpu.parallel.service import BatchingQueue

        c = registry.factory("jerasure", "", {
            "plugin": "jerasure", "technique": "reed_sol_van",
            "k": "3", "m": "2", "w": "16"})
        s = StripeInfo(k=3, stripe_width=3 * 2048)
        data = os.urandom(4 * 3 * 2048)
        want = batched_encode(c, s, data, queue=None)
        q = BatchingQueue(max_delay=0.001)
        try:
            got = batched_encode(c, s, data, queue=q)
            assert q.perf.get("submit_packedbit") == 0, \
                "w=16 dispatched on the packed-bit lane"
            assert q.perf.get("submit_packed") == 1
        finally:
            q.close()
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestGroupEncode:
    def test_group_encode_matches_per_buffer(self):
        """batched_encode_group_async: one group submit, per-buffer shard
        lists byte-identical to the per-buffer path."""
        import asyncio

        import numpy as np

        from ceph_tpu.ec.registry import registry
        from ceph_tpu.parallel.service import BatchingQueue
        from ceph_tpu.rados.ecutil import (StripeInfo, batched_encode,
                                           batched_encode_group_async)

        codec = registry.factory("jerasure", "", {
            "plugin": "jerasure", "technique": "reed_sol_van",
            "k": "4", "m": "2"})
        sinfo = StripeInfo(4, 4 * 4096)
        rng = np.random.default_rng(21)
        bufs = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
                for n in (4 * 4096 * 3, 4 * 4096 * 2, 1000)]
        q = BatchingQueue(max_delay=0.01, mesh=False)
        try:
            async def go():
                return await batched_encode_group_async(
                    codec, sinfo, bufs, queue=q)

            group = asyncio.run(go())
            for data, shards in zip(bufs, group):
                want = batched_encode(codec, sinfo, data, queue=None)
                assert len(shards) == len(want)
                for a, b in zip(shards, want):
                    assert np.array_equal(np.asarray(a), np.asarray(b)), \
                        "group-encoded shard differs from per-buffer encode"
        finally:
            q.close()

    def test_scatter_decode_matches_contiguous(self):
        """decode_object(scatter=True) returns a BufferList whose bytes
        equal the contiguous decode for the all-data fast path."""
        import numpy as np

        from ceph_tpu.ec.registry import registry
        from ceph_tpu.rados.ecutil import (StripeInfo, batched_encode,
                                           decode_object)
        from ceph_tpu.rados.messenger import BufferList

        codec = registry.factory("jerasure", "", {
            "plugin": "jerasure", "technique": "reed_sol_van",
            "k": "3", "m": "2"})
        sinfo = StripeInfo(3, 3 * 512)
        rng = np.random.default_rng(22)
        for size in (3 * 512 * 4, 3 * 512 * 4 - 100, 3 * 512 * 2 + 1):
            data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
            shards = batched_encode(codec, sinfo, data)
            avail = {i: np.asarray(shards[i]) for i in range(3)}
            flat = decode_object(codec, sinfo, dict(avail), size)
            scat = decode_object(codec, sinfo, dict(avail), size,
                                 scatter=True)
            assert isinstance(scat, BufferList), type(scat)
            assert len(scat) == size
            assert scat.tobytes() == flat == data
