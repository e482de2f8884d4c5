"""EC stripe math + cumulative shard hashes (reference src/osd/ECUtil.{h,cc}).

`StripeInfo` is the reference's ``ECUtil::stripe_info_t`` (ECUtil.h:27-80):
an object is logically striped in ``stripe_width = k * chunk_size`` units;
these helpers convert logical byte offsets/lengths to per-shard chunk
offsets and back, and round ranges out to stripe boundaries — the math the
RMW write plan and shard reads are built on.

`HashInfo` is the reference's cumulative per-shard crc32 state
(ECUtil.h:101-160): updated on every append with the NEW bytes only
(``crc32(next, prev_crc)`` chaining), persisted as an object xattr
(``hinfo_key``), and compared by deep scrub against a running crc of the
stored shard.

`batched_encode` is the north-star loop inverted: where the reference
dispatches the codec once per stripe (ECUtil.cc:123-160), this slices a
buffer into stripes and submits them ALL to the stripe-batching queue as a
single device dispatch (ceph_tpu/parallel/service.py), returning the
per-shard concatenations.
"""

from __future__ import annotations

import json
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.perf_counters import PerfCountersBuilder
from ceph_tpu.parallel.service import StripeRows, subchunk_geometry
from ceph_tpu.rados.extent_cache import keepable


@dataclass(frozen=True)
class StripeInfo:
    """stripe_info_t role: k data chunks x chunk_size = stripe_width."""

    k: int
    stripe_width: int

    def __post_init__(self):
        assert self.stripe_width % self.k == 0, \
            "stripe_width must be a multiple of k"

    @property
    def chunk_size(self) -> int:
        return self.stripe_width // self.k

    # -- logical <-> chunk conversions (ECUtil.h:35-79) ----------------------

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        """Chunk offset of the stripe CONTAINING logical `offset`."""
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        """Chunk offset just PAST logical `offset`, rounded up."""
        return -(-offset // self.stripe_width) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        return -(-offset // self.stripe_width) * self.stripe_width

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return offset // self.k

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return offset * self.k

    def offset_len_to_stripe_bounds(self, offset: int,
                                    length: int) -> Tuple[int, int]:
        """Round a logical extent OUT to stripe boundaries (the RMW read
        set, ECUtil.h:55-60): returns (start, len)."""
        start = self.logical_to_prev_stripe_offset(offset)
        end = self.logical_to_next_stripe_offset(offset + length)
        return start, end - start

    def pad_to_stripe(self, data: bytes) -> bytes:
        want = self.logical_to_next_stripe_offset(len(data))
        if want == len(data):
            return data  # aligned: no copy on the hot path
        if not isinstance(data, (bytes, bytearray)):
            # buffer view (an rx blob landed uninitialized): materialize
            # for the pad concat — only UNALIGNED tails pay this
            data = bytes(data)
        return data + b"\x00" * (want - len(data))


class HashInfo:
    """Cumulative per-shard crc32s, chained across appends (ECUtil.h:101).

    ``dirty`` marks a record whose non-self entries went stale: a partial
    (spliced) overwrite rewrites one shard's bytes without the primary
    holding every other shard's blob, so each shard refreshes only its OWN
    crc entry.  Deep scrub always trusts the self entry; cross-shard
    comparison is only meaningful while the record is clean (the reference
    sidesteps this by disabling hinfo under ec_overwrites)."""

    XATTR_KEY = "hinfo_key"

    def __init__(self, n_shards: int, total_chunk_size: int = 0,
                 crcs: Optional[List[int]] = None, dirty: bool = False):
        self.total_chunk_size = total_chunk_size
        self.crcs = list(crcs) if crcs else [0] * n_shards
        self.dirty = dirty

    def append(self, shard_chunks: Dict[int, bytes]) -> None:
        """Fold the NEW chunk bytes of one append into each shard's
        running crc (crc32 chaining, as the reference's bufferlist crc32c
        cumulative update does)."""
        from ceph_tpu.utils.checksum import checksum

        sizes = {len(c) for c in shard_chunks.values()}
        assert len(sizes) == 1, "appends must be chunk-aligned and equal"
        for shard, chunk in shard_chunks.items():
            self.crcs[shard] = checksum(chunk, self.crcs[shard])
        self.total_chunk_size += sizes.pop()

    def shard_crc(self, shard: int) -> int:
        return self.crcs[shard]

    def encode(self) -> bytes:
        return json.dumps({"total_chunk_size": self.total_chunk_size,
                           "crcs": self.crcs, "dirty": self.dirty}).encode()

    @classmethod
    def decode(cls, blob: bytes) -> "HashInfo":
        d = json.loads(blob)
        return cls(len(d["crcs"]), d["total_chunk_size"], d["crcs"],
                   d.get("dirty", False))


def concat_safe(codec) -> bool:
    """True when the codec transforms a chunk as independent aligned
    blocks, making the concatenation of per-stripe chunks itself a valid
    chunk set: byte-layout codecs operate column-wise per byte, and the
    packet (bitmatrix) family operates per w*packetsize block — both
    divide chunks into units the per-stripe alignment already respects.
    Only sub-chunk codecs (CLAY) derive intra-chunk structure from the
    TOTAL chunk size and must be driven stripe by stripe: by their codec
    on the CPU paths, and as whole chunks on the queue's "subchunk" lane
    (_lane), which is told the chunk and keeps the stripes apart."""
    try:
        return codec.get_sub_chunk_count() == 1
    except Exception:
        return False


def _mapped_shard_list(codec, data_rows: np.ndarray,
                       coding_rows: np.ndarray) -> List[np.ndarray]:
    """Arrange logical data/coding rows into PHYSICAL shard order (the
    chunk_index remap base.encode applies for 'mapping' profiles)."""
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    out: List[Optional[np.ndarray]] = [None] * n
    for logical in range(n):
        row = data_rows[logical] if logical < k else coding_rows[logical - k]
        out[codec.chunk_index(logical)] = row
    return out  # type: ignore[return-value]


# the `ecplan` set: how many stripe plans the served paths made (its time
# is the loop set's `self_ecplan`, from the sections below).  One per
# process, listed by every OSD's collection like `gf2_sched`.
ECPLAN_PERF = (PerfCountersBuilder("ecplan")
               .add_u64_counter("plans", "encode/decode plans built for "
                                         "the queue")
               .add_u64_counter("stripes", "stripes those plans covered")
               .add_u64_counter("packs", "resident bit-rows packed to "
                                         "bytes (_pack_rows)")
               .add_u64_counter("loop_layout_bytes",
                                "row bytes an encode plan laid out on its "
                                "caller's thread (no queue, or a source "
                                "that may change): the rest is "
                                "ec_tpu.staged_layout_bytes")
               .create_perf_counters())


def lane_for(codec, resident: bool = False, cols: int = 0):
    """THE rule for which BatchingQueue lane (a key of
    parallel/service.LANES) applies a bit-matrix for this codec, and the
    dtype that lane takes the matrix in: (kind, dtype), or None where no
    lane does.  The codec's bit_layout and w decide, and its sub-chunk
    count, nothing else:
    a sub-chunk code (CLAY) whose encode is one linear round
    (`encode_geometry`) rides "subchunk" with that geometry in the
    matrix's place, keeps no residents, and has no lane otherwise;
    packet-layout codes (cauchy/liberation family) ride "packetrows", the
    packed-bit lane whose layout stages are block transposes, and keep no
    residents; w=8 byte-layout codes the packed-bit pair (static XOR
    schedule over u32 plane words — a resident's `cols` must be whole
    words); the rest (w=16/w=4, or CEPH_TPU_PACKEDBIT=0) the int8-plane
    pair, whose matrix is a matmul operand.  The plans below, the
    resident encode and the tpu plugin's direct seam all ask here."""
    from ceph_tpu.ops.gf2 import packedbit_enabled

    if codec.get_sub_chunk_count() > 1:
        linear = getattr(codec, "encode_geometry", lambda: None)()
        return None if resident or linear is None else ("subchunk", np.uint8)
    if getattr(codec, "bit_layout", "byte") == "packet":
        return None if resident else ("packetrows", np.uint8)
    if (getattr(codec, "w", 8) == 8 and not (resident and cols % 32)
            and packedbit_enabled()):
        return ("packedbit_resident" if resident else "packedbit"), np.uint8
    return ("resident" if resident else "packed"), np.int8


def _lane(codec, sinfo: StripeInfo):
    """The lane this codec's encode/decode plans ride, as lane_for's
    (kind, dtype) plus the packet size on the packet-layout lane and the
    chunk on the sub-chunk lane.  None when no lane takes the codec: a
    chunk remap, chunks that are not whole w*packetsize blocks, a
    sub-chunk code whose encode is not one linear round (lane_for) or
    whose sub-chunks are not whole u32 plane words."""
    if codec.get_chunk_mapping():
        return None
    lane = lane_for(codec)
    if lane is None:
        return None
    if lane[0] == "packetrows":
        if sinfo.chunk_size % (codec.w * codec.packetsize):
            return None
        return (*lane, codec.packetsize)
    if lane[0] == "subchunk":
        if sinfo.chunk_size % (codec.get_sub_chunk_count() * 32):
            return None
        return (*lane, sinfo.chunk_size)
    return lane


def _encode_matrix(codec, lane):
    """What an encode request of `codec` carries on `lane` (_lane's):
    the code's geometry on the sub-chunk lane, else the codec's bit
    generator, or None where it has none."""
    if lane[0] == "subchunk":
        return subchunk_geometry(*codec.encode_geometry())
    return codec.bit_generator()


def _lane_item(lane, codec, bitmatrix, rows, out_rows: int):
    """The lane request for applying `bitmatrix` (an encode generator,
    an inverted decode signature, a sub-chunk code's geometry) to
    `[n, n_stripes*chunk]` rows (or the StripeRows that names them), as
    BatchingQueue.submit / submit_group take it: (mbits, rows, w,
    out_rows, kind[, packetsize])."""
    kind, dtype, *packetsize = lane
    return (np.asarray(bitmatrix).astype(dtype), rows,
            getattr(codec, "w", 8), out_rows, kind, *packetsize)


def _stripe_rows(sinfo: StripeInfo, data, queued: bool = True):
    """The `[k, n_stripes*chunk]` data rows of the non-empty buffer
    `data` as an encode request's source.  A queued request over a source
    that cannot change (extent_cache.keepable: `bytes`, or a read-only
    view of the whole of a buffer that owns its memory — what the wire
    delivers and _do_write passes on) only NAMES the rows (StripeRows):
    the queue's thread writes pad and stripe order straight into its
    staging buffer, and no byte of the object is copied here.  Anything
    else — a writable view somebody may reuse, a slice of a larger
    buffer, no queue to stage it — is laid out now, on the caller's
    thread, in one pass."""
    src = StripeRows(np.frombuffer(data, dtype=np.uint8), sinfo.k,
                     sinfo.chunk_size)
    if queued and keepable(data):
        return src
    ECPLAN_PERF.inc("loop_layout_bytes", src.nbytes)
    return src.rows()


@tracing.sectioned("ecplan", "encode_plan")
def _encode_plan_parts(codec, sinfo: StripeInfo, data):
    """The submit-free half of the queue encode plan for the non-empty
    buffer `data`: when the codec is batchable (a bit seam or a
    sub-chunk geometry, no chunk remap), returns (item, reassemble) —
    the lane request (_lane_item) a
    caller hands to BatchingQueue.submit or (with several buffers) to
    submit_group as one whole-stripe-group handoff, and what turns that
    request's result into the per-shard blob list.  None when the queue
    path does not apply."""
    lane = _lane(codec, sinfo)
    mbits = _encode_matrix(codec, lane) if lane is not None else None
    if mbits is None:
        return None
    k = codec.get_data_chunk_count()
    m = codec.get_chunk_count() - k
    # columns = stripes concatenated; one submit -> one device call.  The
    # layout stages run on the device: byte and packet layout alike give
    # the queue [k, n_stripes*chunk] rows, here mostly by name
    src = _stripe_rows(sinfo, data)
    ECPLAN_PERF.inc("plans")
    ECPLAN_PERF.inc("stripes", src.shape[1] // sinfo.chunk_size)
    item = _lane_item(lane, codec, mbits, src, m)

    @tracing.sectioned("ecplan", "reassemble")
    def reassemble(result) -> List[np.ndarray]:
        # the data rows ARE the per-shard data blobs, each contiguous:
        # the staging buffer's own rows for a source the queue laid out
        # (its result carries them), else the rows laid out above —
        # stripe-major views of `data` would make every consumer (store
        # write, sub-write framing) pay an ascontiguousarray copy a shard
        parity, rows = (result if isinstance(src, StripeRows)
                        else (result, src))
        p = np.asarray(parity).reshape(m, src.shape[1])
        return [rows[i] for i in range(k)] + [p[j] for j in range(m)]

    return item, reassemble


def _queue_encode_plan(codec, sinfo: StripeInfo, data, queue, span=None):
    """When the codec/queue combination is batchable (a bit seam, byte or
    packet layout, or a sub-chunk code with a linear encode; no chunk
    remap), submit the whole non-empty buffer as
    ONE queue request and return (future, reassemble) — reassemble turns
    the future's result into the per-shard blob list.  None when the
    queue path does not apply (mapped codecs, codecs without a bit seam,
    sub-chunk geometries _lane refuses)."""
    parts = _encode_plan_parts(codec, sinfo, data)
    if parts is None:
        return None
    item, reassemble = parts
    return queue.submit(*item, span=span), reassemble


def batched_encode(codec, sinfo: StripeInfo, data: bytes,
                   queue=None, span=None) -> List[np.ndarray]:
    """Encode a multi-stripe buffer with ONE device dispatch.

    The reference's ECUtil::encode calls the codec once per stripe_width
    piece (ECUtil.cc:123-160, the ▓ hot loop); on a TPU that per-stripe
    dispatch is the bottleneck, so here every stripe rides one batched
    call: the buffer is re-interleaved into per-shard rows
    (`[k, n_stripes*chunk]`) and the codec transforms all stripes at once
    — through the shared BatchingQueue when one is provided (byte- and
    packet-layout codecs, and CLAY on its sub-chunk lane: _lane), else
    through encode_chunks (one
    direct device dispatch for plugin=tpu, on the caller's thread).
    Byte-identical
    to the per-stripe loop for every concat-safe codec (see concat_safe);
    without a queue CLAY takes the per-stripe path.  Returns one concatenated per-shard
    buffer each, `[n_shards][n_stripes*chunk]`, in physical shard order.

    Blocking variant (tests/benchmark); daemons on an event loop use
    ``batched_encode_async`` so concurrent ops actually COALESCE — a
    blocking .result() on the loop thread would serialize submissions.
    """
    k = codec.get_data_chunk_count()
    n = codec.get_chunk_count()
    assert sinfo.k == k
    if queue is not None and len(data):
        # the interface's bit seam drives ANY byte- or packet-layout
        # codec through the queue's lanes, a sub-chunk code's geometry
        # its own lane; mapped codecs and whatever _lane refuses take
        # the encode_chunks/per-stripe paths below.
        # Single-stripe objects ride the queue too — coalescing across
        # OBJECTS/ops is the point (SURVEY.md §7.5), and small concurrent
        # writes are exactly the dispatch-latency-bound workload.
        # Empty objects (len 0) cannot take the queue path — the codec's
        # own encode handles the degenerate padding rules.
        planned = _queue_encode_plan(codec, sinfo, data, queue, span=span)
        if planned is not None:
            fut, reassemble = planned
            return reassemble(fut.result())
    # no queue to stage it: the caller's thread pads and lays out
    padded = sinfo.pad_to_stripe(data)
    n_stripes = max(1, len(padded) // sinfo.stripe_width)
    # stripe-major view (no copy): [n_stripes, k, chunk]
    arr = (np.frombuffer(padded, dtype=np.uint8).reshape(
               n_stripes, k, sinfo.chunk_size)
           if len(padded) else None)
    if n_stripes <= 1 or arr is None:
        # one stripe IS one dispatch: the codec encodes the whole buffer
        enc = codec.encode(set(range(n)), padded)
        return [np.asarray(enc[i]) for i in range(n)]
    if concat_safe(codec):
        # ONE encode_chunks call over all stripes: per-shard rows are the
        # stored blob layout, so no post-hoc concatenation either
        rows = np.ascontiguousarray(
            arr.transpose(1, 0, 2).reshape(k, n_stripes * sinfo.chunk_size))
        coding = np.asarray(codec.encode_chunks(rows))
        return _mapped_shard_list(codec, rows, coding)
    # sub-chunk codecs: per-stripe loop (the reference's shape)
    shards: List[List[np.ndarray]] = [[] for _ in range(n)]
    for s in range(n_stripes):
        enc = codec.encode(set(range(n)), arr[s].tobytes())
        for i in range(n):
            shards[i].append(np.asarray(enc[i]))
    return [np.concatenate(chunks) for chunks in shards]


async def batched_encode_async(codec, sinfo: StripeInfo, data: bytes,
                               queue=None, span=None) -> List[np.ndarray]:
    """Event-loop-friendly batched_encode: the queue future is AWAITED,
    so concurrent ops keep submitting while this one waits — that
    concurrency is what the queue coalesces into one device dispatch.
    The loop copies nothing of a stable `data` (_stripe_rows): the queue's
    thread reads it after this returns to the loop, so the caller leaves
    it unwritten until the encode is back."""
    if queue is not None and len(data):
        import asyncio

        planned = _queue_encode_plan(codec, sinfo, data, queue, span=span)
        if planned is not None:
            fut, reassemble = planned
            return reassemble(await asyncio.wrap_future(fut))
    return batched_encode(codec, sinfo, data, queue=None)


async def batched_encode_group_async(codec, sinfo: StripeInfo, buffers,
                                     queue=None, span=None):
    """Encode SEVERAL objects' buffers with ONE group-aware queue submit
    (BatchingQueue.submit_group): the whole-stripe-group handoff seam —
    a recovery round's re-encodes, or a messenger rx batch of writes,
    reach the EC tier as one buffer-list submission (one queue lock, one
    worker wakeup, one coalesced dispatch window) instead of per-object
    submits that only the delay window may happen to coalesce.

    Returns the per-buffer shard lists, index-aligned with ``buffers``.
    Buffers the queue plan cannot take (mapped codecs, geometries _lane
    refuses, empty objects, no queue) fall back to the plain
    batched_encode path."""
    import asyncio

    out: List[Optional[List[np.ndarray]]] = [None] * len(buffers)
    items = []
    metas = []
    for i, data in enumerate(buffers):
        if queue is not None and len(data):
            parts = _encode_plan_parts(codec, sinfo, data)
            if parts is not None:
                items.append(parts[0])
                metas.append((i, parts[1]))
                continue
        out[i] = batched_encode(codec, sinfo, data, queue=None)
    if items:
        futs = queue.submit_group(items, span=span)
        for (i, reassemble), fut in zip(metas, futs):
            out[i] = reassemble(await asyncio.wrap_future(fut))
    return out


@tracing.sectioned("ecplan", "decode_plan")
def _queue_decode_plan(codec, sinfo: StripeInfo,
                       arrays: Dict[int, np.ndarray], object_size: int,
                       queue, span=None):
    """Queue submission for a reconstructing decode: CPU picks/inverts
    the decode matrix via the codec's OWN selection rule (LRU-cached per
    erasure signature, the ISA table cache design), the device applies it
    — so decode and recovery ride the same batched kernel as encode,
    packet-layout pools on the same lane as their encode (the inverted
    bit-matrix of an erasure signature is one more matrix for it).
    Returns (future, finish) with finish(rows) -> the reconstructed
    logical bytes trimmed to object_size, or None when the queue path
    does not apply."""
    lane = _lane(codec, sinfo)
    if (lane is None or not concat_safe(codec)
            or not hasattr(codec, "decode_selection")):
        return None
    blob_len = len(next(iter(arrays.values())))
    if blob_len == 0 or blob_len % sinfo.chunk_size:
        return None  # degenerate/ragged blobs: codec paths handle them
    k = codec.get_data_chunk_count()
    cs = sinfo.chunk_size
    n_stripes = blob_len // cs
    if all(i in arrays for i in range(k)):
        return None  # nothing erased that matters: pure de-interleave
    try:
        chosen, inv = codec.decode_selection(set(range(k)), set(arrays))
    except Exception:
        return None
    if any(c not in arrays for c in chosen):
        return None
    # dispatch ONLY the missing data rows (available ones pass through):
    # the matmul shrinks from k rows to n_lost — same trimming the codec
    # CPU path does, so queue and CPU decode stay work-equivalent
    missing = sorted(c for c in range(k) if c not in arrays)
    w = codec.w
    if lane[0] == "packetrows":
        # the bitmatrix codecs invert at bit level: chunk c's w rows
        inv_bm = np.vstack([inv[c * w:(c + 1) * w] for c in missing])
    else:
        from ceph_tpu.ec.matrices import matrix_to_bitmatrix

        inv_bm = matrix_to_bitmatrix(inv[missing], w)
    src = np.ascontiguousarray(np.stack([arrays[c] for c in chosen]))
    # on the schedule lanes the inverted signature matrix compiles to its
    # own static XOR schedule behind the gf2 LRU (per-decode-signature
    # compilation — the ErasureCodeIsaTableCache design at compile scope)
    fut = queue.submit(
        *_lane_item(lane, codec, inv_bm, src, len(missing)), span=span)

    @tracing.sectioned("ecplan", "decode_finish")
    def finish(rows: np.ndarray) -> bytes:
        rebuilt = np.asarray(rows)
        full = np.empty((k, n_stripes * cs), dtype=np.uint8)
        for i, c in enumerate(missing):
            full[c] = rebuilt[i]
        for c in range(k):
            if c not in missing:
                full[c] = arrays[c]
        # de-interleave [k, S, cs] -> stripe-major logical bytes
        r = full.reshape(k, n_stripes, cs).transpose(1, 0, 2)
        return r.reshape(-1)[:object_size].tobytes()

    return fut, finish


def _all_data_fast(codec, arrays: Dict[int, np.ndarray], cs: int,
                   n_stripes: int, object_size: int,
                   scatter: bool = False) -> Optional[bytes]:
    """When every DATA shard is present (the normal, non-degraded read)
    reconstruction is pure de-interleave — no GF math, no codec, no
    device: one strided gather into the output buffer.  The reference's
    read path similarly skips decode when want ⊆ avail
    (ECBackend::CallClientContexts with no reconstruction needed).
    Identity-mapped, concat-safe codecs only; returns None otherwise.

    With ``scatter=True`` the gather copy itself disappears: the result
    is a messenger BufferList of per-stripe chunk VIEWS over the shard
    buffers in logical order — the wire path writev's them as one blob
    (the reference's bufferlist read reply), so a whole-object read never
    materializes a contiguous copy on the primary at all."""
    k = codec.get_data_chunk_count()
    if (n_stripes <= 1 or not concat_safe(codec)
            or codec.get_chunk_mapping()
            or any(c not in arrays for c in range(k))):
        return None
    want = n_stripes * cs
    for c in range(k):
        if len(arrays[c]) < want:
            return None  # short shard: let the codec's padding rules run
    if scatter:
        from ceph_tpu.rados.messenger import BufferList

        views = [memoryview(np.ascontiguousarray(arrays[c][:want]))
                 for c in range(k)]
        segs = []
        remaining = object_size
        base = 0
        for _ in range(n_stripes):
            for c in range(k):
                if remaining <= 0:
                    break
                n = cs if remaining >= cs else remaining
                segs.append(views[c][base:base + n])
                remaining -= n
            base += cs
        return BufferList(segs)
    out = np.empty(n_stripes * k * cs, dtype=np.uint8)
    view = out.reshape(n_stripes, k, cs)
    for c in range(k):
        view[:, c, :] = arrays[c][:want].reshape(n_stripes, cs)
    return out[:object_size].tobytes()


def decode_object(codec, sinfo: StripeInfo,
                  blobs: Dict[int, np.ndarray], object_size: int,
                  queue=None, span=None, scatter: bool = False) -> bytes:
    """Reconstruct a striped object from per-shard blobs (each the
    concatenation of that shard's per-stripe chunks) and de-interleave
    back to logical byte order, trimmed to `object_size`.

    Concat-safe codecs decode ALL stripes in one codec.decode call — the
    multi-stripe mirror of the reference's per-stripe
    objects_read_and_reconstruct loop (ECBackend.cc:2401, ECUtil.cc:25-60
    decode) collapsed into a single device dispatch.

    ``scatter=True`` permits a BufferList return on the all-data fast
    path (zero-copy stripe views; see _all_data_fast) — callers that hand
    the result to the messenger opt in; everyone else gets bytes."""
    k = codec.get_data_chunk_count()
    cs = sinfo.chunk_size
    arrays = {s: np.asarray(b, dtype=np.uint8) for s, b in blobs.items()}
    blob_len = len(next(iter(arrays.values())))
    n_stripes = max(1, blob_len // cs)
    fast = _all_data_fast(codec, arrays, cs, n_stripes, object_size,
                          scatter=scatter)
    if fast is not None:
        return fast
    if queue is not None:
        planned = _queue_decode_plan(codec, sinfo, arrays, object_size, queue,
                                     span=span)
        if planned is not None:
            fut, finish = planned
            return finish(fut.result())
    if n_stripes <= 1 or not concat_safe(codec):
        if n_stripes <= 1:
            return bytes(codec.decode_concat(arrays)[:object_size])
        pieces: List[bytes] = []
        for s in range(n_stripes):
            stripe_chunks = {c: a[s * cs:(s + 1) * cs]
                             for c, a in arrays.items()}
            pieces.append(bytes(codec.decode_concat(stripe_chunks)))
        return b"".join(pieces)[:object_size]
    # decode_concat over whole blobs yields the data rows (shard-major);
    # de-interleave [k, S, cs] -> stripe-major logical bytes
    rows = np.frombuffer(codec.decode_concat(arrays), dtype=np.uint8)
    rows = rows.reshape(k, n_stripes, cs).transpose(1, 0, 2)
    return rows.reshape(-1)[:object_size].tobytes()


async def decode_object_async(codec, sinfo: StripeInfo,
                              blobs: Dict[int, np.ndarray],
                              object_size: int, queue=None,
                              span=None, scatter: bool = False) -> bytes:
    """Event-loop-friendly decode_object (see batched_encode_async)."""
    if queue is not None:
        import asyncio

        arrays = {s: np.asarray(b, dtype=np.uint8) for s, b in blobs.items()}
        blob_len = len(next(iter(arrays.values())))
        n_stripes = max(1, blob_len // sinfo.chunk_size)
        fast = _all_data_fast(codec, arrays, sinfo.chunk_size, n_stripes,
                              object_size, scatter=scatter)
        if fast is not None:
            return fast
        planned = _queue_decode_plan(codec, sinfo, arrays, object_size, queue,
                                     span=span)
        if planned is not None:
            fut, finish = planned
            return finish(await asyncio.wrap_future(fut))
    return decode_object(codec, sinfo, blobs, object_size, queue=None,
                         scatter=scatter)


# -- bit-planar residency (the resident store: ceph_tpu/rados/pagestore.py) --
#
# Shards stay in HBM as bit-planes across encode -> decode -> recovery,
# and the pack/unpack boundary is paid once, when bytes enter or leave the
# device tier.  The reference's per-stripe hot loop
# (src/osd/ECUtil.cc:123-160) keeps its buffer cache-resident for one
# stripe; residency here spans pipeline stages.  Byte-layout, unmapped,
# concat-safe codecs only — the same
# eligibility as the batching-queue encode plan.  For w=8 codecs the
# resident layout is PACKED-BIT u32 words (the production lane, 1 HBM
# byte per data byte and the measured 1.45x XOR-schedule kernel);
# w=16/w=4 pools keep int8 planes.  planar_rows/planar_object_bytes tell
# the layouts apart by the resident's dtype.


def planar_eligible(codec) -> bool:
    # packet-layout pools encode and decode on the queue (_lane) but keep
    # no residents: an install of their rows is PERF.md section 7's
    # open question
    return (getattr(codec, "bit_layout", "byte") == "byte"
            and not codec.get_chunk_mapping()
            and concat_safe(codec)
            and codec.bit_generator() is not None)


async def planar_encode_async(codec, sinfo: StripeInfo, data: bytes,
                              queue=None, span=None):
    """Encode with planar residency: the data rows ride the queue's
    RESIDENT lane — one fused batched device call (unpack + matmul +
    parity pack) shared with every concurrent op — and come back as
    (packed parity for persistence, planar rows to keep HBM-resident).
    Submission does no device work on the caller's thread, so concurrent
    ops coalesce exactly like the packed lane.  Returns (blobs, all_bits,
    n_rows, n_cols, w) — blobs is the per-shard host list (same contract
    as batched_encode); w MUST be recorded with the resident (w=16/w=4
    pools unpack to different plane layouts) — or None when the codec is
    not planar-eligible."""
    import asyncio

    with tracing.section("ecplan", "planar_plan"):
        if not planar_eligible(codec) or not len(data):
            return None
        k = codec.get_data_chunk_count()
        n = codec.get_chunk_count()
        m = n - k
        w = getattr(codec, "w", 8)
        # the data rows: by name where the queue's thread can lay them
        # out (no copy here), laid out now otherwise
        flat = _stripe_rows(sinfo, data, queued=queue is not None)
        L = flat.shape[1]
        ECPLAN_PERF.inc("plans")
        ECPLAN_PERF.inc("stripes", L // sinfo.chunk_size)
        # (w=8 byte codecs have whole u32 words per plane row: chunk_size
        # is a multiple of w*4=32)
        kind, dtype = lane_for(codec, resident=True, cols=L)
        mbits = np.asarray(codec.bit_generator()).astype(dtype)
    if queue is not None:
        parity, all_bits, *rows = await asyncio.wrap_future(
            queue.submit(mbits, flat, w, m, kind, span=span))
        if rows:
            flat, = rows  # the staging buffer's own rows
    else:
        from ceph_tpu.ops.gf2 import (bucket_columns, gf2_encode_resident,
                                      gf2_encode_packedbit_resident)

        Lb = bucket_columns(L)  # pow2 bucketing bounds XLA recompiles
        buf = flat
        if Lb != L:
            buf = np.zeros((k, Lb), dtype=np.uint8)
            buf[:, :L] = flat
        if kind == "packedbit_resident":
            parity, all_bits = gf2_encode_packedbit_resident(mbits, buf)
        else:
            parity, all_bits = gf2_encode_resident(mbits, buf, w, m)
        parity = np.asarray(parity)
    with tracing.section("ecplan", "planar_reassemble"):
        parity = parity[:, :L]
        blobs = [flat[i] for i in range(k)] + [parity[j] for j in range(m)]
    return blobs, all_bits, n, L, w


# the codec/slab-host-roundtrip lint exemption: _pack_rows IS the
# declared device->host exit for slab-gather results in this module
SLAB_IO_BOUNDARY = ("_pack_rows",)


@tracing.sectioned("ecplan", "pack_rows")
def _pack_rows(bits, w: int, n_rows: int, L: int,
               store=None) -> np.ndarray:
    """Resident bit-rows -> packed [n_rows, L] uint8 (the one exit
    boundary, shared by every planar_* helper; dtype tells the packed-bit
    u32 lane apart from int8 planes).  On a device-arm paged store the
    gather result is a device array and the np.asarray here is the
    single d2h of the read — counted on the store (``d2h_gathers``)
    when the caller hands it in."""
    t0 = time.monotonic()
    if np.dtype(bits.dtype) == np.uint32:
        from ceph_tpu.ops.gf2 import from_packedbit

        out = np.asarray(from_packedbit(bits, n_rows))[:, :L]
    else:
        from ceph_tpu.ops.gf2 import from_planar

        out = np.asarray(from_planar(bits, w, n_rows))[:, :L]
    ECPLAN_PERF.inc("packs")
    note = getattr(store, "note_d2h", None)
    if note is not None:
        note()
        # the store's exit boundary: its own read() ticks pack_s, and so
        # does the served read, which packs here
        store.perf.tinc("pack_s", time.monotonic() - t0)
    return out


@tracing.sectioned("ecplan", "planar_rows")
def planar_rows(store, key, version) -> Optional[List[np.ndarray]]:
    """All n shard rows packed from the planar resident under `key`, or
    None when absent, at a different version, or PARTIAL (a paged
    resident whose parity pages were shed serves object reads but not
    whole-stripe re-encodes).  ONE device pack serves recovery/repair
    re-encodes with no matmul at all — the resident IS the encoded
    object."""
    got = store.touch(key)
    if got is None:
        return None
    w, n_rows, meta = got
    if not meta or meta[0] != version:
        return None
    bits = store.gather_rows(key, 0, n_rows * w)
    if bits is None:
        return None
    rows = _pack_rows(bits, w, n_rows, meta[1], store=store)
    return [rows[i] for i in range(n_rows)]


@tracing.sectioned("ecplan", "planar_shard_bytes")
def planar_shard_bytes(store, key, version, shard: int) -> Optional[bytes]:
    """ONE shard's packed bytes from the resident's bit-rows — the
    writeback flush/sub-read shape: a dirty resident's deferred local
    shard apply materializes exactly the blob the write-through path
    would have stored (byte-identity of the packed-bit lane)."""
    got = store.entry_info(key)
    if got is None:
        return None
    w, _n_rows, meta = got
    if not meta or meta[0] != version:
        return None
    bits = store.gather_rows(key, shard * w, (shard + 1) * w)
    if bits is None:
        return None
    return _pack_rows(bits, w, 1, meta[1],
                      store=store).reshape(-1).tobytes()


@tracing.sectioned("ecplan", "planar_object_bytes")
def planar_object_bytes(store, key, version, k: int, cs: int,
                        object_size: int) -> Optional[bytes]:
    """The logical object bytes packed from the planar resident's DATA
    rows (a reconstructing read with zero shard reads and zero decode),
    or None when absent/stale.  The pack result memoizes in the store's
    exit-boundary memo (dies with the entry / on version change), so a
    cache-tier resident read many times pays the device pack ONCE —
    the store's 'pack once per resident lifetime' contract held under
    repeated reads.  Served through the shared residency protocol
    (touch/gather_rows), so a PAGED resident whose parity pages were
    shed still answers from its data-row prefix."""
    got = store.touch(key)
    if got is None:
        return None
    w, _n_rows, meta = got
    if not meta or meta[0] != version:
        return None
    cached = store.memo_get(key, version)
    if cached is not None:
        return cached
    data_bits = store.gather_rows(key, 0, k * w)
    if data_bits is None:
        return None
    L = meta[1]
    rows = _pack_rows(data_bits, w, k, L, store=store)
    n_stripes = max(1, L // cs)
    out = rows.reshape(k, n_stripes, cs).transpose(1, 0, 2)
    result = out.reshape(-1)[:object_size].tobytes()
    store.memo_put(key, version, result)
    return result
