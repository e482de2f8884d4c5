"""Async messenger: typed messages over an authenticated, crc-guarded,
replay-safe framed TCP protocol.

Role-equivalent of the reference's AsyncMessenger + ProtocolV2 stack
(reference src/msg/async/AsyncMessenger.h:73, ProtocolV2.cc, frames_v2.cc):
every daemon creates one Messenger, registers a Dispatcher, and exchanges
versioned typed messages over ordered per-peer Connections.  The v2-style
connection bring-up is banner -> hello (peer name/type, nonce, session
cookie, requested policy, optional HMAC auth over a shared secret — the
cephx role, src/auth/) -> session.  Data frames carry a crc32 (ms_crc_data
mode) and an optional zlib-compressed payload (compression_onwire.cc role,
ms_compress_min_size).

Policies mirror the reference's (Policy::lossy_client vs lossless_peer),
negotiated at handshake: on a lossless session BOTH sides keep one
long-lived Connection object per peer session — frames are sequenced,
acked, and kept queued until acked; after a transport drop the initiator
reconnects and each side replays its un-acked frames onto the new transport
(the server adopts the new socket into the existing session Connection, the
reference's session-reconnect + out_queue replay, ProtocolV2.cc
reuse_connection) — with receiver-side seq dedupe making dispatch
exactly-once in both directions, the OSD<->OSD guarantee PG consistency is
built on.  Lossy connections just fail and are replaced wholesale.

A config-driven fault injector (reference
src/common/options/global.yaml.in:1240) exercises the failure paths
without code changes: ms_inject_socket_failures severs connections,
ms_inject_delay_max delays sends, and ms_inject_dup_frames delivers
client-op-plane messages twice (two frames, two seqs — duplicates the
receiver's seq dedupe CANNOT filter, proving the application layer's
reqid/pop-once dedup instead).  A dispatch throttle
(ms_dispatch_throttle_bytes) applies receive-side backpressure.

Wire formats, by plane (see README "Wire-format threat model"):
- DATA plane (MOSDOp/MOSDOpReply/ECSub*/MPushShard): fixed binary field
  layouts (FLAG_FIXED; FIXED_FIELDS declared in types.py) — struct-speed
  and incapable of executing code on decode, like the reference's
  fixed-layout dencoder structs.  Bulk bytes ride the zero-copy blob
  lane with their own crc32c.
- CONTROL plane (maps, peering, paxos, config): pickled dataclass
  fields — an internal trusted-cluster format behind cephx-lite auth.
- COLOCATED daemons (ms_local_fastpath): no serialization at all —
  typed messages hand over by reference (Messenger local_connection
  role).
The reference's cross-version dencoder discipline is represented by the
per-type version field checked on decode (and exercised by
tools/dencoder + the wire corpus).

Cork/flush discipline (the corked wire data plane): every Connection owns
an OUTBOX.  ``send()`` frames the message and appends the segments to the
outbox; a single per-connection flusher task drains the outbox with ONE
``writelines`` + ONE ``drain()`` per flush window, so frames queued by
concurrent senders (a k+m stripe fan-out, a burst of sub-write replies)
coalesce into one scatter-gather write instead of paying a
lock/write/drain round-trip each (the reference's ProtocolV2 out_queue +
segment writev).  The flush window is self-clocking: while one window
drains, new frames pile into the next — no added latency for an isolated
send, automatic batching under load.  On plaintext TCP the flusher also
swaps the StreamWriter for a CorkedWriter that ``sendmsg``-writevs the
frame segments STRAIGHT FROM their owning buffers (encode outputs, store
blobs, BufferList pieces) — zero copies between codec and kernel.  A
window big enough for it to pay (``CorkedWriter.OFFLOOP_MIN_BYTES``)
leaves on the process's SENDER THREAD: a native thread that runs no
Python and never takes the GIL copies it into the kernel while the loop
goes on, and the loop hears of the windows that finished in one callback
(``_Offloop``; CorkedWriter "Off the loop").

Acks WAIT FOR COMPANY: dispatching a frame records a debt on the
connection (``queue_ack``: the highest seq owed — acks are cumulative, so
the latest seq covers every earlier one — the payload bytes it covers,
when the debt began) and wakes nobody.  An ack only trims the sender's
replay queue; no send, op or throttle waits for it, so what a later ack
costs is the sender's memory held a little longer and a longer replay
after a reconnect.  The debt is settled by whichever comes first:
(1) a DATA WINDOW on that connection — the flusher appends the ack to any
window that has data (a ping's ack beside its reply, an op's beside
MOSDOpReply); (2) the BOUND on what the sender must hold — once the
frames owed cover ``Messenger.ACK_OWED_BYTES`` (4 MiB, one put's worth)
the ack leaves at once in a window of its own; (3) the messenger's SWEEP
— one timer a messenger (``_AckSweep``), armed only while a
connection of it owes, whose tick writes in ONE loop step the ack of
every connection whose debt is ``Messenger.ACK_DELAY_S`` old (500 ms),
through the connection's own write path and accounting.  Most sockets here carry data one way only
(``Messenger.send`` uses the sender's OUTBOUND session, so a pair of OSDs
has two sockets): an ack written the moment it was owed rode alone, one
frame, one writev and one read step on the far side for nearly every
message.  The rx side mirrors the batching: the serve loop
drains every frame ALREADY BUFFERED on the transport into one batch,
dispatches the batch (through ``group_dispatcher`` when the daemon
installs one — the whole-stripe group handoff seam), and acks once.

Lossless-replay interaction: a frame enters the unacked replay queue
BEFORE it enters the outbox, and close() fails the pending flush window
and clears the outbox — un-flushed frames replay from the unacked queue
onto the adopted transport in seq order, and the receiver's dedupe floor
makes any flush/replay overlap exactly-once.

One loop, one wire: a messenger lives on ONE event loop (the one that
ran ``bind`` or its first ``connect``) and reaches a peer over TCP —
plaintext (``FrameReceiver`` + ``CorkedWriter``; the native wirepath, or
the python arm where the library does not build) or ``SecureStream`` —
or, between daemons of one process that both set ``ms_local_fastpath``
(the test clusters), through ``LocalConnection``.  Nothing else.

Multi-lane peer striping (``ms_lanes_per_peer`` > 1, negotiated — an old
peer that doesn't advertise ``lanes_ok`` gets one lane): a peer pair
opens N parallel lanes, each a full Connection (own cork/outbox, own seq
space, own unacked replay queue, own flusher), all on the messenger's
loop.  Lane 0 is the CONTROL lane — pings, acks, maps, backoffs, health
are never queued behind data.  Data-plane messages (LANE_STRIPE types)
are striped round-robin across lanes 1..N-1, stamped with a
connection-global ``gseq``; the receiving LaneGroup reassembles gseq
order before dispatch, so per-(peer,type) ordering (in fact total
data-plane order) and the reqid/dedup machinery above are preserved.
Messages with blobs >= ``ms_lane_stripe_min`` are FRAGMENTED: the blob
splits into per-lane MLaneSegment frames sent concurrently and
reassembled into one buffer on the receiver.  A dead lane pins and
replays only ITS unacked frames (per-lane sessions); the remaining lanes
keep draining, and the gseq reorder buffer absorbs the replayed hole.
"""

from __future__ import annotations

import asyncio
import atexit
import collections
import errno
import hashlib
import hmac
import itertools
import json
import os
import pickle
import random
import struct
import threading
import time
import traceback
import weakref
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.perf_counters import PerfCounters, PerfCountersBuilder
from ceph_tpu.common.throttle import Throttle
from ceph_tpu.utils import wirepath as _wirepath

# Per-process identity token; MLaneHello.proc carries a short digest of
# it, for diagnostics.  Random (not pid): a pid recurs across containers.
PROC_TOKEN = random.randbytes(16).hex()


def _build_wire_perf() -> PerfCounters:
    """The `wire` counter set — one per Messenger, added to the owning
    daemon's PerfCountersCollection so `perf dump` and the mgr prometheus
    exporter carry the wire-path breakdown the ROADMAP names as the
    reason the device-tier win is invisible over TCP.  COUNTER SCHEMA
    (name -> meaning -> kind):

      tx_msgs / rx_msgs    u64         messages sent / dispatched
      tx_bytes / rx_bytes  u64         frame bytes written / received on
                                       the socket (tx side counts EVERY
                                       write: messages, acks, session
                                       replays)
      tx_framing           longrunavg  encode + frame-build seconds per send
      tx_io                longrunavg  socket write + drain seconds per
                                       write (messages, acks, replays)
      rx_io                longrunavg  payload read seconds per frame,
                                       waits included (a FrameReceiver:
                                       per burst, from the read that
                                       brought a front to its frames
                                       stashed)
      rx_framed            u64         frames a FrameReceiver completed
      rx_inline_acks       u64         of those, acks applied in place
      rx_copied_bytes      u64         body bytes copied head ->
                                       destination; over rx_bytes: what
                                       did NOT land in place
      rx_framing           longrunavg  decode_message seconds per dispatch
      local_msgs           u64         colocated-fastpath handoffs (no
                                       framing or socket at all)
      tx_flushes           u64         outbox flush windows written (each is
                                       one writelines + one drain)
      tx_flush_frames      histogram   frames coalesced per flush window
      tx_flush_bytes       histogram   bytes per flush window
      tx_flush_data        u64         windows cut carrying data frames
      tx_flush_ack         u64         ack-only windows (no data pending)
      tx_flush_mixed       u64         windows whose frames were of more
                                       than one family (MSG_FAMILY; the
                                       ack frame counts): there the loop
                                       meter's charge is split by bytes, a
                                       rule; elsewhere it is a measurement
      tx_acks              u64         ack frames written; each left one of
                                       three ways, which sum to it:
      tx_acks_rode         u64         in a window that had data
      tx_acks_bound        u64         alone, the frames owed covering
                                       Messenger.ACK_OWED_BYTES
      tx_acks_swept        u64         alone, the debt ACK_DELAY_S old (the
                                       messenger's sweep)
      ack_frames_covered   u64         sequenced frames the ack frames newly
                                       covered; over tx_acks: frames an ack
      tx_acks_coalesced    u64         acks absorbed into a pending ack
                                       (would have been standalone frames)
      tx_crc_reused        u64         blob frames whose wire crc reused an
                                       app-level crc (no recompute pass)
      rx_batches           u64         multi-frame rx batches drained
      rx_batch_msgs        histogram   messages per rx dispatch batch
      wirepath_kind        u64 gauge   1 = native wirepath, 0 = python arm
      native_tx_calls      u64         released-GIL tx wirepath calls
                                       (a window on the sender thread:
                                       one, and one more each time it
                                       found the socket full)
      tx_offloop_windows   u64         flush windows handed to the
                                       process's sender thread instead of
                                       written in the loop's step
      tx_offloop_bytes     u64         their bytes; over tx_bytes: how
                                       often the hand-over engages
      tx_offloop_behind    u64         of those windows, the ones under
                                       CorkedWriter.OFFLOOP_MIN_BYTES that
                                       followed a job of their fd (order)
      tx_offloop_eagain    u64         times the thread found a socket
                                       full and left the fd to its epoll
      tx_offloop_lat       longrunavg  hand-over -> completion seen by the
                                       loop, seconds per window (a WAIT:
                                       the thread's copy and the loop's
                                       own delay in looking)
      tx_offloop_depth     longrunavg  jobs the thread had unfinished at a
                                       hand-over (sum / count)
      native_rx_calls      u64         released-GIL rx wirepath calls
      native_bytes         u64         bytes touched by native wirepath
                                       passes (counted once per pass)
      tx_<Type> / rx_<Type>        u64  per-message-type counts (dynamic)
      tx_bytes_<Type> / rx_bytes_<Type>  u64  per-type frame bytes

    framing vs io is the actionable split: framing seconds are Python
    encode cost a scatter-gather/zero-copy PR can remove; io seconds are
    the socket's.  With the corked outbox, tx_io is per FLUSH WINDOW (not
    per message): sum(tx_io)/tx_msgs is the per-message socket cost and
    drops as flush windows batch more frames.

    tx_io and rx_io INCLUDE WAITS (the drain; a readexactly chain's
    awaits, or on a FrameReceiver the loop turns between a body's
    reads): on a busy loop they measure how long the connection waited
    for the loop, not what the messenger did — 209 s of rx_io in a 30 s
    window was parked readers (PERF.md, PR 26).  The messenger's own
    time is the `loop` set's `self_messenger` (common/tracing.py): the
    sections here (encode_frame with the blob's crc, sock_write,
    rx_frame with its crc_verify, decode) plus asyncio's
    transport reads and writes.  WHOSE message that time served is the
    same set's `msg_<Type>` and `for_<family>` (tracing.charge): the
    receiver charges a burst to its frames, a send to its message, the
    serve loop a decode and a dispatch, the flusher a window."""
    b = PerfCountersBuilder("wire")
    b.add_u64_counter("tx_msgs", "messages sent")
    b.add_u64_counter("tx_bytes", "frame bytes sent")
    b.add_u64_counter("rx_msgs", "messages dispatched")
    b.add_u64_counter("rx_bytes", "frame bytes received")
    b.add_time_avg("tx_framing", "encode + frame-build seconds per send")
    b.add_time_avg("tx_io", "socket write + drain seconds per flush window")
    b.add_time_avg("rx_io", "payload read seconds per frame (post-header)")
    b.add_u64_counter("rx_framed", "frames the receiver completed")
    b.add_u64_counter("rx_inline_acks", "acks applied where they arrived")
    b.add_u64_counter("rx_copied_bytes",
                      "bytes copied after the kernel delivered them")
    b.add_time_avg("rx_framing", "decode seconds per dispatched message")
    b.add_u64_counter("local_msgs", "colocated-fastpath handoffs")
    b.add_u64_counter("tx_flushes", "outbox flush windows written")
    b.add_histogram("tx_flush_frames", "frames coalesced per flush window")
    b.add_histogram("tx_flush_bytes", "bytes per flush window")
    b.add_u64_counter("tx_flush_data", "flush windows carrying data frames")
    b.add_u64_counter("tx_flush_ack", "ack-only flush windows")
    b.add_u64_counter("tx_flush_mixed", "flush windows holding frames of "
                                        "more than one family")
    b.add_u64_counter("tx_acks", "ack frames written")
    b.add_u64_counter("tx_acks_rode", "ack frames in a window with data")
    b.add_u64_counter("tx_acks_bound",
                      "ack frames sent alone at the bound on bytes owed")
    b.add_u64_counter("tx_acks_swept",
                      "ack frames sent alone by the messenger's sweep")
    b.add_u64_counter("ack_frames_covered",
                      "sequenced frames newly covered by ack frames")
    b.add_u64_counter("tx_acks_coalesced",
                      "acks absorbed into a pending cumulative ack")
    b.add_u64_counter("tx_crc_reused",
                      "blob frames reusing an app-level crc on the wire")
    b.add_u64_counter("rx_batches", "multi-frame rx dispatch batches")
    b.add_histogram("rx_batch_msgs", "messages per rx dispatch batch")
    # multi-lane plane (module docstring "Multi-lane peer striping");
    # per-lane splits ride dynamic tx_lane<k>_msgs / tx_lane<k>_bytes
    # counters
    b.add_u64_counter("lane_rx_parked",
                      "striped frames parked awaiting a gseq gap")
    b.add_u64_counter("lane_frag_tx", "lane fragments sent (large blobs "
                                      "split across data lanes)")
    b.add_u64_counter("lane_frag_rx", "lane fragments reassembled")
    b.add_u64_counter("lane_frag_overflow",
                      "fragments refused by the reassembly memory cap")
    b.add_u64_counter("lane_revivals", "dead lanes redialed and replayed")
    # native wirepath (utils/wirepath.py): which arm ran and how much of
    # the per-byte hot loop it carried — wirepath_kind is the arm gauge
    # (1 = native, 0 = python; BENCH records the string alongside)
    b.add_u64("wirepath_kind", "wirepath arm: 1 = native, 0 = python")
    b.add_u64_counter("native_tx_calls",
                      "released-GIL wirepath calls on the tx side "
                      "(whole-window writev, batch blob crc)")
    b.add_u64_counter("tx_offloop_windows",
                      "flush windows written by the sender thread")
    b.add_u64_counter("tx_offloop_bytes", "bytes of those windows")
    b.add_u64_counter("tx_offloop_behind",
                      "small windows that followed a job of their fd")
    b.add_u64_counter("tx_offloop_eagain",
                      "full sockets the sender thread left to its epoll")
    b.add_time_avg("tx_offloop_lat",
                   "seconds from a hand-over to its completion seen by "
                   "the loop")
    b.add_time_avg("tx_offloop_depth",
                   "jobs unfinished on the sender thread at a hand-over")
    b.add_u64_counter("native_rx_calls",
                      "released-GIL wirepath calls on the rx side "
                      "(a burst's crc verify, a landed body's)")
    b.add_u64_counter("native_bytes",
                      "bytes touched by native wirepath passes (each "
                      "pass counts: a byte crc-verified then scattered "
                      "counts once per pass)")
    # µs histograms of the socket-io longrunavgs: tail-latency
    # percentiles (p50/p99/p999) come out of the power-of-2 buckets, so
    # the BENCH record reports wire tx/rx TAILS, not just means
    b.add_histogram("tx_io_us", "socket write+drain µs per flush window")
    b.add_histogram("rx_io_us", "payload read µs per frame")
    return b.create_perf_counters()

BANNER = b"ceph_tpu msgr v2\n"
_HDR = struct.Struct("<IHHBIQ")  # len, type, version, flags, crc, seq

# blob-frame payload prefix: pickled length + blob checksum
_BLOB_PFX = struct.Struct("<II")
_ACK_SEQ = struct.Struct("<Q")  # an ACK_TYPE frame's payload

FLAG_COMPRESSED = 1
# FLAG_FIXED: the payload (or the header part of a blob frame) is the
# class's FIXED_FIELDS binary layout, not pickle — the data-plane
# framing discipline (reference fixed-layout dencoder encode for
# MOSDOp/ECSubWrite wire structs, src/osd/ECMsgTypes.h encode_payload):
# nothing on the hot path can execute code on decode, and field packing
# is struct-speed.  Control-plane types keep pickle (internal
# trusted-cluster format; see module docstring).
FLAG_FIXED = 4
# FLAG_BLOB: payload = [u32 plen][u32 blob_crc][pickled(plen)][blob].
# The large binary field of a message (MOSDOp.data, MECSubWrite.chunk, ...)
# rides OUT OF BAND from the pickle: the sender never copies it into a
# serialized buffer (scatter-gather writev via writer.writelines), the
# header crc covers only the small pickled part, and the blob's own
# hardware crc32c protects the bulk bytes — the zero-copy framing half of
# the reference's bufferlist-based wire path (src/msg/async/ProtocolV2.cc
# segments + crc sections role).
FLAG_BLOB = 2
# only bulk payloads are worth the second checksum + reattach bookkeeping
BLOB_MIN = 16 * 1024

ACK_TYPE = 0xFFF0  # control frame: payload is the acked seq (u64)

MAX_SESSIONS = 4096  # LRU cap on server-side peer sessions

# -- message registry --------------------------------------------------------

_MSG_TYPES: Dict[int, type] = {}
_MSG_IDS: Dict[type, int] = {}


def message(type_id: int, version: int = 1):
    """Register a message dataclass with a wire type id + version."""

    def deco(cls):
        existing = _MSG_TYPES.get(type_id)
        if existing is not None and existing.__name__ != cls.__name__:
            raise ValueError(
                f"wire type id {type_id} already taken by "
                f"{existing.__name__}; cannot register {cls.__name__}"
            )
        cls = dataclass(cls)
        cls.TYPE_ID = type_id
        cls.VERSION = version
        _MSG_TYPES[type_id] = cls
        _MSG_IDS[cls] = type_id
        return cls

    return deco


# Whose work a message is (common/tracing.py FAMILIES; PERF.md section 3):
# the loop meter books the time a message costs the loop to its type and to
# its type's family.  `op` is what a client op causes, `liveness` what the
# cluster says to stay a cluster, `tier` the hit sets, `recovery` peering,
# backfill and scrub, `control` the rest.  EVERY registered class is listed
# (tests/test_messenger.py); one that is not runs as `control`.
MSG_FAMILY: Dict[str, str] = {
    name: family for family, names in {
        "op": """MOSDOp MOSDOpReply MOSDBackoff MECSubWrite MECSubWriteReply
            MECSubRead MECSubReadReply MECSubDelete MECSubRollback
            MFetchShards MFetchShardsReply MListShards MListShardsReply
            MCacheDirty MCacheDirtyAck MSetXattrs MSetOmap MWatchNotify
            MNotifyAck""",
        "liveness": """MOSDPing MPing MOSDFailure MOsdMembership MMonElection
            MMonPaxos""",
        "tier": "MOSDPGHitSet",
        "recovery": """MPushShard MPGInfoReq MPGInfoReply MPGLogReq
            MPGLogReply MBackfillReserve MBackfillReserveReply MScrubShard
            MScrubShardReply MOSDPGTemp""",
        "control": """MGetMap MMapReply MOsdBoot MBootReply MCreatePool
            MCreatePoolReply MDeletePool MPoolSet MMarkDown MForward
            MForwardReply MConfigSet MConfigGet MConfigReply MAuthTicket
            MAuthTicketReply MAuthRotating MAuthRotatingReply MSetUpmap
            MSnapOp MSnapOpReply MOSDSetFlag MSetFullRatio MGetHealth
            MHealthReply MHealthMute MLog MLogAck MLogSubscribe MLogReply
            MCrashReport MCrashReportAck MCrashQuery MCrashQueryReply
            MCommand MCommandReply MCrushOp MCrushOpReply MOsdPredicate
            MOsdPredicateReply MMgrReport MLaneHello MLaneSegment""",
    }.items() for name in names.split()}
ACK_CHARGE = ("ack", "ack")  # an ACK_TYPE frame, an ack-only flush window


class _TypeSlot:
    """What a messenger keeps per message type, found by type id with one
    dict read a message (Messenger._type_slot): the loop meter's charge
    key and the names of the type's `wire` counters (made in its set when
    the direction is first used)."""

    __slots__ = ("name", "charge", "tx", "tx_bytes", "rx", "rx_bytes")

    def __init__(self, name: str) -> None:
        self.name = name
        self.charge = (MSG_FAMILY.get(name, "control"), name)
        self.tx = self.tx_bytes = self.rx = self.rx_bytes = None


# -- lane negotiation / fragmentation wire types -----------------------------
# Messenger-internal data-plane types (fixed layouts; corpus + dencoder
# covered like every other wire type).  They live HERE, not types.py,
# because the lane layer itself produces and consumes them.


@message(71)
class MLaneHello:
    """First frame on every lane of a multi-lane peer session: binds the
    carrying connection to lane ``lane`` of lane-group ``group`` (the
    connection-negotiation fields of the wire plane).  Lane 0's hello
    CREATES the group on the acceptor; joining lanes attach to it.
    ``proc`` carries a short digest of the sender's process token for
    diagnostics only."""

    group: str = ""
    lane: int = 0
    n_lanes: int = 1
    proc: str = ""
    flags: int = 0

    FIXED_FIELDS = [("group", "s"), ("lane", "q"), ("n_lanes", "q"),
                    ("proc", "s"), ("flags", "Q")]


@message(72)
class MLaneSegment:
    """One fragment of a striped large message: blobs >=
    ``ms_lane_stripe_min`` split into per-data-lane segments sent
    concurrently; the receiver reassembles ``nfrags`` chunks into one
    contiguous buffer, decodes the original message from ``header``
    (fragment 0 carries it) and releases it into the gseq reorder at
    ``gseq``.  ``total`` is the full blob length, ``off`` this chunk's
    byte offset — explicit, so reassembly never depends on arrival
    order or even chunk sizing."""

    gseq: int = 0
    idx: int = 0
    nfrags: int = 1
    total: int = 0
    off: int = 0
    type_id: int = 0
    version: int = 1
    fixed: bool = False
    header: bytes = b""
    chunk: bytes = b""

    FIXED_FIELDS = [("gseq", "Q"), ("idx", "q"), ("nfrags", "q"),
                    ("total", "q"), ("off", "q"), ("type_id", "q"),
                    ("version", "q"), ("fixed", "?"), ("header", "y"),
                    ("chunk", "y")]
    BLOB_ATTR = "chunk"
    BLOB_VIEW_OK = True


# store-resident buffers may be memoryviews (ownership-transferred
# encode outputs); when one rides a pickled message field on the REAL
# wire, serialize it as its bytes — the local fastpath never serializes
import copyreg  # noqa: E402

copyreg.pickle(memoryview, lambda m: (bytes, (bytes(m),)))


def _norm_segments(segments):
    """Normalize buffers to non-empty 1-D byte memoryviews; returns
    (views, total_bytes).  Shared by BufferList and CorkedWriter so the
    cast/skip-empty rules cannot drift apart."""
    segs = []
    total = 0
    for s in segments:
        mv = s if isinstance(s, memoryview) else memoryview(s)
        if mv.ndim != 1 or mv.itemsize != 1:
            mv = mv.cast("B")
        if mv.nbytes:
            segs.append(mv)
            total += mv.nbytes
    return segs, total


class BufferList:
    """A blob made of multiple buffers (the reference's bufferlist,
    src/common/buffer.h): a message's bulk field may be handed over as a
    LIST of byte pieces — per-stripe chunk views, extent slices — and the
    corked send path writev's the pieces straight from their owning
    buffers.  No producer-side gather copy: the de-interleave a read
    reply used to pay (stripes -> one contiguous buffer -> frame) becomes
    a list of views the kernel gathers.  The frame crc chains across the
    pieces, so the bytes on the wire (and the receiver, which sees one
    contiguous blob land in its frame buffer) are identical to the
    concatenation.  Pickling one (control-plane ride-along, sub-threshold
    fallback) materializes to plain bytes."""

    __slots__ = ("segments", "nbytes")

    def __init__(self, segments=()):
        self.segments, self.nbytes = _norm_segments(segments)

    def __len__(self) -> int:
        return self.nbytes

    def tobytes(self) -> bytes:
        return b"".join(self.segments)

    def __bytes__(self) -> bytes:
        return self.tobytes()


# a BufferList that rides pickle (local-fastpath control copy, or a
# sub-threshold blob folded into the payload) lands as plain bytes
copyreg.pickle(BufferList, lambda bl: (bytes, (bl.tobytes(),)))


def as_bytes(data) -> bytes:
    """Materialize a message bulk field to bytes: blob-lane fields may be
    bytes, bytearray, memoryview, or BufferList depending on the path the
    message took (wire rx buffer, store view, scatter-gather reply)."""
    if isinstance(data, bytes):
        return data
    if isinstance(data, BufferList):
        return data.tobytes()
    return bytes(data)


# -- fixed binary field codec ------------------------------------------------
# Data-plane messages declare FIXED_FIELDS = [(name, kind)]: a flat,
# versioned-by-frame binary layout.  Kinds: q/Q/d/? scalars, s (u32-len
# utf8), y (u32-len bytes), Q* (u64 list), s* (str list), qq* (list of
# (i64, i64) pairs), addr ((host, port) or None).  A class may gate
# eligibility with FIXED_WHEN(msg) — e.g. MOSDOp falls back to pickle
# when a compound op vector is attached.

_FIX = {k: struct.Struct("<" + k) for k in ("q", "Q", "d", "?")}
_LEN32 = struct.Struct("<I")
_PAIR = struct.Struct("<qq")


def _pack_fixed(msg: Any, fields, blob_attr=None) -> bytes:
    parts = []
    for name, kind in fields:
        v = msg.__dict__.get(name)
        if name == blob_attr:
            v = b""  # rides the blob lane; reattached on decode
        st = _FIX.get(kind)
        if st is not None:
            parts.append(st.pack(v if kind != "?" else bool(v)))
        elif kind == "s":
            b = (v or "").encode()
            parts.append(_LEN32.pack(len(b)))
            parts.append(b)
        elif kind == "y":
            b = v if isinstance(v, (bytes, bytearray)) else \
                (b"" if v is None else bytes(v))
            parts.append(_LEN32.pack(len(b)))
            parts.append(b)
        elif kind == "Q*":
            v = v or ()
            parts.append(_LEN32.pack(len(v)))
            parts.append(struct.pack(f"<{len(v)}Q", *v))
        elif kind == "s*":
            v = v or ()
            parts.append(_LEN32.pack(len(v)))
            for s in v:
                b = s.encode()
                parts.append(_LEN32.pack(len(b)))
                parts.append(b)
        elif kind == "qq*":
            v = v or ()
            parts.append(_LEN32.pack(len(v)))
            for a, b in v:
                parts.append(_PAIR.pack(a, b))
        elif kind == "addr":
            if not v:
                parts.append(_LEN32.pack(0xFFFFFFFF))
            else:
                h = str(v[0]).encode()
                parts.append(_LEN32.pack(len(h)))
                parts.append(h)
                parts.append(_FIX["q"].pack(int(v[1])))
        else:  # pragma: no cover - schema bug
            raise ValueError(f"unknown fixed kind {kind!r}")
    return b"".join(parts)


def _default_copy(v):
    return list(v) if isinstance(v, list) else (
        dict(v) if isinstance(v, dict) else v)


def _unpack_fixed(cls, payload: bytes, blob: Any):
    obj = cls.__new__(cls)
    d = obj.__dict__
    # non-fixed fields keep their dataclass defaults (fresh containers)
    defaults = _FIXED_DEFAULTS.get(cls)
    if defaults is None:
        defaults = _FIXED_DEFAULTS[cls] = {
            k: v for k, v in cls().__dict__.items()}
    fixed_names = {n for n, _ in cls.FIXED_FIELDS}
    for k, v in defaults.items():
        if k not in fixed_names:
            d[k] = _default_copy(v)
    off = 0
    mv = memoryview(payload)
    for idx, (name, kind) in enumerate(cls.FIXED_FIELDS):
        if off >= len(payload):
            # truncated tail: the sender's FIXED_FIELDS list was SHORTER
            # — an old build predating trailing additions like the
            # trace-id pair.  Default the unsent remainder (the
            # fixed-layout analog of the reference's versioned-decode
            # "new fields default" rule); new fields MUST append.
            for tail_name, _ in cls.FIXED_FIELDS[idx:]:
                d[tail_name] = _default_copy(defaults[tail_name])
            break
        st = _FIX.get(kind)
        if st is not None:
            d[name] = st.unpack_from(payload, off)[0]
            off += st.size
        elif kind in ("s", "y"):
            (n,) = _LEN32.unpack_from(payload, off)
            off += 4
            raw = bytes(mv[off:off + n])
            off += n
            d[name] = raw.decode() if kind == "s" else raw
        elif kind == "Q*":
            (n,) = _LEN32.unpack_from(payload, off)
            off += 4
            d[name] = list(struct.unpack_from(f"<{n}Q", payload, off))
            off += 8 * n
        elif kind == "s*":
            (n,) = _LEN32.unpack_from(payload, off)
            off += 4
            out = []
            for _ in range(n):
                (sn,) = _LEN32.unpack_from(payload, off)
                off += 4
                out.append(bytes(mv[off:off + sn]).decode())
                off += sn
            d[name] = out
        elif kind == "qq*":
            (n,) = _LEN32.unpack_from(payload, off)
            off += 4
            out = []
            for _ in range(n):
                out.append(_PAIR.unpack_from(payload, off))
                off += _PAIR.size
            d[name] = out
        elif kind == "addr":
            (n,) = _LEN32.unpack_from(payload, off)
            off += 4
            if n == 0xFFFFFFFF:
                d[name] = None
            else:
                host = bytes(mv[off:off + n]).decode()
                off += n
                port = _FIX["q"].unpack_from(payload, off)[0]
                off += 8
                d[name] = (host, port)
    if blob is not None:
        d[getattr(cls, "BLOB_ATTR")] = blob
    return obj


_FIXED_DEFAULTS: Dict[type, Dict[str, Any]] = {}


def encode_payload(msg: Any) -> bytes:
    return pickle.dumps(msg.__dict__, protocol=5)


def encode_payload_parts(msg: Any):
    """(header, blob, fixed): when the message class declares BLOB_ATTR
    and the field is bulk bytes, it is stripped from the header part and
    returned separately so framing can scatter-gather it with zero
    copies.  Data-plane classes with FIXED_FIELDS get the fixed binary
    layout for the header part (fixed=True) instead of pickle."""
    cls = type(msg)
    attr = getattr(cls, "BLOB_ATTR", None)
    blob = None
    if attr is not None:
        b = msg.__dict__.get(attr)
        if isinstance(b, (bytes, bytearray, memoryview, BufferList)) \
                and len(b) >= BLOB_MIN:
            blob = b
    fields = getattr(cls, "FIXED_FIELDS", None)
    if fields is not None:
        when = getattr(cls, "FIXED_WHEN", None)
        if when is None or when(msg):
            return (_pack_fixed(msg, fields,
                                blob_attr=attr if blob is not None
                                else None),
                    blob, True)
    if blob is not None:
        d = dict(msg.__dict__)
        d[attr] = None  # reattached by decode_message
        return pickle.dumps(d, protocol=5), blob, False
    if attr is not None:
        b = msg.__dict__.get(attr)
        if isinstance(b, memoryview):
            # below the blob threshold the field rides the pickle,
            # which cannot serialize memoryviews natively fast
            d = dict(msg.__dict__)
            d[attr] = bytes(b)
            return pickle.dumps(d, protocol=5), None, False
    return pickle.dumps(msg.__dict__, protocol=5), None, False


def decode_message(type_id: int, version: int, payload: bytes,
                   blob: Any = None, fixed: bool = False) -> Any:
    cls = _MSG_TYPES.get(type_id)
    if cls is None:
        raise ValueError(f"unknown message type {type_id}")
    if version > cls.VERSION:
        raise ValueError(
            f"{cls.__name__} wire version {version} > supported {cls.VERSION}"
        )
    if fixed:
        if getattr(cls, "FIXED_FIELDS", None) is None:
            raise ValueError(f"{cls.__name__}: unexpected fixed frame")
        return _unpack_fixed(cls, payload, blob)
    obj = cls.__new__(cls)
    obj.__dict__.update(pickle.loads(payload))
    if blob is not None:
        setattr(obj, getattr(cls, "BLOB_ATTR"), blob)
    return obj


# frame/bulk checksum: the shared hardware-crc32c resolver.  The KIND in
# use rides the handshake hello: when the two ends resolved differently
# (one host's native build failed), the connection falls back to zlib for
# its frames instead of looping on BadFrame forever.
from ceph_tpu.utils.checksum import checksum, checksum_kind  # noqa: E402


class BadFrame(Exception):
    pass


# Everything a send/dial can legitimately raise when the PEER (not this
# process) is at fault: socket errors, handshake refusals/garbage, dial
# timeouts.  Daemons catching "send failed, treat as missing ack" catch
# THIS, not Exception — a TypeError in our own framing code must crash
# loudly, not melt into a silent degraded loop.  (ConnectionError and
# PermissionError are OSError subclasses and IncompleteReadError an
# EOFError subclass — listed anyway to document the intended surface.)
TRANSPORT_ERRORS = (ConnectionError, OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError, EOFError, BadFrame,
                    PermissionError, json.JSONDecodeError)


# -- policies ----------------------------------------------------------------


@dataclass
class Policy:
    lossy: bool = True
    replay: bool = False  # keep unacked queue + replay on reconnect

    @classmethod
    def lossy_client(cls) -> "Policy":
        return cls(lossy=True, replay=False)

    @classmethod
    def lossless_peer(cls) -> "Policy":
        return cls(lossy=False, replay=True)


def _cget(conf, key: str, default: Any) -> Any:
    try:
        v = conf.get(key, default)
    except TypeError:
        v = conf.get(key) if key in conf else default
    return default if v is None else v


# -- local fast dispatch -----------------------------------------------------

# addr -> live Messenger in THIS process.  Colocated daemons' frames can
# skip the TCP stack entirely (ms_local_fastpath): the in-process
# equivalent of the reference's Messenger local_connection fast dispatch
# and the colocated-transport seam its pluggable NetworkStack keeps open
# (src/msg/async/Stack.h; DPDK/RDMA lanes plug in there the same way).
_LOCAL_REGISTRY: Dict[Tuple[str, int], "Messenger"] = {}


class LocalConnection:
    """In-process session with a colocated daemon: typed messages hand
    over BY REFERENCE through a receiver-side FIFO — no sockets,
    framing, checksums, or serialization.  Delivery matches a lossless
    wire session: per-connection order (one pump task), exactly-once
    (no transport to fail mid-frame), and dispatcher isolation
    (exceptions log, never propagate into the sender — the _serve
    discipline).  Shared contract with the reference's local delivery:
    a message is immutable once sent.

    Enabled per-messenger by ms_local_fastpath; vstart turns it on for
    plain clusters, while any wire-exercising configuration (auth,
    secure mode, fault injection) keeps real sockets so those paths
    stay covered."""

    def __init__(self, messenger: "Messenger", peer_messenger: "Messenger",
                 reverse: Optional["LocalConnection"] = None):
        self.messenger = messenger
        self.peer_messenger = peer_messenger
        self.peer = tuple(peer_messenger.addr or ("local", 0))
        self.peer_name = peer_messenger.name
        self.policy = Policy.lossless_peer()
        self.outbound = reverse is None
        # how the peer "authenticated": same-process construction IS the
        # trust statement (fastpath is off whenever auth is configured)
        self.auth_kind = "local"
        self.auth_entity_type = peer_messenger.entity_type
        self.closed = False
        # bounded: a flooding sender parks on put() exactly like a full
        # socket buffer parks drain()
        self._queue: asyncio.Queue = asyncio.Queue(maxsize=1024)
        self._pump: Optional[asyncio.Task] = None
        self.reverse = reverse if reverse is not None else \
            LocalConnection(peer_messenger, messenger, reverse=self)

    async def send(self, msg: Any) -> None:
        peer = self.peer_messenger
        if (self.closed or peer._shutdown
                or _LOCAL_REGISTRY.get(self.peer) is not peer):
            self.closed = True
            raise ConnectionError(f"local peer {self.peer_name} gone")
        cls = type(msg)
        fields = getattr(cls, "FIXED_FIELDS", None)
        when = getattr(cls, "FIXED_WHEN", None)
        if fields is None or (when is not None and not when(msg)):
            # CONTROL-plane (or exotic) payload: give the receiver its
            # own object graph, exactly as the pickled wire would.
            # By-reference handoff is only safe for the flat, immutable
            # data-plane set — a control payload like MMapReply carries
            # the mon's LIVE OSDMap, whose next in-place mutation would
            # otherwise tear every colocated daemon's shared copy.
            msg = pickle.loads(pickle.dumps(msg, protocol=5))
        await self.reverse._deliver(msg)
        self.messenger.perf.inc("local_msgs")

    async def _deliver(self, msg: Any) -> None:
        await self._queue.put(msg)
        if self._pump is None or self._pump.done():
            m = self.messenger
            self._pump = asyncio.get_running_loop().create_task(
                self._pump_loop())
            m._tasks.add(self._pump)
            self._pump.add_done_callback(m._tasks.discard)

    async def _pump_loop(self) -> None:
        while not self.closed and not self.messenger._shutdown:
            msg = await self._queue.get()
            disp = self.messenger.dispatcher
            if disp is None:
                continue
            try:
                await disp(self, msg)
            except (asyncio.CancelledError, GeneratorExit):
                raise
            except Exception:
                traceback.print_exc()

    async def close(self, gen: int = 0) -> None:
        self.closed = True
        if self._pump is not None:
            self._pump.cancel()


# -- connection --------------------------------------------------------------


class FrameReceiver(asyncio.BufferedProtocol):
    """The one place where a plaintext-TCP connection's bytes turn into
    frames: installed over the transport (transport.set_protocol) AFTER
    the handshake, in place of the StreamReader chain.

    Between frames the transport reads into a small HEAD buffer, and
    buffer_updated parses, in place, every frame that is whole in it.  A
    frame whose body is not all in hand (a blob; a payload larger than
    the head) gets its destination right away (_blob_dest), the body
    bytes that came with the head are copied there ONCE, and from then
    on the kernel is handed dest[pos:]: the rest lands where it will
    live.  A finished burst is crc-verified in one pass
    (wirepy_verify_regions on the native arm, the connection's crc_fn
    otherwise), received acks are applied on the spot, the other frames
    go on conn._rx_stash as read_frame's tuples, and the serve task is
    woken once.  A bad frame keeps a per-frame reader's order: the good
    frames before it are delivered, then read_frame raises BadFrame.

    The transport's read is the only way bytes enter (feed() replays
    what the StreamReader held at the swap by the same two calls, before
    any later read); a body begins landing a loop turn after its front.

    Receive-side backpressure is by bytes: with more than one frame
    landed and not popped (the body in flight counts by what has landed
    of it), above _LIMIT the transport is paused, and resumed below
    half; one frame alone never pauses its own connection.  So a
    connection holds about two frames (or _LIMIT of small ones) outside
    the dispatch throttle: one popped or stashed, one landing.  Write-side
    flow control keeps working by forwarding pause_writing /
    resume_writing to the original stream protocol."""

    _HEAD = 16 << 10
    _LIMIT = 1 << 20

    def __init__(self, conn: "Connection", transport, stream_protocol):
        self._conn = conn
        self._perf = conn.messenger.perf
        self._transport = transport
        self._stream_protocol = stream_protocol
        self._head = bytearray(self._HEAD)
        self._head_mv = memoryview(self._head)
        self._pos = self._fill = 0  # the head's parsed prefix, its bytes
        self._t0 = 0.0  # when the read that brought the newest front came
        # the frame in flight: its destination (a memoryview), how much
        # of it has landed, (read_frame's tuple, the body's crc or 0,
        # compressed) and the front's bytes as the wire had them
        self._body = None
        self._body_pos = 0
        self._frame: Optional[tuple] = None
        self._front = b""
        self._held = 0  # bytes of stashed frames nobody popped yet
        self._waiter: Optional[asyncio.Future] = None
        self._eof = False
        self._exc: Optional[BaseException] = None
        self._read_paused = False
        self._dead = False  # after a bad frame nothing more is read
        # the connection's CorkedWriter, when one took over the tx side:
        # connection_lost must fail its drain waiters too
        self.corked = None

    # -- protocol side -------------------------------------------------------

    def get_buffer(self, sizehint: int):
        if self._body is not None:
            return self._body[self._body_pos:]
        return self._head_mv if self._dead else self._head_mv[self._fill:]

    def buffer_updated(self, nbytes: int) -> None:
        if self._dead:
            return
        body = self._body
        if body is None:
            self._fill += nbytes
            self._t0 = time.monotonic()
        else:
            # the step (recv_into included) is the frame's in flight
            if tracing.metered():
                tracing.charge(self._conn.messenger._type_slot(
                    self._frame[0][0]).charge)
            self._body_pos += nbytes
            if self._body_pos < len(body):
                if self._held:
                    self._backpressure()
                return
        with tracing.section("messenger", "rx_frame"):
            if body is None:
                done, error, copied = self._parse()
                if tracing.metered():
                    self._charge_burst(done)
            else:
                done, error, copied = self._finish_body()
            self._deliver(done, error, copied)

    def _charge_burst(self, done: list) -> None:
        """The step that read and parsed a head is its frames', by the
        bytes each landed: those complete, the one put in flight (by its
        front and what came with it), received acks as acks."""
        slot_of = self._conn.messenger._type_slot
        whose: Dict[tuple, int] = {}
        for f in done:
            if type(f) is int:
                key, n = ACK_CHARGE, _HDR.size + _ACK_SEQ.size
            else:
                key, n = slot_of(f[0]).charge, _HDR.size + f[4]
            whose[key] = whose.get(key, 0) + n
        if self._frame is not None:
            key = slot_of(self._frame[0][0]).charge
            whose[key] = whose.get(key, 0) + len(self._front) \
                + self._body_pos
        tracing.charge_many(whose)

    def feed(self, data) -> None:
        """Bytes that did not come through the transport (what the
        StreamReader had buffered at the swap), by the transport's own
        two calls."""
        mv = memoryview(data)
        while len(mv) and not self._dead:
            buf = self.get_buffer(-1)
            n = min(len(buf), len(mv))
            buf[:n] = mv[:n]
            self.buffer_updated(n)
            mv = mv[n:]

    def _blob_dest(self, type_id: int, flags: int, seq: int,
                   payload: bytes, blob_len: int):
        """Where a blob's bytes will live, as (the view to fill, what the
        message gets): a lane fragment's slice of its group's assembly
        buffer, an uninitialised array for the classes whose consumers
        take a view (no memset pass over bytes the socket is about to
        overwrite), else a bytearray."""
        conn = self._conn
        cls = _MSG_TYPES.get(type_id)
        if cls is MLaneSegment and conn.lane_group is not None \
                and (flags & FLAG_FIXED) and blob_len \
                and not (seq and seq <= conn.in_seq):
            # the in_seq guard keeps a REPLAYED duplicate (acked but
            # re-sent across a lane revival) from re-creating reassembly
            # state the serve loop is about to drop
            try:
                dest = conn.lane_group.frag_view(
                    _unpack_fixed(cls, payload, None), blob_len)
            except Exception:
                dest = None
            if dest is not None:
                if dest.ndim != 1 or dest.itemsize != 1:
                    dest = dest.cast("B")
                return dest, dest
        if getattr(cls, "BLOB_VIEW_OK", False):
            dest = memoryview(np.empty(blob_len, dtype=np.uint8)).cast("B")
            return dest, dest
        blob = bytearray(blob_len)
        return memoryview(blob), blob

    def _parse(self) -> tuple:
        """Every frame that is whole in the head, verified: (what to
        deliver, in order: an int is a received ack's seq, a tuple is
        read_frame's; the BadFrame that ends the stream, or None; body
        bytes copied head -> destination).  A frame whose body is not
        all here goes in flight; a partial front stays in the head."""
        head, mv = self._head, self._head_mv
        pos, fill = self._pos, self._fill
        crc_on = self._conn.crc_enabled
        # crc regions of the head, and for each the index in `done` and
        # the type it belongs to
        offs, lens, wants, owner = [], [], [], []
        done: list = []
        zipped: list = []
        error: Optional[BaseException] = None
        copied = 0
        need = _HDR.size  # bytes from pos before the parser can go on
        while fill - pos >= _HDR.size:
            length, type_id, version, flags, crc, seq = \
                _HDR.unpack_from(head, pos)
            start = pos + _HDR.size
            if flags & FLAG_BLOB:
                need = _HDR.size + _BLOB_PFX.size
                if fill - pos < need:
                    break
                plen, blob_crc = _BLOB_PFX.unpack_from(head, start)
                if _BLOB_PFX.size + plen > length:
                    # a corrupt plen would desync the stream
                    error = BadFrame(f"bad blob prefix on type {type_id}")
                    break
                need += plen
                if fill - pos < need:
                    break
                front = pos + need
                payload = bytes(mv[front - plen:front])
                blob_len = length - _BLOB_PFX.size - plen
                if crc and crc_on:
                    # one region covers prefix+pickled: crc32c over the
                    # contiguous span == the chained tx-side crc
                    offs.append(start)
                    lens.append(front - start)
                    wants.append(crc)
                    owner.append((len(done), type_id))
                dest, blob = self._blob_dest(type_id, flags, seq, payload,
                                             blob_len)
                have = min(fill - front, blob_len)
                dest[:have] = mv[front:front + have]
                copied += have
                want = blob_crc if crc_on else 0
                frame = (type_id, version, seq, payload, length, blob,
                         bool(flags & FLAG_FIXED), bool(want))
                if have < blob_len:
                    self._body, self._body_pos = dest, have
                    self._frame = (frame, want, 0)
                    self._front = bytes(mv[pos:front])
                    pos = fill
                    break
                if want:
                    offs.append(front)
                    lens.append(blob_len)
                    wants.append(want)
                    owner.append((len(done), type_id))
                done.append(frame)
                pos = front + blob_len
            else:
                need = _HDR.size + length
                if need > len(head):
                    # a payload the head cannot hold lands like a body
                    dest = memoryview(bytearray(length))
                    have = fill - start
                    dest[:have] = mv[start:fill]
                    copied += have
                    self._body, self._body_pos = dest, have
                    self._frame = ((type_id, version, seq, dest.obj, length,
                                    None, bool(flags & FLAG_FIXED), False),
                                   crc if crc_on else 0,
                                   flags & FLAG_COMPRESSED)
                    self._front = bytes(mv[pos:start])
                    pos = fill
                    break
                if fill - pos < need:
                    break
                if crc and crc_on:
                    offs.append(start)
                    lens.append(length)
                    wants.append(crc)
                    owner.append((len(done), type_id))
                if type_id == ACK_TYPE and length == _ACK_SEQ.size:
                    done.append(_ACK_SEQ.unpack_from(head, start)[0])
                else:
                    if flags & FLAG_COMPRESSED:
                        zipped.append(len(done))
                    done.append((type_id, version, seq,
                                 bytes(mv[start:start + length]), length,
                                 None, bool(flags & FLAG_FIXED), False))
                pos += need
            need = _HDR.size
        if offs:
            bad = self._verify(head, offs, lens, wants)
            if bad >= 0:
                # the first bad region is the first bad frame: what is
                # before it is delivered, nothing after it is looked at
                idx, type_id = owner[bad]
                del done[idx:]
                error = BadFrame(f"crc mismatch on frame type {type_id}")
        if error is not None:
            return done, error, copied
        for i in zipped:
            f = done[i]
            done[i] = f[:3] + (zlib.decompress(f[3]),) + f[4:]
        if pos == fill:
            pos = fill = 0
        elif pos + need > len(head):
            part = bytes(mv[pos:fill])
            if need > len(head):  # a blob frame's front: rare, and kept
                self._head = head = bytearray(need)
                self._head_mv = memoryview(head)
            head[:len(part)] = part
            pos, fill = 0, len(part)
        self._pos, self._fill = pos, fill
        return done, None, copied

    def _finish_body(self) -> tuple:
        """The frame in flight has all its bytes: _parse's triple."""
        (frame, want, compressed), body = self._frame, self._body
        self._frame = self._body = None
        if want and self._verify(body, [0], [len(body)], [want]) >= 0:
            return [], BadFrame(("blob crc" if frame[5] is not None
                                 else "crc") + " mismatch on frame type "
                                + str(frame[0])), 0
        if compressed:
            frame = frame[:3] + (zlib.decompress(frame[3]),) + frame[4:]
        return [frame], None, 0

    def _verify(self, buf, offs: list, lens: list, wants: list) -> int:
        """Index of the first region of `buf` whose crc is not the one
        stated, or -1: one released-GIL call on the native arm."""
        conn = self._conn
        with tracing.section("messenger", "crc_verify"):
            if conn.wp is not None and conn.crc_fn is checksum:
                self._perf.inc("native_rx_calls")
                self._perf.inc("native_bytes", sum(lens))
                return conn.wp.wirepy_verify_regions(buf, offs, lens, wants)
            mv = memoryview(buf)
            for i, off in enumerate(offs):
                if conn.crc_fn(mv[off:off + lens[i]]) != wants[i]:
                    return i
        return -1

    def _deliver(self, done: list, error, copied: int) -> None:
        conn, perf = self._conn, self._perf
        stash = conn._rx_stash
        acks = held = 0
        for f in done:
            if type(f) is int:
                conn.handle_ack(f)
                acks += 1
            else:
                stash.append(f)
                held += _HDR.size + f[4]
        if done:
            self._held += held
            perf.inc("rx_framed", len(done))
            perf.inc("rx_bytes", held + acks * (_HDR.size + _ACK_SEQ.size))
            if acks:
                perf.inc("rx_inline_acks", acks)
            # as on the other readers: from a frame's front in hand to
            # its payload in hand, the loop turns between a body's reads
            # included (the framer's own time is the section rx_frame)
            dt = time.monotonic() - self._t0
            perf.tinc("rx_io", dt)
            perf.hinc("rx_io_us", dt * 1e6)
        if copied:
            perf.inc("rx_copied_bytes", copied)
        if error is not None:
            conn._rx_error = error
            self._dead = True
            self._body = self._frame = None
            self._pause(True)
        elif self._held:
            self._backpressure()
        if stash or error is not None:
            self._wake()

    def _backpressure(self) -> None:
        landed = self._held
        frames = len(self._conn._rx_stash)
        if self._body is not None:
            landed += self._body_pos
            frames += 1
        if self._read_paused:
            if frames <= 1 or landed < self._LIMIT // 2:
                self._pause(False)
        elif frames > 1 and landed > self._LIMIT:
            self._pause(True)

    def _pause(self, on: bool) -> None:
        self._read_paused = on
        try:
            if on:
                self._transport.pause_reading()
            else:
                self._transport.resume_reading()
        except Exception:
            pass

    def eof_received(self):
        self._eof = True
        self._wake()
        return False

    def connection_lost(self, exc) -> None:
        self._eof = True
        self._exc = exc
        self._wake()
        if self.corked is not None:
            self.corked._on_lost(exc)
        # the StreamWriter still drains through the ORIGINAL stream
        # protocol: without this forward, a drain() parked on a paused
        # writer never learns the connection died and waits forever —
        # holding the connection send lock and wedging every reconnect
        try:
            self._stream_protocol.connection_lost(exc)
        except Exception:
            pass

    def pause_writing(self) -> None:
        self._stream_protocol.pause_writing()

    def resume_writing(self) -> None:
        self._stream_protocol.resume_writing()

    def _wake(self) -> None:
        w = self._waiter
        if w is not None and not w.done():
            w.set_result(None)

    # -- reader side ---------------------------------------------------------

    async def wait(self) -> None:
        """Park read_frame until a burst leaves something to pop; at the
        stream's end raise what a readexactly would have (the frame in
        flight goes with its transport: replay delivers it)."""
        if self._eof:
            if self._exc is not None and not isinstance(
                    self._exc, (ConnectionError, OSError)):
                raise self._exc
            raise asyncio.IncompleteReadError(b"", None)
        self._waiter = asyncio.get_running_loop().create_future()
        try:
            await self._waiter
        finally:
            self._waiter = None

    def popped(self, cost: int) -> None:  # read_frame took a frame
        self._held -= _HDR.size + cost
        if self._read_paused:
            self._backpressure()

    def unframed(self) -> bytes:
        """The bytes received that are not a frame yet, as the wire had
        them (the framer's tests read where it stands)."""
        if self._body is None:
            return bytes(self._head_mv[self._pos:self._fill])
        return self._front + bytes(self._body[:self._body_pos])


def _writelines(writer, segs) -> None:
    """writer.writelines(segs), refused on a transport that is closing:
    asyncio's transport.writelines (3.12) has no _conn_lost guard (its
    write() has), so on a connection already lost it registers a writer
    that outlives the socket, and the fd's next owner can never add its
    reader: its handshake waits forever (ROADMAP D0(a))."""
    t = getattr(writer, "transport", None)
    if t is not None and t.is_closing():
        raise ConnectionResetError("transport is closing")
    writer.writelines(segs)


class CorkedWriter:
    """Zero-copy scatter-gather tx path: once the handshake is done (and
    the transport's own write buffer is empty), the connection's flusher
    swaps the StreamWriter for this — writes go STRAIGHT from the frame
    segments to ``socket.sendmsg`` (writev), so frame bytes are never
    joined or copied into a transport buffer.  The asyncio transport
    keeps owning the rx side (FrameReceiver) and the fd's lifetime; this
    class only owns which bytes leave.

    Congestion handling: segments queue in a deque; a full socket
    registers an add_writer callback that resumes sendmsg as the kernel
    drains.  ``drain()`` parks senders until the backlog is fully
    written: queued segments are VIEWS of live caller buffers (encode
    outputs, store blobs), and a drain that returned with segments still
    queued would let the owner mutate bytes before the kernel reads
    them.  Zero-copy therefore trades the overlap a buffered writer has
    — the copies it saves are the whole point.

    Failure: a send error (or the transport's connection_lost, forwarded
    by FrameReceiver) fails queued segments and drain waiters with the
    transport error — the same surface StreamWriter.drain() has.

    Off the loop (PR 49): on the native arm a window of OFFLOOP_MIN_BYTES
    or more is not written in the loop's step.  Its segments are pinned
    and queued for the process's sender thread (`_Offloop`;
    native/wirepath.h), which copies them into the kernel without ever
    holding the GIL while the loop goes on; while the fd has a job there,
    every later window follows it, whatever its size (order on an fd is
    the order handed).  ``drain()`` keeps its contract: the completion
    the thread posts is what takes the bytes off ``_buffered``.  Before
    the socket may close, `_detach` takes the fd off the thread and waits
    until the thread is in no system call on it: the fd's next owner
    finds nothing of this one.  Only a writer whose transport's loss is
    forwarded to it hands over (`hears_loss`): no other path could call
    `_detach` in time."""

    IOV_MAX = 512  # segments per sendmsg call (conservative vs UIO_MAXIOV)
    # the window size from which the hand-over and its share of a completion
    # step cost the loop less than the writev they save: on the chip host
    # 64 KiB reads 33-41 us inline against 44-46 handed over, 512 KiB 143-150
    # against 68-72, the lines crossing near 107 KiB (tools/offloop_table.py;
    # PERF.md section 6, PR 49)
    OFFLOOP_MIN_BYTES = 128 << 10

    def __init__(self, transport, sock, stream_writer, wp=None, perf=None):
        self._transport = transport
        self._sock = sock
        self._sw = stream_writer  # close/wait_closed/extra-info delegate
        # native wirepath arm: one released-GIL writev call drains the
        # whole backlog (partial writes, EINTR, IOV batching loop in C)
        # instead of the Python sendmsg walk below; perf counts the arm
        self._wp = wp
        self._perf = perf
        loop = asyncio.get_running_loop()
        self._loop = loop
        # the PRIVATE writer registration transports themselves use: the
        # public add_writer refuses fds owned by a transport (ours is —
        # the transport keeps the rx side).  _maybe_cork gates on these
        # existing, so an event loop without them just never corks.
        self._add_writer = loop._add_writer
        self._remove_writer = loop._remove_writer
        self._fd = sock.fileno()
        self._segs: Deque = collections.deque()
        self._buffered = 0
        self._writer_on = False  # add_writer registered
        self._waiters: list = []
        self._exc: Optional[BaseException] = None
        # the loop's end of the sender thread once hears_loss gave it, the
        # jobs this fd has there, and whose the window being written is
        # (the flusher says, for the completion step's charge)
        self._off: Optional["_Offloop"] = None
        self._off_jobs = 0
        self.whose: Any = None

    def hears_loss(self, offloop: Optional["_Offloop"]) -> None:
        """The transport's protocol forwards connection_lost to this
        writer from now on (FrameReceiver.corked): its windows may go to
        the sender thread, `offloop` being the running loop's end of it
        (None: no native arm).  The fd number's last owner may have died
        without a word (a loop closed under its sockets): what the thread
        still holds for the number goes first."""
        if offloop is not None and self._off is None:
            offloop.cancel(self._fd)
            self._off = offloop

    def offloop_takes(self, nbytes: int) -> bool:
        """A window of `nbytes` written now would go to the sender thread:
        it is big enough for the hand-over to pay, or the fd has a job
        there that it has to follow."""
        off = self._off
        return off is not None and not off.closed and (
            self._off_jobs > 0 or nbytes >= self.OFFLOOP_MIN_BYTES)

    # -- StreamWriter surface -------------------------------------------------

    def write(self, data) -> None:
        self.writelines([data])

    def writelines(self, segments) -> None:
        if self._exc is not None:
            return  # error surfaces at drain(), like StreamWriter
        segs, total = _norm_segments(segments)
        if total and self.offloop_takes(total):
            self._hand_over(segs, total)
            return
        self._segs.extend(segs)
        self._buffered += total
        if not self._writer_on:
            self._do_send()

    def _hand_over(self, segs, total: int) -> None:
        """Queue the window for the sender thread.  What the loop's own
        writer still waits to write (small windows a full socket refused:
        nothing is on the thread then) leaves first, in the same job."""
        nbytes = total
        if self._segs:
            segs = [*self._segs, *segs]
            nbytes += self._buffered
            self._segs.clear()
            self._buffered = 0
            self._writer_off()
        whose, self.whose = self.whose, None
        try:
            depth = self._off.submit(self, segs, nbytes, whose)
        except OSError as e:
            self._on_lost(e)
            return
        self._buffered += nbytes
        self._off_jobs += 1
        perf = self._perf
        if perf is not None:
            perf.inc("tx_offloop_windows")
            perf.inc("tx_offloop_bytes", nbytes)
            perf.tinc("tx_offloop_depth", depth)
            if total < self.OFFLOOP_MIN_BYTES:
                perf.inc("tx_offloop_behind")

    def _job_done(self, nbytes: int, result: int, eagains: int,
                  waited: float) -> None:
        """The sender thread ended a job of this fd (`_Offloop._reap`, on
        the loop): `result` is its bytes, all of them, or -errno."""
        self._off_jobs -= 1
        if self._exc is not None or result == -errno.ECANCELED:
            return  # lost or detached meanwhile: nobody waits for these
        if result < 0:
            self._on_lost(OSError(-result, os.strerror(-result)))
            return
        perf = self._perf
        if perf is not None:
            perf.inc("native_tx_calls", 1 + eagains)
            perf.inc("native_bytes", nbytes)
            perf.tinc("tx_offloop_lat", waited)
            if eagains:
                perf.inc("tx_offloop_eagain", eagains)
        self._buffered -= nbytes
        self._wake()

    async def drain(self) -> None:
        while self._exc is None and self._buffered > 0:
            fut = self._loop.create_future()
            self._waiters.append(fut)
            await fut
        if self._exc is not None:
            exc = self._exc
            raise exc if isinstance(exc, Exception) \
                else ConnectionResetError("connection lost")

    def close(self) -> None:
        # best-effort final flush, then the transport closes the fd; any
        # still-unsent segments are dropped (lossless replay re-delivers)
        if self._exc is None and self._segs and not self._writer_on:
            self._do_send()
        self._detach()
        self._sw.close()

    async def wait_closed(self) -> None:
        await self._sw.wait_closed()

    def get_extra_info(self, *a, **kw):
        return self._sw.get_extra_info(*a, **kw)

    @property
    def transport(self):
        return self._transport

    # -- socket side ----------------------------------------------------------

    def _do_send(self) -> None:
        try:
            if self._wp is not None and self._segs:
                # ONE foreign call writes the whole backlog with the
                # GIL released — wirepy_writev loops partial writes /
                # EINTR / IOV_MAX internally and returns only on
                # completion or EAGAIN (the PyDLL shim parses the
                # segment list itself, so the Python side pays a bare
                # list() per call)
                written = self._wp.wirepy_writev(self._fd,
                                                 list(self._segs))
                if self._perf is not None:
                    self._perf.inc("native_tx_calls")
                    if written:
                        self._perf.inc("native_bytes", written)
                if written:
                    self._advance(written)
                if self._segs:
                    raise BlockingIOError  # kernel buffer full
            while self._segs:
                if len(self._segs) > self.IOV_MAX:
                    batch = list(itertools.islice(self._segs, self.IOV_MAX))
                else:
                    batch = list(self._segs)
                sent = self._sock.sendmsg(batch)
                self._advance(sent)
        except (BlockingIOError, InterruptedError):
            if not self._writer_on:
                self._writer_on = True
                self._add_writer(self._fd, self._do_send)
            return
        except OSError as e:
            self._on_lost(e)
            return
        self._writer_off()
        self._wake()

    def _advance(self, n: int) -> None:
        self._buffered -= n
        while n and self._segs:
            head = self._segs[0]
            if n >= head.nbytes:
                n -= head.nbytes
                self._segs.popleft()
            else:
                self._segs[0] = head[n:]
                n = 0

    def _wake(self) -> None:
        if self._buffered == 0 or self._exc is not None:
            waiters, self._waiters = self._waiters, []
            for w in waiters:
                if not w.done():
                    w.set_result(None)

    def _writer_off(self) -> None:
        if self._writer_on:
            self._writer_on = False
            try:
                self._remove_writer(self._fd)
            except Exception:
                pass

    def _detach(self) -> None:
        """Nothing of this writer touches the fd after this: the loop's
        writer registration goes, and the sender thread's jobs with it
        (returns with the thread in no system call on the fd)."""
        self._writer_off()
        if self._off_jobs > 0:
            self._off.cancel(self._fd)

    def _on_lost(self, exc) -> None:
        if self._exc is None:
            self._exc = exc if exc is not None else \
                ConnectionResetError("connection lost")
        self._detach()
        self._segs.clear()
        self._buffered = 0
        self._wake()


class _Offloop:
    """One event loop's end of the process's sender thread
    (native/wirepath.h; CorkedWriter "Off the loop").  It owns the eventfd
    the thread writes when jobs of this loop ended (once per batch: not
    again until the loop looked) and the ONE reader callback that takes
    them, however many windows finished: each job's buffers are released,
    its writer's `_buffered` advances and its drain() waiters wake, an
    error goes to the writer's `_on_lost`.  The step is the messenger's
    layer by its kind, and is charged to the finished windows' bytes by
    key as the flusher's own steps are (PERF.md section 3)."""

    def __init__(self, loop, wp) -> None:
        self.loop = loop
        self.wp = wp
        self.efd = os.eventfd(0, os.EFD_NONBLOCK | os.EFD_CLOEXEC)
        # token -> (writer, bytes, whose, handed over at)
        self.jobs: Dict[int, tuple] = {}
        # the messengers with connections on this loop; the last one's
        # shutdown closes this
        self.users: "weakref.WeakSet[Messenger]" = weakref.WeakSet()
        self.closed = False
        loop.add_reader(self.efd, self._on_done)

    def submit(self, writer: "CorkedWriter", segs, nbytes: int,
               whose) -> int:
        """Pin `segs` and queue them for `writer`'s fd; returns the jobs
        the thread had unfinished."""
        token = next(_OFFLOOP_TOKENS)
        depth = self.wp.wirepy_sender_submit(writer._fd, self.efd, token,
                                             segs)
        self.jobs[token] = (writer, nbytes, whose, time.perf_counter())
        return depth

    def cancel(self, fd: int) -> None:
        """Take `fd` off the thread; returns with the thread in no system
        call on it.  The buffers of the jobs dropped, and of one that
        failed on the thread a moment before, are released here."""
        self.wp.wire_sender_cancel(fd)
        self._reap(None)

    def _on_done(self) -> None:
        weights: Optional[dict] = {} if tracing.metered() else None
        self._reap(weights)
        if weights:
            tracing.charge_many(weights)

    def _reap(self, weights: Optional[dict]) -> None:
        jobs = self.jobs
        now = time.perf_counter()
        for token, result, eagains in self.wp.wirepy_sender_reap(self.efd):
            job = jobs.pop(token, None)
            if job is None:
                continue
            writer, nbytes, whose, t0 = job
            if weights is not None:
                if type(whose) is dict:
                    for key, n in whose.items():
                        weights[key] = weights.get(key, 0) + n
                else:
                    weights[whose] = weights.get(whose, 0) + nbytes
            writer._job_done(nbytes, result, eagains, now - t0)

    def release(self, messenger: "Messenger") -> None:
        """`messenger` shut down (any thread's loop): without users this
        closes, on its own loop."""
        with _OFFLOOP_LOCK:
            self.users.discard(messenger)
            if self.users or self.closed:
                return
        try:
            here = asyncio.get_running_loop()
        except RuntimeError:
            here = None
        if here is self.loop or self.loop.is_closed():
            self.close()
        else:
            try:
                self.loop.call_soon_threadsafe(self.close)
            except RuntimeError:
                self.close()  # the loop shut down under us

    def close(self) -> None:
        with _OFFLOOP_LOCK:
            if self.closed or self.users:
                return
            self.closed = True
        if not self.loop.is_closed():
            self.loop.remove_reader(self.efd)
        # what is still on the thread for this loop is dropped (never
        # another loop's jobs on an fd number handed out again), its
        # buffers released, then the thread forgets the eventfd
        self.wp.wire_sender_close_chan(self.efd)
        self._reap(None)
        self.wp.wire_sender_close_chan(self.efd)
        os.close(self.efd)
        # under the lock no loop gets a new end while the thread stops
        with _OFFLOOP_LOCK:
            if all(off.closed for off in _OFFLOOPS.values()):
                self.wp.wire_sender_stop()  # the next hand-over starts it

    def abandon(self) -> None:
        """The loop was closed under its messengers (no shutdown): what
        the thread holds for it is dropped and the eventfd closed."""
        self.users.clear()
        self.close()


# the loops' ends of the sender thread; a job's token is the process's.
# A process runs several loops (one after another in tests, beside each
# other for a client on a thread of its own): the lock guards the table,
# the one-time hooks and each end's users / closed
_OFFLOOPS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_OFFLOOP_TOKENS = itertools.count(1)
_OFFLOOP_HOOKS = False
_OFFLOOP_LOCK = threading.RLock()


def _offloops_forked() -> None:
    """In a fork's child: none of the parent's loops, and a lock that no
    thread of the parent's holds."""
    global _OFFLOOP_LOCK
    _OFFLOOP_LOCK = threading.RLock()
    _OFFLOOPS.clear()


def _offloop_of(loop, wp, user: Optional["Messenger"] = None
                ) -> Optional[_Offloop]:
    """`loop`'s end of the sender thread, made at first use, `user` among
    its users; None where the native arm or the platform cannot give one."""
    global _OFFLOOP_HOOKS
    if wp is None or not hasattr(os, "eventfd"):
        return None
    with _OFFLOOP_LOCK:
        off = _OFFLOOPS.get(loop)
        if off is None or off.closed:
            if not _OFFLOOP_HOOKS:
                _OFFLOOP_HOOKS = True
                # the thread ends with the interpreter; a fork's child has
                # no thread and none of these loops (the library forgets
                # its own half, native/wirepath.cc atfork_child)
                atexit.register(wp.wire_sender_stop)
                os.register_at_fork(after_in_child=_offloops_forked)

                def writev_seconds():
                    st = wp.wire_sender_stats()
                    return st["writev_ns"] * 1e-9, st["writev_calls"]
                tracing.thread_source("messenger", writev_seconds)
            for other in list(_OFFLOOPS.values()):
                if other.loop.is_closed():
                    other.abandon()
            off = _OFFLOOPS[loop] = _Offloop(loop, wp)
        if user is not None:
            off.users.add(user)
        return off


class _AckSweep:
    """One messenger's owed acks (module docstring "Acks WAIT FOR
    COMPANY"): the connections that owe, and the ONE timer
    that settles what no data window and no bound has.  A tick writes, in
    its own loop step, the ack of every connection whose debt would pass
    ACK_DELAY_S before a further tick could come (a quarter of the
    deadline ahead: at most four ticks a deadline, none while nothing is
    owed), then re-arms for the oldest debt left."""

    __slots__ = ("messenger", "owing", "timer", "ticks")

    def __init__(self, messenger: "Messenger") -> None:
        self.messenger = messenger
        # connections whose debt the sweep has yet to look at; one that
        # was settled another way, or went with its transport, leaves at
        # the next tick
        self.owing: Dict["Connection", None] = {}
        self.timer: Optional[asyncio.TimerHandle] = None
        self.ticks = 0

    def owe(self, conn: "Connection") -> None:
        """`conn` began to owe an ack (its _ack_since says when)."""
        self.owing[conn] = None
        if self.timer is None and not self.messenger._shutdown:
            self.timer = asyncio.get_running_loop().call_later(
                self.messenger.ACK_DELAY_S, self._tick)

    def _tick(self) -> None:
        self.timer = None
        self.ticks += 1
        # a timer's step is nobody's until it says so: the messenger's
        # time, and every ack's (tracing.mark, tracing.charge)
        tracing.mark("messenger")
        tracing.charge(ACK_CHARGE)
        delay = self.messenger.ACK_DELAY_S
        now = time.monotonic()
        due = now - 0.75 * delay
        oldest = None
        for conn in list(self.owing):
            if conn._ack_pending < 0:
                del self.owing[conn]
            elif conn._ack_since <= due:
                del self.owing[conn]
                conn.sweep_ack()
            elif oldest is None or conn._ack_since < oldest:
                oldest = conn._ack_since
        if oldest is not None and not self.messenger._shutdown:
            self.timer = asyncio.get_running_loop().call_later(
                oldest + delay - now, self._tick)

    def cancel(self) -> None:
        if self.timer is not None:
            self.timer.cancel()
            self.timer = None
        self.owing.clear()


class Connection:
    """One ordered session with a peer.  For lossless sessions this object
    outlives TCP transports: seqs, the unacked queue, and the dedupe floor
    persist while transports come and go (transport_gen fences stale serve
    loops)."""

    def __init__(self, messenger: "Messenger", reader, writer,
                 peer: Tuple[str, int], policy: Policy,
                 peer_name: str = "", outbound: bool = False):
        self.messenger = messenger
        self.reader = reader
        self.writer = writer
        self.peer = peer
        self.peer_name = peer_name
        self.policy = policy
        self.outbound = outbound
        # how the peer authenticated ("ticket" / "secret" / "none") — set
        # by the acceptor after _handshake_in; outbound conns keep "none"
        self.auth_kind = "none"
        self.auth_entity_type = ""
        self.closed = False
        self.transport_gen = 0
        self.out_seq = 0
        self.in_seq = 0  # highest data seq dispatched (dedupe floor)
        # the lane-group membership when this connection is one lane of
        # a striped peer session
        self.lane_group: Optional["LaneGroup"] = None
        self.lane_idx = 0
        self.throttle = messenger.dispatch_throttle
        # per-connection session id: acceptors key replay sessions on it, so
        # a REPLACED connection never collides with its predecessor's seqs
        self.session_id = random.randbytes(8).hex()
        self.unacked: Deque[Tuple[int, bytes]] = collections.deque()
        from ceph_tpu.common.lockdep import make_async_mutex

        self._send_lock = make_async_mutex("conn-send")
        # corked outbox (module docstring "Cork/flush discipline"):
        # framed segments awaiting the next flush window, the shared
        # future senders in that window await, and the single flusher
        # task that drains windows with one writelines+drain each
        self._outbox: list = []
        self._outbox_frames = 0
        self._outbox_bytes = 0
        # the same bytes by the loop meter's charge key: whose window the
        # flusher's step and its socket write are (tracing.charge_many)
        self._outbox_by: Dict[tuple, int] = {}
        # the ack this side owes (module docstring "Acks WAIT FOR
        # COMPANY"): the highest seq owed (-1 = none), the payload bytes
        # the debt covers, when it began (time.monotonic), the
        # counter of the way it leaves ALONE once it is due (tx_acks_bound
        # / tx_acks_swept; None = not due: only a data window takes it),
        # the highest seq an ack frame has carried
        self._ack_pending = -1
        self._ack_bytes = 0
        self._ack_since = 0.0
        self._ack_due: Optional[str] = None
        self._ack_sent = 0
        self._flush_fut: Optional[asyncio.Future] = None
        self._flusher: Optional[asyncio.Task] = None
        self._corked_ok = bool(_cget(messenger.conf, "ms_corked_writev",
                                     True))
        # crc/compression resolved once per connection (v2 negotiates at
        # handshake time; avoids typed-config parsing on the hot path)
        conf = messenger.conf
        self.crc_enabled = bool(_cget(conf, "ms_crc_data", True))
        self.compress_min = int(_cget(conf, "ms_compress_min_size", 0) or 0)
        # frame checksum for THIS connection: crc32c when both ends run
        # the native build (negotiated via the hello's "ckind"), zlib
        # otherwise — a silent per-host resolver difference must degrade,
        # not deadlock (set by the handshake; default local resolver)
        self.crc_fn = checksum
        # native wirepath arm (messenger-resolved): rx drains consult it
        # together with crc_fn — a zlib-negotiated connection keeps the
        # python arm so frame bytes stay identical either way
        self.wp = messenger.wirepath
        # frames the FrameReceiver completed and verified, awaiting
        # read_frame pops (each entry is read_frame's tuple); _rx_error
        # raises once the stash drains (a bad frame mid-burst fails the
        # connection AFTER its valid predecessors dispatch)
        self._rx_stash: Deque = collections.deque()
        self._rx_error: Optional[BaseException] = None

    def enable_fast_read(self) -> None:
        """Hand the transport's rx side to a FrameReceiver when the
        transport allows it (plaintext TCP; not already swapped).
        Called at serve-loop start — the handshake has fully drained its
        reads, and what the stream already buffered is framed first."""
        r = self.reader
        if not isinstance(r, asyncio.StreamReader):
            return  # SecureStream (AES-GCM) or already a FrameReceiver
        try:
            transport = r._transport  # the stream pair shares it
            if transport is None:
                return
            receiver = FrameReceiver(self, transport,
                                     transport.get_protocol())
            leftover = bytes(r._buffer)
            r._buffer.clear()
            if r.at_eof():
                receiver._eof = True  # FIN landed before the swap
            if isinstance(self.writer, CorkedWriter):
                # corked before this serve loop started, under the stream
                # protocol: it still has to hear of the connection's loss
                receiver.corked = self.writer
                self.writer.hears_loss(self.messenger._offloop_here())
            transport.set_protocol(receiver)
            # the StreamReader may have left the transport paused (its
            # own flow control); the receiver starts unpaused, so resume
            # or reads would hang forever once the leftover is framed
            try:
                transport.resume_reading()
            except Exception:
                pass
        except Exception:
            return
        self.reader = receiver
        receiver.feed(leftover)

    # -- frame IO ------------------------------------------------------------

    def _frame(self, type_id: int, version: int, payload: bytes, seq: int,
               flags: int = 0) -> bytes:
        if self.compress_min and len(payload) >= self.compress_min:
            compressed = zlib.compress(payload, 1)
            if len(compressed) < len(payload):
                payload = compressed
                flags |= FLAG_COMPRESSED
        crc = self.crc_fn(payload) if self.crc_enabled else 0
        return _HDR.pack(len(payload), type_id, version, flags, crc, seq) + payload

    def _frame_segments(self, type_id: int, version: int, pickled: bytes,
                        blob, seq: int, flags: int = 0,
                        blob_crc: Optional[int] = None):
        """Scatter-gather frame for a blob message: the bulk bytes are
        never concatenated into a serialized buffer — the transport
        writev's [hdr, prefix, pickled, blob...] as-is (a BufferList blob
        contributes each piece unjoined).  The header crc covers
        prefix+pickled (small); the blob carries its own crc32c —
        ``blob_crc`` passes a crc the sender already holds over exactly
        these bytes (MECSubWrite.chunk_crc, a stored shard's meta crc) so
        the wire pass is skipped, the reference's bufferlist cached-crc
        discipline.  Blob frames skip on-wire compression (bulk data is
        usually incompressible shard bytes; the pickled part is tiny)."""
        if isinstance(blob, BufferList):
            segs = blob.segments
            blob_len = blob.nbytes
        else:
            segs = [blob]
            blob_len = len(blob)
        if blob_crc is None:
            with tracing.section("messenger", "crc"):
                if not self.crc_enabled:
                    blob_crc = 0
                elif self.wp is not None and len(segs) > 1 \
                        and self.crc_fn is checksum:
                    # multi-piece BufferList: ONE released-GIL call chains
                    # the crc across every piece (was one ctypes
                    # round-trip per piece)
                    blob_crc = self.wp.wirepy_crc_chain(segs)
                    self.messenger.perf.inc("native_tx_calls")
                    self.messenger.perf.inc("native_bytes", blob_len)
                else:
                    blob_crc = 0
                    for s in segs:
                        blob_crc = self.crc_fn(s, blob_crc)
        else:
            self.messenger.perf.inc("tx_crc_reused")
        prefix = _BLOB_PFX.pack(len(pickled), blob_crc)
        crc = (self.crc_fn(pickled, self.crc_fn(prefix))
               if self.crc_enabled else 0)
        hdr = _HDR.pack(_BLOB_PFX.size + len(pickled) + blob_len,
                        type_id, version, FLAG_BLOB | flags, crc, seq)
        return [hdr, prefix, pickled, *segs]

    # -- corked outbox (tx coalescing) ---------------------------------------

    def _seg_len(self, s) -> int:
        return s.nbytes if isinstance(s, memoryview) else len(s)

    def _enqueue(self, data, nbytes: int, charge: tuple,
                 was) -> asyncio.Future:
        """Append one framed message of `nbytes` to the outbox; the
        future returned is the flush window's that carries it.
        Concurrent senders in the same window share ONE writelines + ONE
        drain; a transport failure fails the whole window (each sender
        sees ConnectionResetError).  The sender's step was the message's
        up to here (`charge`, the key its bytes are kept under for the
        flusher); `was` is the caller's charge, put back now: a charge
        ends with its step, and the sender awaits next."""
        tracing.charge(was, claim=False)
        if self.closed:
            raise ConnectionResetError("connection closed")
        if isinstance(data, list):
            self._outbox.extend(data)
        else:
            self._outbox.append(data)
        self._outbox_frames += 1
        self._outbox_bytes += nbytes
        by = self._outbox_by
        by[charge] = by.get(charge, 0) + nbytes
        fut = self._flush_fut
        if fut is None:
            fut = self._flush_fut = \
                asyncio.get_running_loop().create_future()
        self._kick_flusher()
        return fut

    def queue_ack(self, seq: int, nbytes: int = 0) -> None:
        """Owe the peer a cumulative ack up to ``seq``, for ``nbytes``
        more of payload (acks are cumulative: the peer pops every unacked
        frame <= seq, so only the highest seq owed ever needs a frame).
        Nobody is woken: the debt leaves with this connection's next data
        window, or alone once it covers ACK_OWED_BYTES (now) or is
        ACK_DELAY_S old (the messenger's sweep) — module docstring "Acks
        WAIT FOR COMPANY"."""
        if self.closed:
            return
        m = self.messenger
        if self._ack_pending >= 0:
            m.perf.inc("tx_acks_coalesced")
            if seq > self._ack_pending:
                self._ack_pending = seq
        else:
            self._ack_pending = seq
            sweep = m._ack_sweep
            if sweep is None:
                sweep = m._ack_sweep = _AckSweep(m)
            self._ack_since = time.monotonic()
            sweep.owe(self)
        self._ack_bytes += nbytes
        if self._ack_bytes >= m.ACK_OWED_BYTES and self._ack_due is None:
            self._ack_due = "tx_acks_bound"
            self._kick_flusher()

    def _kick_flusher(self) -> None:
        if self._flusher is None or self._flusher.done():
            m = self.messenger
            self._flusher = asyncio.get_running_loop().create_task(
                self._flush_loop())
            m._tasks.add(self._flusher)
            self._flusher.add_done_callback(m._tasks.discard)

    def _ack_frame(self) -> bytes:
        """The frame that settles the debt (seqs are consecutive, so what
        it newly covers is its distance from the last one written)."""
        seq = self._ack_pending
        if seq > self._ack_sent:
            self.messenger.perf.inc("ack_frames_covered",
                                    seq - self._ack_sent)
            self._ack_sent = seq
        self._ack_pending, self._ack_bytes, self._ack_due = -1, 0, None
        payload = _ACK_SEQ.pack(seq)
        return _HDR.pack(8, ACK_TYPE, 1, 0, self.crc_fn(payload), 0) + payload

    def _cut_window(self):
        """Take what is queued as ONE flush window and count it: the
        outbox, and the owed ack when the window has data for it to ride
        or the debt is due.  Returns (segs, nbytes, whose, fut): the
        segments to write (none: nothing to do), their bytes, the same
        bytes by the loop meter's charge key, the future the window's
        senders await.  The caller's step is the window's from here."""
        perf = self.messenger.perf
        segs, self._outbox = self._outbox, []
        frames, self._outbox_frames = self._outbox_frames, 0
        nbytes, self._outbox_bytes = self._outbox_bytes, 0
        whose, self._outbox_by = self._outbox_by, {}
        fut, self._flush_fut = self._flush_fut, None
        had_data = bool(segs)
        if self._ack_pending >= 0 and (had_data or self._ack_due):
            perf.inc("tx_acks_rode" if had_data else self._ack_due)
            ack = self._ack_frame()
            segs.append(ack)
            frames += 1
            nbytes += len(ack)
            whose[ACK_CHARGE] = len(ack)
            perf.inc("tx_acks")
        if segs:
            # this step and the socket write are the window's, by its
            # bytes by type (an ack-only window: the ack's)
            tracing.charge_many(whose)
            if len(whose) > 1 \
                    and len({family for family, _ in whose}) > 1:
                perf.inc("tx_flush_mixed")
            perf.inc("tx_flush_data" if had_data else "tx_flush_ack")
            perf.inc("tx_flushes")
            perf.hinc("tx_flush_frames", frames)
            perf.hinc("tx_flush_bytes", nbytes)
        return segs, nbytes, whose, fut

    def sweep_ack(self) -> None:
        """The messenger's sweep found this debt at its deadline: the ack
        leaves alone.  Where the ordinary window can be written here and
        now — nothing queued or being written, and a writer whose write
        is the socket's (CorkedWriter: plaintext TCP) with no backlog — it
        is, in the sweep's own loop step; else the flusher takes it (the
        ack rides, or follows, the window in hand)."""
        if self._ack_due is None:
            self._ack_due = "tx_acks_swept"
        if not (self._outbox or self._send_lock.locked()
                or (self._flusher is not None and not self._flusher.done())):
            self._maybe_cork()
            w = self.writer
            if isinstance(w, CorkedWriter) and not w._buffered:
                perf = self.messenger.perf
                segs, nbytes, _, _ = self._cut_window()  # no sender waits
                t_io = time.monotonic()
                try:
                    with perf.time_avg("tx_io"):
                        with tracing.section("messenger", "sock_write"):
                            _writelines(w, segs)
                except (ConnectionError, OSError):
                    # the transport is going and its reader's end closes
                    # the session: the peer replays, the dedupe path re-acks
                    return
                perf.inc("tx_bytes", nbytes)
                perf.hinc("tx_io_us", (time.monotonic() - t_io) * 1e6)
                return
        self._kick_flusher()

    async def _flush_loop(self) -> None:
        """The per-connection flusher: drains flush windows until the
        outbox is empty and no ack is due (an ack that is only owed waits
        for a data window, the bound or the sweep: queue_ack).  tx
        accounting lives in the window (_cut_window) and HERE so
        every socket write — messages, acks — lands in tx_io/tx_bytes;
        per-message framing cost and per-type counts are send()'s
        (_note_tx).  The tx_io timer starts INSIDE the lock: queueing
        behind an adopt_transport replay is not socket time."""
        perf = self.messenger.perf
        try:
            while (self._outbox or self._ack_due) and not self.closed:
                async with self._send_lock:
                    if self.closed:
                        break
                    self._maybe_cork()
                    segs, nbytes, whose, fut = self._cut_window()
                    if not segs:
                        break
                    gen = self.transport_gen
                    t_io = time.monotonic()
                    w = self.writer
                    # the inline arm's write, or the hand-over to the
                    # sender thread (whose completion step is charged to
                    # the window as this one is)
                    section = "sock_write"
                    if isinstance(w, CorkedWriter) \
                            and w.offloop_takes(nbytes):
                        section, w.whose = "sock_handoff", whose
                    try:
                        with perf.time_avg("tx_io"):
                            with tracing.section("messenger", section):
                                _writelines(w, segs)
                            await w.drain()
                    except (ConnectionError, OSError,
                            asyncio.TimeoutError) as e:
                        if fut is not None and not fut.done():
                            fut.set_exception(ConnectionResetError(
                                f"flush failed: {e}"))
                            fut.exception()  # mark retrieved (no-waiter GC)
                        # gen-fenced: a no-op here means adopt_transport
                        # replaced the transport under us — loop again and
                        # retry the remaining windows on the new writer
                        # (a genuine close ends the loop via its condition)
                        await self.close(gen)
                        continue
                    except asyncio.CancelledError:
                        raise
                    except BaseException as e:
                        # a framing/writer BUG must crash loudly — but
                        # never by leaving the window's senders parked on
                        # a future nobody will resolve
                        if fut is not None and not fut.done():
                            fut.set_exception(
                                ConnectionResetError(f"flush failed: {e}"))
                            fut.exception()
                        await self.close(gen)
                        raise
                    # a drain that waited resumes in a step of its own,
                    # still this window's
                    tracing.charge_many(whose)
                    perf.inc("tx_bytes", nbytes)
                    perf.hinc("tx_io_us",
                              (time.monotonic() - t_io) * 1e6)
                    if fut is not None and not fut.done():
                        fut.set_result(None)
        finally:
            if self.closed:
                self._fail_pending(ConnectionResetError("connection closed"))

    def _pin_replay_queue(self) -> None:
        """Materialize view segments of queued unacked frames to bytes.
        Runs at transport death: from here the frames may sit queued for
        a whole reconnect window (or forever, for a gone peer), and a
        queued VIEW would pin its whole backing buffer (e.g. the k-row
        encode matrix behind one shard's 1/k-sized view) for that long.
        While the transport is healthy the queue turns over within an
        RTT, so the hot path never pays this copy."""
        for i, (seq, data) in enumerate(self.unacked):
            if isinstance(data, list) \
                    and any(not isinstance(s, bytes) for s in data):
                self.unacked[i] = (seq, [
                    s if isinstance(s, bytes) else bytes(s) for s in data])

    def _fail_pending(self, exc: Exception) -> None:
        """Fail the pending flush window (senders awaiting it see the
        transport error) and drop un-flushed segments: lossless frames
        live in the unacked queue and replay on the adopted transport;
        un-flushed acks are re-queued by the dedupe path when the peer
        replays."""
        fut, self._flush_fut = self._flush_fut, None
        self._outbox = []
        self._outbox_frames = 0
        self._outbox_bytes = 0
        self._outbox_by = {}
        self._ack_pending, self._ack_bytes, self._ack_due = -1, 0, None
        if fut is not None and not fut.done():
            fut.set_exception(exc)
            fut.exception()  # mark retrieved: ok if every sender left

    def _maybe_cork(self) -> None:
        """Swap the StreamWriter for the zero-copy CorkedWriter when the
        transport allows it (plaintext TCP, nothing buffered in the
        transport, sendmsg available).  Called under the send lock at
        flush time — lazily, so it naturally re-engages after an
        adopt_transport handed us a fresh StreamWriter."""
        if not self._corked_ok:
            return
        w = self.writer
        if not isinstance(w, asyncio.StreamWriter):
            return  # SecureStream (AES-GCM) or already corked
        try:
            transport = w.transport
            if (transport is None or transport.is_closing()
                    or transport.get_write_buffer_size() != 0):
                return
            sock = transport.get_extra_info("socket")
            # unwrap asyncio's TransportSocket: its sendmsg() warns (and
            # is slated for removal); the raw socket is the real surface
            sock = getattr(sock, "_sock", sock)
            if sock is None or not hasattr(sock, "sendmsg"):
                return
            loop = asyncio.get_running_loop()
            if not hasattr(loop, "_add_writer"):
                return  # non-selector loop: keep the stream writer
            corked = CorkedWriter(transport, sock, w,
                                  wp=self.messenger.wirepath,
                                  perf=self.messenger.perf)
            proto = transport.get_protocol()
            if isinstance(proto, FrameReceiver):
                proto.corked = corked  # connection_lost fails its waiters
                corked.hears_loss(self.messenger._offloop_here())
        except Exception:
            return
        self.writer = corked

    async def send(self, msg: Any) -> None:
        # whoever awaits a send, the steps it takes are the messenger's
        # time (tracing.mark: the caller's layer is put back after)
        was = tracing.mark("messenger")
        try:
            await self._send(msg)
        finally:
            tracing.mark(was)

    async def _send(self, msg: Any) -> None:
        conf = self.messenger.conf
        inj = _cget(conf, "ms_inject_socket_failures", 0)
        injected = bool(inj) and random.randrange(inj) == 0
        if injected and not self.policy.replay:
            await self.close()
            raise ConnectionResetError("injected socket failure")
        delay = _cget(conf, "ms_inject_delay_max", 0)
        if delay:
            await asyncio.sleep(random.uniform(0, delay))
        # ms_inject_dup_frames: deliver this message TWICE (two frames,
        # two seqs — a genuine at-least-once delivery the receiver's seq
        # dedupe cannot filter), exercising the APPLICATION layer's
        # duplicate absorption.  Scoped to the client-op plane, which is
        # the layer contracted to absorb duplicates: MOSDOp dups dedupe
        # against the PG log's reqid set, MOSDOpReply dups against the
        # client's pop-once reply futures.  Other planes (sub-write
        # replies, peering gathers) count messages and are entitled to
        # the session's exactly-once delivery.
        dup_inj = _cget(conf, "ms_inject_dup_frames", 0)
        duplicate = (bool(dup_inj)
                     and type(msg).__name__ in ("MOSDOp", "MOSDOpReply")
                     and random.randrange(dup_inj) == 0)
        self.out_seq += 1
        seq = self.out_seq
        slot = self.messenger._type_slot(msg.TYPE_ID)
        # from here to the enqueue the step is this message's; what it
        # did before (a handler, an op's continuation) is not, so no claim
        was = tracing.charge(slot.charge, claim=False)
        t_frame = time.monotonic()
        with tracing.section("messenger", "encode_frame"):
            pickled, blob, fixed = encode_payload_parts(msg)
            flags = FLAG_FIXED if fixed else 0
            if blob is not None:
                # cached-crc reuse: a message that already carries a crc
                # of EXACTLY its blob bytes (BLOB_CRC_ATTR) skips the wire
                # crc pass — only when this connection's negotiated
                # checksum is the shared resolver the app-level crc was
                # computed with
                pre_crc = None
                crc_attr = getattr(type(msg), "BLOB_CRC_ATTR", None)
                if crc_attr is not None and self.crc_enabled \
                        and self.crc_fn is checksum:
                    v = msg.__dict__.get(crc_attr) or 0
                    if v:
                        pre_crc = v & 0xFFFFFFFF
                data = self._frame_segments(
                    msg.TYPE_ID, msg.VERSION, pickled, blob, seq, flags,
                    blob_crc=pre_crc)
            else:
                pre_crc = None
                data = self._frame(msg.TYPE_ID, msg.VERSION, pickled, seq,
                                   flags)
        nbytes = (sum(self._seg_len(p) for p in data)
                  if isinstance(data, list) else len(data))
        self.messenger._note_tx(slot, nbytes, time.monotonic() - t_frame)
        if self.policy.replay:
            # lossless send never fails: the frame joins the session queue
            # and reconnect+replay delivers it exactly once (reference
            # lossless_peer out_queue semantics).  Blob VIEWS stay views
            # here — on a healthy session the ack pops the frame within
            # an RTT, so the pin on the backing buffer is transient; the
            # frames only materialize to bytes when the transport DIES
            # (close() -> _pin_replay_queue), which is when a frame can
            # actually sit queued long enough for pinning to matter.
            self.unacked.append((seq, data))
            if injected:
                # injected transport failure: frame stays queued, session
                # survives, reconnect+replay delivers
                await self.close()
                return
            try:
                await self._enqueue(data, nbytes, slot.charge, was)
            except (ConnectionError, OSError):
                await self.close()
        else:
            await self._enqueue(data, nbytes, slot.charge, was)
        if duplicate and not self.closed:
            # the duplicate frame is best-effort: the knob exists to
            # exercise dedup, and a transport error here already has the
            # original frame's failure handling covering the message
            self.out_seq += 1
            dseq = self.out_seq
            if blob is not None:
                ddata = self._frame_segments(
                    msg.TYPE_ID, msg.VERSION, pickled, blob, dseq, flags,
                    blob_crc=pre_crc)
            else:
                ddata = self._frame(msg.TYPE_ID, msg.VERSION, pickled,
                                    dseq, flags)
            if self.policy.replay:
                self.unacked.append((dseq, ddata))
            try:
                await self._enqueue(ddata, nbytes, slot.charge, None)
            except (ConnectionError, OSError):
                pass

    def handle_ack(self, seq: int) -> None:
        while self.unacked and self.unacked[0][0] <= seq:
            self.unacked.popleft()

    def buffered_frame_len(self) -> Optional[int]:
        """Payload length of the next COMPLETE frame in hand: a frame
        the FrameReceiver stashed first, else whatever is fully buffered
        on a reader of another kind — the serve loop's rx batching
        predicate (batch only what needs no network wait)."""
        if self._rx_stash:
            return self._rx_stash[0][4]
        return Messenger._buffered_frame_len(self.reader)

    async def read_frame(self) -> Tuple[int, int, int, bytes, int, Any,
                                        bool, bool]:
        """Returns (type_id, version, seq, payload, cost, blob, fixed,
        blob_verified).  The dispatch throttle is charged `cost` bytes
        before the frame is handed over (receive-side backpressure,
        reference DispatchQueue throttle; a FrameReceiver bounds what
        lands ahead of it); the caller must put() cost back when done
        with the payload.  The reader's type picks the path: a
        FrameReceiver has framed the stream already and this pops its
        stash; a SecureStream
        (bytes are decrypted before they can land) or a plain
        StreamReader takes the readexactly chain below.  Blob frames (FLAG_BLOB) return the bulk bytes
        separately, checked against their own crc32c — ``blob_verified``
        says that check actually ran (crc enabled and present), so
        handlers holding an app-level crc of the same bytes
        (MECSubWrite.chunk_crc) can skip their own verify pass."""
        stash = self._rx_stash
        r = self.reader
        framed = isinstance(r, FrameReceiver)
        while framed and not stash:
            # the receiver frames the stream (and counted the bytes):
            # park until a burst leaves something to pop
            if self._rx_error is not None:
                err, self._rx_error = self._rx_error, None
                raise err
            await r.wait()
            if self.reader is not r:
                raise ConnectionResetError("transport replaced")
        if stash:
            frame = stash.popleft()
            if framed:
                r.popped(frame[4])
            await self.throttle.get(frame[4])
            return frame
        hdr = await self.reader.readexactly(_HDR.size)
        length, type_id, version, flags, crc, seq = _HDR.unpack(hdr)
        cost = length
        await self.throttle.get(cost)
        # rx_io clock starts AFTER the header lands: the header read is
        # where idle between-message waiting parks, and folding that into
        # the per-frame number would drown the transfer cost it measures
        t_io = time.monotonic()
        blob_verified = False
        try:
            blob = None
            if flags & FLAG_BLOB:
                head = await self.reader.readexactly(_BLOB_PFX.size)
                plen, blob_crc = _BLOB_PFX.unpack_from(head)
                if _BLOB_PFX.size + plen > length:
                    # a corrupt plen would drive the blob read negative
                    # and desync the stream — reject before any read
                    raise BadFrame(f"bad blob prefix on type {type_id}")
                pickled = await self.reader.readexactly(plen)
                blob = await self.reader.readexactly(
                    length - _BLOB_PFX.size - plen)
                with tracing.section("messenger", "crc_verify"):
                    if crc and self.crc_enabled and self.crc_fn(
                            pickled, self.crc_fn(head)) != crc:
                        raise BadFrame(
                            f"crc mismatch on frame type {type_id}")
                    if blob_crc and self.crc_enabled:
                        if self.crc_fn(blob) != blob_crc:
                            raise BadFrame(
                                f"blob crc mismatch on type {type_id}")
                        blob_verified = True
                payload = pickled
            else:
                payload = await self.reader.readexactly(length)
                with tracing.section("messenger", "crc_verify"):
                    if crc and self.crc_enabled \
                            and self.crc_fn(payload) != crc:
                        raise BadFrame(
                            f"crc mismatch on frame type {type_id}")
                if flags & FLAG_COMPRESSED:
                    payload = zlib.decompress(payload)
        except BaseException:
            self.throttle.put(cost)
            raise
        perf = self.messenger.perf
        rx_dt = time.monotonic() - t_io
        perf.tinc("rx_io", rx_dt)
        perf.hinc("rx_io_us", rx_dt * 1e6)
        perf.inc("rx_bytes", _HDR.size + length)
        return (type_id, version, seq, payload, cost, blob,
                bool(flags & FLAG_FIXED), blob_verified)

    async def adopt_transport(self, reader, writer) -> None:
        """Adopt a fresh transport into this session and replay unacked
        frames (both directions of the reference's session reconnect:
        the initiator replays requests, the acceptor replays replies)."""
        old_writer = self.writer
        async with self._send_lock:
            self.reader = reader
            self.writer = writer
            self.closed = False
            self.transport_gen += 1
            # pre-verified frames from the DEAD transport: never
            # dispatched, never acked — the peer replays them on this
            # transport, and the in_seq dedupe floor keeps it exactly-once
            self._rx_stash.clear()
            self._rx_error = None
            try:
                old_writer.close()
            except Exception:
                pass
            replayed = 0
            with self.messenger.perf.time_avg("tx_io"):
                for _, data in list(self.unacked):
                    if isinstance(data, list):
                        _writelines(self.writer, data)
                        replayed += sum(len(p) for p in data)
                    else:
                        self.writer.write(data)
                        replayed += len(data)
                await self.writer.drain()
            if replayed:
                self.messenger.perf.inc("tx_bytes", replayed)

    async def close(self, gen: Optional[int] = None) -> None:
        """Close the current transport.  With gen, only close if the
        transport hasn't been replaced since the caller observed it."""
        if gen is not None and gen != self.transport_gen:
            return
        if not self.closed:
            self.closed = True
            # senders parked on the pending flush window see the error
            # now; their frames replay from the unacked queue (lossless)
            self._fail_pending(ConnectionResetError("connection closed"))
            self._pin_replay_queue()
            self.writer.close()
            try:
                # bounded: wait_closed can block if the peer never reads
                await asyncio.wait_for(self.writer.wait_closed(), timeout=0.5)
            except Exception:
                pass


# -- multi-lane peer sessions ------------------------------------------------


class LaneGroup:
    """A striped peer session: N lane Connections plus the cross-lane
    sequencing/reassembly seam (module docstring "Multi-lane peer
    striping").  Duck-types the Connection surface daemons touch
    (send / close / peer / peer_name / auth metadata), so handlers reply
    through the group and replies stripe too.

    TX: LANE_STRIPE messages get the next connection-global ``gseq`` and
    round-robin across lanes 1..N-1 (lane 0 is control-only); blobs >=
    ``frag_min`` split into MLaneSegment fragments sent over ALL data
    lanes concurrently.  RX: every lane's serve loop pushes decoded
    messages here; gseq order is restored (holes park, a dead lane's
    replay fills them), fragments reassemble, and a single pump task
    dispatches in order.

    Throttle note: frames PARKED for a gap or a partial reassembly
    release their dispatch-throttle cost at park time (a dead lane may
    hold a gap open for seconds; holding budget hostage would stall the
    messenger's other sessions) — parked memory is instead bounded by
    PARK_CAP, past which the reorderer force-drains in gseq order."""

    PARK_CAP = 8192  # parked frames before the reorderer force-drains
    # reassembly memory caps: fragment geometry is PEER-CLAIMED and read
    # before the frame crc can reject it, so the allocation it drives
    # must be bounded independently of the dispatch throttle (which only
    # accounts wire bytes).  Overflowing assemblies are refused and
    # counted (lane_frag_overflow); upper-layer resend recovers.
    FRAG_MAX_ASSEMBLIES = 64
    FRAG_MAX_BYTES = 256 << 20

    def __init__(self, messenger: "Messenger", addr: Tuple[str, int],
                 group_id: str, n_lanes: int, outbound: bool,
                 policy: Policy):
        self.messenger = messenger
        self.peer = tuple(addr)
        self.group_id = group_id
        self.n_lanes = max(2, int(n_lanes))
        self.outbound = outbound
        self.policy = policy
        self.lanes: List[Optional[Connection]] = [None] * self.n_lanes
        self.closed = False
        self.frag_min = int(_cget(messenger.conf, "ms_lane_stripe_min",
                                  1 << 20) or 0)
        self._tx_gseq = 0
        self._rr = 0
        # rx reorder + reassembly state
        self._rx_next = 1
        self._parked: Dict[int, Tuple[Any, Any]] = {}  # gseq -> (conn, msg)
        # gseq -> [seen, chunks, hdr, all_verified, buf, confirmed_ranges]
        self._frags: Dict[int, list] = {}
        self._frag_bytes = 0  # aggregate assembly-buffer bytes live
        self._fifo: Deque = collections.deque()  # (conn, msg, cost)
        self._pump_task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None
        self._reviving: set = set()

    # -- Connection surface ---------------------------------------------------

    def _lane0(self) -> Optional[Connection]:
        return self.lanes[0]

    @property
    def peer_name(self) -> str:
        c = self._lane0()
        return c.peer_name if c is not None else ""

    @property
    def auth_kind(self) -> str:
        c = self._lane0()
        return c.auth_kind if c is not None else "none"

    @property
    def auth_entity_type(self) -> str:
        c = self._lane0()
        return c.auth_entity_type if c is not None else ""

    def _lane(self, idx: int) -> Connection:
        conn = self.lanes[idx]
        if conn is None:
            conn = self.lanes[0]
        if conn is None:
            raise ConnectionResetError("lane group has no lanes")
        return conn

    @property
    def n_data_lanes(self) -> int:
        return self.n_lanes - 1

    async def send(self, msg: Any) -> None:
        if self.closed:
            raise ConnectionResetError("lane group closed")
        cls = type(msg)
        if not getattr(cls, "LANE_STRIPE", False):
            # control plane: lane 0, no gseq — never queued behind data
            await self._lane(0).send(msg)
            return
        self._tx_gseq += 1
        gseq = self._tx_gseq
        msg.gseq = gseq
        blob_attr = getattr(cls, "BLOB_ATTR", None)
        blob = msg.__dict__.get(blob_attr) if blob_attr else None
        blob_len = len(blob) if blob is not None else 0
        if (self.frag_min and blob_len >= self.frag_min
                and self.n_data_lanes > 1):
            if await self._send_fragmented(msg, gseq):
                return
        idx = 1 + (gseq - 1) % self.n_data_lanes
        self._note_lane_tx(idx, blob_len)
        await self._lane(idx).send(msg)

    def _note_lane_tx(self, idx: int, nbytes: int) -> None:
        p = self.messenger.perf
        p.ensure(f"tx_lane{idx}_msgs", desc=f"messages striped to lane {idx}")
        p.ensure(f"tx_lane{idx}_bytes", desc=f"blob bytes striped to lane {idx}")
        p.inc(f"tx_lane{idx}_msgs")
        p.inc(f"tx_lane{idx}_bytes", nbytes)

    async def _send_fragmented(self, msg: Any, gseq: int) -> bool:
        """Split a large blob across all data lanes as MLaneSegment
        frames sent concurrently; returns False when the message isn't
        actually blob-framed (caller falls back to whole-message)."""
        header, blob, fixed = encode_payload_parts(msg)
        if blob is None:
            return False
        if isinstance(blob, BufferList):
            segs, total = blob.segments, blob.nbytes
        else:
            segs, total = _norm_segments([blob])
        n = self.n_data_lanes
        base, extra = divmod(total, n)
        # walk the segment list once, carving n contiguous byte ranges
        sends = []
        seg_i, seg_off = 0, 0
        off = 0
        for i in range(n):
            want = base + (1 if i < extra else 0)
            pieces = []
            while want and seg_i < len(segs):
                seg = segs[seg_i]
                take = min(want, seg.nbytes - seg_off)
                pieces.append(seg[seg_off:seg_off + take])
                want -= take
                seg_off += take
                if seg_off >= seg.nbytes:
                    seg_i += 1
                    seg_off = 0
            chunk: Any = pieces[0] if len(pieces) == 1 else BufferList(pieces)
            frag = MLaneSegment(gseq=gseq, idx=i, nfrags=n, total=total,
                                off=off,
                                type_id=type(msg).TYPE_ID,
                                version=type(msg).VERSION,
                                fixed=bool(fixed),
                                header=header if i == 0 else b"",
                                chunk=chunk)
            lane_idx = 1 + (gseq + i - 1) % n
            self._note_lane_tx(lane_idx, len(chunk))
            sends.append(self._lane(lane_idx).send(frag))
            off += len(chunk)
        self.messenger.perf.inc("lane_frag_tx", n)
        results = await asyncio.gather(*sends, return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r
        return True

    # -- rx: reassembly + ordered dispatch ------------------------------------

    def rx_push(self, conn: Connection, msg: Any, cost: int) -> None:
        """Called by each lane's serve loop with a decoded message.
        Restores gseq order (parking holes), reassembles fragments, and
        feeds the ready run to the single dispatch pump.  Cost transfers
        with READY messages (released after dispatch); parked frames
        release theirs immediately (see class docstring)."""
        ready = self._ingest(conn, msg)
        first = True
        for c, m in ready:
            # THIS arrival's cost rides the first ready entry (parked
            # entries released theirs at park time; a reassembled
            # message inherits its completing fragment's) — pump
            # returns it after dispatch
            self._fifo.append((c, m, cost if first else 0))
            first = False
        if not ready and cost:
            self.messenger.dispatch_throttle.put(cost)
        if ready:
            self._kick_pump()

    def _ingest(self, conn: Connection, msg: Any):
        """Returns the in-order run of (conn, msg) this
        arrival unlocks ([] when it parked)."""
        if type(msg).__name__ == "MLaneSegment":
            msg = self._ingest_fragment(conn, msg)
            if msg is None:
                return []
        g = getattr(msg, "gseq", 0) or 0
        if g == 0 or g < self._rx_next:
            # control-plane (no gseq) dispatches immediately; g <
            # expected is a cross-lane duplicate (dup injection, replay
            # overlap) the application layer's reqid dedupe absorbs
            return [(conn, msg)]
        if g > self._rx_next:
            self._parked[g] = (conn, msg)
            self.messenger.perf.inc("lane_rx_parked")
            if len(self._parked) > self.PARK_CAP:
                # liveness backstop: force-drain in gseq order rather
                # than grow without bound (a hole this old means the
                # owning lane session is gone for good)
                self.messenger.dout(
                    1, f"lane group {self.group_id[:8]}: PARK_CAP "
                       f"({self.PARK_CAP}) exceeded at gseq hole "
                       f"{self._rx_next}; force-draining reorder buffer")
                keys = sorted(self._parked)
                out = [self._parked.pop(k) for k in keys]
                self._rx_next = keys[-1] + 1
                return out
            return []
        out = [(conn, msg)]
        self._rx_next += 1
        while self._rx_next in self._parked:
            out.append(self._parked.pop(self._rx_next))
            self._rx_next += 1
        return out

    def frag_view(self, seg: Any, blob_len: int):
        """Reassembly destination for one inbound MLaneSegment: the
        [off, off+blob_len) slice of gseq's assembly buffer, so the
        frame reader lands the bytes in place (zero-copy reassembly).
        None when the segment's geometry doesn't fit (corrupt/hostile
        frame: the caller falls back to a private buffer and the normal
        bounds-checked ingest)."""
        if (seg.total <= 0 or seg.total > (1 << 31) or seg.off < 0
                or seg.off + blob_len > seg.total
                or not (0 <= seg.idx < seg.nfrags <= 4096)):
            # implausible geometry (corrupt/hostile frame): refuse the
            # assembly allocation before the crc check can reject it
            return None
        st = self._frag_state(seg.gseq, seg.nfrags, seg.total)
        if st is None:
            return None
        if self._range_conflict(st, seg.idx, seg.off, blob_len):
            # overlaps a CONFIRMED fragment (or re-claims a consumed
            # idx): land in a private buffer instead — the crc check
            # will kill the corrupt frame without stomping verified
            # bytes, and a mere duplicate is dropped by _ingest
            return None
        return memoryview(st[4]).cast("B")[seg.off:seg.off + blob_len]

    def _frag_state(self, gseq: int, nfrags: int, total: int):
        """The reassembly entry for gseq, created if absent
        and the caps allow; None when refused (stale gseq, geometry
        mismatch, or the FRAG_MAX_* memory bounds)."""
        st = self._frags.get(gseq)
        if st is not None:
            return st if len(st[4]) == total else None
        if 0 < gseq < self._rx_next:
            # gseq already dispatched: a stale duplicate must not
            # re-open a completed (deleted) assembly
            return None
        if (len(self._frags) >= self.FRAG_MAX_ASSEMBLIES
                or self._frag_bytes + total > self.FRAG_MAX_BYTES):
            self.messenger.perf.inc("lane_frag_overflow")
            return None
        st = self._frags[gseq] = [0, [None] * nfrags, b"", True,
                                  np.empty(total, dtype=np.uint8), {}]
        self._frag_bytes += total
        return st

    @staticmethod
    def _range_conflict(st, idx: int, off: int, length: int) -> bool:
        """True when [off, off+length) overlaps a CONFIRMED fragment's
        bytes (or idx itself is already confirmed) — the guard that
        keeps a corrupt-geometry frame, whose blob lands BEFORE its crc
        is checked, from stomping verified regions of the assembly."""
        ranges = st[5]
        if idx in ranges:
            return True
        end = off + length
        for o, ln in ranges.values():
            if off < o + ln and o < end:
                return True
        return False

    def _frag_drop(self, gseq: int) -> None:
        st = self._frags.pop(gseq, None)
        if st is not None:
            self._frag_bytes -= len(st[4])

    def _ingest_fragment(self, conn: Connection, frag: Any):
        """Collect one MLaneSegment; returns the reassembled original
        message when complete, else None."""
        if frag.total <= 0 or frag.total > (1 << 31) \
                or not (0 < frag.nfrags <= 4096):
            return None
        st = self._frag_state(frag.gseq, frag.nfrags, frag.total)
        if st is None:
            return None
        seen, chunks, _hdr, ok, buf, ranges = st
        if 0 <= frag.idx < len(chunks) and chunks[frag.idx] is None:
            chunk = frag.chunk
            in_place = (isinstance(chunk, memoryview)
                        and chunk.obj is buf)
            nbytes = len(chunk)
            if not in_place:
                if frag.off < 0 or frag.off + nbytes > len(buf) \
                        or self._range_conflict(st, frag.idx, frag.off,
                                                nbytes):
                    # corrupt geometry: drop the fragment WITHOUT
                    # consuming its slot — a valid retransmission of
                    # this index must still be able to land
                    return None
                mv = chunk if isinstance(chunk, memoryview) \
                    else memoryview(as_bytes(chunk)
                                    if isinstance(chunk, BufferList)
                                    else chunk)
                if mv.ndim != 1 or mv.itemsize != 1:
                    mv = mv.cast("B")
                # single-fragment landing: the bounds/overlap guard above
                # already enforced everything the C-side guard would, and
                # one slice-assign is cheaper than a ctypes segment-list
                # round-trip (batched fragments ride the native drain)
                view = memoryview(buf).cast("B")
                view[frag.off:frag.off + mv.nbytes] = mv
            chunks[frag.idx] = True
            ranges[frag.idx] = (frag.off, nbytes)
            st[0] = seen = seen + 1
            if frag.header:
                st[2] = frag.header
            if not getattr(frag, "_wire_verified", False):
                st[3] = False
        if seen < len(chunks):
            return None
        self._frag_drop(frag.gseq)
        self.messenger.perf.inc("lane_frag_rx", len(chunks))
        msg = decode_message(frag.type_id, frag.version,
                             bytes(st[2]) if isinstance(st[2], (bytearray,
                                                                memoryview))
                             else st[2],
                             memoryview(st[4]).cast("B"), bool(frag.fixed))
        if st[3]:
            msg._wire_verified = True
        msg.gseq = frag.gseq
        return msg

    def _kick_pump(self) -> None:
        if self._wake is None:
            self._wake = asyncio.Event()
        self._wake.set()
        if self._pump_task is None or self._pump_task.done():
            m = self.messenger
            self._pump_task = asyncio.get_running_loop().create_task(
                self._pump())
            m._tasks.add(self._pump_task)
            self._pump_task.add_done_callback(m._tasks.discard)

    async def _pump(self) -> None:
        """The group's single ordered dispatcher."""
        m = self.messenger
        while not self.closed and not m._shutdown:
            await self._wake.wait()
            self._wake.clear()
            while self._fifo and not self.closed and not m._shutdown:
                await self._pump_once(m)

    async def _pump_once(self, m: "Messenger") -> None:
        batch: list = []
        costs = 0
        while self._fifo and len(batch) < m.RX_BATCH_MSGS:
            conn, msg, cost = self._fifo.popleft()
            batch.append((conn, msg))
            costs += cost
        if not batch:
            return
        try:
            if m.group_dispatcher is not None \
                    and (len(batch) > 1 or m.dispatcher is None):
                await m.group_dispatcher(self, [msg for _, msg in batch])
            elif m.dispatcher is not None:
                for _, msg in batch:
                    try:
                        await m.dispatcher(self, msg)
                    except (asyncio.CancelledError, GeneratorExit):
                        raise
                    except Exception:
                        traceback.print_exc()
        except (asyncio.CancelledError, GeneratorExit):
            raise
        except Exception:
            traceback.print_exc()
        finally:
            if costs:
                m.dispatch_throttle.put(costs)

    # -- lifecycle ------------------------------------------------------------

    def bind_lane(self, conn: Connection, lane: int) -> None:
        if 0 <= lane < self.n_lanes:
            self.lanes[lane] = conn
        conn.lane_group = self
        conn.lane_idx = lane

    async def close(self) -> None:
        self.closed = True
        for conn in self.lanes:
            if conn is not None:
                await conn.close()
        if self._pump_task is not None:
            self._pump_task.cancel()
        # undispatched fifo entries still hold dispatch-throttle budget
        # (pump releases after dispatch): return it now or the receive
        # path leaks it permanently under group churn
        owed = sum(cost for _c, _m, cost in self._fifo)
        self._fifo.clear()
        self._parked.clear()
        self._frags.clear()
        self._frag_bytes = 0
        if owed:
            self.messenger.dispatch_throttle.put(owed)

    def dump(self) -> Dict[str, Any]:
        lanes = []
        for i, c in enumerate(self.lanes):
            if c is None:
                lanes.append({"lane": i, "state": "absent"})
                continue
            lanes.append({
                "lane": i, "state": "closed" if c.closed else "open",
                "control": i == 0,
                "outbox_frames": c._outbox_frames,
                "outbox_bytes": c._outbox_bytes,
                "unacked": len(c.unacked),
                "out_seq": c.out_seq, "in_seq": c.in_seq})
        return {"peer": list(self.peer), "group": self.group_id,
                "outbound": self.outbound, "n_lanes": self.n_lanes,
                "tx_gseq": self._tx_gseq, "rx_next": self._rx_next,
                "rx_parked": len(self._parked), "rx_fifo": len(self._fifo),
                "reassembling": len(self._frags), "lanes": lanes}


# -- messenger ---------------------------------------------------------------


class Messenger:
    """One per daemon.  dispatcher(conn, msg) is awaited per message
    (fast-dispatch style); receive-side bytes ride a dispatch throttle."""

    def __init__(self, name: str, conf: Optional[Any] = None,
                 entity_type: str = "client"):
        self.name = name
        self.conf = conf if conf is not None else {}
        self.entity_type = entity_type
        # whose time a dispatched message's handler is (tracing.mark):
        # the op path's, the client's, or the cluster's housekeeping
        self._daemon_layer = {"osd": "osd", "client": "client"}.get(
            entity_type, "background")
        # resolve the frame checksum NOW (may g++-build the native
        # library, seconds): daemon construction, never the hot path
        checksum_kind()
        # native wirepath arm for this messenger (utils/wirepath.py):
        # the bridge module when the native hot loop resolved AND the
        # config allows it, else None (pure-python arm).  Resolved here
        # for the same reason as the checksum — never on the hot path.
        self.wirepath = (_wirepath.impl()
                         if bool(_cget(self.conf, "ms_wirepath_native",
                                       True)) else None)
        # the `wire` counter set (framing vs socket-io split; schema in
        # _build_wire_perf) — owning daemons add it to their collection
        self.perf = _build_wire_perf()
        self.perf.set("wirepath_kind", 1 if self.wirepath is not None
                      else 0)
        # gauge survives `perf reset` (bench/tests zero the window's
        # counters; the ARM doesn't change) — the resync hook restores
        # it, the service-plane gauge discipline
        self.perf.resync = lambda: self.perf.set(
            "wirepath_kind", 1 if self.wirepath is not None else 0)
        self._slots: Dict[int, _TypeSlot] = {}  # by wire type id
        # per-daemon log (debug_ms levels): daemons attach their
        # Context's Log; raw messengers stay silent.  Per-frame douts are
        # call-site guarded with log.wants("ms", 20) so a disabled level
        # costs one cached compare on the hot path — turning up debug_ms
        # at runtime (asok / `ceph tell ... config set`) is the
        # diagnostic workflow.
        self.log = None
        self.dispatcher: Optional[Callable] = None
        # optional group-dispatch hook: group_dispatcher(conn, msgs) gets
        # a whole rx batch (frames that were already buffered) so the
        # daemon can hand stripe groups to the EC tier in one submit and
        # coalesce replies; falls back to per-message dispatcher when None
        self.group_dispatcher: Optional[Callable] = None
        self.server: Optional[asyncio.AbstractServer] = None
        self.addr: Optional[Tuple[str, int]] = None
        self._conns: Dict[Tuple[str, int], Connection] = {}
        self._conn_locks: Dict[Tuple[str, int], asyncio.Lock] = {}
        self._tasks: set = set()
        # reference defaults: clients are lossy, daemon peers lossless
        self.policies: Dict[str, Policy] = {
            "client": Policy.lossy_client(),
            "osd": Policy.lossless_peer(),
            "mon": Policy.lossless_peer(),
            "mgr": Policy.lossless_peer(),
        }
        self.dispatch_throttle = Throttle(
            f"{name}-dispatch", _cget(self.conf, "ms_dispatch_throttle_bytes", 100 << 20)
        )
        self._shutdown = False
        # cephx-lite state: this entity's service ticket + session key
        # (initiator side) and the rotating-secret keyring used to
        # validate presented tickets (acceptor side, daemons only)
        self.ticket: Optional[bytes] = None
        self.session_key: Optional[bytes] = None
        self.keyring = None  # Optional[TicketKeyring]
        # async callable: re-fetch rotating secrets on a validation miss
        # (a ticket sealed under a JUST-rotated secret must not be
        # refused until the periodic refresh happens to run)
        self.keyring_refresh: Optional[Callable] = None
        # session id -> session Connection, LRU-capped (peers come and go)
        self._sessions: "collections.OrderedDict[str, Connection]" = (
            collections.OrderedDict()
        )
        # colocated-daemon fast dispatch (LocalConnection): opt-in, and
        # only meaningful when BOTH endpoints run with it on
        self._local_fastpath = bool(
            _cget(self.conf, "ms_local_fastpath", False))
        self._local_conns: Dict[Tuple[str, int], LocalConnection] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self.lanes_per_peer = max(1, int(
            _cget(self.conf, "ms_lanes_per_peer", 1) or 1))
        # acceptor-side lane groups, keyed by group id (LRU-capped with
        # the session table)
        self._lane_groups: "collections.OrderedDict[str, LaneGroup]" = (
            collections.OrderedDict())
        # the sweep of owed acks, made when a connection first owes
        self._ack_sweep: Optional[_AckSweep] = None

    def policy_for(self, peer_type: str) -> Policy:
        return self.policies.get(peer_type, Policy.lossy_client())

    def dout(self, level: int, message: str) -> None:
        """debug_ms-leveled dout into the owning daemon's log (no-op on
        raw messengers).  Hot paths guard with ``self.log.wants`` first."""
        log = self.log
        if log is not None:
            log.dout("ms", level, message)

    def _offloop_here(self) -> Optional[_Offloop]:
        """The CURRENT loop's end of the process's sender thread, this
        messenger among its users until it shuts down; None on the python
        arm."""
        if self.wirepath is None or self._shutdown:
            return None
        return _offloop_of(asyncio.get_running_loop(), self.wirepath, self)

    # -- wire accounting -----------------------------------------------------

    def conn_backlog(self, addr: Tuple[str, int]) -> Tuple[int, int]:
        """(frames sent to `addr` and not acked yet, bytes in the outbox
        awaiting a flush window) of the session with that peer, its lanes
        together: what a daemon notes down when the peer did not answer."""
        conn = self._conns.get(tuple(addr))
        lanes = getattr(conn, "lanes", None) or [conn]
        # (an absent session holds neither)
        return (sum(len(getattr(c, "unacked", ())) for c in lanes),
                sum(getattr(c, "_outbox_bytes", 0) for c in lanes))

    def _type_slot(self, type_id: int) -> _TypeSlot:
        slot = self._slots.get(type_id)
        if slot is None:
            cls = _MSG_TYPES.get(type_id)
            slot = self._slots[type_id] = _TypeSlot(
                cls.__name__ if cls is not None else f"type{type_id}")
        return slot

    def _note_tx(self, slot: _TypeSlot, nbytes: int, framing_s: float) -> None:
        # tx_bytes is NOT counted here: _write_raw owns it, so acks and
        # session replays land in the socket totals too
        p = self.perf
        p.inc("tx_msgs")
        p.tinc("tx_framing", framing_s)
        if slot.tx is None:
            slot.tx, slot.tx_bytes = f"tx_{slot.name}", f"tx_bytes_{slot.name}"
            p.ensure(slot.tx, desc=f"{slot.name} messages sent")
            p.ensure(slot.tx_bytes, desc=f"{slot.name} bytes sent")
        p.inc(slot.tx)
        p.inc(slot.tx_bytes, nbytes)

    def _note_rx(self, slot: _TypeSlot, nbytes: int, framing_s: float) -> None:
        p = self.perf
        p.inc("rx_msgs")
        p.tinc("rx_framing", framing_s)
        if slot.rx is None:
            slot.rx, slot.rx_bytes = f"rx_{slot.name}", f"rx_bytes_{slot.name}"
            p.ensure(slot.rx, desc=f"{slot.name} messages dispatched")
            p.ensure(slot.rx_bytes, desc=f"{slot.name} bytes received")
        p.inc(slot.rx)
        p.inc(slot.rx_bytes, nbytes)

    # -- handshake -----------------------------------------------------------

    def _auth_tag(self, nonce: bytes, key: Optional[bytes] = None,
                  transcript: bytes = b"") -> str:
        """HMAC proof over a handshake nonce + negotiated-mode transcript:
        with a ticket session key when one is in play (cephx role), else
        the cluster bootstrap secret.  Binding the transcript (the secure
        flags both sides sent) into the tag makes mode-stripping by an
        active MITM detectable — the reference binds the negotiated mode
        into msgr2's signed handshake payload the same way."""
        if key is not None:
            return hmac.new(key, nonce + transcript, hashlib.sha256).hexdigest()
        secret = str(_cget(self.conf, "ms_auth_secret", "") or "")
        if not secret:
            return ""
        return hmac.new(secret.encode(), nonce + transcript,
                        hashlib.sha256).hexdigest()

    @staticmethod
    def _mode_transcript(initiator_secure: bool, acceptor_secure: bool) -> bytes:
        return f"|mode:i{int(bool(initiator_secure))}a{int(bool(acceptor_secure))}".encode()

    def _secure_key(self, session_key: Optional[bytes],
                    nonce_a: bytes, nonce_b: bytes) -> Optional[bytes]:
        """Key material for AES-GCM on-wire mode: the ticket session key,
        else a key derived from the cluster secret and both nonces."""
        if session_key is not None:
            return session_key
        secret = str(_cget(self.conf, "ms_auth_secret", "") or "")
        if not secret:
            return None
        return hmac.new(secret.encode(), b"onwire" + nonce_a + nonce_b,
                        hashlib.sha256).digest()

    def _wrap_secure(self, reader, writer, key: bytes):
        from ceph_tpu.rados.auth import SecureStream

        s = SecureStream(reader, writer, key)
        return s, s

    async def _handshake_out(self, reader, writer, lossless: bool,
                             session_id: str):
        """Returns (peer_name, resumed, peer_ckind, lanes_ok, reader,
        writer) — the pair is AES-GCM wrapped when secure mode was
        negotiated.  ``lanes_ok`` says the acceptor understands the
        multi-lane plane (old peers fall back to one lane)."""
        secure_want = bool(_cget(self.conf, "ms_secure_mode", False))
        writer.write(BANNER)
        nonce = random.randbytes(16)
        hello = {"name": self.name, "type": self.entity_type,
                 "nonce": nonce.hex(), "auth": "",
                 "session": session_id, "lossless": lossless,
                 "secure": secure_want, "ckind": checksum_kind(),
                 "lanes_ok": True}
        if self.ticket is not None:
            hello["ticket"] = self.ticket.hex()
        writer.write(json.dumps(hello).encode() + b"\n")
        await writer.drain()
        banner = await reader.readexactly(len(BANNER))
        if banner != BANNER:
            raise BadFrame("bad banner from peer")
        peer_hello = json.loads(await reader.readline())
        key = self.session_key if self.ticket is not None else None
        # both secure flags ride the HMAC material: a stripped flag makes
        # the tags disagree instead of silently downgrading to plaintext
        transcript = self._mode_transcript(secure_want,
                                           peer_hello.get("secure", False))
        # acceptor proves knowledge of the secret (or of OUR ticket's
        # session key, which only rotating-secret holders can open) by
        # tagging OUR nonce
        expect = self._auth_tag(nonce, key, transcript)
        if expect and not hmac.compare_digest(peer_hello.get("auth", ""), expect):
            raise PermissionError("peer failed auth (bad cluster secret)")
        # then we prove ourselves by tagging THEIR nonce
        try:
            their_nonce = bytes.fromhex(peer_hello.get("nonce", ""))
        except ValueError:
            raise BadFrame("garbled nonce in peer hello") from None
        tag = self._auth_tag(their_nonce, key, transcript)
        writer.write(json.dumps({"auth": tag}).encode() + b"\n")
        await writer.drain()
        fin = json.loads(await reader.readline())
        if not fin.get("ok", False):
            raise PermissionError("peer rejected our auth")
        if secure_want:
            # ms_secure_mode is a REQUIREMENT, not a preference: ending up
            # on plaintext (peer refused, or no key material to derive a
            # session key from) is a failed connection, never a downgrade
            skey = (self._secure_key(key, nonce, their_nonce)
                    if peer_hello.get("secure") else None)
            if skey is None:
                raise PermissionError(
                    "ms_secure_mode set but connection would be plaintext")
            reader, writer = self._wrap_secure(reader, writer, skey)
        return (peer_hello.get("name", ""), bool(peer_hello.get("resumed")),
                peer_hello.get("ckind", "zlib"),
                bool(peer_hello.get("lanes_ok")), reader, writer)

    async def _handshake_in(self, reader, writer):
        """Returns (peer_name, peer_type, session, lossless, auth_kind,
        auth_entity_type, peer_ckind, reader, writer) — the pair is
        AES-GCM wrapped when secure mode was negotiated.  ``auth_kind`` records HOW the
        peer proved itself ("ticket", "secret", or "none"): authorization
        decisions (e.g. who may fetch the rotating service secrets) key on
        it, not on the peer's self-declared type."""
        secure_want = bool(_cget(self.conf, "ms_secure_mode", False))
        banner = await reader.readexactly(len(BANNER))
        if banner != BANNER:
            raise BadFrame("bad banner from peer")
        peer_hello = json.loads(await reader.readline())
        writer.write(BANNER)
        nonce = random.randbytes(16)
        their_nonce = bytes.fromhex(peer_hello.get("nonce", ""))
        key: Optional[bytes] = None
        auth_kind = "none"
        auth_entity_type = ""
        ticket_hex = peer_hello.get("ticket", "")
        if ticket_hex and self.keyring is not None:
            tkt = self.keyring.validate(bytes.fromhex(ticket_hex))
            if tkt is None and self.keyring_refresh is not None:
                # maybe sealed under a rotation we haven't fetched yet
                try:
                    await asyncio.wait_for(self.keyring_refresh(), timeout=2.0)
                except Exception:
                    pass
                tkt = self.keyring.validate(bytes.fromhex(ticket_hex))
            if tkt is None:
                # a PRESENTED ticket must verify: silently falling back to
                # the shared-secret path would let an expired/forged
                # ticket ride a daemon's bootstrap credentials
                writer.write(json.dumps({"ok": False}).encode() + b"\n")
                await writer.drain()
                raise PermissionError(
                    f"invalid ticket from {peer_hello.get('name')}")
            key = tkt["session_key"]
            auth_kind = "ticket"
            auth_entity_type = tkt.get("type", "")
        # tell the initiator whether we still hold its session: if not, it
        # must reset its reply-dedupe floor (our out_seq restarts at 1)
        resumed = peer_hello.get("session", "") in self._sessions
        transcript = self._mode_transcript(peer_hello.get("secure", False),
                                           secure_want)
        hello = {"name": self.name, "type": self.entity_type,
                 "nonce": nonce.hex(),
                 "auth": self._auth_tag(their_nonce, key, transcript),
                 "resumed": resumed, "secure": secure_want,
                 "ckind": checksum_kind(), "lanes_ok": True}
        writer.write(json.dumps(hello).encode() + b"\n")
        await writer.drain()
        proof = json.loads(await reader.readline())
        expect = self._auth_tag(nonce, key, transcript)
        ok = not expect or hmac.compare_digest(proof.get("auth", ""), expect)
        writer.write(json.dumps({"ok": ok}).encode() + b"\n")
        await writer.drain()
        if not ok:
            raise PermissionError(f"auth failed for peer {peer_hello.get('name')}")
        if expect and auth_kind == "none":
            auth_kind = "secret"  # peer proved the cluster bootstrap secret
        if secure_want:
            # required, not best-effort (see _handshake_out)
            skey = (self._secure_key(key, their_nonce, nonce)
                    if peer_hello.get("secure") else None)
            if skey is None:
                raise PermissionError(
                    "ms_secure_mode set but connection would be plaintext")
            reader, writer = self._wrap_secure(reader, writer, skey)
        return (peer_hello.get("name", ""), peer_hello.get("type", "client"),
                peer_hello.get("session", ""), bool(peer_hello.get("lossless")),
                auth_kind, auth_entity_type,
                peer_hello.get("ckind", "zlib"), reader, writer)

    # -- lifecycle -----------------------------------------------------------

    async def disconnect(self, addr) -> None:
        """Drop the live outbound connection to ``addr`` (if any): the
        next send re-dials and re-runs the handshake — used when the
        credentials the old handshake was built on changed (e.g. a ticket
        was dropped to force bootstrap-secret auth)."""
        key = tuple(addr)
        conn = self._conns.pop(key, None)
        if conn is not None:
            await conn.close()

    async def bind(self, host: str = "127.0.0.1", port: int = 0) -> Tuple[str, int]:
        self.server = await asyncio.start_server(self._accept, host, port)
        self.addr = self.server.sockets[0].getsockname()[:2]
        if self._local_fastpath:
            self._loop = asyncio.get_running_loop()
            _LOCAL_REGISTRY[tuple(self.addr)] = self
        self.dout(1, f"bind {self.addr[0]}:{self.addr[1]} "
                     f"(lanes/peer {self.lanes_per_peer})")
        return self.addr

    @staticmethod
    def _negotiated_crc(peer_ckind: str):
        """Per-connection frame checksum: the fast shared resolver when
        both ends resolved the same KIND, zlib (which every build has)
        when they differ — a per-host native-build failure must degrade,
        never loop every frame through BadFrame."""
        return checksum if peer_ckind == checksum_kind() else zlib.crc32

    async def _accept(self, reader, writer) -> None:
        peer = writer.get_extra_info("peername")[:2]
        task = asyncio.current_task()
        self._tasks.add(task)
        try:
            try:
                (peer_name, peer_type, cookie, lossless, auth_kind,
                 auth_entity_type, peer_ckind,
                 reader, writer) = await self._handshake_in(reader, writer)
            except (PermissionError, BadFrame, ConnectionError, json.JSONDecodeError,
                    asyncio.IncompleteReadError, ValueError):
                writer.close()
                return
            evicted_conns = []
            if lossless and cookie:
                conn = self._sessions.get(cookie)
                if conn is not None:
                    self._sessions.move_to_end(cookie)
                else:
                    conn = Connection(self, reader, writer, peer,
                                      Policy.lossless_peer(), peer_name)
                    self._sessions[cookie] = conn
                    while len(self._sessions) > MAX_SESSIONS:
                        _, ev = self._sessions.popitem(last=False)
                        evicted_conns.append(ev)
                for ev in evicted_conns:
                    await ev.close()
                if conn.reader is not reader:
                    # session reconnect: adopt the new socket, replay our
                    # un-acked frames (e.g. replies lost in the drop)
                    await conn.adopt_transport(reader, writer)
            else:
                conn = Connection(self, reader, writer, peer,
                                  Policy.lossy_client(), peer_name)
            # how the peer proved itself, for authorization decisions
            # (refreshed on every reconnect handshake)
            conn.auth_kind = auth_kind
            conn.auth_entity_type = auth_entity_type
            conn.crc_fn = self._negotiated_crc(peer_ckind)
            await self._serve(conn)
        finally:
            self._tasks.discard(task)

    # rx batch budget: how many already-buffered frames one dispatch
    # round may drain before acking (bounds latency of the first ack and
    # the throttle bytes held across a group dispatch)
    RX_BATCH_MSGS = 32
    RX_BATCH_BYTES = 32 << 20
    # what an owed ack may wait for (module docstring "Acks WAIT FOR
    # COMPANY"; Connection.queue_ack): its age before the sweep sends it
    # alone — of 50 / 200 / 500 ms the one that left fewest ack-only
    # windows and returned most (PERF.md section 6, PR 41; 200 ms is the
    # ceiling of Linux's delayed ack, 500 ms RFC 1122's) — and the payload
    # bytes a sender must hold for it, one put's worth.  Constants, not
    # options: the rule reads the connection's own state and nothing else.
    ACK_DELAY_S = 0.5
    ACK_OWED_BYTES = 4 << 20

    @staticmethod
    def _buffered_frame_len(reader) -> Optional[int]:
        """Payload length of a COMPLETE frame (header + payload) already
        buffered on the reader, else None — the rx batching predicate:
        batch only what needs no further network wait, so a half-arrived
        frame never stalls dispatch of messages already in hand."""
        try:
            if isinstance(reader, FrameReceiver):
                return None  # its complete frames are on the stash
            if isinstance(reader, asyncio.StreamReader):
                buf, off = reader._buffer, 0
            else:  # SecureStream
                buf, off = reader._buf, 0
            avail = len(buf) - off
            if avail < _HDR.size:
                return None
            (length,) = struct.unpack_from("<I", buf, off)
            return length if avail >= _HDR.size + length else None
        except (AttributeError, struct.error):
            return None

    async def _serve(self, conn: Connection) -> None:
        gen = conn.transport_gen
        conn.enable_fast_read()
        try:
            while not conn.closed and conn.transport_gen == gen:
                # drain every frame ALREADY buffered into one batch: one
                # dispatch round, one cumulative ack — under a sub-write
                # burst or an op-reply flood the per-message standalone
                # ack (and its flush) collapses into one frame
                batch: list = []  # (seq, msg)
                costs: list = []
                top_seq = 0
                try:
                    while (len(batch) < self.RX_BATCH_MSGS
                           and sum(costs) < self.RX_BATCH_BYTES):
                        if batch:
                            nxt = conn.buffered_frame_len()
                            if nxt is None or not \
                                    conn.throttle.would_admit(nxt):
                                # nothing fully buffered, or the throttle
                                # would BLOCK — and its budget only
                                # returns after dispatch, which this
                                # batch still owes (self-deadlock)
                                break
                        (type_id, version, seq, payload, cost,
                         blob, fixed, verified) = await conn.read_frame()
                        if conn.transport_gen != gen:
                            conn.throttle.put(cost)
                            return  # transport replaced while suspended
                        if type_id == ACK_TYPE:
                            tracing.charge(ACK_CHARGE)
                            conn.handle_ack(struct.unpack("<Q", payload)[0])
                            conn.throttle.put(cost)
                            continue
                        if seq and seq <= conn.in_seq:
                            # replayed duplicate: re-ack (the original ack
                            # may have been lost) but don't re-dispatch
                            conn.queue_ack(seq, cost)
                            conn.throttle.put(cost)
                            continue
                        try:
                            slot = self._type_slot(type_id)
                            tracing.charge(slot.charge)
                            t_dec = time.monotonic()
                            with tracing.section("messenger", "decode"):
                                msg = decode_message(type_id, version,
                                                     payload, blob, fixed)
                            if verified:
                                # the frame layer checked the blob's crc:
                                # handlers holding an app-level crc of the
                                # same bytes skip their own pass
                                msg._wire_verified = True
                            self._note_rx(slot, _HDR.size + cost,
                                          time.monotonic() - t_dec)
                            log = self.log
                            if log is not None and log.wants("ms", 20):
                                # per-frame rx trace: debug_ms 20 only
                                # (the wants() guard keeps the hot path
                                # at one cached compare)
                                log.dout(
                                    "ms", 20,
                                    f"rx {type(msg).__name__} seq={seq} "
                                    f"{cost}B from {conn.peer[0]}:"
                                    f"{conn.peer[1]}")
                        except Exception as e:
                            # undecodable (type/version skew): poison-
                            # discard so replay can't redeliver it forever
                            print(f"messenger {self.name}: dropping "
                                  f"undecodable frame type={type_id} "
                                  f"v={version}: {e}")
                            if seq:
                                conn.in_seq = seq
                                conn.queue_ack(seq, cost)
                            conn.throttle.put(cost)
                            continue
                        if isinstance(msg, MLaneHello):
                            # lane negotiation frame: messenger-internal
                            # — binds this connection into its lane
                            # group, never reaches the daemon
                            self._bind_lane(conn, msg)
                            if seq:
                                conn.in_seq = max(conn.in_seq, seq)
                                conn.queue_ack(seq, cost)
                            conn.throttle.put(cost)
                            continue
                        if conn.lane_group is not None:
                            # striped session: the LaneGroup restores
                            # gseq order, reassembles fragments, and
                            # dispatches through its single pump — a
                            # debt per frame (queue_ack coalesces)
                            if seq:
                                conn.in_seq = max(conn.in_seq, seq)
                                conn.queue_ack(seq, cost)
                            conn.lane_group.rx_push(conn, msg, cost)
                            continue
                        batch.append((seq, msg))
                        costs.append(cost)
                        if seq:
                            top_seq = max(top_seq, seq)
                    if not batch:
                        continue
                    if len(batch) > 1:
                        self.perf.inc("rx_batches")
                        self.perf.hinc("rx_batch_msgs", len(batch))
                    try:
                        if self.group_dispatcher is not None \
                                and (len(batch) > 1
                                     or self.dispatcher is None):
                            # whole-group handoff: the daemon partitions
                            # the batch itself (stripe groups to the EC
                            # tier in one submit, coalesced replies).
                            # Singletons also route here when no plain
                            # dispatcher is installed — a group-only
                            # daemon must not have isolated frames
                            # consumed-and-acked undispatched.
                            if len(batch) > 1 and tracing.metered():
                                # the daemon walks the batch itself: its
                                # synchronous head is the batch's types',
                                # by their counts
                                whose: Dict[tuple, int] = {}
                                for _, msg in batch:
                                    key = self._type_slot(msg.TYPE_ID).charge
                                    whose[key] = whose.get(key, 0) + 1
                                tracing.charge_many(whose, claim=False)
                            was = tracing.mark(self._daemon_layer)
                            try:
                                await self.group_dispatcher(
                                    conn, [m for _, m in batch])
                            finally:
                                tracing.mark(was)
                        elif self.dispatcher is not None:
                            many = len(batch) > 1  # else decode's holds
                            for _, msg in batch:
                                if many:
                                    tracing.charge(self._type_slot(
                                        msg.TYPE_ID).charge, claim=False)
                                was = tracing.mark(self._daemon_layer)
                                try:
                                    await self.dispatcher(conn, msg)
                                except (asyncio.CancelledError,
                                        GeneratorExit):
                                    raise
                                except Exception:
                                    # a dispatcher bug must not wedge the
                                    # session into infinite redelivery
                                    traceback.print_exc()
                                finally:
                                    tracing.mark(was)
                    except (asyncio.CancelledError, GeneratorExit):
                        raise
                    except Exception:
                        traceback.print_exc()
                    # ack AFTER dispatch: an ack'd frame is a consumed
                    # frame; one cumulative ack covers the whole batch
                    if top_seq:
                        conn.in_seq = max(conn.in_seq, top_seq)
                        conn.queue_ack(top_seq, sum(costs))
                finally:
                    for c in costs:
                        conn.throttle.put(c)
        except (asyncio.IncompleteReadError, ConnectionError, BadFrame):
            pass
        finally:
            await conn.close(gen)
            if conn.closed:
                self.dout(1, f"connection {conn.peer[0]}:{conn.peer[1]} "
                             f"({conn.peer_name or '?'}) closed"
                             + (" [lane]" if conn.lane_group is not None
                                else ""))
            group = conn.lane_group
            if group is not None:
                # lane death: a LOSSLESS lane revives in place (its
                # unacked frames — and only its — replay on the fresh
                # transport while the other lanes keep draining); a
                # lossy lane group dies wholesale, like a lossy conn
                if (conn.outbound and conn.closed and not self._shutdown
                        and not group.closed):
                    coro = (self._revive_lane(group, conn)
                            if conn.policy.replay
                            else self._group_fatal(group))
                    t = asyncio.get_running_loop().create_task(coro)
                    self._tasks.add(t)
                    t.add_done_callback(self._tasks.discard)
            # lossless sessions reconnect from the initiator side so queued
            # frames (ours AND the acceptor's pending replies) replay even
            # when no further application send would trigger it
            elif (conn.outbound and conn.policy.replay and conn.closed
                    and not self._shutdown):
                t = asyncio.get_running_loop().create_task(self._reconnect(conn))
                self._tasks.add(t)
                t.add_done_callback(self._tasks.discard)

    async def _reconnect(self, conn: Connection) -> None:
        delay = 0.02
        for _ in range(10):
            await asyncio.sleep(delay)
            delay = min(delay * 2, 1.0)
            if self._shutdown or self._conns.get(conn.peer) is not conn:
                return
            if not conn.closed:
                return  # something else already revived it
            try:
                await self.connect(conn.peer)
                return
            except (ConnectionError, OSError):
                continue
        # peer looks gone for good: forget the session (the cluster map's
        # failure detection is responsible for marking it down)
        if self._conns.get(conn.peer) is conn:
            self._conns.pop(conn.peer, None)

    # -- lane plane ----------------------------------------------------------

    def _bind_lane(self, conn: Connection, m: "MLaneHello") -> None:
        """Acceptor side of lane negotiation: an MLaneHello (first frame
        on every lane) attaches the carrying connection to its group,
        creating the group on lane 0's hello."""
        group = self._lane_groups.get(m.group)
        if group is None:
            group = LaneGroup(self, conn.peer, m.group,
                              max(2, m.n_lanes), outbound=False,
                              policy=conn.policy)
            self._lane_groups[m.group] = group
            while len(self._lane_groups) > MAX_SESSIONS:
                # full close (lanes + pump + queued throttle costs),
                # not just a flag
                _, old = self._lane_groups.popitem(last=False)
                old.closed = True
                t = asyncio.get_running_loop().create_task(old.close())
                self._tasks.add(t)
                t.add_done_callback(self._tasks.discard)
        else:
            self._lane_groups.move_to_end(m.group)
        self.dout(4, f"lane {m.lane}/{m.n_lanes} bound for group "
                     f"{m.group[:8]} from {conn.peer[0]}:{conn.peer[1]}")
        group.bind_lane(conn, m.lane)

    async def _revive_lane(self, group: LaneGroup, conn: Connection) -> None:
        """Initiator-side failover for one dead lossless lane: redial, adopt
        the fresh transport into the SAME lane session — its pinned
        unacked frames (and only its) replay; the gseq reorder buffer on
        the far side absorbs the refilled hole.  An acceptor that lost
        the lane session (restart/eviction) is group-fatal: per-lane
        dedupe floors can't be trusted across it, so the whole group is
        torn down and the next send dials a fresh one."""
        key = (id(conn),)
        if key in group._reviving:
            return
        group._reviving.add(key)
        try:
            delay = 0.02
            for _ in range(10):
                await asyncio.sleep(delay)
                delay = min(delay * 2, 1.0)
                if self._shutdown or group.closed:
                    return
                if not conn.closed:
                    return  # already revived
                try:
                    reader, writer = await asyncio.open_connection(
                        *group.peer)
                except (ConnectionError, OSError):
                    continue
                try:
                    (peer_name, resumed, peer_ckind, lanes_ok,
                     reader, writer) = await self._handshake_out(
                        reader, writer, True, conn.session_id)
                except TRANSPORT_ERRORS:
                    try:
                        writer.close()
                    except Exception:
                        pass
                    continue
                if not resumed:
                    try:
                        writer.close()
                    except Exception:
                        pass
                    await self._group_fatal(group)
                    return
                conn.crc_fn = self._negotiated_crc(peer_ckind)
                await conn.adopt_transport(reader, writer)
                self.perf.inc("lane_revivals")
                self.dout(1, f"lane revived in place for group "
                             f"{group.group_id[:8]} peer "
                             f"{group.peer[0]}:{group.peer[1]} (unacked "
                             f"frames replayed)")
                t = asyncio.get_running_loop().create_task(
                    self._serve(conn))
                self._tasks.add(t)
                t.add_done_callback(self._tasks.discard)
                return
            await self._group_fatal(group)
        finally:
            group._reviving.discard(key)

    async def _group_fatal(self, group: LaneGroup) -> None:
        """Tear a lane group down wholesale (lossy lane death, peer gone
        for good, acceptor session loss): the next send dials fresh."""
        if group.closed:
            return
        group.closed = True
        if self._conns.get(group.peer) is group:
            self._conns.pop(group.peer, None)
        await group.close()

    # -- outbound ------------------------------------------------------------

    async def connect(self, addr: Tuple[str, int],
                      peer_type: str = "osd") -> Connection:
        """Get (or create) an ordered session with a peer.  A cached dead
        lossless connection is revived in place (same session state, fresh
        transport, unacked replay); dead lossy connections are replaced.
        Serialized per addr so concurrent senders share one session.

        Lane negotiation happens here: a lanes-capable peer gets
        ``ms_lanes_per_peer`` parallel lanes (LaneGroup); anything else
        the single TCP Connection — transparently, the caller just gets
        an object with ``send``."""
        addr = tuple(addr)
        conn = self._conns.get(addr)
        if conn is not None and not conn.closed:
            return conn
        lock = self._conn_locks.setdefault(addr, asyncio.Lock())
        async with lock:
            conn = self._conns.get(addr)
            if conn is not None and not conn.closed:
                return conn
            policy = self.policy_for(peer_type)
            reviving = (isinstance(conn, Connection)
                        and conn.lane_group is None and conn.policy.replay)
            session_id = conn.session_id if reviving \
                else random.randbytes(8).hex()
            reader, writer = await asyncio.open_connection(*addr)
            try:
                (peer_name, resumed, peer_ckind, lanes_ok,
                 reader, writer) = await self._handshake_out(
                    reader, writer, policy.replay, session_id)
            except Exception:
                writer.close()
                raise
            crc_fn = self._negotiated_crc(peer_ckind)
            if reviving:
                if not resumed:
                    # acceptor lost the session (restart/eviction): its reply
                    # stream restarts at seq 1, so our dedupe floor must too.
                    # Replayed frames may re-dispatch there (at-least-once
                    # across an acceptor restart, as in the reference — PG
                    # reqid dedupe above absorbs it).
                    conn.in_seq = conn._ack_sent = 0
                conn.crc_fn = crc_fn
                await conn.adopt_transport(reader, writer)
                task = asyncio.get_running_loop().create_task(
                    self._serve(conn))
                self._tasks.add(task)
                task.add_done_callback(self._tasks.discard)
                return conn
            base = Connection(self, reader, writer, addr, policy,
                              peer_name, outbound=True)
            base.crc_fn = crc_fn
            base.session_id = session_id
            want_lanes = self.lanes_per_peer if lanes_ok else 1
            if want_lanes <= 1:
                self._conns[addr] = base
                task = asyncio.get_running_loop().create_task(
                    self._serve(base))
                self._tasks.add(task)
                task.add_done_callback(self._tasks.discard)
                return base
            # multi-lane session: lane 0 (this conn) is the control lane
            group = LaneGroup(self, addr, random.randbytes(8).hex(),
                              want_lanes, outbound=True, policy=policy)
            group.bind_lane(base, 0)
            task = asyncio.get_running_loop().create_task(self._serve(base))
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)
            await base.send(MLaneHello(group=group.group_id, lane=0,
                                       n_lanes=want_lanes,
                                       proc=PROC_TOKEN[:8]))
            results = await asyncio.gather(
                *[self._dial_lane(group, k)
                  for k in range(1, want_lanes)],
                return_exceptions=True)
            errs = [r for r in results if isinstance(r, BaseException)]
            if errs:
                await self._group_fatal(group)
                raise errs[0] if isinstance(errs[0], Exception) \
                    else ConnectionError("lane dial failed")
            self._conns[addr] = group
            return group

    async def _dial_lane(self, group: LaneGroup, lane_idx: int) -> None:
        """Open one data lane of a lane group."""
        reader, writer = await asyncio.open_connection(*group.peer)
        session_id = random.randbytes(8).hex()
        try:
            (peer_name, _resumed, peer_ckind, _lanes_ok,
             reader, writer) = await self._handshake_out(
                reader, writer, group.policy.replay, session_id)
        except Exception:
            writer.close()
            raise
        conn = Connection(self, reader, writer, group.peer,
                          group.policy, peer_name, outbound=True)
        conn.crc_fn = self._negotiated_crc(peer_ckind)
        conn.session_id = session_id
        group.bind_lane(conn, lane_idx)
        # the lane's first frame binds it on the acceptor — before
        # any striped data can ride it
        await conn.send(MLaneHello(group=group.group_id,
                                   lane=lane_idx,
                                   n_lanes=group.n_lanes,
                                   proc=PROC_TOKEN[:8]))
        task = asyncio.get_running_loop().create_task(
            self._serve(conn))
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def send(self, addr: Tuple[str, int], msg: Any, retries: int = 3,
                   peer_type: str = "osd") -> None:
        if self._local_fastpath:
            addr_t = tuple(addr)
            for _ in range(2):  # one retry: the peer may have re-bound
                peer = _LOCAL_REGISTRY.get(addr_t)
                if (peer is None or peer._shutdown
                        or not peer._local_fastpath
                        or peer._loop is not asyncio.get_running_loop()):
                    break  # not colocated (or another loop): real wire
                conn = self._local_conns.get(addr_t)
                if conn is None or conn.closed \
                        or conn.peer_messenger is not peer:
                    conn = LocalConnection(self, peer)
                    self._local_conns[addr_t] = conn
                try:
                    await conn.send(msg)
                    return
                except ConnectionError:
                    self._local_conns.pop(addr_t, None)
        last: Optional[Exception] = None
        for _ in range(retries + 1):
            try:
                conn = await self.connect(addr, peer_type)
                await conn.send(msg)
                return
            except PermissionError:
                raise
            except (ConnectionError, OSError) as e:
                last = e
                conn = self._conns.get(tuple(addr))
                if conn is not None and not conn.policy.replay:
                    self._conns.pop(tuple(addr), None)
        raise last  # type: ignore[misc]

    async def shutdown(self) -> None:
        self._shutdown = True
        if self.addr is not None \
                and _LOCAL_REGISTRY.get(tuple(self.addr)) is self:
            _LOCAL_REGISTRY.pop(tuple(self.addr), None)
        for lconn in list(self._local_conns.values()):
            await lconn.close()
        self._local_conns.clear()
        # cancel serve loops FIRST: in py3.12 Server.wait_closed() waits for
        # all connection handlers, so live inbound loops would deadlock it.
        if self._ack_sweep is not None:
            self._ack_sweep.cancel()
        for t in list(self._tasks):
            if not t.get_loop().is_closed():
                t.cancel()
        for conn in list(self._conns.values()):
            await conn.close()
        groups = list(self._lane_groups.values())
        self._lane_groups.clear()
        for g in groups:
            await g.close()
        sessions = list(self._sessions.values())
        self._sessions.clear()
        for conn in sessions:
            await conn.close()
        if self.server is not None:
            self.server.close()
            try:
                await asyncio.wait_for(self.server.wait_closed(), timeout=1.0)
            except asyncio.TimeoutError:
                pass
        # every connection is closed, so nothing of this messenger is on
        # the sender thread: the last messenger of a loop closes the
        # loop's end of it, the last loop's stops the thread
        with _OFFLOOP_LOCK:
            mine = [off for off in _OFFLOOPS.values() if self in off.users]
        for off in mine:
            off.release(self)

    # -- wire-plane introspection --------------------------------------------

    def dump_reactors(self) -> Dict[str, Any]:
        """asok ``dump_reactors`` payload: the wire arm and per-peer lane
        state (rendered by ``ceph daemon``)."""
        groups = [c for c in self._conns.values() if isinstance(c, LaneGroup)]
        groups += [g for g in self._lane_groups.values() if g not in groups]
        return {
            "lanes_per_peer": self.lanes_per_peer,
            "wirepath": "native" if self.wirepath is not None else "python",
            "peers": [g.dump() for g in groups],
        }
