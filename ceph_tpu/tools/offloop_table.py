"""What a flush window costs the event loop, written in its step or handed
to the sender thread: the table behind ``CorkedWriter.OFFLOOP_MIN_BYTES``
(rados/messenger.py; PERF.md section 6, PR 49).

    python -m ceph_tpu.tools.offloop_table [--rounds 200]

One loopback TCP session whose two ends share this process's loop, as a
vstart cluster's do: the receiving end takes the bytes with ``recv_into``
on the loop and throws them away.  For each window size the same window
(frame header, prefix, pickled part, blob) is written `rounds` times
through a CorkedWriter, each write drained before the next, once with
the inline arm and once with every window handed over.  Per window, in
µs of the loop's wall time:

  inline    CorkedWriter._do_send, every call (the first, and those the
            loop's writer callback made after an EAGAIN)
  handoff   CorkedWriter._hand_over: pin, enqueue, wake the thread
  done      _Offloop._reap: the completion step's own work
  thread    the sender thread's seconds inside writev (not the loop's)

`burst` repeats the hand-over with eight connections written back to
back before any drain: the thread is awake for the later ones and one
completion step may serve several windows.  What no column holds: the
selector's own cost of one more ready fd per completion batch.  Every
line is JSON; the numbers are a host's, never a device's."""

from __future__ import annotations

import argparse
import asyncio
import json
import time

from ceph_tpu.rados import messenger as msgr
from ceph_tpu.utils import wirepath

SIZES = (64, 4 << 10, 64 << 10, 512 << 10, 4 << 20)


class _Sink(asyncio.BufferedProtocol):
    """The receiving end: recv_into a 1 MiB buffer, count, forget."""

    def __init__(self) -> None:
        self.buf = memoryview(bytearray(1 << 20))
        self.got = 0

    def get_buffer(self, sizehint):
        return self.buf

    def buffer_updated(self, nbytes):
        self.got += nbytes


class _Timed:
    """Sum the wall seconds and calls of one method of one class."""

    def __init__(self, cls, name: str) -> None:
        self.cls, self.name, self.inner = cls, name, getattr(cls, name)
        self.seconds, self.calls = 0.0, 0
        inner, me = self.inner, self

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return inner(*args, **kwargs)
            finally:
                me.seconds += time.perf_counter() - t0
                me.calls += 1
        setattr(cls, name, timed)

    def take(self):
        out = (self.seconds, self.calls)
        self.seconds, self.calls = 0.0, 0
        return out

    def restore(self) -> None:
        setattr(self.cls, self.name, self.inner)


def _window(size: int):
    """A blob frame's segments, `size` bytes in all."""
    head = [b"h" * 21, b"p" * 8, b"k" * min(35, max(0, size - 29))]
    rest = size - sum(len(s) for s in head)
    if rest > 0:
        head.append(memoryview(bytearray(rest)))
    return [s for s in head if len(s)]


async def _writers(n: int, wp, server_port: int):
    loop = asyncio.get_running_loop()
    out = []
    for _ in range(n):
        r, w = await asyncio.open_connection("127.0.0.1", server_port)
        sock = w.transport.get_extra_info("socket")
        sock = getattr(sock, "_sock", sock)
        corked = msgr.CorkedWriter(w.transport, sock, w, wp=wp)
        corked.hears_loss(msgr._offloop_of(loop, wp))
        out.append(corked)
    return out


async def _run(rounds: int) -> None:
    wp = wirepath.impl()
    if wp is None:
        print(json.dumps({"error": "no native wirepath arm on this host"}))
        return
    loop = asyncio.get_running_loop()
    server = await loop.create_server(_Sink, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    writers = await _writers(8, wp, port)
    one = writers[0]
    send = _Timed(msgr.CorkedWriter, "_do_send")
    hand = _Timed(msgr.CorkedWriter, "_hand_over")
    done = _Timed(msgr._Offloop, "_reap")
    line = msgr.CorkedWriter.OFFLOOP_MIN_BYTES
    try:
        for size in SIZES:
            segs = _window(size)
            row = {"window_bytes": size, "rounds": rounds}
            # the inline arm
            msgr.CorkedWriter.OFFLOOP_MIN_BYTES = 1 << 62
            for _ in range(rounds):
                one.writelines(segs)
                await one.drain()
            seconds, calls = send.take()
            row["inline_us"] = seconds / rounds * 1e6
            row["inline_calls"] = calls / rounds
            # every window handed over, one at a time: the thread sleeps
            # between them, so each hand-over pays its wake
            msgr.CorkedWriter.OFFLOOP_MIN_BYTES = 0
            st0 = wp.wire_sender_stats()
            for _ in range(rounds):
                one.writelines(segs)
                await one.drain()
            st1 = wp.wire_sender_stats()
            seconds, _ = hand.take()
            row["handoff_us"] = seconds / rounds * 1e6
            seconds, calls = done.take()
            row["done_us"] = seconds / rounds * 1e6
            row["done_steps"] = calls / rounds
            row["thread_us"] = (st1["writev_ns"] - st0["writev_ns"]) \
                / rounds * 1e-3
            # eight connections written back to back
            bursts = max(1, rounds // 8)
            for _ in range(bursts):
                for w in writers:
                    w.writelines(segs)
                for w in writers:
                    await w.drain()
            seconds, _ = hand.take()
            row["burst_handoff_us"] = seconds / (bursts * 8) * 1e6
            seconds, calls = done.take()
            row["burst_done_us"] = seconds / (bursts * 8) * 1e6
            row["burst_done_steps"] = calls / (bursts * 8)
            send.take()
            print(json.dumps(row), flush=True)
    finally:
        msgr.CorkedWriter.OFFLOOP_MIN_BYTES = line
        for t in (send, hand, done):
            t.restore()
        for w in writers:
            w.close()
        server.close()
        await asyncio.sleep(0.05)
        msgr._OFFLOOPS[loop].close()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rounds", type=int, default=200)
    args = ap.parse_args()
    asyncio.run(_run(args.rounds))


if __name__ == "__main__":
    main()
