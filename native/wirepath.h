// Native wirepath: the messenger's per-byte hot loop below the GIL.
//
// In Python every per-byte operation of the wire — frame crc, fragment
// memcpy, writev segment assembly — runs under the interpreter lock, on
// the messenger's one loop.  These entry points batch that work into
// single foreign calls (ctypes drops the GIL around them), the
// wire-plane application of the specialize-the-byte-loops technique
// from "Accelerating XOR-based Erasure Coding using Program
// Optimization Techniques" (arXiv:2108.02692): the compiler vectorizes
// the copy/crc loops, and other threads run while a call does.
//
// Contract shared with ceph_tpu/native/bridge.py and the python arm in
// ceph_tpu/utils/wirepath.py: every function is a PURE function of its
// input bytes (byte-identity with the python arm is the correctness
// gate), never calls back into Python, and validates peer-claimed
// geometry (offsets, lengths, overlap) before touching memory — the
// FRAG_MAX overlap guard of LaneGroup.frag_view must hold here too.

#pragma once

#include <cstddef>
#include <cstdint>

extern "C" {

// which arm is live — "native" (mirrors ceph_tpu_crc32c_kind's role:
// BENCH records and /metrics report the arm that actually ran)
const char* ceph_tpu_wirepath_kind();

// Batch chained crc32c: ngroups frame-crc groups over a flat segment
// list; group g covers segments [starts[g], starts[g+1]) (starts has
// ngroups+1 entries, nondecreasing, ending at nseg) and chains
// crc32c from seeds[g] across its segments into out_crcs[g] — one
// released-GIL call for a whole flush window / rx burst instead of one
// ctypes round-trip per segment.  Returns 0, or -EINVAL on bad
// geometry (nothing written).
int32_t ceph_tpu_wire_crc_batch(const uint8_t* const* ptrs,
                                const size_t* lens, int32_t nseg,
                                const int32_t* starts, int32_t ngroups,
                                const uint32_t* seeds, uint32_t* out_crcs);

// Gather nseg segments into one contiguous tx buffer (the corked flush
// window's segment walk, natively).  Returns total bytes gathered, or
// -EINVAL when the segments exceed `cap` (nothing written).
int64_t ceph_tpu_wire_gather(const uint8_t* const* ptrs, const size_t* lens,
                             int32_t nseg, uint8_t* out, size_t cap);

// Single-pass copy + crc32c: copies src[0..n) to dst and returns the
// crc32c of the bytes, chained from `seed` — the rx verify+land step
// fused (blockwise, so the checksum pass runs cache-hot behind the
// copy).  dst may be NULL to checksum without copying.
uint32_t ceph_tpu_wire_copy_crc32c(const uint8_t* src, uint8_t* dst,
                                   size_t n, uint32_t seed);

// writev the segment list (minus `skip` leading logical bytes) onto a
// NONBLOCKING fd, looping over partial writes, EINTR, and IOV_MAX
// batches until everything is written or the kernel would block.
// Returns bytes written this call (0 = would-block immediately), or
// -errno on a hard socket error.  One foreign call drains a whole
// corked flush window with the GIL released.
int64_t ceph_tpu_wire_writev(int fd, const uint8_t* const* ptrs,
                             const size_t* lens, int32_t nseg, size_t skip);

// rx burst verify: n regions of ONE contiguous buffer (the
// FrameReceiver's pending backlog), each at offs[i]/lens[i], must
// crc32c (seed 0) to want[i].  One released-GIL call covers a whole
// burst's frame+blob crc sections — the caller passes plain integer
// offsets, so no per-region marshalling happens above.  Returns -1
// when every region matches, the first mismatching index on crc
// failure, or -EINVAL on out-of-bounds geometry.
int32_t ceph_tpu_wire_verify_regions(const uint8_t* base, size_t base_len,
                                     const int64_t* offs,
                                     const size_t* lens,
                                     const uint32_t* want, int32_t n);

// rx scatter: copy nfrags source fragments into dst at dst_offs[i],
// refusing peer-claimed geometry that is out of bounds or overlaps
// another fragment in the batch (the assembly-buffer overlap guard).
// With check_crc, fragment i's crc32c must equal want_crcs[i] — the
// crc runs over the SOURCE bytes before any copy, so a corrupt frame
// never lands a byte.  Fragments are validated and copied in order;
// on refusal *bad_idx gets the offending index and no later fragment
// is touched.  Returns fragments copied (== nfrags on success),
// -EINVAL (geometry) or -EBADMSG (crc) with *bad_idx set.
int32_t ceph_tpu_wire_scatter(const uint8_t* const* src_ptrs,
                              const size_t* src_lens, int32_t nfrags,
                              const int64_t* dst_offs, uint8_t* dst,
                              size_t dst_len, const uint32_t* want_crcs,
                              int32_t check_crc, int32_t* bad_idx);

// ---- the off-loop sender (ISSUE 49) ---------------------------------------
//
// ONE native thread a process takes over the kernel's half of a big
// send: a flush window's writev runs here, with no GIL anywhere near
// it, while the event loop that handed it over goes on.  The thread
// runs no Python and never takes the GIL; it owns an epoll of its own
// and a FIFO of jobs per fd.  A job is written with the loop of
// ceph_tpu_wire_writev (partial writes, EINTR, IOV_MAX); on EAGAIN the
// fd is armed for EPOLLOUT and the thread goes on with the other fds,
// so a receiver that pauses holds back its own connection only.  Order
// on an fd is the order handed.
//
// A job's completion is posted to its CHANNEL, an eventfd of the
// caller's (one per event loop): (token, bytes written or -errno).  The
// eventfd is written once per batch: a channel that was signalled and
// not yet reaped is not written again.  The segments' memory belongs to
// the caller and stays as it is until the job's completion was reaped.
//
// The thread starts at the first submit and ends at
// ceph_tpu_wire_sender_stop; a fork's child finds no thread, no queue
// and no fd of the parent's sender (pthread_atfork) and starts its own
// at its first submit.

// Queue one job: nseg segments to `fd` (nonblocking), completion to
// `chan`.  Returns the jobs the thread had unfinished before this one
// (>= 0), or -EINVAL (bad geometry: nothing queued), -EAGAIN (no thread
// could be started).
int32_t ceph_tpu_wire_sender_submit(int fd, int chan, uint64_t token,
                                    const uint8_t* const* ptrs,
                                    const size_t* lens, int32_t nseg);

// Take up to `cap` completions of `chan` and reset its eventfd.  Returns
// how many were copied; a caller that got `cap` asks again.  eagains[i]
// is how often job i found its socket full.
int32_t ceph_tpu_wire_sender_reap(int chan, uint64_t* tokens,
                                  int64_t* results, uint32_t* eagains,
                                  int32_t cap);

// Drop every job of `fd` and return only when the thread is in no
// system call on it: after this the fd may be closed and its number
// reused.  Each dropped job completes with -ECANCELED on its channel.
// Returns the jobs dropped (0: the fd had none).
int32_t ceph_tpu_wire_sender_cancel(int fd);

// Drop every job whose completion goes to `chan` (as cancel does, fd by
// fd), and forget the channel if nothing of it waits to be reaped: a
// caller that keeps memory for its jobs reaps what this posted, then
// calls again.  Returns the jobs dropped.
int32_t ceph_tpu_wire_sender_close_chan(int chan);

// Stop the thread (joined before this returns); jobs still queued
// complete with -ECANCELED.  The next submit starts a new thread.
// Returns the jobs dropped.
int32_t ceph_tpu_wire_sender_stop();

// Counters since the library was loaded (a fork's child: since the
// fork), out[0..10): jobs submitted, completed, failed, cancelled, bytes
// written, writev calls, EAGAINs, nanoseconds inside writev, thread
// starts, eventfd writes to channels.  Jobs unfinished
// = submitted - completed - failed - cancelled.
void ceph_tpu_wire_sender_stats(uint64_t out[10]);

// Adversarial self-battery: truncated, overlapping, corrupt-offset and
// oversize fragment geometries against the scatter/gather/crc entry
// points above.  Returns 0 when every hostile case is refused and every
// benign case round-trips; a nonzero return is the failing case number.
// Runs under the ASan/UBSan flavor in the slow native test leg (an
// asan .so cannot be dlopen'd into a plain python process, so the
// battery lives here and a sanitized exe wraps it) and via ctypes in
// the tier-1 smoke.
int32_t ceph_tpu_wirepath_selftest();

}  // extern "C"
