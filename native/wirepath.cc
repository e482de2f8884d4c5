// Native wirepath entry points (see wirepath.h): batch crc, gather,
// fused copy+crc, whole-window writev, and guarded rx scatter for the
// Python messenger's hot loop.  Byte-identity with the python arm is
// the contract — every function is a pure function of its input bytes,
// with crc32c (crc32c.cc, hardware or table — bit-identical) as the
// only checksum.

#include "wirepath.h"

#include <algorithm>
#include <cerrno>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <mutex>
#include <unordered_map>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <signal.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

// crc32c.cc exports this without a header of its own
extern "C" uint32_t ceph_tpu_crc32c(uint32_t seed, const uint8_t* data,
                                    size_t len);

namespace {

// one batch's iovec ceiling: conservative vs UIO_MAXIOV (1024 on
// linux), matching the Python CorkedWriter's IOV_MAX discipline
constexpr int kIovMax = 512;

// fused copy+crc block: big enough to amortize the two loop heads,
// small enough that the crc pass re-reads L1/L2-hot bytes
constexpr size_t kCopyBlock = 64 * 1024;

}  // namespace

extern "C" {

const char* ceph_tpu_wirepath_kind() { return "native"; }

int32_t ceph_tpu_wire_crc_batch(const uint8_t* const* ptrs,
                                const size_t* lens, int32_t nseg,
                                const int32_t* starts, int32_t ngroups,
                                const uint32_t* seeds, uint32_t* out_crcs) {
  if (nseg < 0 || ngroups < 0 || !starts || !out_crcs) return -EINVAL;
  if ((nseg > 0 && (!ptrs || !lens)) || starts[ngroups] != nseg)
    return -EINVAL;
  // validate EVERY boundary before dereferencing any segment: a single
  // corrupt starts[] entry must not drive an out-of-bounds ptrs[] read
  for (int32_t g = 0; g < ngroups; ++g)
    if (starts[g] < 0 || starts[g] > starts[g + 1]) return -EINVAL;
  for (int32_t s = 0; s < nseg; ++s)
    if (!ptrs[s] && lens[s]) return -EINVAL;
  for (int32_t g = 0; g < ngroups; ++g) {
    uint32_t crc = seeds ? seeds[g] : 0;
    for (int32_t s = starts[g]; s < starts[g + 1]; ++s)
      crc = ceph_tpu_crc32c(crc, ptrs[s], lens[s]);
    out_crcs[g] = crc;
  }
  return 0;
}

int64_t ceph_tpu_wire_gather(const uint8_t* const* ptrs, const size_t* lens,
                             int32_t nseg, uint8_t* out, size_t cap) {
  if (nseg < 0 || !out || (nseg > 0 && (!ptrs || !lens))) return -EINVAL;
  size_t total = 0;
  for (int32_t i = 0; i < nseg; ++i) {
    if (!ptrs[i] && lens[i]) return -EINVAL;
    if (lens[i] > cap - total) return -EINVAL;  // cap - total can't wrap
    total += lens[i];
  }
  size_t off = 0;
  for (int32_t i = 0; i < nseg; ++i) {
    if (lens[i]) std::memcpy(out + off, ptrs[i], lens[i]);
    off += lens[i];
  }
  return static_cast<int64_t>(total);
}

uint32_t ceph_tpu_wire_copy_crc32c(const uint8_t* src, uint8_t* dst,
                                   size_t n, uint32_t seed) {
  uint32_t crc = seed;
  if (!src) return crc;
  if (!dst) return ceph_tpu_crc32c(crc, src, n);
  size_t off = 0;
  while (off < n) {
    size_t blk = std::min(kCopyBlock, n - off);
    std::memcpy(dst + off, src + off, blk);
    // checksum the DESTINATION bytes: cache-hot from the copy, and it
    // proves the landed copy, not just the source
    crc = ceph_tpu_crc32c(crc, dst + off, blk);
    off += blk;
  }
  return crc;
}

int64_t ceph_tpu_wire_writev(int fd, const uint8_t* const* ptrs,
                             const size_t* lens, int32_t nseg, size_t skip) {
  if (fd < 0 || nseg < 0 || (nseg > 0 && (!ptrs || !lens))) return -EINVAL;
  int32_t i = 0;
  size_t off = skip;
  while (i < nseg && off >= lens[i]) {
    off -= lens[i];
    ++i;
  }
  if (i >= nseg) return off ? -EINVAL : 0;  // skip past the end
  int64_t written = 0;
  std::vector<iovec> iov;
  iov.reserve(std::min(nseg - i, kIovMax));
  while (i < nseg) {
    iov.clear();
    size_t batch_bytes = 0;
    size_t o = off;
    for (int32_t j = i; j < nseg && static_cast<int>(iov.size()) < kIovMax;
         ++j) {
      if (!ptrs[j] && lens[j]) return -EINVAL;
      size_t len = lens[j] - o;
      if (len) {
        iovec v;
        v.iov_base = const_cast<uint8_t*>(ptrs[j]) + o;
        v.iov_len = len;
        iov.push_back(v);
        batch_bytes += len;
      }
      o = 0;
    }
    if (iov.empty()) break;  // nothing but empty segments left
    ssize_t w = ::writev(fd, iov.data(), iov.size());
    if (w < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) return written;
      return -static_cast<int64_t>(errno);
    }
    written += w;
    size_t n = static_cast<size_t>(w);
    while (i < nseg && n >= lens[i] - off) {
      n -= lens[i] - off;
      off = 0;
      ++i;
    }
    off += n;
    if (static_cast<size_t>(w) < batch_bytes) {
      // short write: the socket buffer is nearly full — one more
      // writev round usually returns EAGAIN; loop rather than assume
      continue;
    }
  }
  return written;
}

int32_t ceph_tpu_wire_verify_regions(const uint8_t* base, size_t base_len,
                                     const int64_t* offs,
                                     const size_t* lens,
                                     const uint32_t* want, int32_t n) {
  if (n < 0 || (n > 0 && (!base || !offs || !lens || !want)))
    return -EINVAL;
  for (int32_t i = 0; i < n; ++i) {
    int64_t o = offs[i];
    if (o < 0 || static_cast<uint64_t>(o) > base_len
        || lens[i] > base_len - static_cast<size_t>(o))
      return -EINVAL;
  }
  for (int32_t i = 0; i < n; ++i) {
    if (ceph_tpu_crc32c(0, base + offs[i], lens[i]) != want[i]) return i;
  }
  return -1;
}

int32_t ceph_tpu_wire_scatter(const uint8_t* const* src_ptrs,
                              const size_t* src_lens, int32_t nfrags,
                              const int64_t* dst_offs, uint8_t* dst,
                              size_t dst_len, const uint32_t* want_crcs,
                              int32_t check_crc, int32_t* bad_idx) {
  if (bad_idx) *bad_idx = -1;
  if (nfrags < 0 || !dst
      || (nfrags > 0 && (!src_ptrs || !src_lens || !dst_offs)))
    return -EINVAL;
  if (check_crc && !want_crcs) return -EINVAL;
  int32_t copied = 0;
  for (int32_t f = 0; f < nfrags; ++f) {
    int64_t o = dst_offs[f];
    size_t len = src_lens[f];
    if (!src_ptrs[f] || o < 0 || static_cast<uint64_t>(o) > dst_len
        || len > dst_len - static_cast<size_t>(o)) {
      if (bad_idx) *bad_idx = f;
      return -EINVAL;
    }
    // overlap guard vs the fragments already accepted in THIS batch
    // (the Python LaneGroup guards against previously-confirmed
    // ranges before the call; together they keep a corrupt-offset
    // fragment from stomping verified bytes of the assembly buffer)
    for (int32_t p = 0; p < f; ++p) {
      int64_t po = dst_offs[p];
      size_t plen = src_lens[p];
      if (o < po + static_cast<int64_t>(plen)
          && po < o + static_cast<int64_t>(len)) {
        if (bad_idx) *bad_idx = f;
        return -EINVAL;
      }
    }
    if (check_crc) {
      // verify the SOURCE bytes first: a corrupt fragment must die
      // before a single byte of it lands in the assembly
      if (ceph_tpu_crc32c(0, src_ptrs[f], len) != want_crcs[f]) {
        if (bad_idx) *bad_idx = f;
        return -EBADMSG;
      }
    }
    if (len) std::memcpy(dst + o, src_ptrs[f], len);
    ++copied;
  }
  return copied;
}

}  // extern "C"

// ---- the off-loop sender (wirepath.h) --------------------------------------

namespace {

struct SendJob {
  uint64_t token;
  int fd;
  int chan;
  std::vector<const uint8_t*> ptrs;
  std::vector<size_t> lens;
  size_t total = 0;
  size_t written = 0;
  uint32_t eagains = 0;
};

// one fd's jobs, oldest first.  `busy`: the thread is in a system call
// on the fd (the mutex is NOT held then); `armed`: the fd waits for
// EPOLLOUT; `queued`: the fd is on the ready list; `cancelling`:
// somebody waits for `busy` to end and owns the jobs from there.
struct FdQueue {
  std::deque<SendJob*> jobs;
  bool busy = false;
  bool armed = false;
  bool registered = false;  // known to the epoll (armed or spent oneshot)
  bool queued = false;
  bool cancelling = false;
};

struct Done {
  uint64_t token;
  int64_t result;
  uint32_t eagains;
};

struct Chan {
  std::vector<Done> done;
  // a write of its eventfd is owed or was made, and no reap came since
  bool signalled = false;
  int writers = 0;  // threads in that write now (signal_chans)
};

enum {
  kSubmitted, kCompleted, kFailed, kCancelled, kBytes, kWritevCalls,
  kEagains, kWritevNs, kStarts, kSignals, kNStats
};

struct Sender {
  std::mutex mu;
  std::condition_variable idle;  // an fd's `busy` ended
  pthread_t thread{};
  bool started = false;
  bool stop = false;
  bool sleeping = false;  // the thread is in epoll_wait with no timeout
  int ep = -1;
  int wake = -1;  // eventfd in `ep`: work arrived while the thread slept
  int n_armed = 0;
  std::unordered_map<int, FdQueue> fds;
  std::deque<int> ready;
  std::unordered_map<int, Chan> chans;
  std::vector<int> to_signal;  // channels post_done marked, not yet written
  uint64_t stats[kNStats] = {};
};

Sender* g_sender = nullptr;
std::once_flag g_sender_once;

uint64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<uint64_t>(ts.tv_sec) * 1000000000ull
         + static_cast<uint64_t>(ts.tv_nsec);
}

// -- everything below runs with s.mu held unless it says otherwise ----------

void post_done(Sender& s, SendJob* job, int64_t result) {
  Chan& c = s.chans[job->chan];
  c.done.push_back(Done{job->token, result, job->eagains});
  if (!c.signalled) {
    c.signalled = true;
    s.to_signal.push_back(job->chan);
  }
  delete job;
}

// Write the eventfds of the channels post_done marked, with the mutex
// RELEASED: an event loop takes it with the GIL held (submit, reap), and
// a system call of the thread's must not make the loop and every Python
// thread behind it wait.  Whoever posted calls this before it lets go of
// the mutex for good; close_chan waits for `writers` to end before its
// loop may close the eventfd (a number handed out again would take the
// write).  A channel whose eventfd is gone keeps its completions until
// close_chan forgets them.
void signal_chans(Sender& s, std::unique_lock<std::mutex>& lk) {
  if (s.to_signal.empty()) return;
  std::vector<int> chans;
  chans.swap(s.to_signal);
  for (int chan : chans) ++s.chans[chan].writers;
  lk.unlock();
  uint64_t wrote = 0;
  for (int chan : chans) {
    uint64_t one = 1;
    if (::write(chan, &one, sizeof(one)) == sizeof(one)) ++wrote;
  }
  lk.lock();
  s.stats[kSignals] += wrote;
  for (int chan : chans) --s.chans[chan].writers;
  s.idle.notify_all();
}

void enqueue_ready(Sender& s, int fd, FdQueue& q) {
  if (!q.queued && !q.armed && !q.busy && !q.jobs.empty()) {
    q.queued = true;
    s.ready.push_back(fd);
  }
}

void wake_thread(Sender& s) {
  if (s.sleeping) {
    s.sleeping = false;  // one write a sleep, however many submits
    uint64_t one = 1;
    ssize_t r = ::write(s.wake, &one, sizeof(one));
    (void)r;
  }
}

void forget_fd(Sender& s, int fd, FdQueue& q) {
  if (q.armed) --s.n_armed;
  if (q.registered) ::epoll_ctl(s.ep, EPOLL_CTL_DEL, fd, nullptr);
  s.fds.erase(fd);  // `q` is gone from here
}

// every job of `fd` ends with `result`; the caller made sure the thread
// is in no system call on it
int32_t drop_fd(Sender& s, int fd, FdQueue& q, int64_t result,
                int counter) {
  int32_t n = 0;
  for (SendJob* job : q.jobs) {
    post_done(s, job, result);
    ++s.stats[counter];
    ++n;
  }
  q.jobs.clear();
  forget_fd(s, fd, q);
  return n;
}

int32_t cancel_fd(Sender& s, std::unique_lock<std::mutex>& lk, int fd) {
  auto it = s.fds.find(fd);
  if (it == s.fds.end()) return 0;
  if (it->second.busy) {
    it->second.cancelling = true;
    s.idle.wait(lk, [&] {
      auto again = s.fds.find(fd);
      return again == s.fds.end() || !again->second.busy;
    });
    it = s.fds.find(fd);
    if (it == s.fds.end()) return 0;  // a second canceller took it
  }
  return drop_fd(s, fd, it->second, -ECANCELED, kCancelled);
}

void take_events(Sender& s, const epoll_event* evs, int n) {
  for (int i = 0; i < n; ++i) {
    int fd = evs[i].data.fd;
    if (fd == s.wake) {
      uint64_t v;
      ssize_t r = ::read(s.wake, &v, sizeof(v));
      (void)r;
      continue;
    }
    auto it = s.fds.find(fd);
    if (it == s.fds.end() || !it->second.armed) continue;
    // EPOLLOUT, or EPOLLERR / EPOLLHUP: the next writev says which
    it->second.armed = false;
    --s.n_armed;
    enqueue_ready(s, fd, it->second);
  }
}

void* sender_main(void* arg) {
  Sender& s = *static_cast<Sender*>(arg);
  epoll_event evs[64];
  std::unique_lock<std::mutex> lk(s.mu);
  const int ep = s.ep;  // the thread's own until it was joined
  for (;;) {
    signal_chans(s, lk);  // what the last turn posted
    if (s.stop) break;
    if (s.ready.empty() || s.n_armed > 0) {
      // nothing to write: sleep until a submit or a socket wakes us.
      // Work in hand and sockets armed: look at them without waiting,
      // so a drained socket does not wait behind the other fds' jobs
      bool wait = s.ready.empty();
      s.sleeping = wait;
      lk.unlock();
      int n = ::epoll_wait(ep, evs, 64, wait ? -1 : 0);
      lk.lock();
      s.sleeping = false;
      if (n > 0) take_events(s, evs, n);
      if (s.ready.empty()) continue;
    }
    int fd = s.ready.front();
    s.ready.pop_front();
    auto it = s.fds.find(fd);
    if (it == s.fds.end()) continue;  // cancelled while it waited
    FdQueue& q = it->second;  // stays where it is while `busy`
    q.queued = false;
    if (q.jobs.empty() || q.armed || q.busy || q.cancelling) continue;
    SendJob* job = q.jobs.front();
    q.busy = true;
    lk.unlock();
    uint64_t t0 = now_ns();
    int64_t w = ceph_tpu_wire_writev(
        job->fd, job->ptrs.data(), job->lens.data(),
        static_cast<int32_t>(job->ptrs.size()), job->written);
    uint64_t took = now_ns() - t0;
    if (w > 0) job->written += static_cast<size_t>(w);
    // the socket is full: the fd waits for EPOLLOUT, the others go on.
    // Armed here, still `busy` and the mutex released: the event can
    // only reach this thread's own epoll_wait
    bool full = w >= 0 && job->written < job->total;
    int arm_errno = 0;
    if (full) {
      epoll_event ev{};
      ev.events = EPOLLOUT | EPOLLONESHOT;
      ev.data.fd = fd;
      if (::epoll_ctl(ep, q.registered ? EPOLL_CTL_MOD : EPOLL_CTL_ADD, fd,
                      &ev) != 0)
        arm_errno = errno;
    }
    lk.lock();
    q.busy = false;
    ++s.stats[kWritevCalls];
    s.stats[kWritevNs] += took;
    if (w > 0) s.stats[kBytes] += static_cast<uint64_t>(w);
    if (full && arm_errno == 0) q.registered = true;
    if (q.cancelling) {
      s.idle.notify_all();  // the canceller owns the jobs now
      continue;
    }
    if (w < 0) {
      // the transport is gone: this job says why, those behind it on
      // the fd never ran
      q.jobs.pop_front();
      post_done(s, job, w);
      ++s.stats[kFailed];
      drop_fd(s, fd, q, -ECANCELED, kCancelled);
      continue;
    }
    if (!full) {
      q.jobs.pop_front();
      post_done(s, job, static_cast<int64_t>(job->total));
      ++s.stats[kCompleted];
      if (q.jobs.empty())
        forget_fd(s, fd, q);
      else
        enqueue_ready(s, fd, q);
      continue;
    }
    ++job->eagains;
    ++s.stats[kEagains];
    if (arm_errno != 0) {
      q.jobs.pop_front();
      post_done(s, job, -static_cast<int64_t>(arm_errno));
      ++s.stats[kFailed];
      drop_fd(s, fd, q, -ECANCELED, kCancelled);
      continue;
    }
    q.armed = true;
    ++s.n_armed;
  }
  signal_chans(s, lk);
  return nullptr;
}

void close_fds(Sender& s) {
  if (s.ep >= 0) ::close(s.ep);
  if (s.wake >= 0) ::close(s.wake);
  s.ep = s.wake = -1;
}

bool start_thread(Sender& s) {
  s.ep = ::epoll_create1(EPOLL_CLOEXEC);
  s.wake = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.fd = s.wake;
  if (s.ep < 0 || s.wake < 0
      || ::epoll_ctl(s.ep, EPOLL_CTL_ADD, s.wake, &ev) != 0) {
    close_fds(s);
    return false;
  }
  // the thread takes no signal: a handler of the interpreter's never
  // runs on it
  sigset_t all, old;
  sigfillset(&all);
  pthread_sigmask(SIG_SETMASK, &all, &old);
  s.stop = false;
  int rc = pthread_create(&s.thread, nullptr, sender_main, &s);
  pthread_sigmask(SIG_SETMASK, &old, nullptr);
  if (rc != 0) {
    close_fds(s);
    return false;
  }
  s.started = true;
  ++s.stats[kStarts];
  return true;
}

// fork: the forking thread holds the mutex across it, so the child finds
// the state whole; the child has no sender thread, none of its jobs (the
// parent's thread writes them) and closes its copies of the thread's fds
void atfork_prepare() { g_sender->mu.lock(); }
void atfork_parent() { g_sender->mu.unlock(); }
void atfork_child() {
  Sender& s = *g_sender;
  for (auto& kv : s.fds)
    for (SendJob* job : kv.second.jobs) delete job;
  s.fds.clear();
  s.ready.clear();
  s.chans.clear();
  s.n_armed = 0;
  s.started = s.stop = s.sleeping = false;
  close_fds(s);
  std::memset(s.stats, 0, sizeof(s.stats));
  s.mu.unlock();
}

Sender& sender() {
  std::call_once(g_sender_once, [] {
    g_sender = new Sender();  // never destroyed: the thread may outlive main
    pthread_atfork(atfork_prepare, atfork_parent, atfork_child);
  });
  return *g_sender;
}

}  // namespace

extern "C" {

int32_t ceph_tpu_wire_sender_submit(int fd, int chan, uint64_t token,
                                    const uint8_t* const* ptrs,
                                    const size_t* lens, int32_t nseg) {
  if (fd < 0 || chan < 0 || nseg <= 0 || !ptrs || !lens) return -EINVAL;
  size_t total = 0;
  for (int32_t i = 0; i < nseg; ++i) {
    if (!ptrs[i] && lens[i]) return -EINVAL;
    total += lens[i];
  }
  if (total == 0) return -EINVAL;
  SendJob* job = new SendJob();
  job->token = token;
  job->fd = fd;
  job->chan = chan;
  job->ptrs.assign(ptrs, ptrs + nseg);
  job->lens.assign(lens, lens + nseg);
  job->total = total;
  Sender& s = sender();
  std::lock_guard<std::mutex> lk(s.mu);
  if (!s.started && !start_thread(s)) {
    delete job;
    return -EAGAIN;
  }
  uint64_t depth = s.stats[kSubmitted] - s.stats[kCompleted]
                   - s.stats[kFailed] - s.stats[kCancelled];
  ++s.stats[kSubmitted];
  FdQueue& q = s.fds[fd];
  q.jobs.push_back(job);
  enqueue_ready(s, fd, q);
  wake_thread(s);
  return static_cast<int32_t>(std::min<uint64_t>(depth, INT32_MAX));
}

int32_t ceph_tpu_wire_sender_reap(int chan, uint64_t* tokens,
                                  int64_t* results, uint32_t* eagains,
                                  int32_t cap) {
  if (chan < 0 || cap <= 0 || !tokens || !results || !eagains)
    return -EINVAL;
  Sender& s = sender();
  std::lock_guard<std::mutex> lk(s.mu);
  auto it = s.chans.find(chan);
  if (it == s.chans.end()) return 0;
  Chan& c = it->second;
  // the eventfd is reset whether or not `signalled` says it was written:
  // the write is made after the mutex was let go (signal_chans) and may
  // land after the reap that took its completions, and the eventfd must
  // not stay readable then.  The caller's own system call, under the
  // mutex: it keeps the thread from posting for as long, nobody else
  uint64_t v;
  ssize_t r = ::read(chan, &v, sizeof(v));
  (void)r;
  c.signalled = false;
  size_t n = std::min(c.done.size(), static_cast<size_t>(cap));
  for (size_t i = 0; i < n; ++i) {
    tokens[i] = c.done[i].token;
    results[i] = c.done[i].result;
    eagains[i] = c.done[i].eagains;
  }
  c.done.erase(c.done.begin(), c.done.begin() + n);
  return static_cast<int32_t>(n);
}

int32_t ceph_tpu_wire_sender_cancel(int fd) {
  if (fd < 0) return -EINVAL;
  Sender& s = sender();
  std::unique_lock<std::mutex> lk(s.mu);
  int32_t n = cancel_fd(s, lk, fd);
  signal_chans(s, lk);
  return n;
}

int32_t ceph_tpu_wire_sender_close_chan(int chan) {
  if (chan < 0) return -EINVAL;
  Sender& s = sender();
  std::unique_lock<std::mutex> lk(s.mu);
  int32_t n = 0;
  for (;;) {
    // one fd at a time: a cancel may wait, and the map moves meanwhile
    int fd = -1;
    for (auto& kv : s.fds) {
      for (SendJob* job : kv.second.jobs)
        if (job->chan == chan) fd = kv.first;
      if (fd >= 0) break;
    }
    if (fd < 0) break;
    n += cancel_fd(s, lk, fd);
  }
  // nobody is left writing the eventfd when this returns
  signal_chans(s, lk);
  s.idle.wait(lk, [&] {
    auto c = s.chans.find(chan);
    return c == s.chans.end() || c->second.writers == 0;
  });
  auto it = s.chans.find(chan);
  if (it != s.chans.end() && it->second.done.empty()) s.chans.erase(it);
  return n;
}

int32_t ceph_tpu_wire_sender_stop() {
  Sender& s = sender();
  std::unique_lock<std::mutex> lk(s.mu);
  if (!s.started) return 0;
  s.stop = true;
  s.sleeping = true;  // make wake_thread write whatever the thread does
  wake_thread(s);
  pthread_t th = s.thread;
  lk.unlock();
  pthread_join(th, nullptr);
  lk.lock();
  s.started = false;
  int32_t n = 0;
  while (!s.fds.empty()) {
    auto it = s.fds.begin();
    n += drop_fd(s, it->first, it->second, -ECANCELED, kCancelled);
  }
  s.ready.clear();
  close_fds(s);
  signal_chans(s, lk);
  return n;
}

void ceph_tpu_wire_sender_stats(uint64_t out[10]) {
  static_assert(kNStats == 10, "wirepath.h documents ten counters");
  Sender& s = sender();
  std::lock_guard<std::mutex> lk(s.mu);
  std::memcpy(out, s.stats, sizeof(s.stats));
}

}  // extern "C"

namespace {

// wait (at most ~5 s) until `chan` handed out `want` completions
int reap_n(int chan, int want, uint64_t* tokens, int64_t* results) {
  uint32_t eagains[8];
  int got = 0;
  for (int spin = 0; got < want && spin < 5000; ++spin) {
    int n = ceph_tpu_wire_sender_reap(chan, tokens + got, results + got,
                                      eagains, want - got);
    if (n < 0) return n;
    got += n;
    if (got < want) ::usleep(1000);
  }
  return got;
}

// the sender's own battery: start, hand over, cancel while a job is half
// written, stop with jobs queued, bad geometry refused, stats add up.
// Skipped (0) while the process's sender has work of somebody else's: the
// battery stops the thread.
int32_t sender_selftest(const uint8_t* data, size_t n) {
  uint64_t before[kNStats], after[kNStats];
  ceph_tpu_wire_sender_stats(before);
  if (before[kSubmitted] != before[kCompleted] + before[kFailed]
                                + before[kCancelled])
    return 0;
  int sv[2], sw[2];
  if (::socketpair(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0, sv) != 0
      || ::socketpair(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0, sw) != 0)
    return 30;
  int chan = ::eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
  if (chan < 0) return 31;
  int32_t rc = 0;
  uint64_t tokens[8];
  int64_t results[8];
  uint32_t eagain1[1];
  std::vector<uint8_t> big(4u << 20, 0x5a);
  const uint8_t* bigp[1] = {big.data()};
  size_t bigl[1] = {big.size()};
  do {
    const uint8_t* ptrs[3] = {data, data + 100, data + 1000};
    size_t lens[3] = {100, 900, n - 1000};
    // bad geometry: nothing is queued
    size_t zero[1] = {0};
    const uint8_t* null_seg[1] = {nullptr};
    if (ceph_tpu_wire_sender_submit(-1, chan, 9, ptrs, lens, 3) != -EINVAL
        || ceph_tpu_wire_sender_submit(sv[0], -1, 9, ptrs, lens, 3) != -EINVAL
        || ceph_tpu_wire_sender_submit(sv[0], chan, 9, ptrs, lens, 0)
               != -EINVAL
        || ceph_tpu_wire_sender_submit(sv[0], chan, 9, null_seg, lens, 1)
               != -EINVAL
        || ceph_tpu_wire_sender_submit(sv[0], chan, 9, ptrs, zero, 1)
               != -EINVAL) {
      rc = 32;
      break;
    }
    // two jobs on one fd: both arrive whole, in the order handed
    if (ceph_tpu_wire_sender_submit(sv[0], chan, 1, ptrs, lens, 3) < 0
        || ceph_tpu_wire_sender_submit(sv[0], chan, 2, ptrs + 1, lens + 1, 2)
               < 0) {
      rc = 33;
      break;
    }
    if (reap_n(chan, 2, tokens, results) != 2 || tokens[0] != 1
        || tokens[1] != 2 || results[0] != static_cast<int64_t>(n)
        || results[1] != static_cast<int64_t>(n - 100)) {
      rc = 34;
      break;
    }
    std::vector<uint8_t> got(2 * n);
    size_t have = 0;
    while (have < 2 * n - 100) {
      ssize_t r = ::read(sv[1], got.data() + have, got.size() - have);
      if (r <= 0) break;
      have += static_cast<size_t>(r);
    }
    if (have != 2 * n - 100 || std::memcmp(got.data(), data, n) != 0
        || std::memcmp(got.data() + n, data + 100, n - 100) != 0) {
      rc = 35;
      break;
    }
    // nobody reads sv[1]: a 4 MiB job is half written and waits for
    // EPOLLOUT, a job to another fd passes it meanwhile; the cancel
    // returns with the thread off the fd
    uint64_t mid[kNStats];
    if (ceph_tpu_wire_sender_submit(sv[0], chan, 3, bigp, bigl, 1) < 0) {
      rc = 36;
      break;
    }
    for (int spin = 0; spin < 5000; ++spin) {
      ceph_tpu_wire_sender_stats(mid);
      if (mid[kEagains] > before[kEagains]) break;
      ::usleep(1000);
    }
    if (mid[kEagains] == before[kEagains]) {
      rc = 37;
      break;
    }
    if (ceph_tpu_wire_sender_submit(sw[0], chan, 4, ptrs, lens, 3) < 0
        || reap_n(chan, 1, tokens, results) != 1 || tokens[0] != 4
        || results[0] != static_cast<int64_t>(n)) {
      rc = 38;
      break;
    }
    if (ceph_tpu_wire_sender_cancel(sv[0]) != 1
        || reap_n(chan, 1, tokens, results) != 1 || tokens[0] != 3
        || results[0] != -ECANCELED
        || ceph_tpu_wire_sender_cancel(sv[0]) != 0) {
      rc = 39;
      break;
    }
    // stop with a job parked and one queued behind it: both end
    // -ECANCELED, and the next submit finds a new thread
    if (ceph_tpu_wire_sender_submit(sv[0], chan, 5, bigp, bigl, 1) < 0
        || ceph_tpu_wire_sender_submit(sv[0], chan, 6, ptrs, lens, 3) < 0) {
      rc = 40;
      break;
    }
    if (ceph_tpu_wire_sender_stop() != 2
        || reap_n(chan, 2, tokens, results) != 2
        || results[0] != -ECANCELED || results[1] != -ECANCELED) {
      rc = 41;
      break;
    }
    if (ceph_tpu_wire_sender_submit(sw[0], chan, 7, ptrs, lens, 3) < 0
        || reap_n(chan, 1, tokens, results) != 1 || tokens[0] != 7
        || results[0] != static_cast<int64_t>(n)) {
      rc = 42;
      break;
    }
    if (ceph_tpu_wire_sender_stop() != 0) {
      rc = 43;
      break;
    }
    // a channel's eventfd is written after the thread let go of the
    // mutex, so the write may land after the reap that took its
    // completions: the next reap finds nothing and still resets it
    uint64_t late = 1, left = 0;
    if (::write(chan, &late, sizeof(late)) != sizeof(late)
        || ceph_tpu_wire_sender_reap(chan, tokens, results, eagain1, 1) != 0
        || ::read(chan, &left, sizeof(left)) >= 0 || errno != EAGAIN) {
      rc = 45;
      break;
    }
    // the stats add up: seven jobs, four written whole, three dropped
    ceph_tpu_wire_sender_stats(after);
    if (after[kSubmitted] - before[kSubmitted] != 7
        || after[kCompleted] - before[kCompleted] != 4
        || after[kCancelled] - before[kCancelled] != 3
        || after[kFailed] != before[kFailed]
        || after[kStarts] == before[kStarts]
        || after[kBytes] - before[kBytes] < 4 * n - 100) {
      rc = 44;
      break;
    }
  } while (false);
  ceph_tpu_wire_sender_stop();
  ceph_tpu_wire_sender_close_chan(chan);
  ::close(chan);
  ::close(sv[0]);
  ::close(sv[1]);
  ::close(sw[0]);
  ::close(sw[1]);
  return rc;
}

}  // namespace

extern "C" {

int32_t ceph_tpu_wirepath_selftest() {
  // deterministic payload
  uint8_t data[4096];
  for (size_t i = 0; i < sizeof(data); ++i)
    data[i] = static_cast<uint8_t>((i * 131) ^ (i >> 3));

  // 1: crc_batch == chained single crc
  {
    const uint8_t* ptrs[3] = {data, data + 100, data + 1000};
    size_t lens[3] = {100, 900, 3096};
    int32_t starts[3] = {0, 2, 3};
    uint32_t seeds[2] = {0, 7};
    uint32_t out[2] = {0, 0};
    if (ceph_tpu_wire_crc_batch(ptrs, lens, 3, starts, 2, seeds, out) != 0)
      return 1;
    uint32_t want0 = ceph_tpu_crc32c(ceph_tpu_crc32c(0, data, 100),
                                     data + 100, 900);
    uint32_t want1 = ceph_tpu_crc32c(7, data + 1000, 3096);
    if (out[0] != want0 || out[1] != want1) return 2;
    // bad geometry: starts not ending at nseg / decreasing
    int32_t bad_starts[3] = {0, 2, 2};
    if (ceph_tpu_wire_crc_batch(ptrs, lens, 3, bad_starts, 2, seeds, out)
        != -EINVAL)
      return 3;
    int32_t dec_starts[3] = {0, 2, 1};
    if (ceph_tpu_wire_crc_batch(ptrs, lens, 1, dec_starts, 2, seeds, out)
        != -EINVAL)
      return 4;
  }

  // 2: gather round-trip + cap refusal
  {
    const uint8_t* ptrs[2] = {data, data + 2048};
    size_t lens[2] = {2048, 2048};
    uint8_t out[4096];
    if (ceph_tpu_wire_gather(ptrs, lens, 2, out, sizeof(out)) != 4096)
      return 5;
    if (std::memcmp(out, data, 4096) != 0) return 6;
    if (ceph_tpu_wire_gather(ptrs, lens, 2, out, 4095) != -EINVAL)
      return 7;  // truncated destination must refuse, not spill
  }

  // 3: fused copy+crc == memcmp + plain crc
  {
    uint8_t out[4096];
    std::memset(out, 0xAA, sizeof(out));
    uint32_t crc = ceph_tpu_wire_copy_crc32c(data, out, sizeof(data), 5);
    if (crc != ceph_tpu_crc32c(5, data, sizeof(data))) return 8;
    if (std::memcmp(out, data, sizeof(data)) != 0) return 9;
    if (ceph_tpu_wire_copy_crc32c(data, nullptr, 64, 0)
        != ceph_tpu_crc32c(0, data, 64))
      return 10;
  }

  // 4: scatter — benign reassembly, then the hostile battery
  {
    uint8_t dst[4096];
    std::memset(dst, 0, sizeof(dst));
    const uint8_t* srcs[2] = {data + 2048, data};
    size_t lens[2] = {2048, 2048};
    int64_t offs[2] = {2048, 0};  // arrival order != offset order
    uint32_t crcs[2] = {ceph_tpu_crc32c(0, data + 2048, 2048),
                        ceph_tpu_crc32c(0, data, 2048)};
    int32_t bad = -1;
    if (ceph_tpu_wire_scatter(srcs, lens, 2, offs, dst, sizeof(dst), crcs,
                              1, &bad) != 2 || bad != -1)
      return 11;
    if (std::memcmp(dst, data, sizeof(dst)) != 0) return 12;

    // corrupt offset: fragment 1 claims an offset overlapping frag 0
    int64_t overlap_offs[2] = {0, 1024};
    if (ceph_tpu_wire_scatter(srcs, lens, 2, overlap_offs, dst,
                              sizeof(dst), crcs, 1, &bad) != -EINVAL
        || bad != 1)
      return 13;

    // out-of-bounds tail: off + len > dst_len (truncated assembly)
    int64_t oob_offs[1] = {3000};
    if (ceph_tpu_wire_scatter(srcs, lens, 1, oob_offs, dst, sizeof(dst),
                              crcs, 1, &bad) != -EINVAL || bad != 0)
      return 14;

    // negative offset (corrupt i64 from the wire)
    int64_t neg_offs[1] = {-1};
    if (ceph_tpu_wire_scatter(srcs, lens, 1, neg_offs, dst, sizeof(dst),
                              crcs, 1, &bad) != -EINVAL || bad != 0)
      return 15;

    // crc mismatch: the corrupt fragment must not land a byte
    std::memset(dst, 0x55, sizeof(dst));
    uint32_t wrong[1] = {crcs[0] ^ 1};
    if (ceph_tpu_wire_scatter(srcs, lens, 1, offs, dst, sizeof(dst),
                              wrong, 1, &bad) != -EBADMSG || bad != 0)
      return 16;
    for (size_t i = 0; i < sizeof(dst); ++i)
      if (dst[i] != 0x55) return 17;

    // zero-length fragment at the boundary is legal (empty tail)
    size_t zlen[1] = {0};
    int64_t edge[1] = {static_cast<int64_t>(sizeof(dst))};
    uint32_t zcrc[1] = {0};
    if (ceph_tpu_wire_scatter(srcs, zlen, 1, edge, dst, sizeof(dst), zcrc,
                              1, &bad) != 1)
      return 18;
  }

  // 5: burst region verify — match, mismatch index, truncated bounds
  {
    int64_t offs[3] = {0, 512, 2048};
    size_t lens[3] = {512, 1536, 2048};
    uint32_t want[3] = {ceph_tpu_crc32c(0, data, 512),
                        ceph_tpu_crc32c(0, data + 512, 1536),
                        ceph_tpu_crc32c(0, data + 2048, 2048)};
    if (ceph_tpu_wire_verify_regions(data, sizeof(data), offs, lens, want,
                                     3) != -1)
      return 19;
    want[1] ^= 1;
    if (ceph_tpu_wire_verify_regions(data, sizeof(data), offs, lens, want,
                                     3) != 1)
      return 20;
    // region running past the buffer (truncated backlog) must refuse
    // before any read, not checksum out of bounds
    int64_t oob[1] = {4000};
    size_t oob_len[1] = {1000};
    if (ceph_tpu_wire_verify_regions(data, sizeof(data), oob, oob_len,
                                     want, 1) != -EINVAL)
      return 21;
  }

  // 6: the off-loop sender's own battery
  return sender_selftest(data, sizeof(data));
}

}  // extern "C"
