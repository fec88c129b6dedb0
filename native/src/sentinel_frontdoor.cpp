// Native TCP front door for the token server: the netty-pipeline analog
// (``NettyTransportServer.java:73-101``: LengthFieldBasedFrameDecoder →
// request decoder → handler → writeAndFlush) re-expressed as an epoll loop
// that decodes every request frame that asks for a verdict (the reference
// client's four single types and this wire's batch frames) STRAIGHT into a
// shared request arena and encodes verdict frames back without Python
// touching a single byte of the data plane. Python's role shrinks to one
// call per *device step*:
// ``wait_batch`` (blocks, GIL released) → run the jitted decision kernel →
// ``submit`` (verdict arrays in, frames out).
//
// Round-3 review: the asyncio front door served ~1/8 of the device kernel's
// ceiling — per-frame Python costs (frame splitting, queue hops, slicing,
// drain) dominated. This moves the whole per-frame path into C++.
//
// Data plane (handled here):
//   BATCH_FLOW (type 5): n×(flow_id:i64, count:i32, prio:u8) rows → arena
//   FLOW       (type 1): single request → arena as a 1-row frame
//   BATCH_PARAM_FLOW (type 27, codec rev 8): n:u16 k:u8 then n×(flow_id:i64,
//     count:i32, prio:u8, k×hash:i64) → the param arena, BATCH_FLOW's code
//     with a wider row. Flow rows and param rows never share a pull:
//     sn_fd_wait_any hands out whichever arena's head frame arrived first,
//     and of the param arena a run of frames with one k. Replies are
//     BATCH_FLOW's rows under type 27.
//   BATCH_CONCURRENT_ACQUIRE (type 28, codec rev 9): BATCH_FLOW's request
//     rows; BATCH_CONCURRENT_RELEASE (type 29): n:u16 then n×token_id:i64,
//     a row each with the id in the flow_id column. Both go to the
//     concurrency arena in arrival order, so a pull is a connection-ordered
//     run of release ids and acquire rows (sn_fd_wait_any: *k_out = -1) and
//     never mixes with flow or param rows. A body that is not exactly its
//     rows closes the connection, and so does an acquire frame of more rows
//     than a reply frame holds (kMaxAcquireRows: the reply's rows are the
//     wider, and its u16 length would wrap). Replies: an acquire's rows are FLOW's with
//     token_id:i64 behind them (sn_fd_submit's token_ids), a release's one
//     status byte a row.
//   PARAM_FLOW (type 2, the reference client's requestParamToken frame):
//     FLOW's body, n_params:u8, then n_params×hash:i64 → the param arena as
//     a one-row frame with k = n_params, stamped like any data frame. The
//     arena's rule stands (a pull is a run of frames with one k), so single
//     and batch frames of one k share a pull; each is answered in its own
//     layout: a single frame by FLOW's reply under type 2. A body shorter
//     than its values closes the connection as a runt frame does; a frame
//     with no value at all is not a row of the sketch and goes to the
//     control plane, which answers it as it always has.
//   CONCURRENT_ACQUIRE / CONCURRENT_RELEASE (types 3 and 4, the reference
//     client's): FLOW's fixed body (a release's flow_id slot carries its
//     token id) → the concurrency arena as a one-row frame, in arrival
//     order with the batch frames. Replies: FLOW's under the frame's own
//     type, an acquire's with token_id:i64 behind it.
// Control plane (forwarded to Python, rare): PING, a PARAM_FLOW frame with
//   no value, every other type (replication, moves, leases, shares, outcome
//   reports), plus open/close connection events so the host keeps its
//   ConnectionManager (namespace groups, idle sweep) exact. After every
//   push to the control queue the door rings the server's bell
//   (sn_bell_*, below; sn_fd_set_bell), outside its own mutex: the host's
//   one control thread sleeps on the bell, not on this door's cv, which
//   every data frame notifies.
//
// Threading: one IO thread owns epoll, all sockets, and all writes. Python
// threads call wait_batch/submit/control APIs guarded by a mutex + eventfd
// wakeups; they never touch a socket. Back-pressure: when the arena is
// full, a connection's remaining bytes stay in its read buffer and its
// EPOLLIN is parked until the next arena swap (the kernel's TCP window then
// back-pressures the client, like netty's autoRead=false).
//
// Spans (all on CLOCK_MONOTONIC, which is Python's time.monotonic_ns()): a
// data frame is stamped when the recv() that completed it returned
// (FrameMeta.rx_ns), the stamp rides the pull out (f_rx_ns) and comes back
// with the verdicts (sn_fd_submit), and the IO thread closes the span when
// send() has taken the last byte of the reply's buffer. Three histograms are
// counted where their span ends, on relaxed atomics (SpanHist, read by
// sn_fd_span_stats): door_in (rx -> the pull about to return to its
// caller), door_out (sn_fd_submit's entry -> last byte sent) and
// door_residence (rx -> last byte sent). A stamp of 0 counts nothing.

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <deque>
#include <mutex>
#include <string>
#include <chrono>
#include <thread>
#include <unordered_map>
#include <vector>

#if defined(_WIN32)
#define SN_EXPORT extern "C" __declspec(dllexport)
#else
#define SN_EXPORT extern "C" __attribute__((visibility("default")))
#endif

namespace {

constexpr int kHead = 5;           // xid:i32 + type:u8
constexpr int kReqRow = 13;        // flow_id:i64 + count:i32 + prio:u8
constexpr int kRspRow = 9;         // status:i8 + remaining:i32 + wait:i32
constexpr uint8_t kTypeFlow = 1;
constexpr uint8_t kTypeParamFlow = 2;
constexpr uint8_t kTypeConcAcquire = 3;
constexpr uint8_t kTypeConcRelease = 4;
constexpr uint8_t kTypeBatchFlow = 5;
constexpr uint8_t kTypeBatchParam = 27;
constexpr uint8_t kTypeBatchAcquire = 28;
constexpr uint8_t kTypeBatchRelease = 29;
constexpr int kAcquireRspRow = 17;  // kRspRow + token_id:i64
// an acquire's reply rows are wider than its request rows: the most rows
// whose reply still fits a frame (protocol.MAX_ACQUIRE_PER_FRAME, 3854)
constexpr int32_t kMaxAcquireRows = (65535 - kHead - 2) / kAcquireRspRow;
// one max-size param frame holds at most (65535 - 8) / 21 * 1 .. 8190 values
constexpr size_t kMaxFrameValues = 8192;
constexpr size_t kMaxFrame = 65535;
constexpr size_t kReadChunk = 1 << 16;
// control-plane queue bound: beyond this the sender's conn parks (same
// backpressure idiom as the data-plane arena) until Python drains to half
constexpr size_t kMaxControls = 8192;

struct Frontdoor;
void wake(Frontdoor *s);

// The control lane's bell: one a server, rung by every door of it (the TCP
// doors here, the shm door through sn_bell_ring) after a push to its
// control queue, waited on by the host's one control thread with the GIL
// released. The generation is what keeps a ring between the waiter's last
// empty look at the queues and its wait from being lost: a wait returns at
// once when the generation is not the one the waiter saw last.
struct Bell {
  std::mutex mu;
  std::condition_variable cv;
  uint64_t generation = 0;  // guarded by mu
};

inline uint16_t be16(const uint8_t *p) {
  return uint16_t(p[0]) << 8 | uint16_t(p[1]);
}
inline int32_t be32(const uint8_t *p) {
  return int32_t(uint32_t(p[0]) << 24 | uint32_t(p[1]) << 16 |
                 uint32_t(p[2]) << 8 | uint32_t(p[3]));
}
inline int64_t be64(const uint8_t *p) {
  uint64_t v = 0;
  for (int i = 0; i < 8; ++i) v = v << 8 | p[i];
  return int64_t(v);
}
inline void put16(uint8_t *p, uint16_t v) {
  p[0] = uint8_t(v >> 8);
  p[1] = uint8_t(v);
}
inline void put32(uint8_t *p, uint32_t v) {
  p[0] = uint8_t(v >> 24);
  p[1] = uint8_t(v >> 16);
  p[2] = uint8_t(v >> 8);
  p[3] = uint8_t(v);
}
// the reference client's one-request frames: one row, answered by a frame
// of FLOW's reply layout under the request's own type
inline bool is_single(uint8_t type) {
  return type == kTypeFlow || type == kTypeParamFlow ||
         type == kTypeConcAcquire || type == kTypeConcRelease;
}

// one outbound buffer: the wire bytes and, behind them in the same
// allocation, the rx_ns stamp of every frame it answers (verdict buffers of
// a caller that handed the stamps back; none otherwise)
struct OutBuf {
  std::string data;
  size_t wire = 0;        // wire bytes at the head of data
  int64_t submit_ns = 0;  // mono_ns() at the entry of sn_fd_submit
};

struct Conn {
  int fd = -1;
  uint32_t gen = 0;
  int64_t last_rx_ns = 0;     // CLOCK_MONOTONIC of the last recv() with bytes:
                              // the frames' rx stamp, and the idle sweep's
  std::vector<uint8_t> rbuf;  // unparsed inbound bytes
  size_t rpos = 0;            // parse cursor into rbuf
  std::deque<OutBuf> wq;      // queued outbound buffers
  size_t woff = 0;             // offset into wq.front()
  bool want_write = false;     // EPOLLOUT armed
  bool paused = false;         // EPOLLIN parked (arena full)
  bool open = true;
  std::string peer;
};

// one decoded data-plane frame awaiting verdicts
struct FrameMeta {
  int32_t fd;
  uint32_t gen;
  int32_t xid;
  int32_t n;       // requests in this frame
  uint8_t type;    // the wire's: a single type (kTypeFlow, kTypeParamFlow,
                   // kTypeConcAcquire, kTypeConcRelease: n = 1) or a batch
                   // type (kTypeBatchFlow, kTypeBatchParam,
                   // kTypeBatchAcquire, kTypeBatchRelease)
  uint8_t k;       // values per request (param frames; 0 otherwise)
  uint64_t seq;    // arrival order over the arenas
  int64_t rx_ns;   // mono_ns() after the recv() that read its last byte
};

// decoded rows awaiting a pull; the param arena also holds value hashes
struct Arena {
  std::vector<int64_t> flow_ids;
  std::vector<int32_t> counts;
  std::vector<uint8_t> prios;
  std::vector<int64_t> hashes;  // param arena only: n×k per frame, in order
  std::vector<FrameMeta> frames;
  size_t n_requests = 0;
  size_t n_hashes = 0;
};

// control event forwarded to Python
struct Control {
  int32_t kind;  // 0 = frame, 1 = open, 2 = close
  int32_t fd;
  uint32_t gen;
  std::string payload;  // frame bytes (kind 0) or peer address (kind 1)
  int64_t t_ns = 0;     // CLOCK_MONOTONIC when a frame (kind 0) was queued
};

// One span histogram: count, sum, max and a count per bucket of the bounds
// the host handed over (sn_fd_set_span_bounds: its own LatencyHistogram's,
// in ns; le-inclusive, the last bucket is the overflow; written once,
// before n_bounds). Relaxed atomics: each value is its own monotonic
// series, not one consistent snapshot.
constexpr int kMaxSpanBounds = 128;
struct SpanHist {
  int64_t bounds[kMaxSpanBounds] = {};
  std::atomic<int32_t> n_bounds{0};
  std::atomic<uint64_t> count{0}, sum_ns{0}, max_ns{0};
  std::atomic<uint64_t> buckets[kMaxSpanBounds + 1];
  SpanHist() {
    for (auto &b : buckets) b.store(0, std::memory_order_relaxed);
  }
};

enum SpanKind { kDoorIn = 0, kDoorOut = 1, kDoorResidence = 2 };

struct Frontdoor {
  int listen_fd = -1;
  int epoll_fd = -1;
  int wake_fd = -1;  // eventfd: submit()/stop()/swap wakeups
  uint16_t port = 0;
  std::thread io;
  std::atomic<bool> stopping{false};

  // transport echo (bench/tests only) — see sn_fd_echo_start
  std::thread echo;
  std::atomic<bool> echo_stop{false};

  std::mutex mu;
  std::condition_variable cv;  // signaled when arena/control non-empty

  // request arenas (guarded by mu): flow rows, param rows, and the rows
  // of the concurrency frames (acquire rows; a release row is its token id
  // in the flow_id column)
  size_t cap;
  size_t hash_cap;
  Arena flow, param, conc;
  uint64_t next_seq = 0;
  bool arena_was_full = false;

  std::deque<Control> controls;  // guarded by mu
  bool controls_was_full = false;  // guarded by mu
  // rung after every push to controls, outside mu (sn_fd_set_bell); null
  // until the host sets one
  std::atomic<Bell *> bell{nullptr};

  // listener parking after accept failure (EMFILE etc): level-triggered
  // epoll would otherwise spin the IO thread at 100% until an fd frees
  bool listener_parked = false;   // IO thread only
  int64_t listener_parked_ms = 0;  // IO thread only

  // outbound handoff: Python-side submit() parks encoded frames here; the
  // IO thread moves them onto the conn write queues (guarded by mu)
  std::vector<std::pair<std::pair<int32_t, uint32_t>, OutBuf>> outbox;

  std::unordered_map<int, Conn> conns;  // IO thread only

  SpanHist spans[3];  // by SpanKind, as sn_fd_span_stats numbers them

  // stats (relaxed)
  std::atomic<uint64_t> frames_in{0}, requests_in{0}, bytes_in{0},
      bytes_out{0};
  // single PARAM_FLOW frames decoded into the param arena
  // (sn_fd_param_single_frames)
  std::atomic<uint64_t> param_single_in{0};

  // idle reaping (ScanIdleConnectionTask analog), 0 = disabled
  std::atomic<int64_t> idle_ttl_ms{0};
  int64_t last_sweep_ms = 0;

  explicit Frontdoor(size_t arena_cap)
      : cap(arena_cap), hash_cap(std::max(arena_cap, kMaxFrameValues)) {
    for (Arena *a : {&flow, &param, &conc}) {
      a->flow_ids.resize(cap);
      a->counts.resize(cap);
      a->prios.resize(cap);
      a->frames.reserve(4096);
    }
    param.hashes.resize(hash_cap);
  }
};

int64_t mono_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return int64_t(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
}

int64_t mono_ms() { return mono_ns() / 1000000; }

void bell_ring(Bell *b) {
  {
    std::lock_guard<std::mutex> lk(b->mu);
    ++b->generation;
  }
  b->cv.notify_all();
}

// a control event was queued (call with s->mu released)
void ring_bell(Frontdoor *s) {
  if (Bell *b = s->bell.load(std::memory_order_acquire)) bell_ring(b);
}

// n spans of one length end here (negative: a clock that went back; skipped)
void span_count(SpanHist &h, int64_t ns, uint64_t n) {
  if (ns < 0) return;
  const int64_t *b = h.bounds;
  int32_t nb = h.n_bounds.load(std::memory_order_acquire);
  size_t i = size_t(std::lower_bound(b, b + nb, ns) - b);
  h.buckets[i].fetch_add(n, std::memory_order_relaxed);
  h.count.fetch_add(n, std::memory_order_relaxed);
  h.sum_ns.fetch_add(uint64_t(ns) * n, std::memory_order_relaxed);
  uint64_t seen = h.max_ns.load(std::memory_order_relaxed);
  while (uint64_t(ns) > seen &&
         !h.max_ns.compare_exchange_weak(seen, uint64_t(ns),
                                         std::memory_order_relaxed)) {
  }
}

// door_in of the frames a pull just took: one search per run of frames
// that one recv() completed
void count_door_in(Frontdoor *s, const int64_t *rx, int32_t n_frames,
                   int64_t wake_ns) {
  for (int32_t i = 0; i < n_frames;) {
    int32_t j = i + 1;
    while (j < n_frames && rx[j] == rx[i]) ++j;
    if (rx[i])
      span_count(s->spans[kDoorIn], wake_ns - rx[i], uint64_t(j - i));
    i = j;
  }
}

// send() took the last byte of a verdict buffer: door_out and
// door_residence of every stamped frame it answered
void close_spans(Frontdoor *s, const OutBuf &buf) {
  size_t n = (buf.data.size() - buf.wire) / sizeof(int64_t);
  const char *tail = buf.data.data() + buf.wire;
  int64_t done = mono_ns();
  for (size_t i = 0; i < n;) {
    int64_t rx, nxt;
    memcpy(&rx, tail + i * sizeof(int64_t), sizeof(rx));
    size_t j = i + 1;
    for (; j < n; ++j) {
      memcpy(&nxt, tail + j * sizeof(int64_t), sizeof(nxt));
      if (nxt != rx) break;
    }
    if (rx) {
      span_count(s->spans[kDoorOut], done - buf.submit_ns, uint64_t(j - i));
      span_count(s->spans[kDoorResidence], done - rx, uint64_t(j - i));
    }
    i = j;
  }
}

void epoll_mod(Frontdoor *s, Conn &c) {
  epoll_event ev{};
  ev.events = (c.paused ? 0u : EPOLLIN) | (c.want_write ? EPOLLOUT : 0u);
  ev.data.fd = c.fd;
  epoll_ctl(s->epoll_fd, EPOLL_CTL_MOD, c.fd, &ev);
}

void close_conn(Frontdoor *s, Conn &c) {
  if (!c.open) return;
  c.open = false;
  epoll_ctl(s->epoll_fd, EPOLL_CTL_DEL, c.fd, nullptr);
  ::close(c.fd);
  {
    std::lock_guard<std::mutex> lk(s->mu);
    s->controls.push_back({2, c.fd, c.gen, std::string()});
  }
  s->cv.notify_all();
  ring_bell(s);
}

// Parse as many frames as the arena allows; returns false if the conn
// should be closed (protocol error). Every data frame parsed is stamped
// c.last_rx_ns: the recv() that brought its last byte (a conn parked on a
// full arena reads nothing more, so the stamp holds for what it buffered).
bool parse_frames(Frontdoor *s, Conn &c) {
  bool notify = false;
  bool control = false;  // a control frame was queued: ring the bell
  bool wake_self = false;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    for (;;) {
      size_t avail = c.rbuf.size() - c.rpos;
      if (avail < 2) break;
      const uint8_t *p = c.rbuf.data() + c.rpos;
      size_t flen = be16(p);
      if (flen < size_t(kHead)) return false;  // runt frame
      if (avail < 2 + flen) break;
      const uint8_t *payload = p + 2;
      uint8_t type = payload[4];
      // the values of a single PARAM_FLOW frame (FLOW's body, n_params:u8,
      // then the hashes); a body shorter than they are closes the conn
      int32_t single_k = 0;
      if (type == kTypeParamFlow) {
        if (flen < size_t(kHead + kReqRow + 1)) return false;
        single_k = payload[kHead + kReqRow];
        if (flen < size_t(kHead + kReqRow + 1) + 8 * size_t(single_k))
          return false;
      }
      // data plane: every type that asks for verdicts, but a PARAM_FLOW
      // frame with no value (no row of the sketch: the control plane's)
      if (type == kTypeBatchFlow || type == kTypeBatchParam ||
          type == kTypeBatchAcquire || type == kTypeBatchRelease ||
          (is_single(type) && (type != kTypeParamFlow || single_k > 0))) {
        int32_t n;
        int32_t k = 0;  // values per request: param frames only
        const uint8_t *rows;
        if (type == kTypeBatchAcquire || type == kTypeBatchRelease) {
          // rev 9: the body is exactly its rows (runt or over-long: closed)
          if (flen < size_t(kHead + 2)) return false;
          n = be16(payload + kHead);
          size_t row = type == kTypeBatchRelease ? 8 : size_t(kReqRow);
          if (flen != size_t(kHead + 2) + size_t(n) * row) return false;
          // more rows than a reply frame can answer: closed as well
          if (type == kTypeBatchAcquire && n > kMaxAcquireRows) return false;
          rows = payload + kHead + 2;
        } else if (type == kTypeBatchParam) {
          if (flen < size_t(kHead + 3)) return false;
          n = be16(payload + kHead);
          k = payload[kHead + 2];
          if (n > 0 && k == 0) return false;  // rows without a value
          if (flen < size_t(kHead + 3) + size_t(n) * (kReqRow + 8 * size_t(k)))
            return false;
          rows = payload + kHead + 3;
        } else if (type == kTypeBatchFlow) {
          if (flen < size_t(kHead + 2)) return false;
          n = be16(payload + kHead);
          if (flen < size_t(kHead + 2) + size_t(n) * kReqRow) return false;
          rows = payload + kHead + 2;
        } else {
          // a single frame: FLOW's body (a CONCURRENT_RELEASE's flow_id
          // slot carries its token id), a PARAM_FLOW's values behind it
          if (flen < size_t(kHead + kReqRow)) return false;
          n = 1;
          k = single_k;
          rows = payload + kHead;
        }
        int32_t xid = be32(payload);
        if (n == 0) {
          // empty batch frame: answer inline with an empty verdict frame —
          // wait_batch only wakes for n_requests > 0, so queuing a
          // zero-row FrameMeta would strand it (and its sender) forever
          OutBuf rsp;
          rsp.data.assign(size_t(2 + kHead + 2), '\0');
          rsp.wire = rsp.data.size();
          uint8_t *q = reinterpret_cast<uint8_t *>(&rsp.data[0]);
          put16(q, uint16_t(kHead + 2));
          put32(q + 2, uint32_t(xid));
          q[6] = type;
          put16(q + 7, 0);
          s->outbox.emplace_back(std::make_pair(c.fd, uint32_t(c.gen)),
                                 std::move(rsp));
          s->frames_in.fetch_add(1, std::memory_order_relaxed);
          c.rpos += 2 + flen;
          wake_self = true;
          continue;
        }
        const bool conc = type == kTypeBatchAcquire ||
                          type == kTypeBatchRelease ||
                          type == kTypeConcAcquire || type == kTypeConcRelease;
        Arena &a = k ? s->param : conc ? s->conc : s->flow;
        if (a.n_requests + size_t(n) > s->cap ||
            a.n_hashes + size_t(n) * size_t(k) > s->hash_cap) {
          // arena full: park this conn; bytes stay buffered
          c.paused = true;
          s->arena_was_full = true;
          epoll_mod(s, c);
          break;
        }
        size_t base = a.n_requests;
        int64_t *hv = k ? a.hashes.data() + a.n_hashes : nullptr;
        if (type == kTypeBatchRelease) {  // a row is its token id
          for (int32_t i = 0; i < n; ++i, rows += 8) {
            a.flow_ids[base + i] = be64(rows);
            a.counts[base + i] = 0;
            a.prios[base + i] = 0;
          }
        } else {
          for (int32_t i = 0; i < n; ++i) {
            a.flow_ids[base + i] = be64(rows);
            a.counts[base + i] = be32(rows + 8);
            a.prios[base + i] = rows[12];
            rows += kReqRow;
            if (type == kTypeParamFlow) ++rows;  // n_params
            for (int32_t j = 0; j < k; ++j, rows += 8) *hv++ = be64(rows);
          }
        }
        a.n_requests += size_t(n);
        a.n_hashes += size_t(n) * size_t(k);
        a.frames.push_back({c.fd, c.gen, xid, n, type, uint8_t(k),
                            s->next_seq++, c.last_rx_ns});
        s->frames_in.fetch_add(1, std::memory_order_relaxed);
        s->requests_in.fetch_add(uint64_t(n), std::memory_order_relaxed);
        if (type == kTypeParamFlow)
          s->param_single_in.fetch_add(1, std::memory_order_relaxed);
        notify = true;
      } else {
        // control plane: hand the raw payload to Python. Bounded: a peer
        // streaming control frames faster than the Python control thread
        // drains parks (like the data-plane arena) instead of growing the
        // deque without bound.
        if (s->controls.size() >= kMaxControls) {
          c.paused = true;
          s->controls_was_full = true;
          epoll_mod(s, c);
          break;
        }
        s->controls.push_back(
            {0, c.fd, c.gen,
             std::string(reinterpret_cast<const char *>(payload), flen),
             mono_ns()});
        notify = true;
        control = true;
      }
      c.rpos += 2 + flen;
    }
  }
  if (c.rpos > 0 && c.rpos == c.rbuf.size()) {
    c.rbuf.clear();
    c.rpos = 0;
  } else if (c.rpos > (1 << 20)) {
    c.rbuf.erase(c.rbuf.begin(), c.rbuf.begin() + c.rpos);
    c.rpos = 0;
  }
  if (notify) s->cv.notify_all();
  if (control) ring_bell(s);
  // schedule an outbox drain for inline responses (parse runs on the IO
  // thread; the eventfd write makes the next epoll_wait return at once)
  if (wake_self) wake(s);
  return true;
}

void flush_writes(Frontdoor *s, Conn &c) {
  while (!c.wq.empty()) {
    const OutBuf &buf = c.wq.front();
    ssize_t w = ::send(c.fd, buf.data.data() + c.woff, buf.wire - c.woff,
                       MSG_NOSIGNAL);
    if (w > 0) {
      s->bytes_out.fetch_add(uint64_t(w), std::memory_order_relaxed);
      c.woff += size_t(w);
      if (c.woff == buf.wire) {
        if (buf.data.size() > buf.wire) close_spans(s, buf);
        c.wq.pop_front();
        c.woff = 0;
      }
      continue;
    }
    if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      if (!c.want_write) {
        c.want_write = true;
        epoll_mod(s, c);
      }
      return;
    }
    close_conn(s, c);
    return;
  }
  if (c.want_write) {
    c.want_write = false;
    epoll_mod(s, c);
  }
}

void io_loop(Frontdoor *s) {
  epoll_event evs[256];
  // per-loop recv scratch (IO thread only); heap, not stack — 64 KiB
  // would dominate the thread's stack frame
  std::vector<uint8_t> scratch_vec(kReadChunk);
  uint8_t *scratch = scratch_vec.data();
  while (!s->stopping.load(std::memory_order_acquire)) {
    int n = epoll_wait(s->epoll_fd, evs, 256, 100);
    if (n < 0) {
      if (errno == EINTR) continue;
      break;
    }
    bool drain_outbox = false;
    for (int i = 0; i < n; ++i) {
      int fd = evs[i].data.fd;
      if (fd == s->listen_fd) {
        for (;;) {
          sockaddr_in addr{};
          socklen_t alen = sizeof(addr);
          int cfd = accept4(s->listen_fd, reinterpret_cast<sockaddr *>(&addr),
                            &alen, SOCK_NONBLOCK);
          if (cfd < 0) {
            if (errno == ECONNABORTED) continue;  // peer gone; try next
            if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR) {
              // fd exhaustion (EMFILE/ENFILE) or kernel pressure: the
              // pending backlog keeps the level-triggered listen fd
              // readable, so park it for ~1s instead of spinning
              epoll_ctl(s->epoll_fd, EPOLL_CTL_DEL, s->listen_fd, nullptr);
              s->listener_parked = true;
              s->listener_parked_ms = mono_ms();
            }
            break;
          }
          int one = 1;
          setsockopt(cfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
          Conn &c = s->conns[cfd];
          c = Conn{};
          c.fd = cfd;
          c.last_rx_ns = mono_ns();
          static std::atomic<uint32_t> gen_counter{1};
          c.gen = gen_counter.fetch_add(1);
          char ip[64];
          inet_ntop(AF_INET, &addr.sin_addr, ip, sizeof(ip));
          c.peer = std::string(ip) + ":" + std::to_string(ntohs(addr.sin_port));
          epoll_event ev{};
          ev.events = EPOLLIN;
          ev.data.fd = cfd;
          epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, cfd, &ev);
          {
            std::lock_guard<std::mutex> lk(s->mu);
            s->controls.push_back({1, cfd, c.gen, c.peer});
          }
          s->cv.notify_all();
          ring_bell(s);
        }
        continue;
      }
      if (fd == s->wake_fd) {
        uint64_t tok;
        while (read(s->wake_fd, &tok, sizeof(tok)) > 0) {
        }
        drain_outbox = true;
        continue;
      }
      auto it = s->conns.find(fd);
      if (it == s->conns.end()) continue;
      Conn &c = it->second;
      if (evs[i].events & (EPOLLHUP | EPOLLERR)) {
        close_conn(s, c);
        s->conns.erase(it);
        continue;
      }
      if (evs[i].events & EPOLLOUT) flush_writes(s, c);
      if (!c.open) {
        s->conns.erase(it);
        continue;
      }
      if (evs[i].events & EPOLLIN) {
        bool closed = false;
        for (;;) {
          // recv into the shared scratch then append only what arrived:
          // resizing rbuf by kReadChunk up front would value-initialize
          // (memset) 64 KiB per recv on the serving hot path
          ssize_t r = ::recv(fd, scratch, kReadChunk, 0);
          if (r > 0) {
            c.rbuf.insert(c.rbuf.end(), scratch, scratch + size_t(r));
            // the one clock read of this recv(): the rx stamp of every
            // frame it completes, and the idle sweep's mark
            c.last_rx_ns = mono_ns();
            s->bytes_in.fetch_add(uint64_t(r), std::memory_order_relaxed);
            if (!parse_frames(s, c)) {
              closed = true;
              close_conn(s, c);
              break;
            }
            if (size_t(r) < kReadChunk || c.paused) break;
          } else if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
            break;
          } else {
            closed = true;
            close_conn(s, c);
            break;
          }
        }
        if (closed) {
          s->conns.erase(it);
          continue;
        }
      }
    }
    // re-arm a parked listener after ~1s (the epoll_wait timeout gives a
    // natural tick even when no events fire)
    if (s->listener_parked && mono_ms() - s->listener_parked_ms >= 1000) {
      epoll_event ev{};
      ev.events = EPOLLIN;
      ev.data.fd = s->listen_fd;
      epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, s->listen_fd, &ev);
      s->listener_parked = false;
    }
    // idle sweep: close connections quiet past the ttl (the reference's
    // ScanIdleConnectionTask); checked at most once a second
    int64_t ttl = s->idle_ttl_ms.load(std::memory_order_relaxed);
    if (ttl > 0) {
      int64_t now = mono_ms();
      if (now - s->last_sweep_ms >= 1000) {
        s->last_sweep_ms = now;
        std::vector<int> stale;
        for (auto &kv : s->conns)
          if (kv.second.open && now - kv.second.last_rx_ns / 1000000 > ttl)
            stale.push_back(kv.first);
        for (int fd : stale) {
          auto it = s->conns.find(fd);
          if (it != s->conns.end()) {
            close_conn(s, it->second);
            s->conns.erase(it);
          }
        }
      }
    }
    // move submitted frames onto conn write queues + flush; also resume
    // parked conns after an arena swap
    if (drain_outbox) {
      std::vector<std::pair<std::pair<int32_t, uint32_t>, OutBuf>> out;
      bool resume;
      {
        std::lock_guard<std::mutex> lk(s->mu);
        out.swap(s->outbox);
        bool arena_ok = s->arena_was_full && s->flow.n_requests < s->cap &&
                        s->param.n_requests < s->cap &&
                        s->conc.n_requests < s->cap &&
                        s->param.n_hashes < s->hash_cap;
        if (arena_ok) s->arena_was_full = false;
        bool ctrl_ok =
            s->controls_was_full && s->controls.size() < kMaxControls / 2;
        if (ctrl_ok) s->controls_was_full = false;
        resume = arena_ok || ctrl_ok;
      }
      for (auto &item : out) {
        auto it = s->conns.find(item.first.first);
        if (it == s->conns.end() || it->second.gen != item.first.second ||
            !it->second.open)
          continue;
        if (item.second.data.empty()) {  // zero-length = host-requested close
          close_conn(s, it->second);
          s->conns.erase(it);
          continue;
        }
        it->second.wq.push_back(std::move(item.second));
        flush_writes(s, it->second);
        // flush_writes closes on send error; drop the map entry too or the
        // rbuf/wq buffers linger until the kernel reuses this fd number
        if (!it->second.open) s->conns.erase(it);
      }
      if (resume) {
        for (auto it = s->conns.begin(); it != s->conns.end();) {
          Conn &c = it->second;
          if (c.paused && c.open) {
            c.paused = false;
            epoll_mod(s, c);
            if (!parse_frames(s, c)) {
              close_conn(s, c);
              it = s->conns.erase(it);
              continue;
            }
          }
          ++it;
        }
      }
    }
  }
  // shutdown: close everything
  for (auto &kv : s->conns) {
    if (kv.second.open) {
      ::close(kv.second.fd);
      kv.second.open = false;
    }
  }
  s->conns.clear();
}

void wake(Frontdoor *s) {
  uint64_t one = 1;
  ssize_t unused = write(s->wake_fd, &one, sizeof(one));
  (void)unused;
}

}  // namespace

// ---------------------------------------------------------------------------
// C API (ctypes)
// ---------------------------------------------------------------------------

SN_EXPORT void *sn_fd_create(const char *host, int32_t port,
                             int32_t arena_cap) {
  auto *s = new (std::nothrow) Frontdoor(size_t(arena_cap));
  if (!s) return nullptr;
  s->listen_fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
  int one = 1;
  setsockopt(s->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  // SO_REUSEPORT: N Frontdoor instances may bind the same port, and the
  // kernel spreads accepted connections across their listen queues — the
  // multi-door intake sharding the Python server builds on. Unconditional:
  // harmless for a single door, and gating it behind a new export would
  // break ctypes signature resolution against stale .so builds.
  setsockopt(s->listen_fd, SOL_SOCKET, SO_REUSEPORT, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(uint16_t(port));
  addr.sin_addr.s_addr = host && *host ? inet_addr(host) : htonl(INADDR_ANY);
  if (bind(s->listen_fd, reinterpret_cast<sockaddr *>(&addr), sizeof(addr)) <
          0 ||
      listen(s->listen_fd, 1024) < 0) {
    ::close(s->listen_fd);
    delete s;
    return nullptr;
  }
  socklen_t alen = sizeof(addr);
  getsockname(s->listen_fd, reinterpret_cast<sockaddr *>(&addr), &alen);
  s->port = ntohs(addr.sin_port);
  s->epoll_fd = epoll_create1(0);
  s->wake_fd = eventfd(0, EFD_NONBLOCK);
  if (s->epoll_fd < 0 || s->wake_fd < 0) {
    // fd exhaustion: without this check the handle looks live but the IO
    // loop's first epoll_wait would fail and exit silently — clients
    // would connect into the kernel backlog and hang forever
    if (s->epoll_fd >= 0) ::close(s->epoll_fd);
    if (s->wake_fd >= 0) ::close(s->wake_fd);
    ::close(s->listen_fd);
    delete s;
    return nullptr;
  }
  epoll_event ev{};
  ev.events = EPOLLIN;
  ev.data.fd = s->listen_fd;
  epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, s->listen_fd, &ev);
  ev.events = EPOLLIN;
  ev.data.fd = s->wake_fd;
  epoll_ctl(s->epoll_fd, EPOLL_CTL_ADD, s->wake_fd, &ev);
  s->io = std::thread(io_loop, s);
  return s;
}

SN_EXPORT int32_t sn_fd_port(void *h) {
  return int32_t(static_cast<Frontdoor *>(h)->port);
}

SN_EXPORT void sn_fd_stop(void *h) {
  auto *s = static_cast<Frontdoor *>(h);
  s->stopping.store(true, std::memory_order_release);
  if (s->echo.joinable()) {
    s->echo_stop.store(true, std::memory_order_release);
    s->echo.join();
  }
  wake(s);
  if (s->io.joinable()) s->io.join();
  // listen/epoll fds are IO-thread-only, closable once it has joined (and
  // closing the listener now releases the port for an immediate rebind).
  // wake_fd stays open until destroy: dispatcher/control threads may still
  // be inside submit()/send() whose wake() writes it — closing here could
  // land those 8 bytes in a recycled fd. Post-stop writes to the live
  // eventfd are harmless (nobody reads; the counter just accumulates).
  ::close(s->listen_fd);
  ::close(s->epoll_fd);
  s->listen_fd = s->epoll_fd = -1;
  s->cv.notify_all();
}

SN_EXPORT void sn_fd_destroy(void *h) {
  auto *s = static_cast<Frontdoor *>(h);
  if (s->wake_fd >= 0) ::close(s->wake_fd);
  delete s;
}

namespace {

// Take whole frames off the head of one arena into the caller's arrays
// (called with mu held; the caller unlocks). Of the param arena a run of
// frames with the head frame's k, so the pull's hashes are one [n, k]
// array. Returns the request count; 0 when not even one frame fits.
int32_t take_frames(Arena &a, int64_t *ids, int32_t *counts,
                    uint8_t *prios, int64_t *hashes, int32_t max_n,
                    int32_t max_hashes, int32_t *f_fd, int32_t *f_gen,
                    int32_t *f_xid, int32_t *f_n, uint8_t *f_type,
                    int64_t *f_rx_ns, int32_t max_frames,
                    int32_t *n_frames_out) {
  size_t take_req = 0, n_take = 0, take_hashes = 0;
  const uint8_t k = a.frames.empty() ? 0 : a.frames.front().k;
  for (const FrameMeta &fm : a.frames) {
    if (n_take + 1 > size_t(max_frames) || fm.k != k ||
        take_req + size_t(fm.n) > size_t(max_n) ||
        take_hashes + size_t(fm.n) * k > size_t(max_hashes))
      break;
    take_req += size_t(fm.n);
    take_hashes += size_t(fm.n) * k;
    n_take += 1;
  }
  *n_frames_out = int32_t(n_take);
  if (n_take == 0) return 0;  // caller buffers too small (misuse)
  memcpy(ids, a.flow_ids.data(), take_req * sizeof(int64_t));
  memcpy(counts, a.counts.data(), take_req * sizeof(int32_t));
  memcpy(prios, a.prios.data(), take_req);
  if (take_hashes)
    memcpy(hashes, a.hashes.data(), take_hashes * sizeof(int64_t));
  for (size_t i = 0; i < n_take; ++i) {
    f_fd[i] = a.frames[i].fd;
    f_gen[i] = int32_t(a.frames[i].gen);
    f_xid[i] = a.frames[i].xid;
    f_n[i] = a.frames[i].n;
    f_type[i] = a.frames[i].type;
    if (f_rx_ns) f_rx_ns[i] = a.frames[i].rx_ns;
  }
  // compact the remainder (rare: only when a burst exceeds caller capacity)
  size_t rest_req = a.n_requests - take_req;
  if (rest_req > 0) {
    memmove(a.flow_ids.data(), a.flow_ids.data() + take_req,
            rest_req * sizeof(int64_t));
    memmove(a.counts.data(), a.counts.data() + take_req,
            rest_req * sizeof(int32_t));
    memmove(a.prios.data(), a.prios.data() + take_req, rest_req);
  }
  size_t rest_hashes = a.n_hashes - take_hashes;
  if (rest_hashes > 0)
    memmove(a.hashes.data(), a.hashes.data() + take_hashes,
            rest_hashes * sizeof(int64_t));
  a.frames.erase(a.frames.begin(), a.frames.begin() + n_take);
  a.n_requests = rest_req;
  a.n_hashes = rest_hashes;
  return int32_t(take_req);
}

// The end of a pull, mu released: the wake stamp and the frames' door_in.
void pulled(Frontdoor *s, const int64_t *f_rx_ns, int32_t n_frames,
            int64_t *wake_ns_out) {
  if (!f_rx_ns && !wake_ns_out) return;
  int64_t wake_ns = mono_ns();
  if (f_rx_ns) count_door_in(s, f_rx_ns, n_frames, wake_ns);
  if (wake_ns_out) *wake_ns_out = wake_ns;
}

}  // namespace

// Block until flow requests are queued (or timeout/stop). Copies up
// to max_n FLOW / BATCH_FLOW requests + their frame list into the caller's
// arrays and resets the arena. Returns the request count (0 on
// timeout/stop); *n_frames_out receives the frame count. Whole frames only —
// a frame never splits across two batches. Param frames are not seen here:
// a host that serves them pulls with sn_fd_wait_any. f_rx_ns (may be null)
// receives each frame's rx stamp, to be handed back to sn_fd_submit, and
// *wake_ns_out (may be null) mono_ns() just before the return: what the
// caller's own clock reads later than that, it spent getting back to run
// (from Python: the ctypes return and the wait for the GIL).
SN_EXPORT int32_t sn_fd_wait_batch(void *h, int32_t timeout_ms, int64_t *ids,
                                   int32_t *counts, uint8_t *prios,
                                   int32_t max_n, int32_t *f_fd,
                                   int32_t *f_gen, int32_t *f_xid,
                                   int32_t *f_n, uint8_t *f_type,
                                   int64_t *f_rx_ns, int32_t max_frames,
                                   int32_t *n_frames_out,
                                   int64_t *wake_ns_out) {
  auto *s = static_cast<Frontdoor *>(h);
  std::unique_lock<std::mutex> lk(s->mu);
  if (s->flow.n_requests == 0) {
    s->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms), [s] {
      return s->flow.n_requests > 0 ||
             s->stopping.load(std::memory_order_acquire);
    });
  }
  *n_frames_out = 0;
  if (s->flow.n_requests == 0) return 0;
  int32_t n = take_frames(s->flow, ids, counts, prios, nullptr, max_n, 0,
                          f_fd, f_gen, f_xid, f_n, f_type, f_rx_ns,
                          max_frames, n_frames_out);
  bool resume = s->arena_was_full;
  lk.unlock();
  if (resume) wake(s);  // unpark conns the full arena throttled
  pulled(s, f_rx_ns, *n_frames_out, wake_ns_out);
  return n;
}

// sn_fd_wait_batch for a host that serves every kind of rows: one pull is
// flow rows (*k_out = 0), param rows (*k_out = values per request, their
// hashes in ``hashes`` as [n, k]) or the rows of concurrency frames (*k_out
// = -1: f_type says which frames are releases, types 4 and 29, whose rows
// carry a token id in ``ids``), never two kinds: the arena whose head frame
// arrived first is served. A pull holds single and batch frames alike, and
// f_type tells sn_fd_submit each one's reply layout. max_hashes bounds the
// values of one pull.
SN_EXPORT int32_t sn_fd_wait_any(void *h, int32_t timeout_ms, int64_t *ids,
                                 int32_t *counts, uint8_t *prios,
                                 int64_t *hashes, int32_t max_n,
                                 int32_t max_hashes, int32_t *f_fd,
                                 int32_t *f_gen, int32_t *f_xid, int32_t *f_n,
                                 uint8_t *f_type, int64_t *f_rx_ns,
                                 int32_t max_frames, int32_t *n_frames_out,
                                 int32_t *k_out, int64_t *wake_ns_out) {
  auto *s = static_cast<Frontdoor *>(h);
  std::unique_lock<std::mutex> lk(s->mu);
  auto queued = [s] {
    return s->flow.n_requests + s->param.n_requests + s->conc.n_requests > 0;
  };
  if (!queued()) {
    s->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms), [s, &queued] {
      return queued() || s->stopping.load(std::memory_order_acquire);
    });
  }
  *n_frames_out = 0;
  *k_out = 0;
  if (!queued()) return 0;
  // the arena whose head frame arrived first
  Arena *first = nullptr;
  for (Arena *c : {&s->flow, &s->param, &s->conc})
    if (c->n_requests > 0 &&
        (!first || c->frames.front().seq < first->frames.front().seq))
      first = c;
  Arena &a = *first;
  if (first == &s->param) *k_out = a.frames.front().k;
  if (first == &s->conc) *k_out = -1;
  int32_t n = take_frames(a, ids, counts, prios, hashes, max_n, max_hashes,
                          f_fd, f_gen, f_xid, f_n, f_type, f_rx_ns,
                          max_frames, n_frames_out);
  bool resume = s->arena_was_full;
  lk.unlock();
  if (resume) wake(s);  // unpark conns the full arena throttled
  pulled(s, f_rx_ns, *n_frames_out, wake_ns_out);
  return n;
}

// Encode + enqueue verdict frames for the frames returned by wait_batch.
// status/remaining/wait are request-order arrays covering all frames
// back-to-back (same order wait_batch returned them). Scatter encode:
// consecutive frames for the SAME connection are laid into ONE contiguous
// per-writer buffer — one allocation, one outbox item, and (usually) one
// send() per connection instead of one per frame. Pipelined clients queue
// many frames per socket, so fused groups collapse to a handful of writes.
// f_rx_ns (may be null) hands the frames' rx stamps back: they ride each
// buffer, behind its wire bytes, to the send() that closes their spans.
SN_EXPORT void sn_fd_submit(void *h, int32_t n_frames, const int32_t *f_fd,
                            const int32_t *f_gen, const int32_t *f_xid,
                            const int32_t *f_n, const uint8_t *f_type,
                            const int64_t *f_rx_ns, const int8_t *status,
                            const int32_t *remaining,
                            const int32_t *wait_ms,
                            const int64_t *token_ids) {
  auto *s = static_cast<Frontdoor *>(h);
  // bytes of one reply row of a batch frame, by its type
  auto rsp_row = [](uint8_t type) -> size_t {
    return type == kTypeBatchAcquire   ? size_t(kAcquireRspRow)
           : type == kTypeBatchRelease ? size_t(1)
                                       : size_t(kRspRow);
  };
  // bytes of a single frame's reply body: FLOW's, and behind it the token
  // id of a CONCURRENT_ACQUIRE
  auto single_rsp = [](uint8_t type) -> size_t {
    return size_t(kRspRow) + (type == kTypeConcAcquire ? 8 : 0);
  };
  const int64_t submit_ns = f_rx_ns ? mono_ns() : 0;
  std::vector<std::pair<std::pair<int32_t, uint32_t>, OutBuf>> staged;
  size_t off = 0;
  for (int32_t i = 0; i < n_frames;) {
    // run of consecutive frames bound for one connection
    int32_t run_end = i + 1;
    while (run_end < n_frames && f_fd[run_end] == f_fd[i] &&
           f_gen[run_end] == f_gen[i])
      ++run_end;
    size_t total = 0;
    for (int32_t k = i; k < run_end; ++k)
      total += !is_single(f_type[k])
                   ? 2 + size_t(kHead) + 2 + size_t(f_n[k]) * rsp_row(f_type[k])
                   : 2 + size_t(kHead) + single_rsp(f_type[k]);
    OutBuf buf;
    buf.wire = total;
    buf.submit_ns = submit_ns;
    size_t stamps = f_rx_ns ? size_t(run_end - i) * sizeof(int64_t) : 0;
    buf.data.resize(total + stamps);
    if (stamps) memcpy(&buf.data[total], f_rx_ns + i, stamps);
    uint8_t *p = reinterpret_cast<uint8_t *>(&buf.data[0]);
    for (int32_t k = i; k < run_end; ++k) {
      int32_t n = f_n[k];
      if (!is_single(f_type[k])) {  // the rows of a batch frame
        const size_t rsz = rsp_row(f_type[k]);
        size_t payload = size_t(kHead) + 2 + size_t(n) * rsz;
        put16(p, uint16_t(payload));
        put32(p + 2, uint32_t(f_xid[k]));
        p[6] = f_type[k];
        put16(p + 7, uint16_t(n));
        uint8_t *row = p + 9;
        for (int32_t j = 0; j < n; ++j, row += rsz) {
          row[0] = uint8_t(status[off + size_t(j)]);
          if (rsz == 1) continue;  // a release's row is its status
          put32(row + 1, uint32_t(remaining[off + size_t(j)]));
          put32(row + 5, uint32_t(wait_ms[off + size_t(j)]));
          if (rsz == size_t(kAcquireRspRow)) {
            uint64_t id = token_ids ? uint64_t(token_ids[off + size_t(j)]) : 0;
            put32(row + 9, uint32_t(id >> 32));
            put32(row + 13, uint32_t(id));
          }
        }
        p += 2 + payload;
      } else {  // a single frame's response, under the request's type
        size_t payload = size_t(kHead) + single_rsp(f_type[k]);
        put16(p, uint16_t(payload));
        put32(p + 2, uint32_t(f_xid[k]));
        p[6] = f_type[k];
        p[7] = uint8_t(status[off]);
        put32(p + 8, uint32_t(remaining[off]));
        put32(p + 12, uint32_t(wait_ms[off]));
        if (f_type[k] == kTypeConcAcquire) {
          uint64_t id = token_ids ? uint64_t(token_ids[off]) : 0;
          put32(p + 16, uint32_t(id >> 32));
          put32(p + 20, uint32_t(id));
        }
        p += 2 + payload;
      }
      off += size_t(n);
    }
    staged.emplace_back(
        std::make_pair(f_fd[i], uint32_t(f_gen[i])), std::move(buf));
    i = run_end;
  }
  {
    std::lock_guard<std::mutex> lk(s->mu);
    for (auto &item : staged) s->outbox.push_back(std::move(item));
  }
  wake(s);
}

// Enqueue an arbitrary pre-encoded frame (control-plane responses: PING,
// lease, share and replication replies, pushes — Python encodes those).
SN_EXPORT void sn_fd_send(void *h, int32_t fd, int32_t gen,
                          const uint8_t *data, int32_t len) {
  auto *s = static_cast<Frontdoor *>(h);
  OutBuf buf;
  buf.data.assign(reinterpret_cast<const char *>(data), size_t(len));
  buf.wire = buf.data.size();
  {
    std::lock_guard<std::mutex> lk(s->mu);
    s->outbox.emplace_back(std::make_pair(fd, uint32_t(gen)), std::move(buf));
  }
  wake(s);
}

// Pop one control event. Returns its kind (0 frame, 1 open, 2 close) or -1
// if none. payload_out receives up to max_len bytes; *len_out the true size;
// *t_ns_out the CLOCK_MONOTONIC ns (Python's time.monotonic_ns()) at which
// the IO thread queued a frame, 0 for an open or close event.
SN_EXPORT int32_t sn_fd_next_control(void *h, int32_t *fd_out,
                                     int32_t *gen_out, uint8_t *payload_out,
                                     int32_t max_len, int32_t *len_out,
                                     int64_t *t_ns_out) {
  auto *s = static_cast<Frontdoor *>(h);
  bool unpark;
  Control c;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    if (s->controls.empty()) return -1;
    c = std::move(s->controls.front());
    s->controls.pop_front();
    unpark = s->controls_was_full && s->controls.size() < kMaxControls / 2;
  }
  // drained below half after a full queue: nudge the IO thread so conns
  // parked by the control-plane cap resume reading
  if (unpark) wake(s);
  *fd_out = c.fd;
  *gen_out = int32_t(c.gen);
  int32_t n = int32_t(c.payload.size());
  *len_out = n;
  *t_ns_out = c.t_ns;
  if (n > 0 && n <= max_len) memcpy(payload_out, c.payload.data(), size_t(n));
  return c.kind;
}

SN_EXPORT void sn_fd_set_idle_ttl(void *h, int64_t ttl_ms) {
  static_cast<Frontdoor *>(h)->idle_ttl_ms.store(ttl_ms,
                                                 std::memory_order_relaxed);
}

// The control lane's bell (struct Bell). sn_bell_wait blocks until the
// generation is no longer ``seen`` or ``timeout_ms`` passed, and returns the
// generation it found: the caller's next ``seen``. A wait that a ring ends
// (or finds rung already) sleeps ``settle_ms`` more before it returns, so
// that the waiter does not touch the interpreter in the moment the frame
// arrived (server_native._control_loop says why); a time-out does not, nor
// does ``timeout_ms`` 0, which only reads. A bell outlives every door it
// was handed to.
SN_EXPORT void *sn_bell_new() { return new (std::nothrow) Bell(); }

SN_EXPORT void sn_bell_free(void *b) { delete static_cast<Bell *>(b); }

SN_EXPORT void sn_bell_ring(void *b) { bell_ring(static_cast<Bell *>(b)); }

SN_EXPORT uint64_t sn_bell_wait(void *h, uint64_t seen, int32_t timeout_ms,
                                int32_t settle_ms) {
  auto *b = static_cast<Bell *>(h);
  std::unique_lock<std::mutex> lk(b->mu);
  bool rung = b->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                             [&] { return b->generation != seen; });
  if (rung && timeout_ms > 0 && settle_ms > 0) {
    lk.unlock();
    std::this_thread::sleep_for(std::chrono::milliseconds(settle_ms));
    lk.lock();
  }
  return b->generation;
}

// Ring ``bell`` (sn_bell_new's, or null for none) after every push to this
// door's control queue.
SN_EXPORT void sn_fd_set_bell(void *h, void *bell) {
  static_cast<Frontdoor *>(h)->bell.store(static_cast<Bell *>(bell),
                                          std::memory_order_release);
}

// Close one connection from the host side (e.g. an operator kick).
SN_EXPORT void sn_fd_close_conn(void *h, int32_t fd, int32_t gen) {
  auto *s = static_cast<Frontdoor *>(h);
  // executed on the IO thread via the outbox: an empty frame with a close
  // marker would complicate the protocol — instead reuse the outbox with a
  // zero-length payload the drain loop interprets as "close".
  {
    std::lock_guard<std::mutex> lk(s->mu);
    s->outbox.emplace_back(std::make_pair(fd, uint32_t(gen)), OutBuf());
  }
  wake(s);
}

// Each value is an independently monotonic relaxed atomic; the four loads
// are NOT one consistent snapshot (the IO thread may bump frames_in between
// loads). Documented contract: consumers treat each counter as its own
// monotonic series and clamp cross-counter deltas at zero.
SN_EXPORT void sn_fd_stats(void *h, uint64_t *out4) {
  auto *s = static_cast<Frontdoor *>(h);
  out4[0] = s->frames_in.load(std::memory_order_relaxed);
  out4[1] = s->requests_in.load(std::memory_order_relaxed);
  out4[2] = s->bytes_in.load(std::memory_order_relaxed);
  out4[3] = s->bytes_out.load(std::memory_order_relaxed);
}

// Single PARAM_FLOW frames decoded into the param arena (relaxed, like
// sn_fd_stats). What the control loop still answers of them it counts
// itself (ServerMetrics.param_control_frames_total).
SN_EXPORT uint64_t sn_fd_param_single_frames(void *h) {
  return static_cast<Frontdoor *>(h)->param_single_in.load(
      std::memory_order_relaxed);
}

// The bucket bounds of span histogram ``which`` (0 door_in, 1 door_out,
// 2 door_residence), in ns, ascending: the host's own histogram's
// (LatencyHistogram), handed over once, before traffic. Spans counted
// before the call all land in bucket 0.
SN_EXPORT void sn_fd_set_span_bounds(void *h, int32_t which,
                                     const int64_t *bounds_ns, int32_t n) {
  SpanHist &sh = static_cast<Frontdoor *>(h)->spans[which];
  n = std::min(n, int32_t(kMaxSpanBounds));
  memcpy(sh.bounds, bounds_ns, size_t(n) * sizeof(int64_t));
  sh.n_bounds.store(n, std::memory_order_release);
}

// Span histogram ``which``: count, sum_ns, max_ns, then n_bounds + 1 bucket
// counts (not cumulative; the last is the overflow). Returns the values
// written (n_bounds + 4), or what that would be when max_out is too small.
// Like sn_fd_stats, no one snapshot.
SN_EXPORT int32_t sn_fd_span_stats(void *h, int32_t which, uint64_t *out,
                                   int32_t max_out) {
  SpanHist &sh = static_cast<Frontdoor *>(h)->spans[which];
  int32_t nb = sh.n_bounds.load(std::memory_order_acquire);
  if (nb + 4 > max_out) return nb + 4;
  *out++ = sh.count.load(std::memory_order_relaxed);
  *out++ = sh.sum_ns.load(std::memory_order_relaxed);
  *out++ = sh.max_ns.load(std::memory_order_relaxed);
  for (int32_t i = 0; i <= nb; ++i)
    *out++ = sh.buckets[i].load(std::memory_order_relaxed);
  return nb + 4;
}

// --- transport echo (bench/tests only) -----------------------------------

// Pure-C echo loop: wait_batch -> all-GRANTED submit, no Python in the
// round trip. The TCP mirror of sn_shm_echo_start, so the two doors'
// per-frame host cost can be compared transport-against-transport with an
// identical serving loop behind each.
SN_EXPORT void sn_fd_echo_start(void *h) {
  auto *s = static_cast<Frontdoor *>(h);
  if (s->echo.joinable()) return;
  s->echo_stop.store(false, std::memory_order_release);
  s->echo = std::thread([s, h] {
    constexpr int32_t kMaxN = 65536, kMaxF = 4096;
    std::vector<int64_t> ids(kMaxN);
    std::vector<int32_t> counts(kMaxN), f_fd(kMaxF), f_gen(kMaxF),
        f_xid(kMaxF), f_n(kMaxF), rem(kMaxN), wait(kMaxN, 0);
    std::vector<uint8_t> prios(kMaxN), f_type(kMaxF);
    std::vector<int64_t> f_rx(kMaxF);
    std::vector<int8_t> status(kMaxN, 0);  // GRANTED
    int32_t nf = 0;
    while (!s->echo_stop.load(std::memory_order_acquire)) {
      int32_t n = sn_fd_wait_batch(h, 5, ids.data(), counts.data(),
                                   prios.data(), kMaxN, f_fd.data(),
                                   f_gen.data(), f_xid.data(), f_n.data(),
                                   f_type.data(), f_rx.data(), kMaxF, &nf,
                                   nullptr);
      if (n <= 0) continue;
      for (int32_t i = 0; i < n; ++i) rem[i] = counts[i];
      sn_fd_submit(h, nf, f_fd.data(), f_gen.data(), f_xid.data(),
                   f_n.data(), f_type.data(), f_rx.data(), status.data(),
                   rem.data(), wait.data(), nullptr);
    }
  });
}

SN_EXPORT void sn_fd_echo_stop(void *h) {
  auto *s = static_cast<Frontdoor *>(h);
  if (!s->echo.joinable()) return;
  s->echo_stop.store(true, std::memory_order_release);
  s->echo.join();
}
