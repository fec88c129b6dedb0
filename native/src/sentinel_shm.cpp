// Shared-memory ring front door for co-located sidecar clients.
//
// Transport shape: one mmap'd segment file per client under the door's
// directory, holding a lock-free SPSC request ring and a response ring.
// Slots are cache-line aligned and carry the SAME wire-rev frame payloads
// the TCP door speaks (everything after the 2-byte length prefix; a u32
// slot len field plays the prefix's role), so the Python codecs and the
// StagingPool decode-into path are reused verbatim on both sides.
//
// Commit protocol (torn-writer safety): the producer memcpys the payload
// into the slot, stores the len word, then publishes with a release store
// of the ring tail. The consumer acquires the tail before touching the
// slot, so a writer killed or parked mid-slot simply never publishes —
// the server can never observe a torn frame. A *hostile* publish (bogus
// len, malformed batch geometry) is caught by the same validation the TCP
// parser applies and drops the whole segment, mirroring a closed conn.
//
// Doorbell: the steady state is zero syscalls per batch. The server
// poller spins over all segments for spin_us after the last progress,
// then advertises SLEEPING in the control segment (seq_cst), re-checks
// every ring (Dekker handshake against the client's publish + fence +
// state load), and futex-waits on a shared doorbell word. Clients only
// pay the futex_wake syscall when they actually observed SLEEPING.
// Responses mirror this per segment: the client spins briefly, then
// parks on its per-segment doorbell which the server rings only when
// the client advertised it went to sleep.
//
// Liveness: segment headers carry the client pid; the poller sweeps
// attached segments every ~500ms and reclaims (close event -> munmap ->
// unlink) any whose pid is gone, plus any whose client set the closing
// flag. The control segment carries the server pid so clients can tell
// a dead server from an idle one.

#if !defined(__linux__)
// The shm door is Linux-only (futex, /proc-free pid probes via kill(0)).
// Non-Linux builds still get the TCP door; lib.py gates on the exports.
#else

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include <dirent.h>
#include <errno.h>
#include <fcntl.h>
#include <linux/futex.h>
#include <signal.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <sys/syscall.h>
#include <sys/types.h>
#include <time.h>
#include <unistd.h>

#define SN_EXPORT extern "C" __attribute__((visibility("default")))

// the control lane's bell (sentinel_frontdoor.cpp)
extern "C" void sn_bell_ring(void *bell);

namespace {

constexpr int kHead = 5;     // xid:i32 + type:u8
constexpr int kReqRow = 13;  // flow_id:i64 + count:i32 + prio:u8
constexpr int kRspRow = 9;   // status:i8 + remaining:i32 + wait:i32
constexpr uint8_t kTypeFlow = 1;
constexpr uint8_t kTypeBatchFlow = 5;
constexpr size_t kMaxFrame = 65535;
constexpr size_t kMaxControls = 8192;

constexpr uint64_t kSegMagic = 0x534E2D52494E4731ULL;  // "SN-RING1"
constexpr uint64_t kCtlMagic = 0x534E2D52494E4743ULL;  // "SN-RINGC"
constexpr uint32_t kVersion = 1;
constexpr size_t kHdrBytes = 4096;   // header page of both file kinds
constexpr size_t kSlotHdr = 64;      // u32 len + pad; payload starts aligned

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

int64_t mono_ms() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return int64_t(ts.tv_sec) * 1000 + ts.tv_nsec / 1000000;
}
int64_t mono_us() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return int64_t(ts.tv_sec) * 1000000 + ts.tv_nsec / 1000;
}

// Shared (cross-process) futex — FUTEX_PRIVATE_FLAG must NOT be set.
int futex_wait(std::atomic<uint32_t> *addr, uint32_t expected,
               int64_t timeout_ms) {
  timespec ts;
  ts.tv_sec = timeout_ms / 1000;
  ts.tv_nsec = (timeout_ms % 1000) * 1000000;
  return int(syscall(SYS_futex, reinterpret_cast<uint32_t *>(addr),
                     FUTEX_WAIT, expected, &ts, nullptr, 0));
}
void futex_wake(std::atomic<uint32_t> *addr, int n) {
  syscall(SYS_futex, reinterpret_cast<uint32_t *>(addr), FUTEX_WAKE, n,
          nullptr, nullptr, 0);
}

// --- shared file layouts -------------------------------------------------

struct SegHeader {
  uint64_t magic;
  uint32_t version;
  uint32_t slot_size;  // bytes per slot incl kSlotHdr; multiple of 64
  uint32_t n_slots;    // power of two
  uint32_t client_pid;
  std::atomic<uint32_t> client_flag;  // 1 = ready, 2 = closing
  std::atomic<uint32_t> server_flag;  // 0 = unseen, 1 = attached, 2 = dropped
  alignas(64) std::atomic<uint64_t> req_tail;  // client produces
  alignas(64) std::atomic<uint64_t> req_head;  // server consumes
  alignas(64) std::atomic<uint64_t> rsp_tail;  // server produces
  alignas(64) std::atomic<uint64_t> rsp_head;  // client consumes
  alignas(64) std::atomic<uint32_t> client_sleep;     // 1 = parked on futex
  alignas(64) std::atomic<uint32_t> client_doorbell;  // futex word
};
static_assert(sizeof(SegHeader) <= kHdrBytes, "segment header fits a page");

struct CtlHeader {
  uint64_t magic;
  uint32_t version;
  uint32_t server_pid;
  alignas(64) std::atomic<uint32_t> server_sleep;  // 1 = poller parked
  alignas(64) std::atomic<uint32_t> doorbell;      // futex word
  alignas(64) std::atomic<uint64_t> dir_epoch;     // bumped on segment create
};
static_assert(sizeof(CtlHeader) <= kHdrBytes, "ctl header fits a page");

// --- server side ---------------------------------------------------------

struct FrameMeta {
  int32_t fd;  // segment id
  uint32_t gen;
  int32_t xid;
  int32_t n;
  uint8_t type;
};

struct Control {
  int32_t kind;  // 0 = frame, 1 = open, 2 = close
  int32_t fd;
  uint32_t gen;
  std::string payload;
  int64_t t_ns = 0;  // CLOCK_MONOTONIC when a frame (kind 0) was queued
};

struct Segment {
  int32_t id = 0;
  uint32_t gen = 0;
  std::string path;  // for unlink on reclaim
  std::string name;  // dirent name, dedup key
  uint8_t *base = nullptr;
  size_t map_len = 0;
  SegHeader *hdr = nullptr;
  uint8_t *req_ring = nullptr;
  uint8_t *rsp_ring = nullptr;
  uint32_t slot_size = 0;
  uint32_t n_slots = 0;
  uint64_t mask = 0;
  uint32_t pid = 0;
  std::mutex w_mu;        // response-ring producer (reply lanes + control)
  std::atomic<bool> dead{false};

  ~Segment() {
    if (base) munmap(base, map_len);
  }
};

struct ShmDoor {
  std::string dir;
  std::string ctl_path;
  int ctl_fd = -1;
  CtlHeader *ctl = nullptr;
  uint32_t spin_us = 0;

  std::thread poller;
  std::thread echo;
  std::atomic<bool> stopping{false};
  std::atomic<bool> echo_stop{false};

  std::mutex mu;               // arena + controls (mirrors the TCP door)
  std::condition_variable cv;
  size_t cap;
  std::vector<int64_t> flow_ids;
  std::vector<int32_t> counts;
  std::vector<uint8_t> prios;
  std::vector<FrameMeta> frames;
  size_t n_requests = 0;
  bool arena_was_full = false;
  std::deque<Control> controls;
  bool controls_was_full = false;
  // the server's control bell (sentinel_frontdoor.cpp's sn_bell_*), rung
  // after every push to controls, outside mu; null until sn_shm_set_bell
  std::atomic<void *> bell{nullptr};

  std::mutex segs_mu;  // the map only; segments pin via shared_ptr
  std::unordered_map<int32_t, std::shared_ptr<Segment>> segs;
  // names ever attached this generation of the file (avoid re-attach races
  // between unlink and the next scan)
  std::unordered_map<std::string, uint32_t> seen_names;
  int32_t next_id = 1;
  uint32_t next_gen = 1;

  uint64_t scanned_epoch = 0;
  int64_t last_scan_ms = 0;
  int64_t last_sweep_ms = 0;

  // poller could not drain (arena or controls full): wait_batch /
  // next_control ring the doorbell after freeing space so a sleeping
  // poller resumes immediately instead of on the futex timeout
  std::atomic<bool> stalled{false};

  // stats — each counter is independently monotonic (relaxed); readers
  // must not assume the set is a consistent snapshot (see sn_shm_stats)
  std::atomic<uint64_t> frames_in{0}, requests_in{0}, bytes_in{0},
      bytes_out{0}, polls{0}, doorbells{0}, ring_full{0};

  explicit ShmDoor(size_t arena_cap) : cap(arena_cap) {
    flow_ids.resize(cap);
    counts.resize(cap);
    prios.resize(cap);
    frames.reserve(4096);
  }
};

// a control event was queued (call with s->mu released)
void ring_bell(ShmDoor *s) {
  if (void *b = s->bell.load(std::memory_order_acquire)) sn_bell_ring(b);
}

void ring_server_doorbell(ShmDoor *s) {
  if (!s->ctl) return;
  if (s->ctl->server_sleep.load(std::memory_order_seq_cst) == 1) {
    s->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
    futex_wake(&s->ctl->doorbell, 1);
  }
}

bool pid_alive(uint32_t pid) {
  if (pid == 0) return false;
  return kill(pid_t(pid), 0) == 0 || errno != ESRCH;
}

// Publish one pre-encoded frame payload into a segment's response ring.
// Returns false when the ring stayed full past the bounded wait (client
// not draining) — the frame is dropped and counted; the client's own
// timeout machinery recovers, same as a TCP conn with a full socket.
bool rsp_push(ShmDoor *s, Segment *seg, const uint8_t *payload, size_t len) {
  if (seg->dead.load(std::memory_order_relaxed)) return false;
  if (len > size_t(seg->slot_size) - kSlotHdr) return false;  // cannot fit
  uint64_t tail = seg->hdr->rsp_tail.load(std::memory_order_relaxed);
  int64_t deadline = mono_us() + 2000;  // bounded: 2ms then drop
  for (;;) {
    uint64_t head = seg->hdr->rsp_head.load(std::memory_order_acquire);
    if (tail - head < seg->n_slots) break;
    if (mono_us() >= deadline || seg->dead.load(std::memory_order_relaxed)) {
      s->ring_full.fetch_add(1, std::memory_order_relaxed);
      return false;
    }
    sched_yield();
  }
  uint8_t *slot = seg->rsp_ring + size_t(tail & seg->mask) * seg->slot_size;
  memcpy(slot + kSlotHdr, payload, len);
  *reinterpret_cast<uint32_t *>(slot) = uint32_t(len);
  seg->hdr->rsp_tail.store(tail + 1, std::memory_order_release);
  s->bytes_out.fetch_add(len, std::memory_order_relaxed);
  return true;
}

// Ring the client's doorbell if it advertised sleeping (Dekker pairing
// with the client's publish-check in shm_client recv).
void rsp_doorbell(Segment *seg) {
  std::atomic_thread_fence(std::memory_order_seq_cst);
  if (seg->hdr->client_sleep.load(std::memory_order_seq_cst) == 1) {
    seg->hdr->client_doorbell.fetch_add(1, std::memory_order_seq_cst);
    futex_wake(&seg->hdr->client_doorbell, 1);
  }
}

// Detach + reclaim a segment: close event to Python, mark dropped so a
// live client sees the server let go, unlink the file. The mapping stays
// valid until the last shared_ptr (a racing submit) releases it.
void drop_segment(ShmDoor *s, const std::shared_ptr<Segment> &seg) {
  bool expected = false;
  if (!seg->dead.compare_exchange_strong(expected, true)) return;
  seg->hdr->server_flag.store(2, std::memory_order_release);
  rsp_doorbell(seg.get());  // unpark a blocked recv so it sees the drop
  unlink(seg->path.c_str());
  {
    std::lock_guard<std::mutex> lk(s->segs_mu);
    s->segs.erase(seg->id);
  }
  {
    std::lock_guard<std::mutex> lk(s->mu);
    s->controls.push_back({2, seg->id, seg->gen, std::string()});
  }
  s->cv.notify_all();
  ring_bell(s);
}

// Validate + attach one segment file. Returns true if attached.
bool attach_segment(ShmDoor *s, const std::string &name) {
  std::string path = s->dir + "/" + name;
  int fd = open(path.c_str(), O_RDWR | O_CLOEXEC);
  if (fd < 0) return false;
  struct stat st;
  if (fstat(fd, &st) != 0 || size_t(st.st_size) < kHdrBytes + 2 * 128) {
    close(fd);
    return false;
  }
  size_t map_len = size_t(st.st_size);
  void *base = mmap(nullptr, map_len, PROT_READ | PROT_WRITE, MAP_SHARED,
                    fd, 0);
  close(fd);  // the mapping keeps the inode pinned
  if (base == MAP_FAILED) return false;
  auto *hdr = reinterpret_cast<SegHeader *>(base);
  bool ok = hdr->magic == kSegMagic && hdr->version == kVersion &&
            hdr->slot_size >= 128 && hdr->slot_size % 64 == 0 &&
            hdr->n_slots >= 2 && hdr->n_slots <= 65536 &&
            (hdr->n_slots & (hdr->n_slots - 1)) == 0 &&
            map_len == kHdrBytes +
                           2 * size_t(hdr->slot_size) * size_t(hdr->n_slots) &&
            hdr->client_flag.load(std::memory_order_acquire) == 1;
  if (ok && !pid_alive(hdr->client_pid)) {
    // orphan from a dead client (or a dead prior server's era): reclaim
    munmap(base, map_len);
    unlink(path.c_str());
    return false;
  }
  if (!ok) {
    munmap(base, map_len);
    return false;
  }
  auto seg = std::make_shared<Segment>();
  seg->path = path;
  seg->name = name;
  seg->base = reinterpret_cast<uint8_t *>(base);
  seg->map_len = map_len;
  seg->hdr = hdr;
  seg->slot_size = hdr->slot_size;
  seg->n_slots = hdr->n_slots;
  seg->mask = uint64_t(hdr->n_slots) - 1;
  seg->req_ring = seg->base + kHdrBytes;
  seg->rsp_ring = seg->req_ring + size_t(seg->slot_size) * seg->n_slots;
  seg->pid = hdr->client_pid;
  {
    std::lock_guard<std::mutex> lk(s->segs_mu);
    seg->id = s->next_id++;
    seg->gen = s->next_gen++;
    s->segs[seg->id] = seg;
  }
  hdr->server_flag.store(1, std::memory_order_release);
  {
    std::lock_guard<std::mutex> lk(s->mu);
    std::string peer = "shm:" + std::to_string(hdr->client_pid) + ":" + name;
    s->controls.push_back({1, seg->id, seg->gen, std::move(peer)});
  }
  s->cv.notify_all();
  ring_bell(s);
  return true;
}

void scan_dir(ShmDoor *s) {
  DIR *d = opendir(s->dir.c_str());
  if (!d) return;
  while (dirent *e = readdir(d)) {
    if (strncmp(e->d_name, "seg-", 4) != 0) continue;
    size_t len = strlen(e->d_name);
    if (len < 10 || strcmp(e->d_name + len - 5, ".ring") != 0) continue;
    std::string name(e->d_name);
    {
      std::lock_guard<std::mutex> lk(s->segs_mu);
      auto it = s->seen_names.find(name);
      if (it != s->seen_names.end()) continue;
      s->seen_names.emplace(name, 1);
    }
    if (!attach_segment(s, name)) {
      // not attachable (partially initialized, dead, or invalid): allow a
      // later scan to retry unless it was reclaimed/unlinked above
      std::lock_guard<std::mutex> lk(s->segs_mu);
      s->seen_names.erase(name);
    }
  }
  closedir(d);
}

// Drain one segment's request ring into the arena. Mirrors parse_frames;
// returns true if any progress was made. On protocol violation the whole
// segment is dropped (the TCP analog closes the conn).
bool drain_segment(ShmDoor *s, const std::shared_ptr<Segment> &seg) {
  uint64_t tail = seg->hdr->req_tail.load(std::memory_order_acquire);
  uint64_t head = seg->hdr->req_head.load(std::memory_order_relaxed);
  if (head == tail) {
    if (seg->hdr->client_flag.load(std::memory_order_acquire) == 2) {
      drop_segment(s, seg);
      return true;
    }
    return false;
  }
  bool progress = false;
  bool notify = false;
  bool control = false;  // a control frame was queued: ring the bell
  bool violated = false;
  std::vector<std::pair<int32_t, std::string>> inline_rsps;  // empty batches
  {
    std::lock_guard<std::mutex> lk(s->mu);
    while (head != tail) {
      const uint8_t *slot =
          seg->req_ring + size_t(head & seg->mask) * seg->slot_size;
      size_t flen = *reinterpret_cast<const uint32_t *>(slot);
      const uint8_t *payload = slot + kSlotHdr;
      if (flen < size_t(kHead) || flen > kMaxFrame ||
          flen > size_t(seg->slot_size) - kSlotHdr) {
        violated = true;  // hostile publish: kill the segment
        break;
      }
      uint8_t type = payload[4];
      if (type == kTypeBatchFlow || type == kTypeFlow) {
        int32_t n;
        const uint8_t *rows;
        if (type == kTypeBatchFlow) {
          if (flen < size_t(kHead + 2)) {
            violated = true;
            break;
          }
          n = be16(payload + kHead);
          if (flen < size_t(kHead + 2) + size_t(n) * kReqRow) {
            violated = true;
            break;
          }
          rows = payload + kHead + 2;
        } else {
          if (flen < size_t(kHead + kReqRow)) {
            violated = true;
            break;
          }
          n = 1;
          rows = payload + kHead;
        }
        int32_t xid = be32(payload);
        if (n == 0) {
          // empty BATCH_FLOW: answer inline (wait_batch only wakes for
          // n_requests > 0 — same rule as the TCP door)
          std::string rsp(size_t(kHead + 2), '\0');
          uint8_t *q = reinterpret_cast<uint8_t *>(&rsp[0]);
          put32(q, uint32_t(xid));
          q[4] = kTypeBatchFlow;
          put16(q + 5, 0);
          inline_rsps.emplace_back(xid, std::move(rsp));
          s->frames_in.fetch_add(1, std::memory_order_relaxed);
          s->bytes_in.fetch_add(flen, std::memory_order_relaxed);
          ++head;
          progress = true;
          continue;
        }
        if (s->n_requests + size_t(n) > s->cap) {
          s->arena_was_full = true;
          s->stalled.store(true, std::memory_order_release);
          break;  // leave in ring; client backpressures on ring-full
        }
        size_t base = s->n_requests;
        for (int32_t i = 0; i < n; ++i, rows += kReqRow) {
          s->flow_ids[base + i] = be64(rows);
          s->counts[base + i] = be32(rows + 8);
          s->prios[base + i] = rows[12];
        }
        s->n_requests += size_t(n);
        s->frames.push_back({seg->id, seg->gen, xid, n, type});
        s->frames_in.fetch_add(1, std::memory_order_relaxed);
        s->requests_in.fetch_add(uint64_t(n), std::memory_order_relaxed);
        s->bytes_in.fetch_add(flen, std::memory_order_relaxed);
        notify = true;
      } else {
        if (s->controls.size() >= kMaxControls) {
          s->controls_was_full = true;
          s->stalled.store(true, std::memory_order_release);
          break;  // leave in ring until Python drains
        }
        s->controls.push_back(
            {0, seg->id, seg->gen,
             std::string(reinterpret_cast<const char *>(payload), flen),
             mono_us() * 1000});
        s->bytes_in.fetch_add(flen, std::memory_order_relaxed);
        notify = true;
        control = true;
      }
      ++head;
      progress = true;
    }
  }
  if (progress) seg->hdr->req_head.store(head, std::memory_order_release);
  if (notify) s->cv.notify_all();
  if (control) ring_bell(s);
  if (!inline_rsps.empty()) {
    std::lock_guard<std::mutex> lk(seg->w_mu);
    for (auto &pr : inline_rsps)
      rsp_push(s, seg.get(),
               reinterpret_cast<const uint8_t *>(pr.second.data()),
               pr.second.size());
    rsp_doorbell(seg.get());
  }
  if (violated) drop_segment(s, seg);
  return progress;
}

void poller_loop(ShmDoor *s) {
  int64_t spin_until = mono_us() + s->spin_us;
  for (;;) {
    if (s->stopping.load(std::memory_order_acquire)) return;
    s->polls.fetch_add(1, std::memory_order_relaxed);

    uint64_t epoch = s->ctl->dir_epoch.load(std::memory_order_acquire);
    int64_t now_ms = mono_ms();
    if (epoch != s->scanned_epoch || now_ms - s->last_scan_ms >= 200) {
      s->scanned_epoch = epoch;
      s->last_scan_ms = now_ms;
      scan_dir(s);
    }

    std::vector<std::shared_ptr<Segment>> snap;
    {
      std::lock_guard<std::mutex> lk(s->segs_mu);
      snap.reserve(s->segs.size());
      for (auto &kv : s->segs) snap.push_back(kv.second);
    }
    bool sweep = now_ms - s->last_sweep_ms >= 500;
    if (sweep) s->last_sweep_ms = now_ms;
    bool progress = false;
    for (auto &seg : snap) {
      if (sweep && !pid_alive(seg->pid)) {
        drop_segment(s, seg);
        continue;
      }
      progress |= drain_segment(s, seg);
    }
    // stalled = a drain left frames in a ring because the arena or the
    // control queue was full: spinning cannot make progress, so go
    // straight to the doorbell (wait_batch/next_control ring it after
    // freeing space)
    bool stalled_now = s->stalled.exchange(false, std::memory_order_acq_rel);
    if (progress && !stalled_now) {
      spin_until = mono_us() + s->spin_us;
      continue;
    }
    if (!stalled_now && mono_us() < spin_until) {
#if defined(__x86_64__) || defined(__i386__)
      __builtin_ia32_pause();
#else
      std::this_thread::yield();
#endif
      continue;
    }

    // spin budget exhausted: advertise sleeping, re-check (Dekker), park
    uint32_t bell = s->ctl->doorbell.load(std::memory_order_seq_cst);
    s->ctl->server_sleep.store(1, std::memory_order_seq_cst);
    std::atomic_thread_fence(std::memory_order_seq_cst);
    bool pending =
        s->ctl->dir_epoch.load(std::memory_order_seq_cst) != s->scanned_epoch;
    if (!pending && stalled_now) {
      // only actionable work is Python draining the arena/controls; the
      // bell value was read before this check, so a drain that raced us
      // either shows up here or bumps the bell and EAGAINs the wait
      std::lock_guard<std::mutex> lk(s->mu);
      pending = s->n_requests < s->cap && s->controls.size() < kMaxControls;
    } else if (!pending) {
      for (auto &seg : snap) {
        if (seg->hdr->req_tail.load(std::memory_order_seq_cst) !=
            seg->hdr->req_head.load(std::memory_order_relaxed)) {
          pending = true;
          break;
        }
      }
    }
    if (!pending && !s->stopping.load(std::memory_order_acquire)) {
      // bounded park: the 50ms timeout caps segment-discovery and pid-
      // sweep latency when no client ever rings
      int rc = futex_wait(&s->ctl->doorbell, bell, 50);
      if (rc == 0) s->doorbells.fetch_add(1, std::memory_order_relaxed);
    }
    s->ctl->server_sleep.store(0, std::memory_order_seq_cst);
    spin_until = mono_us() + s->spin_us;
  }
}

std::shared_ptr<Segment> find_segment(ShmDoor *s, int32_t id, uint32_t gen) {
  std::lock_guard<std::mutex> lk(s->segs_mu);
  auto it = s->segs.find(id);
  if (it == s->segs.end() || it->second->gen != gen) return nullptr;
  return it->second;
}

// --- client side ---------------------------------------------------------

struct ShmClient {
  std::string seg_path;
  uint8_t *base = nullptr;
  size_t map_len = 0;
  SegHeader *hdr = nullptr;
  uint8_t *req_ring = nullptr;
  uint8_t *rsp_ring = nullptr;
  uint32_t slot_size = 0;
  uint32_t n_slots = 0;
  uint64_t mask = 0;
  uint32_t spin_us = 50;

  std::string ctl_path;
  CtlHeader *ctl = nullptr;
  size_t ctl_len = 0;

  bool unlink_on_destroy = true;

  ~ShmClient() {
    if (base) munmap(base, map_len);
    if (ctl) munmap(reinterpret_cast<void *>(ctl), ctl_len);
  }
};

bool server_gone(ShmClient *c) {
  if (c->hdr->server_flag.load(std::memory_order_acquire) == 2) return true;
  return false;
}

}  // namespace

// --- server exports ------------------------------------------------------

// Create the door: owns <dir>/sentinel-shm.ctl (re-initialized in place so
// surviving client mappings of the same inode stay coherent across server
// restarts) and a poller thread. spin_us bounds the busy-poll window after
// the last progress before the poller parks on the futex doorbell.
SN_EXPORT void *sn_shm_create(const char *dir, int64_t arena_cap,
                              int32_t spin_us) {
  mkdir(dir, 0777);  // best effort; may already exist
  auto *s = new ShmDoor(size_t(arena_cap));
  s->dir = dir;
  s->spin_us = uint32_t(spin_us < 0 ? 0 : spin_us);
  s->ctl_path = s->dir + "/sentinel-shm.ctl";
  int fd = open(s->ctl_path.c_str(), O_RDWR | O_CREAT | O_CLOEXEC, 0666);
  if (fd < 0) {
    delete s;
    return nullptr;
  }
  if (ftruncate(fd, off_t(kHdrBytes)) != 0) {
    close(fd);
    delete s;
    return nullptr;
  }
  void *base =
      mmap(nullptr, kHdrBytes, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (base == MAP_FAILED) {
    delete s;
    return nullptr;
  }
  s->ctl = reinterpret_cast<CtlHeader *>(base);
  s->ctl->server_sleep.store(0, std::memory_order_relaxed);
  s->ctl->doorbell.store(0, std::memory_order_relaxed);
  s->ctl->dir_epoch.store(1, std::memory_order_relaxed);
  s->ctl->server_pid = uint32_t(getpid());
  s->ctl->version = kVersion;
  std::atomic_thread_fence(std::memory_order_seq_cst);
  s->ctl->magic = kCtlMagic;  // last: clients gate on it
  s->ctl_fd = -1;
  s->poller = std::thread(poller_loop, s);
  return s;
}

SN_EXPORT void sn_shm_stop(void *h) {
  auto *s = static_cast<ShmDoor *>(h);
  if (s->stopping.exchange(true)) return;
  if (s->echo.joinable()) {
    s->echo_stop.store(true, std::memory_order_release);
    s->echo.join();
  }
  // wake the poller regardless of its sleep state
  s->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
  futex_wake(&s->ctl->doorbell, 1);
  if (s->poller.joinable()) s->poller.join();
  std::vector<std::shared_ptr<Segment>> snap;
  {
    std::lock_guard<std::mutex> lk(s->segs_mu);
    for (auto &kv : s->segs) snap.push_back(kv.second);
  }
  for (auto &seg : snap) {
    seg->dead.store(true, std::memory_order_relaxed);
    seg->hdr->server_flag.store(2, std::memory_order_release);
    rsp_doorbell(seg.get());
    unlink(seg->path.c_str());
  }
  {
    std::lock_guard<std::mutex> lk(s->segs_mu);
    s->segs.clear();
  }
  s->ctl->magic = 0;  // future clients refuse to attach to a dead door
  s->cv.notify_all();
}

SN_EXPORT void sn_shm_destroy(void *h) {
  auto *s = static_cast<ShmDoor *>(h);
  sn_shm_stop(h);
  unlink(s->ctl_path.c_str());
  munmap(reinterpret_cast<void *>(s->ctl), kHdrBytes);
  s->ctl = nullptr;
  delete s;
}

// Identical contract to sn_fd_wait_batch: whole frames only, frame "fd" is
// the segment id.
SN_EXPORT int32_t sn_shm_wait_batch(void *h, int32_t timeout_ms, int64_t *ids,
                                    int32_t *counts, uint8_t *prios,
                                    int32_t max_n, int32_t *f_fd,
                                    int32_t *f_gen, int32_t *f_xid,
                                    int32_t *f_n, uint8_t *f_type,
                                    int32_t max_frames,
                                    int32_t *n_frames_out) {
  auto *s = static_cast<ShmDoor *>(h);
  std::unique_lock<std::mutex> lk(s->mu);
  if (s->n_requests == 0) {
    s->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms), [s] {
      return s->n_requests > 0 || s->stopping.load(std::memory_order_acquire);
    });
  }
  if (s->n_requests == 0) {
    *n_frames_out = 0;
    return 0;
  }
  size_t take_req = 0, take_frames = 0;
  for (const FrameMeta &fm : s->frames) {
    if (take_frames + 1 > size_t(max_frames) ||
        take_req + size_t(fm.n) > size_t(max_n))
      break;
    take_req += size_t(fm.n);
    take_frames += 1;
  }
  if (take_frames == 0) {
    *n_frames_out = 0;
    return 0;
  }
  memcpy(ids, s->flow_ids.data(), take_req * sizeof(int64_t));
  memcpy(counts, s->counts.data(), take_req * sizeof(int32_t));
  memcpy(prios, s->prios.data(), take_req);
  for (size_t i = 0; i < take_frames; ++i) {
    f_fd[i] = s->frames[i].fd;
    f_gen[i] = int32_t(s->frames[i].gen);
    f_xid[i] = s->frames[i].xid;
    f_n[i] = s->frames[i].n;
    f_type[i] = s->frames[i].type;
  }
  *n_frames_out = int32_t(take_frames);
  size_t rest_req = s->n_requests - take_req;
  if (rest_req > 0) {
    memmove(s->flow_ids.data(), s->flow_ids.data() + take_req,
            rest_req * sizeof(int64_t));
    memmove(s->counts.data(), s->counts.data() + take_req,
            rest_req * sizeof(int32_t));
    memmove(s->prios.data(), s->prios.data() + take_req, rest_req);
  }
  s->frames.erase(s->frames.begin(), s->frames.begin() + take_frames);
  s->n_requests = rest_req;
  bool resume = s->arena_was_full;
  s->arena_was_full = false;
  lk.unlock();
  if (resume) {
    // unconditional bump: a poller racing into its futex park re-reads the
    // bell and EAGAINs instead of missing this drain (cheap — arena-full
    // transitions are rare)
    s->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
    futex_wake(&s->ctl->doorbell, 1);
  }
  return int32_t(take_req);
}

// Scatter-encode verdict frames straight into each segment's response
// ring: consecutive frames for the same segment publish under one lock
// hold and one doorbell. status/remaining/wait are request-order arrays
// covering all frames back-to-back, exactly like sn_fd_submit.
SN_EXPORT void sn_shm_submit(void *h, int32_t n_frames, const int32_t *f_fd,
                             const int32_t *f_gen, const int32_t *f_xid,
                             const int32_t *f_n, const uint8_t *f_type,
                             const int8_t *status, const int32_t *remaining,
                             const int32_t *wait_ms) {
  auto *s = static_cast<ShmDoor *>(h);
  size_t off = 0;
  std::vector<uint8_t> buf;
  for (int32_t i = 0; i < n_frames;) {
    int32_t run_end = i + 1;
    while (run_end < n_frames && f_fd[run_end] == f_fd[i] &&
           f_gen[run_end] == f_gen[i])
      ++run_end;
    auto seg = find_segment(s, f_fd[i], uint32_t(f_gen[i]));
    if (!seg) {
      for (int32_t k = i; k < run_end; ++k) off += size_t(f_n[k]);
      i = run_end;
      continue;
    }
    {
      std::lock_guard<std::mutex> lk(seg->w_mu);
      for (int32_t k = i; k < run_end; ++k) {
        int32_t n = f_n[k];
        if (f_type[k] == kTypeBatchFlow) {
          size_t payload = size_t(kHead) + 2 + size_t(n) * kRspRow;
          buf.resize(payload);
          uint8_t *p = buf.data();
          put32(p, uint32_t(f_xid[k]));
          p[4] = kTypeBatchFlow;
          put16(p + 5, uint16_t(n));
          uint8_t *row = p + 7;
          for (int32_t j = 0; j < n; ++j, row += kRspRow) {
            row[0] = uint8_t(status[off + size_t(j)]);
            put32(row + 1, uint32_t(remaining[off + size_t(j)]));
            put32(row + 5, uint32_t(wait_ms[off + size_t(j)]));
          }
          rsp_push(s, seg.get(), buf.data(), payload);
        } else {
          size_t payload = size_t(kHead) + kRspRow;
          buf.resize(payload);
          uint8_t *p = buf.data();
          put32(p, uint32_t(f_xid[k]));
          p[4] = kTypeFlow;
          p[5] = uint8_t(status[off]);
          put32(p + 6, uint32_t(remaining[off]));
          put32(p + 10, uint32_t(wait_ms[off]));
          rsp_push(s, seg.get(), buf.data(), payload);
        }
        off += size_t(n);
      }
    }
    rsp_doorbell(seg.get());
    i = run_end;
  }
}

// Enqueue one pre-encoded frame PAYLOAD (no 2-byte length prefix — the
// slot len word plays that role) for control-plane responses.
SN_EXPORT void sn_shm_send(void *h, int32_t fd, int32_t gen,
                           const uint8_t *data, int32_t len) {
  auto *s = static_cast<ShmDoor *>(h);
  auto seg = find_segment(s, fd, uint32_t(gen));
  if (!seg) return;
  {
    std::lock_guard<std::mutex> lk(seg->w_mu);
    rsp_push(s, seg.get(), data, size_t(len));
  }
  rsp_doorbell(seg.get());
}

// as sn_fd_next_control (*t_ns_out: when the poller queued a frame)
SN_EXPORT int32_t sn_shm_next_control(void *h, int32_t *fd_out,
                                      int32_t *gen_out, uint8_t *payload_out,
                                      int32_t max_len, int32_t *len_out,
                                      int64_t *t_ns_out) {
  auto *s = static_cast<ShmDoor *>(h);
  bool unpark;
  Control c;
  {
    std::lock_guard<std::mutex> lk(s->mu);
    if (s->controls.empty()) return -1;
    c = std::move(s->controls.front());
    s->controls.pop_front();
    unpark = s->controls_was_full && s->controls.size() < kMaxControls / 2;
    if (unpark) s->controls_was_full = false;
  }
  if (unpark) ring_server_doorbell(s);
  *fd_out = c.fd;
  *gen_out = int32_t(c.gen);
  int32_t n = int32_t(c.payload.size());
  *len_out = n;
  *t_ns_out = c.t_ns;
  if (n > 0 && n <= max_len) memcpy(payload_out, c.payload.data(), size_t(n));
  return c.kind;
}

// Ring ``bell`` (sn_bell_new's, or null for none) after every push to this
// door's control queue.
SN_EXPORT void sn_shm_set_bell(void *h, void *bell) {
  static_cast<ShmDoor *>(h)->bell.store(bell, std::memory_order_release);
}

SN_EXPORT void sn_shm_close_conn(void *h, int32_t fd, int32_t gen) {
  auto *s = static_cast<ShmDoor *>(h);
  auto seg = find_segment(s, fd, uint32_t(gen));
  if (seg) drop_segment(s, seg);
}

// out10: frames_in, requests_in, bytes_in, bytes_out, polls, doorbells,
// ring_full, segments, req_slots_used, req_slots_total.
// Each counter is INDEPENDENTLY monotonic (relaxed atomics, no cross-
// counter snapshot) — consumers diffing two reads must clamp derived
// deltas at zero rather than assume the set was coherent.
SN_EXPORT void sn_shm_stats(void *h, uint64_t *out10) {
  auto *s = static_cast<ShmDoor *>(h);
  out10[0] = s->frames_in.load(std::memory_order_relaxed);
  out10[1] = s->requests_in.load(std::memory_order_relaxed);
  out10[2] = s->bytes_in.load(std::memory_order_relaxed);
  out10[3] = s->bytes_out.load(std::memory_order_relaxed);
  out10[4] = s->polls.load(std::memory_order_relaxed);
  out10[5] = s->doorbells.load(std::memory_order_relaxed);
  out10[6] = s->ring_full.load(std::memory_order_relaxed);
  uint64_t used = 0, total = 0, nsegs = 0;
  {
    std::lock_guard<std::mutex> lk(s->segs_mu);
    for (auto &kv : s->segs) {
      auto &seg = kv.second;
      uint64_t t = seg->hdr->req_tail.load(std::memory_order_relaxed);
      uint64_t hd = seg->hdr->req_head.load(std::memory_order_relaxed);
      used += (t >= hd) ? (t - hd) : 0;
      total += seg->n_slots;
      ++nsegs;
    }
  }
  out10[7] = nsegs;
  out10[8] = used;
  out10[9] = total;
}

// --- transport echo (bench/tests only) -----------------------------------

// Pure-C echo loop: wait_batch -> all-GRANTED submit, no Python in the
// round trip. Used to measure the raw ring+doorbell RTT and host cost.
SN_EXPORT void sn_shm_echo_start(void *h) {
  auto *s = static_cast<ShmDoor *>(h);
  if (s->echo.joinable()) return;
  s->echo_stop.store(false, std::memory_order_release);
  s->echo = std::thread([s] {
    constexpr int32_t kMaxN = 65536, kMaxF = 4096;
    std::vector<int64_t> ids(kMaxN);
    std::vector<int32_t> counts(kMaxN), f_fd(kMaxF), f_gen(kMaxF),
        f_xid(kMaxF), f_n(kMaxF), rem(kMaxN), wait(kMaxN, 0);
    std::vector<uint8_t> prios(kMaxN), f_type(kMaxF);
    std::vector<int8_t> status(kMaxN, 0);  // GRANTED
    int32_t nf = 0;
    while (!s->echo_stop.load(std::memory_order_acquire)) {
      int32_t n = sn_shm_wait_batch(s, 5, ids.data(), counts.data(),
                                    prios.data(), kMaxN, f_fd.data(),
                                    f_gen.data(), f_xid.data(), f_n.data(),
                                    f_type.data(), kMaxF, &nf);
      if (n <= 0) continue;
      for (int32_t i = 0; i < n; ++i) rem[i] = counts[i];
      sn_shm_submit(s, nf, f_fd.data(), f_gen.data(), f_xid.data(),
                    f_n.data(), f_type.data(), status.data(), rem.data(),
                    wait.data());
    }
  });
}

SN_EXPORT void sn_shm_echo_stop(void *h) {
  auto *s = static_cast<ShmDoor *>(h);
  if (!s->echo.joinable()) return;
  s->echo_stop.store(true, std::memory_order_release);
  s->echo.join();
}

// --- client exports ------------------------------------------------------

// Attach to the door in `dir`: requires a live server (ctl magic + pid).
// Creates this client's segment file and rings the discovery doorbell.
// slot_size is the payload capacity hint; it is rounded up to a cache-line
// multiple including the slot header. n_slots is rounded up to a power of
// two (>= 2).
SN_EXPORT void *sn_shm_client_create(const char *dir, int32_t slot_size,
                                     int32_t n_slots, int32_t spin_us) {
  auto *c = new ShmClient();
  c->ctl_path = std::string(dir) + "/sentinel-shm.ctl";
  int cfd = open(c->ctl_path.c_str(), O_RDWR | O_CLOEXEC);
  if (cfd < 0) {
    delete c;
    return nullptr;
  }
  void *cbase =
      mmap(nullptr, kHdrBytes, PROT_READ | PROT_WRITE, MAP_SHARED, cfd, 0);
  close(cfd);
  if (cbase == MAP_FAILED) {
    delete c;
    return nullptr;
  }
  c->ctl = reinterpret_cast<CtlHeader *>(cbase);
  c->ctl_len = kHdrBytes;
  if (c->ctl->magic != kCtlMagic || c->ctl->version != kVersion ||
      !pid_alive(c->ctl->server_pid)) {
    delete c;
    return nullptr;
  }
  uint32_t payload_cap = uint32_t(slot_size < 256 ? 256 : slot_size);
  uint32_t ssz = uint32_t((payload_cap + kSlotHdr + 63) / 64) * 64;
  uint32_t ns = 2;
  while (ns < uint32_t(n_slots < 2 ? 2 : n_slots)) ns <<= 1;
  size_t map_len = kHdrBytes + 2 * size_t(ssz) * size_t(ns);

  static std::atomic<uint32_t> seq{0};
  std::string name = "seg-" + std::to_string(getpid()) + "-" +
                     std::to_string(seq.fetch_add(1)) + "-" +
                     std::to_string(mono_us() & 0xffffff) + ".ring";
  c->seg_path = std::string(dir) + "/" + name;
  int fd = open(c->seg_path.c_str(), O_RDWR | O_CREAT | O_EXCL | O_CLOEXEC,
                0666);
  if (fd < 0) {
    delete c;
    return nullptr;
  }
  if (ftruncate(fd, off_t(map_len)) != 0) {
    close(fd);
    unlink(c->seg_path.c_str());
    delete c;
    return nullptr;
  }
  void *base =
      mmap(nullptr, map_len, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (base == MAP_FAILED) {
    unlink(c->seg_path.c_str());
    delete c;
    return nullptr;
  }
  c->base = reinterpret_cast<uint8_t *>(base);
  c->map_len = map_len;
  c->hdr = reinterpret_cast<SegHeader *>(base);
  c->slot_size = ssz;
  c->n_slots = ns;
  c->mask = uint64_t(ns) - 1;
  c->req_ring = c->base + kHdrBytes;
  c->rsp_ring = c->req_ring + size_t(ssz) * ns;
  c->spin_us = uint32_t(spin_us < 0 ? 0 : spin_us);

  c->hdr->version = kVersion;
  c->hdr->slot_size = ssz;
  c->hdr->n_slots = ns;
  c->hdr->client_pid = uint32_t(getpid());
  c->hdr->req_tail.store(0, std::memory_order_relaxed);
  c->hdr->req_head.store(0, std::memory_order_relaxed);
  c->hdr->rsp_tail.store(0, std::memory_order_relaxed);
  c->hdr->rsp_head.store(0, std::memory_order_relaxed);
  c->hdr->client_sleep.store(0, std::memory_order_relaxed);
  c->hdr->client_doorbell.store(0, std::memory_order_relaxed);
  c->hdr->server_flag.store(0, std::memory_order_relaxed);
  c->hdr->magic = kSegMagic;
  // full init before announcing: the ready flag is the server's gate
  c->hdr->client_flag.store(1, std::memory_order_seq_cst);
  c->ctl->dir_epoch.fetch_add(1, std::memory_order_seq_cst);
  if (c->ctl->server_sleep.load(std::memory_order_seq_cst) == 1) {
    c->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
    futex_wake(&c->ctl->doorbell, 1);
  }
  return c;
}

// Graceful goodbye: closing flag + doorbell so the poller reclaims the
// segment promptly (it also unlinks; the unlink here covers a door that
// never attached us).
SN_EXPORT void sn_shm_client_destroy(void *h) {
  auto *c = static_cast<ShmClient *>(h);
  if (c->hdr) {
    c->hdr->client_flag.store(2, std::memory_order_seq_cst);
    if (c->ctl && c->ctl->magic == kCtlMagic) {
      c->ctl->dir_epoch.fetch_add(1, std::memory_order_seq_cst);
      c->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
      futex_wake(&c->ctl->doorbell, 1);
    }
    if (c->unlink_on_destroy) unlink(c->seg_path.c_str());
  }
  delete c;
}

// Returns 1 on publish, 0 when the request ring is full (caller decides to
// spin/back off), -1 when the server dropped us or died. data is the frame
// PAYLOAD (no 2-byte length prefix).
SN_EXPORT int32_t sn_shm_client_send(void *h, const uint8_t *data,
                                     int32_t len) {
  auto *c = static_cast<ShmClient *>(h);
  if (server_gone(c)) return -1;
  if (len <= 0 || size_t(len) > size_t(c->slot_size) - kSlotHdr) return -1;
  uint64_t tail = c->hdr->req_tail.load(std::memory_order_relaxed);
  uint64_t head = c->hdr->req_head.load(std::memory_order_acquire);
  if (tail - head >= c->n_slots) {
    // ring full: if the server looks dead, tell the caller instead of
    // letting it spin forever against a stuck ring
    if (c->ctl->magic != kCtlMagic || !pid_alive(c->ctl->server_pid))
      return -1;
    return 0;
  }
  uint8_t *slot = c->req_ring + size_t(tail & c->mask) * c->slot_size;
  memcpy(slot + kSlotHdr, data, size_t(len));
  *reinterpret_cast<uint32_t *>(slot) = uint32_t(len);
  c->hdr->req_tail.store(tail + 1, std::memory_order_release);
  // Dekker: publish, fence, then check whether the poller went to sleep
  std::atomic_thread_fence(std::memory_order_seq_cst);
  if (c->ctl->server_sleep.load(std::memory_order_seq_cst) == 1) {
    c->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
    futex_wake(&c->ctl->doorbell, 1);
  }
  return 1;
}

// Pop one response frame payload. Returns its length, 0 on timeout, -1
// when the server dropped us / died / published garbage.
SN_EXPORT int32_t sn_shm_client_recv(void *h, uint8_t *buf, int32_t max_len,
                                     int32_t timeout_ms) {
  auto *c = static_cast<ShmClient *>(h);
  int64_t deadline = mono_ms() + timeout_ms;
  int64_t spin_until = mono_us() + c->spin_us;
  for (;;) {
    uint64_t head = c->hdr->rsp_head.load(std::memory_order_relaxed);
    uint64_t tail = c->hdr->rsp_tail.load(std::memory_order_acquire);
    if (head != tail) {
      const uint8_t *slot =
          c->rsp_ring + size_t(head & c->mask) * c->slot_size;
      size_t flen = *reinterpret_cast<const uint32_t *>(slot);
      if (flen == 0 || flen > size_t(c->slot_size) - kSlotHdr ||
          flen > size_t(max_len))
        return -1;
      memcpy(buf, slot + kSlotHdr, flen);
      c->hdr->rsp_head.store(head + 1, std::memory_order_release);
      return int32_t(flen);
    }
    if (server_gone(c)) return -1;
    int64_t now = mono_ms();
    if (now >= deadline) return 0;
    if (mono_us() < spin_until) {
#if defined(__x86_64__) || defined(__i386__)
      __builtin_ia32_pause();
#else
      std::this_thread::yield();
#endif
      continue;
    }
    // park: advertise sleeping, re-check (Dekker vs server's publish)
    uint32_t bell = c->hdr->client_doorbell.load(std::memory_order_seq_cst);
    c->hdr->client_sleep.store(1, std::memory_order_seq_cst);
    std::atomic_thread_fence(std::memory_order_seq_cst);
    if (c->hdr->rsp_tail.load(std::memory_order_seq_cst) == head &&
        !server_gone(c)) {
      int64_t remain = deadline - mono_ms();
      if (remain > 0)
        futex_wait(&c->hdr->client_doorbell, bell,
                   remain < 50 ? remain : 50);
    }
    c->hdr->client_sleep.store(0, std::memory_order_seq_cst);
    if (c->ctl->magic != kCtlMagic || !pid_alive(c->ctl->server_pid))
      return -1;
    spin_until = mono_us() + c->spin_us;
  }
}

// Timed round-trip probe: send one payload, wait for one response, discard
// it. out_ns receives per-iteration wall times. Returns iterations that
// completed. Runs entirely in C so the measured distribution is the
// transport (ring + doorbell), not the ctypes/codec overhead around it.
SN_EXPORT int32_t sn_shm_client_rtt(void *h, const uint8_t *data, int32_t len,
                                    int32_t iters, int64_t *out_ns) {
  auto *c = static_cast<ShmClient *>(h);
  std::vector<uint8_t> buf(c->slot_size);
  int32_t done = 0;
  for (int32_t i = 0; i < iters; ++i) {
    timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    int32_t rc = sn_shm_client_send(h, data, len);
    if (rc == 0) {
      // ring full shouldn't happen at depth 1; back off once
      usleep(100);
      rc = sn_shm_client_send(h, data, len);
    }
    if (rc != 1) break;
    if (sn_shm_client_recv(h, buf.data(), int32_t(buf.size()), 1000) <= 0)
      break;
    clock_gettime(CLOCK_MONOTONIC, &t1);
    out_ns[done++] = (int64_t(t1.tv_sec) - int64_t(t0.tv_sec)) * 1000000000 +
                     (int64_t(t1.tv_nsec) - int64_t(t0.tv_nsec));
  }
  return done;
}

// Torn/hostile-writer fuzz hook (tests only). Stages:
//   0: full payload + len staged in the NEXT slot, tail NOT published
//      (the parked/killed-mid-write shape — server must never see it)
//   1: half the payload staged, no len, no publish
//   2: PUBLISH a slot whose len word is out of range (hostile: the server
//      must drop the whole segment, not read past the slot)
//   3: PUBLISH a valid-length slot full of the caller's garbage bytes
//      (flows to frame validation / the control plane like TCP fuzz bytes)
// Returns 1 if the stage was performed, 0 if the ring is full.
SN_EXPORT int32_t sn_shm_client_fuzz(void *h, const uint8_t *data,
                                     int32_t len, int32_t stage) {
  auto *c = static_cast<ShmClient *>(h);
  uint64_t tail = c->hdr->req_tail.load(std::memory_order_relaxed);
  uint64_t head = c->hdr->req_head.load(std::memory_order_acquire);
  if (tail - head >= c->n_slots) return 0;
  uint8_t *slot = c->req_ring + size_t(tail & c->mask) * c->slot_size;
  size_t cap = size_t(c->slot_size) - kSlotHdr;
  size_t n = size_t(len) < cap ? size_t(len) : cap;
  switch (stage) {
    case 0:
      memcpy(slot + kSlotHdr, data, n);
      *reinterpret_cast<uint32_t *>(slot) = uint32_t(n);
      break;  // no publish
    case 1:
      memcpy(slot + kSlotHdr, data, n / 2);
      break;  // no len, no publish
    case 2:
      *reinterpret_cast<uint32_t *>(slot) = uint32_t(cap + 4096);
      c->hdr->req_tail.store(tail + 1, std::memory_order_release);
      break;
    case 3:
      memcpy(slot + kSlotHdr, data, n);
      *reinterpret_cast<uint32_t *>(slot) = uint32_t(n);
      c->hdr->req_tail.store(tail + 1, std::memory_order_release);
      break;
    default:
      return 0;
  }
  std::atomic_thread_fence(std::memory_order_seq_cst);
  if (c->ctl->server_sleep.load(std::memory_order_seq_cst) == 1) {
    c->ctl->doorbell.fetch_add(1, std::memory_order_seq_cst);
    futex_wake(&c->ctl->doorbell, 1);
  }
  return 1;
}

// 1 while the server side looks alive and attached-or-pending, 0 once it
// dropped us or its pid is gone.
SN_EXPORT int32_t sn_shm_client_alive(void *h) {
  auto *c = static_cast<ShmClient *>(h);
  if (server_gone(c)) return 0;
  if (c->ctl->magic != kCtlMagic || !pid_alive(c->ctl->server_pid)) return 0;
  return 1;
}

#endif  // __linux__
