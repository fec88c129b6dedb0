// Native host runtime for sentinel-tpu: the per-call hot paths that the
// reference implements with JVM concurrency primitives (LongAdder arrays,
// CAS window loops — LeapArray.java:116-160, RateLimiterController.java:46-91,
// ParamFlowChecker.java:127-190) re-expressed as lock-free C++.
//
// The Python host layer uses these through ctypes (sentinel_tpu/native/).
// Semantics are kept bit-identical with the numpy fallbacks in
// sentinel_tpu/local/stat.py: same ring math, same mask-on-read deprecation,
// so either backend can serve the local (non-cluster) decision path. The
// device engine (JAX/Pallas) remains the source of truth for batched and
// cluster decisions.
//
// Concurrency model: counters are atomic doubles (CAS add); bucket reset
// takes a per-bucket spinlock, mirroring the reference's single
// ReentrantLock-guarded reset arm (LeapArray.java:53). Readers never block:
// a bucket whose start is stale is simply excluded by the validity mask,
// exactly like isWindowDeprecated().

#include <atomic>
#include <cstdint>
#include <cstring>
#include <new>
#include <utility>
#include <vector>

#if defined(_WIN32)
#define SN_EXPORT extern "C" __declspec(dllexport)
#else
#define SN_EXPORT extern "C" __attribute__((visibility("default")))
#endif

namespace {

constexpr int64_t NEVER = -(int64_t(1) << 60);

inline void atomic_add_double(std::atomic<double> &cell, double n) {
  double old = cell.load(std::memory_order_relaxed);
  while (!cell.compare_exchange_weak(old, old + n, std::memory_order_relaxed)) {
  }
}

struct SpinLock {
  std::atomic_flag flag = ATOMIC_FLAG_INIT;
  void lock() {
    while (flag.test_and_set(std::memory_order_acquire)) {
    }
  }
  void unlock() { flag.clear(std::memory_order_release); }
};

// ---------------------------------------------------------------------------
// Sliding window (HostWindow / LeapArray analog)
// ---------------------------------------------------------------------------

struct Window {
  int32_t bucket_ms;
  int32_t n_buckets;
  int32_t n_channels;
  int64_t interval_ms;
  std::atomic<int64_t> *starts;  // [n_buckets]
  SpinLock *reset_locks;         // [n_buckets]
  std::atomic<double> *counts;   // [n_buckets * n_channels]
  // serializes the matured-borrow transfer when this window is a node's
  // future array (see touch_transfer) — admission readers must never see
  // tokens drained from here but not yet credited to the second window
  SpinLock xfer_lock;

  Window(int32_t bms, int32_t nb, int32_t nc)
      : bucket_ms(bms), n_buckets(nb), n_channels(nc),
        interval_ms(int64_t(bms) * nb) {
    starts = new std::atomic<int64_t>[nb];
    reset_locks = new SpinLock[nb];
    counts = new std::atomic<double>[size_t(nb) * nc];
    for (int32_t b = 0; b < nb; b++) starts[b].store(NEVER);
    for (size_t i = 0; i < size_t(nb) * nc; i++) counts[i].store(0.0);
  }
  ~Window() {
    delete[] starts;
    delete[] reset_locks;
    delete[] counts;
  }

  inline int32_t idx_of(int64_t t) const {
    return int32_t((t / bucket_ms) % n_buckets);
  }
  inline int64_t start_of(int64_t t) const { return t - t % bucket_ms; }

  // Occupy the ring slot for window-start `ws` at slot `idx`, zeroing it if a
  // different window holds it (reset arm of LeapArray.currentWindow).
  void occupy(int32_t idx, int64_t ws) {
    if (starts[idx].load(std::memory_order_acquire) == ws) return;
    reset_locks[idx].lock();
    if (starts[idx].load(std::memory_order_relaxed) != ws) {
      for (int32_t c = 0; c < n_channels; c++)
        counts[size_t(idx) * n_channels + c].store(0.0,
                                                   std::memory_order_relaxed);
      starts[idx].store(ws, std::memory_order_release);
    }
    reset_locks[idx].unlock();
  }

  void add(int64_t now, int32_t chan, double n) {
    int32_t idx = idx_of(now);
    occupy(idx, start_of(now));
    atomic_add_double(counts[size_t(idx) * n_channels + chan], n);
  }

  inline bool valid(int64_t now, int32_t b) const {
    int64_t age = now - starts[b].load(std::memory_order_acquire);
    return age >= 0 && age < interval_ms;
  }

  double sum(int64_t now, int32_t chan) const {
    double total = 0.0;
    for (int32_t b = 0; b < n_buckets; b++)
      if (valid(now, b))
        total += counts[size_t(b) * n_channels + chan].load(
            std::memory_order_relaxed);
    return total;
  }
};

// ---------------------------------------------------------------------------
// Token bucket array (ParamFlowChecker.passDefaultLocalCheck analog)
// ---------------------------------------------------------------------------

struct TokenBuckets {
  int32_t n_slots;
  std::atomic<double> *tokens;         // remaining tokens per slot
  std::atomic<int64_t> *last_fill_ms;  // last refill time per slot
  SpinLock *locks;

  explicit TokenBuckets(int32_t n) : n_slots(n) {
    tokens = new std::atomic<double>[n];
    last_fill_ms = new std::atomic<int64_t>[n];
    locks = new SpinLock[n];
    for (int32_t i = 0; i < n; i++) {
      tokens[i].store(-1.0);  // -1 → uninitialized (first acquire fills)
      last_fill_ms[i].store(NEVER);
    }
  }
  ~TokenBuckets() {
    delete[] tokens;
    delete[] last_fill_ms;
    delete[] locks;
  }
};

// ---------------------------------------------------------------------------
// Leaky-bucket pacer array (RateLimiterController.latestPassedTime analog)
// ---------------------------------------------------------------------------

struct Pacers {
  int32_t n_slots;
  std::atomic<int64_t> *latest_passed;  // µs-scaled ms like the reference? ms.

  explicit Pacers(int32_t n) : n_slots(n) {
    latest_passed = new std::atomic<int64_t>[n];
    for (int32_t i = 0; i < n; i++) latest_passed[i].store(NEVER);
  }
  ~Pacers() { delete[] latest_passed; }
};

}  // namespace

// ---------------------------------------------------------------------------
// C API
// ---------------------------------------------------------------------------

SN_EXPORT void *sn_window_create(int32_t bucket_ms, int32_t n_buckets,
                                 int32_t n_channels) {
  return new (std::nothrow) Window(bucket_ms, n_buckets, n_channels);
}

SN_EXPORT void sn_window_destroy(void *w) { delete static_cast<Window *>(w); }

SN_EXPORT void sn_window_add(void *w, int64_t now, int32_t chan, double n) {
  static_cast<Window *>(w)->add(now, chan, n);
}

SN_EXPORT double sn_window_sum(void *w, int64_t now, int32_t chan) {
  return static_cast<Window *>(w)->sum(now, chan);
}

// Per-channel valid sums in one pass (metric-log snapshot path).
SN_EXPORT void sn_window_snapshot(void *wp, int64_t now, double *out) {
  Window *w = static_cast<Window *>(wp);
  for (int32_t c = 0; c < w->n_channels; c++) out[c] = 0.0;
  for (int32_t b = 0; b < w->n_buckets; b++)
    if (w->valid(now, b))
      for (int32_t c = 0; c < w->n_channels; c++)
        out[c] += w->counts[size_t(b) * w->n_channels + c].load(
            std::memory_order_relaxed);
}

// Count in the bucket one bucket-length before the current one
// (ArrayMetric.previousWindowPass shape, used by warm-up).
SN_EXPORT double sn_window_prev_bucket(void *wp, int64_t now, int32_t chan) {
  Window *w = static_cast<Window *>(wp);
  int64_t prev_start = w->start_of(now) - w->bucket_ms;
  // floor-mod: prev_start can be negative near the engine epoch
  int32_t idx =
      int32_t(((prev_start / w->bucket_ms) % w->n_buckets + w->n_buckets) %
              w->n_buckets);
  if (w->starts[idx].load(std::memory_order_acquire) == prev_start)
    return w->counts[size_t(idx) * w->n_channels + chan].load(
        std::memory_order_relaxed);
  return 0.0;
}

// min over valid buckets of counts[num]/counts[den] where counts[den] > 0
// (StatisticNode.min_rt shape: rt / success).
SN_EXPORT double sn_window_min_ratio(void *wp, int64_t now, int32_t num_chan,
                                     int32_t den_chan) {
  Window *w = static_cast<Window *>(wp);
  double best = -1.0;
  for (int32_t b = 0; b < w->n_buckets; b++) {
    if (!w->valid(now, b)) continue;
    double den = w->counts[size_t(b) * w->n_channels + den_chan].load(
        std::memory_order_relaxed);
    if (den <= 0) continue;
    double r = w->counts[size_t(b) * w->n_channels + num_chan].load(
                   std::memory_order_relaxed) /
               den;
    if (best < 0 || r < best) best = r;
  }
  return best < 0 ? 0.0 : best;
}

SN_EXPORT int64_t sn_window_start_at(void *wp, int32_t b) {
  return static_cast<Window *>(wp)->starts[b].load(std::memory_order_acquire);
}

SN_EXPORT double sn_window_count_at(void *wp, int32_t b, int32_t chan) {
  Window *w = static_cast<Window *>(wp);
  return w->counts[size_t(b) * w->n_channels + chan].load(
      std::memory_order_relaxed);
}

// --- future (occupy/borrow) semantics on a 1+ channel window ---------------

// Add into the bucket holding `future_time` (FutureBucketLeapArray.addWaiting).
SN_EXPORT void sn_window_add_future(void *wp, int64_t future_time, int32_t chan,
                                    double n) {
  static_cast<Window *>(wp)->add(future_time, chan, n);
}

// Sum of buckets strictly in the future within one interval (currentWaiting).
SN_EXPORT double sn_window_future_waiting(void *wp, int64_t now, int32_t chan) {
  Window *w = static_cast<Window *>(wp);
  double total = 0.0;
  for (int32_t b = 0; b < w->n_buckets; b++) {
    int64_t ahead = w->starts[b].load(std::memory_order_acquire) - now;
    if (ahead > 0 && ahead <= w->interval_ms)
      total += w->counts[size_t(b) * w->n_channels + chan].load(
          std::memory_order_relaxed);
  }
  return total;
}

namespace {
// Drain logic shared by sn_window_take_matured and the composite stat ops.
inline double drain_matured(Window *w, int64_t now, int32_t chan) {
  int64_t cur_start = w->start_of(now);
  int32_t idx = w->idx_of(cur_start);
  if (w->starts[idx].load(std::memory_order_acquire) != cur_start) return 0.0;
  std::atomic<double> &cell = w->counts[size_t(idx) * w->n_channels + chan];
  double old = cell.load(std::memory_order_relaxed);
  while (old != 0.0 &&
         !cell.compare_exchange_weak(old, 0.0, std::memory_order_relaxed)) {
  }
  return old;
}
}  // namespace

// Drain the current bucket if its window has arrived (matured borrows).
SN_EXPORT double sn_window_take_matured(void *wp, int64_t now, int32_t chan) {
  return drain_matured(static_cast<Window *>(wp), now, chan);
}

// ---------------------------------------------------------------------------
// Composite StatisticNode writes — ONE ctypes round-trip per logical stat
// write instead of one per window op (ctypes call overhead dominates the
// local entry hot path otherwise). Channel layout is stat.py's:
// PASS=0 BLOCK=1 EXCEPTION=2 SUCCESS=3 RT=4 OCCUPIED_PASS=5. No cross-window
// lock: the reference's StatisticNode writes its second/minute LeapArrays
// without one either, and each Window op is individually atomic.
// ---------------------------------------------------------------------------

namespace {
// Matured borrowed tokens roll in as PASS (consuming capacity) and
// OCCUPIED_PASS (observability) — OccupiableBucketLeapArray's transfer.
// The future window's xfer_lock makes drain+credit atomic with respect to
// every other composite op on the same node: without it a flow-check read
// between the drain and the credit would see the tokens in NEITHER window
// and over-admit (the Python slow path's node RLock gave the same guarantee).
inline void touch_transfer(Window *s, Window *m, Window *f, int64_t now) {
  f->xfer_lock.lock();
  double matured = drain_matured(f, now, 0);
  if (matured != 0.0) {
    s->add(now, 0, matured);
    s->add(now, 5, matured);
    m->add(now, 0, matured);
    m->add(now, 5, matured);
  }
  f->xfer_lock.unlock();
}
}  // namespace

SN_EXPORT void sn_stat_pass(void *sec, void *minute, void *future, int64_t now,
                            double n) {
  Window *s = static_cast<Window *>(sec);
  Window *m = static_cast<Window *>(minute);
  touch_transfer(s, m, static_cast<Window *>(future), now);
  s->add(now, 0, n);
  m->add(now, 0, n);
}

SN_EXPORT void sn_stat_event(void *sec, void *minute, int64_t now,
                             int32_t chan, double n) {
  static_cast<Window *>(sec)->add(now, chan, n);
  static_cast<Window *>(minute)->add(now, chan, n);
}

SN_EXPORT void sn_stat_rt_success(void *sec, void *minute, int64_t now,
                                  double rt, double n) {
  Window *s = static_cast<Window *>(sec);
  Window *m = static_cast<Window *>(minute);
  s->add(now, 3, n);
  s->add(now, 4, rt);
  m->add(now, 3, n);
  m->add(now, 4, rt);
}

// Touch matured borrows, then return the second-window sum of one channel —
// the flow-check read (StatisticNode.passQps) in one round trip. The sum
// happens under the same xfer_lock so an in-flight transfer on another
// thread can never be observed half-done.
SN_EXPORT double sn_stat_touched_sum(void *sec, void *minute, void *future,
                                     int64_t now, int32_t chan) {
  Window *s = static_cast<Window *>(sec);
  Window *m = static_cast<Window *>(minute);
  Window *f = static_cast<Window *>(future);
  f->xfer_lock.lock();
  double matured = drain_matured(f, now, 0);
  if (matured != 0.0) {
    s->add(now, 0, matured);
    s->add(now, 5, matured);
    m->add(now, 0, matured);
    m->add(now, 5, matured);
  }
  double total = s->sum(now, chan);
  f->xfer_lock.unlock();
  return total;
}

// --- token buckets ---------------------------------------------------------

SN_EXPORT void *sn_tb_create(int32_t n_slots) {
  return new (std::nothrow) TokenBuckets(n_slots);
}

SN_EXPORT void sn_tb_destroy(void *t) {
  delete static_cast<TokenBuckets *>(t);
}

SN_EXPORT void sn_tb_reset(void *tp, int32_t slot) {
  TokenBuckets *t = static_cast<TokenBuckets *>(tp);
  t->tokens[slot].store(-1.0, std::memory_order_relaxed);
  t->last_fill_ms[slot].store(NEVER, std::memory_order_relaxed);
}

// Token-bucket admission with burst (ParamFlowChecker.java:127-190): refill
// `elapsed * count / interval` tokens capped at count + burst, then consume.
// Returns 1 = pass, 0 = block.
SN_EXPORT int32_t sn_tb_try_acquire(void *tp, int32_t slot, int64_t now,
                                    int32_t acquire, double count,
                                    double burst, int64_t interval_ms) {
  TokenBuckets *t = static_cast<TokenBuckets *>(tp);
  double cap = count + burst;
  t->locks[slot].lock();
  double tok = t->tokens[slot].load(std::memory_order_relaxed);
  int64_t last = t->last_fill_ms[slot].load(std::memory_order_relaxed);
  if (tok < 0 || last == NEVER) {
    // first sight of this slot: full bucket; an oversized acquire empties it
    // and blocks (ParamFlowChecker first-fill arm)
    t->last_fill_ms[slot].store(now, std::memory_order_relaxed);
    if (cap < double(acquire)) {
      t->tokens[slot].store(0.0, std::memory_order_relaxed);
      t->locks[slot].unlock();
      return 0;
    }
    t->tokens[slot].store(cap - double(acquire), std::memory_order_relaxed);
    t->locks[slot].unlock();
    return 1;
  }
  if (now > last) {
    double refill = double(now - last) * count / double(interval_ms);
    if (refill > 0) {
      tok = tok + refill > cap ? cap : tok + refill;
      last = now;
    }
  }
  int32_t ok = 0;
  if (tok >= double(acquire)) {
    tok -= double(acquire);
    ok = 1;
  }
  t->tokens[slot].store(tok, std::memory_order_relaxed);
  t->last_fill_ms[slot].store(last, std::memory_order_relaxed);
  t->locks[slot].unlock();
  return ok;
}

// --- leaky-bucket pacers ---------------------------------------------------

SN_EXPORT void *sn_pacer_create(int32_t n_slots) {
  return new (std::nothrow) Pacers(n_slots);
}

SN_EXPORT void sn_pacer_destroy(void *p) { delete static_cast<Pacers *>(p); }

SN_EXPORT void sn_pacer_reset(void *pp, int32_t slot) {
  static_cast<Pacers *>(pp)->latest_passed[slot].store(
      NEVER, std::memory_order_relaxed);
}

// Uniform-pacing admission (RateLimiterController.java:46-91): cost of
// `acquire` tokens is `acquire / count * 1000` ms after the latest passed
// time. Returns the ms the caller must sleep (0 = immediate), or -1 = block
// (expected wait exceeds max_queue_ms). CAS keeps concurrent callers strictly
// serialized on the shared latest_passed timeline.
SN_EXPORT int64_t sn_pacer_try_pass(void *pp, int32_t slot, int64_t now,
                                    int32_t acquire, double count_per_sec,
                                    int64_t max_queue_ms) {
  if (count_per_sec <= 0) return -1;
  Pacers *p = static_cast<Pacers *>(pp);
  int64_t cost = int64_t(double(acquire) / count_per_sec * 1000.0 + 0.5);
  std::atomic<int64_t> &latest = p->latest_passed[slot];
  for (;;) {
    int64_t prev = latest.load(std::memory_order_acquire);
    if (prev == NEVER) {  // first request on this slot passes immediately
      if (latest.compare_exchange_weak(prev, now, std::memory_order_acq_rel))
        return 0;
      continue;
    }
    int64_t expected = prev + cost;
    if (expected <= now) {
      if (latest.compare_exchange_weak(prev, now, std::memory_order_acq_rel))
        return 0;
      continue;
    }
    int64_t wait = expected - now;
    if (wait > max_queue_ms) return -1;
    if (latest.compare_exchange_weak(prev, expected,
                                     std::memory_order_acq_rel)) {
      // re-check like the reference: a racing sleeper may have pushed the
      // queue past the budget between load and CAS — the CAS serializes, so
      // wait computed from our own CAS'd value is authoritative.
      return wait;
    }
  }
}

// ---------------------------------------------------------------------------
// Wire codec for BATCH_FLOW frames (cluster/protocol.py): big-endian packed
// rows. Decode fills caller-provided (numpy) arrays; encode writes the full
// frame (length prefix + header + rows) into a caller buffer. These are the
// token server's per-frame hot path — ctypes releases the GIL around both,
// so frame codec work overlaps the IO loops under load.

namespace {

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

constexpr int kHead = 5;          // xid:int32 + type:uint8
constexpr int kReqRow = 13;       // flow_id:int64 + count:int32 + prio:uint8
constexpr int kRspRow = 9;        // status:int8 + remaining:int32 + wait:int32
constexpr uint8_t kBatchFlow = 5; // MsgType.BATCH_FLOW

}  // namespace

// payload (without length prefix) → xid, flow_ids[n], counts[n], prios[n].
// Returns n, or -1 if the payload is malformed/truncated.
SN_EXPORT int32_t sn_batch_decode_req(const uint8_t *payload, int32_t len,
                                      int32_t *xid_out, int64_t *flow_ids,
                                      int32_t *counts, uint8_t *prios,
                                      int32_t max_n) {
  if (len < kHead + 2) return -1;
  *xid_out = be32(payload);
  int32_t n = be16(payload + kHead);
  if (n > max_n || len < kHead + 2 + n * kReqRow) return -1;
  const uint8_t *row = payload + kHead + 2;
  for (int32_t i = 0; i < n; ++i, row += kReqRow) {
    flow_ids[i] = be64(row);
    counts[i] = be32(row + 8);
    prios[i] = row[12];
  }
  return n;
}

// Encode a full response frame (length prefix included) into out; returns the
// frame's byte length, or -1 if out_cap is too small or n exceeds a frame.
SN_EXPORT int32_t sn_batch_encode_rsp(int32_t xid, int32_t n,
                                      const int8_t *status,
                                      const int32_t *remaining,
                                      const int32_t *wait_ms, uint8_t *out,
                                      int32_t out_cap) {
  int64_t payload_len = kHead + 2 + int64_t(n) * kRspRow;
  if (payload_len > 65535 || payload_len + 2 > out_cap) return -1;
  put16(out, uint16_t(payload_len));
  put32(out + 2, uint32_t(xid));
  out[6] = kBatchFlow;
  put16(out + 7, uint16_t(n));
  uint8_t *row = out + 9;
  for (int32_t i = 0; i < n; ++i, row += kRspRow) {
    row[0] = uint8_t(status[i]);
    put32(row + 1, uint32_t(remaining[i]));
    put32(row + 5, uint32_t(wait_ms[i]));
  }
  return int32_t(payload_len + 2);
}

// ---------------------------------------------------------------------------
// Flow prep (cluster/token_service.py, phase `prep`): one frame's flow ids,
// acquires and priorities into the decide step's packed host argument
// (engine/decide.py: lines ROW_SLOT / ROW_ACQUIRE / ROW_FLAGS / ROW_HEAD) in
// ONE call with the GIL released. The contract is byte identity with the
// numpy path that stays as fallback, re-prep and reference: `_lookup_from`
// (np.searchsorted into the lookup snapshot's sorted keys), `_prep_batch`
// (np.argsort(slots, kind="stable"), None where the slots arrive ascending)
// and `pack_requests` (slot -1 / acquire 0 / flags 0 beyond n). Every array
// is the caller's; only the sort's scratch lives here, per thread.

namespace {

constexpr int kLanes = 16;     // lookups in flight together: a binary search
                               // is a chain of dependent loads, sixteen
                               // chains keep the memory system busy
constexpr int kDigitBits = 11; // radix digit: 2048 counters a pass
constexpr int32_t kFlagPrioritized = 1, kFlagValid = 2;  // decide.FLAG_*

// pos[l] = the leftmost j with keys[j] >= vals[l] (n_keys where there is
// none), as np.searchsorted(keys, vals) finds it: of `lanes` <= kLanes
// values in n_keys >= 1 ascending keys.
inline void lower_bounds(const int64_t *keys, int64_t n_keys,
                         const int64_t *vals, int lanes, int64_t *pos) {
  for (int l = 0; l < lanes; ++l) pos[l] = 0;
  // branchless, all lanes one halving at a time
  for (int64_t len = n_keys; len > 1;) {
    const int64_t half = len >> 1;
    for (int l = 0; l < lanes; ++l)
      pos[l] += keys[pos[l] + half - 1] < vals[l] ? half : 0;
    len -= half;
  }
  // pos is the last candidate; one more step where it is still below
  for (int l = 0; l < lanes; ++l) pos[l] += keys[pos[l]] < vals[l] ? 1 : 0;
}

// slots[i] = tab[j] where keys[j] == ids[i], else -1.
void lookup_slots(const int64_t *keys, const int32_t *tab, int64_t n_keys,
                  const int64_t *ids, int64_t n, int32_t *slots) {
  if (n_keys == 0) {
    for (int64_t i = 0; i < n; ++i) slots[i] = -1;
    return;
  }
  for (int64_t at = 0; at < n; at += kLanes) {
    const int lanes = int(n - at < kLanes ? n - at : kLanes);
    int64_t pos[kLanes];
    lower_bounds(keys, n_keys, ids + at, lanes, pos);
    for (int l = 0; l < lanes; ++l) {
      const int64_t j = pos[l];
      slots[at + l] = j < n_keys && keys[j] == ids[at + l] ? tab[j] : -1;
    }
  }
}

struct SortScratch {
  std::vector<uint32_t> hist;
  std::vector<int32_t> ping, pong;
};

inline SortScratch &sort_scratch() {
  static thread_local SortScratch scratch;
  return scratch;
}

// The width in bits of `top`, at least 1.
inline int key_bits(uint64_t top) {
  int bits = 1;
  while (bits < 64 && top >> bits) ++bits;
  return bits;
}

// The numbers 0 .. n-1 in ascending key(i), ties in ascending i
// (np.argsort(kind="stable")'s order): a stable LSD radix sort in as few
// digits as `bits`, the width of the largest key, needs. The order lives in
// the thread's scratch until the thread sorts again.
template <class Key>
const int32_t *radix_order(int64_t n, int bits, Key key) {
  SortScratch &scratch = sort_scratch();
  const int passes = (bits + kDigitBits - 1) / kDigitBits;
  const int digit = (bits + passes - 1) / passes;
  const uint32_t mask = (uint32_t(1) << digit) - 1;
  const size_t radix = size_t(1) << digit;
  scratch.hist.assign(size_t(passes) * radix, 0);
  uint32_t *hist = scratch.hist.data();
  for (int64_t i = 0; i < n; ++i) {
    const auto k = key(i);
    for (int p = 0; p < passes; ++p)
      ++hist[p * radix + ((k >> (p * digit)) & mask)];
  }
  for (int p = 0; p < passes; ++p) {
    uint32_t run = 0;
    for (size_t b = 0; b < radix; ++b) {
      const uint32_t count = hist[p * radix + b];
      hist[p * radix + b] = run;
      run += count;
    }
  }
  scratch.ping.resize(size_t(n));
  scratch.pong.resize(size_t(n));
  int32_t *src = scratch.ping.data(), *dst = scratch.pong.data();
  for (int64_t i = 0; i < n; ++i) src[i] = int32_t(i);
  for (int p = 0; p < passes; ++p) {
    uint32_t *at = hist + p * radix;
    for (int64_t k = 0; k < n; ++k) {
      const int32_t i = src[k];
      dst[at[(key(i) >> (p * digit)) & mask]++] = i;
    }
    std::swap(src, dst);
  }
  return src;
}

}  // namespace

// keys[n_keys] ascending with their slots tab[n_keys]; the frame ids / acq /
// pr (bool bytes) of n >= 1 rows; packed: line l of the argument starts at
// packed + l * stride and is `width` >= n wide (stride == width for an array
// of its own, depth * width inside a fused staging block). Writes slots[n]
// in request order, the three request lines grouped by ascending slot (-1
// first, ties in arrival order) and padded, the head line zeroed where
// zero_head, and order[n] (entry k of the lines is request order[k]) unless
// the slots arrived ascending. Returns bit 0: ascending (order not written),
// bit 1: every acquire the same.
SN_EXPORT int32_t sn_flow_prep(const int64_t *keys, const int32_t *tab,
                               int64_t n_keys, const int64_t *ids,
                               const int32_t *acq, const uint8_t *pr,
                               int64_t n, int64_t width, int64_t stride,
                               int32_t zero_head, int32_t *slots,
                               int64_t *order, int32_t *packed) {
  lookup_slots(keys, tab, n_keys, ids, n, slots);
  bool ascending = true;
  int32_t top = -1;
  int32_t a_lo = acq[0], a_hi = acq[0];
  for (int64_t i = 0; i < n; ++i) {
    if (i && slots[i] < slots[i - 1]) ascending = false;
    if (slots[i] > top) top = slots[i];
    if (acq[i] < a_lo) a_lo = acq[i];
    if (acq[i] > a_hi) a_hi = acq[i];
  }
  int32_t *p_slot = packed, *p_acq = packed + stride;
  int32_t *p_flags = packed + 2 * stride;
  auto emit = [&](int64_t k, int64_t i) {  // request i is entry k of the lines
    p_slot[k] = slots[i];
    p_acq[k] = acq[i];
    p_flags[k] = (pr[i] ? kFlagPrioritized : 0) | kFlagValid;
  };
  if (ascending) {
    for (int64_t i = 0; i < n; ++i) emit(i, i);
  } else {
    // the row numbers sorted on slot + 1 (so that -1, no rule, sorts first
    // as it does for argsort)
    const int32_t *src = radix_order(
        n, key_bits(uint32_t(top) + 1),
        [&](int64_t i) { return uint32_t(slots[i]) + 1; });
    for (int64_t k = 0; k < n; ++k) {
      order[k] = src[k];
      emit(k, src[k]);
    }
  }
  for (int64_t k = n; k < width; ++k) {
    p_slot[k] = -1;
    p_acq[k] = 0;
    p_flags[k] = 0;
  }
  if (zero_head)
    std::memset(packed + 3 * stride, 0, size_t(width) * sizeof(int32_t));
  return int32_t(ascending) | int32_t(a_lo == a_hi) << 1;
}

// ---------------------------------------------------------------------------
// Param prep (cluster/token_service.py, phase `prep`, the hot-parameter
// lane): one chunk of whole requests (n requests of k value hashes each)
// into the param step's packed host argument (engine/param.py: lines
// ROW_SLOT / ROW_ACQUIRE / ROW_THRESHOLD, `depth` index lines, the slim
// twin's, the head line) in ONE call with the GIL released. The contract is
// byte identity with the numpy path that stays as fallback and reference:
// `DefaultTokenService._param_rows` (three np.searchsorted with their clamp
// and equality test, `hash_indices` for the sketch and for the slim twin)
// and `pack_param_rows`. Every array is the caller's.

namespace {

// engine/param.py `hash_indices`: splitmix64's finaliser on hash + lane
// constant, modulo the width, all in wrapping uint64
constexpr uint64_t kMix = 0x9E3779B97F4A7C15ull;
constexpr uint64_t kFin1 = 0xBF58476D1CE4E5B9ull;
constexpr uint64_t kFin2 = 0x94D049BB133111EBull;

struct CellLanes {  // one sketch's index lines: `depth` lanes from `salt`
  int32_t *line;    // the first of them in the packed argument
  int depth;
  uint64_t salt, width, mask;  // mask: width - 1 where a power of two, else 0

  CellLanes(int32_t *first, int d, int64_t s, int64_t w)
      : line(first), depth(d), salt(uint64_t(s)), width(uint64_t(w)),
        mask((width & (width - 1)) == 0 ? width - 1 : 0) {}

  void write(uint64_t h, int64_t col, int64_t stride) const {
    for (int j = 0; j < depth; ++j) {
      uint64_t x = h + (salt + uint64_t(j) + 1) * kMix;
      x = (x ^ (x >> 30)) * kFin1;
      x = (x ^ (x >> 27)) * kFin2;
      x ^= x >> 31;
      line[j * stride + col] = int32_t(mask ? x & mask : x % width);
    }
  }
};

inline int32_t float_bits(const float *p) {
  int32_t bits;
  std::memcpy(&bits, p, sizeof bits);
  return bits;
}

}  // namespace

// The look-up snapshot of `_param_tables`: fids[n_rules] ascending with
// their slots and counts; item_hashes[n_hashes] ascending and unique;
// item_keys[n_items] ascending (slot * n_hashes + rank of the hash) with
// item_thr. The chunk: flow_ids[n], acq[n], hashes[n * k] request-major,
// n * k <= bucket, bucket >= 3. Writes req_slot[n] (-1: no rule) and every
// cell of packed[(4 + depth + slim_depth) * bucket]: a (request, value) row
// a column, slot -1 and zeros beyond the last row, the head line
// (0, k, n, 0...): the caller writes the clock. slim_depth 0: no slim lines.
SN_EXPORT void sn_param_prep(
    const int64_t *fids, const int32_t *slots, const float *counts,
    int64_t n_rules, const int64_t *item_hashes, int64_t n_hashes,
    const int64_t *item_keys, const float *item_thr, int64_t n_items,
    const int64_t *flow_ids, const int32_t *acq, const int64_t *hashes,
    int64_t n, int64_t k, int64_t bucket, int32_t depth, int64_t width,
    int32_t slim_depth, int64_t slim_width, int64_t slim_salt,
    int32_t *req_slot, int32_t *packed) {
  const int64_t rows = n * k;
  int32_t *p_slot = packed, *p_acq = packed + bucket;
  int32_t *p_thr = packed + 2 * bucket;
  const CellLanes fat(packed + 3 * bucket, depth, 0, width);
  const CellLanes slim(packed + (3 + depth) * bucket, slim_depth, slim_salt,
                       slim_width);
  // per request: its rule's slot, and that slot, the acquire and the rule's
  // count on each of its k columns
  for (int64_t at = 0; at < n; at += kLanes) {
    const int lanes = int(n - at < kLanes ? n - at : kLanes);
    int64_t pos[kLanes];
    if (n_rules) lower_bounds(fids, n_rules, flow_ids + at, lanes, pos);
    for (int l = 0; l < lanes; ++l) {
      const int64_t i = at + l, j = n_rules ? pos[l] : 0;
      const bool found = j < n_rules && fids[j] == flow_ids[i];
      const int32_t slot = found ? slots[j] : -1;
      const int32_t count = found ? float_bits(counts + j) : 0;
      req_slot[i] = slot;
      for (int64_t c = i * k; c < i * k + k; ++c) {
        p_slot[c] = slot;
        p_acq[c] = acq[i];
        p_thr[c] = count;
      }
    }
  }
  // per row: the item's threshold where (slot, hash) is an item, and the
  // sketch's cells
  for (int64_t at = 0; at < rows; at += kLanes) {
    const int lanes = int(rows - at < kLanes ? rows - at : kLanes);
    if (n_items) {
      int64_t rank[kLanes], key[kLanes], pos[kLanes];
      lower_bounds(item_hashes, n_hashes, hashes + at, lanes, rank);
      for (int l = 0; l < lanes; ++l)
        key[l] = int64_t(p_slot[at + l]) * n_hashes + rank[l];
      lower_bounds(item_keys, n_items, key, lanes, pos);
      for (int l = 0; l < lanes; ++l) {
        const bool known =
            rank[l] < n_hashes && item_hashes[rank[l]] == hashes[at + l];
        if (known && p_slot[at + l] >= 0 && pos[l] < n_items &&
            item_keys[pos[l]] == key[l])
          p_thr[at + l] = float_bits(item_thr + pos[l]);
      }
    }
    for (int l = 0; l < lanes; ++l) {
      const uint64_t h = uint64_t(hashes[at + l]);
      fat.write(h, at + l, bucket);
      slim.write(h, at + l, bucket);
    }
  }
  const int64_t lines = 4 + depth + slim_depth;
  for (int64_t c = rows; c < bucket; ++c) p_slot[c] = -1;
  for (int64_t line = 1; line < lines - 1; ++line)
    std::memset(packed + line * bucket + rows, 0,
                size_t(bucket - rows) * sizeof(int32_t));
  int32_t *head = packed + (lines - 1) * bucket;
  std::memset(head, 0, size_t(bucket) * sizeof(int32_t));
  head[1] = int32_t(k);
  head[2] = int32_t(n);
}

// ---------------------------------------------------------------------------
// Concurrent prep (cluster/concurrent.py `ConcurrentPlane.prep`, phase `prep`
// of the concurrency lane): one dispatch's acquire and release rows into the
// packed host arguments of its steps (engine/concurrent.py: lines ROW_SLOT /
// ROW_COUNT / ROW_TOK_SLOT / ROW_TOK_GEN / ROW_HEAD) in ONE call with the GIL
// released. The contract is byte identity with the numpy body that stays as
// fallback and reference: np.flatnonzero of the two kinds, the
// np.searchsorted look-up of the acquires' flows, `split_token_ids`, the two
// np.argsort(kind="stable") a step and `pack_concurrent_rows`. Every output
// is the caller's; only scratch lives here, per thread.

namespace {

constexpr int32_t kPadSlot = -2;  // engine.concurrent PAD_SLOT

struct ConcurrentScratch {
  std::vector<int32_t> acq_at, rel_at;      // row numbers, arrival order
  std::vector<int64_t> flow_ids;            // of the acquire rows
  std::vector<int32_t> slots;               // ... and their rule slots
};

}  // namespace

// keys[n_keys] ascending with their slots tab[n_keys] (the plane's look-up
// snapshot); ids / counts / is_release (bool bytes) of the dispatch's n rows:
// n_acq acquires of counts[i] on flow ids[i], n - n_acq releases of token
// ids[i]; max_tokens: the ring's slots (`split_token_ids`). plan[n_steps][6]:
// a step's acquires [a_lo, a_hi) and releases [r_lo, r_hi), each counted
// within its kind in arrival order, its bucket (>= 3, and no narrower than
// either run) and the address of its int32[5 * bucket] argument. Writes every
// cell of every argument (the clock 0: the caller writes it), and per kind
// the row numbers in the order of the steps' lines: acq_rows[n_acq], each
// step's run sorted by slot (-1, no rule, first), rel_rows[n - n_acq], each
// step's run sorted by token id; ties in arrival order. Returns 0, or -1
// where n_acq is not the number of acquire rows (nothing usable is written).
SN_EXPORT int32_t sn_concurrent_prep(
    const int64_t *keys, const int32_t *tab, int64_t n_keys,
    const int64_t *ids, const int32_t *counts, const uint8_t *is_release,
    int64_t n, int64_t n_acq, int64_t max_tokens, const int64_t *plan,
    int64_t n_steps, int64_t *acq_rows, int64_t *rel_rows) {
  static thread_local ConcurrentScratch scratch;
  scratch.acq_at.resize(size_t(n));
  scratch.rel_at.resize(size_t(n));
  scratch.flow_ids.resize(size_t(n));
  scratch.slots.resize(size_t(n));
  int32_t *acq_at = scratch.acq_at.data(), *rel_at = scratch.rel_at.data();
  int64_t *flow_ids = scratch.flow_ids.data();
  int32_t *slots = scratch.slots.data();
  int64_t seen = 0;
  for (int64_t i = 0, r = 0; i < n; ++i) {
    if (is_release[i]) {
      rel_at[r++] = int32_t(i);
    } else {
      flow_ids[seen] = ids[i];
      acq_at[seen++] = int32_t(i);
    }
  }
  if (seen != n_acq) return -1;  // before anything of the caller's is written
  lookup_slots(keys, tab, n_keys, flow_ids, n_acq, slots);
  for (int64_t s = 0; s < n_steps; ++s) {
    const int64_t *step = plan + 6 * s;
    const int64_t a_lo = step[0], a = step[1] - a_lo;
    const int64_t r_lo = step[2], r = step[3] - r_lo;
    const int64_t bucket = step[4];
    int32_t *p_slot = reinterpret_cast<int32_t *>(uintptr_t(step[5]));
    int32_t *p_count = p_slot + bucket, *p_tok_slot = p_slot + 2 * bucket;
    int32_t *p_tok_gen = p_slot + 3 * bucket, *head = p_slot + 4 * bucket;
    // the acquires, grouped by slot
    int32_t top = -1;
    for (int64_t k = 0; k < a; ++k)
      if (slots[a_lo + k] > top) top = slots[a_lo + k];
    const int32_t *order = radix_order(
        a, key_bits(uint32_t(top) + 1),
        [&](int64_t k) { return uint32_t(slots[a_lo + k]) + 1; });
    for (int64_t k = 0; k < a; ++k) {
      const int64_t at = a_lo + order[k];
      p_slot[k] = slots[at];
      p_count[k] = counts[acq_at[at]];
      acq_rows[a_lo + k] = acq_at[at];
    }
    for (int64_t k = a; k < bucket; ++k) p_slot[k] = kPadSlot;
    std::memset(p_count + a, 0, size_t(bucket - a) * sizeof(int32_t));
    // the releases, sorted by token id: signed order is unsigned order with
    // the sign bit flipped, and the keys are taken from the run's least
    const uint64_t sign = uint64_t(1) << 63;
    auto token = [&](int64_t k) {
      return uint64_t(ids[rel_at[r_lo + k]]) ^ sign;
    };
    uint64_t least = ~uint64_t(0), most = 0;
    for (int64_t k = 0; k < r; ++k) {
      const uint64_t t = token(k);
      if (t < least) least = t;
      if (t > most) most = t;
    }
    order = radix_order(r, key_bits(r ? most - least : 0),
                        [&](int64_t k) { return token(k) - least; });
    for (int64_t k = 0; k < r; ++k) {
      const int32_t row = rel_at[r_lo + order[k]];
      // split_token_ids: slot -1, generation 0 for an id that is not one
      // (0, negative, or past what 31 bits of generation reach)
      const int64_t id = ids[row];
      const int64_t gen = id > 0 ? id / max_tokens : 0;
      const bool bad = id <= 0 || gen > INT32_MAX;
      p_tok_slot[k] = bad ? -1 : int32_t(id % max_tokens);
      p_tok_gen[k] = bad ? 0 : int32_t(gen);
      rel_rows[r_lo + k] = row;
    }
    for (int64_t k = r; k < bucket; ++k) p_tok_slot[k] = -1;
    std::memset(p_tok_gen + r, 0, size_t(bucket - r) * sizeof(int32_t));
    std::memset(head, 0, size_t(bucket) * sizeof(int32_t));
    head[1] = int32_t(a);
    head[2] = int32_t(r);
  }
  return 0;
}
