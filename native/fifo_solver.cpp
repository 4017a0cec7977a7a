// Native FIFO queue gang solver — the host-CPU lane of the batch
// solver (ops/batch_solver.py::solve_queue), for deployments without a
// TPU and for the bench's CPU fallback.
//
// Replicates the device solver's decisions BIT-EXACTLY (same capacity
// rule as reference capacity.go:36-75 with the negative-availability
// short-circuit; same first-priority driver choice binpack.go:60-87;
// same usage-subtraction quirk sparkpods.go:139-146): the parity suite
// (tests/test_native_fifo.py) runs the randomized differential against
// solve_queue for both tightly-pack and distribute-evenly.
//
// Design notes for the one-core host this runs on:
//  - per app, per-node capacity needs a floor-division per nonzero
//    executor dimension; int32/int32 division done in double is exact
//    (|numerator| < 2^31 and numerator = q*den ⟹ representable; a
//    non-integer quotient is ≥ 1/den > ulp away from any integer since
//    num·den < 2^52) and, unlike integer division, vectorizes.
//  - driver choice walks a rank-sorted candidate list (built once per
//    queue: driver_rank is constant) and computes the with-driver
//    capacity lazily — almost always a handful of probes instead of a
//    second full N-vector pass.
//  - all int32 arithmetic wraps exactly like XLA's (unsigned ops).
//
// C ABI via ctypes (k8s_spark_scheduler_tpu/native/fifo.py).

#include <algorithm>
#include <array>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <map>
#include <mutex>
#include <new>
#include <thread>
#include <vector>

namespace {

constexpr int kDims = 3;
constexpr int32_t kBig = 2147483647;  // batch_solver.BIG

inline int32_t wrap_sub(int32_t a, int32_t b) {
  return static_cast<int32_t>(static_cast<uint32_t>(a) -
                              static_cast<uint32_t>(b));
}

// Per-node executor capacity clamped to [0, k] (capacity.go:36-75 via
// batch_solver.node_capacity): zero-requirement dim is unbounded unless
// availability is negative; any value ≤ 0 clips to 0, so truncating
// division equals the device kernel's floor division after the clip.
// A negative requirement divides by 1 like the host's max(executor, 1)
// (unreachable with valid tensorized Resources, but the parity contract
// covers the whole int32 input domain).
inline int32_t clamped_cap(const int32_t* a, const int32_t* e, int32_t k) {
  int32_t cap = k;
  for (int j = 0; j < kDims; ++j) {
    int32_t c;
    if (e[j] == 0) {
      c = a[j] >= 0 ? kBig : 0;
    } else if (a[j] <= 0) {
      c = 0;
    } else {
      c = static_cast<int32_t>(static_cast<double>(a[j]) /
                               static_cast<double>(std::max(e[j], 1)));
    }
    cap = std::min(cap, c);
  }
  return std::max(cap, 0);
}

// Capacity pass, restructured dim-at-a-time (r5): one sweep per nonzero
// executor dimension over that dimension's availability plane, then a
// finalize sweep.  Measured 2.3x faster than the fused 3-dim loop at
// 10k nodes (/tmp-style A/B harness, NOTES_ROUND4 discipline): the
// single-dim loops vectorize cleanly where the fused body's register
// pressure defeated gcc, and the cap array stays L1/L2-resident between
// sweeps.  Division is reciprocal-multiply with an exact two-step
// integer correction: q0 = trunc(a * (1/e)) is within ±1 of floor(a/e)
// (abs error ≤ 2^31 * 2^-51 « 1/2), and the corrections pin q to the
// largest q with q*e ≤ a — exact floor.  floor == truncation for
// positive quotients; for negative quotients they differ, but every
// consumer clamps at 0 / keys on the sign, so only the sign of a
// non-positive capacity must match the fused pass (it does).
//
// Zero-requirement dims bound capacity only when the availability is
// already overdrawn: cap forced ≤ 0 (kZeroDimNeg) so the finalize clamp
// zeroes it — same observable result as the fused pass's explicit 0/-1.

// first nonzero dim: initializes cap = min(init, floor(a/e))
static inline void dim_first(const int32_t* a, int64_t nb, int32_t e,
                             int32_t init, int32_t* cap) {
  const int32_t d = std::max(e, 1);  // negative req divides by 1
  const double inv = 1.0 / static_cast<double>(d);
  for (int64_t i = 0; i < nb; ++i) {
    int32_t q = static_cast<int32_t>(static_cast<double>(a[i]) * inv);
    q += ((static_cast<int64_t>(q) + 1) * d <= a[i]);
    q -= (static_cast<int64_t>(q) * d > a[i]);
    cap[i] = std::min(init, q);
  }
}

// subsequent nonzero dims: cap = min(cap, floor(a/e))
static inline void dim_next(const int32_t* a, int64_t nb, int32_t e,
                            int32_t* cap) {
  const int32_t d = std::max(e, 1);
  const double inv = 1.0 / static_cast<double>(d);
  for (int64_t i = 0; i < nb; ++i) {
    int32_t q = static_cast<int32_t>(static_cast<double>(a[i]) * inv);
    q += ((static_cast<int64_t>(q) + 1) * d <= a[i]);
    q -= (static_cast<int64_t>(q) * d > a[i]);
    cap[i] = std::min(cap[i], q);
  }
}

// zero-requirement dim: negative availability forces cap non-positive
static inline void dim_zero_mask(const int32_t* a, int64_t nb,
                                 int32_t* cap) {
  for (int64_t i = 0; i < nb; ++i) cap[i] = a[i] >= 0 ? cap[i] : int32_t{-1};
}

// shared sweep plan: division dims then zero-dim masks, cap initialized
// to `init` (k for the clamped pass, kMfSent for min-frag)
static inline void cap_sweeps(const int32_t* a0, const int32_t* a1,
                              const int32_t* a2, int64_t nb,
                              const int32_t* e, int32_t init, int32_t* cap) {
  const int32_t* planes[kDims] = {a0, a1, a2};
  int nz[kDims], nnz = 0, zd[kDims], nzd = 0;
  for (int j = 0; j < kDims; ++j) {
    if (e[j] != 0) nz[nnz++] = j; else zd[nzd++] = j;
  }
  if (nnz == 0) {
    std::fill(cap, cap + nb, init);
  } else {
    dim_first(planes[nz[0]], nb, e[nz[0]], init, cap);
    for (int t = 1; t < nnz; ++t) dim_next(planes[nz[t]], nb, e[nz[t]], cap);
  }
  for (int t = 0; t < nzd; ++t) dim_zero_mask(planes[zd[t]], nb, cap);
}

// clamped capacity pass (solve_queue): cap in [0, k], Σ cap returned
int64_t cap_pass_all(const int32_t* a0, const int32_t* a1, const int32_t* a2,
                     const uint8_t* exec_ok, int64_t nb, const int32_t* e,
                     int32_t k, int32_t* cap) {
  cap_sweeps(a0, a1, a2, nb, e, k, cap);
  int64_t total = 0;
  for (int64_t i = 0; i < nb; ++i) {
    int32_t c = exec_ok[i] ? cap[i] : 0;
    c = std::max(c, 0);
    cap[i] = c;
    total += c;
  }
  return total;
}

// ---------------------------------------------------------------------------
// Minimal-fragmentation drain (minimal_fragmentation.go:59-137 semantics,
// matching ops/batch_adapter.minimal_fragmentation_from_capacities and —
// under the solver's MF sentinel guard — the device kernel
// batch_solver.min_frag_counts).
// ---------------------------------------------------------------------------

// Unbounded-capacity sentinel (the device kernel's batch_solver.MF_SENT):
// callers hold the mf_sentinel_safe guard (scaled availabilities ≤
// MF_SENT − 1), so a real capacity can never collide with it and the
// explicit has-sentinel subset rule below equals the host decode's
// 2^62-sentinel (k + max)/2 formula.
constexpr int32_t kMfSent = 2147483646;

inline int64_t floor_div32(int32_t a, int32_t b) {  // b > 0
  return a >= 0 ? a / b : -((-(int64_t)a + b - 1) / b);
}

// UNCLAMPED per-node capacity for the min-frag drain (capacity.go:36-75:
// floor division per dim; zero-requirement dim unbounded unless the
// availability is already negative; negative requirement divides by 1).
inline int32_t mf_cap_one(int32_t a0, int32_t a1, int32_t a2,
                          const int32_t* e) {
  const int32_t a[kDims] = {a0, a1, a2};
  int64_t cap = kMfSent;
  for (int j = 0; j < kDims; ++j) {
    int64_t c;
    if (e[j] == 0) {
      c = a[j] >= 0 ? kMfSent : 0;
    } else {
      c = floor_div32(a[j], std::max(e[j], 1));
    }
    cap = std::min(cap, c);
  }
  return static_cast<int32_t>(std::max<int64_t>(cap, 0));
}

// Whole-axis min-frag capacity pass, built on the shared dim-at-a-time
// sweeps (cap_sweeps with a kMfSent init).  Writes UNCLAMPED exact-floor
// capacities (values ≤ 0 mean ineligible) and returns Σ clamp(c, 0, k),
// the tightly feasibility total, so the min-frag queue step needs no
// separate feasibility pass over the node axis.
// Branchless extremes of a capacity vector, folded into the pass (and
// recomputable standalone after the driver-node fix-up): the max, the
// smallest capacity ≥ k, and the smallest positive capacity.  These
// three values decide the whole min-frag attempt structure (see
// mf_assign); the standalone scan vectorizes fully (~0.3 us at 10k
// nodes), so it runs after the driver-node fix-up rather than fused
// into the pass (where the extra accumulators break vectorization).
struct MfExtremes {
  int32_t maxc = 0;
  int32_t min_ge = kBig;   // min capacity ≥ k (kBig = none)
  int32_t min_pos = kBig;  // min capacity > 0 (kBig = none)
};

int64_t mf_cap_pass_all(const int32_t* a0, const int32_t* a1,
                        const int32_t* a2, const uint8_t* elig, int64_t nb,
                        const int32_t* e, int32_t k, int32_t* cap) {
  cap_sweeps(a0, a1, a2, nb, e, kMfSent, cap);
  int64_t total = 0;
  for (int64_t i = 0; i < nb; ++i) {
    int32_t c = elig[i] ? cap[i] : 0;
    cap[i] = c;
    total += std::clamp<int32_t>(c, 0, k);
  }
  return total;
}

// pure single-accumulator reductions vectorize; the fused 3-accumulator
// select loop does not (measured 20 us vs 3.6 us at 10k — r5 A/B), so
// the conditional mins are a select MAP into scratch followed by a pure
// min REDUCE.
__attribute__((noinline)) int32_t reduce_max(const int32_t* p, int64_t n) {
  int32_t m = 0;
  for (int64_t i = 0; i < n; ++i) m = std::max(m, p[i]);
  return m;
}

__attribute__((noinline)) int32_t reduce_min(const int32_t* p, int64_t n) {
  int32_t m = kBig;
  for (int64_t i = 0; i < n; ++i) m = std::min(m, p[i]);
  return m;
}

MfExtremes mf_extremes(const std::vector<int32_t>& caps, int32_t k,
                       std::vector<int32_t>& scratch) {
  MfExtremes ext;
  const int32_t* p = caps.data();
  const int64_t n = static_cast<int64_t>(caps.size());
  scratch.resize(n);
  int32_t* s = scratch.data();
  ext.maxc = reduce_max(p, n);
  for (int64_t i = 0; i < n; ++i) s[i] = p[i] >= k ? p[i] : kBig;
  ext.min_ge = reduce_min(s, n);
  for (int64_t i = 0; i < n; ++i) s[i] = p[i] > 0 ? p[i] : kBig;
  ext.min_pos = reduce_min(s, n);
  return ext;
}

// (node, executors-placed) segments in DRAIN order — the reference's
// placement list order, which the single-AZ zone score consumes as the
// occurrence sequence.  Nodes are unique across segments.
using MfSegs = std::vector<std::pair<int32_t, int64_t>>;

// minimal_fragmentation.go:96-137 WITHOUT the sort: the ascending-order
// drain only ever consults (a) the first sorted entry with cap ≥ k —
// i.e. the smallest such capacity, earliest node among equals — and
// (b) the max-capacity class in node order, so two O(N) scans per drain
// round replace the O(N log N) sort (a 10k-node sort per app dominated
// the whole queue pass).  Round count is bounded by the number of fully
// drained classes, itself ≤ k.  `caps` is by-node (≤ 0 = ineligible)
// and is consumed (drained entries zeroed).
bool mf_drain(std::vector<int32_t>& caps, int64_t k, MfSegs& segs) {
  const int64_t nb = static_cast<int64_t>(caps.size());
  while (true) {
    int64_t best = -1;
    int32_t best_cap = 0, maxc = 0;
    for (int64_t i = 0; i < nb; ++i) {
      const int32_t c = caps[i];
      if (c <= 0) continue;
      if (c >= k && (best < 0 || c < best_cap)) {
        best = i;
        best_cap = c;
      }
      if (c > maxc) maxc = c;
    }
    if (best >= 0) {  // first node that can fit everything that's left
      segs.emplace_back(static_cast<int32_t>(best), k);
      return true;
    }
    if (maxc <= 0) return false;
    // drain the max-capacity class in node order
    for (int64_t i = 0; i < nb && k >= maxc; ++i) {
      if (caps[i] == maxc) {
        segs.emplace_back(static_cast<int32_t>(i), maxc);
        k -= maxc;
        caps[i] = 0;
      }
    }
    if (k == 0) return true;
  }
}

// Scratch for the bucketed drain, reused across apps (allocation-free
// steady state).
struct MfScratch {
  std::vector<int32_t> bucket_count;   // per capacity value in [1, k)
  std::vector<int32_t> bucket_offset;  // cursor into nodes (consumed prefix)
  std::vector<int32_t> bucket_end;
  std::vector<int32_t> nodes;          // bucket-grouped node ids, node order
  std::vector<int32_t> copy;           // fallback for the scan drain
};

// bucket-capped drain: every capacity entering a drain is < k (a cap
// ≥ k resolves on the instant-fit probe before any draining), so a
// counting sort by value gives O(nb + k) rounds-free access to both
// "smallest capacity ≥ remainder" and "max class in node order".
// `in_subset(c)` selects the eligible entries.
template <typename Pred>
bool mf_drain_bucketed(const std::vector<int32_t>& caps, int64_t k,
                       Pred in_subset, MfScratch& ws, MfSegs& segs) {
  const int64_t nb = static_cast<int64_t>(caps.size());
  const int64_t kb = k;  // bucket domain: values 1..k-1
  ws.bucket_count.assign(kb, 0);
  for (int64_t i = 0; i < nb; ++i) {
    const int32_t c = caps[i];
    if (c > 0 && in_subset(c)) ++ws.bucket_count[c];  // c < k guaranteed
  }
  ws.bucket_offset.resize(kb);
  ws.bucket_end.resize(kb);
  int32_t total_nodes = 0;
  for (int64_t v = 1; v < kb; ++v) {
    ws.bucket_offset[v] = total_nodes;
    total_nodes += ws.bucket_count[v];
    ws.bucket_end[v] = total_nodes;
  }
  if (total_nodes == 0) return false;
  ws.nodes.resize(total_nodes);
  {
    std::vector<int32_t>& cursor = ws.bucket_count;  // reuse as fill cursor
    for (int64_t v = 1; v < kb; ++v) cursor[v] = ws.bucket_offset[v];
    for (int64_t i = 0; i < nb; ++i) {
      const int32_t c = caps[i];
      if (c > 0 && in_subset(c)) ws.nodes[cursor[c]++] = static_cast<int32_t>(i);
    }
  }
  int64_t rem = k;
  int64_t maxv = kb - 1;
  while (true) {
    while (maxv >= 1 && ws.bucket_offset[maxv] == ws.bucket_end[maxv]) --maxv;
    if (maxv < 1) return false;
    // instant fit: smallest unconsumed capacity ≥ rem, earliest node
    if (rem <= maxv) {
      int64_t v = rem;
      while (ws.bucket_offset[v] == ws.bucket_end[v]) ++v;  // ≤ maxv by above
      segs.emplace_back(ws.nodes[ws.bucket_offset[v]], rem);
      return true;
    }
    // drain the max class in node order while rem ≥ maxv
    while (rem >= maxv && ws.bucket_offset[maxv] != ws.bucket_end[maxv]) {
      segs.emplace_back(ws.nodes[ws.bucket_offset[maxv]++], maxv);
      rem -= maxv;
    }
    if (rem == 0) return true;
  }
}

// minimal_fragmentation.go:71-94: the avoid-mostly-empty-nodes subset
// attempt (capacities < (k + max)/2), then the full set.  The attempt
// structure is decided entirely from the pass's branchless extremes:
//  - subset first probe = smallest capacity ≥ k *within* the subset.
//    The overall smallest capacity ≥ k (min_ge) IS that winner whenever
//    min_ge < target (subset candidates are a subset of the ≥ k
//    candidates, all ≥ min_ge, and the min_ge node itself qualifies);
//    if min_ge ≥ target the subset has no ≥ k member at all.
//  - subset non-empty ⟺ the smallest positive capacity < target.
//  - entering a drain implies every eligible capacity < k, so the
//    counting-bucket drain applies (O(nb + k), copy-free).
// Only the fast-path placement needs a further scan: find the earliest
// node holding the winning capacity value.
bool mf_assign(const std::vector<int32_t>& caps_by_node, int64_t k,
               const MfExtremes& ext, MfScratch& ws, MfSegs& segs) {
  segs.clear();
  if (k <= 0 || ext.maxc <= 0) return false;

  // a sentinel present makes the subset "every bounded node" and the
  // attempt unconditional (min_frag_counts' has_sent rule — identical
  // to the host's (k + 2^62)/2 threshold)
  const bool has_sent = ext.maxc == kMfSent;
  const bool attempt_subset = has_sent || k < ext.maxc;
  const int64_t target =
      has_sent
          ? static_cast<int64_t>(kMfSent)
          : (attempt_subset ? (k + static_cast<int64_t>(ext.maxc)) / 2 : 0);

  auto place_first_with = [&](int32_t value) {
    // blocked any-match (the fixed-length inner loop vectorizes; an
    // early-exit elementwise scan would not)
    const int64_t nb = static_cast<int64_t>(caps_by_node.size());
    const int32_t* caps = caps_by_node.data();
    constexpr int64_t B = 256;
    int64_t i = 0;
    for (; i + B <= nb; i += B) {
      bool any = false;
      for (int64_t j = i; j < i + B; ++j) any |= caps[j] == value;
      if (any) break;
    }
    for (; i < nb; ++i) {
      if (caps[i] == value) {
        segs.emplace_back(static_cast<int32_t>(i), k);
        return;
      }
    }
  };

  const bool have_ge = ext.min_ge != kBig && ext.min_ge >= k;
  if (attempt_subset) {
    if (have_ge && ext.min_ge < target) {
      place_first_with(ext.min_ge);
      return true;
    }
    const bool sub_any = ext.min_pos != kBig && ext.min_pos < target;
    if (sub_any) {
      // no subset capacity is ≥ k here (min_ge ≥ target or none)
      bool ok;
      if (k < (int64_t{1} << 16)) {
        ok = mf_drain_bucketed(caps_by_node, k,
                               [&](int32_t c) { return c < target; }, ws,
                               segs);
      } else {
        ws.copy = caps_by_node;
        for (int32_t& c : ws.copy) {
          if (c >= target) c = 0;
        }
        ok = mf_drain(ws.copy, k, segs);
      }
      if (ok) return true;
      segs.clear();
    }
  }
  if (have_ge) {
    place_first_with(ext.min_ge);
    return true;
  }
  if (k < (int64_t{1} << 16)) {
    return mf_drain_bucketed(caps_by_node, k, [](int32_t) { return true; },
                             ws, segs);
  }
  ws.copy = caps_by_node;
  return mf_drain(ws.copy, k, segs);
}

// ---------------------------------------------------------------------------
// Sharded capacity pass — the cold-solve fallback of the delta-solve
// session (ops/deltasolve.py).  The per-app capacity pass is the only
// O(nodes) cost with no carry dependency, so it shards cleanly: each
// worker runs the dim-at-a-time sweeps over a contiguous node range and
// reports a partial total; the caller sums partials in shard order, so
// results are BIT-identical to the serial pass (per-node caps are
// independent, int64 partial sums are exact).  Dispatch is condvar
// wake + condvar completion, never spinning: on an oversubscribed or
// single-core host idle workers cost nothing.  The pool only engages
// when the session was loaded with n_threads > 1 AND the node axis is
// long enough that the ~10us dispatch round-trip amortizes (the
// min_pool_nodes load parameter; at 10k nodes a pass is ~20us, at 100k
// ~200us — the pool is for the latter).
// ---------------------------------------------------------------------------

constexpr int kMaxPoolThreads = 8;

struct CapTask {
  const int32_t* a0;
  const int32_t* a1;
  const int32_t* a2;
  const uint8_t* elig;
  const int32_t* e;
  int32_t k;
  int mode;  // 0 = clamped [0,k] (solve_queue); 1 = unclamped min-frag
  int32_t* cap;
  int64_t* totals;  // [shards] partial totals, summed in shard order
  int64_t nb;
  int shards;
};

void cap_task_shard(const CapTask& t, int shard) {
  const int64_t lo = t.nb * shard / t.shards;
  const int64_t hi = t.nb * (shard + 1) / t.shards;
  if (hi <= lo) {
    t.totals[shard] = 0;
    return;
  }
  const int32_t init = t.mode == 0 ? t.k : kMfSent;
  cap_sweeps(t.a0 + lo, t.a1 + lo, t.a2 + lo, hi - lo, t.e, init, t.cap + lo);
  int64_t total = 0;
  if (t.mode == 0) {
    for (int64_t i = lo; i < hi; ++i) {
      int32_t c = t.elig[i] ? t.cap[i] : 0;
      c = std::max(c, 0);
      t.cap[i] = c;
      total += c;
    }
  } else {
    for (int64_t i = lo; i < hi; ++i) {
      int32_t c = t.elig[i] ? t.cap[i] : 0;
      t.cap[i] = c;
      total += std::clamp<int32_t>(c, 0, t.k);
    }
  }
  t.totals[shard] = total;
}

class SweepPool {
 public:
  explicit SweepPool(int workers) : n_(std::max(workers, 1)) {
    for (int w = 1; w < n_; ++w) {
      threads_.emplace_back([this, w] { worker(w); });
    }
  }

  ~SweepPool() {
    {
      std::lock_guard<std::mutex> g(m_);
      stop_ = true;
    }
    cv_work_.notify_all();
    for (auto& t : threads_) t.join();
  }

  int workers() const { return n_; }

  // Runs cap_task_shard for every shard; the caller thread takes shard 0
  // and blocks until all workers report done.
  void run(const CapTask& t) {
    if (n_ <= 1) {
      cap_task_shard(t, 0);
      return;
    }
    {
      std::lock_guard<std::mutex> g(m_);
      task_ = &t;
      ++gen_;
      pending_ = n_ - 1;
    }
    cv_work_.notify_all();
    cap_task_shard(t, 0);
    std::unique_lock<std::mutex> g(m_);
    cv_done_.wait(g, [this] { return pending_ == 0; });
    task_ = nullptr;
  }

 private:
  void worker(int w) {
    uint64_t seen = 0;
    std::unique_lock<std::mutex> g(m_);
    for (;;) {
      cv_work_.wait(g, [&] { return stop_ || gen_ != seen; });
      if (stop_) return;
      seen = gen_;
      const CapTask* t = task_;
      g.unlock();
      cap_task_shard(*t, w);
      g.lock();
      if (--pending_ == 0) cv_done_.notify_one();
    }
  }

  const int n_;
  std::vector<std::thread> threads_;
  std::mutex m_;
  std::condition_variable cv_work_, cv_done_;
  const CapTask* task_ = nullptr;
  uint64_t gen_ = 0;
  int pending_ = 0;
  bool stop_ = false;
};

// Serial when pool is null / single-worker, sharded otherwise; the two
// produce identical caps and totals (see CapTask notes).
int64_t cap_pass_sharded(SweepPool* pool, int mode, const int32_t* a0,
                         const int32_t* a1, const int32_t* a2,
                         const uint8_t* elig, int64_t nb, const int32_t* e,
                         int32_t k, int32_t* cap) {
  if (pool == nullptr || pool->workers() <= 1) {
    return mode == 0 ? cap_pass_all(a0, a1, a2, elig, nb, e, k, cap)
                     : mf_cap_pass_all(a0, a1, a2, elig, nb, e, k, cap);
  }
  int64_t totals[kMaxPoolThreads] = {0};
  CapTask t{a0, a1, a2, elig, e,  k,
            mode, cap, totals, nb, pool->workers()};
  pool->run(t);
  int64_t total = 0;
  for (int s = 0; s < t.shards; ++s) total += totals[s];
  return total;
}

// ---------------------------------------------------------------------------
// Shared per-app queue step — ONE implementation of the FIFO step for
// both the stateless entry points (fifo_solve_queue /
// fifo_solve_queue_minfrag) and the persistent session below, so the
// session's warm-resume decisions are bit-identical to a cold solve by
// construction, not by parallel maintenance of two loops.
// ---------------------------------------------------------------------------

struct QueueScratch {
  std::vector<int32_t> cap;      // clamped capacities (plain policies)
  std::vector<int32_t> mf_caps;  // unclamped min-frag capacities
  MfScratch mf_ws;
  MfSegs segs;
};

std::vector<int32_t> build_cand(const int32_t* driver_rank, int64_t nb) {
  std::vector<int32_t> cand;
  cand.reserve(nb);
  for (int64_t i = 0; i < nb; ++i) {
    if (driver_rank[i] < kBig) cand.push_back(static_cast<int32_t>(i));
  }
  std::sort(cand.begin(), cand.end(), [&](int32_t x, int32_t y) {
    return driver_rank[x] < driver_rank[y];
  });
  return cand;
}

// Optional per-step usage capture for the provenance explainer
// (fifo_explain_queue): how many nodes hosted executors (each loses one
// executor row — the sparkpods.go:139-146 quirk) and whether the driver
// row was applied separately.  nullptr (every hot-path caller) costs one
// pointer test per app — zero observable cost when provenance is off.
struct StepUsage {
  int32_t hosting_nodes = 0;
  int32_t driver_row_applied = 0;
};

// One tightly/evenly FIFO step: capacity pass + first-rank driver probe
// + the usage-subtraction quirk.  Mutates the planes on success.
// Returns the driver index or -1 (infeasible, planes untouched).
int32_t step_app_plain(int32_t* a0, int32_t* a1, int32_t* a2,
                       const uint8_t* exec_ok, int64_t nb,
                       const std::vector<int32_t>& cand, const int32_t* d,
                       const int32_t* e, int32_t k, int evenly,
                       QueueScratch& ws, SweepPool* pool,
                       StepUsage* usage = nullptr) {
  int32_t* cap = ws.cap.data();
  int64_t total =
      cap_pass_sharded(pool, 0, a0, a1, a2, exec_ok, nb, e, k, cap);
  int32_t didx = -1;
  int32_t capd = 0;
  if (total >= k) {
    for (int32_t i : cand) {
      int32_t a[kDims] = {a0[i], a1[i], a2[i]};
      if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
      int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
      if (total - cap[i] + cwd >= k) {
        didx = i;
        capd = cwd;
        break;
      }
    }
  }
  if (didx < 0) return -1;
  auto sub_exec = [&](int64_t i) {
    a0[i] = wrap_sub(a0[i], e[0]);
    a1[i] = wrap_sub(a1[i], e[1]);
    a2[i] = wrap_sub(a2[i], e[2]);
  };
  bool driver_hosts_exec = false;
  int32_t hosts = 0;
  if (evenly) {
    // hosting nodes = first k capacity-bearing nodes in node order
    int32_t placed = 0;
    for (int64_t i = 0; i < nb && placed < k; ++i) {
      int32_t c = (i == didx) ? capd : cap[i];
      if (c <= 0) continue;
      ++placed;
      ++hosts;
      if (i == didx) driver_hosts_exec = true;
      sub_exec(i);
    }
  } else {
    // tightly-pack: greedy fill in node order until k executors sit
    int64_t cum = 0;
    for (int64_t i = 0; i < nb && cum < k; ++i) {
      int32_t c = (i == didx) ? capd : cap[i];
      if (c <= 0) continue;
      cum += c;
      ++hosts;
      if (i == didx) driver_hosts_exec = true;
      sub_exec(i);
    }
  }
  if (!driver_hosts_exec) {
    a0[didx] = wrap_sub(a0[didx], d[0]);
    a1[didx] = wrap_sub(a1[didx], d[1]);
    a2[didx] = wrap_sub(a2[didx], d[2]);
  }
  if (usage != nullptr) {
    usage->hosting_nodes = hosts;
    usage->driver_row_applied = driver_hosts_exec ? 0 : 1;
  }
  return didx;
}

// One minimal-fragmentation FIFO step (fifo_solve_queue_minfrag body).
int32_t step_app_minfrag(int32_t* a0, int32_t* a1, int32_t* a2,
                         const uint8_t* exec_ok, int64_t nb,
                         const std::vector<int32_t>& cand, const int32_t* d,
                         const int32_t* e, int32_t k, QueueScratch& ws,
                         SweepPool* pool, StepUsage* usage = nullptr) {
  int32_t* caps = ws.mf_caps.data();
  // ONE pass yields both the UNCLAMPED min-frag capacities and the
  // tightly feasibility total sum(clamp(c, 0, k))
  int64_t total =
      cap_pass_sharded(pool, 1, a0, a1, a2, exec_ok, nb, e, k, caps);
  int32_t didx = -1;
  if (total >= k) {
    for (int32_t i : cand) {
      int32_t a[kDims] = {a0[i], a1[i], a2[i]};
      if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
      int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
      if (total - std::clamp<int32_t>(caps[i], 0, k) + cwd >= k) {
        didx = i;
        break;
      }
    }
  }
  if (didx < 0) return -1;

  // min-frag placement with the driver subtracted on its node — only
  // the driver node's capacity differs from the fused pass
  if (exec_ok[didx]) {
    int32_t av[kDims];
    av[0] = wrap_sub(a0[didx], d[0]);
    av[1] = wrap_sub(a1[didx], d[1]);
    av[2] = wrap_sub(a2[didx], d[2]);
    caps[didx] = mf_cap_one(av[0], av[1], av[2], e);
  }
  bool placed_any =
      k > 0 && mf_assign(ws.mf_caps, k,
                         mf_extremes(ws.mf_caps, k, ws.mf_ws.copy), ws.mf_ws,
                         ws.segs);

  // usage subtraction quirk: one executor's worth per hosting node,
  // the driver row on its node unless it also hosts executors
  bool driver_hosts_exec = false;
  if (placed_any) {
    for (const auto& seg : ws.segs) {
      const int32_t i = seg.first;
      if (i == didx) driver_hosts_exec = true;
      a0[i] = wrap_sub(a0[i], e[0]);
      a1[i] = wrap_sub(a1[i], e[1]);
      a2[i] = wrap_sub(a2[i], e[2]);
    }
  }
  if (!driver_hosts_exec) {
    a0[didx] = wrap_sub(a0[didx], d[0]);
    a1[didx] = wrap_sub(a1[didx], d[1]);
    a2[didx] = wrap_sub(a2[didx], d[2]);
  }
  if (usage != nullptr) {
    // MfSegs nodes are unique across segments, so the segment count IS
    // the hosting-node count
    usage->hosting_nodes =
        placed_any ? static_cast<int32_t>(ws.segs.size()) : 0;
    usage->driver_row_applied = driver_hosts_exec ? 0 : 1;
  }
  return didx;
}

void split_planes(const int32_t* rows, int64_t nb, std::vector<int32_t>& a0,
                  std::vector<int32_t>& a1, std::vector<int32_t>& a2) {
  a0.resize(nb);
  a1.resize(nb);
  a2.resize(nb);
  for (int64_t i = 0; i < nb; ++i) {
    a0[i] = rows[i * kDims + 0];
    a1[i] = rows[i * kDims + 1];
    a2[i] = rows[i * kDims + 2];
  }
}

void join_planes(const std::vector<int32_t>& a0, const std::vector<int32_t>& a1,
                 const std::vector<int32_t>& a2, int64_t nb, int32_t* rows) {
  for (int64_t i = 0; i < nb; ++i) {
    rows[i * kDims + 0] = a0[i];
    rows[i * kDims + 1] = a1[i];
    rows[i * kDims + 2] = a2[i];
  }
}

// ---------------------------------------------------------------------------
// Equivalence-class compressed stepping (ROADMAP 2: the Firmament /
// Borg-style node-aggregation relaxation).  Real fleets have a few
// dozen machine shapes, so most of the per-app O(nodes) capacity pass
// recomputes identical divisions.  The class solver partitions nodes by
// EXACT (avail triple, exec_ok) equality, evaluates each capacity
// formula once per class, and weights by multiplicity.  Nodes whose
// planes diverge from their class representative (because a placement
// wrote them) move to a small sorted overlay evaluated per node; when
// the overlay outgrows nb/32 the partition is rebuilt in one O(nb)
// hash pass.
//
// Parity is by construction, not by approximation:
//  - the planes stay authoritative — every plane read (driver probe,
//    subtraction, checkpointing) is the row solver's exact read;
//  - live class members share the representative triple EXACTLY, so
//    the per-class capacity equals the per-row capacity;
//  - fills and drains walk merged per-class member cursors + the
//    overlay in ascending node order — the same node visit order as
//    the row loops — and bind concrete node ids at that moment
//    (deterministic bind-time expansion);
//  - min-frag class values come from mf_cap_one (clamped at 0), which
//    is observationally equivalent to the row pass's unclamped
//    negatives: every consumer filters on c > 0 / c >= k / equality
//    with a positive value.
// The property suite (tests/test_class_compression.py) re-verifies the
// byte-identity across seeds, policies, and session lanes.
// ---------------------------------------------------------------------------

inline uint64_t class_hash(int32_t a0, int32_t a1, int32_t a2, uint8_t e) {
  uint64_t h = static_cast<uint32_t>(a0);
  h = h * 0x9E3779B97F4A7C15ull + static_cast<uint32_t>(a1);
  h = h * 0x9E3779B97F4A7C15ull + static_cast<uint32_t>(a2);
  h = h * 0x9E3779B97F4A7C15ull + e;
  h ^= h >> 29;
  h *= 0xBF58476D1CE4E5B9ull;
  h ^= h >> 32;
  return h;
}

struct ClassSolver {
  struct Cls {
    int32_t a[kDims];
    uint8_t eok = 0;
    int32_t live = 0;                // members whose planes still match a[]
    std::vector<int32_t> members;    // ascending node ids (dead ones are
                                     // skipped via node_cls mismatch)
  };
  std::vector<Cls> classes;
  std::vector<int32_t> node_cls;  // node -> class id, -1 = overlay (diverged)
  std::vector<int32_t> ov_nodes;  // ascending diverged node ids
  // open-addressing hash over class keys (power-of-two table)
  std::vector<int32_t> table;
  uint64_t mask = 0;
  int64_t nb = 0;
  int64_t ov_limit = 0;
  // per-app scratch (allocation-free steady state)
  std::vector<int32_t> cls_caps;   // per-class capacity value
  std::vector<int32_t> ov_caps;    // per-overlay-entry capacity value
  std::vector<size_t> cls_cur;     // per-class member cursor (fills)
  std::vector<int32_t> newly;      // nodes written by the current app
  std::vector<int32_t> merge_tmp;  // fresh overlay ids to splice in
  std::vector<std::pair<int32_t, int32_t>> heap;  // (node, source) min-heap
  // compression evidence for the bench lane / session stats
  int64_t classes_last = 0;  // class count at the most recent rebuild
  int64_t rebuilds = 0;
  int64_t ov_peak = 0;
};

void class_rebuild(ClassSolver& cs, const int32_t* a0, const int32_t* a1,
                   const int32_t* a2, const uint8_t* eok, int64_t nb) {
  cs.nb = nb;
  cs.classes.clear();
  cs.node_cls.assign(nb, -1);
  cs.ov_nodes.clear();
  uint64_t want = 16;
  while (want < static_cast<uint64_t>(nb) * 2) want <<= 1;
  cs.table.assign(want, -1);
  cs.mask = want - 1;
  for (int64_t i = 0; i < nb; ++i) {
    uint64_t slot = class_hash(a0[i], a1[i], a2[i], eok[i]) & cs.mask;
    int32_t id = -1;
    while (true) {
      const int32_t t = cs.table[slot];
      if (t < 0) break;
      const ClassSolver::Cls& c = cs.classes[t];
      if (c.a[0] == a0[i] && c.a[1] == a1[i] && c.a[2] == a2[i] &&
          c.eok == eok[i]) {
        id = t;
        break;
      }
      slot = (slot + 1) & cs.mask;
    }
    if (id < 0) {
      id = static_cast<int32_t>(cs.classes.size());
      ClassSolver::Cls c;
      c.a[0] = a0[i];
      c.a[1] = a1[i];
      c.a[2] = a2[i];
      c.eok = eok[i];
      cs.classes.push_back(std::move(c));
      cs.table[slot] = id;
    }
    cs.classes[id].members.push_back(static_cast<int32_t>(i));
    ++cs.classes[id].live;
    cs.node_cls[i] = id;
  }
  // rebuild threshold: a rebuild is one O(nb) hash pass (~1 ms at
  // 100k), while every app pays O(overlay) — nb/64 keeps the mean
  // overlay cost below the per-app class pass without rebuild churn
  cs.ov_limit = std::max<int64_t>(int64_t{512}, nb / 64);
  cs.classes_last = static_cast<int64_t>(cs.classes.size());
  ++cs.rebuilds;
}

// Node's capacity under the current per-class / per-overlay values
// (driver-probe read: identical to the row pass's cap[i] because live
// members share the representative triple exactly).
inline int32_t class_cap_at(const ClassSolver& cs, int32_t i) {
  const int32_t c = cs.node_cls[i];
  if (c >= 0) return cs.cls_caps[c];
  const auto it =
      std::lower_bound(cs.ov_nodes.begin(), cs.ov_nodes.end(), i);
  return cs.ov_caps[static_cast<size_t>(it - cs.ov_nodes.begin())];
}

// Fold the nodes the current app wrote into the overlay (they diverged
// from their class representative); rebuild the whole partition once
// the overlay outgrows its bound.  `newly` holds unique node ids.
void class_absorb(ClassSolver& cs, const int32_t* a0, const int32_t* a1,
                  const int32_t* a2, const uint8_t* eok) {
  if (cs.newly.empty()) return;
  std::sort(cs.newly.begin(), cs.newly.end());
  cs.merge_tmp.clear();
  for (const int32_t i : cs.newly) {
    const int32_t c = cs.node_cls[i];
    if (c < 0) continue;  // already diverged in an earlier step
    cs.node_cls[i] = -1;
    --cs.classes[c].live;
    cs.merge_tmp.push_back(i);
  }
  cs.newly.clear();
  if (cs.merge_tmp.empty()) return;
  const size_t before = cs.ov_nodes.size();
  cs.ov_nodes.insert(cs.ov_nodes.end(), cs.merge_tmp.begin(),
                     cs.merge_tmp.end());
  std::inplace_merge(cs.ov_nodes.begin(),
                     cs.ov_nodes.begin() + static_cast<int64_t>(before),
                     cs.ov_nodes.end());
  cs.ov_peak =
      std::max(cs.ov_peak, static_cast<int64_t>(cs.ov_nodes.size()));
  if (static_cast<int64_t>(cs.ov_nodes.size()) > cs.ov_limit) {
    class_rebuild(cs, a0, a1, a2, eok, cs.nb);
  }
}

// One tightly/evenly FIFO step over the class partition — same contract
// as step_app_plain (mutates planes on success, returns didx or -1) and
// byte-identical verdicts/planes by construction.
int32_t step_app_plain_classes(ClassSolver& cs, int32_t* a0, int32_t* a1,
                               int32_t* a2, const uint8_t* exec_ok,
                               int64_t nb, const std::vector<int32_t>& cand,
                               const int32_t* d, const int32_t* e, int32_t k,
                               int evenly) {
  const int64_t nc = static_cast<int64_t>(cs.classes.size());
  cs.cls_caps.resize(nc);
  int64_t total = 0;
  for (int64_t c = 0; c < nc; ++c) {
    const ClassSolver::Cls& cl = cs.classes[c];
    const int32_t cap = cl.eok ? clamped_cap(cl.a, e, k) : 0;
    cs.cls_caps[c] = cap;
    total += static_cast<int64_t>(cap) * cl.live;
  }
  const int64_t nov = static_cast<int64_t>(cs.ov_nodes.size());
  cs.ov_caps.resize(nov);
  for (int64_t j = 0; j < nov; ++j) {
    const int32_t i = cs.ov_nodes[j];
    const int32_t a[kDims] = {a0[i], a1[i], a2[i]};
    const int32_t cap = exec_ok[i] ? clamped_cap(a, e, k) : 0;
    cs.ov_caps[j] = cap;
    total += cap;
  }

  // driver probe — the row walk verbatim (planes are authoritative)
  int32_t didx = -1;
  int32_t capd = 0;
  if (total >= k) {
    for (const int32_t i : cand) {
      const int32_t a[kDims] = {a0[i], a1[i], a2[i]};
      if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
      const int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
      if (total - class_cap_at(cs, i) + cwd >= k) {
        didx = i;
        capd = cwd;
        break;
      }
    }
  }
  if (didx < 0) return -1;

  // fill: merged ascending walk over the positive-capacity nodes.
  // Sources: one cursor per class (live members, didx excluded), one
  // overlay cursor, and the didx singleton carrying capd — together
  // they enumerate exactly the nodes the row loop would visit, in the
  // same order.  Source ids: [0, nc) classes, nc overlay, nc+1 didx.
  const int32_t kSrcOv = static_cast<int32_t>(nc);
  const int32_t kSrcD = static_cast<int32_t>(nc) + 1;
  cs.cls_cur.assign(static_cast<size_t>(nc), 0);
  cs.heap.clear();
  auto cls_next = [&](int32_t c) -> int32_t {
    const ClassSolver::Cls& cl = cs.classes[c];
    size_t& cur = cs.cls_cur[c];
    while (cur < cl.members.size()) {
      const int32_t m = cl.members[cur++];
      if (cs.node_cls[m] == c && m != didx) return m;
    }
    return -1;
  };
  int64_t ov_cur = 0;
  int64_t ov_head_j = -1;
  auto ov_next = [&]() -> int32_t {
    while (ov_cur < nov) {
      const int64_t j = ov_cur++;
      if (cs.ov_caps[j] > 0 && cs.ov_nodes[j] != didx) {
        ov_head_j = j;
        return cs.ov_nodes[j];
      }
    }
    ov_head_j = -1;
    return -1;
  };
  const auto hcmp = [](const std::pair<int32_t, int32_t>& x,
                       const std::pair<int32_t, int32_t>& y) {
    return x.first > y.first;  // min-heap on node id
  };
  for (int32_t c = 0; c < nc; ++c) {
    if (cs.cls_caps[c] <= 0) continue;
    const int32_t n = cls_next(c);
    if (n >= 0) cs.heap.emplace_back(n, c);
  }
  {
    const int32_t n = ov_next();
    if (n >= 0) cs.heap.emplace_back(n, kSrcOv);
  }
  if (capd > 0) cs.heap.emplace_back(didx, kSrcD);
  std::make_heap(cs.heap.begin(), cs.heap.end(), hcmp);

  auto sub_exec = [&](int32_t i) {
    a0[i] = wrap_sub(a0[i], e[0]);
    a1[i] = wrap_sub(a1[i], e[1]);
    a2[i] = wrap_sub(a2[i], e[2]);
  };
  cs.newly.clear();
  bool driver_hosts_exec = false;
  int64_t cum = 0;      // tightly: cumulative capacity
  int32_t placed = 0;   // evenly: hosting nodes
  while (!cs.heap.empty()) {
    if (evenly ? placed >= k : cum >= k) break;
    std::pop_heap(cs.heap.begin(), cs.heap.end(), hcmp);
    const auto [i, src] = cs.heap.back();
    cs.heap.pop_back();
    int32_t cap_i;
    int32_t nxt = -1;
    if (src == kSrcD) {
      cap_i = capd;
    } else if (src == kSrcOv) {
      cap_i = cs.ov_caps[ov_head_j];
      nxt = ov_next();
    } else {
      cap_i = cs.cls_caps[src];
      nxt = cls_next(src);
    }
    if (nxt >= 0) {
      cs.heap.emplace_back(nxt, src);
      std::push_heap(cs.heap.begin(), cs.heap.end(), hcmp);
    }
    cum += cap_i;
    ++placed;
    if (i == didx) driver_hosts_exec = true;
    sub_exec(i);
    cs.newly.push_back(i);
  }
  if (!driver_hosts_exec) {
    a0[didx] = wrap_sub(a0[didx], d[0]);
    a1[didx] = wrap_sub(a1[didx], d[1]);
    a2[didx] = wrap_sub(a2[didx], d[2]);
    cs.newly.push_back(didx);
  }
  class_absorb(cs, a0, a1, a2, exec_ok);
  return didx;
}

// --- class-structured min-frag drain -----------------------------------
// The row drain orders nodes by capacity VALUE (instant fit = smallest
// value ≥ remainder, then drain the max value in node order).  The class
// variant keeps a value-ordered map whose entries enumerate the nodes
// holding that value — per-class member cursors, an overlay list, and
// the didx singleton — and pops the globally earliest node among the
// sources, reproducing the bucketed drain's consumed-prefix node order.

struct ClsDrainVal {
  // (class id, member cursor, cached head node or kBig) triples
  std::vector<std::array<int32_t, 3>> cls;
  std::vector<int32_t> ov;  // ascending overlay node ids with this value
  size_t ov_cur = 0;
  bool has_didx = false;
};

int32_t cls_drain_head(const ClassSolver& cs, ClsDrainVal& dv, int32_t didx) {
  int32_t best = kBig;
  for (auto& src : dv.cls) {
    if (src[2] == kBig && src[1] >= 0) {
      // refresh the cached head: next live member != didx
      const ClassSolver::Cls& cl = cs.classes[src[0]];
      int32_t head = kBig;
      size_t cur = static_cast<size_t>(src[1]);
      while (cur < cl.members.size()) {
        const int32_t m = cl.members[cur];
        if (cs.node_cls[m] == src[0] && m != didx) {
          head = m;
          break;
        }
        ++cur;
      }
      src[1] = static_cast<int32_t>(cur);
      src[2] = head;
      if (head == kBig) src[1] = -1;  // exhausted
    }
    if (src[2] < best) best = src[2];
  }
  if (dv.ov_cur < dv.ov.size()) best = std::min(best, dv.ov[dv.ov_cur]);
  if (dv.has_didx && didx < best) best = didx;
  return best == kBig ? -1 : best;
}

void cls_drain_advance(const ClassSolver& cs, ClsDrainVal& dv, int32_t node,
                       int32_t didx) {
  if (dv.has_didx && node == didx) {
    dv.has_didx = false;
    return;
  }
  if (dv.ov_cur < dv.ov.size() && dv.ov[dv.ov_cur] == node) {
    ++dv.ov_cur;
    return;
  }
  for (auto& src : dv.cls) {
    if (src[2] == node) {
      ++src[1];
      src[2] = kBig;  // head consumed; refresh lazily
      return;
    }
  }
}

bool cls_drain_exhausted(const ClassSolver& cs, ClsDrainVal& dv,
                         int32_t didx) {
  return cls_drain_head(cs, dv, didx) < 0;
}

// One minimal-fragmentation FIFO step over the class partition — same
// contract as step_app_minfrag, byte-identical by construction.
int32_t step_app_minfrag_classes(ClassSolver& cs, int32_t* a0, int32_t* a1,
                                 int32_t* a2, const uint8_t* exec_ok,
                                 int64_t nb,
                                 const std::vector<int32_t>& cand,
                                 const int32_t* d, const int32_t* e,
                                 int32_t k, MfSegs& segs) {
  const int64_t nc = static_cast<int64_t>(cs.classes.size());
  cs.cls_caps.resize(nc);
  int64_t total = 0;
  for (int64_t c = 0; c < nc; ++c) {
    const ClassSolver::Cls& cl = cs.classes[c];
    const int32_t v = cl.eok ? mf_cap_one(cl.a[0], cl.a[1], cl.a[2], e) : 0;
    cs.cls_caps[c] = v;
    total += static_cast<int64_t>(std::clamp<int32_t>(v, 0, k)) * cl.live;
  }
  const int64_t nov = static_cast<int64_t>(cs.ov_nodes.size());
  cs.ov_caps.resize(nov);
  for (int64_t j = 0; j < nov; ++j) {
    const int32_t i = cs.ov_nodes[j];
    const int32_t v =
        exec_ok[i] ? mf_cap_one(a0[i], a1[i], a2[i], e) : 0;
    cs.ov_caps[j] = v;
    total += std::clamp<int32_t>(v, 0, k);
  }

  int32_t didx = -1;
  if (total >= k) {
    for (const int32_t i : cand) {
      const int32_t a[kDims] = {a0[i], a1[i], a2[i]};
      if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
      const int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
      if (total - std::clamp<int32_t>(class_cap_at(cs, i), 0, k) + cwd >= k) {
        didx = i;
        break;
      }
    }
  }
  if (didx < 0) return -1;

  // driver-node fix-up: didx contributes its own value (mf_cap_one on
  // avail − driver when eligible, 0 otherwise) and is excluded from its
  // class's multiplicity everywhere below
  int32_t dval = 0;
  if (exec_ok[didx]) {
    dval = mf_cap_one(wrap_sub(a0[didx], d[0]), wrap_sub(a1[didx], d[1]),
                      wrap_sub(a2[didx], d[2]), e);
  }
  const int32_t didx_cls = cs.node_cls[didx];
  auto eff_live = [&](int64_t c) {
    return cs.classes[c].live - (didx_cls == static_cast<int32_t>(c) ? 1 : 0);
  };

  bool placed_any = false;
  segs.clear();
  if (k > 0) {
    // extremes over the implied by-node capacity vector
    int32_t maxc = 0, min_ge = kBig, min_pos = kBig;
    auto fold = [&](int32_t v) {
      maxc = std::max(maxc, v);
      if (v >= k && v < min_ge) min_ge = v;
      if (v > 0 && v < min_pos) min_pos = v;
    };
    for (int64_t c = 0; c < nc; ++c) {
      if (eff_live(c) > 0) fold(cs.cls_caps[c]);
    }
    for (int64_t j = 0; j < nov; ++j) {
      if (cs.ov_nodes[j] != didx) fold(cs.ov_caps[j]);
    }
    fold(dval);

    if (maxc > 0) {
      const bool has_sent = maxc == kMfSent;
      const bool attempt_subset = has_sent || k < maxc;
      const int64_t target =
          has_sent ? static_cast<int64_t>(kMfSent)
                   : (attempt_subset
                          ? (k + static_cast<int64_t>(maxc)) / 2
                          : 0);

      auto place_first_with = [&](int32_t value) {
        int32_t best = kBig;
        for (int64_t c = 0; c < nc; ++c) {
          if (cs.cls_caps[c] != value || eff_live(c) <= 0) continue;
          for (const int32_t m : cs.classes[c].members) {
            if (cs.node_cls[m] == static_cast<int32_t>(c) && m != didx) {
              best = std::min(best, m);
              break;
            }
          }
        }
        for (int64_t j = 0; j < nov; ++j) {
          if (cs.ov_caps[j] == value && cs.ov_nodes[j] != didx) {
            best = std::min(best, cs.ov_nodes[j]);
            break;
          }
        }
        if (dval == value) best = std::min(best, didx);
        segs.emplace_back(best, static_cast<int64_t>(k));
      };

      // value-ordered drain over the class-structured capacity multiset
      auto drain = [&](int64_t bound) -> bool {
        std::map<int32_t, ClsDrainVal> vals;
        for (int64_t c = 0; c < nc; ++c) {
          const int32_t v = cs.cls_caps[c];
          if (v > 0 && v < bound && eff_live(c) > 0) {
            vals[v].cls.push_back({static_cast<int32_t>(c), 0, kBig});
          }
        }
        for (int64_t j = 0; j < nov; ++j) {
          const int32_t v = cs.ov_caps[j];
          if (v > 0 && v < bound && cs.ov_nodes[j] != didx) {
            vals[v].ov.push_back(cs.ov_nodes[j]);
          }
        }
        if (dval > 0 && dval < bound) vals[dval].has_didx = true;
        int64_t rem = k;
        while (true) {
          if (vals.empty()) return false;
          auto last = std::prev(vals.end());
          const int32_t maxv = last->first;
          if (rem <= maxv) {
            // instant fit: smallest unconsumed value ≥ rem, earliest
            // node among its remaining holders
            auto it = vals.lower_bound(static_cast<int32_t>(rem));
            const int32_t node = cls_drain_head(cs, it->second, didx);
            segs.emplace_back(node, rem);
            return true;
          }
          ClsDrainVal& dv = last->second;
          while (rem >= maxv) {
            const int32_t node = cls_drain_head(cs, dv, didx);
            if (node < 0) break;
            cls_drain_advance(cs, dv, node, didx);
            segs.emplace_back(node, static_cast<int64_t>(maxv));
            rem -= maxv;
          }
          if (rem == 0) return true;
          if (cls_drain_exhausted(cs, dv, didx)) vals.erase(last);
        }
      };

      const bool have_ge = min_ge != kBig && min_ge >= k;
      if (attempt_subset && have_ge && min_ge < target) {
        place_first_with(min_ge);
        placed_any = true;
      } else if (attempt_subset && min_pos != kBig && min_pos < target &&
                 drain(std::min<int64_t>(target, kBig))) {
        placed_any = true;
      } else {
        segs.clear();
        if (have_ge) {
          place_first_with(min_ge);
          placed_any = true;
        } else {
          placed_any = drain(static_cast<int64_t>(kBig));
        }
      }
    }
  }

  bool driver_hosts_exec = false;
  cs.newly.clear();
  if (placed_any) {
    for (const auto& seg : segs) {
      const int32_t i = seg.first;
      if (i == didx) driver_hosts_exec = true;
      a0[i] = wrap_sub(a0[i], e[0]);
      a1[i] = wrap_sub(a1[i], e[1]);
      a2[i] = wrap_sub(a2[i], e[2]);
      cs.newly.push_back(i);
    }
  } else {
    segs.clear();
  }
  if (!driver_hosts_exec) {
    a0[didx] = wrap_sub(a0[didx], d[0]);
    a1[didx] = wrap_sub(a1[didx], d[1]);
    a2[didx] = wrap_sub(a2[didx], d[2]);
    cs.newly.push_back(didx);
  }
  class_absorb(cs, a0, a1, a2, exec_ok);
  return didx;
}

// ---------------------------------------------------------------------------
// Decision-provenance explainer (ops side: provenance/explain.py).
//
// A refused driver's verdict is a bare infeasible bit; the explainer
// recovers the WHY: which dimension is short and by how much (the
// shortfall vector), which node comes closest to hosting the gang, and
// which earlier FIFO drivers consumed the capacity this app needed (the
// blocker set).  Runs only on demand — the hot solve paths never call
// any of this, and the StepUsage capture they share is nullptr there.
// ---------------------------------------------------------------------------

// One feasibility probe of an app against fixed planes, with the
// diagnostic decomposition: full clamped capacity total, per-dim-alone
// totals (dim j as the only constraint — the argmin is the tightest
// dimension), the best single node, and the count of driver candidates
// whose availability covers the driver row.  Feasibility reproduces
// step_app_plain's rule exactly (min-frag feasibility equals tightly's:
// the drain is work-conserving), so a probe verdict always matches the
// solver's verdict at the same planes.
struct ExplainProbe {
  int64_t dim_total[kDims] = {0, 0, 0};
  int64_t cap_total = 0;
  int32_t max_cap = 0;
  int32_t max_node = -1;
  int64_t driver_fit = 0;
  bool feasible = false;
};

void explain_probe(const int32_t* a0, const int32_t* a1, const int32_t* a2,
                   const uint8_t* eok, int64_t nb,
                   const std::vector<int32_t>& cand, const int32_t* d,
                   const int32_t* e, int32_t k,
                   std::vector<int32_t>& cap_ws, ExplainProbe* out) {
  cap_ws.resize(nb);
  int32_t* cap = cap_ws.data();
  const int64_t total = cap_pass_all(a0, a1, a2, eok, nb, e, k, cap);
  out->cap_total = total;
  const int32_t* planes[kDims] = {a0, a1, a2};
  for (int j = 0; j < kDims; ++j) {
    int64_t tj = 0;
    const int32_t* a = planes[j];
    if (e[j] == 0) {
      // a zero-requirement dim bounds nothing unless overdrawn: per
      // node it contributes the full clamp k when non-negative
      for (int64_t i = 0; i < nb; ++i) {
        if (eok[i] && a[i] >= 0) tj += k;
      }
    } else {
      const int32_t den = std::max(e[j], 1);
      for (int64_t i = 0; i < nb; ++i) {
        if (!eok[i] || a[i] <= 0) continue;
        tj += std::min<int64_t>(a[i] / den, k);
      }
    }
    out->dim_total[j] = tj;
  }
  int32_t maxc = 0;
  int64_t maxi = -1;
  for (int64_t i = 0; i < nb; ++i) {
    if (cap[i] > maxc) {
      maxc = cap[i];
      maxi = i;
    }
  }
  out->max_cap = maxc;
  out->max_node = static_cast<int32_t>(maxi);
  int64_t dfit = 0;
  bool feas = false;
  for (int32_t i : cand) {
    const int32_t a[kDims] = {a0[i], a1[i], a2[i]};
    if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
    ++dfit;
    if (!feas && total >= k) {
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
      const int32_t cwd = eok[i] ? clamped_cap(am, e, k) : 0;
      if (total - cap[i] + cwd >= k) feas = true;
    }
  }
  out->driver_fit = dfit;
  out->feasible = feas;
}

// ---------------------------------------------------------------------------
// Exact packing-efficiency math (efficiency.go:80-105 via
// ops/fifo_solver.efficiencies_from_rows): float64 ops in the same IEEE
// order as the numpy columns, so zone scores are bit-identical to the
// solver's host lane.
// ---------------------------------------------------------------------------

inline int64_t ceil_div64(int64_t a, int64_t b) {  // b > 0
  return a >= 0 ? (a + b - 1) / b : -((-a) / b);
}

// int64 wrap arithmetic matching numpy's (signed overflow is UB in C++,
// defined mod 2^64 via unsigned)
inline int64_t wrap_addsub64(int64_t s, int64_t sub, int64_t add) {
  return static_cast<int64_t>(static_cast<uint64_t>(s) -
                              static_cast<uint64_t>(sub) +
                              static_cast<uint64_t>(add));
}

// max(gpu, cpu, memory) of one node's reserved/schedulable ratios.
// s* are base-unit schedulable rows (milli-cpu, bytes, milli-gpu);
// r* the reserved numerators (same units).
inline double max_eff(int64_t s0, int64_t s1, int64_t s2, int64_t r0,
                      int64_t r1, int64_t r2) {
  const int64_t den_c = std::max<int64_t>(ceil_div64(s0, 1000), 1);
  const double cpu =
      static_cast<double>(ceil_div64(r0, 1000)) / static_cast<double>(den_c);
  const double mem = static_cast<double>(r1) /
                     static_cast<double>(std::max<int64_t>(s1, 1));
  const int64_t s_gpu = ceil_div64(s2, 1000);
  double gpu = 0.0;
  if (s_gpu != 0) {
    gpu = static_cast<double>(ceil_div64(r2, 1000)) /
          static_cast<double>(std::max<int64_t>(s_gpu, 1));
  }
  return std::max(gpu, std::max(cpu, mem));
}

}  // namespace

extern "C" {

// Whole-FIFO-queue solve (batch_solver.solve_queue semantics,
// with_placements=False): scan apps in order carrying availability.
//   avail_io      [nb*3] int32 row-major — updated in place to the
//                 post-queue availability
//   driver_rank   [nb] int32 (kBig = not a driver candidate)
//   exec_ok       [nb] uint8
//   drivers/executors [na*3] int32, counts [na] int32, app_valid [na] u8
//   evenly        0 = tightly-pack fill, 1 = distribute-evenly mask
//   out_feasible  [na] uint8
//   out_driver_idx[na] int32 (= nb when infeasible)
// Scratch buffers are internal; returns 1 (always succeeds).
int fifo_solve_queue(int64_t nb, int64_t na, int32_t* avail_io,
                     const int32_t* driver_rank, const uint8_t* exec_ok,
                     const int32_t* drivers, const int32_t* executors,
                     const int32_t* counts, const uint8_t* app_valid,
                     int evenly, uint8_t* out_feasible,
                     int32_t* out_driver_idx) {
  // rank-sorted driver candidates, built once (ranks are unique);
  // availability as column planes for the SIMD capacity pass, written
  // back to the row-major buffer at the end.  The per-app step itself
  // is shared with the persistent session (step_app_plain): capacity
  // pass, first-rank driver probe whose total < k early-out is exact
  // (for fitting nodes avail−driver stays in [0, avail], so capacity
  // can only shrink), and the sparkpods.go:139-146 subtraction quirk.
  std::vector<int32_t> cand = build_cand(driver_rank, nb);
  std::vector<int32_t> a0, a1, a2;
  split_planes(avail_io, nb, a0, a1, a2);
  QueueScratch ws;
  ws.cap.resize(nb);

  for (int64_t ai = 0; ai < na; ++ai) {
    const int32_t* d = drivers + ai * kDims;
    const int32_t* e = executors + ai * kDims;
    const int32_t k = counts[ai];
    out_feasible[ai] = 0;
    out_driver_idx[ai] = static_cast<int32_t>(nb);
    if (!app_valid[ai]) continue;
    int32_t didx = step_app_plain(a0.data(), a1.data(), a2.data(), exec_ok,
                                  nb, cand, d, e, k, evenly, ws, nullptr);
    if (didx < 0) continue;
    out_feasible[ai] = 1;
    out_driver_idx[ai] = didx;
  }
  join_planes(a0, a1, a2, nb, avail_io);
  return 1;
}

// Whole-FIFO-queue solve under the minimal-fragmentation policy
// (batch_solver.solve_queue_min_frag semantics, with_placements=False):
// feasibility + driver choice equal tightly-pack's (the drain is work-
// conserving); the carried usage subtraction comes from the min-frag
// drain counts.  Caller must hold the MF sentinel guard
// (batch_solver.mf_sentinel_safe) exactly like the device lanes.
int fifo_solve_queue_minfrag(int64_t nb, int64_t na, int32_t* avail_io,
                             const int32_t* driver_rank,
                             const uint8_t* exec_ok, const int32_t* drivers,
                             const int32_t* executors, const int32_t* counts,
                             const uint8_t* app_valid, uint8_t* out_feasible,
                             int32_t* out_driver_idx) {
  // per-app step shared with the persistent session (step_app_minfrag):
  // one fused pass yields both the UNCLAMPED min-frag capacities and
  // the tightly feasibility total, the driver-node capacity is fixed up
  // after the choice (batch_solver.min_frag_step_counts), and the
  // carried subtraction comes from the drain segments.
  std::vector<int32_t> cand = build_cand(driver_rank, nb);
  std::vector<int32_t> a0, a1, a2;
  split_planes(avail_io, nb, a0, a1, a2);
  QueueScratch ws;
  ws.mf_caps.resize(nb);

  for (int64_t ai = 0; ai < na; ++ai) {
    const int32_t* d = drivers + ai * kDims;
    const int32_t* e = executors + ai * kDims;
    const int32_t k = counts[ai];
    out_feasible[ai] = 0;
    out_driver_idx[ai] = static_cast<int32_t>(nb);
    if (!app_valid[ai]) continue;
    int32_t didx = step_app_minfrag(a0.data(), a1.data(), a2.data(), exec_ok,
                                    nb, cand, d, e, k, ws, nullptr);
    if (didx < 0) continue;
    out_feasible[ai] = 1;
    out_driver_idx[ai] = didx;
  }
  join_planes(a0, a1, a2, nb, avail_io);
  return 1;
}

// Whole-FIFO-queue solve over node equivalence classes (ROADMAP 2):
// byte-identical verdicts and post-queue availability to
// fifo_solve_queue / fifo_solve_queue_minfrag at the same inputs, with
// the per-app cost O(classes + diverged overlay) instead of O(nodes).
//   apps8    [na][8] packed rows: d0 d1 d2 e0 e1 e2 count valid
//   policy   0 tightly-pack, 1 distribute-evenly, 2 min-frag
//   out_stats (nullable) [4] int64 compression evidence:
//     [0] classes at the initial partition   [1] partition rebuilds
//     [2] overlay peak size                  [3] classes at the last rebuild
// Returns 1 (always succeeds).
int fifo_solve_queue_classes(int64_t nb, int64_t na, int32_t* avail_io,
                             const int32_t* driver_rank,
                             const uint8_t* exec_ok, const int32_t* apps8,
                             int policy, uint8_t* out_feasible,
                             int32_t* out_didx, int64_t* out_stats) {
  std::vector<int32_t> cand = build_cand(driver_rank, nb);
  std::vector<int32_t> a0, a1, a2;
  split_planes(avail_io, nb, a0, a1, a2);
  MfSegs segs;
  ClassSolver cs;
  class_rebuild(cs, a0.data(), a1.data(), a2.data(), exec_ok, nb);
  const int64_t classes_initial = cs.classes_last;
  for (int64_t ai = 0; ai < na; ++ai) {
    const int32_t* row = apps8 + ai * 8;
    const int32_t* d = row;
    const int32_t* e = row + 3;
    const int32_t k = row[6];
    out_feasible[ai] = 0;
    out_didx[ai] = static_cast<int32_t>(nb);
    if (!row[7]) continue;
    int32_t di;
    if (policy == 2) {
      di = step_app_minfrag_classes(cs, a0.data(), a1.data(), a2.data(),
                                    exec_ok, nb, cand, d, e, k, segs);
    } else {
      di = step_app_plain_classes(cs, a0.data(), a1.data(), a2.data(),
                                  exec_ok, nb, cand, d, e, k, policy == 1);
    }
    if (di >= 0) {
      out_feasible[ai] = 1;
      out_didx[ai] = di;
    }
  }
  join_planes(a0, a1, a2, nb, avail_io);
  if (out_stats != nullptr) {
    out_stats[0] = classes_initial;
    out_stats[1] = cs.rebuilds;
    out_stats[2] = cs.ov_peak;
    out_stats[3] = cs.classes_last;
  }
  return 1;
}

// Whole-FIFO-queue solve for the single-AZ policies
// (single_az.go:23-97 × resource.go:224-262): per app, per-zone
// tightly-pack (or min-frag) solves with the zone chosen by EXACT
// float64 average packing efficiency — the same IEEE operation sequence
// as the solver's host lane (pack_one → _choose_best_result), so no
// fixed-point uncertainty valve is needed.
//   zone_id      [nb] int32 — disjoint candidate-zone index per node
//                (-1 = in no candidate zone)
//   sched_base   [nb*3] int64 — base-unit schedulable rows
//   scale        [3] int64 — tensorize scale vector
//   az_aware     adds the cross-zone tightly-pack fallback (zone = nz)
//   minfrag      single-az-minimal-fragmentation inner placements
//   strict       reference no-write-back quirk: zone scores see only the
//                driver's reservation
//   out_zone     [na] int32 — chosen zone; nz = cross-zone; -1 = none
int fifo_solve_queue_single_az(
    int64_t nb, int64_t na, int64_t nz, int32_t* avail_io,
    const int32_t* driver_rank, const uint8_t* exec_ok,
    const int32_t* zone_id, const int32_t* drivers, const int32_t* executors,
    const int32_t* counts, const uint8_t* app_valid,
    const int64_t* sched_base, const int64_t* scale, int az_aware,
    int minfrag, int strict, uint8_t* out_feasible, int32_t* out_zone,
    int32_t* out_driver_idx) {
  std::vector<int32_t> cand;
  cand.reserve(nb);
  for (int64_t i = 0; i < nb; ++i) {
    if (driver_rank[i] < kBig) cand.push_back(static_cast<int32_t>(i));
  }
  std::sort(cand.begin(), cand.end(), [&](int32_t x, int32_t y) {
    return driver_rank[x] < driver_rank[y];
  });

  std::vector<int32_t> a0(nb), a1(nb), a2(nb), cap(nb);
  for (int64_t i = 0; i < nb; ++i) {
    a0[i] = avail_io[i * kDims + 0];
    a1[i] = avail_io[i * kDims + 1];
    a2[i] = avail_io[i * kDims + 2];
  }

  std::vector<int64_t> total_z(std::max<int64_t>(nz, 1));
  std::vector<int32_t> didx_z(std::max<int64_t>(nz, 1));
  std::vector<int32_t> capd_z(std::max<int64_t>(nz, 1));
  std::vector<MfSegs> segs_z(std::max<int64_t>(nz, 1));
  std::vector<int32_t> mf_caps(nb);
  MfScratch mf_ws;
  // per-zone eligibility bytes: lets the min-frag capacity pass run
  // vectorized per zone instead of a branchy zone_id test per node
  std::vector<std::vector<uint8_t>> zone_elig;
  if (minfrag) {
    zone_elig.assign(std::max<int64_t>(nz, 1), std::vector<uint8_t>(nb, 0));
    for (int64_t i = 0; i < nb; ++i) {
      const int32_t z = zone_id[i];
      if (z >= 0 && z < nz && exec_ok[i]) zone_elig[z][i] = 1;
    }
  }

  // reserved/schedulable ratio of one node under this app's packing
  // (eff_count executors + the driver when on it), exact float64
  auto node_max_eff = [&](int64_t i, int64_t eff_count, const int32_t* d,
                          const int32_t* e, bool is_driver) {
    int64_t r[kDims];
    for (int j = 0; j < kDims; ++j) {
      const int64_t res =
          eff_count * e[j] + (is_driver ? static_cast<int64_t>(d[j]) : 0);
      const int64_t avail_j =
          static_cast<int64_t>((j == 0 ? a0 : j == 1 ? a1 : a2)[i]);
      r[j] = wrap_addsub64(
          sched_base[i * kDims + j],
          static_cast<int64_t>(
              static_cast<uint64_t>(avail_j) *
              static_cast<uint64_t>(scale[j])),
          static_cast<int64_t>(
              static_cast<uint64_t>(res) * static_cast<uint64_t>(scale[j])));
    }
    return max_eff(sched_base[i * kDims + 0], sched_base[i * kDims + 1],
                   sched_base[i * kDims + 2], r[0], r[1], r[2]);
  };

  for (int64_t ai = 0; ai < na; ++ai) {
    const int32_t* d = drivers + ai * kDims;
    const int32_t* e = executors + ai * kDims;
    const int32_t k = counts[ai];
    out_feasible[ai] = 0;
    out_zone[ai] = -1;
    out_driver_idx[ai] = static_cast<int32_t>(nb);
    if (!app_valid[ai]) continue;

    cap_pass_all(a0.data(), a1.data(), a2.data(), exec_ok, nb, e, k,
                 cap.data());
    std::fill(total_z.begin(), total_z.end(), 0);
    for (int64_t i = 0; i < nb; ++i) {
      const int32_t z = zone_id[i];
      if (z >= 0 && z < nz) total_z[z] += cap[i];
    }

    // one rank-ordered walk finds every zone's first feasible driver
    std::fill(didx_z.begin(), didx_z.end(), -1);
    int64_t found = 0;
    for (int32_t i : cand) {
      if (found == nz) break;
      const int32_t z = zone_id[i];
      if (z < 0 || z >= nz || didx_z[z] >= 0) continue;
      int32_t a[kDims] = {a0[i], a1[i], a2[i]};
      if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
      int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
      if (total_z[z] - cap[i] + cwd >= k) {
        didx_z[z] = i;
        capd_z[z] = cwd;
        ++found;
      }
    }

    // per feasible zone: placement segments + exact zone score
    int32_t best_zone = -1;
    double best_avg = 0.0;
    for (int64_t z = 0; z < nz; ++z) {
      const int32_t dz = didx_z[z];
      if (dz < 0) continue;
      MfSegs& segs = segs_z[z];
      segs.clear();
      bool ok = true;
      if (minfrag) {
        // drain over UNCLAMPED zone capacities (vectorized pass over the
        // per-zone eligibility bytes), driver subtracted on its node
        mf_cap_pass_all(a0.data(), a1.data(), a2.data(),
                        zone_elig[z].data(), nb, e, k, mf_caps.data());
        if (zone_elig[z][dz]) {
          int32_t av[kDims];
          for (int j = 0; j < kDims; ++j)
            av[j] = wrap_sub((j == 0 ? a0 : j == 1 ? a1 : a2)[dz], d[j]);
          mf_caps[dz] = mf_cap_one(av[0], av[1], av[2], e);
        }
        if (k > 0)
          ok = mf_assign(mf_caps, k, mf_extremes(mf_caps, k, mf_ws.copy),
                         mf_ws, segs);
      } else if (k > 0) {
        // tightly-pack greedy fill in node order within the zone
        int64_t cum = 0;
        for (int64_t i = 0; i < nb && cum < k; ++i) {
          if (zone_id[i] != z) continue;
          const int64_t c = (i == dz) ? capd_z[z] : cap[i];
          if (c <= 0) continue;
          const int64_t take = std::min<int64_t>(c, k - cum);
          segs.emplace_back(static_cast<int32_t>(i), take);
          cum += take;
        }
        ok = cum == k;  // guaranteed by the driver-choice condition
      }
      if (!ok) {
        didx_z[z] = -1;
        continue;
      }
      // occurrence-ordered float64 sum of per-node max efficiencies
      // ([driver] + executor placements, single_az.go:75-97).  Under
      // strict min-frag parity the reservation side sees only the
      // driver (the reference's no-write-back quirk); occurrences still
      // weight every placement.
      const bool eff_zero = minfrag && strict;
      double max_sum = 0.0;
      {
        int64_t eff_driver = 0;
        if (!eff_zero) {
          for (const auto& seg : segs) {
            if (seg.first == dz) eff_driver = seg.second;
          }
        }
        max_sum += node_max_eff(dz, eff_driver, d, e, true);
      }
      for (const auto& seg : segs) {
        const int64_t eff_count = eff_zero ? 0 : seg.second;
        const double v =
            node_max_eff(seg.first, eff_count, d, e, seg.first == dz);
        for (int64_t c = 0; c < seg.second; ++c) max_sum += v;
      }
      const double avg =
          max_sum / static_cast<double>(static_cast<int64_t>(k) + 1);
      if (best_avg < avg) {  // strict improvement, zone order
        best_avg = avg;
        best_zone = static_cast<int32_t>(z);
      }
    }

    int32_t chosen_didx = -1;
    const MfSegs* chosen_segs = nullptr;
    MfSegs cross_segs;
    if (best_zone >= 0) {
      chosen_didx = didx_z[best_zone];
      chosen_segs = &segs_z[best_zone];
    } else if (az_aware) {
      // cross-zone tightly-pack fallback (az_aware_pack_tightly.go:27-38)
      int64_t total = 0;
      for (int64_t i = 0; i < nb; ++i) total += cap[i];
      int32_t didx = -1, capd = 0;
      if (total >= k) {
        for (int32_t i : cand) {
          int32_t a[kDims] = {a0[i], a1[i], a2[i]};
          if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
          int32_t am[kDims];
          for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
          int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
          if (total - cap[i] + cwd >= k) {
            didx = i;
            capd = cwd;
            break;
          }
        }
      }
      if (didx >= 0) {
        int64_t cum = 0;
        for (int64_t i = 0; i < nb && cum < k; ++i) {
          const int64_t c = (i == didx) ? capd : cap[i];
          if (c <= 0) continue;
          const int64_t take = std::min<int64_t>(c, k - cum);
          cross_segs.emplace_back(static_cast<int32_t>(i), take);
          cum += take;
        }
        chosen_didx = didx;
        chosen_segs = &cross_segs;
        best_zone = static_cast<int32_t>(nz);
      }
    }
    if (chosen_didx < 0) continue;

    out_feasible[ai] = 1;
    out_zone[ai] = best_zone;
    out_driver_idx[ai] = chosen_didx;

    bool driver_hosts_exec = false;
    for (const auto& seg : *chosen_segs) {
      const int32_t i = seg.first;
      if (i == chosen_didx) driver_hosts_exec = true;
      a0[i] = wrap_sub(a0[i], e[0]);
      a1[i] = wrap_sub(a1[i], e[1]);
      a2[i] = wrap_sub(a2[i], e[2]);
    }
    if (!driver_hosts_exec) {
      a0[chosen_didx] = wrap_sub(a0[chosen_didx], d[0]);
      a1[chosen_didx] = wrap_sub(a1[chosen_didx], d[1]);
      a2[chosen_didx] = wrap_sub(a2[chosen_didx], d[2]);
    }
  }
  for (int64_t i = 0; i < nb; ++i) {
    avail_io[i * kDims + 0] = a0[i];
    avail_io[i * kDims + 1] = a1[i];
    avail_io[i * kDims + 2] = a2[i];
  }
  return 1;
}

// Single-app solve against a fixed availability (batch_solver.solve_app
// semantics): fills out_exec_counts [nb] with the tightly-pack fill
// counts and out_caps [nb] with the post-driver-placement capacities
// (AppSolve.exec_capacity — the distribute-evenly decode consumes
// these; both zeroed when infeasible).  Availability is NOT mutated.
int fifo_solve_app(int64_t nb, const int32_t* avail,
                   const int32_t* driver_rank, const uint8_t* exec_ok,
                   const int32_t* driver, const int32_t* executor,
                   int32_t k, uint8_t* out_feasible, int32_t* out_driver_idx,
                   int32_t* out_exec_counts, int32_t* out_caps) {
  *out_feasible = 0;
  *out_driver_idx = static_cast<int32_t>(nb);
  for (int64_t i = 0; i < nb; ++i) out_exec_counts[i] = 0;
  for (int64_t i = 0; i < nb; ++i) out_caps[i] = 0;

  std::vector<int32_t> cap(nb);
  int64_t total = 0;
  for (int64_t i = 0; i < nb; ++i) {
    int32_t c = exec_ok[i] ? clamped_cap(avail + i * kDims, executor, k) : 0;
    cap[i] = c;
    total += c;
  }
  int32_t best_rank = kBig;
  int32_t didx = -1;
  int32_t capd = 0;
  if (total >= k) {
    for (int64_t i = 0; i < nb; ++i) {
      if (driver_rank[i] >= best_rank) continue;
      const int32_t* a = avail + i * kDims;
      if (a[0] < driver[0] || a[1] < driver[1] || a[2] < driver[2]) continue;
      int32_t am[kDims];
      for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], driver[j]);
      int32_t cwd = exec_ok[i] ? clamped_cap(am, executor, k) : 0;
      if (total - cap[i] + cwd >= k) {
        best_rank = driver_rank[i];
        didx = static_cast<int32_t>(i);
        capd = cwd;
      }
    }
  }
  if (didx < 0) return 1;
  *out_feasible = 1;
  *out_driver_idx = didx;
  cap[didx] = capd;
  int64_t cum = 0;
  for (int64_t i = 0; i < nb; ++i) {
    out_caps[i] = cap[i];
    if (cum < k) {
      int64_t take = std::min<int64_t>(cap[i], k - cum);
      if (take > 0) {
        out_exec_counts[i] = static_cast<int32_t>(take);
        cum += take;
      }
    }
  }
  return 1;
}

// ---------------------------------------------------------------------------
// Persistent solver session (ops/deltasolve.py) — the warm path of the
// incremental delta-solve engine.
//
// A session pins one (cluster basis, policy) problem in native memory:
// the scaled availability planes at queue position 0, the rank-sorted
// driver-candidate list (sorted ONCE per basis instead of once per
// request), the queue rows it last solved with their per-position
// verdicts, and prefix checkpoints of the carried availability every
// `stride` positions plus the final tail.  A warm solve self-verifies
// the queue prefix byte-for-byte against the cached rows (the Python
// caller's id-based bookkeeping is an optimization, never a correctness
// input), restores the nearest checkpoint at or below the first changed
// position, and re-runs only the suffix — O(changed suffix × nodes)
// instead of O(queue × nodes).
//
// Checkpoint memory is bounded: at most kMaxCheckpoints live at once;
// when the queue grows past stride × kMaxCheckpoints the stride doubles
// and odd checkpoints are dropped (positions at even multiples of the
// old stride are exactly the multiples of the new one), so resume
// granularity degrades gracefully instead of memory growing with the
// queue.
// ---------------------------------------------------------------------------

namespace {
constexpr int64_t kMaxCheckpoints = 24;
}

struct FifoSession {
  int64_t nb = 0;
  int policy = 0;  // 0 tightly-pack, 1 distribute-evenly, 2 min-frag
  int64_t stride = 64;
  std::vector<int32_t> basis0, basis1, basis2;  // planes at position 0
  std::vector<uint8_t> eok;
  std::vector<int32_t> cand;  // rank-sorted driver candidates
  // last-solved queue: packed rows [na][8] = d0 d1 d2 e0 e1 e2 count
  // valid, plus the per-position verdicts
  std::vector<int32_t> apps;
  std::vector<uint8_t> feas;
  std::vector<int32_t> didx;
  int64_t na = 0;
  // chk*[j] = planes BEFORE the app at position (j+1)*stride
  std::vector<std::vector<int32_t>> chk0, chk1, chk2;
  // planes after all `na` cached apps (the "checkpoint at position na")
  std::vector<int32_t> tail0, tail1, tail2;
  std::vector<int32_t> a0, a1, a2;  // working planes
  QueueScratch ws;
  SweepPool* pool = nullptr;
  // class-compressed stepping (opt-in): the partition mirrors the
  // working planes at queue position cls_pos (-1 = stale, rebuild
  // before stepping).  A warm full-prefix resume (r == na) keeps the
  // partition synced at the tail, so the steady state never rebuilds.
  int use_classes = 0;
  int64_t cls_pos = -1;
  ClassSolver cls;
  ~FifoSession() { delete pool; }
};

extern "C" void* fifo_sess_create() {
  return new (std::nothrow) FifoSession();
}

extern "C" void fifo_sess_destroy(void* handle) {
  delete static_cast<FifoSession*>(handle);
}

// (Re)load the session basis: scaled availability rows [nb,3] at queue
// position 0, driver ranks, executor eligibility, policy, checkpoint
// stride, worker count for the sharded cold pass (engages only when
// n_threads > 1 and nb >= min_pool_nodes).  Drops all cached queue
// state.  Returns 1 on success.
extern "C" int fifo_sess_load(void* handle, int64_t nb,
                              const int32_t* avail_rows,
                              const int32_t* driver_rank,
                              const uint8_t* exec_ok, int policy,
                              int64_t stride, int n_threads,
                              int64_t min_pool_nodes) {
  FifoSession* s = static_cast<FifoSession*>(handle);
  if (s == nullptr || nb <= 0 || stride <= 0) return 0;
  s->nb = nb;
  s->policy = policy;
  s->stride = stride;
  split_planes(avail_rows, nb, s->basis0, s->basis1, s->basis2);
  s->eok.assign(exec_ok, exec_ok + nb);
  s->cand = build_cand(driver_rank, nb);
  s->apps.clear();
  s->feas.clear();
  s->didx.clear();
  s->na = 0;
  s->chk0.clear();
  s->chk1.clear();
  s->chk2.clear();
  s->tail0 = s->basis0;
  s->tail1 = s->basis1;
  s->tail2 = s->basis2;
  s->a0.resize(nb);
  s->a1.resize(nb);
  s->a2.resize(nb);
  s->ws.cap.resize(nb);
  s->ws.mf_caps.resize(nb);
  s->cls_pos = -1;
  int want = std::min(n_threads, kMaxPoolThreads);
  if (want <= 1 || nb < min_pool_nodes) {
    delete s->pool;
    s->pool = nullptr;
  } else if (s->pool == nullptr || s->pool->workers() != want) {
    delete s->pool;
    s->pool = new (std::nothrow) SweepPool(want);
  }
  return 1;
}

// Solve the queue `apps8` ([na][8] packed rows, same scaled units as
// the loaded basis) against the session basis, resuming from the
// nearest prefix checkpoint.  Writes per-position verdicts and the
// post-queue availability rows.  Returns the resume position (0 = full
// cold solve, na = everything served from cache), or -2 when the
// session has no basis.
extern "C" int64_t fifo_sess_solve(void* handle, int64_t na,
                                   const int32_t* apps8, uint8_t* out_feas,
                                   int32_t* out_didx,
                                   int32_t* out_avail_rows) {
  FifoSession* s = static_cast<FifoSession*>(handle);
  if (s == nullptr || s->nb == 0 || na < 0) return -2;
  const int64_t nb = s->nb;

  // 1. first position whose packed row differs from the cached run —
  // blocked memcmp then a row scan, so the common all-equal prefix
  // costs one pass of memcmp bandwidth (~us at 1k apps)
  const int64_t lim = std::min(na, s->na);
  int64_t diff = lim;
  {
    const int32_t* cached = s->apps.data();
    constexpr int64_t B = 256;
    int64_t i = 0;
    while (i < lim) {
      const int64_t hi = std::min(lim, i + B);
      if (std::memcmp(apps8 + i * 8, cached + i * 8,
                      static_cast<size_t>(hi - i) * 8 * sizeof(int32_t)) ==
          0) {
        i = hi;
        continue;
      }
      while (i < hi && std::memcmp(apps8 + i * 8, cached + i * 8,
                                   8 * sizeof(int32_t)) == 0) {
        ++i;
      }
      break;
    }
    diff = i;
  }

  // 2. stride doubling keeps the checkpoint set bounded as na grows
  while (na / s->stride > kMaxCheckpoints) {
    const int64_t keep = static_cast<int64_t>(s->chk0.size()) / 2;
    for (int64_t j = 0; j < keep; ++j) {
      // old index 2j+1 holds position (2j+2)·stride = (j+1)·(2·stride)
      s->chk0[j] = std::move(s->chk0[2 * j + 1]);
      s->chk1[j] = std::move(s->chk1[2 * j + 1]);
      s->chk2[j] = std::move(s->chk2[2 * j + 1]);
    }
    s->chk0.resize(keep);
    s->chk1.resize(keep);
    s->chk2.resize(keep);
    s->stride *= 2;
  }

  // 3. resume position: the largest checkpointed position ≤ diff (the
  // tail counts as the checkpoint at position s->na)
  int64_t r;
  if (diff >= s->na) {
    r = s->na;
  } else {
    int64_t j = diff / s->stride;
    if (j > static_cast<int64_t>(s->chk0.size())) {
      j = static_cast<int64_t>(s->chk0.size());
    }
    r = j * s->stride;
  }

  // 4. restore working planes from that checkpoint
  if (r == s->na) {
    s->a0 = s->tail0;
    s->a1 = s->tail1;
    s->a2 = s->tail2;
  } else if (r == 0) {
    s->a0 = s->basis0;
    s->a1 = s->basis1;
    s->a2 = s->basis2;
  } else {
    const int64_t j = r / s->stride - 1;
    s->a0 = s->chk0[j];
    s->a1 = s->chk1[j];
    s->a2 = s->chk2[j];
  }

  // 5. checkpoints past the resume point describe a superseded suffix
  const int64_t keep_chk = r / s->stride;
  if (static_cast<int64_t>(s->chk0.size()) > keep_chk) {
    s->chk0.resize(keep_chk);
    s->chk1.resize(keep_chk);
    s->chk2.resize(keep_chk);
  }

  // 6. adopt the new queue rows + verdict storage (prefix verdicts for
  // [0, r) stay valid by construction)
  s->apps.assign(apps8, apps8 + na * 8);
  s->feas.resize(na);
  s->didx.resize(na);

  // 7. solve the suffix, dropping fresh checkpoints as positions pass
  int32_t* a0 = s->a0.data();
  int32_t* a1 = s->a1.data();
  int32_t* a2 = s->a2.data();
  const uint8_t* eok = s->eok.data();
  // class mode: the partition must mirror the restored planes.  It does
  // iff it was left at exactly this queue position (the warm tail
  // resume); any other restore point rebuilds it in one O(nb) pass.
  if (s->use_classes && s->cls_pos != r && r < na) {
    class_rebuild(s->cls, a0, a1, a2, eok, nb);
  }
  for (int64_t i = r; i < na; ++i) {
    if (i > 0 && i % s->stride == 0 &&
        static_cast<int64_t>(s->chk0.size()) == i / s->stride - 1) {
      s->chk0.push_back(s->a0);
      s->chk1.push_back(s->a1);
      s->chk2.push_back(s->a2);
    }
    const int32_t* row = s->apps.data() + i * 8;
    const int32_t* d = row;
    const int32_t* e = row + 3;
    const int32_t k = row[6];
    s->feas[i] = 0;
    s->didx[i] = static_cast<int32_t>(nb);
    if (!row[7]) continue;
    int32_t di;
    if (s->use_classes) {
      if (s->policy == 2) {
        di = step_app_minfrag_classes(s->cls, a0, a1, a2, eok, nb, s->cand,
                                      d, e, k, s->ws.segs);
      } else {
        di = step_app_plain_classes(s->cls, a0, a1, a2, eok, nb, s->cand, d,
                                    e, k, s->policy == 1);
      }
    } else if (s->policy == 2) {
      di = step_app_minfrag(a0, a1, a2, eok, nb, s->cand, d, e, k, s->ws,
                            s->pool);
    } else {
      di = step_app_plain(a0, a1, a2, eok, nb, s->cand, d, e, k,
                          s->policy == 1, s->ws, s->pool);
    }
    if (di >= 0) {
      s->feas[i] = 1;
      s->didx[i] = di;
    }
  }

  // 8. tail + outputs
  s->tail0 = s->a0;
  s->tail1 = s->a1;
  s->tail2 = s->a2;
  s->na = na;
  if (s->use_classes) {
    // partition mirrors the new tail unless the queue was truncated to
    // a checkpoint with nothing to step (no rebuild ran there)
    s->cls_pos = (r < na || s->cls_pos == r) ? na : -1;
  }
  if (na > 0) {
    std::memcpy(out_feas, s->feas.data(), static_cast<size_t>(na));
    std::memcpy(out_didx, s->didx.data(),
                static_cast<size_t>(na) * sizeof(int32_t));
  }
  join_planes(s->a0, s->a1, s->a2, nb, out_avail_rows);
  return r;
}

// Toggle class-compressed stepping for the session (ROADMAP 2).  The
// partition is built lazily at the next solve; verdicts and planes are
// byte-identical either way, so this is purely a performance mode.
extern "C" void fifo_sess_set_classes(void* handle, int enable) {
  FifoSession* s = static_cast<FifoSession*>(handle);
  if (s == nullptr) return;
  s->use_classes = enable != 0;
  s->cls_pos = -1;
}

// Compression evidence of the session's class partition: [0] class
// count at the last rebuild, [1] cumulative rebuilds, [2] overlay peak,
// [3] current overlay size.  Zeros until class mode has stepped.
extern "C" void fifo_sess_class_stats(void* handle, int64_t* out4) {
  FifoSession* s = static_cast<FifoSession*>(handle);
  if (s == nullptr || out4 == nullptr) return;
  out4[0] = s->cls.classes_last;
  out4[1] = s->cls.rebuilds;
  out4[2] = s->cls.ov_peak;
  out4[3] = static_cast<int64_t>(s->cls.ov_nodes.size());
}

// Resident bytes of the session's buffers (basis + checkpoints + tail +
// working planes + queue cache) — the soak's bounded-memory assertion
// reads this through the engine.
extern "C" int64_t fifo_sess_mem_bytes(void* handle) {
  FifoSession* s = static_cast<FifoSession*>(handle);
  if (s == nullptr) return 0;
  auto vb = [](const std::vector<int32_t>& v) {
    return static_cast<int64_t>(v.capacity()) * sizeof(int32_t);
  };
  int64_t total = vb(s->basis0) + vb(s->basis1) + vb(s->basis2) +
                  vb(s->tail0) + vb(s->tail1) + vb(s->tail2) + vb(s->a0) +
                  vb(s->a1) + vb(s->a2) + vb(s->cand) + vb(s->apps) +
                  vb(s->didx) + vb(s->ws.cap) + vb(s->ws.mf_caps) +
                  static_cast<int64_t>(s->eok.capacity()) +
                  static_cast<int64_t>(s->feas.capacity());
  for (const auto& c : s->chk0) total += vb(c);
  for (const auto& c : s->chk1) total += vb(c);
  for (const auto& c : s->chk2) total += vb(c);
  return total;
}

// Explain one queue position's verdict (provenance/explain.py): replay
// the queue from the given basis with the policy-correct step function,
// probing the target app's feasibility along the way, and report
//
//   out_info[0]  flip — the queue position whose (feasible) step turned
//                the target infeasible; -1 = target feasible at its own
//                position; -2 = infeasible even against the empty basis
//                (the cluster is undersized, no earlier driver to blame)
//   out_info[1]  target feasible at its own position (0/1)
//   out_info[2]  clamped capacity total at the target position
//   out_info[3..5]  per-dim-alone capacity totals (tightest = argmin)
//   out_info[6]  best single-node capacity,  out_info[7] its index
//   out_info[8]  driver candidates whose availability covers the driver
//   out_info[9]  tightest dimension (-1 = capacity fine, driver-blocked)
//   out_info[10] shortfall in executor units (k − capacity total)
//   out_info[11] blocker count
//   out_blockers [na] u8 — the blocker set: walking back from the flip
//                position, the feasible earlier drivers whose recorded
//                consumption in the tightest dimension covers the
//                resource shortfall (the preemption-vocabulary victim
//                candidates); the flip driver is always included
//
// Feasibility is monotone along the queue (steps only subtract), so
// probing stops at the first flip.  Cost: ≤ 2 cold solves worth of
// passes — explain is an on-demand diagnostic, never a hot path.
int fifo_explain_queue(int64_t nb, int64_t na, const int32_t* avail_rows,
                       const int32_t* driver_rank, const uint8_t* exec_ok,
                       const int32_t* apps8, int policy, int64_t target,
                       uint8_t* out_blockers, int64_t* out_info) {
  if (nb <= 0 || na <= 0 || target < 0 || target >= na) return 0;
  std::vector<int32_t> cand = build_cand(driver_rank, nb);
  std::vector<int32_t> a0, a1, a2;
  split_planes(avail_rows, nb, a0, a1, a2);
  QueueScratch ws;
  ws.cap.resize(nb);
  ws.mf_caps.resize(nb);
  std::vector<int32_t> probe_ws;
  for (int64_t i = 0; i < na; ++i) out_blockers[i] = 0;

  const int32_t* trow = apps8 + target * 8;
  const int32_t* td = trow;
  const int32_t* te = trow + 3;
  const int32_t tk = trow[6];

  ExplainProbe probe;
  explain_probe(a0.data(), a1.data(), a2.data(), exec_ok, nb, cand, td, te,
                tk, probe_ws, &probe);
  int64_t flip = -1;
  bool still_feasible = probe.feasible;
  if (!still_feasible) flip = -2;

  std::vector<std::array<int64_t, kDims>> used(
      target, std::array<int64_t, kDims>{0, 0, 0});
  std::vector<uint8_t> step_feas(target, 0);

  for (int64_t i = 0; i < target; ++i) {
    const int32_t* row = apps8 + i * 8;
    if (!row[7]) continue;
    StepUsage su;
    int32_t di;
    if (policy == 2) {
      di = step_app_minfrag(a0.data(), a1.data(), a2.data(), exec_ok, nb,
                            cand, row, row + 3, row[6], ws, nullptr, &su);
    } else {
      di = step_app_plain(a0.data(), a1.data(), a2.data(), exec_ok, nb, cand,
                          row, row + 3, row[6], policy == 1, ws, nullptr,
                          &su);
    }
    if (di < 0) continue;
    step_feas[i] = 1;
    for (int j = 0; j < kDims; ++j) {
      used[i][j] = static_cast<int64_t>(su.hosting_nodes) * row[3 + j] +
                   (su.driver_row_applied ? static_cast<int64_t>(row[j]) : 0);
    }
    if (still_feasible) {
      ExplainProbe after;
      explain_probe(a0.data(), a1.data(), a2.data(), exec_ok, nb, cand, td,
                    te, tk, probe_ws, &after);
      if (!after.feasible) {
        still_feasible = false;
        flip = i;
      }
    }
  }

  // the verdict the operator saw: the target against its own position
  explain_probe(a0.data(), a1.data(), a2.data(), exec_ok, nb, cand, td, te,
                tk, probe_ws, &probe);

  int64_t tightest = -1;
  int64_t shortfall = 0;
  if (!probe.feasible && probe.cap_total < tk) {
    for (int j = 0; j < kDims; ++j) {
      if (te[j] == 0) continue;
      if (tightest < 0 || probe.dim_total[j] < probe.dim_total[tightest]) {
        tightest = j;
      }
    }
    shortfall = tk - probe.cap_total;
  }

  int64_t blocker_count = 0;
  if (!probe.feasible && flip >= 0) {
    const int64_t need =
        (tightest >= 0) ? shortfall * static_cast<int64_t>(te[tightest]) : 0;
    int64_t freed = 0;
    for (int64_t i = flip; i >= 0; --i) {
      if (!step_feas[i]) continue;
      out_blockers[i] = 1;
      ++blocker_count;
      if (tightest < 0) break;  // driver-blocked: the flip driver alone
      freed += used[i][tightest];
      if (freed >= need) break;
    }
  }

  out_info[0] = flip;
  out_info[1] = probe.feasible ? 1 : 0;
  out_info[2] = probe.cap_total;
  out_info[3] = probe.dim_total[0];
  out_info[4] = probe.dim_total[1];
  out_info[5] = probe.dim_total[2];
  out_info[6] = probe.max_cap;
  out_info[7] = probe.max_node;
  out_info[8] = probe.driver_fit;
  out_info[9] = tightest;
  out_info[10] = shortfall;
  out_info[11] = blocker_count;
  return 1;
}

// ---------------------------------------------------------------------------
// Capacity-observatory probes (ops side: capacity/probe.py).
//
// What-if analytics against a FIXED availability basis: the largest
// gang of a given (driver, executor) shape the solver would admit, and
// a per-dimension fragmentation report.  Read-only — the planes are
// never mutated, and nothing here runs on a scheduling hot path.
// ---------------------------------------------------------------------------

// Batched headroom probe: for each shape s (rows [s*6..s*6+2] driver,
// [s*6+3..s*6+5] executor, same scaled units as avail_rows), the
// largest k in [0, k_max] for which the FIFO step at queue position 0
// would admit a gang of k executors — exactly step_app_plain's
// feasibility rule (shared by distribute-evenly, and by min-frag whose
// drain is work-conserving, so one probe covers all three policies).
//
// Feasibility is monotone in k: per node min(c,k)·(k+1) ≥ min(c,k+1)·k,
// so Σ min(c_i,k+1) ≥ k+1 implies Σ min(c_i,k) ≥ k, and the same
// scaling applies to the with-driver total of the k+1 witness
// candidate.  Bisection therefore needs O(log k_max) feasibility
// evaluations; the UNCLAMPED per-node capacities are computed once per
// shape (they are k-independent), so each evaluation is one clamp-sum
// sweep plus the driver-candidate walk.
//
// Outputs per shape:
//   out_headroom[s]    largest admissible k (0 = not even one executor,
//                      or no node covers the driver row)
//   out_usable[s*3+j]  Σ_i clamp(c_i, 0, k_max) · e_j — scaled units of
//                      dimension j actually reachable by executors of
//                      this shape (vs. raw free: the fragmentation gap)
//   out_probes[s]      feasibility evaluations spent (bisection depth)
int fifo_probe_headroom(int64_t nb, const int32_t* avail_rows,
                        const int32_t* driver_rank, const uint8_t* exec_ok,
                        int64_t nshapes, const int32_t* shapes,
                        int32_t k_max, int64_t* out_headroom,
                        int64_t* out_usable, int64_t* out_probes) {
  if (nb <= 0 || nshapes <= 0 || k_max <= 0) return 0;
  std::vector<int32_t> cand = build_cand(driver_rank, nb);
  std::vector<int32_t> a0, a1, a2;
  split_planes(avail_rows, nb, a0, a1, a2);
  std::vector<int32_t> caps(nb);

  for (int64_t s = 0; s < nshapes; ++s) {
    const int32_t* d = shapes + s * 6;
    const int32_t* e = shapes + s * 6 + 3;
    // unclamped exact-floor capacities (≤ 0 = ineligible), shared by
    // every feasibility evaluation of this shape
    cap_sweeps(a0.data(), a1.data(), a2.data(), nb, e, kMfSent, caps.data());
    for (int64_t i = 0; i < nb; ++i) {
      if (!exec_ok[i]) caps[i] = 0;
    }

    int64_t total_kmax = 0;
    for (int64_t i = 0; i < nb; ++i) {
      total_kmax += std::clamp<int32_t>(caps[i], 0, k_max);
    }
    for (int j = 0; j < kDims; ++j) {
      out_usable[s * 3 + j] = total_kmax * static_cast<int64_t>(e[j]);
    }

    int64_t probes = 0;
    auto feasible = [&](int32_t k) -> bool {
      ++probes;
      int64_t total = 0;
      for (int64_t i = 0; i < nb; ++i) {
        total += std::clamp<int32_t>(caps[i], 0, k);
      }
      if (total < k) return false;
      for (int32_t i : cand) {
        const int32_t a[kDims] = {a0[i], a1[i], a2[i]};
        if (a[0] < d[0] || a[1] < d[1] || a[2] < d[2]) continue;
        int32_t am[kDims];
        for (int j = 0; j < kDims; ++j) am[j] = wrap_sub(a[j], d[j]);
        const int32_t cwd = exec_ok[i] ? clamped_cap(am, e, k) : 0;
        if (total - std::clamp<int32_t>(caps[i], 0, k) + cwd >= k) {
          return true;
        }
      }
      return false;
    };

    int64_t headroom = 0;
    int64_t hi = std::min<int64_t>(k_max, total_kmax);
    if (hi >= 1) {
      if (feasible(static_cast<int32_t>(hi))) {
        headroom = hi;
      } else if (feasible(1)) {
        // invariant: lo feasible, hi infeasible
        int64_t lo = 1;
        while (hi - lo > 1) {
          const int64_t mid = lo + (hi - lo) / 2;
          if (feasible(static_cast<int32_t>(mid))) {
            lo = mid;
          } else {
            hi = mid;
          }
        }
        headroom = lo;
      }
    }
    out_headroom[s] = headroom;
    out_probes[s] = probes;
  }
  return 1;
}

// One-sweep per-dimension fragmentation report over the eligible
// (exec_ok) rows:
//   out[j*4+0] total free      Σ max(avail_ij, 0)
//   out[j*4+1] largest chunk   max(avail_ij, 0) over single nodes
//   out[j*4+2] free nodes      count with avail_ij > 0
//   out[j*4+3] overdrawn nodes count with avail_ij < 0
// The fragmentation index (1 − largest/total) is computed by the
// Python caller, which also rescales to base units.
int fifo_frag_report(int64_t nb, const int32_t* avail_rows,
                     const uint8_t* exec_ok, int64_t* out12) {
  if (nb < 0) return 0;
  for (int j = 0; j < kDims * 4; ++j) out12[j] = 0;
  for (int64_t i = 0; i < nb; ++i) {
    if (!exec_ok[i]) continue;
    for (int j = 0; j < kDims; ++j) {
      const int64_t a = avail_rows[i * kDims + j];
      if (a > 0) {
        out12[j * 4 + 0] += a;
        if (a > out12[j * 4 + 1]) out12[j * 4 + 1] = a;
        ++out12[j * 4 + 2];
      } else if (a < 0) {
        ++out12[j * 4 + 3];
      }
    }
  }
  return 1;
}

// CPython-compatible float64 sum: the packing-efficiency gauge
// contract is bit-equality with the host lane's builtin sum(), whose
// float fast path is NEUMAIER-compensated summation (Python 3.12, the
// interpreter this is written for).  The optimize attribute pins scalar
// in-order codegen (vectorizing would reassociate).
__attribute__((optimize("no-tree-vectorize", "no-unroll-loops")))
double seq_sum_f64(const double* v, int64_t n) {
  double s = 0.0, c = 0.0;
  for (int64_t i = 0; i < n; ++i) {
    const double x = v[i];
    const double t = s + x;
    if (std::abs(s) >= std::abs(x)) {
      c += (s - t) + x;
    } else {
      c += (x - t) + s;
    }
    s = t;
  }
  return s + c;
}

}  // extern "C"
