// Native row bucketing + byte-packing for the streaming execution path.
//
// The streaming engine (pipelinedp_tpu/ops/streaming.py) hash-shards rows
// by privacy id into pid-disjoint buckets and ships each bucket byte-packed
// to the device. Doing that with numpy costs one full-array pass per bucket
// (flatnonzero + three gathers + byte splits, ~1 s per bucket at the
// benchmark scale); this helper does the whole job in one two-pass radix
// partition over the input, multithreaded, writing the packed per-bucket
// buffers directly. Role: the native data-loader stage (SURVEY.md §2.5 —
// the reference delegates its loader hot path to Beam/Spark native runners).
//
// Layout written: out[bucket][slot] = bytes_pid little-endian bytes of
// (pid - pid_lo) | bytes_pk bytes of pk | 4 bytes f32 value (or 2 bytes
// f16 when value_f16). Buckets are pid-disjoint by construction
// (bucket = knuth_hash(pid - pid_lo) % n_buckets, identical to the Python
// fallback in streaming.py).

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cmath>
#include <functional>
#include <cstring>
#include <memory>
#include <thread>
#include <vector>

namespace {

constexpr uint32_t kHashMult = 2654435761u;

// Worker-pool width override (0 = auto: hardware_concurrency capped at
// 16). Set through pdp_set_encode_threads — the Python loader wires the
// validated PIPELINEDP_TPU_ENCODE_THREADS value through before encode
// calls. Output is bit-identical for every width: workers own disjoint
// buckets (RunPool) or disjoint input ranges with precomputed write
// offsets (pdp_pack_buckets, pdp_rle_prep), so the thread count only
// changes wall time, never bytes.
std::atomic<int> g_encode_threads{0};

int64_t PoolWidth(int64_t auto_cap) {
  const int forced = g_encode_threads.load(std::memory_order_relaxed);
  if (forced > 0) return forced;
  unsigned hw = std::thread::hardware_concurrency();
  int64_t n = hw < 1 ? 1 : static_cast<int64_t>(hw);
  return n > auto_cap ? auto_cap : n;
}

inline uint32_t BucketOf(int32_t shifted, uint32_t n_buckets) {
  return ((static_cast<uint32_t>(shifted) * kHashMult) >> 16) % n_buckets;
}

// f32 -> f16 (round-to-nearest-even), bit-level.
inline uint16_t F32ToF16(float f) {
  uint32_t x;
  std::memcpy(&x, &f, 4);
  uint32_t sign = (x >> 16) & 0x8000u;
  int32_t exp = static_cast<int32_t>((x >> 23) & 0xff) - 127 + 15;
  uint32_t mant = x & 0x7fffffu;
  if (exp >= 31) {
    // Overflow -> inf; NaN keeps a nonzero mantissa (matching numpy's
    // f32->f16 cast so the packer and the fallback stay bit-identical).
    if (((x >> 23) & 0xff) == 255 && mant) {
      uint32_t m = mant >> 13;
      if (!m) m = 1;
      return static_cast<uint16_t>(sign | 0x7c00u | m);
    }
    return static_cast<uint16_t>(sign | 0x7c00u);
  }
  if (exp <= 0) {
    if (exp < -10) return static_cast<uint16_t>(sign);
    mant |= 0x800000u;
    uint32_t shift = static_cast<uint32_t>(14 - exp);
    uint32_t half = mant >> shift;
    uint32_t rem = mant & ((1u << shift) - 1);
    uint32_t halfway = 1u << (shift - 1);
    if (rem > halfway || (rem == halfway && (half & 1u))) half += 1;
    return static_cast<uint16_t>(sign | half);
  }
  uint32_t half = (static_cast<uint32_t>(exp) << 10) | (mant >> 13);
  uint32_t rem = mant & 0x1fffu;
  if (rem > 0x1000u || (rem == 0x1000u && (half & 1u))) half += 1;
  return static_cast<uint16_t>(sign | half);
}

struct PackArgs {
  const int32_t* pid;
  const int32_t* pk;
  const float* value;
  int64_t n;
  int32_t pid_lo;
  uint32_t n_buckets;
  int bytes_pid;
  int bytes_pk;
  bool value_f16;
  uint8_t* out;
  int64_t cap;
  int width;
};

inline void WriteRow(const PackArgs& a, int64_t row, uint8_t* dst) {
  uint32_t spid = static_cast<uint32_t>(a.pid[row] - a.pid_lo);
  for (int b = 0; b < a.bytes_pid; ++b) dst[b] = (spid >> (8 * b)) & 0xff;
  uint32_t pk = static_cast<uint32_t>(a.pk[row]);
  uint8_t* d = dst + a.bytes_pid;
  for (int b = 0; b < a.bytes_pk; ++b) d[b] = (pk >> (8 * b)) & 0xff;
  d += a.bytes_pk;
  if (a.value_f16) {
    uint16_t h = F32ToF16(a.value ? a.value[row] : 0.0f);
    d[0] = h & 0xff;
    d[1] = (h >> 8) & 0xff;
  } else {
    float v = a.value ? a.value[row] : 0.0f;
    std::memcpy(d, &v, 4);
  }
}

}  // namespace

extern "C" {

// Two-pass multithreaded radix partition + byte pack.
//   out: n_buckets * cap * width bytes (bucket-major).
//   counts: n_buckets entries, filled with rows per bucket.
// Returns 0 on success, 1 on bad args, 2 if any bucket exceeds cap
// (counts still valid — caller re-allocates with counts.max() and retries).
int pdp_pack_buckets(const int32_t* pid, const int32_t* pk,
                     const float* value, int64_t n, int32_t pid_lo,
                     int64_t n_buckets, int bytes_pid, int bytes_pk,
                     int value_f16, uint8_t* out, int64_t cap,
                     int64_t* counts) {
  if (!pid || !pk || !out || !counts || n < 0 || n_buckets <= 0 ||
      bytes_pid < 1 || bytes_pid > 4 || bytes_pk < 1 || bytes_pk > 4) {
    return 1;
  }
  PackArgs args{pid,      pk,       value,
                n,        pid_lo,   static_cast<uint32_t>(n_buckets),
                bytes_pid, bytes_pk, value_f16 != 0,
                out,      cap,      bytes_pid + bytes_pk + (value_f16 ? 2 : 4)};

  int64_t n_threads = PoolWidth(16);
  if (g_encode_threads.load(std::memory_order_relaxed) <= 0 &&
      n < (1 << 16)) {
    n_threads = 1;  // auto mode: thread spawn beats the work below 64k rows
  }
  if (n_threads > n && n > 0) n_threads = n;
  if (n_threads < 1) n_threads = 1;
  int64_t per = (n + n_threads - 1) / n_threads;

  // Pass 1: per-thread per-bucket counts.
  std::vector<std::vector<int64_t>> thread_counts(
      n_threads, std::vector<int64_t>(n_buckets, 0));
  {
    std::vector<std::thread> threads;
    for (int64_t t = 0; t < n_threads; ++t) {
      threads.emplace_back([&, t] {
        int64_t lo = t * per;
        int64_t hi = lo + per < n ? lo + per : n;
        auto& local = thread_counts[t];
        for (int64_t i = lo; i < hi; ++i) {
          local[BucketOf(pid[i] - pid_lo, args.n_buckets)] += 1;
        }
      });
    }
    for (auto& th : threads) th.join();
  }
  // Per-(thread, bucket) write offsets; totals into counts.
  std::vector<std::vector<int64_t>> offsets(
      n_threads, std::vector<int64_t>(n_buckets, 0));
  bool overflow = false;
  for (int64_t b = 0; b < n_buckets; ++b) {
    int64_t acc = 0;
    for (int64_t t = 0; t < n_threads; ++t) {
      offsets[t][b] = acc;
      acc += thread_counts[t][b];
    }
    counts[b] = acc;
    if (acc > cap) overflow = true;
  }
  if (overflow) return 2;

  // Pass 2: write rows, bucket-major output, per-thread disjoint slots.
  {
    std::vector<std::thread> threads;
    for (int64_t t = 0; t < n_threads; ++t) {
      threads.emplace_back([&, t] {
        int64_t lo = t * per;
        int64_t hi = lo + per < n ? lo + per : n;
        auto local = offsets[t];  // copy: mutated as we write
        for (int64_t i = lo; i < hi; ++i) {
          uint32_t b = BucketOf(pid[i] - pid_lo, args.n_buckets);
          int64_t slot = local[b]++;
          uint8_t* dst =
              out + (static_cast<int64_t>(b) * cap + slot) * args.width;
          WriteRow(args, i, dst);
        }
      });
    }
    for (auto& th : threads) th.join();
  }
  return 0;
}

}  // extern "C"


// ---------------------------------------------------------------------------
// Lossless RLE + bit-plane wire codec (native fast path; the numpy
// reference in ops/wirecodec.py produces bit-identical buffers).
//
// Three-call API so the per-slab encode can overlap the previous slab's
// async host->device transfer (ops/streaming.py drives it):
//   pdp_rle_prep        count + scatter over contiguous row ranges on the
//                       pool: bucket rows (pid-hash, same bucketing as
//                       pdp_pack_buckets) into bucket-major SoA temps, and
//                       (span permitting) exact per-bucket RLE entry
//                       counts — so the wire format is known BEFORE any
//                       sorting and the sort can pipeline per slab.
//   pdp_rle_sort_range  per bucket: LSD radix sort by shifted pid (stable,
//                       byte passes only up to the bucket's max id) +
//                       exact RLE entry counts. The expensive step —
//                       callers interleave it slab-by-slab with emit +
//                       device_put so it hides behind transfer + kernel.
//   pdp_rle_emit_range  per bucket: write one flat slab row =
//                       [uniq ids | uint16 run lengths | pk bit-planes |
//                       value planes/raw], runs split at 65535 — or, in
//                       pid_mode 1, unsorted pid bit-planes (no host sort;
//                       the device kernel sorts anyway).
//   pdp_rle_free        release the state.
//
// Bit-planes are LSB-first: plane j, byte r>>3, bit r&7 = bit j of row r.
// Packing works in 8-row register groups (one byte store per plane per 8
// rows). Every call runs on the PoolWidth worker pool (prep over row
// ranges, sort and emit over buckets); the bytes never depend on the
// width.
// ---------------------------------------------------------------------------

namespace {

constexpr int64_t kRunSplit = 65535;

// Largest (pid_span + 1) for which prep builds the per-pid count table
// that yields exact RLE entry counts BEFORE any sorting (the count table
// is 4 bytes per id in the span). Knowing the entry counts up front lets
// the caller fix the wire format immediately and pipeline the per-bucket
// radix sort behind the transfers instead of running it all up front
// (ops/streaming.py drives this).
constexpr int64_t kMaxEntryCountSpan = int64_t{1} << 26;

// Below this many rows prep stays on one thread whatever the pool width:
// starting the workers costs more than the two passes they would split.
constexpr int64_t kPrepParallelRows = int64_t{1} << 16;

struct RleState {
  int64_t n = 0;
  int64_t k = 0;
  int value_mode = 0;  // 0 none, 1 planes(vidx), 2 raw f32, 3 raw f16
  bool low_grouped = false;  // prep pre-grouped rows by pid low byte
  std::vector<int64_t> bucket_start;  // [k+1]
  // Bucket-major SoA; after sort_range a bucket's slice is pid-sorted.
  // Left uninitialised on allocation: prep's scatter writes every slot,
  // so its workers take the first-touch page faults side by side.
  std::unique_ptr<uint32_t[]> tpid;
  std::unique_ptr<int32_t[]> tpk;
  std::unique_ptr<float[]> tval;
  std::unique_ptr<int32_t[]> tvidx;
  std::vector<char> sorted;  // per bucket
};

// Stable LSD radix sort of (pid << 32 | local_index) pairs, key byte
// passes [first_pass, nbytes). Stability (index in the low bits) makes
// the row order identical to numpy's kind="stable" argsort in the
// reference encoder; prep's scatter already performed pass 0 (grouping by
// the pid low byte), so sorts normally start at pass 1.
void RadixSortPairs(uint64_t* a, uint64_t* tmp, int64_t m, int first_pass,
                    int nbytes) {
  for (int p = first_pass; p < nbytes; ++p) {
    const int shift = 32 + 8 * p;
    int64_t hist[256] = {0};
    for (int64_t i = 0; i < m; ++i) hist[(a[i] >> shift) & 0xff]++;
    int64_t acc = 0;
    for (int v = 0; v < 256; ++v) {
      int64_t c = hist[v];
      hist[v] = acc;
      acc += c;
    }
    for (int64_t i = 0; i < m; ++i) tmp[hist[(a[i] >> shift) & 0xff]++] = a[i];
    std::swap(a, tmp);
  }
  if ((nbytes - first_pass) & 1) {
    std::memcpy(tmp, a, m * 8);  // result back into caller's a
  }
}

void SortBucket(RleState* st, int64_t b) {
  const int64_t s = st->bucket_start[b];
  const int64_t m = st->bucket_start[b + 1] - s;
  if (m == 0 || st->sorted[b]) {
    st->sorted[b] = 1;
    return;
  }
  uint32_t maxpid = 0;
  for (int64_t i = 0; i < m; ++i) maxpid |= st->tpid[s + i];
  int nbytes = 1;
  while (nbytes < 4 && (maxpid >> (8 * nbytes))) ++nbytes;
  const int first_pass = st->low_grouped ? 1 : 0;
  if (nbytes <= first_pass) {
    st->sorted[b] = 1;  // single-byte ids: the prep grouping IS the sort
    return;
  }
  std::vector<uint64_t> a(m), tmp(m);
  for (int64_t i = 0; i < m; ++i) {
    a[i] = (static_cast<uint64_t>(st->tpid[s + i]) << 32) |
           static_cast<uint64_t>(i);
  }
  // RadixSortPairs leaves the sorted pairs in `a` for any pass count (odd
  // counts copy back).
  RadixSortPairs(a.data(), tmp.data(), m, first_pass, nbytes);
  const uint64_t* order = a.data();
  // Permute payload columns into sorted order via one gather each.
  {
    std::vector<int32_t> scratch(m);
    for (int64_t i = 0; i < m; ++i) {
      scratch[i] = st->tpk[s + (order[i] & 0xffffffffu)];
    }
    std::memcpy(&st->tpk[s], scratch.data(), m * 4);
    if (st->value_mode == 1) {
      for (int64_t i = 0; i < m; ++i) {
        scratch[i] = st->tvidx[s + (order[i] & 0xffffffffu)];
      }
      std::memcpy(&st->tvidx[s], scratch.data(), m * 4);
    } else if (st->value_mode == 2 || st->value_mode == 3) {
      float* fs = reinterpret_cast<float*>(scratch.data());
      for (int64_t i = 0; i < m; ++i) {
        fs[i] = st->tval[s + (order[i] & 0xffffffffu)];
      }
      std::memcpy(&st->tval[s], scratch.data(), m * 4);
    }
  }
  for (int64_t i = 0; i < m; ++i) {
    st->tpid[s + i] = static_cast<uint32_t>(order[i] >> 32);
  }
  st->sorted[b] = 1;
}

int64_t CountRleEntries(const RleState* st, int64_t b) {
  const int64_t s = st->bucket_start[b];
  const int64_t m = st->bucket_start[b + 1] - s;
  int64_t entries = 0, run = 0;
  uint32_t prev = 0;
  for (int64_t i = 0; i < m; ++i) {
    const uint32_t id = st->tpid[s + i];
    if (i == 0 || id != prev || run == kRunSplit) {
      if (i != 0) ++entries;
      prev = id;
      run = 0;
    }
    ++run;
  }
  if (m > 0) ++entries;
  return entries;
}

// Bit-plane pack `col[0..m)` (values < 2^bits) into planes at out
// (stride cap8 bytes per plane), 8 rows per byte store.
void PackPlanes(const int32_t* col, int64_t m, int bits, int64_t cap8,
                uint8_t* out) {
  for (int64_t r8 = 0; r8 * 8 < m; ++r8) {
    const int g = static_cast<int>(m - r8 * 8 < 8 ? m - r8 * 8 : 8);
    uint32_t vals[8] = {0, 0, 0, 0, 0, 0, 0, 0};
    for (int i = 0; i < g; ++i) {
      vals[i] = static_cast<uint32_t>(col[r8 * 8 + i]);
    }
    for (int j = 0; j < bits; ++j) {
      uint8_t byte = 0;
      for (int i = 0; i < 8; ++i) byte |= ((vals[i] >> j) & 1u) << i;
      out[j * cap8 + r8] = byte;
    }
  }
}

// fn(b) for every b in [k0, k1) on up to `pool` workers (default: the
// PoolWidth pool), each taking the next index as it frees.
void RunPool(int64_t k0, int64_t k1, const std::function<void(int64_t)>& fn,
             int64_t pool = -1) {
  if (pool < 0) pool = PoolWidth(16);
  if (pool > k1 - k0) pool = k1 - k0;
  if (pool <= 1) {
    for (int64_t b = k0; b < k1; ++b) fn(b);
    return;
  }
  std::atomic<int64_t> next{k0};
  std::vector<std::thread> threads;
  for (int64_t t = 0; t < pool; ++t) {
    threads.emplace_back([&] {
      for (;;) {
        const int64_t b = next.fetch_add(1);
        if (b >= k1) return;
        fn(b);
      }
    });
  }
  for (auto& th : threads) th.join();
}

}  // namespace

extern "C" {

// Prep: a counting pass and a scatter pass into bucket-major SoA temps,
// both split into T contiguous row ranges on the pool (T = PoolWidth,
// or 1 below kPrepParallelRows rows).
//   1. Worker t counts its range per (bucket, pid low byte) into its own
//      k x 256 table. The tables are prefix-summed in (bucket, low byte,
//      worker) order, so worker t's rows for a cursor land right after
//      worker t-1's: every row takes the slot a single-thread stable
//      scatter gives it, and the temps are the same bytes at any T.
//   2. Worker t scatters its range with its own cursors. The scatter
//      groups rows by the pid low byte inside each bucket — that is pass
//      0 of the stable LSD radix sort, so sort_range only runs the
//      remaining byte passes.
// The per-pid count table (below) is filled after the scatter, one
// bucket per worker: a pid lands in exactly one bucket, so no two
// workers touch the same entry.
//
// value_mode 1 with vidx == NULL computes the affine value index inline:
// idx = rint((value - v_lo) / v_scale), verified bit-exact against the
// float32 reconstruction the device performs. stats[0] is set to 1 (and
// nullptr returned) if any row fails verification or leaves [0, 2^20);
// stats[1] returns the maximum index (for the bit-width of the planes);
// stats[2] returns the max rows of any single pid when the count table
// was built (ABI 7; -1 otherwise) — it bounds every pid segment in every
// bucket, sizing the tile slack of the kernel's segment-local sort;
// stats[3] returns T, the workers the two passes ran on (ABI 8).
//
// pid_span / n_entries: when n_entries is non-null and the shifted pid
// span fits the count-table budget, n_entries[b] receives the EXACT
// post-sort RLE entry count of bucket b (sum of ceil(rows_per_pid /
// 65535) over the bucket's pids — a pid maps to exactly one bucket, so
// this equals what pdp_rle_sort_range will report), computed without
// sorting. Otherwise n_entries[0] is set to -1 and the caller falls back
// to learning entry counts from the upfront sort.
void* pdp_rle_prep(const int32_t* pid, const int32_t* pk, const float* value,
                   const int32_t* vidx, double v_lo, double v_scale,
                   int64_t n, int32_t pid_lo, int64_t k, int value_mode,
                   int64_t pid_span, int64_t* n_entries,
                   int64_t* n_rows, int64_t* stats) {
  if (!pid || !pk || !n_rows || !stats || n < 0 || k <= 0) return nullptr;
  const bool inline_vidx = value_mode == 1 && vidx == nullptr;
  if (value_mode == 1 && !vidx && !value) return nullptr;
  if ((value_mode == 2 || value_mode == 3 || inline_vidx) && !value) {
    return nullptr;
  }
  const int64_t n_threads = n < kPrepParallelRows ? 1 : PoolWidth(16);
  const int64_t per = (n + n_threads - 1) / n_threads;
  const int64_t table = k * 256;  // one worker's (bucket, low byte) cursors
  const uint32_t ku = static_cast<uint32_t>(k);
  stats[0] = 0;
  stats[1] = 0;
  stats[2] = -1;
  stats[3] = n_threads;
  std::unique_ptr<RleState> st(new RleState());
  st->n = n;
  st->k = k;
  st->value_mode = value_mode;
  st->low_grouped = true;
  st->bucket_start.assign(k + 1, 0);
  st->sorted.assign(k, 0);
  // Pass 1: each worker counts its rows per (bucket, pid low byte) — the
  // sub-cursor table that makes the scatter double as radix pass 0.
  std::vector<int64_t> sub(n_threads * table, 0);
  RunPool(0, n_threads, [&](int64_t t) {
    int64_t* local = &sub[t * table];
    const int64_t hi = std::min(n, (t + 1) * per);
    for (int64_t i = t * per; i < hi; ++i) {
      const uint32_t spid = static_cast<uint32_t>(pid[i] - pid_lo);
      local[(static_cast<int64_t>(BucketOf(pid[i] - pid_lo, ku)) << 8) |
            (spid & 0xff)]++;
    }
  }, n_threads);
  // Cursors in (bucket, low byte, worker) order.
  {
    int64_t acc = 0;
    for (int64_t b = 0; b < k; ++b) {
      st->bucket_start[b] = acc;
      for (int64_t v = 0; v < 256; ++v) {
        for (int64_t t = 0; t < n_threads; ++t) {
          int64_t& cell = sub[t * table + ((b << 8) | v)];
          const int64_t c = cell;
          cell = acc;
          acc += c;
        }
      }
      n_rows[b] = acc - st->bucket_start[b];
    }
    st->bucket_start[k] = acc;
  }
  st->tpid.reset(new uint32_t[n]);
  st->tpk.reset(new int32_t[n]);
  if (value_mode == 2 || value_mode == 3) st->tval.reset(new float[n]);
  if (value_mode == 1) st->tvidx.reset(new int32_t[n]);
  // Pass 2: each worker scatters its rows through its own cursors, and
  // keeps its own verification outcome and max index.
  const float lo_f = static_cast<float>(v_lo);
  const float scale_f = static_cast<float>(v_scale);
  std::vector<char> failed(n_threads, 0);
  std::vector<int64_t> max_idx(n_threads, 0);
  RunPool(0, n_threads, [&](int64_t t) {
    int64_t* cursor = &sub[t * table];
    const int64_t hi = std::min(n, (t + 1) * per);
    bool verify_failed = false;
    int64_t local_max = 0;
    for (int64_t i = t * per; i < hi; ++i) {
      const uint32_t spid = static_cast<uint32_t>(pid[i] - pid_lo);
      const uint32_t b = BucketOf(pid[i] - pid_lo, ku);
      const int64_t slot = cursor[(static_cast<int64_t>(b) << 8) |
                                  (spid & 0xff)]++;
      st->tpid[slot] = spid;
      st->tpk[slot] = pk[i];
      if (value_mode == 2 || value_mode == 3) st->tval[slot] = value[i];
      if (value_mode == 1) {
        if (inline_vidx) {
          if (verify_failed) break;  // state is discarded on failure
          const float v = value[i];
          // nearbyint: ties-to-even, matching the numpy reference's
          // np.rint so native and fallback encoders emit bit-identical
          // buffers.
          const int64_t idx = static_cast<int64_t>(
              std::nearbyint((static_cast<double>(v) - v_lo) / v_scale));
          if (idx < 0 || idx >= (1 << 20)) {
            verify_failed = true;
            st->tvidx[slot] = 0;
            continue;
          }
          const float rec = lo_f + static_cast<float>(idx) * scale_f;
          uint32_t rb, vb;
          std::memcpy(&rb, &rec, 4);
          std::memcpy(&vb, &v, 4);
          if (rb != vb) verify_failed = true;
          if (idx > local_max) local_max = idx;
          st->tvidx[slot] = static_cast<int32_t>(idx);
        } else {
          st->tvidx[slot] = vidx[i];
        }
      }
    }
    failed[t] = verify_failed;
    max_idx[t] = local_max;
  }, n_threads);
  if (inline_vidx &&
      std::find(failed.begin(), failed.end(), 1) != failed.end()) {
    stats[0] = 1;
    return nullptr;  // st is discarded
  }
  stats[1] = *std::max_element(max_idx.begin(), max_idx.end());
  // Per-pid row counts (when the span fits the budget), one bucket per
  // worker, for the exact RLE entry counts and the max run.
  const bool count_entries =
      n_entries != nullptr && pid_span >= 0 &&
      pid_span + 1 <= kMaxEntryCountSpan &&
      n <= static_cast<int64_t>(UINT32_MAX) / 2;
  if (n_entries != nullptr) {
    if (count_entries) {
      std::vector<uint32_t> pid_count(pid_span + 1, 0);
      RunPool(0, k, [&](int64_t b) {
        for (int64_t i = st->bucket_start[b]; i < st->bucket_start[b + 1];
             ++i) {
          pid_count[st->tpid[i]]++;
        }
      }, n_threads);
      for (int64_t b = 0; b < k; ++b) n_entries[b] = 0;
      int64_t max_run = 0;
      for (int64_t s = 0; s <= pid_span; ++s) {
        const uint32_t c = pid_count[s];
        if (c) {
          if (static_cast<int64_t>(c) > max_run) {
            max_run = static_cast<int64_t>(c);
          }
          n_entries[BucketOf(static_cast<int32_t>(s), ku)] +=
              (c + kRunSplit - 1) / kRunSplit;
        }
      }
      stats[2] = max_run;
    } else {
      n_entries[0] = -1;
    }
  }
  return st.release();
}

int pdp_rle_sort_range(void* handle, int64_t b0, int64_t b1,
                       int64_t* n_uniq) {
  auto* st = static_cast<RleState*>(handle);
  if (!st || !n_uniq || b0 < 0 || b1 > st->k || b0 > b1) return 1;
  RunPool(b0, b1, [&](int64_t b) {
    SortBucket(st, b);
    n_uniq[b - b0] = CountRleEntries(st, b);
  });
  return 0;
}

// out: [b1-b0, width] flat slab rows.
// pid_mode 0 (RLE): buckets must be sorted; width = ucap*bytes_pid +
//   ucap*2 + bits_pk*cap/8 + value bytes.
// pid_mode 1 (bit-planes): pids ship as bits_pid planes in arrival order —
//   NO host sort required (the device kernel sorts anyway); width =
//   bits_pid*cap/8 + bits_pk*cap/8 + value bytes, and ucap is ignored.
int pdp_rle_emit_range(void* handle, int64_t b0, int64_t b1, int pid_mode,
                       int bytes_pid, int bits_pid,
                       int bits_pk, int bits_val, int64_t cap, int64_t ucap,
                       uint8_t* out, int64_t width) {
  auto* st = static_cast<RleState*>(handle);
  const bool planes = pid_mode == 1;
  if (!st || !out || b0 < 0 || b1 > st->k || b0 > b1 || cap < 8 ||
      (cap % 8) != 0 || bits_pk < 1 || bits_pk > 31 ||
      (planes ? (bits_pid < 1 || bits_pid > 31)
              : (bytes_pid < 1 || bytes_pid > 4 || ucap < 1))) {
    return 1;
  }
  if (st->value_mode == 1 && (bits_val < 1 || bits_val > 31)) return 1;
  const int64_t cap8 = cap / 8;
  const int64_t o_cnt = planes ? bits_pid * cap8 : ucap * bytes_pid;
  const int64_t o_pk = planes ? o_cnt : o_cnt + ucap * 2;
  const int64_t o_val = o_pk + bits_pk * cap8;
  int64_t want = o_val;
  if (st->value_mode == 1) want += bits_val * cap8;
  if (st->value_mode == 2) want += cap * 4;
  if (st->value_mode == 3) want += cap * 2;
  if (want != width) return 1;

  std::atomic<int> rc{0};
  RunPool(b0, b1, [&](int64_t b) {
    const int64_t s = st->bucket_start[b];
    const int64_t m = st->bucket_start[b + 1] - s;
    if ((!planes && !st->sorted[b]) || m > cap) {
      rc.store(2);
      return;
    }
    uint8_t* row = out + (b - b0) * width;
    std::memset(row, 0, width);
    if (planes) {
      // Arrival-order pid planes (shifted ids < 2^bits_pid).
      PackPlanes(reinterpret_cast<const int32_t*>(&st->tpid[s]), m,
                 bits_pid, cap8, row);
    } else {
      // RLE of the sorted pid column.
      int64_t entries = 0, run = 0;
      uint32_t prev = 0;
      auto flush = [&](uint32_t id, int64_t len) {
        if (entries >= ucap) {
          rc.store(3);
          return false;
        }
        uint8_t* u = row + entries * bytes_pid;
        for (int bb = 0; bb < bytes_pid; ++bb) {
          u[bb] = (id >> (8 * bb)) & 0xff;
        }
        row[o_cnt + entries * 2] = len & 0xff;
        row[o_cnt + entries * 2 + 1] = (len >> 8) & 0xff;
        ++entries;
        return true;
      };
      for (int64_t i = 0; i < m; ++i) {
        const uint32_t id = st->tpid[s + i];
        if (i == 0) {
          prev = id;
          run = 0;
        } else if (id != prev || run == kRunSplit) {
          if (!flush(prev, run)) return;
          prev = id;
          run = 0;
        }
        ++run;
      }
      if (m > 0 && !flush(prev, run)) return;
    }
    // pk planes, then the value column.
    PackPlanes(&st->tpk[s], m, bits_pk, cap8, row + o_pk);
    if (st->value_mode == 1) {
      PackPlanes(&st->tvidx[s], m, bits_val, cap8, row + o_val);
    } else if (st->value_mode == 2) {
      std::memcpy(row + o_val, &st->tval[s], m * 4);
    } else if (st->value_mode == 3) {
      uint8_t* v = row + o_val;
      for (int64_t i = 0; i < m; ++i) {
        const uint16_t h = F32ToF16(st->tval[s + i]);
        v[i * 2] = h & 0xff;
        v[i * 2 + 1] = (h >> 8) & 0xff;
      }
    }
  });
  return rc.load();
}

void pdp_rle_free(void* handle) { delete static_cast<RleState*>(handle); }

// Encode worker-pool width: 0 restores auto (hardware_concurrency capped
// at 16); values are clamped to [0, 64]. Applies to pdp_pack_buckets,
// pdp_rle_prep, pdp_rle_sort_range and pdp_rle_emit_range. The callers'
// loader wires PIPELINEDP_TPU_ENCODE_THREADS through here.
void pdp_set_encode_threads(int n) {
  if (n < 0) n = 0;
  if (n > 64) n = 64;
  g_encode_threads.store(n, std::memory_order_relaxed);
}

int pdp_get_encode_threads() {
  return g_encode_threads.load(std::memory_order_relaxed);
}

int pdp_row_packer_abi_version() { return 8; }

}  // extern "C"
