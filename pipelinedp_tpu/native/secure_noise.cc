// Secure discrete-noise sampling for differential privacy.
//
// Native (C++) equivalent of the security-critical sampling the reference
// delegates to Google's C++ differential-privacy library through PyDP
// (SURVEY.md section 2.4; call sites pipeline_dp/dp_computations.py:130-151).
// Naive float Laplace sampling leaks through the float representation
// (Mironov 2012); the defense here is to sample *integers* from the exact
// discrete Laplace / discrete Gaussian distributions and scale by a
// power-of-two granularity on the Python side, so the released value is a
// granularity multiple and the sampler itself never touches floating-point
// transcendentals of secret data.
//
// Sampling algorithms: Canonne, Kamath, Steinke, "The Discrete Gaussian for
// Differential Privacy" (NeurIPS 2020), Algorithms 1-3 — exact rejection
// samplers built from Bernoulli(exp(-x)) coin flips. Entropy: getrandom(2)
// (the kernel CSPRNG), buffered per thread. The only deviation from
// exactness is Bernoulli(p) on a 64-bit uniform, a bias of at most 2^-64
// per coin (the same concession Google's library makes).
//
// Deliberately NOT seedable: secure noise must not be replayable.

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <mutex>
#include <thread>

#include <pthread.h>
#include <sched.h>
#include <sys/random.h>

namespace {

// --- buffered kernel CSPRNG ------------------------------------------------

class EntropyBuffer {
 public:
  uint64_t NextU64() {
    if (pos_ + 8 > kBufSize) Refill();
    uint64_t out;
    std::memcpy(&out, buf_ + pos_, 8);
    pos_ += 8;
    return out;
  }

  // Drops whatever is buffered: the next draw refills from the kernel.
  void Discard() { pos_ = kBufSize; }

 private:
  static constexpr size_t kBufSize = 1 << 16;

  void Refill() {
    size_t got = 0;
    while (got < kBufSize) {
      ssize_t r = getrandom(buf_ + got, kBufSize - got, 0);
      if (r > 0) {
        got += static_cast<size_t>(r);
        continue;
      }
      if (r < 0 && (errno == EINTR || errno == EAGAIN)) continue;
      // Non-retryable (ENOSYS on ancient kernels, EPERM under seccomp):
      // try /dev/urandom once, else die loudly — silently degraded entropy
      // is the one failure a secure sampler must never absorb, and this
      // runs under a ctypes call where an exception can't propagate.
      if (!RefillFromDevUrandom(got)) {
        std::fprintf(stderr,
                     "pipelinedp_tpu secure_noise: no entropy source "
                     "(getrandom errno=%d, /dev/urandom unreadable)\n",
                     errno);
        std::abort();
      }
      got = kBufSize;
    }
    pos_ = 0;
  }

  bool RefillFromDevUrandom(size_t from) {
    std::FILE* f = std::fopen("/dev/urandom", "rb");
    if (!f) return false;
    size_t need = kBufSize - from;
    size_t got = std::fread(buf_ + from, 1, need, f);
    std::fclose(f);
    return got == need;
  }

  unsigned char buf_[kBufSize];
  size_t pos_ = kBufSize;  // force refill on first use
};

thread_local EntropyBuffer tl_entropy;

// Bernoulli(p): bias <= 2^-64. The threshold p * 2^64 is computed exactly
// in 128-bit integer arithmetic from p's (mantissa, exponent) decomposition
// — no extended-precision float type involved, so the bound holds on every
// ABI (long double == double included); only the sub-2^-64 fractional part
// of the threshold is truncated, the same concession as a 64-bit uniform.
inline bool Bernoulli(double p) {
  if (p <= 0.0) return false;
  if (p >= 1.0) return true;
  int e;
  double m = std::frexp(p, &e);  // p = m * 2^e, m in [0.5, 1), e <= 0
  // 53-bit integer mantissa, exact: p = mant * 2^(e-53).
  uint64_t mant = static_cast<uint64_t>(std::ldexp(m, 53));
  int shift = e + 11;  // p * 2^64 = mant * 2^shift
  unsigned __int128 threshold;
  if (shift >= 0) {
    threshold = static_cast<unsigned __int128>(mant) << shift;
  } else if (shift > -64) {
    threshold = mant >> -shift;  // truncation bias < 2^-64
  } else {
    threshold = 0;  // p < 2^-75: below the uniform's resolution
  }
  return static_cast<unsigned __int128>(tl_entropy.NextU64()) < threshold;
}

// Unbiased Uniform{0, ..., n-1} by rejection.
inline uint64_t UniformBelow(uint64_t n) {
  uint64_t limit = UINT64_MAX - (UINT64_MAX % n);
  for (;;) {
    uint64_t u = tl_entropy.NextU64();
    if (u < limit) return u % n;
  }
}

// Bernoulli(exp(-gamma)) for gamma in [0, 1] (CKS Algorithm 1 core): count
// successes of Bernoulli(gamma/k); exp(-gamma) is the probability of an
// even count.
inline bool BernoulliExpAtMostOne(double gamma) {
  uint64_t k = 1;
  for (;;) {
    if (!Bernoulli(gamma / static_cast<double>(k))) break;
    ++k;
  }
  return (k & 1) == 1;  // k-1 successes, even
}

// Bernoulli(exp(-gamma)) for any gamma >= 0.
inline bool BernoulliExp(double gamma) {
  while (gamma > 1.0) {
    if (!BernoulliExpAtMostOne(1.0)) return false;
    gamma -= 1.0;
  }
  return BernoulliExpAtMostOne(gamma);
}

// Discrete Laplace with scale t (integer t >= 1): P(X = x) proportional to
// exp(-|x|/t). CKS Algorithm 2.
inline int64_t DiscreteLaplace(uint64_t t) {
  for (;;) {
    uint64_t u = UniformBelow(t);
    if (!BernoulliExp(static_cast<double>(u) / static_cast<double>(t)))
      continue;
    uint64_t v = 0;
    while (BernoulliExpAtMostOne(1.0)) ++v;
    uint64_t x = u + t * v;
    bool negative = Bernoulli(0.5);
    if (negative && x == 0) continue;
    int64_t xi = static_cast<int64_t>(x);
    return negative ? -xi : xi;
  }
}

// Discrete Gaussian with parameter sigma (in integer units): P(X = x)
// proportional to exp(-x^2 / (2 sigma^2)). CKS Algorithm 3: rejection from
// discrete Laplace(t), t = floor(sigma) + 1.
inline int64_t DiscreteGaussian(double sigma) {
  uint64_t t = static_cast<uint64_t>(std::floor(sigma)) + 1;
  double sigma_sq = sigma * sigma;
  for (;;) {
    int64_t y = DiscreteLaplace(t);
    double ay = static_cast<double>(y < 0 ? -y : y);
    double d = ay - sigma_sq / static_cast<double>(t);
    if (BernoulliExp(d * d / (2.0 * sigma_sq))) return y;
  }
}

// A draw splits into fixed blocks of kBlock values, handed out one block
// at a time to min(blocks, width) threads: the calling thread and helpers
// of a persistent pool. A draw of at most one block stays on the calling
// thread and touches no pool (the scalar draws of the per-value
// backends). Each thread draws from its own thread_local entropy buffer;
// none is shared or copied. The block size and the pool come from
// timings on a 13-CPU TPU v5e host (PERF.md): 4,096 is as fast as any
// size on 17,770 and 59,047 values, and spawning 12 threads per draw
// costs ~2.7 ms there.
constexpr int64_t kBlock = 4096;
// Same cap as row_packer.cc's encoder pool.
constexpr int64_t kMaxThreads = 16;

// The CPUs this process may run on (its affinity mask, which a container
// narrows; hardware_concurrency counts the whole machine), capped.
int64_t PoolWidth() {
  cpu_set_t set;
  CPU_ZERO(&set);
  int64_t n = 1;
  if (sched_getaffinity(0, sizeof(set), &set) == 0) n = CPU_COUNT(&set);
  if (n < 1) n = 1;
  return n > kMaxThreads ? kMaxThreads : n;
}

int64_t ThreadsFor(int64_t n) {
  if (n <= kBlock) return 1;
  int64_t blocks = (n + kBlock - 1) / kBlock;
  int64_t width = PoolWidth();
  return blocks < width ? blocks : width;
}

// Helper threads parked on a condition variable between draws.
class DrawPool {
 public:
  explicit DrawPool(int64_t helpers) : size_(helpers) {
    for (int64_t t = 0; t < helpers; ++t)
      std::thread([this] { Loop(); }).detach();
  }
  int64_t size() const { return size_; }

  // Runs work on the calling thread and on up to `helpers` pool threads;
  // returns when every thread that took a part has finished it.
  void Run(int64_t helpers, const std::function<void()>& work) {
    {
      std::lock_guard<std::mutex> l(mu_);
      work_ = &work;
      want_ = helpers;
      active_ = helpers;
      ++gen_;
    }
    wake_.notify_all();
    work();
    std::unique_lock<std::mutex> l(mu_);
    active_ -= want_;  // parts no helper woke for: their blocks are done
    want_ = 0;
    done_.wait(l, [this] { return active_ == 0; });
  }

 private:
  void Loop() {
    uint64_t seen = 0;
    std::unique_lock<std::mutex> l(mu_);
    for (;;) {
      wake_.wait(l, [&] { return gen_ != seen; });
      seen = gen_;
      if (want_ == 0) continue;
      --want_;
      const std::function<void()>* work = work_;
      l.unlock();
      (*work)();
      l.lock();
      if (--active_ == 0) done_.notify_one();
    }
  }

  const int64_t size_;
  std::mutex mu_;
  std::condition_variable wake_, done_;
  const std::function<void()>* work_ = nullptr;
  uint64_t gen_ = 0;
  int64_t want_ = 0;
  int64_t active_ = 0;
};

// One parallel draw at a time owns the pool. fork() waits for it (the
// prepare handler takes the lock); the child, whose helpers do not exist,
// builds a new pool on its next parallel draw and drops the forking
// thread's buffered entropy, which the parent goes on drawing from.
std::mutex g_pool_mu;
DrawPool* g_pool = nullptr;

const int kAtFork = pthread_atfork(
    [] { g_pool_mu.lock(); }, [] { g_pool_mu.unlock(); },
    [] {
      g_pool = nullptr;  // the parent's pool is leaked, never touched
      tl_entropy.Discard();
      g_pool_mu.unlock();
    });

template <typename T, typename Fn>
void ParallelFill(T* out, int64_t n, const Fn& sample_one) {
  const int64_t n_threads = ThreadsFor(n);
  if (n_threads <= 1) {
    for (int64_t i = 0; i < n; ++i) out[i] = sample_one();
    return;
  }
  const int64_t n_blocks = (n + kBlock - 1) / kBlock;
  std::atomic<int64_t> next{0};
  const std::function<void()> work = [&] {
    for (int64_t b; (b = next.fetch_add(1)) < n_blocks;) {
      const int64_t e = (b + 1) * kBlock < n ? (b + 1) * kBlock : n;
      for (int64_t i = b * kBlock; i < e; ++i) out[i] = sample_one();
    }
  };
  std::lock_guard<std::mutex> l(g_pool_mu);
  if (g_pool == nullptr) g_pool = new DrawPool(PoolWidth() - 1);
  g_pool->Run(std::min(n_threads - 1, g_pool->size()), work);
}

}  // namespace

extern "C" {

// ABI version for the Python loader's sanity check.
int pdp_noise_abi_version() { return 3; }

// The number of host threads a draw of n values runs on.
int pdp_noise_threads_for(int64_t n) {
  return static_cast<int>(ThreadsFor(n));
}

// n samples of discrete Laplace with scale t_units (rounded to >= 1
// integer units). Returns 0 on success.
int pdp_sample_discrete_laplace(int64_t* out, int64_t n, double t_units) {
  if (!out || n < 0 || !(t_units > 0) || !std::isfinite(t_units)) return 1;
  uint64_t t = t_units < 1.0 ? 1 : static_cast<uint64_t>(std::llround(t_units));
  ParallelFill(out, n, [t] { return DiscreteLaplace(t); });
  return 0;
}

// n samples of discrete Gaussian with parameter sigma_units (> 0).
int pdp_sample_discrete_gaussian(int64_t* out, int64_t n,
                                 double sigma_units) {
  if (!out || n < 0 || !(sigma_units > 0) || !std::isfinite(sigma_units))
    return 1;
  ParallelFill(out, n, [sigma_units] { return DiscreteGaussian(sigma_units); });
  return 0;
}

// n uniform doubles in [0, 1) with full 53-bit precision, drawn from the
// kernel CSPRNG. Backs partition-selection keep decisions and exponential-
// mechanism draws: those comparisons ("u < keep_probability") are exactly as
// release-critical as additive noise, so they must not ride a seedable
// userspace PRNG.
int pdp_sample_uniform_double(double* out, int64_t n) {
  if (!out || n < 0) return 1;
  ParallelFill(out, n, [] {
    return static_cast<double>(tl_entropy.NextU64() >> 11) * 0x1.0p-53;
  });
  return 0;
}

}  // extern "C"
