"""Scalar noise core: calibration + sampling for the host-side (driver) path.

Role in the stack: this module is the Python-visible surface of the L0
"native DP primitives" layer (reference reaches it through PyDP's pybind11
wrapper over Google's C++ differential-privacy library —
dp_computations.py:25, see SURVEY.md §2.4). Noise calibration (sigma for the
analytic Gaussian mechanism, Laplace diversity) lives here in pure float
math; *sampling* is delegated to the native C++ library
(pipelinedp_tpu/native/secure_noise.cc — exact discrete Laplace/Gaussian
samplers over the kernel CSPRNG), auto-installed on the first draw when a
compiler is available; the numpy fallback covers environments without one.

Security note (why a native library exists at all): naive float Laplace
sampling leaks information through the floating-point representation
(Mironov 2012, "On significance of the least significant bits for
differential privacy"). The native mitigation is the snapping/granularity
construction: noise is sampled as an *integer* multiple of a power-of-two
granularity (an exact discrete Laplace / discrete Gaussian, Canonne-Kamath-
Steinke 2020), and the value is rounded to the same granularity before
adding. The numpy fallback implements the same granularity snapping on top
of numpy's float samplers — distributions match, bit-level security
guarantees require the native path (check with using_native_sampling()).

The TPU bulk path (pipelinedp_tpu/ops/noise.py, built alongside the JAX
backend) applies the same snapping scheme with JAX's counter-based threefry
PRNG.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np
from scipy import special
from scipy import stats

# 2^-40: relative granularity for Laplace snapping (matches the construction
# used by Google's C++ library: granularity = next power of two of scale/2^40).
_LAPLACE_GRANULARITY_BITS = 40
# 2^-57 for Gaussian.
_GAUSSIAN_GRANULARITY_BITS = 57


def next_power_of_two(x: float) -> float:
    """Smallest power of two >= x (x > 0). Exact for float64."""
    if x <= 0 or not math.isfinite(x):
        raise ValueError(f"next_power_of_two requires finite x > 0, got {x}")
    mantissa, exponent = math.frexp(x)  # x = mantissa * 2**exponent
    if mantissa == 0.5:
        return x
    return math.ldexp(1.0, exponent)


def laplace_granularity(scale: float) -> float:
    return next_power_of_two(
        max(scale, 2.0**-_LAPLACE_GRANULARITY_BITS) *
        2.0**-_LAPLACE_GRANULARITY_BITS)


def gaussian_granularity(stddev: float) -> float:
    return next_power_of_two(
        max(stddev, 2.0**-_GAUSSIAN_GRANULARITY_BITS) *
        2.0**-_GAUSSIAN_GRANULARITY_BITS)


def round_to_granularity(value, granularity: float):
    return np.round(value / granularity) * granularity


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------


def gaussian_delta(sigma: float, eps: float, l2_sensitivity: float) -> float:
    """delta achieved by a Gaussian mechanism (exact analytic expression).

    delta = Phi(s/(2 sigma) - eps sigma/s) - e^eps Phi(-s/(2 sigma) - eps
    sigma/s), per Balle & Wang, "Improving the Gaussian mechanism for
    differential privacy" (arXiv:1805.06530) — the calibration the reference
    uses via PyDP (dp_computations.py:116, cited at
    private_contribution_bounds.py:126).
    """
    s = l2_sensitivity
    a = s / (2.0 * sigma)
    b = eps * sigma / s
    # e^eps Phi(-a-b) in log space: for large eps the exponential overflows
    # while the product stays finite. In fact log_term = eps + log Phi(-a-b)
    # <= eps - (a+b)^2/2 <= eps - 2ab = 0 by AM-GM (2ab = eps), so the
    # product is always <= 1; exp never overflows.
    log_term = eps + special.log_ndtr(-a - b)
    return float(stats.norm.cdf(a - b) - math.exp(log_term))


def analytic_gaussian_sigma(eps: float, delta: float,
                            l2_sensitivity: float) -> float:
    """Minimal sigma with gaussian_delta(sigma) <= delta (binary search)."""
    if eps <= 0:
        raise ValueError(f"eps must be positive, got {eps}")
    if not 0 < delta < 1:
        raise ValueError(f"delta must be in (0, 1), got {delta}")
    if l2_sensitivity <= 0:
        raise ValueError(
            f"l2_sensitivity must be positive, got {l2_sensitivity}")
    # Bracket: classical sigma = sqrt(2 ln(1.25/delta)) * s / eps always works
    # for eps <= 1; double until valid for the general case.
    hi = math.sqrt(2.0 * math.log(1.25 / delta)) * l2_sensitivity / eps
    while gaussian_delta(hi, eps, l2_sensitivity) > delta:
        hi *= 2.0
    lo = hi / 2.0**20
    if gaussian_delta(lo, eps, l2_sensitivity) <= delta:
        return lo
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if gaussian_delta(mid, eps, l2_sensitivity) <= delta:
            hi = mid
        else:
            lo = mid
        if hi - lo <= 1e-12 * hi:
            break
    return hi


def laplace_diversity(eps: float, l1_sensitivity: float) -> float:
    """Laplace scale parameter b = l1_sensitivity / eps."""
    if eps <= 0:
        raise ValueError(f"eps must be positive, got {eps}")
    return l1_sensitivity / eps


# ---------------------------------------------------------------------------
# Sampling. The hooks below start in "autoload" state: the first draw
# builds/loads the native C++ samplers (pipelinedp_tpu/native) and rebinds
# the hooks — deferred so importing the package never shells out to g++.
# The numpy fallback covers environments without a compiler, with a lock
# because backends may draw noise from worker threads.
# ---------------------------------------------------------------------------

import contextlib as _contextlib
import logging as _logging
import threading as _threading

from pipelinedp_tpu import profiler as _profiler

_rng = np.random.default_rng()
_rng_lock = _threading.Lock()

# Profiler counters (OBSERVABILITY.md): every draw through the hooks below,
# and the draws the native sampler split across more than one host thread.
DRAWS_EVENT = "noise/draws"
SPLIT_DRAWS_EVENT = "noise/split_draws"
_draw_peak = _threading.local()


def note_draw(threads: int) -> None:
    """Counts one draw that ran on ``threads`` host threads."""
    _profiler.count_event(DRAWS_EVENT)
    if threads > 1:
        _profiler.count_event(SPLIT_DRAWS_EVENT)
    peak = getattr(_draw_peak, "value", None)
    if peak is not None and threads > peak[0]:
        peak[0] = threads


@_contextlib.contextmanager
def track_draw_threads():
    """Yields a one-item list that ends as the most host threads any draw
    on this thread used inside the block (0 when nothing was drawn)."""
    peak = _draw_peak.value = [0]
    try:
        yield peak
    finally:
        _draw_peak.value = None


def seed_fallback_rng(seed: Optional[int]) -> None:
    """Test hook: reseeds the numpy RNG AND routes sampling through the
    (seedable) fallback — secure native noise is deliberately not
    replayable, so deterministic tests must opt out of it. Call
    pipelinedp_tpu.native.install() to restore the native path."""
    global _rng, sample_laplace, sample_gaussian, sample_uniform
    _rng = np.random.default_rng(seed)
    sample_laplace = _fallback_laplace
    sample_gaussian = _fallback_gaussian
    sample_uniform = _fallback_uniform


def _fallback_laplace(scale: float, size=None):
    g = laplace_granularity(scale)
    with _rng_lock:
        raw = _rng.laplace(0.0, scale, size)
    note_draw(1)
    return round_to_granularity(raw, g)


def _fallback_gaussian(stddev: float, size=None):
    g = gaussian_granularity(stddev)
    with _rng_lock:
        raw = _rng.normal(0.0, stddev, size)
    note_draw(1)
    return round_to_granularity(raw, g)


def _fallback_uniform(size=None):
    note_draw(1)
    with _rng_lock:
        return _rng.random() if size is None else _rng.random(size)


_native_attempted = False


def _try_native_install() -> None:
    """One attempt to build/load the native samplers (rebinds the hooks).

    The first draw may shell out to g++ (see native/loader.py); deployments
    that cannot afford that latency on the first DP release should warm up
    explicitly with pipelinedp_tpu.native.install().
    """
    global _native_attempted
    if _native_attempted:
        return
    _native_attempted = True
    from pipelinedp_tpu.native import loader as native_loader
    try:
        ok = native_loader.install()
    except native_loader.LOADER_ERRORS + (ValueError,) as e:
        # Build/load/ctypes failures fall back to the numpy samplers;
        # NativeRequiredError (and anything else) propagates — under
        # PIPELINEDP_TPU_REQUIRE_NATIVE a toolchain regression must be a
        # hard error, not a silent downgrade of the bit-level guarantees.
        _logging.warning(
            "pipelinedp_tpu: native secure-noise install raised %r; "
            "falling back to the seedable numpy samplers "
            "(distributionally equivalent, weaker bit-level guarantees)", e)
    else:
        if not ok:
            _logging.warning(
                "pipelinedp_tpu: native secure-noise library unavailable "
                "(no compiler, or the build failed — details at INFO "
                "level); noise and selection draws use the seedable numpy "
                "fallback. Warm up at startup with "
                "pipelinedp_tpu.native.install() to control when the "
                "build cost is paid, or ship a prebuilt _secure_noise "
                "shared object matching the current ABI.")


def _autoload_laplace(scale: float, size=None):
    global sample_laplace, sample_gaussian, sample_uniform
    _try_native_install()
    if sample_laplace is _autoload_laplace:  # native unavailable
        _bind_fallbacks()
    return sample_laplace(scale, size)


def _autoload_gaussian(stddev: float, size=None):
    global sample_gaussian
    _try_native_install()
    if sample_gaussian is _autoload_gaussian:
        _bind_fallbacks()
    return sample_gaussian(stddev, size)


def _autoload_uniform(size=None):
    global sample_uniform
    _try_native_install()
    if sample_uniform is _autoload_uniform:
        _bind_fallbacks()
    return sample_uniform(size)


def _bind_fallbacks() -> None:
    global sample_laplace, sample_gaussian, sample_uniform
    sample_laplace = _fallback_laplace
    sample_gaussian = _fallback_gaussian
    sample_uniform = _fallback_uniform


# Hook points: rebound to the native C++ samplers on first draw (or to the
# numpy fallback when no native build is possible / after seed_fallback_rng).
sample_laplace = _autoload_laplace
sample_gaussian = _autoload_gaussian
sample_uniform = _autoload_uniform


def using_native_sampling() -> bool:
    return sample_laplace not in (_fallback_laplace, _autoload_laplace)


def add_laplace_noise(value: float, scale: float) -> float:
    """value snapped to granularity + secure Laplace noise."""
    g = laplace_granularity(scale)
    return float(round_to_granularity(value, g) + sample_laplace(scale))


def add_gaussian_noise(value: float, stddev: float) -> float:
    g = gaussian_granularity(stddev)
    return float(round_to_granularity(value, g) + sample_gaussian(stddev))


def add_laplace_noise_array(values: np.ndarray, scale: float) -> np.ndarray:
    """Vectorized float64 host noise (the secure finalization path for the
    columnar engine: O(num_partitions), off the TPU hot path)."""
    g = laplace_granularity(scale)
    values = np.asarray(values, dtype=np.float64)
    return round_to_granularity(values, g) + sample_laplace(scale,
                                                            values.shape)


def add_gaussian_noise_array(values: np.ndarray, stddev: float) -> np.ndarray:
    g = gaussian_granularity(stddev)
    values = np.asarray(values, dtype=np.float64)
    return round_to_granularity(values, g) + sample_gaussian(stddev,
                                                             values.shape)


def add_noise_array(values: np.ndarray, is_gaussian: bool,
                    scale_or_std: float) -> np.ndarray:
    if is_gaussian:
        return add_gaussian_noise_array(values, scale_or_std)
    return add_laplace_noise_array(values, scale_or_std)
