"""Loader for the native secure-noise library.

Builds `secure_noise.cc` into a shared object on first use (plain
`g++ -O2 -shared`; no external build deps), loads it with ctypes, and
installs the discrete samplers as noise_core's `sample_laplace` /
`sample_gaussian` implementations. When no compiler or writable cache is
available, the numpy granularity-snapping fallback in noise_core stays in
place (distributionally equivalent; without the bit-exact discrete
construction).

Python <-> C++ boundary: ctypes over a 3-function C ABI (the environment
has no pybind11 — see the repo build notes). The samplers return *integer*
noise in granularity units; scaling by the power-of-two granularity happens
here, which is exact in float64.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sysconfig
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_EXT = sysconfig.get_config_var("EXT_SUFFIX") or ".so"

_lock = threading.Lock()
_libs: dict = {}  # stem -> CDLL | None (None = load failed)

# The error types a native build/load can surface; callers that treat the
# native path as an optimization catch exactly these (ops/streaming.py) —
# never a bare Exception, which would also swallow NativeRequiredError.
LOADER_ERRORS = (OSError, subprocess.SubprocessError, AttributeError)

# When set to a truthy value ("1"/"true"/"yes"), a failed native
# build/load raises NativeRequiredError instead of silently installing
# the numpy fallback — CI and prod set it so a toolchain regression is a
# hard error, not a quiet 10x slowdown.
REQUIRE_NATIVE_ENV = "PIPELINEDP_TPU_REQUIRE_NATIVE"


class NativeRequiredError(RuntimeError):
    """Native library unavailable while REQUIRE_NATIVE_ENV demands it."""


# Worker-pool width for the native encode (pdp_pack_buckets,
# pdp_rle_prep, pdp_rle_sort_range, pdp_rle_emit_range): 0 = auto
# (hardware concurrency, capped at 16 in the C++), 1..64 forces the
# width. Output is bit-identical at every width (disjoint buckets or
# disjoint write slots per worker); the knob only trades host wall time —
# see README "Tuning knobs".
ENCODE_THREADS_ENV = "PIPELINEDP_TPU_ENCODE_THREADS"


def env_int(name: str, default: int, lo: int, hi: int) -> int:
    """Validated integer env knob: unset/empty -> default; junk or
    out-of-range values raise instead of silently running misconfigured."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        value = int(raw.strip())
    except ValueError:
        raise ValueError(
            f"{name} must be an integer, got {raw!r}") from None
    if not lo <= value <= hi:
        raise ValueError(
            f"{name} must be in [{lo}, {hi}], got {value}")
    return value


def encode_threads() -> int:
    """The validated PIPELINEDP_TPU_ENCODE_THREADS value (0 = auto)."""
    return env_int(ENCODE_THREADS_ENV, 0, 0, 64)


def apply_encode_threads(lib) -> int:
    """Pushes the env-configured worker-pool width into the native
    library (re-read per call so tests can flip the env between
    encodes). Returns the applied value."""
    n = encode_threads()
    if lib is not None and hasattr(lib, "pdp_set_encode_threads"):
        lib.pdp_set_encode_threads(n)
    return n


def _native_required() -> bool:
    return os.environ.get(REQUIRE_NATIVE_ENV,
                          "").strip().lower() in ("1", "true", "yes")


def _build(stem: str) -> bool:
    src = os.path.join(_DIR, f"{stem}.cc")
    so = os.path.join(_DIR, f"_{stem}{_EXT}")
    cmd = [
        "g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread", src,
        "-o", so
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logging.info("pipelinedp_tpu.native: build of %s failed (%s); "
                     "using the numpy fallback", stem, e)
        return False


def _load_lib(stem: str, abi_symbol: str,
              abi_version: int = 1) -> Optional[ctypes.CDLL]:
    """Builds (if stale/missing) and loads native/<stem>.cc; caches.

    Under REQUIRE_NATIVE_ENV a failure raises NativeRequiredError instead
    of returning None (checked on cache hits too, so a permissive early
    call can't mask a later strict one).
    """
    with _lock:
        if stem in _libs:
            lib = _libs[stem]
            if lib is None and _native_required():
                raise NativeRequiredError(
                    f"native library '{stem}' failed to build/load and "
                    f"{REQUIRE_NATIVE_ENV} is set")
            return lib
        src = os.path.join(_DIR, f"{stem}.cc")
        so = os.path.join(_DIR, f"_{stem}{_EXT}")
        if not os.path.exists(so) or (os.path.exists(src) and
                                      os.path.getmtime(so) <
                                      os.path.getmtime(src)):
            if not _build(stem):
                _libs[stem] = None
                if _native_required():
                    raise NativeRequiredError(
                        f"native library '{stem}' failed to build and "
                        f"{REQUIRE_NATIVE_ENV} is set")
                return None
        lib = _try_load(so, abi_symbol, abi_version)
        if lib is None and os.path.exists(src):
            # A stale prebuilt .so can pass the mtime check (archive
            # extraction and docker COPY normalize mtimes) yet miss the
            # current ABI; rebuild from source once before giving up.
            logging.info(
                "pipelinedp_tpu.native: %s failed to load; rebuilding "
                "from source", stem)
            if _build(stem):
                lib = _try_load(so, abi_symbol, abi_version)
        _libs[stem] = lib
        if lib is None and _native_required():
            raise NativeRequiredError(
                f"native library '{stem}' failed to load and "
                f"{REQUIRE_NATIVE_ENV} is set")
        return lib


def _try_load(so: str, abi_symbol: str,
              abi_version: int) -> Optional[ctypes.CDLL]:
    try:
        lib = ctypes.CDLL(so)
        abi = getattr(lib, abi_symbol)
        abi.restype = ctypes.c_int
        if abi() != abi_version:
            raise OSError(f"ABI version mismatch (want {abi_version}, "
                          f"got {abi()})")
        return lib
    except OSError as e:
        logging.info("pipelinedp_tpu.native: load of %s failed (%s)", so, e)
        return None


def load() -> Optional[ctypes.CDLL]:
    """The secure-noise library, building it if needed; None on failure."""
    lib = _load_lib("secure_noise", "pdp_noise_abi_version", abi_version=2)
    if lib is not None and not getattr(lib, "_pdp_typed", False):
        for name in ("pdp_sample_discrete_laplace",
                     "pdp_sample_discrete_gaussian"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [
                ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                ctypes.c_double
            ]
        fn = lib.pdp_sample_uniform_double
        fn.restype = ctypes.c_int
        fn.argtypes = [ctypes.POINTER(ctypes.c_double), ctypes.c_int64]
        lib._pdp_typed = True
    return lib


def load_row_packer() -> Optional[ctypes.CDLL]:
    """The row bucketing/packing library; None on failure."""
    lib = _load_lib("row_packer", "pdp_row_packer_abi_version",
                    abi_version=8)
    if lib is not None and not getattr(lib, "_pdp_typed", False):
        fn = lib.pdp_set_encode_threads
        fn.restype = None
        fn.argtypes = [ctypes.c_int]
        fn = lib.pdp_get_encode_threads
        fn.restype = ctypes.c_int
        fn.argtypes = []
        fn = lib.pdp_rle_prep
        fn.restype = ctypes.c_void_p
        fn.argtypes = [
            ctypes.POINTER(ctypes.c_int32),  # pid
            ctypes.POINTER(ctypes.c_int32),  # pk
            ctypes.c_void_p,  # value (float* or NULL)
            ctypes.POINTER(ctypes.c_int32),  # vidx (or NULL => inline)
            ctypes.c_double,  # v_lo
            ctypes.c_double,  # v_scale
            ctypes.c_int64,  # n
            ctypes.c_int32,  # pid_lo
            ctypes.c_int64,  # k buckets
            ctypes.c_int,  # value_mode
            ctypes.c_int64,  # pid_span (for exact entry counting)
            ctypes.POINTER(ctypes.c_int64),  # n_entries out (or NULL)
            ctypes.POINTER(ctypes.c_int64),  # n_rows out
            # stats out [fail, max_idx, max_run, prep width]
            ctypes.POINTER(ctypes.c_int64),
        ]
        fn = lib.pdp_rle_sort_range
        fn.restype = ctypes.c_int
        fn.argtypes = [
            ctypes.c_void_p,  # handle
            ctypes.c_int64,  # b0
            ctypes.c_int64,  # b1
            ctypes.POINTER(ctypes.c_int64),  # n_uniq out
        ]
        fn = lib.pdp_rle_emit_range
        fn.restype = ctypes.c_int
        fn.argtypes = [
            ctypes.c_void_p,  # handle
            ctypes.c_int64,  # b0
            ctypes.c_int64,  # b1
            ctypes.c_int,  # pid_mode (0 RLE, 1 unsorted bit-planes)
            ctypes.c_int,  # bytes_pid
            ctypes.c_int,  # bits_pid (planes mode)
            ctypes.c_int,  # bits_pk
            ctypes.c_int,  # bits_val
            ctypes.c_int64,  # cap
            ctypes.c_int64,  # ucap
            ctypes.POINTER(ctypes.c_uint8),  # out slab rows
            ctypes.c_int64,  # width
        ]
        fn = lib.pdp_rle_free
        fn.restype = None
        fn.argtypes = [ctypes.c_void_p]
        fn = lib.pdp_pack_buckets
        fn.restype = ctypes.c_int
        fn.argtypes = [
            ctypes.POINTER(ctypes.c_int32),  # pid
            ctypes.POINTER(ctypes.c_int32),  # pk
            ctypes.c_void_p,  # value (float* or NULL)
            ctypes.c_int64,  # n
            ctypes.c_int32,  # pid_lo
            ctypes.c_int64,  # n_buckets
            ctypes.c_int,  # bytes_pid
            ctypes.c_int,  # bytes_pk
            ctypes.c_int,  # value_f16
            ctypes.POINTER(ctypes.c_uint8),  # out
            ctypes.c_int64,  # cap
            ctypes.POINTER(ctypes.c_int64),  # counts
        ]
        lib._pdp_typed = True
    if lib is not None:
        # Re-applied on every load call (the CDLL itself is cached) so an
        # env change between encodes takes effect immediately.
        apply_encode_threads(lib)
    return lib


def is_loaded() -> bool:
    return _libs.get("secure_noise") is not None


def _sample(fn, units: float, size) -> np.ndarray:
    n = 1 if size is None else int(np.prod(size))
    out = np.empty(max(n, 1), dtype=np.int64)
    rc = fn(out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
            float(units))
    if rc != 0:
        raise ValueError(f"native sampler rejected parameters (units="
                         f"{units})")
    return out[:n]


def install() -> bool:
    """Loads the library and installs the native samplers into noise_core.

    Returns True when the native path is active.
    """
    lib = load()
    if lib is None:
        return False
    from pipelinedp_tpu import noise_core

    def native_laplace(scale: float, size=None):
        g = noise_core.laplace_granularity(scale)
        ints = _sample(lib.pdp_sample_discrete_laplace, scale / g, size)
        noise = ints.astype(np.float64) * g
        return float(noise[0]) if size is None else noise.reshape(size)

    def native_gaussian(stddev: float, size=None):
        g = noise_core.gaussian_granularity(stddev)
        ints = _sample(lib.pdp_sample_discrete_gaussian, stddev / g, size)
        noise = ints.astype(np.float64) * g
        return float(noise[0]) if size is None else noise.reshape(size)

    def native_uniform(size=None):
        n = 1 if size is None else int(np.prod(size))
        out = np.empty(max(n, 1), dtype=np.float64)
        rc = lib.pdp_sample_uniform_double(
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n)
        if rc != 0:
            raise ValueError("native uniform sampler failed")
        return float(out[0]) if size is None else out[:n].reshape(size)

    noise_core.sample_laplace = native_laplace
    noise_core.sample_gaussian = native_gaussian
    noise_core.sample_uniform = native_uniform
    return True
