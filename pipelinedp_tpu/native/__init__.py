"""ctypes loader for the native DP primitives library.

Builds `_dp_primitives.so` from dp_primitives.cc on first use (g++, no
external deps) — and again whenever the source is newer than the binary,
so a stale build can never shadow the committed source — and exposes
typed wrappers. `status()` says which of the two (native / fallback) a
process is running on and whether the build failed. Everything here has a pure
Python/numpy fallback elsewhere in the package — `available()` gates use —
but when present the native library provides:

  * secure snapped discrete-Laplace / discrete-Gaussian noise (CKS20
    integer-only samplers; the counterpart of the reference's PyDP secure
    noise, SURVEY.md §2.4 row 1),
  * analytic Gaussian (eps, delta) -> sigma calibration (Balle-Wang),
  * vectorized partition-selection keep probabilities + sampled decisions.
"""

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_LIB_NAME = "_dp_primitives.so"
_SRC_NAME = "dp_primitives.cc"
_dir = os.path.dirname(os.path.abspath(__file__))

_lock = threading.Lock()
_lib = None
_load_failed = False

_f64p = np.ctypeslib.ndpointer(dtype=np.float64, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(dtype=np.uint8, flags="C_CONTIGUOUS")


def _needs_build() -> bool:
    """The binary is missing, or older than the source it was built
    from (git ignores the binary, so the source can move under it)."""
    src = os.path.join(_dir, _SRC_NAME)
    out = os.path.join(_dir, _LIB_NAME)
    return (not os.path.exists(out) or
            os.path.getmtime(src) > os.path.getmtime(out))


def _try_build() -> bool:
    src = os.path.join(_dir, _SRC_NAME)
    out = os.path.join(_dir, _LIB_NAME)
    # Build beside the target and rename: concurrent processes (test
    # workers) never load a half-written library.
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-o", tmp, src],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logging.warning("native DP primitives build failed: %s", e)
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(lib) -> None:
    lib.dpn_seed_test_rng.argtypes = [ctypes.c_uint64]
    lib.dpn_use_secure_rng.argtypes = []
    lib.dpn_secure_laplace_add.argtypes = [
        _f64p, _f64p, ctypes.c_int64, ctypes.c_double]
    lib.dpn_secure_gaussian_add.argtypes = [
        _f64p, _f64p, ctypes.c_int64, ctypes.c_double]
    lib.dpn_discrete_laplace.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, _i64p, ctypes.c_int64]
    lib.dpn_discrete_gaussian.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, _i64p, ctypes.c_int64]
    lib.dpn_gaussian_delta.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_double]
    lib.dpn_gaussian_delta.restype = ctypes.c_double
    lib.dpn_gaussian_sigma.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_double]
    lib.dpn_gaussian_sigma.restype = ctypes.c_double
    lib.dpn_truncated_geometric_prob_keep.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_int64, ctypes.c_int64,
        _i64p, _f64p, ctypes.c_int64]
    lib.dpn_laplace_threshold.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_int64]
    lib.dpn_laplace_threshold.restype = ctypes.c_double
    lib.dpn_laplace_prob_keep.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_int64, ctypes.c_int64,
        _i64p, _f64p, ctypes.c_int64]
    lib.dpn_gaussian_thresholding_params.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double)]
    lib.dpn_gaussian_prob_keep.argtypes = [
        ctypes.c_double, ctypes.c_double, ctypes.c_int64, ctypes.c_int64,
        _i64p, _f64p, ctypes.c_int64]
    lib.dpn_sample_keep.argtypes = [_f64p, _u8p, ctypes.c_int64]
    lib.dpn_vocab_encode.argtypes = [
        _u8p, ctypes.c_int64, ctypes.c_int64, _i32p, _i64p]
    lib.dpn_vocab_encode.restype = ctypes.c_int64


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        path = os.path.join(_dir, _LIB_NAME)
        # staticcheck: disable=lock-order — intentional build serialization: exactly one thread compiles the library while every other caller waits for it; the double-checked fast path above never takes the lock, so steady state is lock-free
        if _needs_build() and not _try_build():
            _load_failed = True  # staticcheck: disable=thread-escape — double-checked lazy init: this write-once publish happens under _lock; the unlocked fast-path read either sees the final value or falls through to the locked re-check
            return None
        try:
            lib = ctypes.CDLL(path)
            _bind(lib)
            _lib = lib  # staticcheck: disable=thread-escape — double-checked lazy init: write-once publish under _lock; the unlocked fast-path read sees None (and takes the locked slow path, which re-checks) or the final library, never a torn value
        except OSError as e:
            logging.warning("native DP primitives load failed: %s", e)
            _load_failed = True
    return _lib


def available() -> bool:
    """True if the native library could be built/loaded."""
    return _load() is not None


def status() -> dict:
    """Which implementation this process runs on: ``in_use`` (the
    library loaded; False = the numpy/Python fallbacks) and
    ``build_failed`` (no usable library AND the binary is still
    missing or stale — _load() builds whenever that is so, hence the
    build was attempted and failed: no g++, a compile error). Loads
    the library if nothing has yet."""
    in_use = available()
    return {"in_use": in_use,
            "build_failed": not in_use and _needs_build()}


def seed_test_rng(seed: int) -> None:
    """Switches the native RNG to a deterministic test generator.

    TESTS ONLY — the deterministic generator voids the secure-noise
    guarantee. Call use_secure_rng() to switch back."""
    _load().dpn_seed_test_rng(ctypes.c_uint64(seed))


def use_secure_rng() -> None:
    _load().dpn_use_secure_rng()


def secure_laplace_add(values: np.ndarray, scale: float) -> np.ndarray:
    """values + snapped discrete-Laplace(scale) noise, integer-only sampling
    on a power-of-two grid (granularity ~ scale * 2^-40)."""
    values = np.ascontiguousarray(values, dtype=np.float64)
    out = np.empty_like(values)
    _load().dpn_secure_laplace_add(values, out, values.size, float(scale))
    return out


def secure_gaussian_add(values: np.ndarray, sigma: float) -> np.ndarray:
    """values + snapped discrete-Gaussian(sigma) noise (granularity ~
    sigma * 2^-20)."""
    values = np.ascontiguousarray(values, dtype=np.float64)
    out = np.empty_like(values)
    _load().dpn_secure_gaussian_add(values, out, values.size, float(sigma))
    return out


def discrete_laplace(t: int, s: int, n: int) -> np.ndarray:
    """n samples of the integer discrete Laplace, P(z) ∝ exp(-|z| s/t)."""
    out = np.empty(n, dtype=np.int64)
    _load().dpn_discrete_laplace(t, s, out, n)
    return out


def discrete_gaussian(sigma2_num: int, sigma2_den: int, n: int) -> np.ndarray:
    """n samples of the integer discrete Gaussian, variance num/den."""
    out = np.empty(n, dtype=np.int64)
    _load().dpn_discrete_gaussian(sigma2_num, sigma2_den, out, n)
    return out


def gaussian_delta(sigma: float, eps: float, l2_sensitivity: float) -> float:
    return _load().dpn_gaussian_delta(sigma, eps, l2_sensitivity)


def gaussian_sigma(eps: float, delta: float, l2_sensitivity: float) -> float:
    return _load().dpn_gaussian_sigma(eps, delta, l2_sensitivity)


def _prob_keep(fn_name, eps, delta, l0, pre_threshold, counts):
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.empty(counts.size, dtype=np.float64)
    getattr(_load(), fn_name)(
        eps, delta, l0, -1 if pre_threshold is None else int(pre_threshold),
        counts, out, counts.size)
    return out


def truncated_geometric_prob_keep(eps, delta, l0, pre_threshold, counts):
    return _prob_keep("dpn_truncated_geometric_prob_keep", eps, delta, l0,
                      pre_threshold, counts)


def laplace_prob_keep(eps, delta, l0, pre_threshold, counts):
    return _prob_keep("dpn_laplace_prob_keep", eps, delta, l0, pre_threshold,
                      counts)


def gaussian_prob_keep(eps, delta, l0, pre_threshold, counts):
    return _prob_keep("dpn_gaussian_prob_keep", eps, delta, l0, pre_threshold,
                      counts)


def laplace_threshold(eps: float, delta: float, l0: int) -> float:
    return _load().dpn_laplace_threshold(eps, delta, l0)


def gaussian_thresholding_params(eps: float, delta: float, l0: int):
    sigma = ctypes.c_double()
    threshold = ctypes.c_double()
    _load().dpn_gaussian_thresholding_params(eps, delta, l0,
                                             ctypes.byref(sigma),
                                             ctypes.byref(threshold))
    return sigma.value, threshold.value


def sample_keep(probs: np.ndarray) -> np.ndarray:
    """Bernoulli keep decisions from probabilities (native RNG)."""
    probs = np.ascontiguousarray(probs, dtype=np.float64)
    out = np.empty(probs.size, dtype=np.uint8)
    _load().dpn_sample_keep(probs, out, probs.size)
    return out.astype(bool)


def vocab_encode(raw: np.ndarray):
    """First-occurrence-order integer encoding of fixed-width keys.

    One native hash-map pass over the array's raw bytes — the ingest-path
    counterpart of pandas.factorize, several times faster on string
    columns. Returns (codes int32[n], first_occurrence_rows int64[u]), or
    None when the native library is unavailable or the dtype is not a
    fixed-width byte layout (object arrays fall back to pandas).
    """
    lib = _load()
    if lib is None:
        return None
    if raw.ndim != 1 or raw.dtype.hasobject or raw.dtype.itemsize == 0:
        return None
    n = len(raw)
    if n >= 2**31:
        # The C encoder's codes are int32; let callers fall back rather
        # than overflow the vocabulary counter.
        return None
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int64)
    if raw.dtype.kind in "fc":
        # Bitwise equality splits 0.0 / -0.0 (and distinct NaN payloads)
        # that value-based factorization unifies; normalize zeros and
        # reject NaN-bearing float keys to keep parity with pandas.
        if np.isnan(raw).any():
            return None
        raw = raw + 0.0
    data = np.ascontiguousarray(raw).view(np.uint8)
    codes = np.empty(n, dtype=np.int32)
    first_rows = np.empty(n, dtype=np.int64)
    n_unique = lib.dpn_vocab_encode(data, raw.dtype.itemsize, n, codes,
                                    first_rows)
    return codes, first_rows[:n_unique]
