"""ctypes bindings for the C++ native runtime (native/*.cpp).

Loads `native/libdl4jtpu_native.so`, building it from `native/*.cpp` with
`make` on first use (the binary is never committed); every entry point has
a numpy fallback so the framework works without the native library (the
reference's nd4j-native fallback discipline, minus the hard JNI
dependency).  A failed build or load is reported once, as a warning.

Public surface:
- ThresholdCodec: compressed-gradient encode/decode with residual carry
  (reference `encode_threshold`/`EncodedGradientsAccumulator`).
- staging_gather_indexed / u8_to_f32: parallel minibatch assembly
  (reference AsyncDataSetIterator + pinned staging role).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import warnings
from typing import Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdl4jtpu_native.so")

_lib = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(_LIB_PATH) and os.path.exists(
            os.path.join(_NATIVE_DIR, "Makefile")):
        try:
            subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                           capture_output=True, text=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as e:
            warnings.warn(
                f"native runtime not built (`make -C {_NATIVE_DIR}`: {e}"
                f"{(getattr(e, 'stderr', None) or '')[-500:]}); using the "
                "numpy fallbacks", RuntimeWarning)
            return None
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        warnings.warn(f"native runtime not loadable ({e}); using the numpy "
                      "fallbacks", RuntimeWarning)
        return None
    lib.threshold_encode.restype = ctypes.c_int64
    lib.threshold_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_float,
        ctypes.c_void_p, ctypes.c_int64]
    lib.threshold_decode.restype = None
    lib.threshold_decode.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_float, ctypes.c_void_p,
        ctypes.c_int64]
    lib.threshold_density.restype = ctypes.c_double
    lib.threshold_density.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_float]
    lib.staging_gather_indexed.restype = None
    lib.staging_gather_indexed.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p]
    lib.staging_u8_to_f32.restype = None
    lib.staging_u8_to_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_float]
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


class ThresholdCodec:
    """Sparse threshold gradient compression with residual carry-over.

    encode(grad) -> int32 sparse array (sign-in-index format); the residual
    accumulates the un-sent remainder so repeated encode() converges (the
    reference's delta semantics).  decode() scatters back to dense.
    """

    def __init__(self, size: int, threshold: float = 1e-3,
                 max_fraction: float = 1.0):
        self.size = int(size)
        self.threshold = float(threshold)
        self.residual = np.zeros(self.size, np.float32)
        self.max_elements = max(1, int(self.size * max_fraction))

    def encode(self, grad: np.ndarray) -> np.ndarray:
        grad = np.ascontiguousarray(np.asarray(grad, np.float32).ravel())
        if grad.size != self.size:
            raise ValueError(f"size {grad.size} != {self.size}")
        lib = _load()
        out = np.empty(self.max_elements, np.int32)
        if lib is not None:
            n = lib.threshold_encode(_ptr(grad), _ptr(self.residual),
                                     self.size, self.threshold, _ptr(out),
                                     self.max_elements)
            return out[:n].copy()
        # numpy fallback (sequential-overflow semantics approximated:
        # truncate past max_elements, carrying their full value)
        v = grad + self.residual
        pos = v >= self.threshold
        neg = v <= -self.threshold
        idx = np.nonzero(pos | neg)[0]
        kept = idx[: self.max_elements]
        dropped = idx[self.max_elements:]
        enc = np.where(pos[kept], kept + 1, -(kept + 1)).astype(np.int32)
        new_res = v.copy()
        new_res[kept] -= np.where(pos[kept], self.threshold,
                                  -self.threshold)
        # dropped keep full value (same as C path)
        _ = dropped
        self.residual = new_res.astype(np.float32)
        return enc

    def decode(self, encoded: np.ndarray,
               out: Optional[np.ndarray] = None,
               threshold: Optional[float] = None) -> np.ndarray:
        """Scatter a sparse stream back to dense.  `threshold` overrides the
        codec's own (a peer's stream decodes at the peer's threshold) WITHOUT
        mutating `self.threshold`, so decode of peer streams can overlap an
        encode on another thread."""
        thr = self.threshold if threshold is None else float(threshold)
        if out is None:
            out = np.zeros(self.size, np.float32)
        encoded = np.ascontiguousarray(np.asarray(encoded, np.int32))
        lib = _load()
        if lib is not None:
            lib.threshold_decode(_ptr(encoded), encoded.size,
                                 thr, _ptr(out), self.size)
            return out
        pos = encoded[encoded > 0] - 1
        neg = -encoded[encoded < 0] - 1
        np.add.at(out, pos, thr)
        np.add.at(out, neg, -thr)
        return out

    def density(self, grad: np.ndarray) -> float:
        """Fraction over threshold (adaptive-threshold hook)."""
        grad = np.ascontiguousarray(np.asarray(grad, np.float32).ravel())
        lib = _load()
        if lib is not None:
            return float(lib.threshold_density(_ptr(grad),
                                               _ptr(self.residual),
                                               self.size, self.threshold))
        v = grad + self.residual
        return float(np.mean(np.abs(v) >= self.threshold))


def gather_indexed(base: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Parallel minibatch assembly: out[i] = base[indices[i]] (C++ OpenMP
    when available — the staging-buffer role)."""
    base = np.ascontiguousarray(base)
    indices = np.ascontiguousarray(np.asarray(indices, np.int64))
    # validate before touching the native path: the C kernel memcpys blindly,
    # so an out-of-range index would be UB there (the numpy fallback raises)
    if indices.size and (indices.min() < 0 or indices.max() >= base.shape[0]):
        raise IndexError(
            f"gather_indexed: indices out of range [0, {base.shape[0]})")
    out = np.empty((indices.size,) + base.shape[1:], base.dtype)
    lib = _load()
    if lib is not None and base.ndim >= 1:
        row_bytes = base.dtype.itemsize * int(np.prod(base.shape[1:],
                                                      dtype=np.int64))
        lib.staging_gather_indexed(_ptr(base), _ptr(indices), indices.size,
                                   row_bytes, _ptr(out))
        return out
    return base[indices]


def u8_to_f32(src: np.ndarray, scale: float = 1.0 / 255.0) -> np.ndarray:
    """Fused uint8 -> float32 decode+normalize (image pipeline)."""
    src = np.ascontiguousarray(np.asarray(src, np.uint8))
    out = np.empty(src.shape, np.float32)
    lib = _load()
    if lib is not None:
        lib.staging_u8_to_f32(_ptr(src), _ptr(out), src.size,
                              ctypes.c_float(scale))
        return out
    return src.astype(np.float32) * scale
