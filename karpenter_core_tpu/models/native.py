"""ctypes bridge to the native runtime kernels (native/kc_runtime.cc).

Builds the shared library on first use (g++ via the checked-in Makefile) and
caches it; falls back to numpy when no toolchain is available.  Used by the
columnar ingestion path (models.columnar) for pod-class grouping at 50k-pod
scale.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libkc_runtime.so")
_lock = threading.Lock()
_lib: "Optional[ctypes.CDLL]" = None
_build_failed = False
# set while one thread runs the (up to 120 s) g++ build outside the lock;
# latecomers wait on it instead of serializing behind a held mutex
# (kcanalyze lock-order: blocking-under-lock)
_in_flight: "Optional[threading.Event]" = None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed, _in_flight
    while True:
        with _lock:
            if _lib is not None or _build_failed:
                return _lib
            building = _in_flight
            if building is None:
                building = _in_flight = threading.Event()
                break  # this thread builds
        building.wait(timeout=180.0)
    lib = None
    try:
        lib = _build_and_load()
    finally:
        with _lock:
            if lib is None:
                _build_failed = True
            else:
                _lib = lib
            _in_flight = None
        building.set()
    return lib


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Build (if needed) and dlopen the library.  Runs with NO lock held —
    the subprocess can take up to 120 s and must not stall other threads;
    the caller holds the in-flight slot, so the build is still run once.
    ``make`` decides whether the binary is current (a timestamp check when
    it is): an existing .so is never trusted on its own, since the
    git-ignored file can outlive the source it was built from."""
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except Exception as e:  # noqa: BLE001 - fall back to numpy
        log.warning("native runtime build failed, using numpy fallback: %s", e)
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        log.warning("native runtime load failed, using numpy fallback: %s", e)
        return None
    lib.kc_group_rows.restype = ctypes.c_int64
    lib.kc_group_rows.argtypes = [
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.kc_class_totals.restype = ctypes.c_int64
    lib.kc_class_totals.argtypes = [
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
    ]
    return lib


def available() -> bool:
    return _load() is not None


def group_rows(matrix: np.ndarray) -> Tuple[np.ndarray, int]:
    """(class_ids i64[n], n_classes): group identical rows of a u64 matrix,
    classes numbered in first-seen order."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint64)
    n, w = matrix.shape
    lib = _load()
    if lib is not None:
        out = np.empty(n, dtype=np.int64)
        n_classes = lib.kc_group_rows(
            matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            n,
            w,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        if n_classes >= 0:
            return out, int(n_classes)
        log.warning("kc_group_rows returned %d, using numpy fallback", n_classes)
    # numpy fallback: unique rows, remapped to first-seen order
    _, first_idx, inverse = np.unique(
        matrix, axis=0, return_index=True, return_inverse=True
    )
    order = np.argsort(np.argsort(first_idx))
    return order[inverse].astype(np.int64), len(first_idx)


def class_totals(
    matrix: np.ndarray, class_ids: np.ndarray, n_classes: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(totals f32[n_classes, w], counts i64[n_classes]): per-class row sums."""
    matrix = np.ascontiguousarray(matrix, dtype=np.float32)
    class_ids = np.ascontiguousarray(class_ids, dtype=np.int64)
    n, w = matrix.shape
    lib = _load()
    if lib is not None:
        out = np.zeros((n_classes, w), dtype=np.float32)
        counts = np.zeros(n_classes, dtype=np.int64)
        rc = lib.kc_class_totals(
            matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            class_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            n,
            w,
            n_classes,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        if rc == 0:
            return out, counts
        log.warning("kc_class_totals returned %d, using numpy fallback", rc)
    out = np.zeros((n_classes, w), dtype=np.float32)
    np.add.at(out, class_ids, matrix)
    counts = np.bincount(class_ids, minlength=n_classes).astype(np.int64)
    return out, counts
