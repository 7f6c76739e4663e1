"""Loader for the ``kc_sig`` CPython extension (native/kc_sig.cc) — the C
twin of the ingest fast key (models/columnar._fast_sig_key_py).

Builds the extension on first use (g++ via the checked-in Makefile) and
imports it; falls back to the Python implementation when no toolchain or no
Python headers are available.  ``KC_NATIVE_SIG=0`` disables the extension
unconditionally (triage / parity bisection).  Same build discipline as
models.native: one thread builds outside the lock, latecomers wait on the
in-flight event (kcanalyze lock-order: no blocking under a held mutex).
"""

from __future__ import annotations

import importlib.machinery
import importlib.util
import logging
import os
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(__file__))), "native"
)
_SO_PATH = os.path.join(_NATIVE_DIR, "kc_sig.so")
_lock = threading.Lock()
_mod = None
_load_failed = False
_in_flight: "Optional[threading.Event]" = None


def enabled() -> bool:
    return os.environ.get("KC_NATIVE_SIG", "1") != "0"


def load():
    """The ``kc_sig`` module, or None (build/import failed or disabled)."""
    global _mod, _load_failed, _in_flight
    if not enabled():
        return None
    while True:
        with _lock:
            if _mod is not None or _load_failed:
                return _mod
            building = _in_flight
            if building is None:
                building = _in_flight = threading.Event()
                break  # this thread builds
        building.wait(timeout=180.0)
    mod = None
    try:
        mod = _build_and_import()
    finally:
        with _lock:
            if mod is None:
                _load_failed = True
            else:
                _mod = mod
            _in_flight = None
        building.set()
    return mod


def _build_and_import():
    """Build (if needed) and import the extension.  Runs with NO lock held —
    the g++ subprocess must not stall other threads; the caller holds the
    in-flight slot, so the build still runs once.  ``make`` decides whether
    the binary is current (models/native.py has the reason)."""
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "kc_sig.so"],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except Exception as e:  # noqa: BLE001 - fall back to the Python twin
        log.warning("kc_sig build failed, using Python fast key: %s", e)
        return None
    if not os.path.exists(_SO_PATH):
        # headerless toolchain: the Makefile skipped the target gracefully
        log.info("kc_sig.so not built (no Python headers); Python fast key in use")
        return None
    try:
        loader = importlib.machinery.ExtensionFileLoader("kc_sig", _SO_PATH)
        spec = importlib.util.spec_from_loader("kc_sig", loader, origin=_SO_PATH)
        mod = importlib.util.module_from_spec(spec)
        loader.exec_module(mod)
    except Exception as e:  # noqa: BLE001 - fall back to the Python twin
        log.warning("kc_sig import failed, using Python fast key: %s", e)
        return None
    return mod
