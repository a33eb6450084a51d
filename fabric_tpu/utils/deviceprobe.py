"""Bounded accelerator probe.

`jax.devices()` initializes the backend on first call, and a backend
init can fail (UNAVAILABLE) or hang. Everything that *optionally* uses
the device (bccsp.default_provider, CLI probes) goes through
this module instead of calling jax.devices() inline. A program that
REQUIRES the device (chip_smoke.py) calls jax.devices() itself and
fails on what it finds.

The probe runs in a daemon thread and is cached for the process:
- first call starts the thread and waits up to `timeout_s`;
- a timeout returns None but leaves the thread probing, so a *slow*
  (rather than dead) backend flips later calls to success;
- a raise inside the probe (UNAVAILABLE at init) is cached as failure.

Reference contrast: the reference's bccsp factory (bccsp/factory,
SURVEY §2.1) probes PKCS#11 libraries synchronously because a local
.so either loads or errors instantly; an accelerator backend has the
third state — hung — which is the one that needs the thread.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional

_lock = threading.Lock()
_thread: Optional[threading.Thread] = None
_state = {"status": "unknown", "devices": None, "error": None}


def _worker() -> None:
    try:
        import jax

        devs = jax.devices()
        with _lock:
            _state["status"] = "ok"
            _state["devices"] = devs
    except Exception as exc:  # noqa: BLE001 - cache any init failure
        with _lock:
            _state["status"] = "error"
            _state["error"] = str(exc)


def default_timeout() -> float:
    return float(os.environ.get("FABRIC_TPU_PROBE_TIMEOUT_S", "60"))


def probe_devices(timeout_s: Optional[float] = None) -> Optional[List]:
    """jax.devices() bounded by `timeout_s` (default
    FABRIC_TPU_PROBE_TIMEOUT_S or 60s). None = not available (yet)."""
    global _thread
    if timeout_s is None:
        timeout_s = default_timeout()
    with _lock:
        if _state["status"] == "ok":
            return _state["devices"]
        if _state["status"] == "error":
            return None
        if _thread is None:
            _thread = threading.Thread(
                target=_worker, name="device-probe", daemon=True
            )
            _thread.start()
        t = _thread
    t.join(timeout_s)
    with _lock:
        return _state["devices"] if _state["status"] == "ok" else None


def probe_error() -> Optional[str]:
    """The cached init error, or a timeout pseudo-error, or None if the
    probe succeeded / hasn't concluded."""
    with _lock:
        if _state["status"] == "error":
            return _state["error"]
        if _state["status"] == "unknown" and _thread is not None:
            return "device probe timed out (backend init hung)"
        return None


def accelerator_present(timeout_s: Optional[float] = None) -> bool:
    devs = probe_devices(timeout_s)
    return bool(devs) and any(d.platform != "cpu" for d in devs)
