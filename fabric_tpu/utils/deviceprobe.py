"""Bounded accelerator probe.

`jax.devices()` initializes the backend on first call, and a backend
init can fail (UNAVAILABLE) or hang. Everything that *optionally* uses
the device (bccsp.default_provider, bench.py, CLI probes) goes through
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


# -- out-of-process probe ---------------------------------------------------
#
# The daemon-thread probe above bounds the CALLER's wait but cannot kill
# a backend init that wedges (the thread then sits in it forever).  The
# subprocess probe gets a HARD bound — the kernel kills
# the child — at the cost of a fresh interpreter + jax import per cold
# probe (~10s on a healthy box), so it suits batch/CLI entrypoints
# (bench.py) rather than the library path: bccsp.default_provider keeps
# the cheap in-process probe, whose worst case is one wedged daemon
# thread in a process that has already degraded to the software
# provider.

_sub_state: dict = {}


def probe_subprocess(timeout_s: float):
    """(ok, error): ok iff a non-CPU accelerator answered from a freshly
    spawned python within timeout_s.  Cached for the process."""
    if "verdict" in _sub_state:
        return _sub_state["verdict"]
    import json
    import subprocess
    import sys

    code = (
        "import json, sys\n"
        "import jax\n"
        "print(json.dumps([d.platform for d in jax.devices()]))\n"
    )
    ok, error = False, None
    try:
        res = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
        if res.returncode == 0:
            try:
                platforms = json.loads(
                    res.stdout.strip().splitlines()[-1]
                )
                ok = any(p != "cpu" for p in platforms)
                if not ok:
                    error = (
                        f"no accelerator device (platforms={platforms})"
                    )
            except (ValueError, IndexError):
                error = f"probe emitted garbage: {res.stdout[:200]!r}"
        else:
            error = (res.stderr or res.stdout or "probe failed")[-300:]
    except subprocess.TimeoutExpired:
        error = (
            f"device probe subprocess exceeded {timeout_s:.0f}s "
            "(backend init hung) and was killed"
        )
    except Exception as exc:  # noqa: BLE001 - probing must never raise
        error = f"probe subprocess error: {exc}"[:300]
    _sub_state["verdict"] = (ok, error)
    return ok, error
