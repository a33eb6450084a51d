"""Bounded accelerator probe (utils/deviceprobe): a hung backend init
must never block callers past their timeout, a slow-but-alive backend
flips later calls to success, and an init error is cached as failure.

The module holds process-global state; tests operate on reloaded
copies so the real probe (used by default_provider) is untouched."""

import importlib
import threading
import time


def _fresh():
    from fabric_tpu.utils import deviceprobe

    mod = importlib.reload(deviceprobe)
    return mod


def test_hung_probe_returns_none_within_timeout(monkeypatch):
    mod = _fresh()
    release = threading.Event()
    monkeypatch.setattr(mod, "_worker", lambda: release.wait(30))
    t0 = time.monotonic()
    assert mod.probe_devices(0.2) is None
    assert time.monotonic() - t0 < 2.0  # bounded, not hung
    assert "timed out" in (mod.probe_error() or "")
    release.set()


def test_slow_probe_flips_to_success(monkeypatch):
    mod = _fresh()
    release = threading.Event()
    fake_devices = ["fake-tpu"]

    def worker():
        release.wait(10)
        with mod._lock:
            mod._state["status"] = "ok"
            mod._state["devices"] = fake_devices

    monkeypatch.setattr(mod, "_worker", worker)
    assert mod.probe_devices(0.1) is None  # first call times out
    release.set()
    deadline = time.time() + 5
    while time.time() < deadline:
        if mod.probe_devices(0.2) == fake_devices:
            break
    assert mod.probe_devices(0.1) == fake_devices  # cached success
    assert mod.probe_error() is None


def test_init_error_cached_as_failure(monkeypatch):
    mod = _fresh()

    def worker():
        with mod._lock:
            mod._state["status"] = "error"
            mod._state["error"] = "UNAVAILABLE: backend init failed"

    monkeypatch.setattr(mod, "_worker", worker)
    assert mod.probe_devices(2.0) is None
    assert "UNAVAILABLE" in mod.probe_error()
    assert not mod.accelerator_present(0.1)


def test_accelerator_present_filters_cpu(monkeypatch):
    mod = _fresh()

    class Dev:
        platform = "cpu"

    def worker():
        with mod._lock:
            mod._state["status"] = "ok"
            mod._state["devices"] = [Dev()]

    monkeypatch.setattr(mod, "_worker", worker)
    assert mod.probe_devices(2.0) is not None
    assert not mod.accelerator_present(0.1)  # cpu-only != accelerator
