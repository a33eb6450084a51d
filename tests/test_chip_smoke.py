"""chip_smoke.py's seam checks fire — so a later PR cannot make the
smoke pass by weakening it.  No kernel runs here (tier-1 cannot afford
the XLA:CPU compile): each check gets a stub that gave way."""

import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


def _provider(degraded=False, bytes_broken=False, backend="tpu"):
    cls = type(
        "TPUProvider", (),
        {
            "degraded": degraded,
            "_bytes_path_broken": bytes_broken,
            "describe_backend": lambda self: backend,
        },
    )
    return cls()


def _snapshot(device_lanes):
    return {
        "fabric_verify_lanes_total": {
            "kind": "counter",
            "series": {"rung=device": float(device_lanes), "rung=fastec": 9.0},
        }
    }


def test_a_healthy_run_passes_every_check():
    chip_smoke.check_platform(
        [SimpleNamespace(platform="tpu")] * 4, chips=4
    )
    chip_smoke.check_no_serve_env({})
    chip_smoke.check_default_provider(_provider())
    chip_smoke.check_provider_seams(_provider())
    chip_smoke.check_sidecar_client(SimpleNamespace(degraded=False))
    chip_smoke.check_pipeline(SimpleNamespace(last_error=None, dead=False))
    chip_smoke.check_same_bytes("mask", b"\x00\x0a\x00", b"\x00\x0a\x00")
    chip_smoke.check_device_lanes(_snapshot(11968), 11968)
    chip_smoke.check_bucket(2990, 4096, 4096)


@pytest.mark.parametrize(
    "name, call, says",
    [
        (
            "cpu platform",
            lambda: chip_smoke.check_platform(
                [SimpleNamespace(platform="cpu")], chips=1
            ),
            "'cpu'",
        ),
        (
            "one device under --chips 4",
            lambda: chip_smoke.check_platform(
                [SimpleNamespace(platform="tpu")], chips=4
            ),
            "needs 4 devices",
        ),
        (
            "serve env set",
            lambda: chip_smoke.check_no_serve_env(
                {"FABRIC_TPU_SERVE_ADDR": "/tmp/s.sock"}
            ),
            "FABRIC_TPU_SERVE_ADDR",
        ),
        (
            "software default provider",
            lambda: chip_smoke.check_default_provider(
                type("SoftwareProvider", (), {})()
            ),
            "SoftwareProvider",
        ),
        (
            "degraded provider",
            lambda: chip_smoke.check_provider_seams(_provider(degraded=True)),
            "degraded",
        ),
        (
            "bytes path broken",
            lambda: chip_smoke.check_provider_seams(
                _provider(bytes_broken=True)
            ),
            "_bytes_path_broken",
        ),
        (
            "backend label not tpu",
            lambda: chip_smoke.check_provider_seams(
                _provider(backend="tpu-degraded(sw:fastec)")
            ),
            "tpu-degraded",
        ),
        (
            "degraded sidecar client",
            lambda: chip_smoke.check_sidecar_client(
                SimpleNamespace(degraded=True)
            ),
            "SidecarProvider.degraded",
        ),
        (
            "pipeline error",
            lambda: chip_smoke.check_pipeline(
                SimpleNamespace(last_error=RuntimeError("boom"), dead=False)
            ),
            "boom",
        ),
        (
            "dead committer",
            lambda: chip_smoke.check_pipeline(
                SimpleNamespace(last_error=None, dead=True)
            ),
            "dead",
        ),
        (
            "mask differs in one byte",
            lambda: chip_smoke.check_same_bytes(
                "mask", b"\x00\x0a\x00\x00", b"\x00\x0a\x0b\x00"
            ),
            "first differing index 2",
        ),
        (
            "mask shorter than the oracle's",
            lambda: chip_smoke.check_same_bytes("mask", b"\x00", b"\x00\x00"),
            "lengths 1/2",
        ),
        (
            "device lane count short",
            lambda: chip_smoke.check_device_lanes(_snapshot(8976), 11968),
            "8976",
        ),
        (
            "no device lanes at all",
            lambda: chip_smoke.check_device_lanes({}, 11968),
            "is 0",
        ),
        (
            "second bucket",
            lambda: chip_smoke.check_bucket(5000, 8192, 4096),
            "second program",
        ),
    ],
)
def test_each_seam_check_fires(name, call, says):
    with pytest.raises(chip_smoke.SeamGaveWay, match=says):
        call()


def test_poison_plan_is_seeded_and_non_empty():
    import random

    a = chip_smoke.pick_poisons(random.Random("0:c"), 1000)
    b = chip_smoke.pick_poisons(random.Random("0:c"), 1000)
    c = chip_smoke.pick_poisons(random.Random("1:c"), 1000)
    assert a == b and a != c
    assert sorted(a.values()) == sorted(
        chip_smoke.POISONS_PER_KIND
        * ["bad_creator", "high_s", "mvcc", "short_endorsement"]
    )
    for i, kind in a.items():
        if kind == "mvcc":  # its neighbours stay clean
            assert i - 1 not in a and i + 1 not in a


def test_no_chip_exits_nonzero_naming_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok": true' not in proc.stdout
    assert '"ok"' not in proc.stdout  # no result line at all
    assert time.monotonic() - t0 < 60
