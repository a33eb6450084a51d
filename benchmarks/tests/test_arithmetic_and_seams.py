"""Rates, tails and the seam checks (as tests/test_chip_smoke.py shows for
the originals in chip_smoke.py)."""

from types import SimpleNamespace

import pytest

from benchmarks import harness as hs


def test_percentile_interpolates_over_all_values():
    values = list(range(1, 101))
    assert hs.percentile(values, 50) == pytest.approx(50.5)
    assert hs.percentile(values, 90) == pytest.approx(90.1)
    assert hs.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        hs.percentile([], 50)


def test_one_long_stall_moves_the_rate_and_the_tail():
    # 10 s window, a block of 500 tx every 0.1 s, and one 4 s stall
    steady = [0.1 * (i + 1) for i in range(120)]
    stalled = [t if t < 3.0 else t + 4.0 for t in steady]
    assert hs.rate_in_window(steady, [500] * 120, 0.0, 10.0) == pytest.approx(5000)
    # the window closes on the first commit at or after 10 s: 60 blocks in 10 s
    assert hs.rate_in_window(stalled, [500] * 120, 0.0, 10.0) == pytest.approx(
        500 * 60 / 10.0, rel=0.01
    )
    latencies = [100.0] * 89 + [4100.0] * 11
    assert hs.percentile(latencies, 90) > 4000  # p90 sees 11 slow of 100
    assert hs.percentile(latencies, 50) == 100.0  # a median would not


def test_a_stall_at_the_windows_end_counts_too():
    # work stops at 8 s and the block in flight lands at 14 s
    done = [0.1 * (i + 1) for i in range(80)] + [14.0]
    assert hs.rate_in_window(done, [500] * 81, 0.0, 10.0) == pytest.approx(
        500 * 81 / 14.0
    )


def test_whole_blocks_do_not_make_the_rate_step():
    # the same system, 0.1578 s a block, started at two phases
    for phase in (0.0, 0.07):
        done = [phase + 0.1578 * (i + 1) for i in range(300)]
        rate = hs.rate_in_window(done, [500] * 300, 0.0, 30.0)
        assert rate == pytest.approx(500 / 0.1578, rel=0.003)


def test_work_outside_the_window_does_not_count():
    # before t0: left out; after the closing completion: left out
    assert hs.rate_in_window([-1.0, 0.5, 10.5, 11.0], [1, 1, 1, 1], 0.0, 10.0) == (
        pytest.approx(2 / 10.5)
    )
    # nothing completes after the clock's end: the window closes on the clock
    assert hs.rate_in_window([0.5, 2.0], [1, 1], 0.0, 10.0) == pytest.approx(0.2)


def _provider(degraded=False, bytes_broken=False, backend="tpu"):
    cls = type("TPUProvider", (), {
        "degraded": degraded, "_bytes_path_broken": bytes_broken,
        "describe_backend": lambda self: backend,
    })
    return cls()


def _snapshot(lanes):
    return {"fabric_verify_lanes_total": {
        "kind": "counter", "series": {"rung=device": float(lanes), "rung=fastec": 9.0},
    }}


def _stats(**over):
    base = {"engine": "device", "batched_lanes": 3028, "launches": 2,
            "stats": {"lanes": 3028, "requests": 2, "errors": 0, "rejects": 0}}
    base["stats"].update(over.pop("stats", {}))
    base.update(over)
    return base


def test_a_healthy_run_passes_every_check():
    hs.check_platform([SimpleNamespace(platform="tpu")], 1)
    hs.check_no_serve_env({})
    hs.check_default_provider(_provider())
    hs.check_provider_seams(_provider())
    hs.check_sidecar_client(SimpleNamespace(degraded=False))
    hs.check_pipeline(SimpleNamespace(last_error=None, dead=False))
    hs.check_device_lanes(_snapshot(1498), 1498)
    hs.check_bucket(1498, 2048, 2048)
    hs.check_no_compiles({"xla_compiles": 0, "persistent_cache_hits": 0})
    hs.check_sidecar_stats(_stats(), 3028, 2, "device")


@pytest.mark.parametrize("name, call", [
    ("cpu platform", lambda: hs.check_platform([SimpleNamespace(platform="cpu")], 1)),
    ("too few chips", lambda: hs.check_platform([SimpleNamespace(platform="tpu")], 4)),
    ("serve env", lambda: hs.check_no_serve_env({"FABRIC_TPU_SERVE_ADDR": "x"})),
    ("software provider", lambda: hs.check_default_provider(
        type("SoftwareProvider", (), {})())),
    ("degraded", lambda: hs.check_provider_seams(_provider(degraded=True))),
    ("bytes path broken", lambda: hs.check_provider_seams(_provider(bytes_broken=True))),
    ("backend", lambda: hs.check_provider_seams(_provider(backend="tpu-degraded(sw)"))),
    ("client degraded", lambda: hs.check_sidecar_client(SimpleNamespace(degraded=True))),
    ("pipeline error", lambda: hs.check_pipeline(
        SimpleNamespace(last_error=RuntimeError("x"), dead=False))),
    ("pipeline dead", lambda: hs.check_pipeline(SimpleNamespace(last_error=None, dead=True))),
    ("a lane not on the device", lambda: hs.check_device_lanes(_snapshot(1497), 1498)),
    ("second bucket", lambda: hs.check_bucket(3000, 4096, 2048)),
    ("compile in the window", lambda: hs.check_no_compiles(
        {"xla_compiles": 1, "persistent_cache_hits": 0})),
    ("load in the window", lambda: hs.check_no_compiles(
        {"xla_compiles": 0, "persistent_cache_hits": 1})),
    ("host engine", lambda: hs.check_sidecar_stats(_stats(engine="host"), 3028, 2, "device")),
    ("a reject", lambda: hs.check_sidecar_stats(_stats(stats={"rejects": 1}), 3028, 2, "device")),
    ("lanes lost", lambda: hs.check_sidecar_stats(_stats(stats={"lanes": 3000}), 3028, 2, "device")),
])
def test_each_seam_check_fires(name, call):
    with pytest.raises(hs.SeamGaveWay):
        call()


def test_a_seam_that_gave_way_makes_the_run_incorrect(capsys):
    checks = hs.Checks()
    checks.add("mask_mismatch_lanes", 0)
    checks.seam("client", lambda: hs.check_sidecar_client(SimpleNamespace(degraded=False)))
    assert checks.correct
    checks.seam("provider", lambda: hs.check_provider_seams(_provider(degraded=True)))
    assert not checks.correct
    assert checks.rows["provider"] == {"value": 1, "limit": 0}
    assert "degraded" in capsys.readouterr().err


@pytest.mark.parametrize("kind, gives_way", [
    ("tmpfs", True), ("ramfs", True), ("9p", False), ("ext4", False),
    ("overlay", False),
])
def test_a_ledger_in_memory_fails_the_run(kind, gives_way):
    if gives_way:
        with pytest.raises(hs.SeamGaveWay, match="fsync is free"):
            hs.check_fsync_costs(kind)
    else:
        hs.check_fsync_costs(kind)
