"""fabobs: process-wide observability registry (metrics SPI + spans +
flight recorder) and its wiring through the validation data plane.

Discipline mirrors tests/test_faults.py: the disabled path is a no-op,
installation is scoped, and — the mask-safety contract — an
observability failure can never raise into (or alter) a verify path.
"""

import json
import threading
import time

import pytest

from fabric_tpu.common import fabobs
from fabric_tpu.common.fabobs import (
    CANONICAL_METRICS,
    CANONICAL_BY_NAME,
    ObsRegistry,
    obs_installed,
)
from fabric_tpu.common.faults import FaultPlan, InjectedFault, plan_installed
from fabric_tpu.common.metrics import (
    DisabledProvider,
    HistogramOpts,
    PrometheusProvider,
    new_histogram_state,
    observe_into,
    summary_from_histogram_state,
)


@pytest.fixture(autouse=True)
def _no_ambient_obs():
    """Every test starts and ends with the registry disabled (an
    env-enabled run must not leak series between tests)."""
    prev = fabobs.active()
    fabobs.disable()
    yield
    fabobs.disable()
    if prev is not None:
        with fabobs._OBS_LOCK:
            fabobs._OBS = prev


# ---------------- disabled path ----------------


def test_disabled_hooks_are_noops():
    assert not fabobs.enabled()
    fabobs.obs_count("fabric_verify_lanes_total", 5, rung="hostec")
    fabobs.obs_gauge("fabric_batcher_pending_lanes", 1)
    fabobs.obs_observe("fabric_verify_seconds", 0.1, rung="hostec")
    fabobs.obs_event("anything")
    assert fabobs.obs_trigger("anything") is None
    assert fabobs.snapshot() == {}
    s = fabobs.span("x", lanes=3)
    with s:
        pass
    # the shared no-op span: no allocation per call
    assert fabobs.span("y") is fabobs.span("z")


def test_disabled_span_is_reentrant():
    s = fabobs.span("x")
    with s:
        with s:
            pass


# ---------------- installation ----------------


def test_obs_installed_scopes_and_restores():
    assert fabobs.active() is None
    with obs_installed() as reg:
        assert fabobs.active() is reg
        assert fabobs.enabled()
        inner = ObsRegistry()
        with obs_installed(inner):
            assert fabobs.active() is inner
        assert fabobs.active() is reg
    assert fabobs.active() is None


def test_ensure_enabled_first_wins():
    with obs_installed() as reg:
        again = fabobs.ensure_enabled(provider=PrometheusProvider())
        assert again is reg  # existing registry kept, new provider ignored


def test_env_install_semantics(monkeypatch):
    monkeypatch.setenv("FABRIC_TPU_OBS", "0")
    fabobs._install_from_env()
    assert not fabobs.enabled()
    monkeypatch.setenv("FABRIC_TPU_OBS", "1")
    monkeypatch.setenv("FABRIC_TPU_OBS_RING", "notanint")  # degrade, no raise
    fabobs._install_from_env()
    assert fabobs.enabled()
    fabobs.disable()


# ---------------- canonical table + metric sinks ----------------


def test_every_canonical_family_registers_eagerly():
    with obs_installed() as reg:
        text = reg.render()
        for spec in CANONICAL_METRICS:
            assert f"# TYPE {spec.name} {spec.kind}" in text
        # table introspection (README generation surface)
        rows = fabobs.metric_table()
        assert {r["name"] for r in rows} == set(CANONICAL_BY_NAME)


def test_counter_gauge_histogram_record():
    with obs_installed() as reg:
        fabobs.obs_count("fabric_verify_lanes_total", 64, rung="hostec_np")
        fabobs.obs_count("fabric_verify_lanes_total", 36, rung="hostec_np")
        fabobs.obs_gauge("fabric_batcher_pending_lanes", 17)
        fabobs.obs_observe("fabric_verify_seconds", 0.03, rung="hostec_np")
        text = reg.render()
        assert 'fabric_verify_lanes_total{rung="hostec_np"} 100' in text
        assert "fabric_batcher_pending_lanes 17" in text
        assert 'fabric_verify_seconds_count{rung="hostec_np"} 1' in text
        snap = reg.snapshot()
        assert snap["fabric_verify_lanes_total"]["series"]["rung=hostec_np"] == 100
        hist = snap["fabric_verify_seconds"]["series"]["rung=hostec_np"]
        assert hist["n"] == 1


def test_unknown_family_and_bad_labels_swallowed():
    with obs_installed() as reg:
        fabobs.obs_count("not_in_the_table")
        fabobs.obs_count("fabric_verify_lanes_total", 1, wrong_label="x")
        assert reg.dropped >= 1  # bad labels accounted
        # neither call raised, and the good series still works
        fabobs.obs_count("fabric_verify_lanes_total", 1, rung="p256")
        assert 'rung="p256"} 1' in reg.render()


def test_obs_failure_cannot_raise_into_caller():
    class ExplodingProvider(PrometheusProvider):
        def new_counter(self, opts):
            raise RuntimeError("boom")

        def new_gauge(self, opts):
            raise RuntimeError("boom")

        def new_histogram(self, opts):
            raise RuntimeError("boom")

    with obs_installed(ObsRegistry(provider=ExplodingProvider())) as reg:
        # construction swallowed every family; sinks still no-op cleanly
        fabobs.obs_count("fabric_verify_lanes_total", 1, rung="hostec")
        fabobs.obs_gauge("fabric_batcher_pending_lanes", 1)
        with fabobs.span("still.works"):
            pass
        assert reg.dropped >= len(CANONICAL_METRICS)


def test_counter_threads_sum_exactly():
    with obs_installed() as reg:
        def hammer():
            for _ in range(500):
                fabobs.obs_count("fabric_retry_attempts_total")

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert "fabric_retry_attempts_total 4000" in reg.render()


# ---------------- spans + flight recorder ----------------


def test_span_nesting_and_trace_dump():
    with obs_installed() as reg:
        with fabobs.span("outer", kind="test") as outer:
            with fabobs.span("inner") as inner:
                time.sleep(0.002)
            assert inner.parent_id == outer.span_id
        events = reg.trace_events()
        names = [e["name"] for e in events]
        assert names == ["inner", "outer"]  # completion order
        inner_ev = events[0]
        assert inner_ev["ph"] == "X"
        assert inner_ev["dur"] >= 1000  # us
        payload = json.loads(reg.dump())
        assert payload["traceEvents"][1]["args"]["kind"] == "test"
        assert payload["displayTimeUnit"] == "ms"


def test_span_exception_annotated_and_propagated():
    with obs_installed() as reg:
        with pytest.raises(ValueError):
            with fabobs.span("failing"):
                raise ValueError("real error passes through")
        (ev,) = reg.trace_events()
        assert ev["args"]["error"] == "ValueError"
        assert fabobs.current_span() is None  # stack popped


def test_cross_thread_parent_propagation():
    with obs_installed() as reg:
        captured = {}

        def worker(parent):
            with fabobs.span("child", parent=parent) as c:
                captured["parent_id"] = c.parent_id

        with fabobs.span("root") as root:
            t = threading.Thread(target=worker, args=(root,))
            t.start()
            t.join()
        assert captured["parent_id"] == root.span_id


def test_flight_ring_is_bounded():
    with obs_installed(ObsRegistry(ring=32)) as reg:
        for i in range(100):
            fabobs.obs_event("tick", i=i)
        events = reg.trace_events()
        assert len(events) == 32
        assert events[-1]["args"]["i"] == 99  # newest win


def test_trigger_dumps_bounded_files(tmp_path):
    reg = ObsRegistry(dump_dir=str(tmp_path), max_dumps=2)
    with obs_installed(reg):
        fabobs.obs_event("before the fall")
        p1 = fabobs.obs_trigger("batcher.fail_closed", requests=3)
        p2 = fabobs.obs_trigger("serve.client_degraded")
        p3 = fabobs.obs_trigger("one too many")
        assert p1 and p2 and p3 is None  # capped
        assert reg.dumped_paths() == [p1, p2]
        payload = json.loads(open(p1).read())
        names = [e["name"] for e in payload["traceEvents"]]
        assert "before the fall" in names
        assert "trigger:batcher.fail_closed" in names


def test_trigger_without_dump_dir_records_event_only():
    with obs_installed() as reg:
        assert fabobs.obs_trigger("no.dir") is None
        assert reg.trace_events()[-1]["name"] == "trigger:no.dir"


# ---------------- histogram-state summary (metrics helper) ----------------


def test_summary_from_histogram_state():
    buckets = (0.001, 0.01, 0.1, 1.0)
    state = new_histogram_state(buckets)
    assert summary_from_histogram_state(state, buckets) == {"n": 0}
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        observe_into(state, buckets, v)
    out = summary_from_histogram_state(state, buckets)
    assert out["n"] == 5
    assert out["p50_ms"] == 10.0  # 0.01 bucket upper bound
    assert out["mean_ms"] == pytest.approx(1012.1, abs=0.1)
    # the rank lands in the +Inf bucket: report a lower bound on THAT
    # bucket's mean — never below the top finite bound, never the
    # global mean (which would hide the very tail +Inf recorded)
    assert out["p99_ms"] >= 1000.0
    assert out["p99_ms"] == pytest.approx(1060.5, abs=0.1)
    # a tail-heavy series must not report p99 under the ladder top
    tail = new_histogram_state(buckets)
    for _ in range(99):
        observe_into(tail, buckets, 0.001)
    observe_into(tail, buckets, 100.0)
    assert summary_from_histogram_state(tail, buckets)["p99_ms"] >= 1000.0


# ---------------- data-plane wiring ----------------


class _StubProvider:
    """Provider whose batches verify (lane % 2 == 0)."""

    def batch_verify(self, keys, sigs, digests):
        return [k % 2 == 0 for k in keys]


def test_batcher_emits_canonical_series():
    from fabric_tpu.parallel.batcher import VerifyBatcher

    with obs_installed() as reg:
        b = VerifyBatcher(_StubProvider(), max_pending_lanes=64)
        try:
            resolver = b.submit(list(range(8)), [b""] * 8, [b""] * 8)
            assert resolver() == [True, False] * 4
        finally:
            b.stop()
        text = reg.render()
        assert 'fabric_batcher_launches_total{mode="coalesce"} 1' in text
        assert "fabric_batcher_batch_lanes_count 1" in text
        assert "fabric_batcher_submit_wait_seconds_count 1" in text


def test_batcher_busy_reject_counted():
    from fabric_tpu.parallel.batcher import VerifyBatcher

    class _Slow:
        def batch_verify(self, keys, sigs, digests):
            time.sleep(0.2)
            return [True] * len(keys)

    with obs_installed() as reg:
        b = VerifyBatcher(_Slow(), max_pending_lanes=4, linger_s=0.05)
        try:
            b.submit([1, 2, 3], [b""] * 3, [b""] * 3)
            assert b.try_submit([1, 2, 3], [b""] * 3, [b""] * 3) is None
        finally:
            b.stop()
        assert "fabric_batcher_busy_rejects_total 1" in reg.render()


def test_batcher_fail_closed_counted_and_triggers_dump(tmp_path):
    from fabric_tpu.parallel.batcher import VerifyBatcher

    hang = threading.Event()

    class _Hung:
        def batch_verify_async(self, keys, sigs, digests):
            def resolve():
                hang.wait(5.0)
                return [True] * len(keys)

            return resolve

    reg = ObsRegistry(dump_dir=str(tmp_path), max_dumps=4)
    with obs_installed(reg):
        b = VerifyBatcher(_Hung(), join_timeout_s=0.2)
        r = b.submit([1], [b""], [b""])
        time.sleep(0.05)  # let the dispatcher pick it up
        b.stop()
        hang.set()
        assert r() == [False]  # settled fail-closed
        assert "fabric_batcher_fail_closed_total 1" in reg.render()
        assert len(reg.dumped_paths()) == 1  # trigger dumped the ring


def test_bccsp_rung_series():
    from fabric_tpu.crypto.bccsp import SoftwareProvider, ec_backend_name

    from fabric_tpu.common import der, p256
    from fabric_tpu.crypto import hostec
    import hashlib

    d = 0xA11CE
    pub_pt = hostec.scalar_base_mult(d)
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey

    digest = hashlib.sha256(b"obs lane").digest()
    r, s = hostec.sign_digest(d, digest)
    sig = der.marshal_signature(r, s)
    key = ECDSAPublicKey(*pub_pt)
    with obs_installed() as reg:
        mask = SoftwareProvider().batch_verify([key] * 4, [sig] * 4, [digest] * 4)
        assert mask == [True] * 4
        rung = ec_backend_name()
        assert f'fabric_verify_lanes_total{{rung="{rung}"}} 4' in reg.render()


def test_obs_cannot_alter_mask():
    """The mask-safety contract, empirically: a registry whose every
    series write explodes must not change one verdict bit of a batch
    routed through the instrumented provider path."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    provider = SoftwareProvider()
    keys = [None] * 3
    sigs = [b"\x00bad"] * 3
    digests = [b"\x00" * 32] * 3
    baseline = provider.batch_verify(keys, sigs, digests)

    reg = ObsRegistry()

    def explode(*a, **k):
        raise RuntimeError("series write exploded")

    for inst in reg._instruments.values():
        for attr in ("add", "observe", "set", "with_labels"):
            if hasattr(inst, attr):
                setattr(inst, attr, explode)
    with obs_installed(reg):
        mask = provider.batch_verify(keys, sigs, digests)
    assert mask == baseline == [False, False, False]
    assert reg.dropped > 0


def test_pipeline_stage_series_and_spans():
    """The /metrics series counts every block of each stage, and the
    flight ring holds one span per block and stage (the pipeline's own
    per-stage histogram is gone: bucket bounds sold as p50/p99)."""
    from fabric_tpu.peer.pipeline import CommitPipeline
    from fabric_tpu.protos import common_pb2

    class _Chan:
        channel_id = "obs-ch"

        def prepare_block(self, block):
            return "prep"

        def store_block(self, block, prepared=None):
            return "flags"

    with obs_installed() as reg:
        p = CommitPipeline(_Chan())
        try:
            for n in range(3):
                blk = common_pb2.Block()
                blk.header.number = n
                p.submit(blk)
            assert p.drain(5.0)
        finally:
            p.stop()
        assert not hasattr(p, "stage_stats")
        text = reg.render()
        assert 'fabric_pipeline_stage_seconds_count{stage="prepare"} 3' in text
        assert 'fabric_pipeline_stage_seconds_count{stage="commit"} 3' in text
        for stage in ("pipeline.prepare", "pipeline.commit",
                      "pipeline.backpressure", "pipeline.queue_wait"):
            blocks = sorted(
                e["args"]["block"] for e in reg.trace_events()
                if e["name"] == stage
            )
            assert blocks == [0, 1, 2], stage


def test_fault_fires_counted():
    from fabric_tpu.common.faults import fault_point

    with obs_installed() as reg:
        with plan_installed(FaultPlan.parse("obs.site=raise:1.0:max=2")):
            for _ in range(3):
                try:
                    fault_point("obs.site")
                except InjectedFault:
                    pass
        assert 'fabric_fault_fired_total{site="obs.site"} 2' in reg.render()


def test_retry_attempts_counted():
    from fabric_tpu.common.retry import RetryPolicy, call_with_retry

    calls = {"n": 0}

    def flaky(attempt):
        calls["n"] += 1
        if attempt < 2:
            raise ConnectionError("flap")
        return "ok"

    with obs_installed() as reg:
        out = call_with_retry(
            flaky,
            policy=RetryPolicy(base_s=0.001, max_attempts=5),
            sleeper=lambda s: None,
        )
        assert out == "ok" and calls["n"] == 3
        text = reg.render()
        assert "fabric_retry_attempts_total 2" in text
        assert "fabric_retry_backoff_seconds_count 2" in text


def test_serve_stats_emits_spi_series():
    from fabric_tpu.serve.server import ServeStats

    with obs_installed() as reg:
        stats = ServeStats()
        stats.record(lanes=128, bucket=128, seconds=0.004)
        stats.record(lanes=64, bucket=128, seconds=0.002)
        stats.reject()
        stats.error()
        stats.stopping_reply()
        # the exact local summary API is unchanged...
        summary = stats.summary()
        assert summary["requests"] == 2 and summary["rejects"] == 1
        assert summary["request_latency"]["n"] == 2
        # ...and the same calls drove the SPI series
        text = reg.render()
        assert 'fabric_serve_requests_total{status="ok"} 2' in text
        assert 'fabric_serve_requests_total{status="busy"} 1' in text
        assert 'fabric_serve_requests_total{status="error"} 1' in text
        assert 'fabric_serve_requests_total{status="stopping"} 1' in text
        assert "fabric_serve_lanes_total 192" in text
        assert 'fabric_serve_bucket_requests_total{bucket="128"} 2' in text


def test_sidecar_ops_mount_metrics_and_healthz(tmp_path):
    """The acceptance-criteria path: a sidecar with obs enabled answers
    /metrics with the canonical families and /healthz flips 503 with the
    named checker when the batcher dies."""
    import urllib.error
    import urllib.request

    from fabric_tpu.serve.client import SidecarProvider
    from fabric_tpu.serve.server import SidecarServer

    with obs_installed():
        server = SidecarServer(
            str(tmp_path / "obs.sock"), engine="host",
            ops_address="127.0.0.1:0",
        )
        try:
            server.warm()
            addr = server.start()
            ops = server.ops_address
            assert server.ops is not None

            import hashlib

            from fabric_tpu.common import der
            from fabric_tpu.crypto import hostec
            from fabric_tpu.crypto.bccsp import ECDSAPublicKey

            d = 0xB0B
            pub = ECDSAPublicKey(*hostec.scalar_base_mult(d))
            digest = hashlib.sha256(b"ops lane").digest()
            r, s = hostec.sign_digest(d, digest)
            sig = der.marshal_signature(r, s)
            provider = SidecarProvider(address=addr)
            mask = provider.batch_verify([pub] * 8, [sig] * 8, [digest] * 8)
            assert mask == [True] * 8

            with urllib.request.urlopen(f"http://{ops}/metrics") as resp:
                text = resp.read().decode()
            for spec in CANONICAL_METRICS:
                assert f"# TYPE {spec.name}" in text
            assert 'fabric_serve_requests_total{status="ok"} 1' in text
            with urllib.request.urlopen(f"http://{ops}/healthz") as resp:
                assert json.load(resp)["status"] == "OK"
            # the flight recorder is served on demand
            with urllib.request.urlopen(f"http://{ops}/trace") as resp:
                trace = json.load(resp)
            assert any(
                e["name"] == "serve.verify" for e in trace["traceEvents"]
            )

            server.batcher.stop()
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"http://{ops}/healthz")
            payload = json.load(exc.value)
            failed = {c["component"] for c in payload["failed_checks"]}
            assert "batcher" in failed
        finally:
            server.stop()


# ---------------- spans: the mechanism (PR 28) ----------------


def _spans(reg, name=None):
    return [
        e for e in reg.trace_events()
        if e.get("ph") == "X" and (name is None or e["name"] == name)
    ]


def test_record_span_keeps_the_given_start():
    with obs_installed() as reg:
        t0 = time.perf_counter() - 0.25  # a wait that began in the past
        with fabobs.span("outer", block=7) as outer:
            fabobs.obs_record_span("waited", t0, t0 + 0.125, lanes=3)
        fabobs.obs_record_span("handed_over", t0, t0 + 0.5, parent=outer)
        waited, = _spans(reg, "waited")
        handed, = _spans(reg, "handed_over")
        outer_ev, = _spans(reg, "outer")
        assert waited["dur"] == pytest.approx(125000.0, abs=0.2)
        assert handed["dur"] == pytest.approx(500000.0, abs=0.2)
        # the given start, not the moment of recording: before its parent
        assert waited["ts"] == handed["ts"] < outer_ev["ts"]
        # parent: the thread's open span, or the one handed over
        assert waited["args"]["parent_id"] == outer.span_id
        assert handed["args"]["parent_id"] == outer.span_id
        assert waited["args"]["block"] == handed["args"]["block"] == 7
        assert waited["args"]["lanes"] == 3
        assert len({e["args"]["span_id"] for e in _spans(reg)}) == 3


def test_record_span_disabled_is_noop_and_failures_swallowed():
    fabobs.obs_record_span("nobody", 0.0, 1.0)  # disabled: nothing
    with obs_installed() as reg:
        fabobs.obs_record_span("bad", "not a time", 1.0)
        assert reg.dropped == 1 and _spans(reg) == []


def test_child_inherits_block_and_request_ids():
    with obs_installed() as reg:
        with fabobs.span("block.stage", block=0):
            with fabobs.span("inner", lanes=4):
                with fabobs.span("innermost"):
                    pass
            with fabobs.span("own", block=9):
                pass
        with fabobs.span("request", req_id=11, req_ids=[11, 12]):
            with fabobs.span("req.inner"):
                pass
        args = {e["name"]: e["args"] for e in _spans(reg)}
        assert args["inner"]["block"] == args["innermost"]["block"] == 0
        assert args["own"]["block"] == 9
        assert args["req.inner"]["req_id"] == 11
        assert args["req.inner"]["req_ids"] == [11, 12]
        assert "lanes" not in args["innermost"]  # identifiers only


def test_span_set_lands_in_the_ring_even_after_exit():
    with obs_installed() as reg:
        with fabobs.span("encode") as enc:
            pass
        with fabobs.span("roundtrip") as rt:
            rt.set(req_id=5)
        enc.set(req_id=5)  # the id was allocated after `encode` closed
        assert [e["args"]["req_id"] for e in _spans(reg)] == [5, 5]
        snapshot = reg.trace_events()
        enc.set(req_id=6)  # a snapshot taken earlier does not move
        assert snapshot[0]["args"]["req_id"] == 5
    fabobs.span("disabled").set(req_id=1)  # the shared no-op span


def test_span_ids_unique_across_threads():
    with obs_installed(ring=1 << 14) as reg:
        def work():
            for _ in range(500):
                with fabobs.span("t"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        ids = [e["args"]["span_id"] for e in _spans(reg, "t")]
        assert len(ids) == len(set(ids)) == 4000


class _RecordingAnnotation:
    """Stand-in for jax.profiler.TraceAnnotation."""

    log = []

    def __init__(self, name, **kwargs):
        assert not kwargs, "name only: attributes stay in the ring"
        self.name = name

    def __enter__(self):
        type(self).log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        type(self).log.append(("exit", self.name, threading.get_ident()))


def test_every_span_is_one_annotation_of_its_name_on_its_thread(monkeypatch):
    import jax

    monkeypatch.setattr(_RecordingAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _RecordingAnnotation)
    with obs_installed() as reg:
        def other():
            with fabobs.span("on.other.thread"):
                pass

        with fabobs.span("outer", block=1):
            with fabobs.span("inner"):
                pass
            t = threading.Thread(target=other)
            t.start()
            t.join(10)
            fabobs.obs_record_span("past", 0.0, 1.0)  # nobody executes it
        log = _RecordingAnnotation.log
        tid_of = {e["name"]: e["tid"] for e in _spans(reg)}
        for name in ("outer", "inner", "on.other.thread"):
            assert log.count(("enter", name, tid_of[name])) == 1
            assert log.count(("exit", name, tid_of[name])) == 1
        assert tid_of["on.other.thread"] != tid_of["outer"]
        assert len(log) == 6 and not any(n == "past" for _, n, _ in log)
        # properly nested on the thread: inner closes before outer
        mine = [(k, n) for k, n, tid in log if tid == tid_of["outer"]]
        assert mine == [("enter", "outer"), ("enter", "inner"),
                        ("exit", "inner"), ("exit", "outer")]


@pytest.mark.parametrize("fails_in", ["__init__", "__enter__", "__exit__"])
def test_raising_annotation_loses_no_ring_record(monkeypatch, fails_in):
    import jax

    class _Exploding:
        def __init__(self, name):
            if fails_in == "__init__":
                raise RuntimeError("profiler exploded")

        def __enter__(self):
            if fails_in == "__enter__":
                raise RuntimeError("profiler exploded")
            return self

        def __exit__(self, *exc):
            if fails_in == "__exit__":
                raise RuntimeError("profiler exploded")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Exploding)
    with obs_installed() as reg:
        with pytest.raises(KeyError):  # the wrapped code's own error
            with fabobs.span("guarded", block=3):
                with fabobs.span("child"):
                    pass
                raise KeyError("from the wrapped code")
        guarded, = _spans(reg, "guarded")
        child, = _spans(reg, "child")
        assert guarded["args"]["error"] == "KeyError"
        assert child["args"]["parent_id"] == guarded["args"]["span_id"]
        assert reg.dropped == 2  # one per span, swallowed
        assert fabobs.current_span() is None


def test_span_records_with_jax_absent(monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax")
    with obs_installed() as reg:
        with fabobs.span("no.jax", block=2):
            pass
        ev, = _spans(reg, "no.jax")
        assert ev["args"]["block"] == 2 and reg.dropped == 0


def test_fabobs_imports_no_jax():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(fabobs))
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert "jax" not in imported


# ---------------- spans: the sites (PR 28) ----------------


class _PlantedKernel:
    """Stands where TPUProvider keeps ops.p256_kernel: no trace, no
    compile.  The verdict is the host precheck mask (DER ok, low-S, key
    on the curve), which is the right verdict for well-formed lanes."""

    class _Jitted:
        """What the provider uses of a jitted function: the call, and
        ``lower`` (made once per shape, before the first call)."""

        def __call__(self, *operands):
            return operands[-1]  # `ok`

        def lower(self, *operands):
            return None

    verify_batch_bytes_jit = _Jitted()
    verify_batch_jit = _Jitted()


def _planted_tpu_provider():
    from fabric_tpu.crypto.tpu_provider import TPUProvider

    provider = TPUProvider()
    provider._pk = _PlantedKernel()
    return provider


BLOCK_SPANS = (
    "pipeline.prepare", "prepare.content_check", "prepare.parse",
    "prepare.collect_sig_jobs", "tpu.prep", "tpu.dispatch",
    "pipeline.backpressure", "pipeline.queue_wait", "pipeline.commit",
    "commit.await_verdicts", "tpu.resolve", "commit.validate",
    "commit.rwsets", "commit.assemble_pvt", "ledger.mvcc", "ledger.block_append", "ledger.state_commit",
)


def test_one_block_through_the_pipeline_yields_each_span_once(tmp_path):
    pytest.importorskip("cryptography")
    import test_pipeline as tp

    from fabric_tpu.peer.channel import Channel
    from fabric_tpu.peer.pipeline import CommitPipeline

    org_world = tp.world.__wrapped__()
    provider = _planted_tpu_provider()
    ch = Channel(
        tp.CHANNEL, str(tmp_path), org_world["mgr"], org_world["registry"],
        provider,
    )
    blocks = tp._chain(org_world, 2)
    with obs_installed() as reg:
        pipe = CommitPipeline(ch)
        try:
            for b in blocks:
                pipe.submit(b)
            assert pipe.drain(timeout=60)
        finally:
            pipe.stop()
        assert pipe.last_error is None and ch.ledger.height == 2
        events = _spans(reg)

    for number in (0, 1):
        mine = {}
        for e in events:
            if e["args"].get("block") == number:
                assert e["name"] not in mine, f"{e['name']} twice"
                mine[e["name"]] = e
        assert sorted(mine) == sorted(BLOCK_SPANS)
        ident = {n: e["args"]["span_id"] for n, e in mine.items()}
        parent = {n: e["args"].get("parent_id", 0) for n, e in mine.items()}
        # stage A, on the submitting thread
        assert parent["pipeline.prepare"] == 0
        for child in ("prepare.content_check", "prepare.parse",
                      "prepare.collect_sig_jobs", "tpu.prep", "tpu.dispatch"):
            assert parent[child] == ident["pipeline.prepare"], child
        # across the queue: the hand-off carries stage A's span
        for child in ("pipeline.backpressure", "pipeline.queue_wait",
                      "pipeline.commit"):
            assert parent[child] == ident["pipeline.prepare"], child
        assert mine["pipeline.commit"]["tid"] != mine["pipeline.prepare"]["tid"]
        # stage B, on the committer thread
        for child in ("commit.await_verdicts", "commit.validate",
                      "commit.rwsets", "commit.assemble_pvt",
                      "ledger.mvcc", "ledger.block_append",
                      "ledger.state_commit"):
            assert parent[child] == ident["pipeline.commit"], child
            assert mine[child]["tid"] == mine["pipeline.commit"]["tid"]
        assert parent["tpu.resolve"] == ident["commit.await_verdicts"]
        assert mine["tpu.prep"]["args"]["lanes"] == 6
        assert mine["tpu.prep"]["args"]["distinct_keys"] == 2
        assert mine["tpu.dispatch"]["args"]["bucket"] == 128
        # self time = duration - children: never negative, and what the
        # ledger's own clock reads split is inside the stage
        commit = mine["pipeline.commit"]
        children = sum(
            e["dur"] for n, e in mine.items()
            if parent[n] == ident["pipeline.commit"]
        )
        assert 0 <= commit["dur"] - children < commit["dur"]
        # the hand-off's waits lie between the two stages
        prep_end = mine["pipeline.prepare"]["ts"] + mine["pipeline.prepare"]["dur"]
        assert mine["pipeline.backpressure"]["ts"] >= prep_end - 1
        wait = mine["pipeline.queue_wait"]
        assert wait["ts"] + wait["dur"] <= commit["ts"] + 1


def test_device_rung_seconds_leave_out_the_wait_to_be_resolved():
    """fabric_verify_seconds{rung="device"} is the provider's own prep +
    dispatch + resolve, not dispatch -> consumed."""
    import hashlib

    from fabric_tpu.common import der
    from fabric_tpu.crypto import hostec
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey

    d = 0xD1CE
    pub = ECDSAPublicKey(*hostec.scalar_base_mult(d))
    digest = hashlib.sha256(b"device rung").digest()
    sig = der.marshal_signature(*hostec.sign_digest(d, digest))
    provider = _planted_tpu_provider()
    with obs_installed() as reg:
        resolve = provider.batch_verify_async([pub] * 4, [sig] * 4, [digest] * 4)
        time.sleep(0.3)  # the resolver waits to be called
        assert resolve() == [True] * 4
        series = reg.snapshot()["fabric_verify_seconds"]["series"]
        assert series["rung=device"]["n"] == 1
        assert series["rung=device"]["mean_ms"] < 250
        own = sum(
            e["dur"] for e in _spans(reg)
            if e["name"] in ("tpu.prep", "tpu.dispatch", "tpu.resolve")
        )
        assert own < 250e3


REQUEST_SPANS = (
    "client.encode", "client.roundtrip", "client.decode", "serve.decode",
    "serve.verify", "serve.reply", "batcher.queue_wait", "batcher.launch",
    "tpu.prep", "tpu.dispatch", "batcher.settle", "tpu.resolve",
)


def test_one_sidecar_request_yields_its_spans_under_one_req_id(tmp_path):
    import hashlib

    from fabric_tpu.common import der
    from fabric_tpu.crypto import hostec
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey
    from fabric_tpu.serve.client import SidecarProvider
    from fabric_tpu.serve.server import SidecarServer

    d = 0xC0DE
    pub = ECDSAPublicKey(*hostec.scalar_base_mult(d))
    digest = hashlib.sha256(b"one request").digest()
    sig = der.marshal_signature(*hostec.sign_digest(d, digest))
    with obs_installed() as reg:
        server = SidecarServer(
            str(tmp_path / "spans.sock"), engine="device",
            provider=_planted_tpu_provider(), warm_ladder="off",
        )
        try:
            client = SidecarProvider(address=server.start())
            try:
                for _ in range(2):
                    mask = client.batch_verify([pub] * 5, [sig] * 5, [digest] * 5)
                    assert mask == [True] * 5
                assert client.degraded is False
            finally:
                client.client.close()
        finally:
            server.stop()
        events = _spans(reg)

    req_ids = sorted({e["args"]["req_id"] for e in events
                      if e["name"] == "client.roundtrip"})
    assert len(req_ids) == 2
    for req_id in req_ids:
        mine = {}
        for e in events:
            if e["args"].get("req_id") == req_id:
                assert e["name"] not in mine, f"{e['name']} twice"
                mine[e["name"]] = e
        assert sorted(mine) == sorted(REQUEST_SPANS)
        ident = {n: e["args"]["span_id"] for n, e in mine.items()}
        parent = {n: e["args"].get("parent_id", 0) for n, e in mine.items()}
        # the batcher's spans, on the dispatcher thread, hang under the
        # request's serve.verify; the provider's under the batcher's
        assert parent["batcher.queue_wait"] == ident["serve.verify"]
        assert parent["batcher.launch"] == ident["serve.verify"]
        assert parent["batcher.settle"] == ident["batcher.launch"]
        assert parent["tpu.prep"] == parent["tpu.dispatch"] == ident["batcher.launch"]
        assert parent["tpu.resolve"] == ident["batcher.settle"]
        assert mine["batcher.launch"]["tid"] != mine["serve.verify"]["tid"]
        assert mine["batcher.launch"]["args"]["requests"] == 1
        # the wait began when the request was admitted, before its launch
        wait = mine["batcher.queue_wait"]
        launch = mine["batcher.launch"]
        assert wait["ts"] + wait["dur"] <= launch["ts"] + 1
        # the client's round trip holds the server's whole handling
        rt = mine["client.roundtrip"]
        for name in ("serve.decode", "serve.verify"):
            assert rt["ts"] <= mine[name]["ts"]
            assert mine[name]["ts"] + mine[name]["dur"] <= rt["ts"] + rt["dur"]


def test_coalesced_launch_carries_req_ids():
    from fabric_tpu.parallel.batcher import VerifyBatcher

    class _Sync:
        def batch_verify(self, keys, sigs, digests):
            return [True] * len(keys)

    gate = threading.Event()

    class _Gated(_Sync):
        def batch_verify(self, keys, sigs, digests):
            gate.wait(10)
            return super().batch_verify(keys, sigs, digests)

    with obs_installed() as reg:
        batcher = VerifyBatcher(_Gated(), linger_s=0.0)
        try:
            # the first launch holds the dispatcher; the next two requests
            # queue up behind it and leave as one launch
            first = batcher.submit([1], [b"s"], [b"d"])
            time.sleep(0.05)
            resolvers = []
            for req_id in (21, 22):
                with fabobs.span("serve.verify", req_id=req_id) as parent:
                    resolvers.append(batcher.try_submit(
                        [1, 2], [b"s"] * 2, [b"d"] * 2, parent=parent
                    ))
            gate.set()
            assert first() == [True]
            assert [r() for r in resolvers] == [[True, True]] * 2
        finally:
            gate.set()
            batcher.stop()
        launches = _spans(reg, "batcher.launch")
        assert [e["args"]["requests"] for e in launches] == [1, 2]
        assert launches[1]["args"]["req_ids"] == [21, 22]
        assert "req_id" not in launches[1]["args"]
        waits = _spans(reg, "batcher.queue_wait")
        assert sorted(e["args"].get("req_id", 0) for e in waits) == [0, 21, 22]
        settle = _spans(reg, "batcher.settle")[1]
        assert settle["args"]["req_ids"] == [21, 22]


@pytest.mark.parametrize("event, name", [
    ("/jax/core/compile/jaxpr_trace_duration", "program.trace_lower"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "program.trace_lower"),
    ("/jax/core/compile/backend_compile_duration", "program.compile_or_load"),
])
def test_jax_duration_event_becomes_a_program_span(event, name):
    """JAX's own durations of set-up, through the one listener the serve
    registry installs: a span that ends now and began `duration` ago."""
    import jax

    from fabric_tpu.serve.registry import _CompileCounters

    _CompileCounters.install()
    with fabobs.obs_installed() as reg:
        t_before = time.perf_counter()
        jax.monitoring.record_event_duration_secs(event, 0.5, fun_name="f")
        (span,) = _spans(reg, name)
        assert span["dur"] == pytest.approx(0.5e6, abs=1.0)
        end_us = span["ts"] + span["dur"]
        assert end_us >= reg._us(t_before)
        assert end_us <= reg._us(time.perf_counter())
        assert span["args"]["event"] == event.rsplit("/", 1)[-1]
        assert span["args"]["fun"] == "f"
        # a jnp helper's few milliseconds leave no span
        jax.monitoring.record_event_duration_secs(event, 0.05, fun_name="g")
        assert len(_spans(reg, name)) == 1


def test_other_jax_durations_and_a_disabled_fabobs_leave_no_program_span():
    import jax

    from fabric_tpu.serve.registry import _CompileCounters

    _CompileCounters.install()
    c0, h0 = _CompileCounters.snapshot()
    # fabobs off: the counter still counts, nothing is recorded or raised
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5
    )
    assert _CompileCounters.snapshot() == (c0 + 1, h0)
    with fabobs.obs_installed() as reg:
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 9.0
        )
        assert [e for e in reg.trace_events()
                if e["name"].startswith("program.")] == []
