"""fablife unit tests: a firing fixture + negative control per rule
(with the two HISTORICAL bugs re-created in fixture form: the
pre-PR-10 sidecar stop()/accept() shape fires ``thread-unjoined`` and
the pre-PR-8 unclamped ``retry_after_ms`` sleep fires
``wire-unclamped`` — the fixed shapes are the negative controls),
suppression semantics, loud pairs.toml parse errors, CLI plumbing, the
toolkit analyzer-registry protocol, and the repo self-check (the CI
gate invariant: ``fablife fabric_tpu/ tests/`` reports 0
unsuppressed findings).

Fixture code lives in *strings* on purpose: the repo self-check scans
this file too, and only genuine AST shapes may feed the rules."""

import json
import textwrap
from pathlib import Path

import pytest

from fabric_tpu.tools import fablife, fabreg, toolkit
from fabric_tpu.tools.fablife import PairSpec, parse_pairs

REPO_ROOT = Path(__file__).resolve().parent.parent
PKG = "fabric_tpu/m.py"
SERVE = "fabric_tpu/serve/m.py"


def analyze(src, path=PKG, rules=None, pairs=()):
    findings, _n = fablife.analyze_source(
        textwrap.dedent(src), path, rules, pairs=pairs
    )
    return findings


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# thread-unjoined
# ---------------------------------------------------------------------------

# the pre-PR-10 sidecar shape: stop() flips a flag but never joins (or
# wakes) the accept thread — every teardown ate the full join timeout
SIDECAR_PRE_PR10 = """
    import threading

    class Sidecar:
        def start(self):
            self._accept = threading.Thread(
                target=self._accept_loop, name="serve-accept", daemon=True
            )
            self._accept.start()

        def stop(self):
            self._stopping = True
"""

# the post-PR-10 shape: shutdown the listener, then join
SIDECAR_FIXED = """
    import socket
    import threading

    class Sidecar:
        def start(self):
            self._accept = threading.Thread(
                target=self._accept_loop, name="serve-accept", daemon=True
            )
            self._accept.start()

        def stop(self):
            self._stopping = True
            try:
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self._accept.join(timeout=2.0)
"""


def test_thread_unjoined_fires_on_pre_pr10_sidecar_shape():
    findings = analyze(SIDECAR_PRE_PR10, rules=["thread-unjoined"])
    assert rule_ids(findings) == ["thread-unjoined"]
    assert "_accept" in findings[0].message


def test_thread_unjoined_negative_control_is_the_fixed_sidecar():
    assert analyze(SIDECAR_FIXED, rules=["thread-unjoined"]) == []


def test_thread_list_join_loop_satisfies_and_its_absence_fires():
    clean = """
        import threading

        class S:
            def start(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()
                self._threads.append(t)

            def stop(self):
                for t in list(self._threads):
                    t.join(timeout=2.0)
    """
    assert analyze(clean, rules=["thread-unjoined"]) == []
    leaky = """
        import threading

        class S:
            def start(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()
                self._threads.append(t)

            def stop(self):
                self._stopping = True
    """
    findings = analyze(leaky, rules=["thread-unjoined"])
    assert rule_ids(findings) == ["thread-unjoined"]
    assert "_threads" in findings[0].message


def test_thread_unjoined_unbound_start_always_fires():
    src = """
        import threading

        def spawn():
            threading.Thread(target=work, daemon=True).start()
    """
    findings = analyze(src, rules=["thread-unjoined"])
    assert rule_ids(findings) == ["thread-unjoined"]
    assert "unbound" in findings[0].message


def test_thread_unjoined_ownership_transfer_satisfies():
    # handed to a registrar / joined through an alias / stored on
    # another owner object — all ownership transfers, not leaks
    src = """
        import threading

        def spawn(reg, session):
            a = threading.Thread(target=work)
            a.start()
            reg.register(a)
            b = threading.Thread(target=work)
            b.start()
            t = b
            t.join(timeout=1.0)
            c = threading.Thread(target=work)
            session._thread = c
            c.start()
            d = threading.Thread(target=work)
            d.start()
            return d
    """
    assert analyze(src, rules=["thread-unjoined"]) == []


def test_thread_unjoined_scoped_to_the_package():
    assert (
        analyze(SIDECAR_PRE_PR10, path="tests/helper.py",
                rules=["thread-unjoined"])
        == []
    )


# ---------------------------------------------------------------------------
# fd-leak
# ---------------------------------------------------------------------------


def test_fd_leak_straight_line_rmtree_fires_finally_satisfies():
    leaky = """
        import shutil
        import tempfile

        def run():
            d = tempfile.mkdtemp(prefix="x")
            do_work(d)
            shutil.rmtree(d)
    """
    findings = analyze(leaky, rules=["fd-leak"])
    assert rule_ids(findings) == ["fd-leak"]
    assert "straight-line" in findings[0].message
    clean = """
        import shutil
        import tempfile

        def run():
            d = tempfile.mkdtemp(prefix="x")
            try:
                do_work(d)
            finally:
                shutil.rmtree(d, ignore_errors=True)
    """
    assert analyze(clean, rules=["fd-leak"]) == []


def test_fd_leak_tempdir_path_derivation_tracks_through_os_path_join():
    # the fabchaos serve-socket shape: the tracked var is DERIVED from
    # the mkdtemp return; rmtree(dirname(addr)) in a finally releases
    clean = """
        import os
        import shutil
        import tempfile

        def run():
            addr = os.path.join(tempfile.mkdtemp(prefix="s"), "s.sock")
            try:
                serve(addr)
            finally:
                shutil.rmtree(os.path.dirname(addr), ignore_errors=True)
    """
    assert analyze(clean, rules=["fd-leak"]) == []
    # ...and passing the path to a call is NOT an ownership transfer
    leaky = """
        import os
        import tempfile

        def run():
            addr = os.path.join(tempfile.mkdtemp(prefix="s"), "s.sock")
            serve(addr)
    """
    assert rule_ids(analyze(leaky, rules=["fd-leak"])) == ["fd-leak"]


def test_fd_leak_dropped_tempdir_path_fires():
    src = """
        import tempfile

        def run():
            serve(tempfile.mkdtemp(prefix="x"))
    """
    findings = analyze(src, rules=["fd-leak"])
    assert rule_ids(findings) == ["fd-leak"]
    assert "dropped" in findings[0].message


def test_fd_leak_fixture_teardown_after_yield_satisfies():
    src = """
        import shutil
        import tempfile

        def tmp_fixture():
            d = tempfile.mkdtemp(prefix="t")
            yield d
            shutil.rmtree(d, ignore_errors=True)
    """
    assert analyze(src, rules=["fd-leak"]) == []


def test_fd_leak_registered_cleanup_satisfies():
    src = """
        import atexit
        import shutil
        import tempfile

        def run():
            d = tempfile.mkdtemp(prefix="x")
            atexit.register(shutil.rmtree, d, ignore_errors=True)
            do_work(d)
    """
    assert analyze(src, rules=["fd-leak"]) == []


def test_fd_leak_tempdir_facet_covers_tests_and_bench():
    src = """
        import tempfile

        def helper():
            d = tempfile.mkdtemp(prefix="x")
            do_work(d)
    """
    assert rule_ids(
        analyze(src, path="tests/helper.py", rules=["fd-leak"])
    ) == ["fd-leak"]


def test_fd_leak_socket_with_and_finally_satisfy_bare_fires():
    leaky = """
        import socket

        def dial(addr):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.connect(addr)
            s.close()
    """
    findings = analyze(leaky, rules=["fd-leak"])
    assert rule_ids(findings) == ["fd-leak"]
    clean = """
        import socket

        def dial(addr):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            try:
                s.connect(addr)
            finally:
                s.close()

        def dial2(addr):
            with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
                s.connect(addr)
    """
    assert analyze(clean, rules=["fd-leak"]) == []
    # fd facets pin the package only: a test-process socket dies with it
    assert analyze(leaky, path="tests/helper.py", rules=["fd-leak"]) == []


def test_fd_leak_attr_stored_socket_needs_class_release():
    clean = """
        import socket

        class Server:
            def start(self):
                self._listener = socket.socket()

            def stop(self):
                self._listener.close()
    """
    assert analyze(clean, rules=["fd-leak"]) == []
    leaky = """
        import socket

        class Server:
            def start(self):
                self._listener = socket.socket()
    """
    findings = analyze(leaky, rules=["fd-leak"])
    assert rule_ids(findings) == ["fd-leak"]
    assert "_listener" in findings[0].message


def test_fd_leak_return_hands_ownership_to_the_caller():
    src = """
        import socket
        import tempfile

        def make_sock():
            s = socket.socket()
            return s

        def make_dir():
            d = tempfile.mkdtemp()
            return d
    """
    assert analyze(src, rules=["fd-leak"]) == []


# ---------------------------------------------------------------------------
# lock-leak
# ---------------------------------------------------------------------------


def test_lock_leak_bare_acquire_fires_finally_release_satisfies():
    leaky = """
        class C:
            def f(self):
                self._lock.acquire()
                work()
                self._lock.release()
    """
    findings = analyze(leaky, rules=["lock-leak"])
    assert rule_ids(findings) == ["lock-leak"]
    assert "with" in findings[0].message
    clean = """
        class C:
            def f(self):
                self._lock.acquire()
                try:
                    work()
                finally:
                    self._lock.release()

            def g(self):
                with self._lock:
                    work()
    """
    assert analyze(clean, rules=["lock-leak"]) == []


# ---------------------------------------------------------------------------
# pair-imbalance
# ---------------------------------------------------------------------------

QOS_PAIR = PairSpec(
    name="qos-lane", acquire="try_acquire", release=("release",),
    base_like=("ledger", "qos"), mode="base", conditional=True,
    doc="lane ledger",
)
BATCHER_PAIR = PairSpec(
    name="batcher-admit", acquire="try_submit", release=(),
    base_like=("batcher",), mode="result", conditional=True,
    doc="admission resolver",
)
GATE_PAIR = PairSpec(
    name="cooldown-verdict", acquire="ready",
    release=("record_failure", "record_success"),
    base_like=("gate",), mode="base", conditional=True, doc="gate",
)


def test_pair_imbalance_success_path_missing_release_fires():
    src = """
        def f(ledger):
            if ledger.try_acquire(1, 4):
                if overloaded():
                    return None
                work()
                ledger.release(1, 4)
    """
    findings = analyze(src, rules=["pair-imbalance"], pairs=[QOS_PAIR])
    assert rule_ids(findings) == ["pair-imbalance"]
    assert "qos-lane" in findings[0].message


def test_pair_imbalance_release_on_every_success_path_satisfies():
    src = """
        def f(ledger):
            if ledger.try_acquire(1, 4):
                if overloaded():
                    ledger.release(1, 4)
                    return None
                work()
                ledger.release(1, 4)

        def g(ledger):
            if not ledger.try_acquire(1, 4):
                return None
            try:
                work()
            finally:
                ledger.release(1, 4)
    """
    assert analyze(src, rules=["pair-imbalance"], pairs=[QOS_PAIR]) == []


def test_pair_imbalance_base_like_filters_other_receivers():
    src = """
        def f(executor):
            if executor.try_acquire(1):
                return work()
    """
    assert analyze(src, rules=["pair-imbalance"], pairs=[QOS_PAIR]) == []


def test_pair_imbalance_split_phase_class_release_is_the_weak_tier():
    # the serve sidecar shape: lanes release on dispatcher pickup, in
    # ANOTHER method of the owning class (the on_dispatch hook)
    src = """
        class Server:
            def handle(self):
                if self.qos.try_acquire(1, 4):
                    self.enqueue()

            def on_dispatch(self):
                self.qos.release(1, 4)
    """
    assert analyze(src, rules=["pair-imbalance"], pairs=[QOS_PAIR]) == []


def test_pair_imbalance_result_mode_dropped_resolver_fires():
    src = """
        def f(batcher, x):
            batcher.try_submit(x)
    """
    findings = analyze(src, rules=["pair-imbalance"], pairs=[BATCHER_PAIR])
    assert rule_ids(findings) == ["pair-imbalance"]
    assert "drops its result" in findings[0].message


def test_pair_imbalance_result_mode_called_or_handed_satisfies():
    src = """
        def f(batcher, x):
            r = batcher.try_submit(x)
            if r is None:
                return None
            return r()

        def g(batcher, x):
            return batcher.try_submit(x)

        def h(batcher, x, sink):
            r = batcher.try_submit(x)
            if r is not None:
                sink.push(r)
    """
    assert analyze(src, rules=["pair-imbalance"], pairs=[BATCHER_PAIR]) == []


def test_pair_imbalance_result_mode_closure_capture_satisfies():
    # the hostec pool shape: futures are resolved by the returned
    # closure — the closure is the new owner
    spec = PairSpec(
        name="pool-submit", acquire="submit",
        release=("resolve", "shutdown_pool"), base_like=("pool",),
        mode="result", conditional=False, doc="pool shard",
    )
    src = """
        def f(pool, shards):
            futures = [pool.submit(run, s) for s in shards]

            def resolve():
                out = []
                for fu in futures:
                    out.extend(fu.result())
                return out

            return resolve
    """
    assert analyze(src, rules=["pair-imbalance"], pairs=[spec]) == []
    # ...and a declared teardown leaf discharges the failure edge
    src2 = """
        def f(pool, shards):
            futures = [pool.submit(run, s) for s in shards]
            try:
                return [fu.result() for fu in futures]
            except Exception:
                shutdown_pool(broken=True)
                return None
    """
    assert analyze(src2, rules=["pair-imbalance"], pairs=[spec]) == []


def test_pair_imbalance_cooldown_verdict_fires_and_records_satisfy():
    leaky = """
        def f(gate):
            if gate.ready():
                rebuild()
    """
    findings = analyze(leaky, rules=["pair-imbalance"], pairs=[GATE_PAIR])
    assert rule_ids(findings) == ["pair-imbalance"]
    clean = """
        def f(gate):
            if gate.ready():
                try:
                    rebuild()
                    gate.record_success()
                except Exception:
                    gate.record_failure()
    """
    assert analyze(clean, rules=["pair-imbalance"], pairs=[GATE_PAIR]) == []


def test_pair_imbalance_module_global_base_released_elsewhere_in_file():
    # the hostec _POOL_GATE shape: the gate is module-owned; ready() in
    # one function, the verdict recorded by the rebuild/teardown helpers
    src = """
        _GATE = make_gate()

        def get_pool():
            if _GATE.ready():
                return build()
            return None

        def teardown(broken):
            if broken:
                _GATE.record_failure()
            else:
                _GATE.record_success()
    """
    spec = PairSpec(
        name="cooldown-verdict", acquire="ready",
        release=("record_failure", "record_success"),
        base_like=("gate",), mode="base", conditional=True, doc="gate",
    )
    assert analyze(src, rules=["pair-imbalance"], pairs=[spec]) == []


# ---------------------------------------------------------------------------
# pairs.toml
# ---------------------------------------------------------------------------


def test_pairs_toml_packaged_table_parses_and_names_the_contracts():
    specs = fablife.load_default_pairs()
    by_name = {s.name: s for s in specs}
    assert {"qos-lane", "pool-submit", "batcher-admit",
            "cooldown-verdict"} <= set(by_name)
    assert by_name["qos-lane"].release == ("release",)
    assert by_name["qos-lane"].conditional
    assert by_name["pool-submit"].mode == "result"


@pytest.mark.parametrize(
    "text,err",
    [
        ('[[pair]]\nname = "x"\nacquire = "a"\nmode = "base"\n',
         "missing required key"),
        ('[[pair]]\nname = "x"\nacquire = "a"\nrelease = ["r"]\n'
         'mode = "sideways"\n', "mode must be"),
        ('[[pair]]\nname = "x"\nacquire = "a"\nrelease = []\n'
         'mode = "base"\n', "at least one release"),
        ('name = "orphan"\n', "outside a \\[\\[pair\\]\\]"),
        ('[pairs]\n', "unknown section"),
        ('[[pair]]\nname = "x"\nacquire = "a"\nrelease = [r]\n'
         'mode = "base"\n', "quoted"),
        ('[[pair]]\nname = "x"\nacquire = "a"\nrelease = ["r"]\n'
         'mode = "base"\n[[pair]]\nname = "x"\nacquire = "b"\n'
         'release = ["r"]\nmode = "base"\n', "duplicate pair name"),
    ],
)
def test_pairs_toml_parse_errors_are_loud(text, err):
    with pytest.raises(ValueError, match=err):
        parse_pairs(text)


def test_cli_rejects_bad_pair_table(tmp_path, capsys):
    bad = tmp_path / "pairs.toml"
    bad.write_text('[[pair]]\nmode = "sideways"\n')
    target = tmp_path / "m.py"
    target.write_text("x = 1\n")
    rc = fablife.main(["--pairs", str(bad), str(target)])
    assert rc == 2
    assert "pair table" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# wire-unclamped
# ---------------------------------------------------------------------------

# the pre-PR-8 shape: a u32 off the wire slept verbatim — a
# server-controlled unbounded client stall
RETRY_PRE_PR8 = """
    import time

    def wait_for_capacity(sock):
        status, retry_ms, mask, msg = decode_reply(sock)
        time.sleep(retry_ms / 1000.0)
"""

# the post-PR-8 shape: clamp to the client's own policy cap first
RETRY_FIXED = """
    import time

    def wait_for_capacity(sock, cap_s):
        status, retry_ms, mask, msg = decode_reply(sock)
        hint_s = min(retry_ms / 1000.0, cap_s)
        time.sleep(hint_s)
"""


def test_wire_unclamped_fires_on_pre_pr8_retry_after_ms_sleep():
    findings = analyze(RETRY_PRE_PR8, rules=["wire-unclamped"])
    assert rule_ids(findings) == ["wire-unclamped"]
    assert "retry_after_ms" in findings[0].message


def test_wire_unclamped_negative_control_is_the_clamped_shape():
    assert analyze(RETRY_FIXED, rules=["wire-unclamped"]) == []


def test_wire_unclamped_covers_reader_ints_into_sinks():
    src = """
        import collections
        import struct

        def handle(r, sock, buf):
            n = r.u32()
            q = collections.deque(maxlen=n)
            b = bytearray(r.u16())
            (count,) = struct.unpack(">I", buf)
            sock.settimeout(1.0)
            poll(timeout=count)
    """
    findings = analyze(src, rules=["wire-unclamped"])
    assert rule_ids(findings) == ["wire-unclamped"] * 3
    assert {"maxlen=" in f.message or "bytearray" in f.message
            or "timeout=" in f.message for f in findings} == {True}


def test_wire_unclamped_reassignment_and_min_untaint():
    src = """
        def handle(r):
            n = r.u32()
            n = min(n, 64)
            wait(n)
            m = r.u32()
            m = 8
            wait(m)
    """
    assert analyze(src, rules=["wire-unclamped"]) == []


def test_wire_unclamped_sequence_repeat_allocation_fires():
    src = """
        def handle(r):
            n = r.u32()
            pad = b"\\x00" * n
            return pad
    """
    findings = analyze(src, rules=["wire-unclamped"])
    assert rule_ids(findings) == ["wire-unclamped"]
    assert "sequence-repeat" in findings[0].message


# ---------------------------------------------------------------------------
# blocking-unbudgeted
# ---------------------------------------------------------------------------


def test_blocking_unbudgeted_fires_on_request_path_waits():
    src = """
        def pump(q, ev, t):
            item = q.get()
            ev.wait()
            t.join()
    """
    findings = analyze(src, path=SERVE, rules=["blocking-unbudgeted"])
    assert rule_ids(findings) == ["blocking-unbudgeted"] * 3


def test_blocking_unbudgeted_budgeted_and_non_queue_shapes_pass():
    src = """
        def pump(q, ev, t, d, parts):
            item = q.get(timeout=0.5)
            ev.wait(0.5)
            t.join(timeout=2.0)
            x = d.get("key")
            s = ", ".join(parts)
    """
    assert analyze(src, path=SERVE, rules=["blocking-unbudgeted"]) == []


def test_blocking_unbudgeted_block_true_without_timeout_fires():
    src = """
        def pump(q):
            return q.get(True)
    """
    findings = analyze(src, path=SERVE, rules=["blocking-unbudgeted"])
    assert rule_ids(findings) == ["blocking-unbudgeted"]


def test_blocking_unbudgeted_recv_needs_a_bounding_call():
    leaky = """
        def read(sock):
            return sock.recv(4096)
    """
    assert rule_ids(
        analyze(leaky, path=SERVE, rules=["blocking-unbudgeted"])
    ) == ["blocking-unbudgeted"]
    clean = """
        def read(sock, budget):
            sock.settimeout(budget)
            return sock.recv(4096)
    """
    assert analyze(clean, path=SERVE, rules=["blocking-unbudgeted"]) == []


def test_blocking_unbudgeted_scoped_to_request_paths():
    src = """
        def pump(q):
            return q.get()
    """
    assert analyze(
        src, path="fabric_tpu/ledger/m.py", rules=["blocking-unbudgeted"]
    ) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_absorbs_finding_and_is_counted():
    src = """
        import threading

        def spawn():
            threading.Thread(target=work).start()  # fablife: disable=thread-unjoined  # bounded helper: exits with work()
    """
    findings, n_supp = fablife.analyze_source(
        textwrap.dedent(src), PKG, ["thread-unjoined"], pairs=()
    )
    assert findings == []
    assert n_supp == 1


def test_suppression_disable_all_silences_the_line():
    src = """
        import threading

        def spawn():
            threading.Thread(target=work).start()  # fablife: disable=all  # fixture
    """
    findings, n_supp = fablife.analyze_source(
        textwrap.dedent(src), PKG, ["thread-unjoined"], pairs=()
    )
    assert findings == []
    assert n_supp == 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_json_and_exit_codes(tmp_path, capsys):
    bad = tmp_path / "fabric_tpu" / "m.py"
    bad.parent.mkdir()
    bad.write_text(
        "import threading\n\n"
        "def spawn():\n"
        "    threading.Thread(target=w).start()\n"
    )
    rc = fablife.main(["--json", str(bad)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["files"] == 1
    assert [f["rule"] for f in out["findings"]] == ["thread-unjoined"]

    clean = tmp_path / "fabric_tpu" / "ok.py"
    clean.write_text("x = 1\n")
    assert fablife.main([str(clean)]) == 0
    capsys.readouterr()

    assert fablife.main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in fablife.RULES:
        assert rid in listed

    assert fablife.main(["--rules", "no-such-rule", str(clean)]) == 2
    assert fablife.main([str(tmp_path / "missing.py")]) == 2
    assert fablife.main([]) == 2


def test_syntax_error_is_reported_not_raised():
    findings = analyze("def broken(:\n", rules=["fd-leak"])
    assert rule_ids(findings) == ["syntax-error"]


# ---------------------------------------------------------------------------
# toolkit registry + fabreg staleness protocol
# ---------------------------------------------------------------------------


def test_fablife_is_registered_with_the_toolkit():
    assert "fablife" in toolkit.ANALYZER_TOOLS
    spec = toolkit.analyzer_spec("fablife")
    assert spec is not None
    assert spec.module == "fabric_tpu.tools.fablife"
    assert spec.pkg_scope_only is False  # its gate scans tests/ too


def test_live_suppression_keys_reports_absorbing_comments():
    src = textwrap.dedent(
        """
        import threading

        def spawn():
            threading.Thread(target=w).start()  # fablife: disable=thread-unjoined  # bounded helper
        """
    )
    keys = fablife.live_suppression_keys({PKG: src}, {"thread-unjoined"})
    assert len(keys) == 1
    ((path, line, rule),) = keys
    assert rule == "thread-unjoined"
    assert path.endswith("fabric_tpu/m.py")


def test_fabreg_suppression_stale_judges_fablife_via_the_registry():
    live = textwrap.dedent(
        """
        import threading

        def spawn():
            threading.Thread(target=w).start()  # fablife: disable=thread-unjoined  # bounded helper
        """
    )
    stale = textwrap.dedent(
        """
        def quiet():
            x = 1  # fablife: disable=fd-leak  # outlived its cause
            return x
        """
    )
    findings, _stats = fabreg.analyze_sources(
        {"fabric_tpu/live.py": live, "fabric_tpu/stale.py": stale},
        rule_ids=["suppression-stale"],
    )
    assert rule_ids(findings) == ["suppression-stale"]
    assert findings[0].path == "fabric_tpu/stale.py"
    assert "fablife" in findings[0].message


# ---------------------------------------------------------------------------
# repo self-check: the CI gate invariant
# ---------------------------------------------------------------------------


def test_repo_has_zero_unsuppressed_findings():
    findings, stats = fablife.analyze_paths(
        [
            str(REPO_ROOT / "fabric_tpu"),
            str(REPO_ROOT / "tests"),
        ]
    )
    assert findings == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in findings
    )
    # the triaged by-design suppressions (NOTES_BUILD PR 15) are live
    assert stats["suppressed"] >= 1
