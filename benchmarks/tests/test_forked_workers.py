"""ForkedWorkers, from a fresh process with no thread running (as a run of
the benchmark forks them): order, kept results, a failing job."""

import subprocess
import sys
import textwrap

from benchmarks import generator as gen

SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    from benchmarks import generator as gen

    def boom(x):
        raise ValueError("boom %d" % x)

    w = gen.ForkedWorkers({{"square": lambda x: x * x, "boom": boom}}, 3)
    try:
        first = w.run("square", range(2))
        rest = w.start("square", range(2, 11))   # kept until collected
        big = w.run("square", [10 ** 6] * 5)
        assert first == [0, 1], first
        assert list(w.collect(rest)) == [i * i for i in range(2, 11)]
        assert big == [10 ** 12] * 5
        try:
            w.run("boom", [1, 2])
        except RuntimeError as exc:
            assert "boom" in str(exc)
        else:
            raise SystemExit("a failing job did not raise")
    finally:
        w.close()
    print("ok")
""")


def test_forked_workers_keep_order_and_report_failures():
    from benchmarks.tests.conftest import REPO_ROOT

    done = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(root=REPO_ROOT)],
        capture_output=True, text=True, timeout=60,
    )
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "ok"


def test_one_worker_runs_inline():
    w = gen.ForkedWorkers({"square": lambda x: x * x}, 1)
    batch = w.start("square", range(4))
    assert list(w.collect(batch)) == [0, 1, 4, 9]
    w.close()
