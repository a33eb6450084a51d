"""A checkout's first run of a cell compiles in a child and measures in a
process that loaded its programs from the cache (harness.compile_in_a_child):
the child is started once, its failure stops nothing, and the marker follows
the program's sources and the cell's data."""

import os

import pytest

from benchmarks import harness as hs

CELL = {"config": {"block_txs": 500}, "traffic": {"driver": "commit_pipeline"}}


class Child:
    started = []

    def __init__(self, rc):
        self.rc = rc
        self.killed = False

    def __call__(self, cmd, **kwargs):
        Child.started.append((cmd, kwargs))
        return self

    def wait(self, timeout=None):
        if self.rc is None and not self.killed:  # it hangs until it is killed
            import subprocess

            raise subprocess.TimeoutExpired("run.py", timeout)
        return self.rc

    def poll(self):
        return self.rc if self.rc is not None or not self.killed else -9

    pid = 4242


@pytest.fixture
def cache(tmp_path, monkeypatch):
    Child.started = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return tmp_path / "cache"


def start(monkeypatch, rc):
    import subprocess

    child = Child(rc)
    monkeypatch.setattr(subprocess, "Popen", child)
    # a child that hangs is ended with its whole session
    monkeypatch.setattr(
        hs.os, "killpg", lambda pid, sig: setattr(child, "killed", pid == Child.pid)
    )
    hs.compile_in_a_child("peer-catchup", 2147493007, CELL, "benchmarks/run.py")


def test_the_first_run_starts_one_child_and_the_next_none(cache, monkeypatch, capsys):
    start(monkeypatch, 0)
    start(monkeypatch, 0)
    assert len(Child.started) == 1
    cmd, kwargs = Child.started[0]
    assert "--compile-child" in cmd and cmd[cmd.index("--seed") + 1] == "2147493007"
    assert cmd[cmd.index("--seconds") + 1] == str(hs.COMPILE_CHILD_SECONDS)
    # the child's lines never reach standard output: the result line is last
    assert kwargs["stdout"] == 2 and kwargs["start_new_session"] is True
    assert os.listdir(cache) == [
        os.path.basename(hs.cache_marker_path("peer-catchup", CELL))
    ]
    assert '"rc": 0' in capsys.readouterr().out


@pytest.mark.parametrize("rc", [1, 2, None])
def test_a_child_that_fails_leaves_no_marker_and_is_started_again(
        cache, monkeypatch, rc):
    start(monkeypatch, rc)
    assert not cache.exists() or os.listdir(cache) == []
    start(monkeypatch, 0)
    assert len(Child.started) == 2


def test_the_marker_follows_the_cell_and_its_data(cache):
    mine = hs.cache_marker_path("peer-catchup", CELL)
    assert mine == hs.cache_marker_path("peer-catchup", CELL)
    assert mine.startswith(str(cache))
    assert mine != hs.cache_marker_path("sidecar-1peer", CELL)
    other = dict(CELL, config={"block_txs": 10})
    assert mine != hs.cache_marker_path("peer-catchup", other)


def test_the_child_itself_and_a_rehearsal_start_no_child(monkeypatch):
    from benchmarks import run as bench_run

    def never(*args, **kwargs):
        raise AssertionError("a child was started")

    monkeypatch.setattr(hs, "compile_in_a_child", never)

    class Stop(Exception):
        pass

    def stop(*args, **kwargs):
        raise Stop()

    monkeypatch.setattr(hs, "Run", stop)
    for flag in ("--compile-child", "--rehearse-on-cpu"):
        with pytest.raises(Stop):
            bench_run.main(["--workload", "peer-catchup", "--seed", "1",
                            "--seconds", "1", "--trace", "0", flag])
