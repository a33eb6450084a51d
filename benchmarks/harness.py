"""What every driver shares: the seam checks and the compile counter copied
from ``chip_smoke.py`` (PR 22), the arithmetic of rates and tails, the
output check's book-keeping, the working directory, and the profiler
window.  Nothing here knows a configuration, a traffic mix or a metric by
name."""

from __future__ import annotations

import gc
import glob
import json
import math
import os
import shutil
import signal
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK_ROOT = os.path.join(REPO_ROOT, ".bench_work")  # in .gitignore


class SeamGaveWay(Exception):
    """A check of the harness failed: the run is not a measurement."""


def say(**fields) -> None:
    print(json.dumps(fields, sort_keys=True, default=str), flush=True)


# ---------------------------------------------------------------------------
# seam checks — each raises SeamGaveWay; benchmarks/tests shows each fires
# ---------------------------------------------------------------------------


def check_platform(devices: Sequence, chips: int) -> None:
    platform = devices[0].platform
    if platform != "tpu":
        raise SeamGaveWay(
            f"no TPU: jax.devices()[0].platform is {platform!r} "
            f"({len(devices)} device(s))"
        )
    if len(devices) < chips:
        raise SeamGaveWay(
            f"the cell asks for {chips} chip(s), JAX has {len(devices)}"
        )


def check_no_serve_env(environ) -> None:
    set_vars = [
        k for k in ("FABRIC_TPU_SERVE_ADDR", "FABRIC_TPU_SERVE_ENDPOINTS")
        if environ.get(k)
    ]
    if set_vars:
        raise SeamGaveWay(
            f"{set_vars} set: default_provider() would route to a sidecar, "
            "not to the chip"
        )


def check_default_provider(provider) -> None:
    name = type(provider).__name__
    if name != "TPUProvider":
        raise SeamGaveWay(
            f"default_provider() returned {name}, not TPUProvider "
            "(the device probe degraded to software)"
        )


def check_provider_seams(provider) -> None:
    """The in-process device provider served every batch on the device."""
    cls = type(provider)
    if getattr(cls, "degraded", None) is not False:
        raise SeamGaveWay(
            "TPUProvider.degraded is set: a dispatch failed and the batch "
            "was verified in software"
        )
    if getattr(cls, "_bytes_path_broken", None) is not False:
        raise SeamGaveWay(
            "TPUProvider._bytes_path_broken is set: the bytes kernel failed "
            "and the limb-matrix kernel served instead"
        )
    backend = provider.describe_backend()
    if backend != "tpu":
        raise SeamGaveWay(f"describe_backend() is {backend!r}, not 'tpu'")


def check_sidecar_client(client) -> None:
    if client.degraded is not False:
        raise SeamGaveWay(
            "SidecarProvider.degraded is set: a request was served "
            "in-process, not by the sidecar"
        )


def check_pipeline(pipe) -> None:
    if pipe.last_error is not None:
        raise SeamGaveWay(f"CommitPipeline.last_error: {pipe.last_error!r}")
    if pipe.dead:
        raise SeamGaveWay("CommitPipeline committer thread is dead")


def device_lanes(snapshot: Dict) -> int:
    """fabric_verify_lanes_total{rung="device"} out of a fabobs snapshot."""
    series = snapshot.get("fabric_verify_lanes_total", {}).get("series", {})
    return int(series.get("rung=device", 0))


def check_device_lanes(snapshot: Dict, sent: int) -> None:
    counted = device_lanes(snapshot)
    if counted != sent:
        raise SeamGaveWay(
            f"fabric_verify_lanes_total{{rung=\"device\"}} is {counted}, the "
            f"harness sent {sent} lanes to the device"
        )


def check_bucket(lanes: int, bucket: int, want: Optional[int]) -> None:
    if want is not None and bucket != want:
        raise SeamGaveWay(
            f"a {lanes}-lane batch lands in bucket {bucket}, not {want}: the "
            "window would launch a second program shape"
        )


def check_no_compiles(log: Dict) -> None:
    if log["xla_compiles"] or log["persistent_cache_hits"]:
        raise SeamGaveWay(
            f"a program was compiled or loaded inside the window: {log}"
        )


def check_sidecar_stats(stats: Dict, lanes: int, requests: int,
                        engine: str) -> None:
    served = stats["stats"]
    if not (
        stats["engine"] == engine
        and served["lanes"] == lanes
        and stats["batched_lanes"] == lanes
        and served["requests"] == requests
        and served["errors"] == 0
        and served["rejects"] == 0
    ):
        raise SeamGaveWay(
            f"OP_STATS does not show {requests} requests of {lanes} lanes on "
            f"engine {engine!r} with no error and no reject: {stats}"
        )


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------


class CompileLog:
    """Real XLA compiles and persistent-cache hits since the last mark
    (_CompileCounters of serve/registry.py), plus how long each
    compile-or-load of a second or more took (JAX times the two under
    one event: a cold program's compile, a cached program's load)."""

    def __init__(self):
        import jax

        from fabric_tpu.serve.registry import _CompileCounters

        self._counters = _CompileCounters
        _CompileCounters.install()
        self.durations: List[float] = []
        self._mark = (_CompileCounters.snapshot(), 0)

        def on_duration(event: str, duration: float, **kwargs) -> None:
            if "backend_compile" in event:
                self.durations.append(duration)  # GIL-atomic append

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def since_mark(self) -> Dict:
        (c0, h0), d0 = self._mark
        c1, h1 = self._counters.snapshot()
        self._mark = ((c1, h1), len(self.durations))
        return {
            "xla_compiles": c1 - c0,
            "persistent_cache_hits": h1 - h0,
            "compile_or_load_s_per_program": [
                round(d, 1) for d in self.durations[d0:] if d >= 1.0
            ],
        }


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest order statistics, of ALL the values given."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    low = math.floor(rank)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (rank - low)


def rate_in_window(done_at: Sequence[float], amounts: Sequence[float],
                   t0: float, seconds: float) -> float:
    """Work completed per second over the window that opens at t0 and closes
    at the first completion at or after t0 + seconds.  All the work and all
    the time between the two count, so a stall lowers the rate wherever it
    falls.  Closing on a completion, and not on the clock, keeps whole
    blocks from making the rate step: with 190 blocks of 500 tx in 30 s, one
    block more or less inside a clock-cut window is 0.5 % of the rate.
    Where nothing completes after t0 + seconds (the work ran out), the
    window closes on the clock."""
    t_end = t0 + seconds
    closes = min((at for at in done_at if at >= t_end), default=t_end)
    total = sum(
        amount for at, amount in zip(done_at, amounts) if t0 <= at <= closes
    )
    return total / (closes - t0)


def backlog_length(warmup: int, per_second: float, seconds: float) -> int:
    """How many blocks (requests) a driver builds: the warm-up's, and
    `per_second` (the traffic file's) for every second of the window.  The
    traffic file sizes that at 2 x the rate the program sustained when it was
    last read (its ``sustained_per_second``), so that a PR which speeds the
    path up has room before ``chain_exhausted`` fails its runs."""
    return warmup + math.ceil(seconds * float(per_second))


# ---------------------------------------------------------------------------
# the output check's book-keeping
# ---------------------------------------------------------------------------


class Checks:
    """Each number compared, beside its limit.  `correct` is: every number
    within its limit."""

    def __init__(self):
        self.rows: Dict[str, Dict[str, float]] = {}

    def add(self, name: str, value: float, limit: float = 0) -> None:
        self.rows[name] = {"value": value, "limit": limit}

    def seam(self, name: str, check: Callable[[], None]) -> None:
        """A seam check as a number: 0 when it holds, 1 (and the reason on
        standard error) when it gave way."""
        try:
            check()
        except SeamGaveWay as exc:
            print(f"benchmark: seam {name}: {exc}", file=sys.stderr, flush=True)
            self.add(name, 1)
        else:
            self.add(name, 0)

    @property
    def correct(self) -> bool:
        return all(r["value"] <= r["limit"] for r in self.rows.values())


# ---------------------------------------------------------------------------
# the working directory and the device
# ---------------------------------------------------------------------------


# A peer's ledger lives on a local disk; here it lives under WORK_ROOT, in the
# checkout, whatever that is mounted from (9p on the chip machine of PR 26-33,
# which has no disk: PERF.md section 2).  Never on a filesystem that lives in
# memory: there the block store's fsync, which the configuration guarantees
# before every commit callback, costs nothing.
MEMORY_FILESYSTEMS = frozenset({"tmpfs", "ramfs", "devtmpfs", "hugetlbfs"})


def check_fsync_costs(kind: str) -> None:
    if kind in MEMORY_FILESYSTEMS:
        raise SeamGaveWay(
            f"the ledger directory is on a {kind}: its fsync is free, and the "
            "configuration guarantees one before every commit callback"
        )


def fresh_workdir(workload: str) -> str:
    path = os.path.join(WORK_ROOT, workload)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def filesystem_type(path: str) -> str:
    """The type of the filesystem that holds `path`, from /proc/mounts (a
    tmpfs would make the ledger's fsync free)."""
    best, kind = "", "unknown"
    real = os.path.realpath(path)
    try:
        with open("/proc/mounts", encoding="utf-8") as fh:
            for line in fh:
                parts = line.split()
                if len(parts) < 3:
                    continue
                mount = parts[1]
                if (real == mount or real.startswith(mount.rstrip("/") + "/")) \
                        and len(mount) > len(best):
                    best, kind = mount, parts[2]
    except OSError:
        pass
    return kind


def compile_cache_dir() -> Optional[str]:
    import jax

    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# the first run of a checkout: compile in a child, measure in a process that
# loaded its programs from the cache, like every later run
# ---------------------------------------------------------------------------

# A process that has compiled the verify program itself commits blocks at
# 0.6 x the rate, for the rest of its life, of one that loaded it from the
# compile cache (PERF.md section 2), and the driver's sets hold each side's
# first run.  So where nothing says that this checkout's cache holds this
# cell's programs, a child makes a short run of the cell first and exits (one
# process at a time owns the chip: this one has not touched JAX yet), and its
# seconds are this run's set-up.
COMPILE_CHILD_SECONDS = 2.0
COMPILE_CHILD_TIMEOUT_S = 900.0


def cache_marker_path(workload: str, cell_data: Dict) -> str:
    """A file in the compile cache's directory (the program's own rule for
    the place: fabric_tpu/utils/jaxcache.py) whose name says which sources,
    which JAX and which cell's data a child compiled there."""
    import hashlib
    from importlib import metadata

    from fabric_tpu.utils import jaxcache

    digest = hashlib.sha256(
        json.dumps(cell_data, sort_keys=True).encode("utf-8")
    )
    for package in ("jax", "jaxlib", "libtpu"):
        try:
            digest.update(f"{package}={metadata.version(package)};".encode())
        except metadata.PackageNotFoundError:
            digest.update(f"{package}=none;".encode())
    program = os.path.join(REPO_ROOT, "fabric_tpu")
    for folder, folders, files in os.walk(program):
        folders.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                digest.update(os.path.relpath(path, program).encode("utf-8"))
                with open(path, "rb") as fh:
                    digest.update(fh.read())
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or jaxcache.CACHE_DIR
    return os.path.join(
        cache, f"bench_compiled.{workload}.{digest.hexdigest()[:16]}"
    )


def compile_in_a_child(workload: str, seed: int, cell_data: Dict,
                       run_py: str) -> None:
    """Unless a marker says it was done: a short run of the cell in a child
    process, which compiles whatever the cache lacks, then the marker.  A
    child that fails is reported and the run goes on; it then shows the
    fault itself."""
    import subprocess

    marker = cache_marker_path(workload, cell_data)
    if os.path.exists(marker):
        return
    t0 = time.perf_counter()
    cmd = [
        sys.executable, run_py, "--workload", workload, "--seed", str(seed),
        "--seconds", str(COMPILE_CHILD_SECONDS), "--trace", "0",
        "--compile-child",
    ]
    # its lines go to standard error: the last line of standard output is
    # this run's result and no other
    # (a session of its own, so that a child which hangs is ended with the
    # workers it forked)
    child = subprocess.Popen(cmd, cwd=REPO_ROOT, stdout=2, start_new_session=True)
    try:
        rc = child.wait(timeout=COMPILE_CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        if child.poll() is None:
            os.killpg(child.pid, signal.SIGKILL)
            child.wait()
    seconds = round(time.perf_counter() - t0, 1)
    if rc == 0:
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        with open(marker, "w", encoding="utf-8") as fh:
            json.dump({"workload": workload, "seconds": seconds}, fh)
    say(phase="compile_child", workload=workload, rc=rc, seconds=seconds,
        marker=marker)


def native_library() -> str:
    """Whether the program's C++ helpers (block parse, DER parse) are in use:
    without them the host path is the pure-Python one, and slower."""
    from fabric_tpu.utils import native

    return "in use" if native.available() else f"absent: {native.why_unavailable()}"


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the profiler window
# ---------------------------------------------------------------------------


# The TPU's trace buffer holds about six launches of the verify program (the
# looped CIOS makes ~650,000 op events per launch; PR 26's first trace dropped
# its buffers after 1.0 s).  So the profiler runs over a slice of the window,
# from its middle on, and not over all of it: the first tick after 0.6 s
# closes it, which at today's 158-175 ms a launch is 0.63-0.70 s, four
# launches.  Busy and idle time are read over the whole launch-to-launch
# cycles inside the slice (trace_reduce.whole_cycles), so they do not depend
# on this length or on where between two launches the slice begins.
TRACE_SLICE_SECONDS = 0.6
SLICE_ANNOTATION = "bench.trace_slice"


class TraceWindow:
    """``jax.profiler`` over a slice of the measured window, when --trace 1.
    The driver calls ``tick(now, t0, seconds)`` between requests; the slice
    starts at the window's middle and is closed by the first tick after
    TRACE_SLICE_SECONDS.  The Python tracer stays off (it would record every
    call of the host path); host TraceMe events stay on, so that the
    harness's own ``annotate()`` spans land on the trace's clock.  The slice
    itself is the span of the annotation SLICE_ANNOTATION."""

    def __init__(self, enabled: bool, workdir: str):
        self.enabled = enabled
        self.dir = os.path.join(workdir, "profile")
        self._started_at: Optional[float] = None
        self._slice = None
        self.done = False
        self.before_start: Optional[Callable[[], None]] = None

    def tick(self, now: float, t0: float, seconds: float) -> None:
        if not self.enabled or self.done:
            return
        if self._started_at is None:
            if now >= t0 + seconds / 2:
                self._start()
        elif now >= self._started_at + TRACE_SLICE_SECONDS:
            self.stop()

    def _start(self) -> None:
        import jax

        if self.before_start is not None:
            self.before_start()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._slice = jax.profiler.TraceAnnotation(SLICE_ANNOTATION)
        self._slice.__enter__()
        self._started_at = time.perf_counter()

    def stop(self) -> None:
        """Close the slice if it is open (the window's end closes it too)."""
        if self._started_at is None or self.done:
            return
        import jax

        self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True

    def xplane_path(self) -> Optional[str]:
        found = sorted(glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
        ))
        return found[-1] if found else None


class UndisturbedSpan:
    """A fabobs span from the window's opening to the moment the profiler
    starts (to the window's end in a run without one).  Starting and, above
    all, stopping the profiler holds the submitting thread for seconds, so
    the traced run reads its spans and its rate from this part alone: the
    first half of the window."""

    def __init__(self, name: str, tracer: TraceWindow):
        from fabric_tpu.common import fabobs

        self._span = fabobs.span(name)
        self._open = False
        tracer.before_start = self._close

    def __enter__(self) -> "UndisturbedSpan":
        self._span.__enter__()
        self._open = True
        return self

    def _close(self) -> None:
        if self._open:
            self._open = False
            self._span.__exit__(None, None, None)

    def __exit__(self, *exc) -> None:
        self._close()


def undisturbed_seconds(r: "Run") -> float:
    """The length of the window a traced run reads its rate over."""
    return r.seconds / 2 if r.trace else r.seconds


class GcLog:
    """Python's garbage collections inside the window: how many, how long,
    the longest.  Set-up leaves millions of long-lived objects behind (the
    backlog with its expected codes and state, what tracing and lowering the
    program kept), and every full collection scans them all: PR 26 read a
    240 ms pause every ~6 s in `peer-catchup`, four blocks late each time,
    which put the p90 on the edge of the late tenth.  ``settle()``, the last
    act of set-up, collects once and then freezes what is alive, so that the
    window's collections scan what the window allocates."""

    def __init__(self):
        self.pauses_ms: List[float] = []
        self._t0 = 0.0

    @staticmethod
    def settle() -> None:
        gc.collect()
        gc.freeze()

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses_ms.append((time.perf_counter() - self._t0) * 1e3)

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> Dict:
        return {
            "collections": len(self.pauses_ms),
            "total_ms": round(sum(self.pauses_ms), 1),
            "longest_ms": round(max(self.pauses_ms, default=0.0), 1),
        }


def slowest(latencies_ms: Sequence[float], count: int = 5) -> List[List[float]]:
    """[[position in the window, ms]] of the slowest requests: where in the
    window a stall fell."""
    ranked = sorted(enumerate(latencies_ms), key=lambda kv: -kv[1])[:count]
    return [[i, round(ms, 1)] for i, ms in ranked]


def annotate(name: str, enabled: bool):
    """A host span on the profiler's clock, or nothing when not tracing."""
    if not enabled:
        import contextlib

        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------

# What --rehearse-on-cpu changes: tiny blocks (the 256-lane bucket, the one
# program the sandbox's .jax_cache holds), and TPUProvider built directly,
# since default_provider() picks software where no accelerator answers.
REHEARSAL_BLOCK_TXS = 64


class Run:
    """What a driver is handed: the cell's data, the run's arguments, and
    the backend once ``start_backend()`` has been called (a driver forks its
    builders first)."""

    def __init__(self, workload: str, config: Dict, traffic: Dict, seed: int,
                 seconds: float, trace: bool, rehearse: bool,
                 t_process_start: float, chips: int = 1,
                 controls: Sequence[str] = (),
                 provider_factory: Optional[Callable] = None,
                 serve_engine: Optional[str] = None, series: bool = False):
        self.workload = workload
        self.config = dict(config)
        self.traffic = dict(traffic)
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.rehearse = rehearse
        self.t_process_start = t_process_start
        self.chips = chips
        self.controls = tuple(controls)
        # tests plant a provider (and a host sidecar engine) here; a run of
        # the benchmark never does
        self.provider_factory = provider_factory
        self.serve_engine = serve_engine or "device"
        self.on_chip = not rehearse and provider_factory is None
        # the lanes go through TPUProvider (on the chip, or in a rehearsal on
        # the CPU backend): the device seams can be checked
        self.device_path = provider_factory is None
        if rehearse:
            self.config["block_txs"] = REHEARSAL_BLOCK_TXS
        self.want_bucket = (
            int(self.config["lane_bucket"]) if self.on_chip else None
        )
        self.workdir = fresh_workdir(workload)
        self.devices: List = []
        self.obs = None
        self.compiles: Optional[CompileLog] = None
        self.tracer = TraceWindow(trace, self.workdir)
        self.marks: Dict[str, float] = {}
        # run.py --series PATH: the driver also reads the span ring after the
        # window and hands back every block's (request's) times
        self.series = series

    def mark(self, name: str) -> None:
        """Seconds since the process started, for the set-up line."""
        self.marks[name] = round(time.perf_counter() - self.t_process_start, 2)

    def start_backend(self) -> None:
        """Touch JAX: this process is the chip's one owner from here on."""
        # a failed dispatch must set `degraded` at once (and trip the seam
        # check) instead of sleeping 1+3+9 s through the retry ladder first
        os.environ["FABRIC_TPU_DISPATCH_RETRIES"] = "1"
        import jax

        self.devices = jax.devices()
        if self.on_chip:
            check_platform(self.devices, self.chips)
        from fabric_tpu.common import fabobs

        check_no_serve_env(os.environ)
        # the flight ring must hold every span of the window: a driver
        # fails the run where it wrapped
        self.obs = fabobs.enable(ring=SPAN_RING)
        self.compiles = CompileLog()

    def device_provider(self):
        """The provider the peer gets on this machine."""
        if self.provider_factory is not None:
            return self.provider_factory()
        if self.rehearse:
            from fabric_tpu.crypto.tpu_provider import TPUProvider

            provider = TPUProvider()
        else:
            from fabric_tpu.crypto.bccsp import default_provider

            provider = default_provider()
            check_default_provider(provider)
        check_provider_seams(provider)
        return provider

    def device_info(self) -> Dict:
        d = self.devices[0]
        return {
            "platform": d.platform, "kind": d.device_kind,
            "count": len(self.devices),
        }

    def cleanup(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


SPAN_RING = 1 << 18


def spans_in_window(obs, marker: str) -> List[Dict]:
    """The fabobs spans that started inside the span named `marker` (the
    driver's own span around the window).  Fails where the ring wrapped."""
    events = obs.trace_events()
    if len(events) >= SPAN_RING:
        raise SeamGaveWay(
            f"the fabobs flight ring wrapped ({len(events)} events): spans "
            "of the window were lost"
        )
    marks = [e for e in events if e["name"] == marker]
    if len(marks) != 1:
        raise SeamGaveWay(f"{len(marks)} spans named {marker!r}, not one")
    lo, hi = marks[0]["ts"], marks[0]["ts"] + marks[0]["dur"]
    return [
        e for e in events
        if e.get("ph") == "X" and e["name"] != marker and lo <= e["ts"] <= hi
    ]
