"""Bucketed program registry: warm AOT executables per lane-bucket shape.

The ROADMAP's "AOT-compiled bucketed program variants" piece: a resident
sidecar owns ONE registry per device program family, pre-warms an
executable for every bucket on the fixed ladder at startup, and serves
steady-state requests from the warm table — a request can pick a bucket
and dispatch without ever touching ``jax.jit`` again, so no re-trace and
no recompile can hide in the hot path.

Warm-start discipline (three rungs, best to worst):

1. **AOT artifact** (``jax.experimental.serialize_executable``): the
   compiled executable itself, pickled next to the compile cache.  A
   warm restart deserializes it — no trace, no XLA compile at all.
2. **Persistent compile cache** (``utils.jaxcache``): the trace is
   re-paid but the XLA compile is served from ``.jax_cache``.
3. **Cold**: trace + full XLA compile (the 20+-minute pairing
   differentials of NOTES_BUILD live here — exactly what a resident
   process amortizes away).

Every rung is accounted per bucket (``stats()``): compile wall ms,
whether the AOT artifact hit, how many XLA compile events fired — the
numbers the warm-restart test asserts on.

The registry is engine-generic: production wires the ECDSA limb kernel
(``ops.p256_kernel.verify_batch_device``); the CI-able ladder wires
:func:`demo_limb_program` (a real ``ops.bignum`` Montgomery
exponentiation — the same limb code path, a graph small enough to
compile in seconds on the 2-vCPU gate box).

jax is imported lazily and only inside methods — importing this module
costs nothing in jax-free processes (fablint module-import discipline).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from fabric_tpu.common import fabobs
from fabric_tpu.common.flogging import must_get_logger

logger = must_get_logger("serve.registry")

#: The default lane-bucket ladder — the ``tpu_provider._BUCKETS``
#: discipline (a request is padded up to the smallest bucket that fits,
#: so the jitted program's shape set is closed).
DEFAULT_BUCKETS = (128, 256, 512, 1024, 2048, 4096, 8192, 16384)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    """Smallest ladder bucket >= n; oversize rounds up to a multiple of
    the top bucket (the tpu_provider._bucket discipline)."""
    for b in buckets:
        if n <= b:
            return b
    top = buckets[-1]
    return ((n + top - 1) // top) * top


#: Monotonic per-process sequence for AOT-compile module names (see
#: :meth:`BucketProgramRegistry.for_jax_program`): uniqueness is what
#: guarantees the serialized artifact carries its own object code.
_AOT_SEQ = iter(range(1, 1 << 30))

#: The AOT-artifact compile flips the PROCESS-GLOBAL
#: ``jax_enable_compilation_cache`` flag around one compile.  Two
#: registries warming concurrently (serve_flap runs two sidecars in one
#: process) could interleave read-prev/set-False and restore a stale
#: ``False`` — permanently disabling the persistent cache for every
#: later compile in the process.  All flip/restore windows serialize
#: under this lock.
_AOT_COMPILE_LOCK = threading.Lock()


#: JAX durations shorter than this leave no ``program.*`` span: a process
#: traces hundreds of small jits (``jnp`` helpers), and set-up is minutes.
PROGRAM_SPAN_MIN_S = 0.1


class _CompileCounters:
    """Process-wide jax compile/cache-event accounting.

    One listener for the process (jax's listener list only grows);
    readers snapshot-and-diff.  JAX 0.9 times ``compile_or_get_cached``
    as a whole under ``backend_compile_duration``, so that event fires
    for a persistent-cache HIT too (with the retrieval time): a real
    XLA compile is a compile request that was not a hit.

    The same listener puts a process's set-up on the fabobs flight ring:
    each of JAX's own durations of :data:`PROGRAM_SPAN_MIN_S` or more
    becomes a span that ends now and began ``duration`` ago,
    ``program.trace_lower`` (``jaxpr_trace``, ``jaxpr_to_mlir_module``)
    or ``program.compile_or_load`` (``backend_compile``: a cold compile
    or a cache load).  A jit traced inside another's trace nests, so a
    reader takes the union of the spans, not their sum."""

    _lock = threading.Lock()
    _installed = False
    requests = 0  # compile_or_get_cached calls
    cache_hits = 0  # ... of which served from the persistent cache

    @classmethod
    def install(cls) -> None:
        with cls._lock:
            if cls._installed:
                return
            cls._installed = True
        import jax

        def _on_event(event: str, **kwargs) -> None:
            if "cache_hit" in event:
                cls.cache_hits += 1  # GIL-atomic int add, monotonic counter

        def _on_duration(event: str, duration: float, **kwargs) -> None:
            if "backend_compile" in event:
                cls.requests += 1  # GIL-atomic int add, monotonic counter
                name = "program.compile_or_load"
            elif "jaxpr_trace" in event or "jaxpr_to_mlir_module" in event:
                name = "program.trace_lower"
            else:
                return
            if duration >= PROGRAM_SPAN_MIN_S:
                now = time.perf_counter()
                fabobs.obs_record_span(
                    name, now - duration, now,
                    event=event.rsplit("/", 1)[-1],
                    fun=kwargs.get("fun_name"),
                )

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)

    @classmethod
    def snapshot(cls) -> Tuple[int, int]:
        """(real XLA compiles, persistent-cache hits) so far."""
        return cls.requests - cls.cache_hits, cls.cache_hits


class BucketProgramRegistry:
    """Warm table of compiled executables keyed by lane bucket.

    ``builder(bucket)`` returns ``(callable, meta)`` — the warm
    executable for that bucket plus accounting metadata.  The default
    jax builder path is :meth:`for_jax_program`; a host engine that has
    nothing to compile can still use the registry with a trivial builder
    so warm accounting stays uniform.
    """

    def __init__(
        self,
        buckets: Sequence[int],
        builder: Callable[[int], Tuple[Callable, Dict]],
        label: str = "program",
    ):
        if not buckets or list(buckets) != sorted(set(int(b) for b in buckets)):
            raise ValueError(f"bucket ladder must be sorted unique: {buckets!r}")
        self.buckets = tuple(int(b) for b in buckets)
        self.builder = builder
        self.label = label
        self._programs: Dict[int, Callable] = {}
        self._lock = threading.Lock()
        self.warm_report: Dict[int, Dict] = {}
        self.warmed = False

    def bucket_for(self, n: int) -> int:
        return bucket_for(n, self.buckets)

    def warm(self) -> Dict[int, Dict]:
        """Build every bucket's executable, recording per-bucket wall ms
        and the compile/cache counters the build moved.  Idempotent."""
        with self._lock:
            if self.warmed:
                return self.warm_report
            for b in self.buckets:
                c0, h0 = _CompileCounters.snapshot()
                t0 = time.perf_counter()
                program, meta = self.builder(b)
                wall_ms = (time.perf_counter() - t0) * 1000.0
                c1, h1 = _CompileCounters.snapshot()
                self._programs[b] = program
                report = {
                    "warm_ms": round(wall_ms, 1),
                    "xla_compiles": c1 - c0,
                    "cache_hits": h1 - h0,
                }
                report.update(meta)
                self.warm_report[b] = report
                logger.info(
                    "%s bucket %d warm in %.0fms (%s)",
                    self.label, b, wall_ms,
                    "aot" if meta.get("aot_hit") else
                    ("cache" if h1 > h0 else "cold"),
                )
            self.warmed = True
            return self.warm_report

    def program_for(self, n: int) -> Tuple[int, Callable]:
        """(bucket, warm executable) for an n-lane request.  Raises
        KeyError when the bucket was never warmed — steady state must
        not compile, so a missing bucket is a caller bug, not a trigger
        for a hidden jit."""
        b = self.bucket_for(n)
        with self._lock:
            program = self._programs.get(b)
        if program is None:
            raise KeyError(
                f"bucket {b} not warmed for {self.label} "
                f"(ladder {self.buckets})"
            )
        return b, program

    def stats(self) -> Dict:
        with self._lock:
            report = {str(k): dict(v) for k, v in self.warm_report.items()}
        compiles, hits = _CompileCounters.snapshot()
        return {
            "label": self.label,
            "buckets": list(self.buckets),
            "warmed": self.warmed,
            "per_bucket": report,
            "process_xla_compiles": compiles,
            "process_cache_hits": hits,
        }

    # -- jax builder -------------------------------------------------------
    @classmethod
    def for_jax_program(
        cls,
        fn: Callable,
        shapes_for: Callable[[int], Tuple],
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        label: str = "program",
        aot_dir: Optional[str] = None,
    ) -> "BucketProgramRegistry":
        """Registry whose buckets are AOT-compiled variants of ``fn``.

        ``shapes_for(bucket)`` returns the ``jax.ShapeDtypeStruct``
        argument tuple for that bucket.  With ``aot_dir`` set, compiled
        executables are serialized there and warm restarts load them
        back (no trace, no compile); without it, warm restarts still
        ride the persistent compile cache.  Trace-side accounting: the
        traced python body increments ``registry.traces`` — a steady
        state that re-traces (and would therefore recompile) is directly
        observable by tests.
        """
        import jax

        from fabric_tpu.utils.jaxcache import enable_compile_cache

        enable_compile_cache()
        _CompileCounters.install()

        counters = {"traces": 0}

        def traced(*args):
            counters["traces"] += 1  # GIL-atomic add; trace-time only
            return fn(*args)

        def fingerprint(bucket: int) -> str:
            raw = "|".join(
                (
                    label,
                    str(bucket),
                    jax.__version__,
                    jax.default_backend(),
                    str(shapes_for(bucket)),
                )
            )
            return hashlib.sha256(raw.encode()).hexdigest()[:16]

        def builder(bucket: int) -> Tuple[Callable, Dict]:
            meta: Dict = {"aot_hit": False}
            path = None
            if aot_dir:
                path = os.path.join(
                    aot_dir, f"{label}-{bucket}-{fingerprint(bucket)}.aot"
                )
                program = _load_aot(path)
                if program is not None:
                    meta["aot_hit"] = True
                    return program, meta
            t0 = time.perf_counter()
            if path is not None:
                # artifact creation must serialize a REAL, FRESH compile.
                # Two caches can silently hand back an executable whose
                # serialization is a partial blob that fails at load
                # ("Symbols not found"): the persistent compile cache
                # (an entry written by another process deserializes
                # without its object files), and the in-process client
                # layer (a module with the SAME name+content as one
                # already loaded — e.g. warmed earlier from the cache —
                # is deduplicated against it, even with the jax cache
                # disabled).  So exactly here the compile cache is
                # bypassed AND the traced wrapper gets a process-unique
                # name: the HLO module name follows the function name,
                # so nothing in the process can dedupe it.  The cold
                # path pays full price once; every restart loads the AOT.
                def aot_traced(*args):
                    counters["traces"] += 1  # GIL-atomic add; trace-time only
                    return fn(*args)

                aot_traced.__name__ = (
                    f"aot_{os.getpid()}_{next(_AOT_SEQ)}_b{bucket}"
                )
                with _AOT_COMPILE_LOCK:
                    prev = getattr(
                        jax.config, "jax_enable_compilation_cache", True
                    )
                    jax.config.update("jax_enable_compilation_cache", False)
                    try:
                        compiled = (
                            jax.jit(aot_traced)
                            .lower(*shapes_for(bucket))
                            .compile()
                        )
                    finally:
                        jax.config.update("jax_enable_compilation_cache", prev)
                _save_aot(path, compiled)
            else:
                compiled = jax.jit(traced).lower(*shapes_for(bucket)).compile()
            meta["compile_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
            return compiled, meta

        registry = cls(buckets, builder, label=label)
        registry.traces_counter = counters  # type: ignore[attr-defined]
        return registry

    @property
    def traces(self) -> int:
        """Trace count of the jax builder's python body (0 on pure-AOT
        warm starts).  Steady state must keep this flat."""
        counters = getattr(self, "traces_counter", None)
        return 0 if counters is None else counters["traces"]


def _load_aot(path: str) -> Optional[Callable]:
    """Deserialize an AOT artifact written by :func:`_save_aot`; None on
    any failure (missing, version-skewed, corrupt) — the registry then
    falls back to trace+compile, so a stale artifact can only cost time,
    never correctness.  The artifact directory is operator-owned cache
    state (same trust domain as ``.jax_cache`` itself)."""
    try:
        import jax
        from jax.experimental import serialize_executable as se

        with open(path, "rb") as fh:
            trees_len = int.from_bytes(fh.read(8), "big")
            in_tree, out_tree = pickle.loads(fh.read(trees_len))  # fabwire: disable=unbounded-wire-alloc  # operator-owned AOT cache in the same trust domain as .jax_cache: fh.read caps at file EOF and any short/garbled artifact falls into the recompile path below
            blob = fh.read()
        # the builder compiles for the default device (its shapes carry
        # no sharding); without this JAX loads the artifact for EVERY
        # device of the backend and the first call wants one shard each
        return se.deserialize_and_load(
            blob, in_tree, out_tree, execution_devices=jax.devices()[:1]
        )
    except FileNotFoundError:
        return None
    except Exception as exc:  # noqa: BLE001 - stale artifact: rebuild
        logger.warning("AOT artifact %s unusable (%s); recompiling", path, exc)
        return None


def _save_aot(path: str, compiled) -> None:
    try:
        from jax.experimental import serialize_executable as se

        blob, in_tree, out_tree = se.serialize(compiled)
        trees = pickle.dumps((in_tree, out_tree))
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as fh:
            fh.write(len(trees).to_bytes(8, "big"))
            fh.write(trees)
            fh.write(blob)
        os.replace(tmp, path)  # atomic: a killed writer leaves no torn file
    except Exception as exc:  # noqa: BLE001 - best-effort cache write
        logger.warning("AOT artifact %s not written (%s)", path, exc)


# ---------------------------------------------------------------------------
# The CI-able demo ladder: real ops.bignum limb math, small graph
# ---------------------------------------------------------------------------


def demo_limb_program():
    """(fn, shapes_for) for a small-but-real limb program: Montgomery
    exponentiation x^65537 mod P-256's p over a (NLIMBS, bucket) lane
    batch — the exact CIOS kernels the verify program is made of, in a
    graph that compiles in seconds on the CI box.  Used by the
    serve_gate smoke, the warm-restart test, and bench's cold-vs-warm
    compile column when no accelerator is reachable."""
    import jax
    import jax.numpy as jnp

    from fabric_tpu.common import p256
    from fabric_tpu.ops import bignum as bn

    ctx = bn.MontCtx(p256.P)

    def fn(x):
        xm = bn.to_mont(ctx, x)
        y = bn.mont_pow(ctx, xm, 65537)
        return bn.from_mont(ctx, y)

    def shapes_for(bucket: int):
        return (jax.ShapeDtypeStruct((bn.NLIMBS, bucket), jnp.uint32),)

    return fn, shapes_for


def verify_limb_program():
    """(fn, shapes_for) for the REAL device program: the batched ECDSA
    limb-matrix verify kernel.  Minutes of XLA compile cold (NOTES_BUILD)
    — which is the whole point of warming it once in a resident process
    and serializing the executable."""
    import jax
    import jax.numpy as jnp

    from fabric_tpu.ops import bignum as bn
    from fabric_tpu.ops.p256_kernel import verify_batch_device

    def shapes_for(bucket: int):
        limbs = jax.ShapeDtypeStruct((bn.NLIMBS, bucket), jnp.uint32)
        ok = jax.ShapeDtypeStruct((bucket,), jnp.bool_)
        return (limbs, limbs, limbs, limbs, limbs, ok)

    return verify_batch_device, shapes_for
