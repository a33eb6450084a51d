"""envreg — the central registry of every ``FABRIC_TPU_*`` environment
variable the system reads.

PRs 1–10 grew ~two dozen env knobs across the backend ladder, the
pools, the batcher, fault injection, observability and the serve plane
— each read at its consumer with a local default, none declared
anywhere a tool (or an operator) could enumerate.  This module is the
single declarative source of truth: one :class:`EnvVar` row per knob
carrying its name, value type, default, consuming module(s) and a
one-line doc.  The README env-var table is generated from
:func:`env_table`, and ``fabric_tpu.tools.fabreg`` closes the loop
statically both ways:

* ``env-undeclared`` — an ``os.environ``/``os.getenv`` read of a
  ``FABRIC_TPU_*`` name that has no row here is a gate failure, and
* ``env-dead`` — a row with no surviving reader anywhere in the tree
  (scripts and tests count, as deprecation grace) is too.

Dependency-free by design (stdlib ``dataclasses`` only): the tools
layer AST-parses this file rather than importing it, and runtime
consumers may import it without pulling numpy/jax/cryptography.

The shared read discipline (README "Design decisions"): malformed
values warn or silently fall back to the default — an env typo must
degrade a knob, never break an import or a verify path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple


@dataclass(frozen=True)
class EnvVar:
    """One declared environment knob.

    ``type`` is the value vocabulary (``bool`` means the consumer's
    truthy convention, usually ``"1"``; ``enum(...)`` lists the
    accepted tokens).  ``default`` is the *effective* behavior when the
    variable is unset, as a human-readable string.  ``consumer`` names
    the reading module(s) — the place to look for exact semantics."""

    name: str
    type: str
    default: str
    consumer: str
    doc: str


ENV_VARS: Tuple[EnvVar, ...] = (
    # -- backend ladder selection --------------------------------------
    EnvVar(
        "FABRIC_TPU_EC_BACKEND",
        "enum(fastec|hostec_np|hostec|p256|serve|auto)", "auto",
        "crypto/bccsp.py select_ec_backend",
        "pin the ECDSA batch-verify rung; auto walks the ladder "
        "fastec->hostec_np->hostec->p256 (unknown values warn, never "
        "raise)",
    ),
    EnvVar(
        "FABRIC_TPU_IDEMIX_BACKEND",
        "enum(hostbn|scheme|auto)", "auto",
        "crypto/bccsp.py select_idemix_backend",
        "pin the Idemix batch-verify rung; auto prefers hostbn when "
        "numpy is importable",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_ADDR", "addr", "(unset: in-process ladder)",
        "crypto/bccsp.py _default_provider_locked, serve/client.py, "
        "serve/server.py __main__",
        "resident-sidecar address (unix:/path or host:port); routes "
        "default_provider() through the warm sidecar, degrading to the "
        "in-process ladder when unreachable",
    ),
    EnvVar(
        "FABRIC_TPU_OPS_ADDR", "addr", "(unset: no ops server)",
        "serve/server.py __main__",
        "mount the operations/metrics HTTP server inside the sidecar "
        "process at this address",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_ENDPOINTS", "addr list",
        "(unset: single-sidecar or in-process)",
        "serve/router.py endpoints_from_env, crypto/bccsp.py "
        "_default_provider_locked",
        "comma-separated sidecar fleet addresses; routes "
        "default_provider() through the bucket-aware failover router "
        "(wins over FABRIC_TPU_SERVE_ADDR when both are set)",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_QOS", "map", "(unset: every channel normal)",
        "serve/qos.py qos_map_from_env (read by serve/client.py and "
        "serve/router.py)",
        "channel->admission-class map for protocol rev 2, e.g. "
        "'paychan=high;spam*=bulk;*=normal' (exact, prefix* and * "
        "patterns; malformed maps warn and resolve to the default "
        "class)",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_DRAIN_S", "float", "5",
        "serve/server.py main",
        "rolling-restart drain budget: how long SIGTERM/OP_DRAIN waits "
        "for in-flight verify requests to settle with real verdicts "
        "before the sidecar exits (malformed values fall back)",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_DEADLINE_MS", "int", "0 (no deadline)",
        "serve/client.py deadline_ms_from_env (read by SidecarProvider "
        "and serve/router.py SidecarRouter)",
        "per-batch wire latency budget (protocol rev 3): every per-hop "
        "wait — reply wait, busy-retry pacing, hedge polling — derives "
        "from the remaining budget, the server sheds provably-"
        "unfinishable work ST_BUSY, and an expired budget hands the "
        "batch to the in-process ladder (malformed values disable the "
        "knob)",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_HEDGE_FRACTION", "float", "0.05",
        "serve/router.py hedge_fraction_from_env",
        "global hedge budget: extra (hedged) requests as a fraction of "
        "primary requests, enforced by a count-based token bucket so "
        "hedging can never amplify an overloaded fleet into collapse "
        "(0 disables hedging; malformed values fall back)",
    ),
    EnvVar(
        "FABRIC_TPU_SERVE_HEDGE_MIN_MS", "float", "20",
        "serve/router.py hedge_min_ms_from_env",
        "floor on the per-endpoint hedge delay (the delay itself is "
        "2x the endpoint's observed p95, never a static knob): below "
        "this a hedge would race ordinary jitter, not a gray failure "
        "(malformed values fall back)",
    ),
    # -- host crypto pools ----------------------------------------------
    EnvVar(
        "FABRIC_TPU_HOSTEC_PROCS", "int", "min(cpu_count, cap)",
        "crypto/hostec.py pool_procs",
        "hostec process-pool worker count (1 disables the pool); "
        "malformed values fall back to the default",
    ),
    EnvVar(
        "FABRIC_TPU_HOSTEC_NP_PROCS", "int",
        "(falls back to FABRIC_TPU_HOSTEC_PROCS)",
        "crypto/hostec_np.py pool_procs",
        "hostec_np (numpy limb-matrix engine) pool worker count",
    ),
    EnvVar(
        "FABRIC_TPU_HOSTEC_NP_MIN_LANES", "int", "1024",
        "crypto/hostec_np.py verify_parsed_batch_sharded",
        "batches below this lane count delegate down-ladder to hostec's "
        "list engine (the matrix engine's fixed costs amortize above "
        "~1k lanes)",
    ),
    EnvVar(
        "FABRIC_TPU_HOSTEC_START", "enum(forkserver|spawn)", "forkserver",
        "crypto/hostec.py, crypto/hostec_np.py, idemix/batch.py",
        "multiprocessing start method for the crypto pools (fork is "
        "forbidden: live gRPC/XLA threads wedge forked workers)",
    ),
    EnvVar(
        "FABRIC_TPU_HOSTBN_PROCS", "int", "min(cpu_count, cap)",
        "idemix/batch.py pool_procs",
        "hostbn pairing-engine pool worker count (1 disables the pool)",
    ),
    EnvVar(
        "FABRIC_TPU_HOSTBN_MIN_POOL", "int", "64",
        "idemix/batch.py _verify_batch_hostbn",
        "Idemix batches below this size verify inline instead of "
        "round-tripping the process pool",
    ),
    EnvVar(
        "FABRIC_TPU_HOSTBN_MIN_SHARD", "int", "16",
        "idemix/batch.py _shard_plan",
        "never split a pooled Idemix batch into shards smaller than "
        "this",
    ),
    # -- batcher / dispatch ----------------------------------------------
    EnvVar(
        "FABRIC_TPU_BATCHER_MODE", "enum(auto|coalesce|passthrough)",
        "auto",
        "parallel/batcher.py VerifyBatcher",
        "force the transport mode; auto coalesces when the observed "
        "device RTT makes batching pay",
    ),
    EnvVar(
        "FABRIC_TPU_BATCHER_RTT_MS", "float", "25",
        "parallel/batcher.py VerifyBatcher",
        "assumed device round-trip ms before the EWMA has samples "
        "(auto-mode threshold seed)",
    ),
    EnvVar(
        "FABRIC_TPU_DISPATCH_RETRIES", "int", "3",
        "crypto/tpu_provider.py dispatch",
        "bounded retry attempts for a transient device-dispatch "
        "failure before degrading to the host ladder",
    ),
    # -- device probe -----------------------------------------------------
    EnvVar(
        "FABRIC_TPU_PROBE_TIMEOUT_S", "float", "60",
        "utils/deviceprobe.py probe_timeout_s",
        "hard wall-clock cap on the subprocess device probe (a hung "
        "PJRT plugin is killed, not waited on)",
    ),
    # -- fault injection (fabchaos) ---------------------------------------
    EnvVar(
        "FABRIC_TPU_FAULTS", "plan", "(unset: injection disabled)",
        "common/faults.py plan_from_env",
        "fault-injection plan: site=action[:prob][:param=int] entries "
        "joined by ';' (actions raise|delay|corrupt|drop); malformed "
        "plans warn and install nothing",
    ),
    EnvVar(
        "FABRIC_TPU_FAULTS_SEED", "int", "0",
        "common/faults.py plan_from_env",
        "seed for the deterministic per-site fault decision streams "
        "(same seed = same injections, regardless of thread "
        "interleaving)",
    ),
    EnvVar(
        "FABRIC_TPU_CRASH_SITES", "site[@block] list",
        "(unset: no kill points)",
        "common/faults.py _install_from_env",
        "fabcrash kill-point selector: 'site[@block]' entries joined by "
        "';' — sugar for site=kill:max=1[:at=block] fault specs; the "
        "process os._exit(137)s at the armed seam (the crash matrix's "
        "deterministic SIGKILL stand-in); malformed values warn and "
        "install nothing",
    ),
    EnvVar(
        "FABRIC_TPU_RECOVERY_STRICT", "enum(0|1)", "1",
        "ledger/blockstore.py recovery_strict (read by ledger/"
        "pvtdatastore.py and ledger/kvledger.py)",
        "crash-recovery strictness: 1 (default) refuses to open a store "
        "whose damage one interrupted append cannot explain (fail "
        "closed, loud log + refusal counter); 0 is operator-forced "
        "salvage — truncate to the last whole record / rebuild derived "
        "state from the chain, for forensics and manual repair",
    ),
    # -- observability (fabobs) -------------------------------------------
    EnvVar(
        "FABRIC_TPU_OBS", "bool", "(unset: disabled)",
        "common/fabobs.py _install_from_env",
        "enable the process-wide observability registry at import "
        "(PrometheusProvider + span flight ring); malformed values "
        "warn and install nothing",
    ),
    EnvVar(
        "FABRIC_TPU_OBS_RING", "int", "4096",
        "common/fabobs.py _install_from_env",
        "flight-recorder ring size (spans kept for /trace and trigger "
        "dumps)",
    ),
    EnvVar(
        "FABRIC_TPU_OBS_DUMP_DIR", "path", "(unset: no auto dumps)",
        "common/fabobs.py _install_from_env",
        "directory for automatic Chrome-trace dumps on degrade/"
        "fail-closed triggers (capped per process)",
    ),
    # -- test harness knobs ----------------------------------------------
    EnvVar(
        "FABRIC_TPU_CACHE_DEBUG", "enum(0|1)", "0",
        "tests/conftest.py",
        "log every XLA persistent-compilation-cache hit/miss/write "
        "with its key (the PR 8 tier-1 budget forensics switch)",
    ),
    EnvVar(
        "FABRIC_TPU_PAIRING_TESTS", "enum(0|1)", "(unset: tier-1 set)",
        "tests/test_pairing_kernel.py",
        "0 skips the pairing kernel tests entirely; 1 additionally "
        "enables the two deep-debug differentials (per-step Miller "
        "values, idemix batch e2e)",
    ),
)

ENV_BY_NAME: Dict[str, EnvVar] = {v.name: v for v in ENV_VARS}


def env_table() -> List[Dict[str, str]]:
    """The registry as rows (README table generation + gates), the
    same shape discipline as ``fabobs.metric_table``."""
    return [
        {
            "name": v.name,
            "type": v.type,
            "default": v.default,
            "consumer": v.consumer,
            "doc": v.doc,
        }
        for v in ENV_VARS
    ]
