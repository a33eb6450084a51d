"""BCCSP-style pluggable crypto provider SPI.

Shaped after the reference provider interface (bccsp/bccsp.go:90-130:
KeyGen / KeyImport / Hash / Sign / Verify) with one TPU-native extension:
``batch_verify`` — the single-verify API is kept for drop-in compatibility
while batches are what the device kernels actually consume (SURVEY.md §7
Stage 1: the sidecar collects per-block batches under the hood).

Providers:
- SoftwareProvider: host-only, mirrors bccsp/sw (verifyECDSA:
  DER unmarshal -> low-S check -> ecdsa.Verify, bccsp/sw/ecdsa.go:41-57).
  Its curve math rides a four-tier backend ladder: fastec (OpenSSL via
  the cryptography package) -> hostec_np (numpy limb-matrix lanes with
  shared-memory shards) -> hostec (dependency-free vectorized pure
  Python, batches sharded across CPU cores) -> p256 (the clarity-first
  oracle; explicit selection only, never an automatic fallback).
  Select with BCCSP.SW.ECBackend config / FABRIC_TPU_EC_BACKEND /
  select_ec_backend(); introspect with ec_backend_name() and each
  provider's describe_backend().
- TPUProvider (fabric_tpu.crypto.tpu_provider): same decision function,
  ECDSA math executed as a batched JAX kernel.
"""

from __future__ import annotations

import hashlib
import threading
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import os

from fabric_tpu.common import fabobs
from fabric_tpu.common.faults import corrupt_verdicts, fault_point
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.common import der, p256
from fabric_tpu.crypto import hostec

logger = must_get_logger("bccsp")

# ---------------------------------------------------------------------------
# Host EC backend ladder: fastec (OpenSSL) -> hostec_np (numpy
# limb-matrix lanes) -> hostec (vectorized pure Python) -> p256
# (clarity-first oracle).  All tiers share one semantics contract (Go
# crypto/ecdsa.Verify decision, low-S pre-checked by callers via
# parse_and_precheck) and are differentially tested against each other.
# The oracle is never auto-selected — it exists for tests and explicit
# opt-in only.
# ---------------------------------------------------------------------------

EC_TIERS = ("fastec", "hostec_np", "hostec", "p256")


def _load_ec_backend(name: str):
    """Backend module by tier name; raises ImportError/ValueError."""
    if name == "fastec":
        from fabric_tpu.crypto import fastec

        return fastec
    if name == "hostec_np":
        from fabric_tpu.crypto import hostec_np

        if not hostec_np.HAVE_NUMPY:
            # the module itself imports fine without numpy (guarded
            # import), but the TIER is unavailable; callers decide what
            # that means (the auto walk logs the skip, an explicit pin
            # propagates this as a hard error)
            raise ImportError("hostec_np requires numpy")
        return hostec_np
    if name == "hostec":
        return hostec
    if name == "p256":
        return p256
    raise ValueError(
        f"unknown EC backend {name!r} (expected one of {EC_TIERS})"
    )


def available_ec_backends():
    """Tier name -> importable right now. hostec and p256 are pure Python
    and always available; fastec needs the ``cryptography`` package and
    hostec_np needs numpy."""
    out = {}
    for name in EC_TIERS:
        try:
            _load_ec_backend(name)
            out[name] = True
        except ImportError:
            out[name] = False
    return out


def select_ec_backend(name: str = "auto"):
    """Select the process-wide scalar/batch EC backend and return it.

    ``auto`` honors FABRIC_TPU_EC_BACKEND when it names a usable tier,
    else warns and walks the ladder fastec -> hostec_np -> hostec (the
    oracle is never an auto choice) — asking for ``auto`` NEVER raises,
    so a malformed env var cannot poison imports or a valid config.  An
    explicitly named unavailable tier raises ImportError so a configured
    expectation is never silently downgraded."""
    global _ec
    name = str(name or "auto").lower()
    if name != "auto":
        _ec = _load_ec_backend(name)
        return _ec
    env = os.environ.get("FABRIC_TPU_EC_BACKEND", "").lower()
    if env and env != "auto":
        try:
            _ec = _load_ec_backend(env)
            return _ec
        except (ImportError, ValueError) as exc:
            import warnings

            warnings.warn(
                f"FABRIC_TPU_EC_BACKEND: {exc}; using the "
                "fastec->hostec_np->hostec auto ladder",
                RuntimeWarning,
                stacklevel=2,
            )
    for tier in ("fastec", "hostec_np"):
        try:
            _ec = _load_ec_backend(tier)
            return _ec
        except ImportError:
            if tier == "hostec_np":
                # loudly-in-the-log, silently-for-callers: the numpy
                # rung is skipped only here, on the auto walk
                logger.warning(
                    "hostec_np tier skipped (numpy not installed); "
                    "walking down to hostec"
                )
            continue
    _ec = hostec
    return _ec


def ec_backend():
    """The active scalar-EC module: ``fastec`` (OpenSSL) when available,
    else the numpy ``hostec_np`` tier, else the vectorized pure-Python
    ``hostec`` tier; the ``p256`` oracle only on explicit selection.
    Exposed so callers (msp.signer, bench, the validator) share one
    seam and can report which backend actually ran."""
    return _ec


def ec_backend_name() -> str:
    """Short tier name of the active backend
    (``fastec``/``hostec_np``/``hostec``/``p256``)."""
    return _ec.__name__.rsplit(".", 1)[-1]


def ec_pool_ready() -> bool:
    """Health view of the active EC tier's process pool: False while a
    broken pool's rebuild cooldown is open (verifies still serve, but
    inline — degraded throughput an operator should see on /healthz).
    Tiers without a pool gate are trivially ready."""
    gate = getattr(_ec, "_POOL_GATE", None)
    if gate is None:
        return True
    try:
        return bool(gate.ready())
    except Exception as exc:  # noqa: BLE001 - health probe must not raise
        logger.debug("ec pool gate probe failed (%s); reporting ready", exc)
        return True


# Import-time init: select_ec_backend("auto") never raises (see above),
# so a bad env var can't fail every `import bccsp` and re-poison test
# collection wholesale.
_ec = select_ec_backend("auto")


# ---------------------------------------------------------------------------
# Idemix verify backend ladder: hostbn (numpy limb-matrix FP256BN
# pairing lanes, crypto/hostbn.py) -> scheme (the per-signature
# idemix/scheme.py oracle).  Same contract discipline as EC_TIERS: one
# accept/reject set across rungs (differentially tested), pins honored
# hard, the auto walk warns-never-raises.  The "scheme" rung is a
# SENTINEL (None): idemix/batch.py owns the oracle loop — the scheme
# module lives a layer above crypto and is never imported from here.
# ---------------------------------------------------------------------------

IDEMIX_TIERS = ("hostbn", "scheme")


def _load_idemix_backend(name: str):
    """Backend module by tier name (None for the scheme-oracle rung);
    raises ImportError/ValueError like _load_ec_backend."""
    if name == "hostbn":
        from fabric_tpu.crypto import hostbn

        if not hostbn.HAVE_NUMPY:
            raise ImportError("hostbn requires numpy")
        return hostbn
    if name == "scheme":
        return None
    raise ValueError(
        f"unknown idemix backend {name!r} (expected one of {IDEMIX_TIERS})"
    )


def available_idemix_backends():
    """Tier name -> usable right now (hostbn needs numpy; the scheme
    oracle is always available)."""
    out = {}
    for name in IDEMIX_TIERS:
        try:
            _load_idemix_backend(name)
            out[name] = True
        except ImportError:
            out[name] = False
    return out


def select_idemix_backend(name: str = "auto"):
    """Select the process-wide Idemix batch-verify rung and return its
    module (None = the scheme oracle).  ``auto`` honors
    FABRIC_TPU_IDEMIX_BACKEND when it names a usable tier, else warns
    and walks hostbn -> scheme — asking for ``auto`` NEVER raises.  An
    explicitly named unavailable tier raises ImportError so a
    configured expectation is never silently downgraded."""
    global _idemix, _idemix_name
    name = str(name or "auto").lower()
    if name != "auto":
        _idemix = _load_idemix_backend(name)
        _idemix_name = name
        return _idemix
    env = os.environ.get("FABRIC_TPU_IDEMIX_BACKEND", "").lower()
    if env and env != "auto":
        try:
            _idemix = _load_idemix_backend(env)
            _idemix_name = env
            return _idemix
        except (ImportError, ValueError) as exc:
            import warnings

            warnings.warn(
                f"FABRIC_TPU_IDEMIX_BACKEND: {exc}; using the "
                "hostbn->scheme auto ladder",
                RuntimeWarning,
                stacklevel=2,
            )
    try:
        _idemix = _load_idemix_backend("hostbn")
        _idemix_name = "hostbn"
    except ImportError:
        # loudly-in-the-log, silently-for-callers (EC ladder discipline)
        logger.warning(
            "hostbn idemix tier skipped (numpy not installed); "
            "falling back to the scheme oracle rung"
        )
        _idemix = None
        _idemix_name = "scheme"
    return _idemix


def idemix_backend():
    """The active Idemix batch rung module (crypto/hostbn), or None
    when the scheme-oracle rung is active."""
    return _idemix


def idemix_backend_name() -> str:
    """Short tier name of the active Idemix rung (``hostbn``/``scheme``)."""
    return _idemix_name


_idemix = None
_idemix_name = "scheme"
_idemix = select_idemix_backend("auto")


@dataclass(frozen=True)
class ECDSAPublicKey:
    """An imported P-256 public key (reference bccsp/sw/ecdsakey.go analog)."""

    x: int
    y: int

    @property
    def point(self) -> Tuple[int, int]:
        return (self.x, self.y)

    def ski(self) -> bytes:
        """Subject Key Identifier: SHA-256 of the uncompressed point, as the
        reference computes it (bccsp/sw/ecdsakey.go SKI)."""
        return hashlib.sha256(p256.pubkey_to_bytes(self.point)).digest()


@dataclass(frozen=True)
class ECDSAPrivateKey:
    d: int
    public: ECDSAPublicKey


class VerifyError(Exception):
    """Verification *errors* (vs. clean False) — mirrors the reference's
    (bool, error) split: malformed DER and high-S return an error, a failed
    curve equation check returns (false, nil)."""


class Provider:
    """SPI. Verify semantics contract (bccsp/sw/ecdsa.go verifyECDSA):

    - signature fails DER unmarshal or has non-positive R/S -> VerifyError
    - S > N/2 (not low-S)                                   -> VerifyError
    - otherwise                                             -> bool
    """

    def hash(self, msg: bytes) -> bytes:
        return hashlib.sha256(msg).digest()

    def batch_hash(self, msgs: Sequence[bytes]) -> List[bytes]:
        """One digest per message; implementations may batch (the native
        C++ SHA-256 below). Must equal [self.hash(m) for m in msgs]."""
        from fabric_tpu.utils.native import batch_sha256

        return [bytes(d) for d in batch_sha256(msgs)]

    def key_import(self, raw: bytes) -> ECDSAPublicKey:
        x, y = p256.pubkey_from_bytes(raw)
        return ECDSAPublicKey(x, y)

    def key_gen(self) -> ECDSAPrivateKey:
        kp = _ec.generate_keypair()
        return ECDSAPrivateKey(kp.priv, ECDSAPublicKey(*kp.pub))

    def sign(self, key: ECDSAPrivateKey, digest: bytes) -> bytes:
        r, s = _ec.sign_digest(key.d, digest)
        return der.marshal_signature(r, s)

    def verify(self, key: ECDSAPublicKey, signature: bytes, digest: bytes) -> bool:
        raise NotImplementedError

    def batch_verify(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ) -> List[bool]:
        """Batched verification; the host parse/low-S failures map to False
        (batch callers care about the boolean mask, not error strings).
        A lane with no key (identity or SEC1 import failed upstream) is
        a False lane on every tier, as on the hostec tiers and the serve
        wire (NO_KEY) — never an error that fails the batch's good lanes."""
        out = []
        for k, sig, d in zip(keys, signatures, digests, strict=True):
            if k is None:
                out.append(False)
                continue
            try:
                out.append(self.verify(k, sig, d))
            except VerifyError:
                out.append(False)
        return out

    def describe_backend(self) -> str:
        """Short runtime label of the execution path batches actually take
        (surfaced by the validator and bench so an oracle-tier fallback can
        never masquerade as a fast-tier number)."""
        return type(self).__name__


def parse_and_precheck(signature: bytes) -> Tuple[int, int]:
    """Host-side DER unmarshal + low-S gate shared by all providers.

    Raises VerifyError exactly where the reference returns an error.
    """
    try:
        r, s = der.unmarshal_signature(signature)
    except der.DerError as e:
        raise VerifyError(f"failed unmarshalling signature [{e}]") from e
    if not p256.is_low_s(s):
        raise VerifyError("invalid S, must be smaller than half the order")
    return r, s


class SoftwareProvider(Provider):
    """Host provider riding the active EC backend tier: DER parse + low-S
    gate in Python, then the curve math on OpenSSL (fastec, ~11k
    verifies/s/core) or the vectorized pure-Python hostec engine
    (~50-100x the oracle, batches sharded across CPU cores)."""

    def verify(self, key: ECDSAPublicKey, signature: bytes, digest: bytes) -> bool:
        r, s = parse_and_precheck(signature)
        return _ec.verify_digest(key.point, digest, r, s)

    def describe_backend(self) -> str:
        return f"sw:{ec_backend_name()}"

    def _parse_lanes(self, keys, signatures, digests):
        """(pub, digest, r, s) lanes for hostec's vectorized engine; parse
        and low-S failures become r = s = 0 (an always-False lane)."""
        lanes = []
        for k, sig, d in zip(keys, signatures, digests, strict=True):
            try:
                r, s = parse_and_precheck(sig)
            except VerifyError:
                r, s = 0, 0
            lanes.append((k.point if k is not None else None, d, r, s))
        return lanes

    @staticmethod
    def _chaos_verdicts(out: List[bool]) -> List[bool]:
        """``bccsp.verdict`` corrupt seam: only an installed fault plan
        can reach the flip — it exists so the fabchaos oracle gate can
        prove its bit-exact mask assertion CATCHES a corrupted mask
        (corrupt_detect scenario), the empirical twin of the fabflow
        fail-closed proof."""
        spec = fault_point("bccsp.verdict", interprets=("corrupt",))
        if spec is not None and spec.action == "corrupt":
            return corrupt_verdicts(out, spec)
        return out

    def batch_verify(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ) -> List[bool]:
        # unkeyed: batch sizes are static in steady state, so a content
        # key would turn a probabilistic plan into all-or-nothing
        fault_point("bccsp.dispatch")
        rung = ec_backend_name()
        t0 = time.perf_counter()
        with fabobs.span("bccsp.batch_verify", rung=rung, lanes=len(keys)):
            sharded = getattr(_ec, "verify_parsed_batch_sharded", None)
            if sharded is None:
                out = super().batch_verify(keys, signatures, digests)
            else:
                out = sharded(self._parse_lanes(keys, signatures, digests))()
        fabobs.obs_count("fabric_verify_lanes_total", len(keys), rung=rung)
        fabobs.obs_observe(
            "fabric_verify_seconds", time.perf_counter() - t0, rung=rung
        )
        return self._chaos_verdicts(list(out))

    def batch_verify_async(self, keys, signatures, digests):
        """Resolver-style dispatch (the VerifyBatcher/validator seam): on
        the hostec/hostec_np tiers the batch is sharded across the
        process pool and the resolver joins the shards
        (order-preserving), overlapping any host work the caller does
        before resolving.  Other tiers compute synchronously and hand
        back a trivial resolver."""
        fault_point("bccsp.dispatch")
        rung = ec_backend_name()
        t0 = time.perf_counter()
        sharded = getattr(_ec, "verify_parsed_batch_sharded", None)
        if sharded is None:
            out = Provider.batch_verify(self, keys, signatures, digests)
            inner = lambda v=out: v  # noqa: E731
        else:
            inner = sharded(self._parse_lanes(keys, signatures, digests))
        n = len(keys)

        def resolve() -> List[bool]:
            # latency spans dispatch -> resolve: the window a caller
            # actually waits on this rung, pool shards included
            verdicts = self._chaos_verdicts(list(inner()))
            fabobs.obs_count("fabric_verify_lanes_total", n, rung=rung)
            fabobs.obs_observe(
                "fabric_verify_seconds", time.perf_counter() - t0, rung=rung
            )
            return verdicts

        return resolve


class PurePythonProvider(SoftwareProvider):
    """The clarity-first big-int oracle (~5 verifies/s).  Differential tests
    ONLY — never a benchmark baseline or a default path.  Pins the p256
    module regardless of the active backend tier (it IS the oracle the
    other tiers are tested against)."""

    def verify(self, key: ECDSAPublicKey, signature: bytes, digest: bytes) -> bool:
        r, s = parse_and_precheck(signature)
        return p256.verify_digest(key.point, digest, r, s)

    def describe_backend(self) -> str:
        return "sw:p256"

    def batch_verify(self, keys, signatures, digests) -> List[bool]:
        return Provider.batch_verify(self, keys, signatures, digests)

    def batch_verify_async(self, keys, signatures, digests):
        out = Provider.batch_verify(self, keys, signatures, digests)
        return lambda: out

    def sign(self, key: ECDSAPrivateKey, digest: bytes) -> bytes:
        r, s = p256.sign_digest(key.d, digest)
        return der.marshal_signature(r, s)

    def key_gen(self) -> ECDSAPrivateKey:
        kp = p256.generate_keypair()
        return ECDSAPrivateKey(kp.priv, ECDSAPublicKey(*kp.pub))


_default: Optional[Provider] = None
# two channels starting concurrently (one Channel.__init__ per deliver
# thread) must not both construct a provider: a TPUProvider holds the
# device executor, and the loser's instance would keep compiling kernels
# nothing ever reads
_default_lock = threading.Lock()


def default_provider() -> Provider:
    """Factory (reference bccsp/factory analog): the TPU provider if an
    actual accelerator device is present, else the software provider.
    (A CPU-only jax install must NOT route single verifies through the
    XLA kernel — its compile cost alone is minutes.)"""
    with _default_lock:
        return _default_provider_locked()


def _default_provider_locked() -> Provider:
    global _default
    if _default is None:
        # fleet routing first: several sidecars behind the peer-side
        # failover router beat one (FABRIC_TPU_SERVE_ENDPOINTS wins
        # over FABRIC_TPU_SERVE_ADDR when both are set — the single
        # address is the degenerate one-endpoint fleet)
        endpoints = os.environ.get("FABRIC_TPU_SERVE_ENDPOINTS", "")
        addr = os.environ.get("FABRIC_TPU_SERVE_ADDR", "")
        if endpoints or addr:
            # resident-sidecar routing (fabric_tpu.serve): every default
            # consumer (peer channels, the chaos harness) transparently
            # sends its batches to the warm sidecar.  The rung builds
            # WITHOUT contacting the sidecar (a peer may start before
            # its sidecar; batch_verify re-dials behind a failure
            # cooldown, so a late-arriving sidecar is picked up) and
            # degrades through
            # probe_provider() — an accelerator node with a stale env
            # var keeps its device, never silently pins the SW rung
            try:
                from fabric_tpu.crypto.factory import provider_from_config

                serve_cfg: dict = {"Address": addr}
                if endpoints:
                    serve_cfg["Endpoints"] = [
                        a.strip() for a in endpoints.split(",") if a.strip()
                    ]
                _default = provider_from_config(
                    {"Default": "SERVE", "SERVE": serve_cfg}
                )
                return _default
            except Exception as exc:  # noqa: BLE001 - env routing best-effort
                logger.warning(
                    "serve routing (%s) unusable (%s); using the "
                    "in-process provider ladder", endpoints or addr, exc,
                )
        _default = probe_provider()
    return _default


def probe_provider() -> Provider:
    """The device-probe ladder, independent of any sidecar routing: the
    TPU provider if an accelerator answers the bounded probe, else the
    software provider.  Also the sidecar client's degrade target, so an
    accelerator-attached node that loses its sidecar falls back to the
    device, not to a hardcoded SW rung."""
    try:
        # BOUNDED probe: a backend init that hangs would hang a naive
        # jax.devices() call with it — a node start must degrade to
        # the software provider instead
        from fabric_tpu.utils.deviceprobe import accelerator_present

        if accelerator_present():
            from fabric_tpu.crypto.tpu_provider import TPUProvider

            return TPUProvider()
        return SoftwareProvider()
    except Exception as exc:  # noqa: BLE001 - probe flake: SW serves
        logger.warning(
            "device probe failed (%s); using the software provider", exc
        )
        fabobs.obs_count("fabric_degrade_total", seam="bccsp.probe")
        return SoftwareProvider()
