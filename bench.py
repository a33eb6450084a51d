#!/usr/bin/env python
"""Benchmarks for every BASELINE.md config, printed as ONE JSON line.

Headline metric (BASELINE config #1): batched ECDSA-P256 verification
throughput on the accelerator vs the single-core OpenSSL software path.
The `detail.configs` object carries the measured numbers for configs
#2-#5:

  block_1k   — 1k-tx 2-of-3 endorsement block through the full
               BlockValidator: TPU provider vs SW provider ms/block,
               bit-exact TRANSACTIONS_FILTER asserted (config #2).  The
               SW column is the OpenSSL-backed provider (the reference
               SW BCCSP's speed class), NOT the pure-Python oracle.
  idemix     — batched Idemix verify: the hostbn->scheme backend
               ladder per-rung ms/sig at batch 8/64/256, plus the
               device Ate2 pairing column, all vs the scheme oracle
               (config #3).
  mvcc_5k    — 5k-tx MVCC validate-and-prepare, ms/block (config #4).
  multi_4ch  — 4 channels x 2k-tx blocks in one channel-axis device
               step, aggregate tx/s (config #5; sharding across chips is
               validated on the virtual CPU mesh by dryrun_multichip —
               the bench machine has one chip).
  batcher_4ch_small — P7 coalescing: concurrent SMALL blocks across
               channels, direct per-channel launches vs the shared
               VerifyBatcher (launches + lanes/launch reported).

Output discipline (a failed first device dispatch must not cost the
CPU columns):
- the CPU columns are measured FIRST and a complete JSON line is emitted
  before the device is touched at all;
- the device is reached only through the bounded probe
  (utils/deviceprobe) — a backend that cannot initialise records
  device="unavailable" plus
  an error field and every config still reports its CPU column;
- device dispatches retry with backoff and degrade to the software path
  inside TPUProvider (degraded runs are labeled, never mistaken for
  device numbers);
- a watchdog thread re-emits the latest line and exits 0 if anything
  hangs past BENCH_BUDGET_S + BENCH_WATCHDOG_GRACE_S;
- the line is re-emitted after every config completes or fails, so a
  driver that kills the process mid-run still captures the latest
  complete line.  The last line is the most complete.
BENCH_BUDGET_S (default 1500) is the wall-clock budget: configs that
would start after the deadline are recorded as skipped.  Heavy configs
can be skipped entirely with BENCH_HEADLINE_ONLY=1.
"""

import json
import os
import sys
import time

os.environ.setdefault("FABRIC_TPU_CIOS_UNROLL", "1")

import numpy as np

from fabric_tpu.utils.jaxcache import enable_compile_cache

enable_compile_cache()


def gen_triples(n, num_keys=8):
    """(key, der_sig, digest) triples signed through the SW provider's
    ACTIVE EC backend (fastec when cryptography is installed, else the
    vectorized hostec tier), normalized to low-S like the reference
    signer.  Never the oracle: its ~5 signs/s would eat the budget."""
    import hashlib

    from fabric_tpu.crypto import der
    from fabric_tpu.crypto.bccsp import (
        ECDSAPublicKey,
        ec_backend,
        ec_backend_name,
    )

    ec = ec_backend()
    if ec_backend_name() == "p256":  # oracle pinned: sign via hostec
        from fabric_tpu.crypto import hostec as ec
    keys = [ec.generate_keypair() for _ in range(num_keys)]
    triples = []
    for i in range(n):
        kp = keys[i % num_keys]
        msg = f"benchmark tx payload {i}".encode() * 8
        digest = hashlib.sha256(msg).digest()
        r, s = ec.sign_digest(kp.priv, digest)
        triples.append(
            (ECDSAPublicKey(*kp.pub), der.marshal_signature(r, s), digest)
        )
    return triples


def bench_obs_overhead(triples, n_lanes=4096, passes=2):
    """The fabobs acceptance microbench: the 4096-lane host verify with
    the obs registry disabled vs enabled, best-of-``passes`` each,
    interleaved D E D E so background drift hits both modes equally.
    The disabled mode must cost <= 2% over the pre-instrumentation
    baseline — disabled obs is one module-global load per obs point, so
    the honest comparison here is disabled-vs-enabled on the SAME
    binary (recorded in NOTES_BUILD next to the pre-PR absolute)."""
    from fabric_tpu.common import fabobs
    from fabric_tpu.crypto.bccsp import SoftwareProvider, ec_backend_name

    lanes = triples[:n_lanes]
    keys = [t[0] for t in lanes]
    sigs = [t[1] for t in lanes]
    digests = [t[2] for t in lanes]
    sw = SoftwareProvider()
    prev = fabobs.active()
    times = {"disabled": [], "enabled": []}
    try:
        sw.batch_verify(keys[:256], sigs[:256], digests[:256])  # warm pools
        for _ in range(passes):
            for mode in ("disabled", "enabled"):
                if mode == "disabled":
                    fabobs.disable()
                else:
                    fabobs.enable()
                t0 = time.perf_counter()
                mask = sw.batch_verify(keys, sigs, digests)
                times[mode].append(time.perf_counter() - t0)
                if not all(mask):
                    raise RuntimeError("overhead bench lanes must verify")
    finally:
        with fabobs._OBS_LOCK:
            fabobs._OBS = prev
    dis, ena = min(times["disabled"]), min(times["enabled"])
    return {
        "backend": ec_backend_name(),
        "lanes": len(lanes),
        "passes": passes,
        "disabled_s": round(dis, 4),
        "enabled_s": round(ena, 4),
        "disabled_verifies_per_s": round(len(lanes) / dis, 1),
        "enabled_verifies_per_s": round(len(lanes) / ena, 1),
        "enabled_overhead_pct": round((ena - dis) / dis * 100.0, 2),
    }


def bench_cpu_baseline(triples, budget_s=2.0):
    """Single-core CPU column: the ACTUAL SoftwareProvider verify path
    (DER parse + low-S gate + OpenSSL curve math), i.e. the same code the
    validator runs when no accelerator is present — so detail.sw_ec_backend
    labels exactly what was measured."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    sw = SoftwareProvider()
    count = 0
    start = time.perf_counter()
    while time.perf_counter() - start < budget_s:
        pub, sig, digest = triples[count % len(triples)]
        if not sw.verify(pub, sig, digest):
            raise RuntimeError("benchmark signature should verify")
        count += 1
    return count / (time.perf_counter() - start)


# The bounded OUT-OF-PROCESS device probe lives in utils/deviceprobe
# (round-5 postmortem: the in-process daemon-thread probe timed out but
# left the thread wedged inside backend init, and the verdict was
# re-derived per call; the subprocess probe gets a HARD kernel-enforced
# timeout and a per-run cached verdict).  bench.py is its batch-entry
# consumer — the library path keeps the cheap in-process probe.


def bench_host_ladder(triples, budget_s=None):
    """hostec vs hostec_np verifies/s at 1k/4k/16k lanes — the host
    backend-ladder column the numpy tier is judged by.  Both engines
    run their production sharded entrypoints (process pools warm, one
    timed pass per size) on the SAME parsed batch; the 4096-lane ratio
    is the acceptance number."""
    from fabric_tpu.crypto import hostec
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    if budget_s is None:
        budget_s = float(os.environ.get("BENCH_LADDER_BUDGET_S", "150"))
    try:
        from fabric_tpu.crypto import hostec_np
    except Exception:  # pragma: no cover - broken partial install
        hostec_np = None
    have_np = hostec_np is not None and hostec_np.HAVE_NUMPY

    sw = SoftwareProvider()
    out = {"engines": ["hostec"] + (["hostec_np"] if have_np else [])}
    if not have_np:
        out["hostec_np"] = {"skipped": "numpy not installed"}
    else:
        hostec_np.warm_tables()  # one-time comb build out of the timing
    start = time.monotonic()
    sizes = [n for n in (1024, 4096, 16384) if n <= len(triples)]
    if not sizes:
        out["skipped"] = (
            f"BENCH_N={len(triples)} below the smallest ladder size"
        )
        return out
    # one DER parse of the largest batch; the smaller sizes are strict
    # prefixes of it
    sub = triples[: sizes[-1]]
    parsed = sw._parse_lanes(
        [t[0] for t in sub], [t[1] for t in sub], [t[2] for t in sub]
    )
    for lanes_n in sizes:
        out[str(lanes_n)] = {}
    engines = [("hostec", hostec)]
    if have_np:
        engines.append(("hostec_np", hostec_np))
    # engine-major: exactly ONE engine's process pool is alive at a
    # time (on a 2-vCPU box two pools' workers thrash each other), and
    # each engine pays its pool boot once, untimed.  The warm pass uses
    # the LARGEST size: hostec_np only touches its pool from
    # MIN_POOL_LANES lanes up, so a small warm batch would leave the
    # spawn cost inside the first big timed pass.
    for name, mod in engines:
        if time.monotonic() - start > budget_s:
            # don't pay an engine's warm pass (pool boot + a full
            # largest-size verify) when every timed pass would be
            # skipped anyway
            for lanes_n in sizes:
                out[str(lanes_n)][name] = "skipped: ladder budget exhausted"
            continue
        try:
            mod.verify_parsed_batch_sharded(parsed)()
            for lanes_n in sizes:
                if time.monotonic() - start > budget_s:
                    out[str(lanes_n)][name] = (
                        "skipped: ladder budget exhausted"
                    )
                    continue
                # best of two passes: this box's wall clock is noisy
                # enough (shared gVisor host) that one pass swings 1.5x
                best = None
                for _pass in range(2):
                    t0 = time.perf_counter()
                    verdicts = mod.verify_parsed_batch_sharded(
                        parsed[:lanes_n]
                    )()
                    dt = time.perf_counter() - t0
                    if not all(verdicts):
                        raise RuntimeError(
                            f"{name}: benchmark sig rejected"
                        )
                    best = dt if best is None else min(best, dt)
                    if time.monotonic() - start > budget_s:
                        break
                out[str(lanes_n)][name] = round(lanes_n / best, 1)
        finally:
            # a raise mid-pass must not leave this engine's workers
            # alive to compete with every later bench config
            mod.shutdown_pool()
    for lanes_n in sizes:
        row = out[str(lanes_n)]
        if (
            have_np
            and isinstance(row.get("hostec"), float)
            and isinstance(row.get("hostec_np"), float)
        ):
            row["np_speedup"] = round(row["hostec_np"] / row["hostec"], 2)
    r4096 = out.get("4096", {})
    if isinstance(r4096, dict) and "np_speedup" in r4096:
        out["acceptance_ratio_4096"] = r4096["np_speedup"]
    return out


def bench_headline_device(triples, iters):
    """Device half of config #1. Returns (device_rate, degraded) — the
    caller already owns the CPU column. Any raise is caught by main()
    and recorded as an error field, never rc=1 (round-4 postmortem)."""
    from fabric_tpu.crypto.tpu_provider import TPUProvider

    n = len(triples)
    keys = [t[0] for t in triples]
    sigs = [t[1] for t in triples]
    digests = [t[2] for t in triples]

    prov = TPUProvider()
    out = prov.batch_verify(keys, sigs, digests)
    if not all(out):
        raise RuntimeError("verification failed in warmup — kernel bug")
    if TPUProvider.degraded:
        # the warmup batch was actually served by the software fallback:
        # there is no device column to measure
        return 0.0, True

    # depth-3 software pipeline (the peer's P4 discipline, one deeper):
    # keep up to two launches in flight so the per-launch latency
    # hides behind device compute of the neighbours
    from collections import deque

    in_flight = max(int(os.environ.get("BENCH_DEPTH", "3")) - 1, 0)

    def timed_pass() -> float:
        start = time.perf_counter()
        pending: "deque" = deque()
        for _ in range(iters):
            pending.append(prov.batch_verify_async(keys, sigs, digests))
            while len(pending) > in_flight:
                if not all(pending.popleft()()):
                    raise RuntimeError("verification failed mid-bench")
        while pending:
            if not all(pending.popleft()()):
                raise RuntimeError("verification failed mid-bench")
        return n * iters / (time.perf_counter() - start)

    # best of three passes: a transient stall mid-pass would
    # misreport the kernel
    device_rate = max(timed_pass() for _ in range(3))
    return device_rate, TPUProvider.degraded


# ----------------------------------------------------------------------
# shared network fixture for configs #2 and #5
# ----------------------------------------------------------------------


class _Net:
    def __init__(self):
        from fabric_tpu.crypto.bccsp import SoftwareProvider
        from fabric_tpu.msp.cryptogen import generate_org
        from fabric_tpu.msp.identity import MSPManager
        from fabric_tpu.msp.signer import SigningIdentity
        from fabric_tpu.policy import from_dsl
        from fabric_tpu.validation.validator import (
            ChaincodeDefinition,
            ChaincodeRegistry,
        )

        self.sw = SoftwareProvider()
        org1 = generate_org("org1.bench", "Org1MSP")
        org2 = generate_org("org2.bench", "Org2MSP")
        org3 = generate_org("org3.bench", "Org3MSP")
        self.mgr = MSPManager(
            [o.msp(provider=self.sw) for o in (org1, org2, org3)]
        )
        # 2-of-3 endorsement policy (BASELINE config #2)
        self.registry = ChaincodeRegistry(
            [
                ChaincodeDefinition(
                    "benchcc",
                    from_dsl(
                        "OutOf(2,'Org1MSP.member','Org2MSP.member',"
                        "'Org3MSP.member')"
                    ),
                )
            ]
        )
        self.client = SigningIdentity(org1.users[0], self.sw)
        self.endorsers = [
            SigningIdentity(o.peers[0], self.sw) for o in (org1, org2)
        ]

    def make_block(self, channel, n_txs, number=1):
        from fabric_tpu.endorser import (
            create_proposal,
            create_signed_tx,
            endorse_proposal,
        )
        from fabric_tpu.ledger import rwset as rw
        from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset
        from fabric_tpu.protos import protoutil

        block = protoutil.new_block(number, b"\x33" * 32)
        for i in range(n_txs):
            results = serialize_tx_rwset(
                rw.TxRwSet(
                    (
                        rw.NsRwSet(
                            "benchcc",
                            (),
                            (rw.KVWrite(f"k{i}", False, b"v"),),
                        ),
                    )
                )
            )
            bundle = create_proposal(
                self.client, channel, "benchcc", [b"invoke", b"%d" % i]
            )
            responses = [
                endorse_proposal(bundle, e, results) for e in self.endorsers
            ]
            env = create_signed_tx(bundle, self.client, responses)
            block.data.data.append(env.SerializeToString())
        protoutil.seal_block(block)
        return block

    def validator(self, channel, provider):
        from fabric_tpu.validation.validator import BlockValidator

        return BlockValidator(channel, self.mgr, provider, self.registry)


def bench_block_1k(net, device_ok=True, n_txs=1000):
    """Config #2: full validator ms/block, TPU vs SW provider, bit-exact
    masks (reference timers v20/validator.go:261-262)."""
    from fabric_tpu.protos import common_pb2

    block = net.make_block("benchchan", n_txs)

    def run(provider):
        b = common_pb2.Block()
        b.CopyFrom(block)
        v = net.validator("benchchan", provider)
        start = time.perf_counter()
        flags = v.validate(b)
        return (time.perf_counter() - start) * 1000.0, flags.tobytes()

    (sw_ms, sw_mask) = min(run(net.sw), run(net.sw))
    if set(sw_mask) != {0}:
        raise RuntimeError("config #2 expected all-VALID block")
    if not device_ok:
        return {
            "txs": n_txs,
            "cpu_ms_per_block": round(sw_ms, 1),
            "error": "device unavailable — CPU column only",
        }
    from fabric_tpu.crypto.tpu_provider import TPUProvider

    tpu_prov = TPUProvider()
    run(tpu_prov)  # compile warmup
    # best of two measured runs, like the headline: per-launch latency
    # can be noisy while the device+host work is stable
    (tpu_ms, tpu_mask) = min(run(tpu_prov), run(tpu_prov))
    if tpu_mask != sw_mask:
        raise RuntimeError("config #2 mask mismatch TPU vs SW")
    out = {
        "txs": n_txs,
        "tpu_ms_per_block": round(tpu_ms, 1),
        "cpu_ms_per_block": round(sw_ms, 1),
        "speedup": round(sw_ms / tpu_ms, 2),
        "mask_bit_exact": True,
    }
    if TPUProvider.degraded:
        out["error"] = (
            "device degraded mid-config: some lanes fell back to the "
            "software path; tpu_ms is not a pure device number"
        )
    return out


def bench_idemix(device_ok=True, n_sigs=None):
    """Config #3: batched Idemix verify across the idemix backend
    ladder (hostbn numpy lanes -> scheme oracle; crypto/bccsp.py
    IDEMIX_TIERS), per-rung ms/sig at batch 8/64/256 — mirroring the
    host_ladder/sw_ec_backend reporting discipline so an oracle-rung
    fallback can never masquerade as a hostbn number — plus the device
    Ate2 pairing column when a chip answers.  Setup is
    cryptography-free (ALG_NO_REVOCATION with an unsigned CRI, which
    Ver with rev_pk=None never reads), so this config measures on any
    box; host signature GENERATION costs ~1-2s each, so lanes are
    tiled from 8 unique signatures."""
    import random

    from fabric_tpu import idemix
    from fabric_tpu.crypto import fp256bn as bncurve
    from fabric_tpu.crypto.bccsp import (
        available_idemix_backends,
        idemix_backend_name,
    )
    from fabric_tpu.idemix.batch import verify_signatures_batch
    from fabric_tpu.protos import idemix_pb2

    if n_sigs is None:
        n_sigs = int(os.environ.get("BENCH_IDEMIX_SIGS", "64"))
    rng = random.Random(1234)
    attrs = ["OU", "Role", "EnrollmentID", "RevocationHandle"]
    rh_index = 3
    ik = idemix.new_issuer_key(attrs, rng)
    sk = bncurve.rand_mod_order(rng)
    nonce = bncurve.big_to_bytes(bncurve.rand_mod_order(rng))
    req = idemix.new_cred_request(sk, nonce, ik.ipk, rng)
    cred = idemix.new_credential(ik, req, [11, 22, 33, 44], rng)
    cri = idemix_pb2.CredentialRevocationInformation()
    cri.revocation_alg = idemix.ALG_NO_REVOCATION
    disclosure = [0, 0, 0, 0]
    msg = b"idemix bench message"
    uniq = []
    for _ in range(min(n_sigs, 8)):
        nym, r_nym = idemix.make_nym(sk, ik.ipk, rng)
        uniq.append(
            idemix.new_signature(
                cred, sk, nym, r_nym, ik.ipk, disclosure, msg, rh_index, cri, rng
            )
        )

    def batch_args(count):
        sigs_c = [uniq[i % len(uniq)] for i in range(count)]
        return (
            sigs_c,
            [disclosure] * count,
            ik.ipk,
            [msg] * count,
            [[None, None, None, None]] * count,
            rh_index,
        )

    def run(device, count):
        start = time.perf_counter()
        out = verify_signatures_batch(
            *batch_args(count), device_pairing=device
        )
        return (time.perf_counter() - start) * 1000.0, out

    # the oracle column is the PURE-HOST scheme rung
    # (scheme.verify_signature — the reference's signature.go Ver path),
    # timed over a small sample (it runs ~1s/sig here); one warm-up
    # verify amortizes one-time table builds.
    n_host = min(int(os.environ.get("BENCH_IDEMIX_ORACLE_SIGS", "4")), n_sigs)
    verify_signatures_batch(*batch_args(1), backend="scheme")  # warm-up
    start = time.perf_counter()
    host_out = verify_signatures_batch(*batch_args(n_host), backend="scheme")
    host_ms = (time.perf_counter() - start) * 1000.0
    if not all(host_out):
        raise RuntimeError("config #3 host verification failed")
    oracle_ms_per_sig = host_ms / n_host

    active = idemix_backend_name()
    result = {
        "sigs": n_sigs,
        "idemix_backend": active,
        "idemix_tiers_available": available_idemix_backends(),
        "host_ms_per_sig": round(oracle_ms_per_sig, 1),
        "host_sample_sigs": n_host,
        "reference_cpu_ms_per_sig_class": "5-20",
        "note": "host column is the PURE-host oracle (the scheme rung, "
        "python bignum) — honest about THIS implementation but ~2 "
        "orders slower than the reference's compiled amcl Go Ver "
        "(idemix/signature.go:243; reference_cpu_ms_per_sig_class "
        "cites that class: a few pairings at ~1-5ms each on modern "
        "x86). Read the hostbn ladder and device columns against BOTH "
        "numbers. Lanes are tiled from 8 unique signatures.",
    }
    if active == "scheme":
        # never let an oracle-rung run pass as a batch-engine number
        result["idemix_backend_warning"] = (
            "running on the scheme ORACLE rung (~1 s/sig) — the hostbn "
            "numpy tier is unavailable; batch columns are NOT "
            "comparable to hostbn numbers"
        )
        print(
            "bench: WARNING: idemix backend is the scheme oracle rung; "
            "batch verify will be ~2 orders of magnitude slow",
            file=sys.stderr,
            flush=True,
        )

    # per-rung ladder: hostbn ms/sig at batch 8/64/256 (production
    # entrypoint: the pool shards batches >= its threshold), masks
    # asserted against the oracle sample each size
    ladder = {"oracle_ms_per_sig": round(oracle_ms_per_sig, 1)}
    if available_idemix_backends().get("hostbn"):
        from fabric_tpu.crypto import hostbn
        from fabric_tpu.idemix.scheme import ecp2_from_proto

        hostbn.warm_schedules(ecp2_from_proto(ik.ipk.w))  # untimed build
        sizes = [
            int(s)
            for s in os.environ.get(
                "BENCH_IDEMIX_LADDER", "8,64,256"
            ).split(",")
            if s.strip()
        ]
        for size in sizes:
            # acceptance sizes (>= 64, where the pool shards) get best
            # of two passes: the first pays the cold worker spawn +
            # per-worker schedule build, and this box's wall clock is
            # noisy (host_ladder's discipline)
            ms = None
            for _pass in range(2 if size >= 64 else 1):
                start = time.perf_counter()
                out = verify_signatures_batch(
                    *batch_args(size), backend="hostbn"
                )
                elapsed = (time.perf_counter() - start) * 1000.0
                ms = elapsed if ms is None else min(ms, elapsed)
                if out[:n_host] != host_out[: min(n_host, size)] or not all(
                    out
                ):
                    raise RuntimeError(
                        f"config #3 hostbn/oracle mask mismatch at {size}"
                    )
            ladder[str(size)] = {"hostbn_ms_per_sig": round(ms / size, 1)}
            if size >= 64:
                ladder[str(size)]["speedup_vs_oracle"] = round(
                    oracle_ms_per_sig / (ms / size), 1
                )
        from fabric_tpu.idemix import batch as idemix_batch

        idemix_batch.shutdown_pool()
    else:
        ladder["hostbn"] = "skipped (numpy not installed)"
    result["ladder"] = ladder
    # The device Ate2 kernel's first compile is ~3.5 min on the TPU
    # (then cached; this bench's issuer key is seed-fixed so the program
    # caches across runs). BENCH_IDEMIX_DEVICE=0 opts out.
    if device_ok and os.environ.get("BENCH_IDEMIX_DEVICE", "1") == "1":
        run(True, n_sigs)  # compile warmup
        dev_ms, dev_out = run(True, n_sigs)
        if dev_out[:n_host] != host_out or not all(dev_out):
            raise RuntimeError("config #3 device/host mismatch")
        result["device_ms_per_sig"] = round(dev_ms / n_sigs, 1)
        result["speedup"] = round(
            (host_ms / n_host) / (dev_ms / n_sigs), 1
        )
        result["mask_bit_exact"] = True
    elif not device_ok:
        result["device"] = "skipped (device unavailable)"
    else:
        result["device"] = "skipped (BENCH_IDEMIX_DEVICE=0)"
    return result


def bench_mvcc(device_ok=True, n_txs=5000):
    """Config #4: MVCC validate-and-prepare over a 5k-tx block, host
    sequential scan vs the device fixpoint resolver (reference
    validateAndPrepareBatch, validation/validator.go:82; SURVEY P5)."""
    from fabric_tpu.ledger import rwset as rw
    from fabric_tpu.ledger.mvcc import Validator
    from fabric_tpu.ledger.mvcc_device import DeviceValidator
    from fabric_tpu.ledger.statedb import UpdateBatch, VersionedDB
    from fabric_tpu.validation.txflags import TxValidationCode

    db = VersionedDB()
    seed = UpdateBatch()
    for i in range(n_txs):
        seed.put("cc", f"k{i}", b"v0", rw.Version(0, i))
    db.apply_updates(seed)

    # every tx reads its own key at the committed version and writes it;
    # every 10th tx reads a key another in-block tx already wrote ->
    # MVCC_READ_CONFLICT, so the run exercises both outcomes
    rwsets = []
    for i in range(n_txs):
        read_key = f"k{i - 1}" if i % 10 == 5 else f"k{i}"
        read_ver = rw.Version(0, i - 1 if i % 10 == 5 else i)
        rwsets.append(
            rw.TxRwSet(
                (
                    rw.NsRwSet(
                        "cc",
                        (rw.KVRead(read_key, read_ver),),
                        (rw.KVWrite(f"k{i}", False, b"v1"),),
                    ),
                )
            )
        )
    incoming = [TxValidationCode.VALID] * n_txs

    def run(validator):
        start = time.perf_counter()
        codes, _updates, _hashed = validator.validate_and_prepare_batch(
            1, rwsets, list(incoming)
        )
        ms = (time.perf_counter() - start) * 1000.0
        n_conflicts = sum(
            1 for c in codes if c == TxValidationCode.MVCC_READ_CONFLICT
        )
        if n_conflicts != n_txs // 10:
            raise RuntimeError(
                f"config #4 expected {n_txs // 10} conflicts, got {n_conflicts}"
            )
        return ms, codes

    host_ms, host_codes = run(Validator(db))
    if not device_ok:
        return {
            "txs": n_txs,
            "host_ms_per_block": round(host_ms, 1),
            "error": "device unavailable — host column only",
        }
    dev = DeviceValidator(db)
    run(dev)  # compile warmup
    dev_ms, dev_codes = run(dev)
    if dev.last_path != "device" or dev_codes != host_codes:
        raise RuntimeError("config #4 device path mismatch")
    # RESIDENT variant: the table persists across
    # blocks, so the measurement is a real multi-block sequence — block
    # 1 pays one-time slot seeding + compile; steady state (block >= 2)
    # runs committed checks + fixpoint + table update in ONE launch
    # with no per-read host probes. Timed section is the validate call.
    from fabric_tpu.ledger.mvcc_device import ResidentDeviceValidator

    res = ResidentDeviceValidator(db)
    ver = {i: (0, i) for i in range(n_txs)}

    def resident_block(j):
        rwsets2 = []
        for i in range(n_txs):
            rk = i - 1 if i % 10 == 5 else i  # in-block conflict pattern
            rwsets2.append(
                rw.TxRwSet(
                    (
                        rw.NsRwSet(
                            "cc",
                            (rw.KVRead(f"k{rk}", rw.Version(*ver[rk])),),
                            (rw.KVWrite(f"k{i}", False, b"v1"),),
                        ),
                    )
                )
            )
        start = time.perf_counter()
        codes, _u, _h = res.validate_and_prepare_batch(
            j, rwsets2, [TxValidationCode.VALID] * n_txs
        )
        ms = (time.perf_counter() - start) * 1000.0
        n_conf = sum(
            1 for c in codes if c == TxValidationCode.MVCC_READ_CONFLICT
        )
        if n_conf != n_txs // 10 or res.last_path != "device":
            raise RuntimeError(
                f"config #4 resident block {j}: {n_conf} conflicts, "
                f"path {res.last_path}"
            )
        for i in range(n_txs):
            if i % 10 != 5:
                ver[i] = (j, i)
        return ms

    resident_block(1)  # seeding + compile
    res_ms = min(resident_block(2), resident_block(3))
    return {
        "txs": n_txs,
        "host_ms_per_block": round(host_ms, 1),
        "device_ms_per_block": round(dev_ms, 1),
        "speedup": round(host_ms / dev_ms, 2),
        "resident_ms_per_block": round(res_ms, 1),
        "resident_speedup": round(host_ms / res_ms, 2),
        "note": "codes bit-identical; host scan stays the default "
        "(ledger.deviceMVCC opts in). resident_* is the round-5 "
        "device-RESIDENT version table (steady-state block: committed "
        "checks + fixpoint + table update in ONE launch, no per-read "
        "host get_version probes — the win condition round 3 named); "
        "crossover still requires an attached chip if the launch RTT "
        "exceeds the host scan",
    }


def bench_multichannel(net, device_ok=True, n_channels=4, txs_per_channel=2000):
    """Config #5: one channel-axis device step validating one block per
    channel (sharding over real chips is exercised by dryrun_multichip
    on the virtual mesh; this machine has a single chip). The CPU
    aggregate column (BASELINE config #5 "CPU aggregate tx/s") runs the
    same four blocks through plain per-channel SW-provider validators —
    the reference's process-parallel shape collapsed onto this host's
    single core."""
    from fabric_tpu.protos import common_pb2

    channels = [f"bench{i}" for i in range(n_channels)]
    blocks = {
        ch: net.make_block(ch, txs_per_channel) for ch in channels
    }
    total = n_channels * txs_per_channel

    def copy_blocks():
        out = {}
        for ch, b in blocks.items():
            c = common_pb2.Block()
            c.CopyFrom(b)
            out[ch] = c
        return out

    # CPU aggregate: per-channel sequential validation, software provider
    cpu_copies = copy_blocks()
    start = time.perf_counter()
    for ch in channels:
        flags = net.validator(ch, net.sw).validate(cpu_copies[ch])
        if set(flags.tobytes()) != {0}:
            raise RuntimeError(f"config #5 invalid txs in {ch} (cpu)")
    cpu_elapsed = time.perf_counter() - start
    result = {
        "channels": n_channels,
        "txs_per_channel": txs_per_channel,
        "cpu_aggregate_tx_per_s": round(total / cpu_elapsed, 1),
        "cpu_ms_total": round(cpu_elapsed * 1000.0, 1),
    }
    if not device_ok:
        result["error"] = "device unavailable — CPU column only"
        return result

    import jax

    from fabric_tpu.parallel import MultiChannelValidator
    from fabric_tpu.parallel.mesh import grid_mesh

    devices = jax.devices()
    mesh = grid_mesh(1, 1, devices[:1])
    mc = MultiChannelValidator(
        mesh, {ch: net.validator(ch, net.sw) for ch in channels}
    )
    mc.validate(copy_blocks())  # compile warmup
    start = time.perf_counter()
    flags = mc.validate(copy_blocks())
    elapsed = time.perf_counter() - start
    for ch in channels:
        if set(flags[ch].tobytes()) != {0}:
            raise RuntimeError(f"config #5 invalid txs in {ch}")
    result.update(
        {
            "aggregate_tx_per_s": round(total / elapsed, 1),
            "ms_total": round(elapsed * 1000.0, 1),
            "speedup": round(cpu_elapsed / elapsed, 2),
            # duty cycle: share of the wall clock the sharded device step
            # (launch -> masks back) occupied; the rest is host phases
            "device_busy_ms": round(mc.last_device_ms, 1),
            "device_duty_cycle": round(
                mc.last_device_ms / (elapsed * 1000.0), 3
            ),
        }
    )
    return result


def bench_host_tiers(triples, budget_s=6.0):
    """Per-tier host EC batch throughput (the backend ladder column):
    every *available* tier verifies the same batch through the
    SoftwareProvider batch path; the p256 oracle is extrapolated from a
    few lanes (full batch would eat minutes).  Output keys are tier
    names, so an oracle-tier number can never masquerade as fastec."""
    from fabric_tpu.crypto.bccsp import (
        SoftwareProvider,
        available_ec_backends,
        ec_backend_name,
        select_ec_backend,
    )

    keys = [t[0] for t in triples]
    sigs = [t[1] for t in triples]
    digests = [t[2] for t in triples]
    active = ec_backend_name()
    out = {"active": active}
    avail = available_ec_backends()
    # the oracle rides a fixed 4 lanes (~0.8s), the timed tiers split the
    # rest of the budget so the function honors its budget_s contract
    timed_tiers = sum(
        1 for t, ok in avail.items() if ok and t != "p256"
    )
    per_tier_s = max(budget_s - 1.0, 1.0) / max(timed_tiers, 1)
    try:
        for tier, ok in avail.items():
            if not ok:
                out[tier] = {"skipped": "backend unavailable"}
                continue
            select_ec_backend(tier)
            sw = SoftwareProvider()
            # 1024 lanes (the acceptance batch size) bounds one pass to a
            # couple of seconds on the slowest timed tier, so the budget
            # check — which fires between whole batches — actually binds
            lanes = keys[:1024] if tier != "p256" else keys[:4]
            if tier != "p256":
                # untimed warmup: first call pays one-off process-pool
                # spawn (hostec) — the column reports steady state
                sw.batch_verify(lanes, sigs[: len(lanes)], digests[: len(lanes)])
            t0 = time.perf_counter()
            done = 0
            while True:
                verdicts = sw.batch_verify(
                    lanes, sigs[: len(lanes)], digests[: len(lanes)]
                )
                if not all(verdicts):
                    raise RuntimeError(f"{tier}: benchmark sig rejected")
                done += len(lanes)
                elapsed = time.perf_counter() - t0
                if elapsed >= per_tier_s or (tier == "p256" and done >= 4):
                    break
            out[tier] = {
                "verifies_per_s": round(done / elapsed, 1),
                "lanes": len(lanes),
            }
            if tier == "p256":
                out[tier]["note"] = "oracle tier, extrapolated from 4 lanes"
    finally:
        select_ec_backend(active)
    return out


def bench_chaos(device_ok=True, seed=None):
    """fabchaos smoke scorecard: seeded fault-injection scenarios with
    per-stage p50/p99 latency — the trajectory files capture scenario
    coverage and SLO shape, not just a clean-batch headline.  Device
    availability is irrelevant (the harness drives the host planes);
    BENCH_CHAOS_SEED overrides the seed."""
    from fabric_tpu.tools.fabchaos import scorecard_for_bench

    if seed is None:
        seed = int(os.environ.get("BENCH_CHAOS_SEED", "7"))
    return scorecard_for_bench(seed=seed)


def bench_serve(device_ok=True, n_requests=None, lanes_per_request=256):
    """configs.serve: the resident validation sidecar.

    Two measurements:

    1. **cold-vs-warm compile ms per bucket** through the bucketed
       program registry (fresh AOT dir -> cold trace+compile; second
       registry against the same dir -> AOT-loaded warm start) and the
       ladder-level warm speedup.  Uses the CI-able demo limb ladder by
       default; BENCH_SERVE_LADDER=verify runs the REAL ECDSA limb
       kernel (minutes cold — real-silicon runs only).
    2. **per-request p50/p99** through a live sidecar: an in-process
       host-engine sidecar serves mixed batches over the real socket
       protocol via the SidecarProvider client shim, masks asserted
       bit-exact against the in-process provider.
    """
    import hashlib
    import shutil
    import tempfile

    from fabric_tpu.common.metrics import latency_summary
    from fabric_tpu.crypto import der as _der
    from fabric_tpu.crypto.bccsp import (
        ECDSAPublicKey,
        SoftwareProvider,
        ec_backend,
    )
    from fabric_tpu.serve.client import SidecarProvider
    from fabric_tpu.serve.registry import BucketProgramRegistry
    from fabric_tpu.serve.server import SidecarServer

    out = {}

    # ---- 1: cold vs warm compile per bucket (AOT registry) --------------
    ladder = os.environ.get("BENCH_SERVE_LADDER", "demo")
    buckets = tuple(
        int(b)
        for b in os.environ.get("BENCH_SERVE_BUCKETS", "128,256,512").split(",")
    )
    aot_dir = tempfile.mkdtemp(prefix="bench-serve-aot-")
    try:
        from fabric_tpu.serve.registry import (
            demo_limb_program,
            verify_limb_program,
        )

        fn, shapes_for = (
            verify_limb_program() if ladder == "verify" else demo_limb_program()
        )
        cold = BucketProgramRegistry.for_jax_program(
            fn, shapes_for, buckets=buckets, label=f"bench-{ladder}",
            aot_dir=aot_dir,
        )
        cold.warm()
        warm = BucketProgramRegistry.for_jax_program(
            fn, shapes_for, buckets=buckets, label=f"bench-{ladder}",
            aot_dir=aot_dir,
        )
        warm.warm()
        per_bucket = {}
        cold_total = warm_total = 0.0
        for b in buckets:
            c = cold.warm_report[b]
            w = warm.warm_report[b]
            cold_total += c["warm_ms"]
            warm_total += w["warm_ms"]
            per_bucket[str(b)] = {
                "cold_ms": c["warm_ms"],
                "cold_compile_ms": c.get("compile_ms"),
                "warm_ms": w["warm_ms"],
                "warm_aot_hit": bool(w.get("aot_hit")),
            }
        out["compile_ladder"] = {
            "ladder": ladder,
            "buckets": list(buckets),
            "per_bucket": per_bucket,
            "cold_total_ms": round(cold_total, 1),
            "warm_total_ms": round(warm_total, 1),
            "warm_speedup": round(cold_total / max(warm_total, 1e-3), 1),
            "warm_traces": warm.traces,
        }
    except Exception as exc:  # noqa: BLE001 - ladder column is best-effort
        out["compile_ladder"] = {"error": str(exc)[:300]}
    finally:
        shutil.rmtree(aot_dir, ignore_errors=True)

    # ---- 2: request p50/p99 through a live sidecar ----------------------
    if n_requests is None:
        n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "32"))
    sock = os.path.join(tempfile.mkdtemp(prefix="bench-serve-"), "b.sock")
    server = SidecarServer(sock, engine="host", warm_ladder="off")
    provider = None
    try:
        warm_report = server.warm()
        server.start()
        provider = SidecarProvider(address=sock)
        ec = ec_backend()
        kp = ec.generate_keypair()
        pub = ECDSAPublicKey(*kp.pub)
        keys, sigs, digs, expected = [], [], [], []
        for i in range(lanes_per_request):
            digest = hashlib.sha256(b"serve bench lane %d" % i).digest()
            r, s = ec.sign_digest(kp.priv, digest)
            sig = _der.marshal_signature(r, s)
            if i % 5 == 0:  # mixed batch: every 5th lane invalid
                bad = bytearray(sig)
                bad[-1] ^= 0x5A
                sig = bytes(bad)
            keys.append(pub)
            sigs.append(sig)
            digs.append(digest)
            expected.append(i % 5 != 0)
        client_lat = []
        for _ in range(n_requests):
            t0 = time.perf_counter()
            mask = provider.batch_verify(keys, sigs, digs)
            client_lat.append(time.perf_counter() - t0)
            if list(mask) != expected:
                raise RuntimeError("sidecar mask != ground truth")
        inproc = SoftwareProvider().batch_verify(keys, sigs, digs)
        if list(inproc) != expected:
            raise RuntimeError("in-process mask != ground truth")
        client_summary = latency_summary(client_lat)
        described = server.describe()
        out["sidecar"] = {
            "engine": server.engine,
            "requests": n_requests,
            "lanes_per_request": lanes_per_request,
            "host_warm_ms": warm_report.get("host_warm_ms"),
            "client_p50_ms": client_summary["p50_ms"],
            "client_p99_ms": client_summary["p99_ms"],
            "server_latency": described["stats"]["request_latency"],
            "rejects": described["stats"]["rejects"],
            "lanes_per_s": round(
                n_requests * lanes_per_request / max(sum(client_lat), 1e-9), 1
            ),
            "degraded": provider.degraded,
            "mask_exact": True,
        }
    except Exception as exc:  # noqa: BLE001 - emit partial results
        out["sidecar"] = {"error": str(exc)[:300]}
    finally:
        if provider is not None:
            provider.stop()
        server.stop()
        shutil.rmtree(os.path.dirname(sock), ignore_errors=True)
    return out


def bench_fleet(device_ok=True, n_peers=None, requests_per_peer=None):
    """configs.fleet: the multi-peer shared-sidecar soak (ROADMAP
    fleet-scale acceptance).  One warm host-engine sidecar, >= 4 REAL
    peer processes (``fabric_tpu.serve.fleetload`` subprocesses) with a
    zipf channel skew — one paying high-priority channel, the rest
    spam/bulk with 10:1 aggregate request skew — reporting aggregate
    verifies/s across the fleet and per-class p99 off the sidecar's
    per-class stats.  Every peer asserts its masks bit-exact; a
    mismatch fails the column."""
    import shutil
    import subprocess
    import tempfile

    from fabric_tpu.serve.server import SidecarServer

    if n_peers is None:
        n_peers = max(4, int(os.environ.get("BENCH_FLEET_PEERS", "4")))
    if requests_per_peer is None:
        requests_per_peer = int(os.environ.get("BENCH_FLEET_REQUESTS", "6"))
    sock = os.path.join(tempfile.mkdtemp(prefix="bench-fleet-"), "f.sock")
    server = SidecarServer(sock, engine="host", warm_ladder="off")
    out = {}
    try:
        server.warm()
        server.start()
        # zipf-ish skew: peer 0 is the paying channel; spam peers carry
        # 10x its aggregate request count between them
        specs = []
        for i in range(n_peers):
            if i == 0:
                specs.append(("paychan", "high", requests_per_peer, 256))
            else:
                spam_reqs = max(
                    1,
                    (10 * requests_per_peer) // max(1, n_peers - 1),
                )
                specs.append((f"spam{i}", "bulk", spam_reqs, 128))
        t0 = time.perf_counter()
        procs = [
            subprocess.Popen(
                [
                    sys.executable, "-m", "fabric_tpu.serve.fleetload",
                    "--address", sock, "--channel", chan, "--qos", qos,
                    "--requests", str(reqs), "--lanes", str(lanes),
                    "--seed", str(i),
                ],
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            for i, (chan, qos, reqs, lanes) in enumerate(specs)
        ]
        peers = []
        try:
            for p, (chan, _q, _r, _l) in zip(procs, specs):
                stdout, stderr = p.communicate(timeout=240)
                if p.returncode != 0:
                    raise RuntimeError(
                        f"fleet peer {chan} rc={p.returncode}: "
                        f"{stderr.decode()[-200:]}"
                    )
                peers.append(
                    json.loads(stdout.decode().strip().splitlines()[-1])
                )
        except BaseException:
            # one peer failed/timed out: reap the rest before the
            # finally block stops the server out from under them
            for p in procs:
                if p.poll() is None:
                    p.kill()
                try:
                    p.communicate(timeout=10)
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            raise
        wall_s = time.perf_counter() - t0
        total_lanes = sum(
            p["requests"] * p["lanes_per_request"] for p in peers
        )
        per_class = server.stats.summary()["per_class"]
        out = {
            "peers": n_peers,
            "skew": "10:1 spam:paying",
            "aggregate_verifies_per_s": round(total_lanes / wall_s, 1),
            "wall_s": round(wall_s, 2),
            "mask_mismatches": sum(p["mask_mismatches"] for p in peers),
            "busy_rejects": sum(p["busy_rejects"] for p in peers),
            "degraded_peers": sum(1 for p in peers if p["degraded"]),
            # tail-tolerance counters (fabtail): the soak quantifies
            # hedge/deadline/eviction behavior, not just throughput
            "hedges": sum(p.get("hedges", 0) for p in peers),
            "hedge_wins": sum(p.get("hedge_wins", 0) for p in peers),
            "deadline_expired": sum(
                p.get("deadline_expired", 0) for p in peers
            ),
            "slow_evictions": sum(
                p.get("slow_evictions", 0) for p in peers
            ),
            "server_deadline_shed": server.stats.summary()["deadline_shed"],
            "per_peer": peers,
            "per_class_p99_ms": {
                cls: row["latency"].get("p99_ms")
                for cls, row in per_class.items()
            },
            "per_class_served": {
                cls: row["served"] for cls, row in per_class.items()
            },
        }
        if out["mask_mismatches"]:
            raise RuntimeError("fleet soak produced mask mismatches")
    except Exception as exc:  # noqa: BLE001 - emit partial results
        out["error"] = str(exc)[:300]
    finally:
        server.stop()
        shutil.rmtree(os.path.dirname(sock), ignore_errors=True)
    return out


def _ndev_child(n_devices: int, lanes: int) -> None:
    """Subprocess body of the n_devices sweep: pin a hermetic CPU mesh
    of `n_devices` virtual devices BEFORE any backend init, run the
    sharded limb-matrix verify kernel, print one JSON line."""
    import hashlib

    from fabric_tpu.utils.jaxcache import pin_cpu_mesh

    pin_cpu_mesh(n_devices)
    import jax

    have = len(jax.devices())
    if have < n_devices:
        print(json.dumps({"error": f"only {have} devices materialized"}))
        return
    from fabric_tpu.crypto.tpu_provider import TPUProvider, _bucket
    from fabric_tpu.parallel.mesh import flat_mesh
    from fabric_tpu.parallel.sharded import ShardedVerify, pad_lanes

    # sign a small distinct set and tile it: the sweep times the device
    # step, not host signing
    base = gen_triples(min(lanes, 64))
    triples = [base[i % len(base)] for i in range(lanes)]
    provider = TPUProvider()  # safe here: JAX_PLATFORMS=cpu is pinned
    limbs = provider.prep_limbs(
        [t[0] for t in triples], [t[1] for t in triples], [t[2] for t in triples]
    )
    mesh = flat_mesh(jax.devices()[:n_devices])
    sharded = ShardedVerify(mesh)
    size = pad_lanes(_bucket(lanes), sharded.data_size)
    padded = TPUProvider.pad_limbs(limbs, size)
    t0 = time.perf_counter()
    mask = sharded.verify_flat(*padded)[:lanes]
    warm_s = time.perf_counter() - t0
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        mask = sharded.verify_flat(*padded)[:lanes]
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    print(
        json.dumps(
            {
                "n_devices": n_devices,
                "lanes": lanes,
                "first_call_s": round(warm_s, 2),
                "verifies_per_s": round(lanes / best, 1),
                "mask_sha": hashlib.sha256(
                    bytes(1 if b else 0 for b in mask)
                ).hexdigest()[:16],
            }
        )
    )


def bench_n_devices(device_ok=True, deadline=None):
    """configs.n_devices: the ROADMAP multi-chip sweep column.  Each
    device count runs in a SUBPROCESS that pins a hermetic CPU mesh
    (pin_cpu_mesh) before backend init, so the sweep never touches a
    possibly version-skewed accelerator client; the parent additionally
    asserts the verify mask is bit-exact ACROSS shardings.  On real
    multi-chip silicon the same column is the scaling headline; on the
    CI box it mostly measures XLA:CPU virtual-device overhead (and the
    real kernel's compile may exceed the per-child timeout — recorded,
    not fatal)."""
    import subprocess

    if os.environ.get("BENCH_NDEV", "1") == "0":
        return {"skipped": "BENCH_NDEV=0"}
    lanes = int(os.environ.get("BENCH_NDEV_LANES", "512"))
    counts = [
        int(c)
        for c in os.environ.get("BENCH_NDEV_SWEEP", "1,2,4,8").split(",")
    ]
    child_timeout = float(os.environ.get("BENCH_NDEV_TIMEOUT_S", "600"))
    out = {"lanes": lanes, "sweep": {}}
    mask_shas = set()
    for n in counts:
        if deadline is not None and time.monotonic() > deadline:
            out["sweep"][str(n)] = {"skipped": "bench budget exhausted"}
            continue
        budget = child_timeout
        if deadline is not None:
            budget = min(budget, max(deadline - time.monotonic(), 30.0))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # the child pins its own device count dynamically; a forced
        # host-device-count flag from the parent env would override it
        env["XLA_FLAGS"] = " ".join(
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        )
        try:
            proc = subprocess.run(
                [
                    sys.executable,
                    "-c",
                    f"import bench; bench._ndev_child({n}, {lanes})",
                ],
                capture_output=True,
                text=True,
                timeout=budget,
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
            if proc.returncode != 0 or not line:
                out["sweep"][str(n)] = {
                    "error": (proc.stderr or "no output")[-300:]
                }
                continue
            row = json.loads(line)
            out["sweep"][str(n)] = row
            if "mask_sha" in row:
                mask_shas.add(row["mask_sha"])
        except subprocess.TimeoutExpired:
            out["sweep"][str(n)] = {
                "error": f"timeout after {budget:.0f}s (cold XLA compile "
                "exceeds the child budget on this box)"
            }
        except Exception as exc:  # noqa: BLE001 - sweep column best-effort
            out["sweep"][str(n)] = {"error": str(exc)[:300]}
    # only claim cross-sharding bit-exactness when at least two device
    # counts actually produced a mask; with 0-1 successful children the
    # property was never tested (null, not a vacuous True)
    out["mask_bit_exact_across_shardings"] = (
        len(mask_shas) == 1 if sum(
            1 for r in out["sweep"].values() if "mask_sha" in r
        ) >= 2 else None
    )
    rows = [
        r for r in out["sweep"].values() if isinstance(r.get("verifies_per_s"), (int, float))
    ]
    if len(rows) >= 2:
        # baseline against the SMALLEST successful device count, and say
        # which it was: if the n=1 child timed out, ratios labeled
        # "vs 1 device" would silently be ratios vs the 2-device row
        base_row = min(rows, key=lambda r: r["n_devices"])
        base = base_row["verifies_per_s"]
        out["scaling_baseline_n_devices"] = base_row["n_devices"]
        out[f"scaling_vs_{base_row['n_devices']}dev"] = {
            str(r["n_devices"]): round(r["verifies_per_s"] / base, 2)
            for r in rows
        }
    return out


def bench_batcher(net, device_ok=True, n_channels=4, txs_per_channel=128):
    """P7 coalescing: four channels deliver SMALL blocks concurrently.
    Direct mode launches one small device program per channel; the shared
    VerifyBatcher coalesces them into few large launches (reference
    analog: broadcast.go:163 backpressure discipline + the validator
    semaphore's batching effect)."""
    import threading

    from fabric_tpu.crypto.tpu_provider import TPUProvider
    from fabric_tpu.parallel.batcher import BatchingProvider
    from fabric_tpu.protos import common_pb2

    channels = [f"small{i}" for i in range(n_channels)]
    blocks = {ch: net.make_block(ch, txs_per_channel) for ch in channels}

    def run(provider):
        validators = {ch: net.validator(ch, provider) for ch in channels}
        copies = {}
        for ch, b in blocks.items():
            c = common_pb2.Block()
            c.CopyFrom(b)
            copies[ch] = c
        errs = []

        def work(ch):
            try:
                flags = validators[ch].validate(copies[ch])
                if set(flags.tobytes()) != {0}:
                    errs.append(f"{ch}: invalid txs")
            except Exception as e:  # noqa: BLE001
                errs.append(f"{ch}: {e}")

        threads = [
            threading.Thread(target=work, args=(ch,)) for ch in channels
        ]
        start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise RuntimeError("; ".join(errs))
        return (time.perf_counter() - start) * 1000.0

    tpu = TPUProvider()
    run(tpu)  # compile warmup (per-channel bucket)
    direct_ms = min(run(tpu), run(tpu))  # robust to a launch stall
    shared = BatchingProvider(tpu)
    try:
        run(shared)  # compile warmup (coalesced bucket)
        launches0, lanes0 = shared.batcher.launches, shared.batcher.lanes
        batched_ms = min(run(shared), run(shared))
        launches = (shared.batcher.launches - launches0) // 2
        lanes = (shared.batcher.lanes - lanes0) // 2
    finally:
        shared.stop()
    total = n_channels * txs_per_channel
    return {
        "channels": n_channels,
        "txs_per_channel": txs_per_channel,
        "direct_ms": round(direct_ms, 1),
        "batched_ms": round(batched_ms, 1),
        "launches": launches,
        "lanes_per_launch": round(lanes / max(launches, 1), 1),
        "batched_tx_per_s": round(total / (batched_ms / 1000.0), 1),
        "speedup": round(direct_ms / batched_ms, 2),
        "batcher_mode": shared.batcher.mode,
        "batcher_rtt_ema_ms": (
            round(shared.batcher.rtt_ema_ms, 1)
            if shared.batcher.rtt_ema_ms is not None
            else None
        ),
        "note": "transport-regime adaptive (round 5): the batcher "
        "measures its own small-launch RTT and coalesces only when the "
        "launch path is low-latency; where per-launch latency is high it passes "
        "requests through as independent overlapped launches (so "
        "batched ~= direct by construction). Bounded-queue backpressure "
        "(SURVEY P7) holds in both modes.",
    }


def main():
    # 32768 lanes/launch: a fixed per-launch latency weighs half as much
    # on the rate as at 16384 (not measured on the attached chip)
    import threading

    n = int(os.environ.get("BENCH_N", "32768"))
    iters = int(os.environ.get("BENCH_ITERS", "5"))
    headline_only = os.environ.get("BENCH_HEADLINE_ONLY", "") == "1"
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "1500"))
    t0 = time.monotonic()
    deadline = t0 + budget_s

    # ---- CPU columns FIRST: a complete JSON line exists before the
    # ---- device is touched at all (round-4 postmortem: UNAVAILABLE at
    # ---- first dispatch produced rc=1 and zero data)
    from fabric_tpu.crypto.bccsp import ec_backend_name

    configs = {}
    # observe the whole run: every emitted line carries the metrics
    # snapshot scraped at emit time (bench_obs_overhead disables the
    # registry around its own measurement passes and restores it)
    from fabric_tpu.common import fabobs as _fabobs

    _fabobs.ensure_enabled()
    triples = gen_triples(n)
    cpu_rate = bench_cpu_baseline(triples)
    # which scalar-EC tier the SW provider actually runs — guards against
    # a silent fallback mislabeling CPU columns as fastec numbers
    sw_backend = ec_backend_name()
    try:
        configs["host_ec_tiers"] = bench_host_tiers(triples)
    except Exception as exc:  # noqa: BLE001 - ladder column is best-effort
        configs["host_ec_tiers"] = {"error": str(exc)[:300]}
    try:
        configs["host_ladder"] = bench_host_ladder(triples)
    except Exception as exc:  # noqa: BLE001 - ladder column is best-effort
        configs["host_ladder"] = {"error": str(exc)[:300]}
    try:
        configs["obs_overhead"] = bench_obs_overhead(triples)
    except Exception as exc:  # noqa: BLE001 - obs column is best-effort
        configs["obs_overhead"] = {"error": str(exc)[:300]}
    try:
        import subprocess

        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip()
    except Exception:  # noqa: BLE001 - no git: omit
        rev = ""
    result = {
        "metric": "ecdsa_p256_verify_throughput",
        "value": round(cpu_rate, 1),
        "unit": "verifies/s",
        "vs_baseline": 1.0,
        "detail": {
            "rev": rev,
            "batch": n,
            "iters": iters,
            "cpu_baseline_verifies_per_s": round(cpu_rate, 1),
            "device": "pending",
            "error": "device not yet attempted",
            "target_verifies_per_s": 50000,
            "sw_ec_backend": sw_backend,
            "budget_s": budget_s,
            "elapsed_s": 0.0,
            "configs": configs,
        },
    }

    if sw_backend == "p256":
        # never let an oracle-tier run pass as a fast-tier number: the
        # warning rides every emitted line and stderr shouts once
        result["detail"]["sw_ec_backend_warning"] = (
            "running on the pure-Python ORACLE tier (~5 verifies/s) — "
            "CPU columns are NOT comparable to fastec/hostec numbers"
        )
        print(
            "bench: WARNING: EC backend is the p256 oracle tier; "
            "host columns will be ~3 orders of magnitude slow",
            file=sys.stderr,
            flush=True,
        )

    def emit():
        result["detail"]["elapsed_s"] = round(time.monotonic() - t0, 1)
        try:
            # rung counters + stage histograms ride BENCH_*.json next to
            # the throughput columns (ISSUE 10: configs.metrics_snapshot)
            configs["metrics_snapshot"] = _fabobs.snapshot()
        except Exception as exc:  # noqa: BLE001 - snapshot is best-effort
            configs["metrics_snapshot"] = {"error": str(exc)[:200]}
        print(json.dumps(result), flush=True)

    emit()  # valid line on disk before any device call can hang

    # ---- watchdog: if anything (usually a first device dispatch into a
    # ---- hung backend) hangs past the budget + grace, emit what we have
    # ---- and exit 0 — the driver still gets the latest complete line
    grace_s = float(os.environ.get("BENCH_WATCHDOG_GRACE_S", "120"))

    def _watchdog():
        while True:
            left = (deadline + grace_s) - time.monotonic()
            if left <= 0:
                break
            time.sleep(min(left, 10.0))
        # os._exit must run even if emit() races the main thread's dict
        # mutations (json.dumps over a changing dict raises) — a dead
        # watchdog would reintroduce the round-4 infinite hang
        try:
            result["detail"]["watchdog"] = (
                "budget+grace exhausted; a hung call was preempted"
            )
            emit()
        except Exception:  # noqa: BLE001
            pass
        finally:
            os._exit(0)

    threading.Thread(target=_watchdog, name="bench-watchdog", daemon=True).start()

    # ---- bounded device probe (subprocess: a hung backend init is
    # ---- KILLED by the kernel, and the verdict is cached for the run)
    probe_s = min(float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "300")),
                  max(budget_s * 0.3, 60.0))
    from fabric_tpu.utils.deviceprobe import probe_subprocess

    device_ok, probe_err = probe_subprocess(probe_s)
    result["detail"]["probe"] = "subprocess"
    if not device_ok:
        result["detail"]["device"] = "unavailable"
        result["detail"]["error"] = probe_err or "no accelerator device"
        emit()
    else:
        import jax

        result["detail"]["device"] = str(jax.devices()[0])
        try:
            device_rate, degraded = bench_headline_device(triples, iters)
            if degraded or device_rate <= 0.0:
                device_ok = False
                result["detail"]["error"] = (
                    "device dispatch degraded to the software fallback — "
                    "no valid device column"
                )
            else:
                result["value"] = round(device_rate, 1)
                result["vs_baseline"] = round(device_rate / cpu_rate, 2)
                result["detail"].pop("error", None)
        except Exception as exc:  # noqa: BLE001 - keep the CPU line
            device_ok = False
            result["detail"]["error"] = f"headline device error: {exc}"[:300]
        emit()

    if not headline_only:
        net = None
        for name, fn, needs_net in (
            ("block_1k", bench_block_1k, True),
            ("idemix", bench_idemix, False),
            ("mvcc_5k", bench_mvcc, False),
            ("multi_4ch", bench_multichannel, True),
            ("batcher_4ch_small", bench_batcher, True),
            ("serve", bench_serve, False),
            ("fleet", bench_fleet, False),
            ("n_devices", bench_n_devices, False),
            ("chaos", bench_chaos, False),
        ):
            if time.monotonic() > deadline:
                configs[name] = {
                    "skipped": f"wall-clock budget ({budget_s:.0f}s) exhausted"
                }
                emit()
                continue
            if name == "batcher_4ch_small" and not device_ok:
                configs[name] = {
                    "skipped": "device unavailable (coalescing is a "
                    "device-launch experiment)"
                }
                emit()
                continue
            try:
                if needs_net and net is None:
                    net = _Net()
                if name == "idemix" and not needs_net:
                    # cold 64-lane pairing compile costs minutes; with a
                    # tight remaining budget fall back to the proven
                    # 8-lane shape rather than risk a budget skip
                    remaining = deadline - time.monotonic()
                    n_sigs = (
                        None  # env/default (64)
                        if remaining > 420 or not device_ok
                        else 8
                    )
                    configs[name] = fn(device_ok, n_sigs=n_sigs)
                elif name == "n_devices":
                    configs[name] = fn(device_ok, deadline=deadline)
                else:
                    configs[name] = (
                        fn(net, device_ok) if needs_net else fn(device_ok)
                    )
            except Exception as exc:  # noqa: BLE001 - emit partial results
                configs[name] = {"error": str(exc)[:300]}
            emit()


if __name__ == "__main__":
    sys.exit(main())
