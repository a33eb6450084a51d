#!/usr/bin/env python3
"""Bring-up smoke: the block-commit path on one TPU chip, end to end.

    python chip_smoke.py                  # one chip (what the driver runs)
    python chip_smoke.py --chips 4        # the four-chip phase and its oracle only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-on-cpu   # tiny, never "ok": true

One process, the only one that touches JAX.  Phases (one JSON object per
line says what each did; the LAST line is the verdict and nothing else):

- ``commit``: a chain of 4 blocks x 1,000 tx (BASELINE config #2 shape:
  three orgs, ``OutOf(2, Org1, Org2, Org3)``, creator + 2 endorsements =
  ~3,000 signature lanes per block -> the 4,096-lane bucket) with a
  known handful of poisoned transactions per block, committed twice
  through ``peer.channel.Channel`` + ``peer.pipeline.CommitPipeline``
  into two fresh sqlite ledgers: once with ``SoftwareProvider`` (the
  host-only oracle), once with the provider ``default_provider()``
  returns on the chip.  TRANSACTIONS_FILTER bytes, ledger height and
  every written key's state must be identical and carry the expected
  non-VALID codes.
- ``serve``: an in-process ``SidecarServer(engine="device")`` on a unix
  socket and a ``SidecarProvider`` client; three ~3,000-lane requests
  (lanes of the same blocks plus no-key and garbage-DER lanes); masks
  must equal the ``SoftwareProvider`` masks.
- ``--chips 4``: BASELINE config #5's layout at the same block size —
  4 channels x one 1,000-tx block through ``MultiChannelValidator`` on
  ``grid_mesh(4, 1)`` (one channel per chip) — masks equal to the
  per-channel host oracle AND four devices in the output's device set.

Every failure on the device path has a software answer in this codebase
(the degrade ladder is a peer's safety code), so "exit 0" proves nothing
by itself: after every phase the script checks each seam that could
have given way (the ``check_*`` functions) and raises on the first.
There is no try/except that lets a failed phase go on and no watchdog
that exits 0.

``--seed`` fixes the workload's structure: which transactions are
poisoned and how, the keys and values written, which serve lanes are
damaged.  Keys, certificates and ECDSA nonces come from the OS RNG as in
every entry point of this repo; the oracle run sees the very same bytes.

Timings printed here are SMOKE TIMINGS of single runs, labelled with the
device they ran on; they are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence

CHANNEL = "smokechan"
CHAINCODE = "cc"
POLICY = "OutOf(2, 'Org1MSP.member', 'Org2MSP.member', 'Org3MSP.member')"
FULL_BLOCKS, FULL_TXS = 4, 1000
TINY_BLOCKS, TINY_TXS = 2, 64
FULL_BUCKET = 4096
POISONS_PER_KIND = 2  # per block, of each of the four kinds
SERVE_REQUESTS = 3
SERVE_DAMAGED = 8  # per request, of each of: no key, garbage DER


class SeamGaveWay(Exception):
    """A check of this script failed: the run is not a chip pass."""


def say(**fields) -> None:
    print(json.dumps(fields, sort_keys=True, default=str), flush=True)


# ---------------------------------------------------------------------------
# seam checks — each raises SeamGaveWay; tests/test_chip_smoke.py shows
# that each one fires
# ---------------------------------------------------------------------------


def check_platform(devices: Sequence, chips: int) -> None:
    platform = devices[0].platform
    if platform != "tpu":
        raise SeamGaveWay(
            f"no TPU: jax.devices()[0].platform is {platform!r} "
            f"({len(devices)} device(s))"
        )
    if chips == 4 and len(devices) != 4:
        raise SeamGaveWay(f"--chips 4 needs 4 devices, JAX has {len(devices)}")


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


def check_same_bytes(what: str, got: bytes, want: bytes) -> None:
    if got != want:
        diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
        raise SeamGaveWay(
            f"{what}: device answer differs from the host oracle "
            f"(lengths {len(got)}/{len(want)}, first differing index "
            f"{diff[0] if diff else min(len(got), len(want))}, "
            f"{len(diff)} differing)"
        )


def device_lanes(snapshot: Dict) -> int:
    """fabric_verify_lanes_total{rung="device"} out of a fabobs snapshot."""
    series = snapshot.get("fabric_verify_lanes_total", {}).get("series", {})
    return int(series.get("rung=device", 0))


def check_device_lanes(snapshot: Dict, sent: int) -> None:
    counted = device_lanes(snapshot)
    if counted != sent:
        raise SeamGaveWay(
            f"fabric_verify_lanes_total{{rung=\"device\"}} is {counted}, the "
            f"script sent {sent} lanes to the device"
        )


def check_bucket(lanes: int, bucket: int, want: Optional[int]) -> None:
    if want is not None and bucket != want:
        raise SeamGaveWay(
            f"a {lanes}-lane batch lands in bucket {bucket}, not {want}: a "
            "cold run would compile a second program"
        )


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------


class CompileLog:
    """Real XLA compiles and persistent-cache hits per phase
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
# workload
# ---------------------------------------------------------------------------


def build_world():
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.msp.cryptogen import generate_org
    from fabric_tpu.msp.signer import SigningIdentity
    from fabric_tpu.policy import from_dsl
    from fabric_tpu.validation.validator import (
        ChaincodeDefinition,
        ChaincodeRegistry,
    )

    sw = SoftwareProvider()
    orgs = [
        generate_org(f"org{i}.example.com", f"Org{i}MSP") for i in (1, 2, 3)
    ]
    return {
        "orgs": orgs,
        "registry": ChaincodeRegistry(
            [ChaincodeDefinition(CHAINCODE, from_dsl(POLICY))]
        ),
        "clients": [SigningIdentity(o.users[0], sw) for o in orgs],
        "peers": [SigningIdentity(o.peers[0], sw) for o in orgs],
    }


def msp_manager(world, provider):
    from fabric_tpu.msp.identity import MSPManager

    return MSPManager([o.msp(provider=provider) for o in world["orgs"]])


def pick_poisons(rng: random.Random, n_txs: int) -> Dict[int, str]:
    """{tx index: kind}. An mvcc tx reads the key the tx before it
    writes, so both its neighbours stay clean."""
    poisons: Dict[int, str] = {}
    blocked: set = set()
    for kind in ("mvcc", "bad_creator", "short_endorsement", "high_s"):
        placed = 0
        while placed < POISONS_PER_KIND:
            i = rng.randrange(1, n_txs - 1)
            span = {i - 1, i, i + 1} if kind == "mvcc" else {i}
            if span & blocked:
                continue
            blocked |= span
            poisons[i] = kind
            placed += 1
    return poisons


def build_block(world, channel, number, prev_hash, n_txs, rng, seed):
    """One sealed block: ({"raw": serialized block, "codes": expected
    {tx: code}, "state": expected {key: value|None}, "lanes": signature
    lanes}, header hash)."""
    from fabric_tpu.common import der, p256
    from fabric_tpu.common.txflags import TxValidationCode as Code
    from fabric_tpu.endorser import (
        create_proposal,
        create_signed_tx,
        endorse_proposal,
    )
    from fabric_tpu.ledger import rwset as rw
    from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset
    from fabric_tpu.protos import protoutil

    poisons = pick_poisons(rng, n_txs)
    expected_code = {
        "bad_creator": Code.BAD_CREATOR_SIGNATURE,
        "short_endorsement": Code.ENDORSEMENT_POLICY_FAILURE,
        "high_s": Code.ENDORSEMENT_POLICY_FAILURE,
        "mvcc": Code.MVCC_READ_CONFLICT,
    }
    block = protoutil.new_block(number, prev_hash)
    codes: Dict[int, int] = {}
    state: Dict[str, Optional[bytes]] = {}
    lanes = 0
    for i in range(n_txs):
        kind = poisons.get(i)
        key = f"{channel}-b{number}k{i:04d}"
        value = f"v{seed}-{number}-{i}".encode()
        if kind == "mvcc":
            # read (at "absent") and write the key the tx before wrote
            key = f"{channel}-b{number}k{i - 1:04d}"
        results = serialize_tx_rwset(
            rw.TxRwSet(
                (
                    rw.NsRwSet(
                        CHAINCODE,
                        (rw.KVRead(key, None),),
                        (rw.KVWrite(key, False, value),),
                    ),
                )
            )
        )
        client = world["clients"][i % 3]
        endorsers = [world["peers"][i % 3], world["peers"][(i + 1) % 3]]
        if kind == "short_endorsement":
            endorsers = endorsers[:1]
        bundle = create_proposal(
            client, channel, CHAINCODE, [b"put", key.encode()]
        )
        responses = [endorse_proposal(bundle, e, results) for e in endorsers]
        if kind == "high_s":
            r, s = der.unmarshal_signature(responses[1].endorsement.signature)
            responses[1].endorsement.signature = der.marshal_signature(
                r, p256.N - s
            )
        env = create_signed_tx(bundle, client, responses)
        if kind == "bad_creator":
            env.signature = client.sign(b"not this payload")
        block.data.data.append(env.SerializeToString())
        lanes += 1 + len(endorsers)
        if kind is None:
            state[key] = value
        else:
            codes[i] = int(expected_code[kind])
            if kind != "mvcc":
                state[key] = None
    protoutil.seal_block(block)
    entry = {
        "raw": block.SerializeToString(), "codes": codes, "state": state,
        "lanes": lanes,
    }
    return entry, protoutil.block_header_hash(block.header)


def build_chain(world, channel, n_blocks, n_txs, seed):
    rng = random.Random(f"{seed}:{channel}")
    chain, prev = [], b""
    for number in range(n_blocks):
        entry, prev = build_block(
            world, channel, number, prev, n_txs, rng, seed
        )
        chain.append(entry)
    return chain


def parse(raw: bytes):
    from fabric_tpu.protos import common_pb2

    block = common_pb2.Block()
    block.ParseFromString(raw)
    return block


# ---------------------------------------------------------------------------
# phase: commit
# ---------------------------------------------------------------------------


def commit_chain(world, chain, provider, ledger_dir, run: str, want_bucket):
    """Commit `chain` into a fresh ledger through Channel + CommitPipeline.
    Returns (filters per block, height, state of every written key)."""
    from fabric_tpu.crypto.tpu_provider import _bucket
    from fabric_tpu.peer.channel import Channel
    from fabric_tpu.peer.pipeline import CommitPipeline
    from fabric_tpu.protos import common_pb2

    channel = Channel(
        CHANNEL, ledger_dir, msp_manager(world, provider),
        world["registry"], provider,
    )
    committed_at: Dict[int, float] = {}
    pipe = CommitPipeline(
        channel,
        on_commit=lambda b, f: committed_at.__setitem__(
            b.header.number, time.perf_counter()
        ),
    )
    try:
        # back to back, as a deliver loop would: block N+1 is prepared
        # (parsed, dispatched to the device) while block N commits
        submitted, prepared = [], []
        for entry in chain:
            check_bucket(entry["lanes"], _bucket(entry["lanes"]), want_bucket)
            submitted.append(time.perf_counter())
            pipe.submit(parse(entry["raw"]))
            prepared.append(time.perf_counter())
        if not pipe.drain(timeout=1100):
            raise SeamGaveWay(
                f"the chain did not commit in time: {len(committed_at)} of "
                f"{len(chain)} blocks, last_error {pipe.last_error!r}"
            )
        check_pipeline(pipe)
        for number, entry in enumerate(chain):
            say(
                phase="commit", run=run, block=number, lanes=entry["lanes"],
                bucket=_bucket(entry["lanes"]),
                backend=provider.describe_backend(),
                smoke_prepare_s=round(prepared[number] - submitted[number], 3),
                smoke_submit_to_commit_s=round(
                    committed_at[number] - submitted[number], 3
                ),
                note=(
                    "includes the compile"
                    if number == 0 and run == "device" else ""
                ),
            )
    finally:
        pipe.stop()
    try:
        ledger = channel.ledger
        filters = [
            bytes(
                ledger.block_store.get_block_by_number(n).metadata.metadata[
                    common_pb2.TRANSACTIONS_FILTER
                ]
            )
            for n in range(ledger.height)
        ]
        state = {
            key: ledger.get_state(CHAINCODE, key)
            for entry in chain for key in entry["state"]
        }
        return filters, ledger.height, state
    finally:
        channel.ledger.close()


def phase_commit(world, chain, device_provider, tmp, want_bucket, obs, compiles):
    from fabric_tpu.common.txflags import TxValidationCode as Code
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    oracle = commit_chain(
        world, chain, SoftwareProvider(), os.path.join(tmp, "oracle"),
        "oracle", want_bucket,
    )
    compiles.since_mark()  # the oracle run compiles nothing of interest
    device = commit_chain(
        world, chain, device_provider, os.path.join(tmp, "device"),
        "device", want_bucket,
    )
    check_provider_seams(device_provider)
    (o_filters, o_height, o_state) = oracle
    (d_filters, d_height, d_state) = device
    if not (d_height == o_height == len(chain)):
        raise SeamGaveWay(
            f"ledger height: device {d_height}, oracle {o_height}, "
            f"chain {len(chain)}"
        )
    non_valid = 0
    for n, entry in enumerate(chain):
        check_same_bytes(
            f"block {n} TRANSACTIONS_FILTER", d_filters[n], o_filters[n]
        )
        want = bytearray(len(d_filters[n]))  # VALID = 0 everywhere ...
        for i, code in entry["codes"].items():
            want[i] = code  # ... but at the poisoned indices
        check_same_bytes(
            f"block {n} TRANSACTIONS_FILTER vs the poison plan",
            d_filters[n], bytes(want),
        )
        non_valid += sum(1 for c in d_filters[n] if c != int(Code.VALID))
        for key, value in entry["state"].items():
            if not (d_state[key] == o_state[key] == value):
                raise SeamGaveWay(
                    f"state of {key!r}: device {d_state[key]!r}, oracle "
                    f"{o_state[key]!r}, expected {value!r}"
                )
    if non_valid == 0:
        raise SeamGaveWay("every transaction VALID: the poison plan is empty")
    sent = sum(entry["lanes"] for entry in chain)
    check_device_lanes(obs.snapshot(), sent)
    say(
        phase="commit", result="identical to the SoftwareProvider run",
        blocks=len(chain), height=d_height, non_valid_txs=non_valid,
        keys_read_back=len(d_state), device_lanes=sent,
        backend=device_provider.describe_backend(), **compiles.since_mark(),
    )
    return sent


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def block_lanes(world, raw: bytes):
    """(keys, sigs, digests) of a block exactly as the commit path
    flattens them."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.validation.blockparse import parse_block
    from fabric_tpu.validation.validator import BlockValidator

    sw = SoftwareProvider()
    validator = BlockValidator(
        CHANNEL, msp_manager(world, sw), sw, world["registry"]
    )
    _, _, keys, sigs, digests = validator.collect_sig_jobs(
        parse_block(list(parse(raw).data.data))
    )
    return list(keys), list(sigs), list(digests)


def phase_serve(world, chain, tmp, seed, want_bucket, obs, compiles, sent_before):
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.crypto.tpu_provider import _bucket
    from fabric_tpu.serve.client import SidecarProvider
    from fabric_tpu.serve.server import SidecarServer

    rng = random.Random(f"{seed}:serve")
    sw = SoftwareProvider()
    before = set(threading.enumerate())
    # warm_ladder="off" and no warm(): the first request compiles the one
    # bucket bring-up serves
    address = os.path.join(tmp, "serve.sock")
    if len(address.encode()) > 100:  # AF_UNIX sun_path holds 108 bytes
        address = "127.0.0.1:0"
    server = SidecarServer(
        address=address, engine="device",
        warm_ladder="off", buckets=(want_bucket or FULL_BUCKET,),
    )
    server.start()
    client = SidecarProvider(address=server.address)
    sent = 0
    try:
        for req in range(SERVE_REQUESTS):
            keys, sigs, digests = block_lanes(
                world, chain[req % len(chain)]["raw"]
            )
            damaged = rng.sample(range(len(keys)), 2 * SERVE_DAMAGED)
            for lane in damaged[:SERVE_DAMAGED]:
                keys[lane] = None
            for lane in damaged[SERVE_DAMAGED:]:
                sigs[lane] = b"\x30\x07garbage"
            check_bucket(len(keys), _bucket(len(keys)), want_bucket)
            want = sw.batch_verify(keys, sigs, digests)
            t0 = time.perf_counter()
            got = client.batch_verify(keys, sigs, digests)
            wall = time.perf_counter() - t0
            check_sidecar_client(client)
            check_provider_seams(server.provider)
            check_same_bytes(
                f"serve request {req} mask",
                bytes(bool(v) for v in got), bytes(bool(v) for v in want),
            )
            sent += len(keys)
            say(
                phase="serve", request=req, lanes=len(keys),
                lanes_true=sum(1 for v in got if v),
                bucket=_bucket(len(keys)),
                backend=server.provider.describe_backend(),
                smoke_request_wall_s=round(wall, 3),
            )
        stats = client.client.stats()
    finally:
        client.client.close()
        server.stop()
    served = stats["stats"]
    if not (
        stats["engine"] == "device"
        and served["lanes"] == sent
        and stats["batched_lanes"] == sent
        and served["requests"] == SERVE_REQUESTS
        and served["errors"] == 0
        and served["rejects"] == 0
    ):
        raise SeamGaveWay(f"OP_STATS does not show {sent} device lanes: {stats}")
    check_device_lanes(obs.snapshot(), sent_before + sent)
    deadline = time.monotonic() + 5.0
    while True:
        leaked = [
            t.name for t in threading.enumerate()
            if t not in before and t.is_alive()
        ]
        if not leaked or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    if leaked:
        raise SeamGaveWay(f"sidecar stop left threads running: {leaked}")
    say(
        phase="serve", result="masks equal the SoftwareProvider masks",
        requests=SERVE_REQUESTS, device_lanes=sent,
        engine=stats["engine"], launches=stats["launches"],
        **compiles.since_mark(),
    )
    return sent


# ---------------------------------------------------------------------------
# phase: four chips
# ---------------------------------------------------------------------------


def phase_four_chips(world, n_txs, seed, want_bucket, compiles):
    import jax

    from fabric_tpu.common.txflags import TxValidationCode as Code
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.crypto.tpu_provider import _bucket
    from fabric_tpu.parallel.mesh import grid_mesh
    from fabric_tpu.parallel.multichannel import MultiChannelValidator
    from fabric_tpu.validation.validator import BlockValidator

    channels = [f"smoke{i}" for i in range(4)]
    sw = SoftwareProvider()

    def validator(ch):
        return BlockValidator(
            ch, msp_manager(world, sw), sw, world["registry"]
        )

    blocks, expected, plans = {}, {}, {}
    for ch in channels:
        (entry,) = build_chain(world, ch, 1, n_txs, seed)
        check_bucket(entry["lanes"], _bucket(entry["lanes"]), want_bucket)
        blocks[ch] = parse(entry["raw"])
        plans[ch] = entry
        expected[ch] = validator(ch).validate(parse(entry["raw"])).tobytes()
    compiles.since_mark()

    # one channel per chip: channels are independent (no collective in
    # the program), so (4, 1) needs no cross-chip traffic at all
    mesh = grid_mesh(4, 1, jax.devices())
    mc = MultiChannelValidator(mesh, {ch: validator(ch) for ch in channels})
    t0 = time.perf_counter()
    flags = mc.validate(blocks)
    wall = time.perf_counter() - t0
    non_valid = 0
    for ch in channels:
        check_same_bytes(f"channel {ch} mask", flags[ch].tobytes(), expected[ch])
        want = bytearray(len(expected[ch]))
        for i, code in plans[ch]["codes"].items():
            if code != int(Code.MVCC_READ_CONFLICT):
                want[i] = code  # validate() alone runs no MVCC
        check_same_bytes(
            f"channel {ch} mask vs the poison plan", expected[ch], bytes(want)
        )
        non_valid += sum(1 for c in expected[ch] if c)
    if non_valid == 0:
        raise SeamGaveWay("every transaction VALID: the poison plan is empty")
    # where the sharded program's output lived, as the validator read it
    # off the device array before copying it back
    if len(mc.last_device_ids) != 4:
        raise SeamGaveWay(
            "the sharded program's output is not on four devices: "
            f"{sorted(mc.last_device_ids)}"
        )
    say(
        phase="chips4", result="masks equal the per-channel SoftwareProvider masks",
        channels=len(channels), txs_per_channel=n_txs,
        lanes_per_channel=[plans[ch]["lanes"] for ch in channels],
        mesh={k: int(v) for k, v in mesh.shape.items()},
        output_device_ids=sorted(mc.last_device_ids),
        non_valid_txs=non_valid, smoke_validate_wall_s=round(wall, 3),
        note="the wall time includes the compile",
        **compiles.since_mark(),
    )


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run the four-chip phase and its host oracle, nothing else",
    )
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="rehearsal on the CPU backend at 2 blocks x 64 tx; constructs "
        "TPUProvider() directly, skips only the platform and "
        "default_provider() checks, and its last line says \"ok\": false",
    )
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    # a failed dispatch must set `degraded` at once (and trip the seam
    # check) instead of sleeping 1+3+9 s through the retry ladder first
    os.environ["FABRIC_TPU_DISPATCH_RETRIES"] = "1"

    import jax

    devices = jax.devices()  # no probe thread, no subprocess: this IS the owner
    if not args.rehearse_on_cpu:
        check_platform(devices, args.chips)
    elif args.chips == 4 and len(devices) < 4:
        raise SeamGaveWay(
            "rehearse --chips 4 with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=4"
        )
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }

    from fabric_tpu.common import fabobs
    from fabric_tpu.crypto.bccsp import default_provider
    from fabric_tpu.utils import native

    check_no_serve_env(os.environ)
    obs = fabobs.enable()
    if args.rehearse_on_cpu:
        from fabric_tpu.crypto.tpu_provider import TPUProvider

        provider = TPUProvider()
        n_blocks, n_txs, want_bucket = TINY_BLOCKS, TINY_TXS, None
    else:
        provider = default_provider()
        check_default_provider(provider)
        n_blocks, n_txs, want_bucket = FULL_BLOCKS, FULL_TXS, FULL_BUCKET
    check_provider_seams(provider)
    compiles = CompileLog()
    say(
        phase="start", device=device, seed=args.seed,
        native_library=native.available(),
        native_library_why_not=native.why_unavailable(),
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        rehearsal="cpu" if args.rehearse_on_cpu else None,
    )

    world = build_world()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            phase_four_chips(world, n_txs, args.seed, want_bucket, compiles)
        else:
            chain = build_chain(world, CHANNEL, n_blocks, n_txs, args.seed)
            sent = phase_commit(
                world, chain, provider, tmp, want_bucket, obs, compiles
            )
            phase_serve(
                world, chain, tmp, args.seed, want_bucket, obs, compiles, sent
            )
            check_provider_seams(provider)

    say(phase="done", smoke_total_wall_s=round(time.perf_counter() - t_start, 1))
    verdict = {"ok": not args.rehearse_on_cpu, "device": device}
    if args.rehearse_on_cpu:
        verdict["rehearsal"] = "cpu: not a chip pass"
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SeamGaveWay as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        sys.exit(1)
