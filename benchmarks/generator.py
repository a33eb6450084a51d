"""The traffic generator: one general builder of Fabric blocks, driven by a
configuration file and a traffic file.

``build_world`` / ``pick_poisons`` / ``build_envelopes`` / ``seal_block``
are copied out of ``chip_smoke.py`` (PR 22), where they passed on the chip;
the one change is that a block's poison plan is drawn from an RNG of the
block's own (``seed:channel:number``), so that blocks can be built in any
order and in several processes.

``--seed`` fixes what the generator controls: which transactions are
poisoned and how, the keys and values written, which served lanes are
damaged.  Org keys, certificates and ECDSA nonces come from the OS RNG as in
every entry point of this repo; the reference sees the very same bytes, and
the work a block asks for does not depend on them.

Building a 500-tx block is ~1,500 ECDSA signatures on the host.  A window
needs hundreds of blocks, so they are built by forked workers (ForkedWorkers,
below) while the parent warms the program up.
"""

from __future__ import annotations

import multiprocessing
import os
import random
from typing import Callable, Dict, Iterator, List, Optional, Tuple

CHAINCODE = "cc"


def build_world(config: Dict):
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.msp.cryptogen import generate_org
    from fabric_tpu.msp.signer import SigningIdentity
    from fabric_tpu.policy import from_dsl
    from fabric_tpu.validation.validator import (
        ChaincodeDefinition,
        ChaincodeRegistry,
    )

    sw = SoftwareProvider()
    n = int(config["orgs"])
    orgs = [
        generate_org(f"org{i}.example.com", f"Org{i}MSP")
        for i in range(1, n + 1)
    ]
    return {
        "orgs": orgs,
        "registry": ChaincodeRegistry(
            [ChaincodeDefinition(CHAINCODE, from_dsl(config["policy_dsl"]))]
        ),
        "clients": [SigningIdentity(o.users[0], sw) for o in orgs],
        "peers": [SigningIdentity(o.peers[0], sw) for o in orgs],
    }


def msp_manager(world, provider):
    from fabric_tpu.msp.identity import MSPManager

    return MSPManager([o.msp(provider=provider) for o in world["orgs"]])


def msp_roots(world) -> Dict[str, bytes]:
    """{MSP id: the org CA's certificate, PEM} — what the plain reference is
    given as the channel's membership configuration."""
    return {o.msp_id: bytes(o.ca.cert_pem) for o in world["orgs"]}


def pick_poisons(
    rng: random.Random, n_txs: int, per_kind: int
) -> Dict[int, str]:
    """{tx index: kind}. An mvcc tx reads the key the tx before it
    writes, so both its neighbours stay clean."""
    poisons: Dict[int, str] = {}
    blocked: set = set()
    for kind in ("mvcc", "bad_creator", "short_endorsement", "high_s"):
        placed = 0
        while placed < per_kind:
            i = rng.randrange(1, n_txs - 1)
            span = {i - 1, i, i + 1} if kind == "mvcc" else {i}
            if span & blocked:
                continue
            blocked |= span
            poisons[i] = kind
            placed += 1
    return poisons


def build_envelopes(world, config: Dict, number: int, seed: int) -> Dict:
    """The transactions of block `number`: {"envelopes": serialized
    envelopes, "codes": expected {tx: code}, "state": expected {key:
    value|None}, "lanes": signature lanes}."""
    from fabric_tpu.common import der, p256
    from fabric_tpu.common.txflags import TxValidationCode as Code
    from fabric_tpu.endorser import (
        create_proposal,
        create_signed_tx,
        endorse_proposal,
    )
    from fabric_tpu.ledger import rwset as rw
    from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset

    channel = config["channel"]
    n_txs = int(config["block_txs"])
    n_orgs = int(config["orgs"])
    rng = random.Random(f"{seed}:{channel}:{number}")
    poisons = pick_poisons(rng, n_txs, int(config["poisons_per_kind"]))
    expected_code = {
        "bad_creator": Code.BAD_CREATOR_SIGNATURE,
        "short_endorsement": Code.ENDORSEMENT_POLICY_FAILURE,
        "high_s": Code.ENDORSEMENT_POLICY_FAILURE,
        "mvcc": Code.MVCC_READ_CONFLICT,
    }
    envelopes: List[bytes] = []
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
        client = world["clients"][i % n_orgs]
        endorsers = [
            world["peers"][i % n_orgs], world["peers"][(i + 1) % n_orgs]
        ]
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
        envelopes.append(env.SerializeToString())
        lanes += 1 + len(endorsers)
        if kind is None:
            state[key] = value
        else:
            codes[i] = int(expected_code[kind])
            if kind != "mvcc":
                state[key] = None
    return {
        "envelopes": envelopes, "codes": codes, "state": state, "lanes": lanes,
    }


def seal_block(entry: Dict, number: int, prev_hash: bytes) -> bytes:
    """Put a built block's envelopes into the chain at `number`: adds
    "raw" (the serialized block) and "number" to the entry and returns the
    header hash the next block points at."""
    from fabric_tpu.protos import protoutil

    block = protoutil.new_block(number, prev_hash)
    for env in entry.pop("envelopes"):
        block.data.data.append(env)
    protoutil.seal_block(block)
    entry["raw"] = block.SerializeToString()
    entry["number"] = number
    return protoutil.block_header_hash(block.header)


def parse_block_bytes(raw: bytes):
    from fabric_tpu.protos import common_pb2

    block = common_pb2.Block()
    block.ParseFromString(raw)
    return block


def block_lanes(world, config: Dict, envelopes: List[bytes], seed: int,
                number: int) -> Dict:
    """One served request: the (keys, sigs, digests) of a block exactly as
    the commit path flattens them, with `damaged_lanes_per_kind` lanes each
    of no key and of garbage DER.  Keys travel as (x, y) or None."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.validation.blockparse import parse_block
    from fabric_tpu.validation.validator import BlockValidator

    cached = world.get("_lane_validator")
    if cached is None:
        sw = SoftwareProvider()
        cached = world["_lane_validator"] = BlockValidator(
            config["channel"], msp_manager(world, sw), sw, world["registry"]
        )
    _, _, keys, sigs, digests = cached.collect_sig_jobs(
        parse_block(list(envelopes))
    )
    points: List[Optional[Tuple[int, int]]] = [(k.x, k.y) for k in keys]
    sigs = [bytes(s) for s in sigs]
    digests = [bytes(d) for d in digests]
    damaged = int(config["damaged_lanes_per_kind"])
    rng = random.Random(f"{seed}:serve:{number}")
    picked = rng.sample(range(len(points)), 2 * damaged)
    for lane in picked[:damaged]:
        points[lane] = None
    for lane in picked[damaged:]:
        sigs[lane] = b"\x30\x07garbage"
    return {"number": number, "points": points, "sigs": sigs,
            "digests": digests, "lanes": len(points)}


# ---------------------------------------------------------------------------
# forked workers
# ---------------------------------------------------------------------------


def _worker_loop(conn, jobs: Dict[str, Callable]) -> None:
    """One worker: runs its share of a batch when told, keeps the results,
    and sends them one by one when they are collected."""
    kept: Dict[int, List] = {}
    while True:
        message = conn.recv()
        if message[0] == "stop":
            return
        if message[0] == "run":
            _, batch, name, items = message
            try:
                kept[batch] = [("ok", jobs[name](item)) for item in items]
            except Exception as exc:  # noqa: BLE001 - reported to the parent on collect
                kept[batch] = [("error", repr(exc))] * len(items)
        elif message[0] == "collect":
            for row in kept.pop(message[1]):
                conn.send(row)


class ForkedWorkers:
    """Named jobs in forked worker processes.  ``start(name, items)`` deals
    the items out (item i to worker i mod n) and returns at once; the
    workers compute and KEEP their results, so that nothing of it runs in
    this process while it traces and compiles (a pool's result thread would
    unpickle hundreds of megabytes under the GIL beside the warm-up: that
    cost cell 1 25 s of set-up, PR 26).  ``collect(batch)`` then yields the
    results in order.  Create it before the process has a thread or a JAX
    backend (the world's private keys cannot be pickled, which rules
    ``spawn`` out; a fork with no thread running is safe); the workers sleep
    through the window and serve the reference's share of the output check
    after it.  ``close()`` ends and joins every worker."""

    def __init__(self, jobs: Dict[str, Callable], workers: int):
        self._jobs = jobs
        self._procs: List = []
        self._conns: List = []
        self._batches: Dict[int, object] = {}
        self._next_batch = 0
        if workers > 1:
            ctx = multiprocessing.get_context("fork")
            for _ in range(workers):
                ours, theirs = ctx.Pipe()
                proc = ctx.Process(
                    target=_worker_loop, args=(theirs, jobs), daemon=True
                )
                proc.start()
                theirs.close()
                self._procs.append(proc)
                self._conns.append(ours)

    def start(self, name: str, items) -> int:
        items = list(items)
        batch = self._next_batch
        self._next_batch += 1
        if not self._conns:
            self._batches[batch] = (name, items)
            return batch
        n = len(self._conns)
        for w, conn in enumerate(self._conns):
            conn.send(("run", batch, name, items[w::n]))
        self._batches[batch] = len(items)
        return batch

    def collect(self, batch: int) -> Iterator:
        held = self._batches.pop(batch)
        if not self._conns:
            name, items = held
            for item in items:
                yield self._jobs[name](item)
            return
        for conn in self._conns:
            conn.send(("collect", batch))
        n = len(self._conns)
        for i in range(held):
            status, value = self._conns[i % n].recv()
            if status != "ok":
                raise RuntimeError(f"a forked worker failed: {value}")
            yield value

    def run(self, name: str, items) -> List:
        return list(self.collect(self.start(name, items)))

    def close(self) -> None:
        for conn in self._conns:
            try:
                conn.send(("stop",))
            except (OSError, ValueError):
                pass
        for proc in self._procs:
            proc.join(timeout=5)
            if proc.is_alive():
                proc.terminate()
                proc.join()
        for conn in self._conns:
            conn.close()
        self._procs, self._conns = [], []


def worker_count(traffic: Dict) -> int:
    """The traffic file's number of workers, but never more than the cores
    this process may run on less the one it keeps."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    return max(1, min(int(traffic["workers"]), cores - 1))
