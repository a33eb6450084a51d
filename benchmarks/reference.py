"""The plain reference: what a Fabric peer has to answer for a block, and a
verifier for a batch of signature lanes, written straight from the public
protocol and importing nothing of ``fabric_tpu``.

It reads the same bytes the system under test is given (serialized blocks,
the org CAs' certificates, the policy's org list) and takes nothing the
program has made.  ECDSA and X.509 come from the ``cryptography`` package
(OpenSSL); protobuf messages are read with the small wire-format reader
below, by the field numbers of the public Fabric protos.

Semantics implemented, in the order a committing peer applies them:

1. creator: the identity is a certificate issued by its MSP's CA and the
   envelope signature verifies over the payload, low-S — else
   BAD_CREATOR_SIGNATURE (4);
2. endorsement policy ``OutOf(n, members of the listed MSPs)``: at least n
   distinct listed MSPs have a valid low-S endorsement over
   ``proposal_response_payload || endorser`` — else
   ENDORSEMENT_POLICY_FAILURE (10);
3. MVCC in block order: every read's version equals the committed version
   (earlier valid transactions of the same block included) — else
   MVCC_READ_CONFLICT (11); a valid transaction's writes get the version
   (block, tx).

``break_rule`` turns one of those guarantees off: that is the *control* the
output check has to fail (see PERF.md, "How correct is decided").
"""

from __future__ import annotations

import hashlib
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from cryptography import x509
from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives import hashes
from cryptography.hazmat.primitives.asymmetric import ec, utils

VALID = 0
BAD_CREATOR_SIGNATURE = 4
ENDORSEMENT_POLICY_FAILURE = 10
MVCC_READ_CONFLICT = 11

P256_N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551

BREAK_RULES = ("accept_high_s", "skip_policy", "skip_mvcc")


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf: bytes) -> List[Tuple[int, object]]:
    """[(field number, value)]: an int for varints and fixed-width fields,
    bytes for length-delimited ones."""
    out: List[Tuple[int, object]] = []
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value = bytes(buf[pos:pos + size])
            pos += size
        elif wire == 1:
            value = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 5:
            value = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in a Fabric message")
        out.append((number, value))
    if pos != end:
        raise ValueError("truncated protobuf message")
    return out


def first(buf: bytes, number: int, default=b""):
    for n, value in fields(buf):
        if n == number:
            return value
    return default


def every(buf: bytes, number: int) -> List:
    return [value for n, value in fields(buf) if n == number]


# ---------------------------------------------------------------------------
# signatures and identities
# ---------------------------------------------------------------------------


PREHASHED = ec.ECDSA(utils.Prehashed(hashes.SHA256()))


def verify_digest(public_key, signature: bytes, digest: bytes,
                  accept_high_s: bool = False) -> bool:
    """Fabric's rule for one lane: strict DER, s in the lower half of the
    group order, and the ECDSA equation over the 32-byte digest."""
    if public_key is None:
        return False
    try:
        r, s = utils.decode_dss_signature(signature)
    except ValueError:
        return False
    if not (0 < r < P256_N and 0 < s < P256_N):
        return False
    if s > P256_N // 2 and not accept_high_s:
        return False
    try:
        public_key.verify(signature, digest, PREHASHED)
    except InvalidSignature:
        return False
    return True


def public_key_from_point(point: Optional[Tuple[int, int]]):
    if point is None:
        return None
    try:
        return ec.EllipticCurvePublicNumbers(
            point[0], point[1], ec.SECP256R1()
        ).public_key()
    except ValueError:  # not on the curve
        return None


def verify_lanes(points: Sequence[Optional[Tuple[int, int]]],
                 signatures: Sequence[bytes], digests: Sequence[bytes],
                 break_rule: Optional[str] = None) -> List[bool]:
    """The mask a validation sidecar owes for one request."""
    keys: Dict[Optional[Tuple[int, int]], object] = {}
    mask = []
    for point, sig, digest in zip(points, signatures, digests):
        if point not in keys:
            keys[point] = public_key_from_point(point)
        mask.append(verify_digest(
            keys[point], sig, digest,
            accept_high_s=(break_rule == "accept_high_s"),
        ))
    return mask


class Membership:
    """The channel's MSPs: {MSP id: CA certificate (PEM)}.  An identity is
    valid when its certificate was issued by the CA of the MSP it names."""

    def __init__(self, roots: Dict[str, bytes]):
        self._roots = {
            mspid: x509.load_pem_x509_certificate(pem)
            for mspid, pem in roots.items()
        }
        self._cache: Dict[bytes, Tuple[str, object]] = {}

    def identity(self, serialized: bytes) -> Tuple[str, object]:
        """(MSP id, public key) — the key is None for an identity that no
        MSP of the channel vouches for."""
        hit = self._cache.get(serialized)
        if hit is not None:
            return hit
        mspid = first(serialized, 1, b"").decode("utf-8", "replace")
        key = None
        root = self._roots.get(mspid)
        if root is not None:
            try:
                cert = x509.load_pem_x509_certificate(
                    first(serialized, 2, b"")
                )
                cert.verify_directly_issued_by(root)
                key = cert.public_key()
            except Exception:  # noqa: BLE001 - any parse/verify failure: not a member
                key = None
        self._cache[serialized] = (mspid, key)
        return mspid, key


# ---------------------------------------------------------------------------
# a block
# ---------------------------------------------------------------------------


def block_envelopes(raw_block: bytes) -> Tuple[int, List[bytes]]:
    """(block number, serialized envelopes) of a serialized common.Block."""
    header = first(raw_block, 1)
    return int(first(header, 1, 0)), every(first(raw_block, 2), 1)


def _rwset(results: bytes):
    """[(namespace, reads [(key, version|None)], writes [(key, is_delete,
    value)])] of a serialized TxReadWriteSet."""
    out = []
    for ns in every(results, 2):
        namespace = first(ns, 1, b"").decode()
        kv = first(ns, 2)
        reads = []
        for read in every(kv, 1):
            version = None
            raw_version = first(read, 2, None)
            if raw_version is not None:
                version = (int(first(raw_version, 1, 0)),
                           int(first(raw_version, 2, 0)))
            reads.append((first(read, 1, b"").decode(), version))
        writes = [
            (first(w, 1, b"").decode(), bool(first(w, 2, 0)), first(w, 3, b""))
            for w in every(kv, 3)
        ]
        out.append((namespace, reads, writes))
    return out


def check_signatures_and_policy(
    envelopes: Iterable[bytes], membership: Membership, policy: Dict,
    break_rule: Optional[str] = None,
) -> List[Tuple[int, list]]:
    """Steps 1 and 2 for each transaction: [(code before MVCC, rwset)].
    Independent of every other block, so blocks can be checked in any
    order."""
    accept_high_s = break_rule == "accept_high_s"
    listed = set(policy["mspids"])
    out = []
    for env in envelopes:
        payload, signature = first(env, 1), first(env, 2)
        sig_header = first(first(payload, 1), 2)
        _, creator_key = membership.identity(first(sig_header, 1))
        action = first(first(payload, 2), 1)  # Transaction.actions[0]
        endorsed = first(first(action, 2), 2)  # ChaincodeActionPayload.action
        prp = first(endorsed, 1)
        rwset = _rwset(first(first(prp, 2), 1))
        if not verify_digest(
            creator_key, signature, hashlib.sha256(payload).digest(),
            accept_high_s,
        ):
            out.append((BAD_CREATOR_SIGNATURE, rwset))
            continue
        satisfied = set()
        for endorsement in every(endorsed, 2):
            endorser = first(endorsement, 1)
            mspid, key = membership.identity(endorser)
            if mspid in listed and verify_digest(
                key, first(endorsement, 2),
                hashlib.sha256(prp + endorser).digest(), accept_high_s,
            ):
                satisfied.add(mspid)
        if len(satisfied) < int(policy["n"]) and break_rule != "skip_policy":
            out.append((ENDORSEMENT_POLICY_FAILURE, rwset))
            continue
        out.append((VALID, rwset))
    return out


class Ledger:
    """Step 3 and the state it leaves: blocks applied in order."""

    def __init__(self, break_rule: Optional[str] = None):
        self.state: Dict[Tuple[str, str], Tuple[bytes, Tuple[int, int]]] = {}
        self.height = 0
        self.filters: List[bytes] = []
        self._skip_mvcc = break_rule == "skip_mvcc"

    def commit(self, number: int, checked: List[Tuple[int, list]]) -> bytes:
        if number != self.height:
            raise ValueError(f"block {number} at height {self.height}")
        codes = bytearray(len(checked))
        for tx, (code, rwset) in enumerate(checked):
            if code == VALID and not self._skip_mvcc:
                for namespace, reads, _ in rwset:
                    for key, version in reads:
                        held = self.state.get((namespace, key))
                        if (held[1] if held else None) != version:
                            code = MVCC_READ_CONFLICT
            codes[tx] = code
            if code != VALID:
                continue
            for namespace, _, writes in rwset:
                for key, is_delete, value in writes:
                    if is_delete:
                        self.state.pop((namespace, key), None)
                    else:
                        self.state[(namespace, key)] = (value, (number, tx))
        self.height += 1
        self.filters.append(bytes(codes))
        return bytes(codes)

    def get(self, namespace: str, key: str) -> Optional[bytes]:
        held = self.state.get((namespace, key))
        return held[0] if held else None


def written_keys(checked: List[Tuple[int, list]]) -> List[Tuple[str, str]]:
    """Every (namespace, key) a block's transactions ask to write, valid
    or not: the keys whose state the comparison reads back."""
    return [
        (namespace, key)
        for _, rwset in checked
        for namespace, _, writes in rwset
        for key, _, _ in writes
    ]
