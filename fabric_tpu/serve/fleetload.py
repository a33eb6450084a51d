"""Fleet load generator: one "peer" process driving a sidecar fleet.

The ROADMAP's fleet-scale acceptance needs N *processes* (not threads)
multiplexing one warm sidecar — real sockets, real process isolation,
zipf channel skew.  This module is that peer: it signs a mixed
valid/invalid lane set once, then drives ``--requests`` batches through
the ``SidecarProvider`` (or the ``SidecarRouter`` when ``--endpoints``
lists a fleet) under one channel + admission class, asserting every
mask against the by-construction ground truth, and prints ONE JSON
summary line (requests, ok, mask_mismatches, busy_rejects, degraded,
p50/p99 ms, lanes/s) — the contract ``tests/test_fleet.py`` drives as
subprocesses::

    python -m fabric_tpu.serve.fleetload --address /tmp/s.sock \
        --channel paychan --qos high --requests 16 --lanes 256 --seed 3
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time
from typing import List, Optional, Sequence, Tuple

from fabric_tpu.common import p256
from fabric_tpu.serve import protocol as proto

LANE_KINDS = ("good", "bad_sig", "high_s", "garbage")


def build_lanes(
    n: int, seed: int
) -> Tuple[List, List[bytes], List[bytes], List[bool]]:
    """Mixed valid/invalid lanes with exact expected verdicts (the
    serve_gate corruption recipe, seeded per peer)."""
    from fabric_tpu.crypto import der, hostec
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey

    d_priv = 0xF1EE7 + seed * 7919
    pub = ECDSAPublicKey(*hostec.scalar_base_mult(d_priv))
    keys, sigs, digests, expected = [], [], [], []
    for i in range(n):
        digest = hashlib.sha256(
            b"fleetload lane %d %d" % (seed, i)
        ).digest()
        r, s = hostec.sign_digest(d_priv, digest)
        sig = der.marshal_signature(r, s)
        kind = LANE_KINDS[i % len(LANE_KINDS)]
        if kind == "bad_sig":
            bad = bytearray(sig)
            bad[-1] ^= 0x5A
            sig = bytes(bad)
        elif kind == "high_s":
            sig = der.marshal_signature(r, p256.N - s)
        elif kind == "garbage":
            sig = b"\x00\x01garbage"
        keys.append(pub)
        sigs.append(sig)
        digests.append(digest)
        expected.append(kind == "good")
    return keys, sigs, digests, expected


def _pct(sorted_s: Sequence[float], q: float) -> float:
    if not sorted_s:
        return 0.0
    i = min(len(sorted_s) - 1, max(0, int(round(q * (len(sorted_s) - 1)))))
    return sorted_s[i]


def run(
    address: Optional[str] = None,
    endpoints: Optional[Sequence[str]] = None,
    channel: str = "",
    qos: str = "normal",
    n_requests: int = 8,
    lanes: int = 256,
    seed: int = 0,
) -> dict:
    """Drive the load; returns the summary dict (also usable
    in-process by the tier-1 canary)."""
    qos_class = (
        proto.QOS_NAMES.index(qos) if qos in proto.QOS_NAMES
        else proto.DEFAULT_QOS
    )
    if endpoints:
        from fabric_tpu.serve.router import SidecarRouter

        provider = SidecarRouter(
            endpoints=endpoints, qos_class=qos_class, channel=channel
        )
    else:
        from fabric_tpu.serve.client import SidecarProvider

        provider = SidecarProvider(
            address=address, qos_class=qos_class, channel=channel
        )
    keys, sigs, digests, expected = build_lanes(lanes, seed)
    latencies: List[float] = []
    ok = mismatches = 0
    t_start = time.perf_counter()
    for _ in range(n_requests):
        t0 = time.perf_counter()
        mask = provider.batch_verify(keys, sigs, digests)
        latencies.append(time.perf_counter() - t0)
        if list(mask) == expected:
            ok += 1
        else:
            mismatches += 1
    wall_s = time.perf_counter() - t_start
    lat = sorted(latencies)
    # tail-tolerance counters (fabtail): hedge/eviction counters exist
    # on the router only, deadline expiry on both provider shapes —
    # the soak quantifies TAIL behavior, not just throughput
    per_endpoint = None
    if hasattr(provider, "describe"):
        per_endpoint = [
            {
                "address": ep["address"],
                "p99_ms": ep.get("p99_ms"),
                "ewma_ms": ep.get("ewma_ms"),
                "healthy": ep["healthy"],
            }
            for ep in provider.describe()["endpoints"]
        ]
    summary = {
        "channel": channel,
        "cls": proto.qos_name(qos_class),
        "requests": n_requests,
        "lanes_per_request": lanes,
        "ok": ok,
        "mask_mismatches": mismatches,
        "busy_rejects": provider.busy_rejects,
        "degraded": provider.degraded,
        "deadline_expired": getattr(provider, "deadline_expired", 0),
        "hedges": getattr(provider, "hedges", 0),
        "hedge_wins": getattr(provider, "hedge_wins", 0),
        "slow_evictions": getattr(provider, "slow_evictions", 0),
        "p50_ms": round(_pct(lat, 0.50) * 1e3, 3),
        "p99_ms": round(_pct(lat, 0.99) * 1e3, 3),
        "wall_s": round(wall_s, 3),
        "lanes_per_s": round(n_requests * lanes / max(wall_s, 1e-9), 1),
    }
    if per_endpoint is not None:
        summary["per_endpoint"] = per_endpoint
    provider.stop()
    return summary


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="fabric_tpu.serve.fleetload",
        description="one peer process of a multi-peer sidecar soak",
    )
    ap.add_argument("--address", default="")
    ap.add_argument(
        "--endpoints", default="",
        help="comma-separated fleet addresses (routes via SidecarRouter)",
    )
    ap.add_argument("--channel", default="")
    ap.add_argument("--qos", default="normal", choices=proto.QOS_NAMES)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    endpoints = [a.strip() for a in args.endpoints.split(",") if a.strip()]
    summary = run(
        address=args.address or None,
        endpoints=endpoints or None,
        channel=args.channel,
        qos=args.qos,
        n_requests=args.requests,
        lanes=args.lanes,
        seed=args.seed,
    )
    print(json.dumps(summary, sort_keys=True), flush=True)
    # a peer that could not hold the mask contract is a failed worker
    return 0 if summary["mask_mismatches"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
