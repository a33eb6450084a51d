"""What the plain reference lacks for a path that ends at the signature and
policy checks (``MultiChannelValidator.validate``: no MVCC, no ledger write):
the TRANSACTIONS_FILTER such a path owes for a block, the generator's own
plan cut to the same rules, and the count of bytes by which two filters
differ.  Like ``reference.py`` it imports nothing of ``fabric_tpu``."""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from benchmarks import reference as ref


def sigpolicy_filter(rows: Sequence[Tuple[int, list]]) -> bytes:
    """The filter of one block from ``reference.check_signatures_and_policy``'s
    rows: each transaction's code before MVCC, which this path never runs."""
    return bytes(code for code, _ in rows)


def planned_filter(codes: Dict[int, int], n_txs: int) -> bytes:
    """The generator's poison plan as a filter of the same path: a
    read-conflict poison reads VALID here, every other one keeps its code."""
    planned = bytearray(n_txs)
    for tx, code in codes.items():
        if code != ref.MVCC_READ_CONFLICT:
            planned[tx] = code
    return bytes(planned)


def mismatch_bytes(got: bytes, want: bytes) -> int:
    """Bytes by which `got` differs from `want`: positions that differ, and
    every byte one holds more than the other."""
    return abs(len(got) - len(want)) + sum(
        1 for a, b in zip(got, want) if a != b
    )


def rejected_positions(flt: bytes) -> List[int]:
    return [tx for tx, code in enumerate(flt) if code != ref.VALID]
