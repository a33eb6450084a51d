"""fabflow — value-range + dtype abstract interpreter for fabric-tpu.

The whole ops layer rests on a hand-tuned headroom argument: radix-2^13
limbs whose <2^27 partial products are accumulated in uint32/int32 lanes
(fabric_tpu/ops/bignum.py) — one wrong widening or one extra
accumulation and a signature silently verifies wrong.  fablint checks
per-file syntax invariants and fabdep checks the import/concurrency
graph; fabflow checks the *arithmetic itself*: it abstractly interprets
the limb kernels over an interval domain (never importing the analyzed
code — same contract as fablint/fabdep, runs without jax/cryptography)
and mechanizes the 20·2^27 < 2^32 accumulator proof, plus a mask-
soundness pass proving the validation flag paths fail closed.

Analysis 1 — limb value-range / dtype (the LIMB tier: ops/, common/p256,
common/fp256bn, crypto/hostec, ledger/mvcc_device):

  Every function is interpreted flow-sensitively under the module's
  documented canonical-limb contract (array parameters hold limbs in
  [0, LIMB_MASK], dtype uint32; ``int``-annotated parameters are
  arbitrary Python ints, which cannot overflow).  Intervals propagate
  through ``+ - * << >> & | ^ % //``, ``astype``/dtype constructors and
  np/jnp promotion; Python loops with concrete trip counts (the CIOS
  outer loop, ``lax.fori_loop(0, NLIMBS, ...)``) are unrolled
  abstractly, and unknown-trip loops (``lax.scan``/``while``) run to a
  widening fixpoint.  Calls into other analyzed modules are summarized
  interprocedurally (memoized per argument signature).  MontCtx
  instances are modeled by a contract table (per-limb scalars are
  13-bit) — the table IS the per-limb fact base the headroom proof
  rests on.

  Unknown values (⊤) produce no findings: the gate proves what it can
  reach and stays quiet where precision runs out, so every finding is a
  computed bound, never a shrug.

Analysis 2 — mask soundness (the MASK tier: validation/, ledger/txparse,
parallel/, peer/pipeline): in every *flag-producing* function (one that
references TxValidationCode or calls ``set_flag``), each exception
handler must fail closed — raise, assign/return an INVALID-family code,
return an error string, delegate to a fallback validator, or hand the
exception object to a callback/logger — and must never write VALID (or
re-write NOT_VALIDATED, which leaves the flag unset).  Early ``return
TxValidationCode.VALID`` from inside a conditional is likewise flagged:
VALID is only ever assigned at the designated end of assembly.

Rules
-----
limb-overflow       a lane interval may exceed its container dtype's
                    capacity (uint32/int32/...); message carries the
                    computed worst-case interval
dtype-narrowing     astype / dtype constructor that can truncate a live
                    value (known interval outside the target range)
float-contamination a float operand (or true division ``/``) entering
                    an integer kernel lane
const-drift         re-hardcoded 13 / 20 / 0x1fff / 8192 / 260 in an
                    arithmetic context instead of LIMB_BITS / NLIMBS /
                    LIMB_MASK / RADIX_BITS from fabric_tpu.ops.bignum
mask-fail-open      an exception handler or early return in a
                    flag-producing function that can leave a lane VALID
                    or the flag unset

Suppression
-----------
Per line: ``# fabflow: disable=<rule>[,<rule>]  # <computed bound>``.  The
reason must state the actual worst-case interval the headroom bet rests
on (tests/test_fabflow.py enforces a numeric bound in every reason).

Usage
-----
    python -m fabric_tpu.tools.fabflow [--json] [--list-rules]
                                       [--rules a,b] PATH...

Exit status: 0 = clean, 1 = findings, 2 = usage/IO error.
"""

from __future__ import annotations

import ast
import json
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from fabric_tpu.tools import toolkit
from fabric_tpu.tools.toolkit import (  # noqa: F401 - re-exported API
    DEFAULT_EXCLUDES,
    FileContext,
    Finding,
    iter_py_files,
)

__version__ = "1.0"

# --------------------------------------------------------------------------
# Configuration
# --------------------------------------------------------------------------

#: The canonical limb constants (fabric_tpu/ops/bignum.py).  fabflow
#: never imports analyzed code, so it carries its own copies; the
#: const-drift rule keeps the rest of the repo honest about importing
#: the real ones.
LIMB_BITS = 13
NLIMBS = 20
LIMB_MASK = (1 << LIMB_BITS) - 1
RADIX_BITS = LIMB_BITS * NLIMBS

#: hostec_np's pair-condensed compute form (crypto/hostec_np.py):
#: adjacent radix-2^13 limbs packed two-per-uint64 at radix 2^26, with
#: one spare pair-limb of Montgomery headroom.  The L4/L32 bounds are
#: the proven `_mul_kernel` input contracts (lazy limbs carried by the
#: _FE wrapper before they exceed these).
PAIR_BITS = 2 * LIMB_BITS
PAIR_MASK = (1 << PAIR_BITS) - 1
NPAIRS = NLIMBS // 2 + 1
PAIR_L4 = 4 * (PAIR_MASK + 1) - 1
PAIR_L32 = 32 * (PAIR_MASK + 1) - 1

#: Files whose lane arithmetic carries the limb headroom contract.
#: crypto/hostbn.py rides the SAME pair-limb contracts as hostec_np
#: (PairMat/L4/L32 bounds below): its tower/group-law code drives
#: hostec_np's proven kernels with the BN modulus — the MontCtx bound
#: (m < 2^256) and the per-limb L4/L32 input contracts are
#: modulus-independent, so the mechanized headroom proof transfers.
LIMB_TIER = (
    "*fabric_tpu/ops/*.py",
    "*fabric_tpu/common/p256.py",
    "*fabric_tpu/common/fp256bn.py",
    "*fabric_tpu/crypto/hostec.py",
    "*fabric_tpu/crypto/hostec_np.py",
    "*fabric_tpu/crypto/hostbn.py",
    "*fabric_tpu/ledger/mvcc_device.py",
)

#: The device-lane subset of the limb tier: unannotated parameters here
#: are canonical limb arrays; everywhere else in the tier they are host
#: Python ints (no container to overflow).
LANE_FILES = (
    "*fabric_tpu/ops/*.py",
    "*fabric_tpu/ledger/mvcc_device.py",
)

#: Files whose exception discipline decides the VALID/INVALID mask.
#: serve/ joined with the sidecar (PR 8): the client shim's degrade
#: path RE-DERIVES the mask in-process on sidecar death, so its
#: handlers are as mask-load-bearing as the validator's own.
#: common/fabobs.py joined with the observability registry (PR 10): its
#: hooks run INSIDE every mask-critical seam, so the tier proves the
#: wrappers themselves never write a flag or fail open — obs code must
#: be provably unable to alter masks, not just trusted not to.
MASK_TIER = (
    "*fabric_tpu/validation/*.py",
    "*fabric_tpu/ledger/txparse.py",
    "*fabric_tpu/parallel/*.py",
    "*fabric_tpu/peer/pipeline.py",
    "*fabric_tpu/serve/*.py",
    "*fabric_tpu/common/fabobs.py",
)

#: Hardcoded literal -> the canonical name that should be imported.
DRIFT_CONSTANTS = {
    13: "LIMB_BITS",
    20: "NLIMBS",
    8191: "LIMB_MASK",
    8192: "1 << LIMB_BITS",
    260: "RADIX_BITS",
}

#: TxValidationCode members that may never be written in an exception
#: handler: VALID fails open, NOT_VALIDATED leaves the flag unset.
FAIL_OPEN_MEMBERS = {"VALID", "NOT_VALIDATED"}

#: Interpreter budgets: loop-unroll cap, fixpoint iteration cap, and
#: abstract-step budget per analyzed function (bail to ⊤ beyond).
MAX_UNROLL = 512
MAX_FIXPOINT = 24
FUNC_STEP_BUDGET = 400_000
MAX_CALL_DEPTH = 10

# --------------------------------------------------------------------------
# Findings / suppression plumbing (tools.toolkit, shared with
# fablint/fabdep/fabreg)
# --------------------------------------------------------------------------


RULES: Dict[str, str] = {
    "limb-overflow": (
        "computed lane interval may exceed the container dtype's capacity"
    ),
    "dtype-narrowing": (
        "astype/dtype constructor can truncate a live value (known "
        "interval outside the target dtype's range)"
    ),
    "float-contamination": (
        "float operand or true division '/' entering an integer kernel lane"
    ),
    "const-drift": (
        "re-hardcoded limb constant (13/20/0x1fff/8192/260); import "
        "LIMB_BITS/NLIMBS/LIMB_MASK/RADIX_BITS from fabric_tpu.ops.bignum"
    ),
    "mask-fail-open": (
        "exception handler or early return in a flag-producing function "
        "can leave a lane VALID or the flag unset"
    ),
}

def parse_suppressions(source: str) -> Dict[int, Tuple[Set[str], str]]:
    """line -> (disabled rule ids, reason text)."""
    return toolkit.parse_suppressions(source, "fabflow")


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# --------------------------------------------------------------------------
# Interval domain
# --------------------------------------------------------------------------

_INF = float("inf")

#: Widening thresholds: the limb-proof landmarks (LIMB_MASK, 2^26/2^27
#: partial products, dtype capacities) so loop-carried accumulators
#: stabilize on the bound that actually matters.
_THRESHOLDS = sorted(
    {
        0, 1, 2, 16, 255, 256, LIMB_MASK, 1 << LIMB_BITS, 65535, 65536,
        1 << 26, 1 << 27, NLIMBS << 27, (1 << 31) - 1, 1 << 31,
        (1 << 32) - 1, 1 << 32, (1 << 63) - 1, (1 << 64) - 1,
        1 << 256, 1 << RADIX_BITS,
    }
)
_NEG_THRESHOLDS = sorted({-t for t in _THRESHOLDS})


class Interval:
    """[lo, hi] over Python ints; None = unbounded on that side."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: Optional[int], hi: Optional[int]):
        self.lo = lo
        self.hi = hi

    def __repr__(self) -> str:
        lo = "-inf" if self.lo is None else self.lo
        hi = "+inf" if self.hi is None else self.hi
        return f"[{lo}, {hi}]"

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Interval)
            and self.lo == other.lo
            and self.hi == other.hi
        )

    def __hash__(self) -> int:
        return hash((self.lo, self.hi))

    # -- helpers ----------------------------------------------------------
    def _flo(self) -> float:
        return -_INF if self.lo is None else self.lo

    def _fhi(self) -> float:
        return _INF if self.hi is None else self.hi

    @property
    def is_top(self) -> bool:
        return self.lo is None and self.hi is None

    def const(self) -> Optional[int]:
        """The single concrete value, if this interval is a point."""
        if self.lo is not None and self.lo == self.hi:
            return self.lo
        return None

    def nonneg(self) -> bool:
        return self.lo is not None and self.lo >= 0

    def within(self, lo: Optional[int], hi: Optional[int]) -> bool:
        if lo is not None and (self.lo is None or self.lo < lo):
            return False
        if hi is not None and (self.hi is None or self.hi > hi):
            return False
        return True

    @staticmethod
    def _wrap(v: float) -> Optional[int]:
        return None if v in (_INF, -_INF) else int(v)

    @classmethod
    def from_f(cls, lo: float, hi: float) -> "Interval":
        return cls(cls._wrap(lo), cls._wrap(hi))

    # -- lattice ----------------------------------------------------------
    def join(self, other: "Interval") -> "Interval":
        return Interval.from_f(
            min(self._flo(), other._flo()), max(self._fhi(), other._fhi())
        )

    def widen(self, newer: "Interval") -> "Interval":
        """Jump each moving bound to the next proof landmark so loop
        fixpoints terminate in a handful of sweeps."""
        lo: Optional[int]
        hi: Optional[int]
        if newer._flo() < self._flo():
            lo = None
            for t in reversed(_NEG_THRESHOLDS + _THRESHOLDS):
                if newer.lo is not None and t <= newer.lo:
                    lo = t
                    break
        else:
            lo = self.lo
        if newer._fhi() > self._fhi():
            hi = None
            for t in _NEG_THRESHOLDS + _THRESHOLDS:
                if newer.hi is not None and t >= newer.hi:
                    hi = t
                    break
        else:
            hi = self.hi
        return Interval(lo, hi)

    # -- arithmetic -------------------------------------------------------
    def add(self, o: "Interval") -> "Interval":
        return Interval.from_f(self._flo() + o._flo(), self._fhi() + o._fhi())

    def sub(self, o: "Interval") -> "Interval":
        return Interval.from_f(self._flo() - o._fhi(), self._fhi() - o._flo())

    def neg(self) -> "Interval":
        return Interval.from_f(-self._fhi(), -self._flo())

    def mul(self, o: "Interval") -> "Interval":
        cands = []
        for x in (self._flo(), self._fhi()):
            for y in (o._flo(), o._fhi()):
                if x == 0 or y == 0:
                    cands.append(0)
                else:
                    cands.append(x * y)
        return Interval.from_f(min(cands), max(cands))

    def lshift(self, o: "Interval") -> "Interval":
        if o.lo is None or o.lo < 0 or o.hi is None or o.hi > 512:
            return TOP_IVL
        return self.mul(Interval(1 << o.lo, 1 << o.hi))

    def rshift(self, o: "Interval") -> "Interval":
        if o.lo is None or o.lo < 0:
            return TOP_IVL
        khi = 512 if o.hi is None else min(o.hi, 512)
        cands = []
        for x in (self.lo, self.hi):
            for k in (o.lo, khi):
                if x is None:
                    return Interval(
                        None if self.lo is None else min(self.lo >> o.lo, -1, 0),
                        None if self.hi is None else max(self.hi >> o.lo, 0),
                    )
                cands.append(x >> k)
        return Interval(min(cands), max(cands))

    def and_(self, o: "Interval") -> "Interval":
        # x & m ∈ [0, m] for m >= 0, regardless of x's sign (two's
        # complement semantics of Python ints); symmetric in the mask.
        outs = []
        if o.nonneg() and o.hi is not None:
            outs.append(Interval(0, o.hi))
        if self.nonneg() and self.hi is not None:
            outs.append(Interval(0, self.hi))
        if not outs:
            return TOP_IVL
        best = outs[0]
        for iv in outs[1:]:
            if iv.hi is not None and (best.hi is None or iv.hi < best.hi):
                best = iv
        return best

    def or_(self, o: "Interval") -> "Interval":
        if self.nonneg() and o.nonneg():
            # a | b <= a + b for non-negative operands
            return Interval.from_f(
                max(self._flo(), o._flo()), self._fhi() + o._fhi()
            )
        return TOP_IVL

    def xor(self, o: "Interval") -> "Interval":
        if self.nonneg() and o.nonneg():
            return Interval.from_f(0, self._fhi() + o._fhi())
        return TOP_IVL

    def mod(self, o: "Interval") -> "Interval":
        if o.lo is not None and o.lo > 0 and o.hi is not None:
            if self.nonneg() and self.hi is not None and self.hi < o.lo:
                return self
            return Interval(0, o.hi - 1)
        return TOP_IVL

    def floordiv(self, o: "Interval") -> "Interval":
        if o.lo is None or o.lo < 1 or o.hi is None:
            return TOP_IVL
        if self.lo is None or self.hi is None:
            return TOP_IVL
        cands = [
            x // y for x in (self.lo, self.hi) for y in (o.lo, o.hi)
        ]
        return Interval(min(cands), max(cands))


TOP_IVL = Interval(None, None)

# --------------------------------------------------------------------------
# Dtypes
# --------------------------------------------------------------------------

#: name -> (min, max, is_float).  'pyint'/'pyfloat' are host Python
#: scalars (no container to overflow).
DTYPES: Dict[str, Tuple[Optional[int], Optional[int], bool]] = {
    "bool": (0, 1, False),
    "uint8": (0, (1 << 8) - 1, False),
    "uint16": (0, (1 << 16) - 1, False),
    "uint32": (0, (1 << 32) - 1, False),
    "uint64": (0, (1 << 64) - 1, False),
    "int8": (-(1 << 7), (1 << 7) - 1, False),
    "int16": (-(1 << 15), (1 << 15) - 1, False),
    "int32": (-(1 << 31), (1 << 31) - 1, False),
    "int64": (-(1 << 63), (1 << 63) - 1, False),
    "float16": (None, None, True),
    "float32": (None, None, True),
    "float64": (None, None, True),
    "pyint": (None, None, False),
    "pyfloat": (None, None, True),
}

_INT_WIDTH = {
    "bool": 8, "uint8": 8, "int8": 8, "uint16": 16, "int16": 16,
    "uint32": 32, "int32": 32, "uint64": 64, "int64": 64,
}


def dtype_is_float(dt: Optional[str]) -> bool:
    return dt is not None and DTYPES.get(dt, (None, None, False))[2]


def dtype_is_lane_int(dt: Optional[str]) -> bool:
    """A fixed-width integer lane (NOT a host Python int)."""
    return dt in _INT_WIDTH and dt != "bool"


def promote(d1: Optional[str], d2: Optional[str]) -> Optional[str]:
    """jax-x32-flavored promotion, just precise enough for the kernels:
    python scalars are weak, float wins, mixed signedness goes signed at
    the wider width."""
    if d1 == d2:
        return d1
    if d1 is None or d2 is None:
        return None
    if d1 == "pyint":
        return d2 if d2 != "bool" else "pyint"
    if d2 == "pyint":
        return d1 if d1 != "bool" else "pyint"
    f1, f2 = dtype_is_float(d1), dtype_is_float(d2)
    if f1 or f2:
        if d1 == "pyfloat":
            return d2 if f2 else "float32"
        if d2 == "pyfloat":
            return d1 if f1 else "float32"
        if f1 and f2:
            return d1 if _FLOAT_ORDER.get(d1, 0) >= _FLOAT_ORDER.get(d2, 0) else d2
        return d1 if f1 else d2
    if d1 == "bool":
        return d2
    if d2 == "bool":
        return d1
    w = max(_INT_WIDTH[d1], _INT_WIDTH[d2])
    signed = d1.startswith("int") or d2.startswith("int")
    return ("int" if signed else "uint") + str(w)


_FLOAT_ORDER = {"float16": 1, "float32": 2, "float64": 3, "pyfloat": 2}


# --------------------------------------------------------------------------
# Abstract values
# --------------------------------------------------------------------------


class AbsVal:
    """Base abstract value; UNKNOWN (⊤) is the silent default."""

    def key(self, depth: int = 3):
        return "?"


class _Unknown(AbsVal):
    def __repr__(self) -> str:
        return "⊤"


UNKNOWN = _Unknown()


class NoneVal(AbsVal):
    def __repr__(self) -> str:
        return "None"

    def key(self, depth: int = 3):
        return "None"


NONE = NoneVal()


class Num(AbsVal):
    """An integer/float lane (scalar or array): interval + dtype."""

    __slots__ = ("ivl", "dtype")

    def __init__(self, ivl: Interval, dtype: Optional[str]):
        self.ivl = ivl
        self.dtype = dtype

    def __repr__(self) -> str:
        return f"Num({self.ivl}, {self.dtype})"

    def key(self, depth: int = 3):
        return ("N", self.ivl.lo, self.ivl.hi, self.dtype)

    def const(self) -> Optional[int]:
        if self.dtype in ("pyint", "bool") or self.dtype is None:
            return self.ivl.const()
        return None


def num_const(v: int) -> Num:
    return Num(Interval(v, v), "pyint")


def num_bool(v: Optional[bool] = None) -> Num:
    if v is None:
        return Num(Interval(0, 1), "bool")
    return Num(Interval(int(v), int(v)), "bool")


LIMB_DTYPE = "uint32"


def limb_num() -> Num:
    """The canonical-limb parameter assumption: [0, LIMB_MASK] uint32."""
    return Num(Interval(0, LIMB_MASK), LIMB_DTYPE)


class SeqVal(AbsVal):
    """List/tuple: known items, or an element summary when unknown."""

    __slots__ = ("items", "elem", "mutable")

    def __init__(
        self,
        items: Optional[List[AbsVal]] = None,
        elem: AbsVal = UNKNOWN,
        mutable: bool = True,
    ):
        self.items = items
        self.elem = elem
        self.mutable = mutable

    def __repr__(self) -> str:
        if self.items is not None:
            return f"Seq[{len(self.items)}]"
        return f"Seq[?:{self.elem!r}]"

    def key(self, depth: int = 3):
        if depth <= 0:
            return "Seq…"
        if self.items is not None:
            if len(self.items) > 24:
                return ("S", len(self.items), self.summary().key(depth - 1))
            return ("S",) + tuple(v.key(depth - 1) for v in self.items)
        return ("S?", self.elem.key(depth - 1))

    def summary(self) -> AbsVal:
        if self.items is None:
            return self.elem
        out: Optional[AbsVal] = None
        for it in self.items:
            out = it if out is None else join(out, it)
        return out if out is not None else UNKNOWN

    def getitem(self, idx: Optional[int]) -> AbsVal:
        if self.items is not None and idx is not None:
            if -len(self.items) <= idx < len(self.items):
                return self.items[idx]
            return UNKNOWN
        return self.summary()


def limb_seq(n: int = NLIMBS, dtype: str = LIMB_DTYPE) -> SeqVal:
    return SeqVal(items=[Num(Interval(0, LIMB_MASK), dtype) for _ in range(n)])


class ConstVal(AbsVal):
    """A concrete non-numeric Python constant (str/bytes)."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def key(self, depth: int = 3):
        return ("C", repr(self.value)[:40])


class FuncVal(AbsVal):
    """A function defined in an analyzed module (optionally bound).

    `closure` is the environment of the function a nested def or lambda
    was defined in — the dict itself, not a copy, so a call sees what the
    enclosing names hold when it is made, as Python does.  Without it the
    body of a `lax.fori_loop` / `lax.scan` reads ⊤ for every operand it
    closes over, and a proof about the loop is a proof about nothing."""

    __slots__ = ("mod", "node", "qualname", "selfval", "closure")

    def __init__(self, mod, node, qualname, selfval=None, closure=None):
        self.mod = mod
        self.node = node
        self.qualname = qualname
        self.selfval = selfval
        self.closure = closure

    def closure_key(self):
        """The closed-over values a call can read, for the call memo."""
        if not self.closure:
            return ()
        read = {
            n.id for n in ast.walk(self.node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
        }
        return tuple(
            (name, self.closure[name].key())
            for name in sorted(read & self.closure.keys())
        )

    def key(self, depth: int = 3):
        return ("F", self.mod.name, self.qualname)


class ClassVal(AbsVal):
    __slots__ = ("mod", "node")

    def __init__(self, mod, node):
        self.mod = mod
        self.node = node

    def key(self, depth: int = 3):
        return ("K", self.mod.name, self.node.name)


class InstanceVal(AbsVal):
    """An instance of an analyzed class: attr map + optional contract."""

    __slots__ = ("cls_name", "attrs", "contract", "clsval")

    def __init__(self, cls_name, attrs=None, contract=None, clsval=None):
        self.cls_name = cls_name
        self.attrs = attrs if attrs is not None else {}
        self.contract = contract
        self.clsval = clsval

    def key(self, depth: int = 3):
        return ("I", self.cls_name, self.contract)


class ModVal(AbsVal):
    """Reference to an analyzed module or an intrinsic namespace."""

    __slots__ = ("modinfo", "intrinsic")

    def __init__(self, modinfo=None, intrinsic: Optional[str] = None):
        self.modinfo = modinfo
        self.intrinsic = intrinsic

    def key(self, depth: int = 3):
        return ("M", self.intrinsic or (self.modinfo and self.modinfo.name))


class IntrinsicVal(AbsVal):
    """A builtin/numpy/jax callable modeled by a handler."""

    __slots__ = ("name", "handler")

    def __init__(self, name: str, handler):
        self.name = name
        self.handler = handler

    def key(self, depth: int = 3):
        return ("X", self.name)


class MethodVal(AbsVal):
    """A recognized method on an abstract receiver (astype, append...)."""

    __slots__ = ("name", "recv")

    def __init__(self, name: str, recv: AbsVal):
        self.name = name
        self.recv = recv

    def key(self, depth: int = 3):
        return ("m", self.name, self.recv.key(depth - 1))


class RangeVal(AbsVal):
    """range() with possibly-unknown bounds."""

    __slots__ = ("lo", "hi", "step")

    def __init__(self, lo: Num, hi: Num, step: int = 1):
        self.lo = lo
        self.hi = hi
        self.step = step

    def key(self, depth: int = 3):
        return ("R", self.lo.key(1), self.hi.key(1), self.step)


def join(a: AbsVal, b: AbsVal) -> AbsVal:
    if a is b:
        return a
    if isinstance(a, Num) and isinstance(b, Num):
        dt = a.dtype if a.dtype == b.dtype else promote(a.dtype, b.dtype)
        return Num(a.ivl.join(b.ivl), dt)
    if isinstance(a, SeqVal) and isinstance(b, SeqVal):
        if (
            a.items is not None
            and b.items is not None
            and len(a.items) == len(b.items)
        ):
            return SeqVal(
                items=[join(x, y) for x, y in zip(a.items, b.items)]
            )
        return SeqVal(items=None, elem=join(a.summary(), b.summary()))
    if isinstance(a, NoneVal) and isinstance(b, NoneVal):
        return NONE
    # a guarded optional import (`try: import numpy as np / except
    # ImportError: np = None`) joins the module with None at module
    # scope; keep the module binding — the limb kernels only execute in
    # the dependency-present world, and that is the world whose value
    # ranges the gate must prove (joining to ⊤ would silence them).
    if isinstance(a, ModVal) and isinstance(b, NoneVal):
        return a
    if isinstance(b, ModVal) and isinstance(a, NoneVal):
        return b
    if (
        isinstance(a, ConstVal)
        and isinstance(b, ConstVal)
        and a.value == b.value
    ):
        return a
    if isinstance(a, InstanceVal) and isinstance(b, InstanceVal):
        if a.cls_name == b.cls_name and a.contract == b.contract:
            return a
    if isinstance(a, FuncVal) and isinstance(b, FuncVal):
        if a.qualname == b.qualname and a.mod is b.mod:
            return a
    return UNKNOWN


def widen_val(prev: AbsVal, newer: AbsVal) -> AbsVal:
    if isinstance(prev, Num) and isinstance(newer, Num):
        dt = prev.dtype if prev.dtype == newer.dtype else promote(
            prev.dtype, newer.dtype
        )
        return Num(prev.ivl.widen(newer.ivl), dt)
    if (
        isinstance(prev, SeqVal)
        and isinstance(newer, SeqVal)
        and prev.items is not None
        and newer.items is not None
        and len(prev.items) == len(newer.items)
    ):
        return SeqVal(
            items=[widen_val(x, y) for x, y in zip(prev.items, newer.items)]
        )
    j = join(prev, newer)
    if isinstance(j, SeqVal) and isinstance(prev, SeqVal):
        if isinstance(prev.summary(), Num) and isinstance(j.summary(), Num):
            return SeqVal(
                items=None,
                elem=widen_val(prev.summary(), j.summary()),
            )
    return j


# --------------------------------------------------------------------------
# Module universe
# --------------------------------------------------------------------------


class ModuleInfo:
    """One parsed source file: AST + import map + lazily-built globals."""

    def __init__(self, name: str, path: str, tree: ast.Module, source: str):
        self.name = name
        self.path = path
        self.tree = tree
        self.source = source
        self.imports: Dict[str, str] = {}       # alias -> dotted module
        self.import_froms: Dict[str, Tuple[str, str]] = {}  # name -> (mod, attr)
        self.functions: Dict[str, ast.AST] = {}
        self.classes: Dict[str, ast.ClassDef] = {}
        self.globals: Dict[str, AbsVal] = {}
        self.eval_state = "new"  # new | evaluating | done
        self._index()

    def _index(self) -> None:
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    self.imports[alias.asname or alias.name.split(".")[0]] = (
                        alias.name
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    if alias.name != "*":
                        self.import_froms[alias.asname or alias.name] = (
                            node.module, alias.name
                        )


def module_name_for(path: str) -> str:
    parts = Path(path).as_posix().split("/")
    if "fabric_tpu" in parts:
        i = parts.index("fabric_tpu")
        dotted = ".".join(parts[i:])
    else:
        dotted = parts[-1]
    if dotted.endswith(".py"):
        dotted = dotted[: -len(".py")]
    if dotted.endswith(".__init__"):
        dotted = dotted[: -len(".__init__")]
    return dotted


#: intrinsic namespaces recognized by dotted import name
_INTRINSIC_MODULES = {
    "numpy": "numpy",
    "jax": "jax",
    "jax.numpy": "numpy",
    "jax.lax": "lax",
    "jax.ops": "jaxops",
    "math": "math",
    "os": "opaque",
    "threading": "opaque",
    "contextlib": "opaque",
    "functools": "functools",
    "hashlib": "opaque",
    "secrets": "opaque",
    "typing": "opaque",
    "enum": "opaque",
    "queue": "opaque",
    "time": "opaque",
}


# --------------------------------------------------------------------------
# Contracts: MontCtx (the per-limb fact base of the headroom proof)
# --------------------------------------------------------------------------


def _montctx_attr(name: str) -> AbsVal:
    if name in ("m_limbs", "r2_limbs", "one_mont", "one"):
        return limb_seq()
    if name in ("m_scalars_i32",):
        return limb_seq(dtype="int32")
    if name == "m0inv":
        return Num(Interval(0, LIMB_MASK), "uint32")
    if name == "km_scalars_i32":
        # dict k -> int32 limb tuple; modeled as "subscript anything ->
        # int32 limb seq" via a SeqVal summary
        return SeqVal(items=None, elem=limb_seq(dtype="int32"))
    if name == "m":
        return Num(Interval(1, (1 << 256) - 1), "pyint")
    return UNKNOWN


def _montctx_method(name: str):
    if name == "const":
        def const(args, kwargs, interp, node):
            return limb_seq()
        return const
    return None


# --------------------------------------------------------------------------
# Control-flow signals
# --------------------------------------------------------------------------


class _Budget(Exception):
    pass


class _BreakSig(Exception):
    pass


class _ContinueSig(Exception):
    pass


# --------------------------------------------------------------------------
# The abstract interpreter
# --------------------------------------------------------------------------


class Analyzer:
    """Drives interprocedural interval analysis over a module universe."""

    def __init__(
        self,
        universe: Dict[str, ModuleInfo],
        enabled_rules: Set[str],
        suppressions: Dict[str, Dict[int, Tuple[Set[str], str]]],
    ):
        self.universe = universe
        self.enabled = enabled_rules
        self.suppressions = suppressions
        self.findings: Dict[Tuple[str, int, str], Finding] = {}
        self.suppressed = 0
        self._suppressed_keys: Set[Tuple[str, int, str]] = set()
        self.suppressed_findings: List[Finding] = []
        self.memo: Dict[tuple, AbsVal] = {}
        self.in_flight: Set[tuple] = set()

    # -- findings ---------------------------------------------------------
    def report(
        self, rule: str, mod: ModuleInfo, node: ast.AST, message: str
    ) -> None:
        if rule not in self.enabled:
            return
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        key = (mod.path, line, rule)
        if key in self.findings or key in self._suppressed_keys:
            return
        sup = self.suppressions.get(mod.path, {}).get(line)
        if sup is not None and (rule in sup[0] or "all" in sup[0]):
            self.suppressed += 1
            self._suppressed_keys.add(key)
            self.suppressed_findings.append(
                Finding(rule, mod.path, line, col, message)
            )
            return
        self.findings[key] = Finding(rule, mod.path, line, col, message)

    # -- module env -------------------------------------------------------
    def resolve_module(self, dotted: str) -> Optional[ModuleInfo]:
        mod = self.universe.get(dotted)
        if mod is not None:
            return mod
        # the txflags/validation shim family: exact name only, no guessing
        return None

    def module_env(self, mod: ModuleInfo) -> Dict[str, AbsVal]:
        if mod.eval_state == "done":
            return mod.globals
        if mod.eval_state == "evaluating":
            return mod.globals  # import cycle: partial env is sound (⊤s)
        mod.eval_state = "evaluating"
        interp = Interp(self, mod, dict(mod.globals), depth=0,
                        budget=[FUNC_STEP_BUDGET])
        try:
            interp.exec_block(mod.tree.body)
        except _Budget:
            pass
        except RecursionError:
            pass
        mod.globals.update(interp.env)
        mod.eval_state = "done"
        return mod.globals

    # -- interprocedural summaries ---------------------------------------
    def call_function(
        self,
        fv: FuncVal,
        args: List[AbsVal],
        kwargs: Dict[str, AbsVal],
        depth: int,
        budget: List[int],
    ) -> AbsVal:
        if depth > MAX_CALL_DEPTH:
            return UNKNOWN
        node = fv.node
        if isinstance(node, ast.Lambda):
            return self._run_callable(fv, node, args, kwargs, depth, budget)
        key = (
            fv.mod.name,
            fv.qualname,
            tuple(a.key() for a in args),
            tuple(sorted((k, v.key()) for k, v in kwargs.items())),
            fv.closure_key(),
        )
        if key in self.memo:
            return self.memo[key]
        if key in self.in_flight:
            return UNKNOWN  # recursion
        self.in_flight.add(key)
        try:
            out = self._run_callable(fv, node, args, kwargs, depth, budget)
        finally:
            self.in_flight.discard(key)
        self.memo[key] = out
        return out

    def _run_callable(self, fv, node, args, kwargs, depth, budget) -> AbsVal:
        env: Dict[str, AbsVal] = dict(fv.closure) if fv.closure else {}
        a = node.args
        pos = list(args)
        params = list(a.posonlyargs) + list(a.args)
        if fv.selfval is not None:
            pos = [fv.selfval] + pos
        defaults = list(a.defaults)
        # align defaults to the tail of params
        def_off = len(params) - len(defaults)
        for i, p in enumerate(params):
            if i < len(pos):
                env[p.arg] = pos[i]
            elif p.arg in kwargs:
                env[p.arg] = kwargs[p.arg]
            elif i >= def_off:
                env[p.arg] = Interp(
                    self, fv.mod, {}, depth, budget
                ).eval(defaults[i - def_off])
            else:
                env[p.arg] = UNKNOWN
        if a.vararg is not None:
            env[a.vararg.arg] = SeqVal(items=None, elem=UNKNOWN)
        for i, p in enumerate(a.kwonlyargs):
            if p.arg in kwargs:
                env[p.arg] = kwargs[p.arg]
            elif a.kw_defaults[i] is not None:
                env[p.arg] = Interp(
                    self, fv.mod, {}, depth, budget
                ).eval(a.kw_defaults[i])
            else:
                env[p.arg] = UNKNOWN
        if a.kwarg is not None:
            env[a.kwarg.arg] = UNKNOWN
        interp = Interp(self, fv.mod, env, depth + 1, budget)
        if isinstance(node, ast.Lambda):
            try:
                return interp.eval(node.body)
            except (_Budget, RecursionError):
                return UNKNOWN
        try:
            interp.exec_block(node.body)
        except (_Budget, RecursionError):
            return UNKNOWN
        return interp.return_value()

    # -- standalone analysis entry ---------------------------------------
    def default_param(
        self, annotation: Optional[ast.AST], lane: bool = True
    ) -> AbsVal:
        """Parameter assumption under the canonical-limb contract.

        `lane` is True for device-lane files (ops/, mvcc_device): an
        unannotated parameter there is a canonical limb array.  Host
        big-int files (common/p256, common/fp256bn, crypto/hostec) work
        in Python ints, which cannot overflow."""
        ann = _dotted(annotation) if annotation is not None else None
        if isinstance(annotation, ast.Constant) and isinstance(
            annotation.value, str
        ):
            ann = annotation.value
        if isinstance(annotation, ast.Subscript):
            base = _dotted(annotation.value)
            leafb = (base or "").rsplit(".", 1)[-1]
            if leafb == "Optional":
                return self.default_param(annotation.slice, lane)
            if leafb in ("Sequence", "List", "Tuple"):
                inner = annotation.slice
                if isinstance(inner, ast.Tuple):
                    # Tuple[A, B, ...]: per-position element assumptions
                    elts = [e for e in inner.elts if not (
                        isinstance(e, ast.Constant) and e.value is Ellipsis
                    )]
                    if len(elts) == 1 and len(inner.elts) == 2:
                        elem = self.default_param(elts[0], lane)
                        if isinstance(elem, Num) and elem.dtype == LIMB_DTYPE:
                            return SeqVal(
                                items=[limb_num() for _ in range(NLIMBS)]
                            )
                        return SeqVal(items=None, elem=elem)
                    return SeqVal(
                        items=[self.default_param(e, lane) for e in elts]
                    )
                elem = self.default_param(inner, lane)
                if isinstance(elem, Num) and elem.dtype == LIMB_DTYPE:
                    # Sequence[jax.Array]: the canonical limb tuple
                    return SeqVal(items=[limb_num() for _ in range(NLIMBS)])
                return SeqVal(items=None, elem=elem)
        if ann is None:
            return limb_num() if lane else Num(TOP_IVL, "pyint")
        leaf = ann.rsplit(".", 1)[-1]
        if leaf == "int":
            return Num(TOP_IVL, "pyint")
        if leaf == "float":
            return Num(TOP_IVL, "pyfloat")
        if leaf == "bool":
            return num_bool()
        if leaf in ("bytes", "str"):
            return UNKNOWN
        if leaf in ("LimbVec", "Rows"):
            return SeqVal(items=[limb_num() for _ in range(NLIMBS)])
        if leaf in ("Array", "ndarray"):
            return limb_num()
        # hostec_np pair-limb contracts (string annotations on the numpy
        # kernels; bounds enforced at runtime by the _FE wrapper)
        if leaf == "PairMat":
            return Num(Interval(0, PAIR_MASK), "uint64")
        if leaf == "PairMatL4":
            return Num(Interval(0, PAIR_L4), "uint64")
        if leaf == "PairMatL32":
            return Num(Interval(0, PAIR_L32), "uint64")
        if leaf == "AccMat":
            # the REDC sweep's accumulator: the MAC phase's proven bound
            return Num(Interval(0, NPAIRS * (PAIR_L32 + 1) * (PAIR_L4 + 1)), "uint64")
        if leaf == "BiasMat":
            # the REDC complement-fold bias (K*m minus the constant
            # over-add, < m): canonical pair limbs
            return Num(Interval(0, PAIR_MASK), "uint64")
        if leaf in ("Lanes",):
            return SeqVal(items=None, elem=Num(TOP_IVL, "pyint"))
        if leaf == "MontCtx":
            return InstanceVal("MontCtx", contract="montctx")
        return UNKNOWN

    def analyze_function_standalone(
        self, mod: ModuleInfo, node, qualname: str, selfval: Optional[AbsVal]
    ) -> None:
        env: Dict[str, AbsVal] = {}
        lane = FileContext(mod.path).matches(LANE_FILES)
        a = node.args
        params = list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        # map parameter name -> default expression (aligned to the tail)
        pos_params = list(a.posonlyargs) + list(a.args)
        defaults: Dict[str, ast.AST] = {}
        for p, d in zip(pos_params[len(pos_params) - len(a.defaults):],
                        a.defaults):
            defaults[p.arg] = d
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if d is not None:
                defaults[p.arg] = d
        start = 0
        if selfval is not None and params:
            env[params[0].arg] = selfval
            start = 1
        for p in params[start:]:
            if p.annotation is None and p.arg in defaults:
                # an unannotated param with a scalar default is a config
                # scalar (bound counts, window sizes), never a limb lane
                d = defaults[p.arg]
                if isinstance(d, ast.Constant) and isinstance(
                    d.value, (int, float)
                ) and not isinstance(d.value, bool):
                    env[p.arg] = Num(
                        TOP_IVL,
                        "pyfloat" if isinstance(d.value, float) else "pyint",
                    )
                    continue
            env[p.arg] = self.default_param(p.annotation, lane)
        if a.vararg is not None:
            env[a.vararg.arg] = SeqVal(
                items=None, elem=limb_num() if lane else Num(TOP_IVL, "pyint")
            )
        if a.kwarg is not None:
            env[a.kwarg.arg] = UNKNOWN
        interp = Interp(self, mod, env, depth=1, budget=[FUNC_STEP_BUDGET])
        try:
            interp.exec_block(node.body)
        except (_Budget, RecursionError):
            pass


class NamedTupleVal(SeqVal):
    """NamedTuple instance: a known-length tuple with field names."""

    def __init__(self, items: List[AbsVal], fields: Dict[str, int]):
        super().__init__(items=items)
        self.fields = fields

    def key(self, depth: int = 3):
        return ("NT",) + tuple(v.key(depth - 1) for v in (self.items or []))


class DictVal(AbsVal):
    """Dict summary: join of values (keys untracked)."""

    __slots__ = ("vals",)

    def __init__(self, vals: AbsVal = UNKNOWN):
        self.vals = vals

    def key(self, depth: int = 3):
        return ("D", self.vals.key(depth - 1))


class DtypeVal(AbsVal):
    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def key(self, depth: int = 3):
        return ("dt", self.name)


def as_dtype(v: AbsVal) -> Optional[str]:
    if isinstance(v, DtypeVal):
        return v.name
    if isinstance(v, ConstVal) and isinstance(v.value, str):
        return v.value if v.value in DTYPES else None
    return None


def numify(v: AbsVal) -> AbsVal:
    """Collapse a sequence to its lane summary (stack/concatenate)."""
    if isinstance(v, Num):
        return v
    if isinstance(v, SeqVal):
        s = v.summary()
        if isinstance(s, Num):
            return s
        if isinstance(s, SeqVal):
            inner = numify(s)
            return inner if isinstance(inner, Num) else UNKNOWN
    return UNKNOWN


def truth(v: AbsVal) -> Optional[bool]:
    if isinstance(v, Num):
        c = v.ivl.const()
        if c is not None and v.dtype in ("bool", "pyint"):
            return bool(c)
        if v.dtype in ("bool", "pyint") and v.ivl.lo is not None and v.ivl.lo > 0:
            return True
        return None
    if isinstance(v, NoneVal):
        return False
    if isinstance(v, ConstVal):
        return bool(v.value)
    if isinstance(v, SeqVal) and v.items is not None:
        return len(v.items) > 0
    return None


def join_env(e1: Dict[str, AbsVal], e2: Dict[str, AbsVal]) -> Dict[str, AbsVal]:
    out: Dict[str, AbsVal] = {}
    for k in set(e1) | set(e2):
        a, b = e1.get(k), e2.get(k)
        if a is None or b is None:
            out[k] = UNKNOWN if (a or b) is None else (a or b)
            if a is None and b is not None:
                out[k] = b
            elif b is None and a is not None:
                out[k] = a
        else:
            out[k] = join(a, b)
    return out


def env_key(env: Dict[str, AbsVal]) -> tuple:
    return tuple(sorted((k, v.key()) for k, v in env.items()))


class Interp:
    """Flow-sensitive abstract executor for one scope."""

    def __init__(self, analyzer: Analyzer, mod: ModuleInfo,
                 env: Dict[str, AbsVal], depth: int, budget: List[int]):
        self.an = analyzer
        self.mod = mod
        self.env = env
        self.depth = depth
        self.budget = budget
        self.returns: List[AbsVal] = []
        self.terminated = False
        ctx = FileContext(mod.path)
        self.check = ctx.matches(LIMB_TIER)

    # -- bookkeeping ------------------------------------------------------
    def step(self) -> None:
        self.budget[0] -= 1
        if self.budget[0] <= 0:
            raise _Budget()

    def return_value(self) -> AbsVal:
        out: Optional[AbsVal] = None
        for r in self.returns:
            out = r if out is None else join(out, r)
        if out is None or not self.terminated and self.returns:
            # fall-through path returns None too
            out = NONE if out is None else join(out, NONE)
        return out if out is not None else NONE

    # -- statements -------------------------------------------------------
    def exec_block(self, stmts: Sequence[ast.stmt]) -> None:
        for node in stmts:
            if self.terminated:
                return
            self.exec_stmt(node)

    def exec_stmt(self, node: ast.stmt) -> None:
        self.step()
        meth = getattr(self, "exec_" + type(node).__name__, None)
        if meth is not None:
            meth(node)
        elif isinstance(node, ast.Expr):
            self.eval(node.value)

    def exec_Expr(self, node) -> None:
        self.eval(node.value)

    def exec_Pass(self, node) -> None:
        pass

    def exec_Global(self, node) -> None:
        pass

    def exec_Nonlocal(self, node) -> None:
        pass

    def exec_Assert(self, node) -> None:
        self.eval(node.test)

    def exec_Delete(self, node) -> None:
        for t in node.targets:
            if isinstance(t, ast.Name):
                self.env.pop(t.id, None)

    def exec_Import(self, node) -> None:
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            self.env[name] = self.resolve_import(
                alias.name if alias.asname else alias.name.split(".")[0]
            )

    def exec_ImportFrom(self, node) -> None:
        if not node.module:
            return
        for alias in node.names:
            if alias.name == "*":
                continue
            self.env[alias.asname or alias.name] = self.resolve_from_import(
                node.module, alias.name
            )

    def resolve_import(self, dotted: str) -> AbsVal:
        if dotted in _INTRINSIC_MODULES:
            return ModVal(intrinsic=_INTRINSIC_MODULES[dotted])
        m = self.an.resolve_module(dotted)
        if m is not None:
            return ModVal(modinfo=m)
        return ModVal(intrinsic="opaque")

    def resolve_from_import(self, module: str, name: str) -> AbsVal:
        full = module + "." + name
        if full in _INTRINSIC_MODULES:
            return ModVal(intrinsic=_INTRINSIC_MODULES[full])
        if module in _INTRINSIC_MODULES:
            return intrinsic_attr(_INTRINSIC_MODULES[module], name)
        sub = self.an.resolve_module(full)
        if sub is not None:
            return ModVal(modinfo=sub)
        m = self.an.resolve_module(module)
        if m is not None:
            envm = self.an.module_env(m)
            if name in envm:
                return envm[name]
        # canonical-constant fallback: fixtures importing the limb
        # constants resolve even when bignum itself is not analyzed
        if module.endswith(("bignum", "limbparams", ".common")):
            if name == "LIMB_BITS":
                return num_const(LIMB_BITS)
            if name == "NLIMBS":
                return num_const(NLIMBS)
            if name == "LIMB_MASK":
                return num_const(LIMB_MASK)
            if name == "RADIX_BITS":
                return num_const(RADIX_BITS)
        return UNKNOWN

    def exec_FunctionDef(self, node) -> None:
        self.env[node.name] = FuncVal(
            self.mod, node, node.name,
            closure=self.env if self.depth > 0 else None,
        )

    exec_AsyncFunctionDef = exec_FunctionDef

    def exec_ClassDef(self, node) -> None:
        self.env[node.name] = ClassVal(self.mod, node)

    def exec_Return(self, node) -> None:
        self.returns.append(self.eval(node.value) if node.value else NONE)
        self.terminated = True

    def exec_Raise(self, node) -> None:
        if node.exc is not None:
            self.eval(node.exc)
        self.terminated = True

    def exec_Break(self, node) -> None:
        raise _BreakSig()

    def exec_Continue(self, node) -> None:
        raise _ContinueSig()

    def exec_Assign(self, node) -> None:
        val = self.eval(node.value)
        for t in node.targets:
            self.assign(t, val)

    def exec_AnnAssign(self, node) -> None:
        if node.value is not None:
            self.assign(node.target, self.eval(node.value))
        elif isinstance(node.target, ast.Name):
            self.env.setdefault(node.target.id, UNKNOWN)

    def exec_AugAssign(self, node) -> None:
        cur = self.eval(node.target)
        val = self.eval(node.value)
        out = self.binop(node.op, cur, val, node)
        self.assign(node.target, out)

    def assign(self, target: ast.AST, val: AbsVal) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = val
        elif isinstance(target, (ast.Tuple, ast.List)):
            self.unpack(target.elts, val)
        elif isinstance(target, ast.Subscript):
            self.assign_subscript(target, val)
        elif isinstance(target, ast.Attribute):
            base = self.eval(target.value)
            if isinstance(base, InstanceVal):
                prev = base.attrs.get(target.attr)
                base.attrs[target.attr] = (
                    val if prev is None else join(prev, val)
                )
        elif isinstance(target, ast.Starred):
            self.assign(target.value, SeqVal(items=None, elem=UNKNOWN))

    def unpack(self, elts: Sequence[ast.AST], val: AbsVal) -> None:
        starred = [i for i, e in enumerate(elts) if isinstance(e, ast.Starred)]
        if isinstance(val, SeqVal) and val.items is not None and not starred:
            if len(val.items) == len(elts):
                for e, v in zip(elts, val.items):
                    self.assign(e, v)
                return
        if isinstance(val, Num):
            # unpacking an array's first axis: rows share interval/dtype
            for e in elts:
                self.assign(e, val if not isinstance(e, ast.Starred) else val)
            return
        elem = val.summary() if isinstance(val, SeqVal) else UNKNOWN
        for e in elts:
            if isinstance(e, ast.Starred):
                self.assign(e.value, SeqVal(items=None, elem=elem))
            else:
                self.assign(e, elem)

    def assign_subscript(self, target: ast.Subscript, val: AbsVal) -> None:
        base = self.eval(target.value)
        if isinstance(base, SeqVal) and base.items is not None:
            idx = self.eval(target.slice)
            c = idx.const() if isinstance(idx, Num) else None
            if c is not None and -len(base.items) <= c < len(base.items):
                base.items[c] = val
                return
            if isinstance(target.slice, ast.Slice):
                s = val.summary() if isinstance(val, SeqVal) else val
                base.items[:] = [join(x, s) for x in base.items]
                return
            base.items[:] = [join(x, val) for x in base.items]
            return
        if isinstance(base, DictVal):
            base.vals = join(base.vals, val)
            return
        if isinstance(base, Num) and isinstance(target.value, ast.Name):
            v = numify(val) if not isinstance(val, Num) else val
            if isinstance(v, Num):
                if (
                    self.check
                    and dtype_is_lane_int(base.dtype)
                    and v.ivl.lo is not None
                    and v.ivl.hi is not None
                    and not v.ivl.within(*DTYPES[base.dtype][:2])
                ):
                    self.an.report(
                        "dtype-narrowing", self.mod, target,
                        f"store of value in {v.ivl} into a {base.dtype} "
                        f"array truncates",
                    )
                self.env[target.value.id] = Num(
                    base.ivl.join(v.ivl), base.dtype
                )

    def _refine(self, test: ast.AST):
        """(then_bindings, else_bindings) for `x <op> const` tests —
        enough flow sensitivity for the carry/decomp guard idioms."""
        then_b: Dict[str, AbsVal] = {}
        else_b: Dict[str, AbsVal] = {}
        if not (
            isinstance(test, ast.Compare)
            and len(test.ops) == 1
            and len(test.comparators) == 1
        ):
            return then_b, else_b
        op = test.ops[0]
        l, r = test.left, test.comparators[0]
        flip = {ast.Lt: ast.Gt, ast.Gt: ast.Lt, ast.LtE: ast.GtE,
                ast.GtE: ast.LtE}
        if not isinstance(l, ast.Name) and isinstance(r, ast.Name):
            l, r = r, l
            if type(op) in flip:
                op = flip[type(op)]()
        if not isinstance(l, ast.Name):
            return then_b, else_b
        cur = self.env.get(l.id)
        rv = self.eval(r)
        if not (isinstance(cur, Num) and isinstance(rv, Num)):
            return then_b, else_b
        c_lo, c_hi = rv.ivl.lo, rv.ivl.hi

        def cap(lo, hi):
            return Num(
                Interval(
                    lo if cur.ivl.lo is None else (
                        cur.ivl.lo if lo is None else max(cur.ivl.lo, lo)
                    ),
                    hi if cur.ivl.hi is None else (
                        cur.ivl.hi if hi is None else min(cur.ivl.hi, hi)
                    ),
                ),
                cur.dtype,
            )

        if isinstance(op, ast.Lt):
            then_b[l.id] = cap(None, None if c_hi is None else c_hi - 1)
            else_b[l.id] = cap(c_lo, None)
        elif isinstance(op, ast.LtE):
            then_b[l.id] = cap(None, c_hi)
            else_b[l.id] = cap(None if c_lo is None else c_lo + 1, None)
        elif isinstance(op, ast.Gt):
            then_b[l.id] = cap(None if c_lo is None else c_lo + 1, None)
            else_b[l.id] = cap(None, c_hi)
        elif isinstance(op, ast.GtE):
            then_b[l.id] = cap(c_lo, None)
            else_b[l.id] = cap(None, None if c_hi is None else c_hi - 1)
        return then_b, else_b

    def exec_If(self, node) -> None:
        t = truth(self.eval(node.test))
        if t is True:
            self.exec_block(node.body)
            return
        if t is False:
            self.exec_block(node.orelse)
            return
        then_b, else_b = self._refine(node.test)
        saved = dict(self.env)
        term_a = term_b = False
        self.env.update(then_b)
        try:
            self.exec_block(node.body)
        except (_BreakSig, _ContinueSig):
            term_a = True
        env_a, term_a = self.env, self.terminated or term_a
        self.terminated = False
        self.env = dict(saved)
        self.env.update(else_b)
        try:
            self.exec_block(node.orelse)
        except (_BreakSig, _ContinueSig):
            term_b = True
        env_b, term_b = self.env, self.terminated or term_b
        self.terminated = False
        if term_a and term_b:
            self.terminated = True
            self.env = join_env(env_a, env_b)
        elif term_a:
            self.env = env_b
        elif term_b:
            self.env = env_a
        else:
            self.env = join_env(env_a, env_b)

    def exec_With(self, node) -> None:
        for item in node.items:
            v = self.eval(item.context_expr)
            if item.optional_vars is not None:
                self.assign(item.optional_vars, UNKNOWN if v is None else v)
        self.exec_block(node.body)

    exec_AsyncWith = exec_With

    def exec_Try(self, node) -> None:
        pre = dict(self.env)
        self.exec_block(node.body)
        body_env, body_term = dict(self.env), self.terminated
        self.terminated = False
        if not body_term:
            self.exec_block(node.orelse)
            body_env, body_term = dict(self.env), self.terminated
            self.terminated = False
        paths: List[Dict[str, AbsVal]] = []
        if not body_term:
            paths.append(body_env)
        for h in node.handlers:
            self.env = join_env(pre, body_env)
            self.terminated = False
            if h.name:
                self.env[h.name] = UNKNOWN
            if h.type is not None:
                self.eval(h.type)
            try:
                self.exec_block(h.body)
            except (_BreakSig, _ContinueSig):
                self.terminated = True
            if not self.terminated:
                paths.append(dict(self.env))
            self.terminated = False
        if paths:
            out = paths[0]
            for p in paths[1:]:
                out = join_env(out, p)
            self.env = out
            self.terminated = False
        else:
            self.env = join_env(pre, body_env)
            self.terminated = True
        term_after = self.terminated
        self.terminated = False
        self.exec_block(node.finalbody)
        self.terminated = self.terminated or term_after

    exec_TryStar = exec_Try

    # -- loops ------------------------------------------------------------
    def concrete_items(self, it: AbsVal) -> Optional[List[AbsVal]]:
        if isinstance(it, SeqVal) and it.items is not None:
            if len(it.items) <= MAX_UNROLL:
                return list(it.items)
            return None
        if isinstance(it, RangeVal) and it.step in (1, -1):
            lo, hi = it.lo.const(), it.hi.const()
            if lo is not None and hi is not None:
                vals = list(range(lo, hi, it.step))
                if len(vals) <= MAX_UNROLL:
                    return [num_const(v) for v in vals]
        return None

    def loop_elem(self, it: AbsVal) -> AbsVal:
        if isinstance(it, SeqVal):
            return it.summary()
        if isinstance(it, RangeVal):
            lo = it.lo.ivl.lo if it.lo.ivl.lo is not None else None
            hi = it.hi.ivl.hi
            return Num(Interval(lo, None if hi is None else hi - 1), "pyint")
        if isinstance(it, DictVal):
            return UNKNOWN
        if isinstance(it, Num):
            return it
        return UNKNOWN

    def exec_For(self, node) -> None:
        it = self.eval(node.iter)
        items = self.concrete_items(it)
        if items is not None:
            broke = False
            for v in items:
                self.assign(node.target, v)
                try:
                    self.exec_block(node.body)
                except _ContinueSig:
                    continue
                except _BreakSig:
                    broke = True
                    break
                if self.terminated:
                    return
            if not broke:
                self.exec_block(node.orelse)
            return
        elem = self.loop_elem(it)
        self.fixpoint(lambda: self.assign(node.target, elem), node.body)
        self.exec_block(node.orelse)

    exec_AsyncFor = exec_For

    def exec_While(self, node) -> None:
        t = truth(self.eval(node.test))
        if t is False:
            self.exec_block(node.orelse)
            return
        self.fixpoint(lambda: self.eval(node.test), node.body)
        self.exec_block(node.orelse)

    def fixpoint(self, bind, body: Sequence[ast.stmt]) -> None:
        """Run `body` to an abstract fixpoint with widening: the loop
        state converges onto the proof thresholds or tops out."""
        state = dict(self.env)
        skey = env_key(state)
        for i in range(MAX_FIXPOINT):
            self.env = dict(state)
            bind()
            try:
                self.exec_block(body)
            except (_BreakSig, _ContinueSig):
                pass
            if self.terminated:
                # a return/raise on every path through the body: the
                # post-loop state is the pre-iteration one
                self.terminated = False
                self.env = state
                return
            merged = join_env(state, self.env)
            if i >= 2:
                for k, v in list(merged.items()):
                    pv = state.get(k)
                    if pv is not None and v.key() != pv.key():
                        merged[k] = widen_val(pv, v)
            mkey = env_key(merged)
            if mkey == skey:
                self.env = merged
                return
            state, skey = merged, mkey
        # did not converge: top out everything that still moves
        self.env = {k: UNKNOWN for k in state}
        self.env.update(
            {k: v for k, v in state.items() if isinstance(v, (FuncVal, ClassVal, ModVal))}
        )

    # -- expressions ------------------------------------------------------
    def eval(self, node: Optional[ast.AST]) -> AbsVal:
        if node is None:
            return NONE
        self.step()
        meth = getattr(self, "eval_" + type(node).__name__, None)
        if meth is None:
            return UNKNOWN
        return meth(node)

    def eval_Constant(self, node) -> AbsVal:
        v = node.value
        if isinstance(v, bool):
            return num_bool(v)
        if isinstance(v, int):
            return num_const(v)
        if isinstance(v, float):
            return Num(TOP_IVL, "pyfloat")
        if v is None:
            return NONE
        if isinstance(v, (str, bytes)):
            return ConstVal(v)
        return UNKNOWN

    def eval_Name(self, node) -> AbsVal:
        name = node.id
        if name in self.env:
            return self.env[name]
        g = self.mod.globals
        if name in g:
            return g[name]
        if name in self.mod.import_froms:
            m, attr = self.mod.import_froms[name]
            return self.resolve_from_import(m, attr)
        if name in self.mod.imports:
            return self.resolve_import(self.mod.imports[name])
        if name in self.mod.functions:
            return FuncVal(self.mod, self.mod.functions[name], name)
        if name in self.mod.classes:
            return ClassVal(self.mod, self.mod.classes[name])
        return builtin_value(name)

    def eval_NamedExpr(self, node) -> AbsVal:
        v = self.eval(node.value)
        self.assign(node.target, v)
        return v

    def eval_Tuple(self, node) -> AbsVal:
        return self._seq_literal(node, mutable=False)

    def eval_List(self, node) -> AbsVal:
        return self._seq_literal(node, mutable=True)

    def _seq_literal(self, node, mutable: bool) -> AbsVal:
        items: List[AbsVal] = []
        for e in node.elts:
            if isinstance(e, ast.Starred):
                sv = self.eval(e.value)
                if isinstance(sv, SeqVal) and sv.items is not None:
                    items.extend(sv.items)
                else:
                    return SeqVal(
                        items=None,
                        elem=join(
                            sv.summary() if isinstance(sv, SeqVal) else UNKNOWN,
                            _join_all(items),
                        ),
                        mutable=mutable,
                    )
            else:
                items.append(self.eval(e))
        return SeqVal(items=items, mutable=mutable)

    def eval_Set(self, node) -> AbsVal:
        elems = [self.eval(e) for e in node.elts]
        return SeqVal(items=None, elem=_join_all(elems))

    def eval_Dict(self, node) -> AbsVal:
        vals = [self.eval(v) for v in node.values if v is not None]
        for k in node.keys:
            if k is not None:
                self.eval(k)
        return DictVal(vals=_join_all(vals))

    def eval_JoinedStr(self, node) -> AbsVal:
        for v in node.values:
            if isinstance(v, ast.FormattedValue):
                self.eval(v.value)
        return ConstVal("")

    def eval_FormattedValue(self, node) -> AbsVal:
        self.eval(node.value)
        return ConstVal("")

    def eval_Starred(self, node) -> AbsVal:
        return self.eval(node.value)

    def eval_Slice(self, node) -> AbsVal:
        return UNKNOWN

    def eval_Lambda(self, node) -> AbsVal:
        return FuncVal(
            self.mod, node, f"<lambda:{node.lineno}>",
            closure=self.env if self.depth > 0 else None,
        )

    def eval_IfExp(self, node) -> AbsVal:
        t = truth(self.eval(node.test))
        if t is True:
            return self.eval(node.body)
        if t is False:
            return self.eval(node.orelse)
        return join(self.eval(node.body), self.eval(node.orelse))

    def eval_BoolOp(self, node) -> AbsVal:
        vals = [self.eval(v) for v in node.values]
        truths = [truth(v) for v in vals]
        if isinstance(node.op, ast.And):
            for v, t in zip(vals, truths):
                if t is False:
                    return v
            if all(t is True for t in truths):
                return vals[-1]
        else:
            for v, t in zip(vals, truths):
                if t is True:
                    return v
            if all(t is False for t in truths):
                return vals[-1]
        return _join_all(vals)

    def eval_UnaryOp(self, node) -> AbsVal:
        v = self.eval(node.operand)
        if isinstance(node.op, ast.Not):
            t = truth(v)
            return num_bool(None if t is None else not t)
        if not isinstance(v, Num):
            return UNKNOWN
        if isinstance(node.op, ast.USub):
            ivl = v.ivl.neg()
            out = Num(ivl, v.dtype)
            self._overflow_check(out, node)
            return self._clamp(out)
        if isinstance(node.op, ast.UAdd):
            return v
        if isinstance(node.op, ast.Invert):
            ivl = v.ivl.neg().sub(Interval(1, 1))
            if v.dtype == "bool":
                return num_bool()
            out = Num(ivl, v.dtype)
            self._overflow_check(out, node)
            return self._clamp(out)
        return UNKNOWN

    def eval_Compare(self, node) -> AbsVal:
        # a chain is False if ANY link is definitely False, True only if
        # EVERY link is definitely True, else unknown
        left = self.eval(node.left)
        any_unknown = False
        cur = left
        for op, comp in zip(node.ops, node.comparators):
            right = self.eval(comp)
            one = self._compare_one(op, cur, right)
            if one is False:
                return num_bool(False)
            if one is None:
                any_unknown = True
            cur = right
        return num_bool(None) if any_unknown else num_bool(True)

    def _compare_one(self, op, l: AbsVal, r: AbsVal) -> Optional[bool]:
        if isinstance(op, (ast.Is, ast.IsNot)):
            l_none = isinstance(l, NoneVal)
            r_none = isinstance(r, NoneVal)
            if l_none or r_none:
                known_not_none = isinstance(
                    l if r_none else r, (Num, SeqVal, ConstVal, InstanceVal,
                                         FuncVal, ClassVal, DictVal)
                )
                if l_none and r_none:
                    same = True
                elif known_not_none:
                    same = False
                else:
                    return None
                return same if isinstance(op, ast.Is) else not same
            return None
        if isinstance(l, ConstVal) and isinstance(r, ConstVal):
            try:
                if isinstance(op, ast.Eq):
                    return l.value == r.value
                if isinstance(op, ast.NotEq):
                    return l.value != r.value
            except Exception:
                return None
            return None
        if not (isinstance(l, Num) and isinstance(r, Num)):
            return None
        a, b = l.ivl, r.ivl
        if a.lo is None or a.hi is None or b.lo is None or b.hi is None:
            return None
        if isinstance(op, ast.Lt):
            if a.hi < b.lo:
                return True
            if a.lo >= b.hi:
                return False
        elif isinstance(op, ast.LtE):
            if a.hi <= b.lo:
                return True
            if a.lo > b.hi:
                return False
        elif isinstance(op, ast.Gt):
            if a.lo > b.hi:
                return True
            if a.hi <= b.lo:
                return False
        elif isinstance(op, ast.GtE):
            if a.lo >= b.hi:
                return True
            if a.hi < b.lo:
                return False
        elif isinstance(op, ast.Eq):
            ca, cb = a.const(), b.const()
            if ca is not None and ca == cb:
                return True
            if a.hi < b.lo or a.lo > b.hi:
                return False
        elif isinstance(op, ast.NotEq):
            ca, cb = a.const(), b.const()
            if ca is not None and ca == cb:
                return False
            if a.hi < b.lo or a.lo > b.hi:
                return True
        return None

    # -- arithmetic with the overflow checks ------------------------------
    def _clamp(self, v: Num) -> Num:
        """After a reported overflow, continue with the full container
        range (the wrapped value is somewhere in it)."""
        if v.ivl.lo is None or v.ivl.hi is None:
            # nothing was reported (provenance unknown): the value stays
            # unknown, or the next add would report a bound nobody computed
            return v
        if dtype_is_lane_int(v.dtype):
            lo, hi, _ = DTYPES[v.dtype]
            if not v.ivl.within(lo, hi):
                return Num(Interval(lo, hi), v.dtype)
        return v

    def _overflow_check(self, v: Num, node: ast.AST) -> None:
        if not self.check or not dtype_is_lane_int(v.dtype):
            return
        lo, hi, _ = DTYPES[v.dtype]
        if v.ivl.is_top or v.ivl.within(lo, hi):
            return
        if v.ivl.lo is None or v.ivl.hi is None:
            return  # half-open: provenance unknown, stay quiet
        self.an.report(
            "limb-overflow", self.mod, node,
            f"computed interval {v.ivl} exceeds {v.dtype} capacity "
            f"[{lo}, {hi}]",
        )

    def binop(self, op, l: AbsVal, r: AbsVal, node: ast.AST) -> AbsVal:
        # sequence algebra first: concat / repeat
        if isinstance(op, ast.Add) and isinstance(l, SeqVal) and isinstance(r, SeqVal):
            if l.items is not None and r.items is not None:
                return SeqVal(items=l.items + r.items)
            return SeqVal(items=None, elem=join(l.summary(), r.summary()))
        if isinstance(op, ast.Mult):
            if isinstance(l, SeqVal) and isinstance(r, Num):
                c = r.const()
                if l.items is not None and c is not None and 0 <= c * len(l.items) <= 4096:
                    return SeqVal(items=list(l.items) * c)
                return SeqVal(items=None, elem=l.summary())
            if isinstance(r, SeqVal) and isinstance(l, Num):
                return self.binop(op, r, l, node)
        if isinstance(l, ConstVal) or isinstance(r, ConstVal):
            return UNKNOWN
        ln = l if isinstance(l, Num) else numify(l)
        rn = r if isinstance(r, Num) else numify(r)
        if not (isinstance(ln, Num) and isinstance(rn, Num)):
            return UNKNOWN
        if isinstance(op, ast.Div):
            if self.check and (
                dtype_is_lane_int(ln.dtype) or dtype_is_lane_int(rn.dtype)
            ):
                self.an.report(
                    "float-contamination", self.mod, node,
                    "true division '/' on an integer kernel lane produces "
                    "a float; use // or a shift",
                )
            return Num(TOP_IVL, promote(ln.dtype, rn.dtype) if dtype_is_float(
                promote(ln.dtype, rn.dtype) or "float32") else "float32")
        dt = promote(ln.dtype, rn.dtype)
        if self.check and (
            (dtype_is_float(ln.dtype) and dtype_is_lane_int(rn.dtype))
            or (dtype_is_float(rn.dtype) and dtype_is_lane_int(ln.dtype))
        ):
            self.an.report(
                "float-contamination", self.mod, node,
                f"float operand meets integer lane "
                f"({ln.dtype} vs {rn.dtype}) in a limb kernel",
            )
        a, b = ln.ivl, rn.ivl
        if isinstance(op, ast.Add):
            ivl = a.add(b)
        elif isinstance(op, ast.Sub):
            ivl = a.sub(b)
        elif isinstance(op, ast.Mult):
            ivl = a.mul(b)
        elif isinstance(op, ast.LShift):
            ivl = a.lshift(b)
        elif isinstance(op, ast.RShift):
            ivl = a.rshift(b)
        elif isinstance(op, ast.BitAnd):
            ivl = a.and_(b)
        elif isinstance(op, ast.BitOr):
            ivl = a.or_(b)
        elif isinstance(op, ast.BitXor):
            ivl = a.xor(b)
        elif isinstance(op, ast.Mod):
            ivl = a.mod(b)
        elif isinstance(op, ast.FloorDiv):
            ivl = a.floordiv(b)
        elif isinstance(op, ast.Pow):
            ca, cb = a.const(), b.const()
            if ca is not None and cb is not None and 0 <= cb <= 512 and abs(ca) <= 2:
                ivl = Interval(ca ** cb, ca ** cb) if ca >= 0 else TOP_IVL
            else:
                ivl = TOP_IVL
        else:
            ivl = TOP_IVL
        if dtype_is_float(dt):
            return Num(TOP_IVL, dt)
        out = Num(ivl, dt)
        if isinstance(op, (ast.Add, ast.Sub, ast.Mult, ast.LShift)):
            self._overflow_check(out, node)
            out = self._clamp(out)
        return out

    def eval_BinOp(self, node) -> AbsVal:
        return self.binop(node.op, self.eval(node.left),
                          self.eval(node.right), node)

    # -- attribute / subscript -------------------------------------------
    def eval_Attribute(self, node) -> AbsVal:
        base = self.eval(node.value)
        name = node.attr
        if isinstance(base, ModVal):
            if base.intrinsic is not None:
                return intrinsic_attr(base.intrinsic, name)
            m = base.modinfo
            envm = self.an.module_env(m)
            if name in envm:
                return envm[name]
            if name in m.functions:
                return FuncVal(m, m.functions[name], name)
            if name in m.classes:
                return ClassVal(m, m.classes[name])
            sub = self.an.resolve_module(m.name + "." + name)
            if sub is not None:
                return ModVal(modinfo=sub)
            return UNKNOWN
        if isinstance(base, InstanceVal):
            if base.contract == "montctx":
                meth = _montctx_method(name)
                if meth is not None:
                    return IntrinsicVal("montctx." + name, meth)
                return _montctx_attr(name)
            if name in base.attrs:
                return base.attrs[name]
            if base.clsval is not None:
                fn = _class_method(base.clsval, name)
                if fn is not None:
                    return FuncVal(
                        base.clsval.mod, fn, base.cls_name + "." + name,
                        selfval=base,
                    )
            return UNKNOWN
        if isinstance(base, ClassVal):
            fn = _class_method(base, name)
            if fn is not None:
                static = any(
                    _dotted(d) == "staticmethod" for d in fn.decorator_list
                )
                cm = any(
                    _dotted(d) == "classmethod" for d in fn.decorator_list
                )
                if static:
                    return FuncVal(base.mod, fn, base.node.name + "." + name)
                if cm:
                    return FuncVal(
                        base.mod, fn, base.node.name + "." + name,
                        selfval=base,
                    )
                return FuncVal(base.mod, fn, base.node.name + "." + name)
            return UNKNOWN
        if isinstance(base, NamedTupleVal):
            if name in base.fields:
                return base.getitem(base.fields[name])
        if isinstance(base, Num):
            if name in _NUM_METHODS:
                return MethodVal(name, base)
            if name == "shape":
                return SeqVal(
                    items=None, elem=Num(Interval(0, None), "pyint")
                )
            if name == "ndim":
                return Num(Interval(0, 32), "pyint")
            if name == "at":
                return MethodVal("at", base)
            if name == "T":
                return base
            return UNKNOWN
        if isinstance(base, SeqVal):
            if name in _SEQ_METHODS:
                return MethodVal(name, base)
            return UNKNOWN
        if isinstance(base, DictVal):
            if name in _DICT_METHODS:
                return MethodVal(name, base)
            return UNKNOWN
        if isinstance(base, MethodVal) and base.name == "at_indexed":
            if name in ("set", "add", "multiply", "min", "max", "get"):
                return MethodVal("at_" + name, base.recv)
        if isinstance(base, ConstVal):
            return UNKNOWN
        return UNKNOWN

    def eval_Subscript(self, node) -> AbsVal:
        base = self.eval(node.value)
        if isinstance(base, MethodVal) and base.name == "at":
            self.eval(node.slice)
            return MethodVal("at_indexed", base.recv)
        idx = self.eval(node.slice)
        if isinstance(base, SeqVal):
            if isinstance(node.slice, ast.Slice):
                if base.items is not None:
                    lo = node.slice.lower
                    hi = node.slice.upper
                    step = node.slice.step
                    lo_c = self._const_or_none(lo)
                    hi_c = self._const_or_none(hi)
                    st_c = self._const_or_none(step) if step else 1
                    if (
                        (lo is None or lo_c is not None)
                        and (hi is None or hi_c is not None)
                        and st_c in (1, -1, 2, None)
                    ):
                        try:
                            return SeqVal(
                                items=base.items[lo_c:hi_c:st_c or 1]
                            )
                        except Exception:
                            pass
                return SeqVal(items=None, elem=base.summary())
            if isinstance(idx, Num):
                return base.getitem(idx.const())
            return base.summary()
        if isinstance(base, Num):
            return base  # array indexing/slicing preserves lane bounds
        if isinstance(base, DictVal):
            return base.vals
        return UNKNOWN

    def _const_or_none(self, node) -> Optional[int]:
        if node is None:
            return None
        v = self.eval(node)
        return v.const() if isinstance(v, Num) else None

    # -- calls ------------------------------------------------------------
    def eval_Call(self, node) -> AbsVal:
        fv = self.eval(node.func)
        args: List[AbsVal] = []
        for a in node.args:
            if isinstance(a, ast.Starred):
                sv = self.eval(a.value)
                if isinstance(sv, SeqVal) and sv.items is not None:
                    args.extend(sv.items)
                else:
                    args.append(UNKNOWN)
            else:
                args.append(self.eval(a))
        kwargs: Dict[str, AbsVal] = {}
        for kw in node.keywords:
            if kw.arg is not None:
                kwargs[kw.arg] = self.eval(kw.value)
            else:
                self.eval(kw.value)
        return self.dispatch_call(fv, args, kwargs, node)

    def dispatch_call(self, fv, args, kwargs, node) -> AbsVal:
        if isinstance(fv, IntrinsicVal):
            try:
                return fv.handler(args, kwargs, self, node)
            except (_Budget, _BreakSig, _ContinueSig):
                raise
            except Exception:
                return UNKNOWN
        if isinstance(fv, DtypeVal):
            return self.cast(args[0] if args else UNKNOWN, fv.name, node)
        if isinstance(fv, FuncVal):
            return self.an.call_function(fv, args, kwargs, self.depth,
                                         self.budget)
        if isinstance(fv, ClassVal):
            return self.instantiate(fv, args, kwargs, node)
        if isinstance(fv, MethodVal):
            return self.call_method(fv, args, kwargs, node)
        return UNKNOWN

    def instantiate(self, cv: ClassVal, args, kwargs, node) -> AbsVal:
        cname = cv.node.name
        if cname == "MontCtx":
            return InstanceVal("MontCtx", contract="montctx", clsval=cv)
        base_names = {_dotted(b) for b in cv.node.bases}
        base_leaves = {
            (b or "").rsplit(".", 1)[-1] for b in base_names if b
        }
        if "NamedTuple" in base_leaves:
            fields: Dict[str, int] = {}
            defaults: Dict[str, AbsVal] = {}
            for stmt in cv.node.body:
                if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name
                ):
                    fields[stmt.target.id] = len(fields)
                    if stmt.value is not None:
                        defaults[stmt.target.id] = self.eval(stmt.value)
            items: List[AbsVal] = [UNKNOWN] * len(fields)
            for name, i in fields.items():
                if i < len(args):
                    items[i] = args[i]
                elif name in kwargs:
                    items[i] = kwargs[name]
                elif name in defaults:
                    items[i] = defaults[name]
            return NamedTupleVal(items, fields)
        if "Exception" in base_leaves or cname.endswith("Error"):
            return UNKNOWN
        inst = InstanceVal(cname, clsval=cv)
        init = _class_method(cv, "__init__")
        if init is not None:
            self.an.call_function(
                FuncVal(cv.mod, init, cname + ".__init__", selfval=inst),
                args, kwargs, self.depth, self.budget,
            )
        return inst

    def cast(self, v: AbsVal, dtype: str, node: ast.AST) -> AbsVal:
        vn = v if isinstance(v, Num) else numify(v)
        if not isinstance(vn, Num):
            lo, hi, isf = DTYPES[dtype]
            return Num(TOP_IVL if isf else Interval(lo, hi), dtype)
        lo, hi, isf = DTYPES[dtype]
        if isf:
            return Num(TOP_IVL, dtype)
        if vn.ivl.is_top or vn.ivl.lo is None or vn.ivl.hi is None:
            return Num(Interval(lo, hi), dtype)
        if vn.ivl.within(lo, hi):
            return Num(vn.ivl, dtype)
        if self.check and not dtype_is_float(vn.dtype):
            self.an.report(
                "dtype-narrowing", self.mod, node,
                f"cast of value in {vn.ivl} to {dtype} "
                f"[{lo}, {hi}] can truncate",
            )
        return Num(Interval(lo, hi), dtype)

    def call_method(self, m: MethodVal, args, kwargs, node) -> AbsVal:
        name, recv = m.name, m.recv
        if isinstance(recv, Num):
            if name == "astype":
                dt = as_dtype(args[0]) if args else None
                if dt is None:
                    return Num(TOP_IVL, None)
                return self.cast(recv, dt, node)
            if name in ("reshape", "copy", "transpose", "ravel", "flatten",
                        "squeeze", "swapaxes", "view", "block_until_ready"):
                return recv
            if name in ("sum", "prod", "cumsum", "dot"):
                return Num(TOP_IVL, recv.dtype)
            if name in ("min", "max", "mean"):
                return recv if name != "mean" else Num(TOP_IVL, "float32")
            if name in ("all", "any"):
                return num_bool()
            if name == "tolist":
                return SeqVal(items=None, elem=Num(recv.ivl, "pyint"))
            if name == "item":
                return Num(recv.ivl, "pyint")
            if name == "bit_length":
                return Num(Interval(0, 520), "pyint")
            if name == "tobytes":
                return UNKNOWN
            if name == "at_set":
                v = numify(args[0]) if args else UNKNOWN
                if isinstance(v, Num):
                    return join(recv, Num(v.ivl, recv.dtype))
                return recv
            if name in ("at_add", "at_multiply", "at_min", "at_max"):
                v = numify(args[0]) if args else UNKNOWN
                if isinstance(v, Num):
                    opn = {"at_add": ast.Add, "at_multiply": ast.Mult,
                           "at_min": ast.Add, "at_max": ast.Add}[name]()
                    return join(recv, self.binop(opn, recv, v, node))
                return recv
            if name == "at_get":
                return recv
            return UNKNOWN
        if isinstance(recv, SeqVal):
            if name == "append":
                v = args[0] if args else UNKNOWN
                if recv.items is not None and len(recv.items) < 4096:
                    recv.items.append(v)
                else:
                    recv.items = None
                    recv.elem = join(recv.elem, v)
                return NONE
            if name == "extend":
                v = args[0] if args else UNKNOWN
                if (
                    isinstance(v, SeqVal)
                    and v.items is not None
                    and recv.items is not None
                    and len(recv.items) + len(v.items) <= 4096
                ):
                    recv.items.extend(v.items)
                else:
                    s = v.summary() if isinstance(v, SeqVal) else UNKNOWN
                    recv.elem = join(join(recv.summary(), s), recv.elem)
                    recv.items = None
                return NONE
            if name == "insert":
                if recv.items is not None and len(args) >= 2:
                    recv.items.insert(0, args[1])
                return NONE
            if name == "pop":
                if recv.items is not None and recv.items:
                    return recv.items.pop()
                return recv.summary()
            if name in ("sort", "reverse", "clear"):
                if name == "clear" and recv.items is not None:
                    recv.items.clear()
                return NONE
            if name == "copy":
                if recv.items is not None:
                    return SeqVal(items=list(recv.items))
                return SeqVal(items=None, elem=recv.elem)
            if name in ("count", "index"):
                return Num(Interval(0, None), "pyint")
            return UNKNOWN
        if isinstance(recv, DictVal):
            if name == "get":
                default = args[1] if len(args) > 1 else NONE
                return join(recv.vals, default)
            if name == "setdefault":
                if len(args) > 1:
                    recv.vals = join(recv.vals, args[1])
                return recv.vals
            if name in ("items",):
                return SeqVal(items=None, elem=SeqVal(
                    items=[UNKNOWN, recv.vals]
                ))
            if name in ("keys",):
                return SeqVal(items=None, elem=UNKNOWN)
            if name in ("values",):
                return SeqVal(items=None, elem=recv.vals)
            if name == "update":
                if args and isinstance(args[0], DictVal):
                    recv.vals = join(recv.vals, args[0].vals)
                return NONE
            if name == "pop":
                return join(recv.vals, args[1] if len(args) > 1 else NONE)
            if name == "clear":
                return NONE
            return UNKNOWN
        return UNKNOWN

    # -- comprehensions ---------------------------------------------------
    def eval_ListComp(self, node) -> AbsVal:
        return self._comp(node.generators, lambda: self.eval(node.elt))

    def eval_GeneratorExp(self, node) -> AbsVal:
        return self._comp(node.generators, lambda: self.eval(node.elt))

    def eval_SetComp(self, node) -> AbsVal:
        out = self._comp(node.generators, lambda: self.eval(node.elt))
        if isinstance(out, SeqVal):
            return SeqVal(items=None, elem=out.summary())
        return out

    def eval_DictComp(self, node) -> AbsVal:
        out = self._comp(node.generators, lambda: self.eval(node.value))
        if isinstance(out, SeqVal):
            return DictVal(vals=out.summary())
        return DictVal()

    def _comp(self, generators, eval_elt) -> AbsVal:
        saved = dict(self.env)
        try:
            items = self._comp_rec(list(generators), eval_elt, 0)
        finally:
            self.env = saved
        return items

    def _comp_rec(self, gens, eval_elt, gi) -> AbsVal:
        if gi >= len(gens):
            return SeqVal(items=[eval_elt()])
        gen = gens[gi]
        it = self.eval(gen.iter)
        items = self.concrete_items(it)
        if items is None:
            self.assign(gen.target, self.loop_elem(it))
            for cond in gen.ifs:
                self.eval(cond)
            inner = self._comp_rec(gens, eval_elt, gi + 1)
            elem = inner.summary() if isinstance(inner, SeqVal) else UNKNOWN
            return SeqVal(items=None, elem=elem)
        out: List[AbsVal] = []
        for v in items:
            self.assign(gen.target, v)
            keep = True
            for cond in gen.ifs:
                t = truth(self.eval(cond))
                if t is False:
                    keep = False
                    break
            if not keep:
                continue
            inner = self._comp_rec(gens, eval_elt, gi + 1)
            if isinstance(inner, SeqVal) and inner.items is not None:
                out.extend(inner.items)
                if len(out) > 4096:
                    return SeqVal(items=None, elem=_join_all(out))
            else:
                s = inner.summary() if isinstance(inner, SeqVal) else UNKNOWN
                return SeqVal(items=None, elem=join(_join_all(out), s))
        return SeqVal(items=out)

    def eval_Await(self, node) -> AbsVal:
        return self.eval(node.value)

    def eval_Yield(self, node) -> AbsVal:
        if node.value is not None:
            self.eval(node.value)
        return UNKNOWN

    def eval_YieldFrom(self, node) -> AbsVal:
        self.eval(node.value)
        return UNKNOWN


def _join_all(vals: Sequence[AbsVal]) -> AbsVal:
    out: Optional[AbsVal] = None
    for v in vals:
        out = v if out is None else join(out, v)
    return out if out is not None else UNKNOWN


def _class_method(cv: ClassVal, name: str):
    for stmt in cv.node.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if stmt.name == name:
                return stmt
    return None


_NUM_METHODS = {
    "astype", "reshape", "copy", "transpose", "ravel", "flatten", "squeeze",
    "swapaxes", "view", "sum", "prod", "cumsum", "dot", "min", "max", "mean",
    "all", "any", "tolist", "item", "bit_length", "tobytes",
    "block_until_ready",
}
_SEQ_METHODS = {
    "append", "extend", "insert", "pop", "sort", "reverse", "clear", "copy",
    "count", "index",
}
_DICT_METHODS = {
    "get", "setdefault", "items", "keys", "values", "update", "pop", "clear",
}


# --------------------------------------------------------------------------
# Intrinsics: numpy / jax.numpy / jax.lax / builtins
# --------------------------------------------------------------------------

_DTYPE_NAMES = {
    "uint8", "uint16", "uint32", "uint64", "int8", "int16", "int32",
    "int64", "float16", "float32", "float64", "bool_",
}


def _kw_dtype(kwargs: Dict[str, AbsVal]) -> Optional[str]:
    if "dtype" in kwargs:
        return as_dtype(kwargs["dtype"])
    return None


def _h_cast(dtype: str):
    def handler(args, kwargs, interp, node):
        return interp.cast(args[0] if args else UNKNOWN, dtype, node)
    return handler


def _h_fill(value_of):
    def handler(args, kwargs, interp, node):
        dt = _kw_dtype(kwargs) or "float32"
        ivl = value_of(args, interp)
        return Num(ivl, dt)
    return handler


def _h_like(value_of):
    def handler(args, kwargs, interp, node):
        src = numify(args[0]) if args else UNKNOWN
        dt = _kw_dtype(kwargs) or (
            src.dtype if isinstance(src, Num) else None
        )
        return Num(value_of(args, interp), dt)
    return handler


def _h_passthrough(args, kwargs, interp, node):
    return numify(args[0]) if args else UNKNOWN


def _h_asarray(args, kwargs, interp, node):
    v = args[0] if args else UNKNOWN
    dt = _kw_dtype(kwargs)
    if dt is not None:
        return interp.cast(v, dt, node)
    vn = numify(v)
    return vn if isinstance(vn, Num) else Num(TOP_IVL, None)


def _h_where(args, kwargs, interp, node):
    if len(args) >= 3:
        a = numify(args[1]) if not isinstance(args[1], Num) else args[1]
        b = numify(args[2]) if not isinstance(args[2], Num) else args[2]
        if isinstance(a, Num) and isinstance(b, Num):
            return join(a, b)
        return join(args[1], args[2])
    return UNKNOWN


def _h_join_elems(args, kwargs, interp, node):
    v = args[0] if args else UNKNOWN
    if isinstance(v, SeqVal):
        return numify(v)
    if isinstance(v, Num):
        return v
    return UNKNOWN


def _h_arange(args, kwargs, interp, node):
    dt = _kw_dtype(kwargs) or "int32"
    if args:
        n = args[-1] if len(args) <= 1 else args[1]
        if isinstance(n, Num) and n.ivl.hi is not None:
            return Num(Interval(0, max(0, n.ivl.hi - 1)), dt)
    return Num(Interval(0, None), dt)


def _h_clip(args, kwargs, interp, node):
    if len(args) >= 3:
        a, lo, hi = (numify(x) if not isinstance(x, Num) else x for x in args[:3])
        if isinstance(a, Num):
            lo_b = lo.ivl.lo if isinstance(lo, Num) else None
            hi_b = hi.ivl.hi if isinstance(hi, Num) else None
            new_lo = a.ivl.lo if lo_b is None else (
                lo_b if a.ivl.lo is None else max(a.ivl.lo, lo_b)
            )
            new_hi = a.ivl.hi if hi_b is None else (
                hi_b if a.ivl.hi is None else min(a.ivl.hi, hi_b)
            )
            return Num(Interval(new_lo, new_hi), a.dtype)
    return _h_passthrough(args, kwargs, interp, node)


def _h_minmax(is_min: bool):
    def handler(args, kwargs, interp, node):
        nums = [numify(a) if not isinstance(a, Num) else a for a in args]
        nums = [n for n in nums if isinstance(n, Num)]
        if len(nums) == 2:
            a, b = nums
            if is_min:
                ivl = Interval(
                    None if a.ivl.lo is None or b.ivl.lo is None
                    else min(a.ivl.lo, b.ivl.lo),
                    None if a.ivl.hi is None and b.ivl.hi is None
                    else min(
                        x for x in (a.ivl.hi, b.ivl.hi) if x is not None
                    ),
                )
            else:
                ivl = Interval(
                    None if a.ivl.lo is None and b.ivl.lo is None
                    else max(
                        x for x in (a.ivl.lo, b.ivl.lo) if x is not None
                    ),
                    None if a.ivl.hi is None or b.ivl.hi is None
                    else max(a.ivl.hi, b.ivl.hi),
                )
            return Num(ivl, promote(a.dtype, b.dtype))
        if len(nums) == 1:
            return nums[0]
        return UNKNOWN
    return handler


def _h_reduce_same_dtype(args, kwargs, interp, node):
    v = numify(args[0]) if args else UNKNOWN
    if isinstance(v, Num):
        return Num(TOP_IVL, v.dtype)
    return UNKNOWN


def _h_bool_out(args, kwargs, interp, node):
    return num_bool()


def _h_einsum(args, kwargs, interp, node):
    dts = [a.dtype for a in (numify(x) for x in args[1:])
           if isinstance(a, Num)]
    dt = None
    for d in dts:
        dt = d if dt is None else promote(dt, d)
    return Num(TOP_IVL, dt)


def _h_unknown(args, kwargs, interp, node):
    return UNKNOWN


_NUMPY_FUNCS = {
    "asarray": _h_asarray,
    "array": _h_asarray,
    "ascontiguousarray": _h_asarray,
    "zeros": _h_fill(lambda a, i: Interval(0, 0)),
    "ones": _h_fill(lambda a, i: Interval(1, 1)),
    "empty": _h_fill(lambda a, i: TOP_IVL),
    "zeros_like": _h_like(lambda a, i: Interval(0, 0)),
    "ones_like": _h_like(lambda a, i: Interval(1, 1)),
    "full": _h_fill(
        lambda a, i: (
            a[1].ivl
            if len(a) > 1 and isinstance(a[1], Num)
            else TOP_IVL
        )
    ),
    "full_like": _h_like(
        lambda a, i: (
            a[1].ivl
            if len(a) > 1 and isinstance(a[1], Num)
            else TOP_IVL
        )
    ),
    "where": _h_where,
    "stack": _h_join_elems,
    "concatenate": _h_join_elems,
    "hstack": _h_join_elems,
    "vstack": _h_join_elems,
    "broadcast_to": _h_passthrough,
    "tile": _h_passthrough,
    "repeat": _h_passthrough,
    "moveaxis": _h_passthrough,
    "reshape": _h_passthrough,
    "transpose": _h_passthrough,
    "squeeze": _h_passthrough,
    "expand_dims": _h_passthrough,
    "ravel": _h_passthrough,
    "flip": _h_passthrough,
    "take": _h_passthrough,
    "arange": _h_arange,
    "clip": _h_clip,
    "minimum": _h_minmax(True),
    "maximum": _h_minmax(False),
    "sum": _h_reduce_same_dtype,
    "prod": _h_reduce_same_dtype,
    "cumsum": _h_reduce_same_dtype,
    "einsum": _h_einsum,
    "any": _h_bool_out,
    "all": _h_bool_out,
    "array_equal": _h_bool_out,
    "frombuffer": lambda a, k, i, n: Num(
        TOP_IVL if _kw_dtype(k) is None else Interval(*DTYPES[_kw_dtype(k)][:2]),
        _kw_dtype(k),
    ),
    "shape": lambda a, k, i, n: SeqVal(
        items=None, elem=Num(Interval(0, None), "pyint")
    ),
    "broadcast_shapes": _h_unknown,
    "dtype": lambda a, k, i, n: (
        DtypeVal(as_dtype(a[0])) if a and as_dtype(a[0]) else UNKNOWN
    ),
}


def _h_fori_loop(args, kwargs, interp, node):
    if len(args) < 4:
        return UNKNOWN
    lo_v, hi_v, body, init = args[0], args[1], args[2], args[3]
    lo = lo_v.const() if isinstance(lo_v, Num) else None
    hi = hi_v.const() if isinstance(hi_v, Num) else None
    carry = init
    if (
        lo is not None and hi is not None and 0 <= hi - lo <= MAX_UNROLL
        and isinstance(body, FuncVal)
    ):
        for i in range(lo, hi):
            carry = interp.dispatch_call(
                body, [num_const(i), carry], {}, node
            )
        return carry
    if not isinstance(body, FuncVal):
        return UNKNOWN
    i_num = Num(
        Interval(
            lo_v.ivl.lo if isinstance(lo_v, Num) else None,
            None if not isinstance(hi_v, Num) or hi_v.ivl.hi is None
            else hi_v.ivl.hi - 1,
        ),
        "pyint",
    )
    for it in range(MAX_FIXPOINT):
        out = interp.dispatch_call(body, [i_num, carry], {}, node)
        new = join(carry, out)
        if it >= 2:
            new = widen_val(carry, new)
        if new.key() == carry.key():
            return new
        carry = new
    return UNKNOWN


def _h_scan(args, kwargs, interp, node):
    if len(args) < 2:
        return UNKNOWN
    body, init = args[0], args[1]
    xs = args[2] if len(args) > 2 else kwargs.get("xs", NONE)
    elem: AbsVal
    if isinstance(xs, SeqVal):
        elem = xs.summary()
    elif isinstance(xs, Num):
        elem = xs
    else:
        elem = UNKNOWN
    if not isinstance(body, FuncVal):
        return UNKNOWN
    carry = init
    for it in range(MAX_FIXPOINT):
        out = interp.dispatch_call(body, [carry, elem], {}, node)
        new_c = (
            out.getitem(0)
            if isinstance(out, SeqVal) and out.items is not None
            and len(out.items) == 2
            else UNKNOWN
        )
        new = join(carry, new_c)
        if it >= 2:
            new = widen_val(carry, new)
        if new.key() == carry.key():
            return SeqVal(items=[new, UNKNOWN])
        carry = new
    return SeqVal(items=[UNKNOWN, UNKNOWN])


def _h_while_loop(args, kwargs, interp, node):
    if len(args) < 3:
        return UNKNOWN
    cond, body, init = args[0], args[1], args[2]
    if not isinstance(body, FuncVal):
        return UNKNOWN
    carry = init
    for it in range(MAX_FIXPOINT):
        if isinstance(cond, FuncVal):
            interp.dispatch_call(cond, [carry], {}, node)
        out = interp.dispatch_call(body, [carry], {}, node)
        new = join(carry, out)
        if it >= 2:
            new = widen_val(carry, new)
        if new.key() == carry.key():
            return new
        carry = new
    return UNKNOWN


def _h_switch(args, kwargs, interp, node):
    if len(args) < 2:
        return UNKNOWN
    branches = args[1]
    operands = args[2:]
    outs: List[AbsVal] = []
    if isinstance(branches, SeqVal) and branches.items is not None:
        for b in branches.items:
            if isinstance(b, (FuncVal, IntrinsicVal)):
                outs.append(
                    interp.dispatch_call(b, list(operands), {}, node)
                )
    return _join_all(outs) if outs else UNKNOWN


def _h_cond(args, kwargs, interp, node):
    outs = []
    for b in args[1:3]:
        if isinstance(b, (FuncVal, IntrinsicVal)):
            outs.append(interp.dispatch_call(b, list(args[3:]), {}, node))
    return _join_all(outs) if outs else UNKNOWN


_LAX_FUNCS = {
    "fori_loop": _h_fori_loop,
    "scan": _h_scan,
    "while_loop": _h_while_loop,
    "switch": _h_switch,
    "cond": _h_cond,
    "select": _h_where,
}


def _h_jit(args, kwargs, interp, node):
    return args[0] if args else UNKNOWN


_JAX_FUNCS = {
    "jit": _h_jit,
    "vmap": _h_jit,
    "grad": _h_jit,
    "default_backend": _h_unknown,
    "device_put": _h_passthrough,
    "devices": _h_unknown,
}

_JAXOPS_FUNCS = {
    "segment_max": _h_reduce_same_dtype,
    "segment_min": _h_reduce_same_dtype,
    "segment_sum": _h_reduce_same_dtype,
}


def intrinsic_attr(ns: str, name: str) -> AbsVal:
    if ns == "numpy":
        if name in _DTYPE_NAMES:
            return DtypeVal("bool" if name == "bool_" else name)
        if name in ("pi", "e", "inf", "nan"):
            return Num(TOP_IVL, "pyfloat")
        if name in _NUMPY_FUNCS:
            return IntrinsicVal("np." + name, _NUMPY_FUNCS[name])
        if name == "random":
            return ModVal(intrinsic="opaque")
        return UNKNOWN
    if ns == "jax":
        if name == "numpy":
            return ModVal(intrinsic="numpy")
        if name == "lax":
            return ModVal(intrinsic="lax")
        if name == "ops":
            return ModVal(intrinsic="jaxops")
        if name in _JAX_FUNCS:
            return IntrinsicVal("jax." + name, _JAX_FUNCS[name])
        return UNKNOWN
    if ns == "lax":
        if name in _LAX_FUNCS:
            return IntrinsicVal("lax." + name, _LAX_FUNCS[name])
        return UNKNOWN
    if ns == "jaxops":
        if name in _JAXOPS_FUNCS:
            return IntrinsicVal("jax.ops." + name, _JAXOPS_FUNCS[name])
        return UNKNOWN
    if ns == "math":
        if name in ("inf", "pi", "e", "nan", "tau"):
            return Num(TOP_IVL, "pyfloat")
        return IntrinsicVal(
            "math." + name, lambda a, k, i, n: Num(TOP_IVL, "pyfloat")
        )
    if ns == "functools":
        if name == "partial":
            return IntrinsicVal("functools.partial", _h_partial)
        return UNKNOWN
    return UNKNOWN


def _h_partial(args, kwargs, interp, node):
    # partial(f, ...): keep the callable; pre-bound args are dropped
    # (used here only for jit decorators and map helpers)
    return args[0] if args else UNKNOWN


# -- python builtins --------------------------------------------------------


def _h_range(args, kwargs, interp, node):
    nums = [a if isinstance(a, Num) else Num(TOP_IVL, "pyint") for a in args]
    if len(nums) == 1:
        return RangeVal(num_const(0), nums[0])
    if len(nums) >= 2:
        step = 1
        if len(nums) >= 3:
            c = nums[2].const()
            step = c if c in (1, -1) else 0
        return RangeVal(nums[0], nums[1], step if step else 1)
    return RangeVal(num_const(0), Num(TOP_IVL, "pyint"))


def _h_len(args, kwargs, interp, node):
    v = args[0] if args else UNKNOWN
    if isinstance(v, SeqVal) and v.items is not None:
        return num_const(len(v.items))
    if isinstance(v, ConstVal) and isinstance(v.value, (str, bytes)):
        return num_const(len(v.value))
    return Num(Interval(0, None), "pyint")


def _h_int(args, kwargs, interp, node):
    v = numify(args[0]) if args else num_const(0)
    if isinstance(v, Num) and not dtype_is_float(v.dtype):
        return Num(v.ivl, "pyint")
    return Num(TOP_IVL, "pyint")


def _h_zip(args, kwargs, interp, node):
    seqs = [a for a in args]
    known = []
    for s in seqs:
        items = interp.concrete_items(s)
        if items is None:
            elems = [interp.loop_elem(s) for s in seqs]
            return SeqVal(items=None, elem=SeqVal(items=elems, mutable=False))
        known.append(items)
    n = min((len(k) for k in known), default=0)
    return SeqVal(
        items=[
            SeqVal(items=[k[i] for k in known], mutable=False)
            for i in range(n)
        ]
    )


def _h_enumerate(args, kwargs, interp, node):
    v = args[0] if args else UNKNOWN
    items = interp.concrete_items(v)
    if items is not None:
        return SeqVal(
            items=[
                SeqVal(items=[num_const(i), x], mutable=False)
                for i, x in enumerate(items)
            ]
        )
    return SeqVal(
        items=None,
        elem=SeqVal(
            items=[Num(Interval(0, None), "pyint"), interp.loop_elem(v)],
            mutable=False,
        ),
    )


def _h_list(args, kwargs, interp, node):
    if not args:
        return SeqVal(items=[])
    v = args[0]
    items = interp.concrete_items(v)
    if items is not None:
        return SeqVal(items=list(items))
    if isinstance(v, SeqVal):
        return SeqVal(items=None, elem=v.summary())
    if isinstance(v, RangeVal):
        return SeqVal(items=None, elem=interp.loop_elem(v))
    return SeqVal(items=None, elem=UNKNOWN)


def _h_tuple(args, kwargs, interp, node):
    out = _h_list(args, kwargs, interp, node)
    if isinstance(out, SeqVal):
        out.mutable = False
    return out


def _h_divmod(args, kwargs, interp, node):
    if len(args) == 2 and all(isinstance(a, Num) for a in args):
        a, b = args
        q = Num(a.ivl.floordiv(b.ivl), promote(a.dtype, b.dtype))
        r = Num(a.ivl.mod(b.ivl), promote(a.dtype, b.dtype))
        return SeqVal(items=[q, r], mutable=False)
    return SeqVal(items=[UNKNOWN, UNKNOWN], mutable=False)


def _h_abs(args, kwargs, interp, node):
    v = numify(args[0]) if args else UNKNOWN
    if isinstance(v, Num) and v.ivl.lo is not None and v.ivl.hi is not None:
        cands = [abs(v.ivl.lo), abs(v.ivl.hi)]
        lo = 0 if v.ivl.lo <= 0 <= v.ivl.hi else min(cands)
        return Num(Interval(lo, max(cands)), v.dtype)
    return v if isinstance(v, Num) else UNKNOWN


def _h_pow(args, kwargs, interp, node):
    return Num(TOP_IVL, "pyint")


def _h_sum_builtin(args, kwargs, interp, node):
    v = args[0] if args else UNKNOWN
    if isinstance(v, SeqVal):
        s = numify(v)
        if isinstance(s, Num):
            return Num(TOP_IVL, s.dtype)
    return UNKNOWN


_BUILTINS: Dict[str, AbsVal] = {}


def _register_builtins() -> None:
    table = {
        "range": _h_range,
        "len": _h_len,
        "int": _h_int,
        "float": lambda a, k, i, n: Num(TOP_IVL, "pyfloat"),
        "bool": lambda a, k, i, n: num_bool(),
        "abs": _h_abs,
        "min": _h_minmax(True),
        "max": _h_minmax(False),
        "sum": _h_sum_builtin,
        "divmod": _h_divmod,
        "pow": _h_pow,
        "zip": _h_zip,
        "enumerate": _h_enumerate,
        "list": _h_list,
        "tuple": _h_tuple,
        "set": lambda a, k, i, n: SeqVal(items=None, elem=UNKNOWN),
        "dict": lambda a, k, i, n: DictVal(),
        "sorted": _h_list,
        "reversed": _h_list,
        "isinstance": lambda a, k, i, n: num_bool(),
        "issubclass": lambda a, k, i, n: num_bool(),
        "callable": lambda a, k, i, n: num_bool(),
        "hasattr": lambda a, k, i, n: num_bool(),
        "getattr": lambda a, k, i, n: UNKNOWN,
        "setattr": lambda a, k, i, n: NONE,
        "print": lambda a, k, i, n: NONE,
        "repr": lambda a, k, i, n: ConstVal(""),
        "str": lambda a, k, i, n: ConstVal(""),
        "bytes": lambda a, k, i, n: UNKNOWN,
        "bytearray": lambda a, k, i, n: UNKNOWN,
        "id": lambda a, k, i, n: Num(Interval(0, None), "pyint"),
        "hash": lambda a, k, i, n: Num(TOP_IVL, "pyint"),
        "any": lambda a, k, i, n: num_bool(),
        "all": lambda a, k, i, n: num_bool(),
        "iter": _h_list,
        "next": lambda a, k, i, n: (
            a[0].summary() if a and isinstance(a[0], SeqVal) else UNKNOWN
        ),
        "map": lambda a, k, i, n: SeqVal(items=None, elem=UNKNOWN),
        "filter": lambda a, k, i, n: (
            a[1] if len(a) > 1 else SeqVal(items=None, elem=UNKNOWN)
        ),
        "object": lambda a, k, i, n: UNKNOWN,
        "super": lambda a, k, i, n: UNKNOWN,
        "vars": lambda a, k, i, n: DictVal(),
        "globals": lambda a, k, i, n: DictVal(),
    }
    for name, h in table.items():
        _BUILTINS[name] = IntrinsicVal(name, h)


_register_builtins()


def builtin_value(name: str) -> AbsVal:
    if name in _BUILTINS:
        return _BUILTINS[name]
    if name in ("True", "False"):
        return num_bool(name == "True")
    if name == "None":
        return NONE
    if name.endswith("Error") or name in (
        "Exception", "BaseException", "KeyboardInterrupt", "StopIteration",
        "ArithmeticError", "Warning",
    ):
        return IntrinsicVal(name, _h_unknown)
    return UNKNOWN


# --------------------------------------------------------------------------
# const-drift: pure AST pass over the limb tier
# --------------------------------------------------------------------------


def check_const_drift(tree: ast.Module, ctx: FileContext) -> List[Finding]:
    """A re-hardcoded 13/20/0x1fff/8192/260 in an arithmetic context.
    Only contexts where the literal plays the limb-constant role fire:
    shift amounts, mask operands, modulus/divmod bases, range() trip
    counts and 2**13 powers — `table[13]` as data stays quiet."""
    findings: List[Finding] = []
    if not ctx.matches(LIMB_TIER):
        return findings

    def lit(node) -> Optional[int]:
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, int)
            and not isinstance(node.value, bool)
            and node.value in DRIFT_CONSTANTS
        ):
            return node.value
        return None

    def hit(node: ast.AST, value: int, role: str) -> None:
        findings.append(
            Finding(
                "const-drift", ctx.path, node.lineno, node.col_offset,
                f"hardcoded {value} as {role}; import "
                f"{DRIFT_CONSTANTS[value]} from fabric_tpu.ops.bignum "
                f"(fabric_tpu.common re-exports)",
            )
        )

    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp):
            if isinstance(node.op, (ast.LShift, ast.RShift)):
                v = lit(node.right)
                if v is not None:
                    hit(node.right, v, "a shift amount")
            if isinstance(node.op, ast.BitAnd):
                for side in (node.left, node.right):
                    v = lit(side)
                    if v is not None and v in (8191,):
                        hit(side, v, "a limb mask")
            if isinstance(node.op, (ast.Mod, ast.FloorDiv)):
                v = lit(node.right)
                if v is not None and v in (8192, 8191):
                    hit(node.right, v, "a limb modulus")
            if isinstance(node.op, ast.Pow):
                base = node.left
                v = lit(node.right)
                if (
                    v == 13
                    and isinstance(base, ast.Constant)
                    and base.value == 2
                ):
                    hit(node.right, v, "2**13 (the limb radix)")
        elif isinstance(node, ast.Call):
            dn = _dotted(node.func)
            if dn == "range" and len(node.args) == 1:
                v = lit(node.args[0])
                if v in (13, 20, 260):
                    hit(node.args[0], v, "a limb-loop trip count")
            elif dn == "divmod" and len(node.args) == 2:
                v = lit(node.args[1])
                if v is not None:
                    hit(node.args[1], v, "a divmod base")
    return findings


# --------------------------------------------------------------------------
# mask-fail-open: pure AST pass over the mask tier
# --------------------------------------------------------------------------


def _code_member(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
    """'VALID' for TxValidationCode.VALID / a module-level alias of it."""
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        if base is not None and base.rsplit(".", 1)[-1] == "TxValidationCode":
            return node.attr
    if isinstance(node, ast.Name) and node.id in aliases:
        return aliases[node.id]
    return None


def _is_code_write(node: ast.AST, aliases: Dict[str, str]):
    """(member_or_None, is_write) for flag writes: x.code = M,
    flags[i] = M, set_flag(i, M), return M."""
    if isinstance(node, ast.Assign):
        member = _code_member(node.value, aliases)
        for t in node.targets:
            tn = None
            if isinstance(t, ast.Attribute):
                tn = t.attr
            elif isinstance(t, ast.Name):
                tn = t.id
            elif isinstance(t, ast.Subscript):
                tn = _dotted(t.value) or ""
                tn = tn.rsplit(".", 1)[-1]
            if tn is not None and (
                "code" in tn.lower() or "flag" in tn.lower()
            ):
                return member, True
        if member is not None:
            return member, True
        return None, False
    if isinstance(node, ast.Call):
        dn = _dotted(node.func)
        if dn is not None and dn.rsplit(".", 1)[-1] == "set_flag":
            if len(node.args) >= 2:
                return _code_member(node.args[1], aliases), True
        return None, False
    return None, False


def _function_nodes(fn: ast.AST, stop_nested: bool = True):
    """Walk a function's own body, not nested defs'."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if stop_nested and isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _is_flag_producing(fn: ast.AST, aliases: Dict[str, str]) -> bool:
    for node in _function_nodes(fn):
        if isinstance(node, ast.Name) and (
            node.id == "TxValidationCode"
            or node.id in aliases
            or node.id == "flags"  # the ValidationFlags result threading
            # boolean verdict masks (the serve plane's currency): a
            # function that BINDS a mask/verdicts name produces lane
            # verdicts, so its exception discipline is mask-load-bearing
            # even though no TxValidationCode appears (the sidecar
            # client/server trade raw bool masks; flags come later)
            or (
                isinstance(node.ctx, ast.Store)
                and node.id in ("mask", "verdicts", "ok_list")
            )
        ):
            return True
        if isinstance(node, ast.Attribute) and node.attr == "set_flag":
            return True
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Attribute) and t.attr == "code"
            for t in node.targets
        ):
            return True
    return False


def _stmt_accepts(
    stmt: ast.stmt, aliases: Dict[str, str], exc_name: Optional[str]
) -> bool:
    """One statement that, when reached, closes the failure path:
    raise, an INVALID-family code write, an accepting return, or a call
    handing the exception object onward."""
    if isinstance(stmt, ast.Raise):
        return True
    for node in ast.walk(stmt):
        member, is_write = _is_code_write(node, aliases)
        if is_write and member is not None and member not in FAIL_OPEN_MEMBERS:
            return True
    if isinstance(stmt, ast.Return):
        v = stmt.value
        member = _code_member(v, aliases) if v is not None else None
        if member is not None and member not in FAIL_OPEN_MEMBERS:
            return True
        if isinstance(v, ast.Constant) and isinstance(v.value, str) and v.value:
            return True  # error-string convention ("why tx is invalid")
        if v is not None and any(
            isinstance(sub, ast.Call) for sub in ast.walk(v)
        ):
            return True  # delegation: return fallback(...)
        return False
    if exc_name is not None:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Name) and sub.id == exc_name:
                            return True  # exception handed onward
    return False


def _path_closes(
    stmts: Sequence[ast.stmt], aliases: Dict[str, str],
    exc_name: Optional[str],
) -> bool:
    """EVERY control path through `stmts` must hit an accepting action.
    Path-sensitive on If: a delegation wrapped in `if cb is not None:`
    with no else does NOT close (the exact shape of the pipeline's
    pre-fix silent-drop bug)."""
    compound = (
        ast.If, ast.Try, ast.With, ast.AsyncWith, ast.For, ast.AsyncFor,
        ast.While,
    )
    saw_call_assign = False
    for i, s in enumerate(stmts):
        # compound statements are handled structurally below — walking
        # into them here would credit a GUARDED action to every path
        if not isinstance(s, compound) and _stmt_accepts(
            s, aliases, exc_name
        ):
            return True
        if isinstance(s, ast.Assign) and isinstance(s.value, ast.Call):
            # out = fallback(...); ... return out  (delegation split
            # across statements: require the return on this same path)
            saw_call_assign = True
        if (
            saw_call_assign
            and isinstance(s, ast.Return)
            and s.value is not None
        ):
            return True
        if isinstance(s, ast.If):
            if _path_closes(s.body, aliases, exc_name) and _path_closes(
                s.orelse, aliases, exc_name
            ):
                return True
        if isinstance(s, ast.Try):
            closing = _path_closes(s.body, aliases, exc_name) or (
                _path_closes(s.orelse, aliases, exc_name)
            )
            if closing and all(
                _path_closes(h.body, aliases, exc_name) for h in s.handlers
            ):
                return True
            if _path_closes(s.finalbody, aliases, exc_name):
                return True
        if isinstance(s, (ast.With, ast.AsyncWith)):
            if _path_closes(s.body, aliases, exc_name):
                return True
    return False


def _handler_fails_closed(
    handler: ast.ExceptHandler, aliases: Dict[str, str]
) -> bool:
    """True when EVERY path through the handler raises, assigns/returns
    an INVALID-family code, returns an error string, delegates to a
    fallback call, or hands the exception object onward."""
    # narrow-typed retry idiom: `except queue.Empty: continue` decides
    # nothing — the loop re-polls.  Only NARROW exception types qualify;
    # `except Exception: continue` would silently skip a transaction.
    types = (
        [_dotted(e) for e in handler.type.elts]
        if isinstance(handler.type, ast.Tuple)
        else [_dotted(handler.type)] if handler.type is not None else [None]
    )
    narrow = all(
        t is not None and t.rsplit(".", 1)[-1] not in (
            "Exception", "BaseException"
        )
        for t in types
    )
    if narrow and all(
        isinstance(s, ast.Continue) for s in handler.body
    ):
        return True
    return _path_closes(handler.body, aliases, handler.name)


def check_mask_fail_open(tree: ast.Module, ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    if not ctx.matches(MASK_TIER):
        return findings
    # module-level aliases: NAME = TxValidationCode.MEMBER
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            m = _code_member(node.value, {})
            if isinstance(t, ast.Name) and m is not None:
                aliases[t.id] = m

    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _is_flag_producing(fn, aliases):
            continue
        last_stmt = fn.body[-1] if fn.body else None
        for node in _function_nodes(fn):
            if isinstance(node, ast.ExceptHandler):
                # forbidden writes first: VALID / NOT_VALIDATED in a
                # handler fail open or leave the flag unset
                bad = None
                for sub in ast.walk(node):
                    member, is_write = _is_code_write(sub, aliases)
                    if is_write and member in FAIL_OPEN_MEMBERS:
                        bad = (sub, member)
                        break
                    if isinstance(sub, ast.Return) and sub.value is not None:
                        m = _code_member(sub.value, aliases)
                        if m in FAIL_OPEN_MEMBERS:
                            bad = (sub, m)
                            break
                if bad is not None:
                    findings.append(
                        Finding(
                            "mask-fail-open", ctx.path,
                            bad[0].lineno, bad[0].col_offset,
                            f"exception handler in flag-producing "
                            f"{fn.name!r} writes {bad[1]}: a failure path "
                            f"must assign an INVALID-family code",
                        )
                    )
                    continue
                if not _handler_fails_closed(node, aliases):
                    findings.append(
                        Finding(
                            "mask-fail-open", ctx.path,
                            node.lineno, node.col_offset,
                            f"exception handler in flag-producing "
                            f"{fn.name!r} neither raises, assigns an "
                            f"INVALID-family code, delegates, nor "
                            f"propagates the exception — the lane's flag "
                            f"can be left unset (fail-open)",
                        )
                    )
            elif isinstance(node, ast.Return) and node is not last_stmt:
                m = _code_member(node.value, aliases) if node.value else None
                if m == "VALID":
                    findings.append(
                        Finding(
                            "mask-fail-open", ctx.path,
                            node.lineno, node.col_offset,
                            f"early return of VALID from flag-producing "
                            f"{fn.name!r}: VALID may only be assigned at "
                            f"the designated end of code assembly",
                        )
                    )
    return findings


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def _build_universe(
    sources: Dict[str, str]
) -> Tuple[Dict[str, ModuleInfo], List[Finding]]:
    universe: Dict[str, ModuleInfo] = {}
    errors: List[Finding] = []
    for path, source in sources.items():
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as exc:
            errors.append(
                Finding(
                    "syntax-error", path, exc.lineno or 1, exc.offset or 0,
                    f"cannot parse: {exc.msg}",
                )
            )
            continue
        universe[module_name_for(path)] = ModuleInfo(
            module_name_for(path), path, tree, source
        )
    return universe, errors


def analyze_sources(
    sources: Dict[str, str],
    rule_ids: Optional[Iterable[str]] = None,
    collect_suppressed: Optional[List[Finding]] = None,
) -> Tuple[List[Finding], Dict[str, int]]:
    """Analyze a set of {path: source}. Cross-module calls resolve
    within the set; the LIMB/MASK tier path patterns decide which
    analyses run on each file.  ``collect_suppressed`` receives the
    findings per-line suppressions absorbed (fabreg's
    suppression-stale rule)."""
    active = set(rule_ids) if rule_ids is not None else set(RULES)
    for rid in active:
        if rid not in RULES:
            raise ValueError(f"unknown rule id {rid!r}")
    universe, findings = _build_universe(sources)
    suppressions = {
        mod.path: parse_suppressions(mod.source)
        for mod in universe.values()
    }
    an = Analyzer(universe, active, suppressions)

    # pure-AST passes
    ast_findings: List[Finding] = []
    for mod in universe.values():
        ctx = FileContext(mod.path)
        if "const-drift" in active:
            ast_findings.extend(check_const_drift(mod.tree, ctx))
        if "mask-fail-open" in active:
            ast_findings.extend(check_mask_fail_open(mod.tree, ctx))
    suppressed = 0
    for f in ast_findings:
        sup = suppressions.get(f.path, {}).get(f.line)
        if sup is not None and (f.rule in sup[0] or "all" in sup[0]):
            suppressed += 1
            if collect_suppressed is not None:
                collect_suppressed.append(f)
        else:
            findings.append(f)

    # value-range / dtype interpretation over the limb tier
    if active & {"limb-overflow", "dtype-narrowing", "float-contamination"}:
        limb_mods = [
            mod
            for mod in universe.values()
            if FileContext(mod.path).matches(LIMB_TIER)
        ]
        for mod in limb_mods:
            an.module_env(mod)
        for mod in limb_mods:
            for name, fn in mod.functions.items():
                an.analyze_function_standalone(mod, fn, name, None)
            for cname, cls in mod.classes.items():
                cv = ClassVal(mod, cls)
                inst: AbsVal
                if cname == "MontCtx":
                    inst = InstanceVal(cname, contract="montctx", clsval=cv)
                else:
                    inst = InstanceVal(cname, clsval=cv)
                for stmt in cls.body:
                    if isinstance(
                        stmt, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        static = any(
                            _dotted(d) == "staticmethod"
                            for d in stmt.decorator_list
                        )
                        an.analyze_function_standalone(
                            mod, stmt, f"{cname}.{stmt.name}",
                            None if static else inst,
                        )
        findings.extend(an.findings.values())
        suppressed += an.suppressed
        if collect_suppressed is not None:
            collect_suppressed.extend(an.suppressed_findings)

    findings.sort(key=Finding.key)
    stats = {"files": len(sources), "suppressed": suppressed}
    return findings, stats


def analyze_source(
    source: str,
    path: str,
    rule_ids: Optional[Iterable[str]] = None,
) -> Tuple[List[Finding], int]:
    """Single-blob convenience (fixtures/tests)."""
    findings, stats = analyze_sources({path: source}, rule_ids)
    return findings, stats["suppressed"]


def analyze_paths(
    paths: Sequence[str],
    rule_ids: Optional[Iterable[str]] = None,
    excludes: Sequence[str] = DEFAULT_EXCLUDES,
    collect_suppressed: Optional[List[Finding]] = None,
) -> Tuple[List[Finding], Dict[str, int]]:
    files = iter_py_files(paths, excludes)
    sources, io_findings = toolkit.read_sources(files)
    findings, stats = analyze_sources(sources, rule_ids, collect_suppressed)
    findings.extend(io_findings)
    findings.sort(key=Finding.key)
    stats["files"] = len(files)
    return findings, stats


def suppression_reasons(
    paths: Sequence[str], excludes: Sequence[str] = DEFAULT_EXCLUDES
) -> List[Tuple[str, int, Set[str], str]]:
    """Every fabflow suppression in the tree: (path, line, rules,
    reason).  The self-check test requires a computed bound (a number)
    in every reason."""
    out = []
    for f in iter_py_files(paths, excludes):
        try:
            source = Path(f).read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError):
            continue
        for line, (rules, reason) in parse_suppressions(source).items():
            out.append((f, line, rules, reason))
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = toolkit.build_parser(
        "fabflow",
        "value-range + dtype abstract interpreter for "
        "fabric-tpu (dependency-free; never imports the analyzed code)",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        toolkit.print_rule_list(RULES, width=20)
        return 0

    rc = toolkit.check_paths_exist(args.paths, "fabflow", parser)
    if rc:
        return rc
    rule_ids, rc = toolkit.parse_rule_arg(args.rules, RULES, "fabflow")
    if rc:
        return rc

    excludes = tuple(DEFAULT_EXCLUDES) + tuple(args.exclude)
    findings, stats = analyze_paths(args.paths, rule_ids, excludes)

    if args.json:
        print(
            json.dumps(
                {
                    "version": __version__,
                    "files": stats["files"],
                    "suppressed": stats["suppressed"],
                    "findings": [f.to_dict() for f in findings],
                },
                indent=2,
            )
        )
    else:
        toolkit.print_findings(findings)
        print(
            f"fabflow: {len(findings)} finding(s) in {stats['files']} "
            f"file(s) ({stats['suppressed']} suppressed)"
        )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
