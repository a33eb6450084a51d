"""fablife — resource-lifetime + wire-trust analyzer for fabric-tpu.

fablint pins per-file syntax invariants, fabdep the import graph and
lock discipline, fabflow value ranges and mask soundness, fabreg the
declarative metadata tables.  The failure class none of them models is
the one that kills long soaks: *lifetimes*.  The bug ledger since PR 8
is a lifetime ledger — a sidecar ``stop()`` that never woke its
``accept()`` thread (2s eaten per teardown, PR 10), conn threads and
``_conns`` bookkeeping leaked per reconnecting client, serve-socket
tempdirs never ``rmtree``'d, and QoS lane leak/double-free that PR 14
could only prove absent with *runtime* acquired/released counters.
fablife is the static twin of those counters: every acquire must reach
its release on every path, checked at parse time, before the fleet soak
scales to ≥8 peers for hours.

Like its siblings it is pure ``ast`` on the shared ``tools/toolkit.py``
chassis: it never imports analyzed code and runs without
numpy/jax/cryptography.

Rules
-----
Lifetime family (path-sensitive must-analysis, the fabflow
mask-fail-open mold):

thread-unjoined     a ``Thread.start()`` with no join reachable from
                    the owning scope: a started thread bound to a local
                    must be ``join()``-ed (or handed onward) in that
                    function; one stored on ``self.<attr>`` (directly
                    or via an ``append`` to a thread-list attr) must be
                    joined somewhere in the owning class — the
                    ``stop()``/``close()``/``__exit__`` teardown
                    family; an *unbound* ``Thread(...).start()`` can
                    never be joined and always fires.
fd-leak             a ``socket.socket``/``create_connection``/``open``/
                    ``tempfile.mkdtemp``/``TemporaryDirectory`` acquire
                    whose release (``close``/``rmtree``/``cleanup``) is
                    not guaranteed on exception edges: ``with``,
                    ``try/finally``, a registered cleanup
                    (``atexit.register``/``addCleanup``/
                    ``addfinalizer``/``ExitStack``), a generator
                    releasing after its ``yield`` (the pytest-fixture
                    idiom), or an ownership hand-off (returned, stored
                    on the owner, passed onward) all satisfy.  A
                    release that merely *exists* on the straight-line
                    path does not: the exception edge still leaks.
                    Tempdir paths are never ownership-transferred by
                    passing them to a call — a path string travels
                    freely; the creator still owes the ``rmtree``.
lock-leak           a bare ``X.acquire()`` whose ``X.release()`` is not
                    inside a ``finally`` in the same function (``with
                    lock:`` is the sanctioned shape).
pair-imbalance      driven by the declarative pair table
                    ``tools/pairs.toml`` (ClassLedger
                    ``try_acquire``→``release``, pool
                    ``submit``→``resolve``/teardown, CooldownGate
                    ``ready``→``record_*``, batcher
                    ``try_submit``/``submit``→resolver called): every
                    acquire site must discharge its obligation on every
                    success path — in a ``finally``, on all paths of
                    the success region, or (weakest tier, for
                    split-phase designs like the dispatcher's
                    ``on_dispatch`` release hook) somewhere else in the
                    owning class.

Wire-trust family (intraprocedural taint from wire-decoded integers —
the exact ``retry_after_ms`` class fixed by hand in PR 8, where a u32
off the wire bought a server-controlled unbounded client sleep):

wire-unclamped      an integer sourced from ``struct.unpack`` / the
                    protocol reader (``u8``/``u16``/``u32``/``u64``) /
                    a ``decode_*`` frame helper flowing into
                    ``sleep``/a ``timeout=`` argument/``deque(maxlen=)``
                    /``bytearray``/sequence-repeat allocation without
                    passing through ``min``/``clamp`` first.
blocking-unbudgeted a ``recv``/``join``/``get``/``wait``/``result``
                    with no timeout on the serve/router/batcher request
                    paths (``fabric_tpu/serve/*``,
                    ``parallel/batcher.py``) — every per-hop wait must
                    derive from a budget (the fabtail discipline as a
                    checked invariant).  ``recv`` is exempted when the
                    enclosing function also wields
                    ``settimeout``/``select`` (the bounded-demux
                    shape).

Suppression
-----------
Per line, toolkit grammar: ``# fablife: disable=rule-id  # <reason>``.
The reason must name the by-design release path (enforced by review +
the NOTES_BUILD triage ledger, like fabflow's computed-bound
discipline).

Usage
-----
    python -m fabric_tpu.tools.fablife [--json] [--list-rules]
        [--rules a,b] [--pairs FILE] PATH...

Exit status: 0 = clean, 1 = findings, 2 = usage/IO/pair-table error
(a half-read pair table checking nothing would be silent drift — parse
errors are loud by design).
"""

from __future__ import annotations

import ast
import json
import re
import sys
from dataclasses import dataclass, field
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

RULES: Dict[str, str] = {
    "thread-unjoined": (
        "Thread.start() with no join reachable from the owning scope "
        "(function-local join, or a join anywhere in the owning class "
        "for self-attr / thread-list threads)"
    ),
    "fd-leak": (
        "socket/open/mkdtemp/TemporaryDirectory acquired without a "
        "release guaranteed on exception edges (with, try/finally, "
        "registered cleanup, fixture-after-yield, or ownership "
        "hand-off)"
    ),
    "lock-leak": (
        "bare X.acquire() whose X.release() is not in a finally in the "
        "same function (use `with lock:`)"
    ),
    "pair-imbalance": (
        "a tools/pairs.toml acquire (ClassLedger try_acquire, pool "
        "submit, CooldownGate ready, batcher try_submit/submit) whose "
        "release is not reached on every success path"
    ),
    "wire-unclamped": (
        "wire-decoded integer (struct.unpack / reader u8-u64 / "
        "decode_*) flows into sleep/timeout/deque(maxlen)/allocation "
        "size without a min/clamp"
    ),
    "blocking-unbudgeted": (
        "recv/join/get/wait/result with no timeout on the "
        "serve/router/batcher request paths (every per-hop wait must "
        "derive from a budget)"
    ),
}

#: lifetime + wire rules pin the runtime package; the tempdir facet of
#: fd-leak additionally covers tests/ — a leaked fd dies
#: with the test process, a leaked /tmp dir accumulates across every CI
#: run of an hours-long soak.
PKG_SCOPE = ("*fabric_tpu/*",)
REQUEST_SCOPE = ("*fabric_tpu/serve/*", "*fabric_tpu/parallel/batcher.py")

_WIRE_SOURCE_LEAVES = {"u8", "u16", "u32", "u64", "unpack", "unpack_from"}
_WIRE_SANITIZERS = {"min", "clamp"}
_TIMEOUT_KWARGS = {"timeout", "maxlen"}
#: leaves whose FIRST positional is a timeout; ``get`` is excluded (its
#: first positional is a dict key / block flag — its timeout is the
#: second positional, handled separately)
_TIMEOUT_POSITION_LEAVES = {"join", "wait"}
_ALLOC_LEAVES = {"bytearray", "deque"}

_BLOCKING_LEAVES = {"join", "wait", "get", "result"}
_RECV_LEAVES = {"recv", "recv_into"}
_RECV_BOUNDING_LEAVES = {"settimeout", "setblocking", "select", "poll"}

_CLEANUP_REG_LEAVES = {
    "register", "addCleanup", "addfinalizer", "finalize", "callback",
    "push", "enter_context",
}


# --------------------------------------------------------------------------
# pairs.toml
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class PairSpec:
    name: str
    acquire: str
    release: Tuple[str, ...]
    base_like: Tuple[str, ...]
    mode: str  # "base" | "result"
    conditional: bool
    doc: str = ""


def default_pairs_file() -> Path:
    return Path(__file__).resolve().parent / "pairs.toml"


_LIST_RE = re.compile(r"^\[(.*)\]$")


def _parse_toml_value(raw: str, where: str):
    raw = raw.strip()
    if raw in ("true", "false"):
        return raw == "true"
    if raw.startswith('"') and raw.endswith('"') and len(raw) >= 2:
        return raw[1:-1]
    m = _LIST_RE.match(raw)
    if m:
        inner = m.group(1).strip()
        if not inner:
            return []
        items = []
        for part in inner.split(","):
            part = part.strip()
            if not (part.startswith('"') and part.endswith('"')):
                raise ValueError(f"{where}: list items must be \"quoted\"")
            items.append(part[1:-1])
        return items
    raise ValueError(f"{where}: expected \"string\", [list] or true/false")


def parse_pairs(text: str, path: str = "<pairs>") -> List[PairSpec]:
    """Parse the tiny TOML subset the analyzers already use for
    layers.toml, extended with ``[[pair]]`` array-of-tables.  LOUD on
    any malformed line: a half-read pair table silently checking
    nothing would be config drift."""
    entries: List[Dict[str, object]] = []
    current: Optional[Dict[str, object]] = None
    for n, raw in enumerate(text.splitlines(), start=1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line == "[[pair]]":
            current = {}
            entries.append(current)
            continue
        if line.startswith("["):
            raise ValueError(f"{path}:{n}: unknown section {line!r}")
        if "=" not in line:
            raise ValueError(f"{path}:{n}: expected 'key = value'")
        if current is None:
            raise ValueError(f"{path}:{n}: key outside a [[pair]] entry")
        key, _, value = line.partition("=")
        key = key.strip()
        if "#" in value and not value.strip().startswith('"'):
            value = value.split("#", 1)[0]
        current[key] = _parse_toml_value(value, f"{path}:{n}")
    specs: List[PairSpec] = []
    seen: Set[str] = set()
    for i, e in enumerate(entries, start=1):
        where = f"{path}: [[pair]] #{i}"
        for req in ("name", "acquire", "release", "mode"):
            if req not in e:
                raise ValueError(f"{where}: missing required key {req!r}")
        name = e["name"]
        if not isinstance(name, str) or not name:
            raise ValueError(f"{where}: name must be a non-empty string")
        if name in seen:
            raise ValueError(f"{where}: duplicate pair name {name!r}")
        seen.add(name)
        mode = e["mode"]
        if mode not in ("base", "result"):
            raise ValueError(
                f"{where}: mode must be \"base\" or \"result\", got {mode!r}"
            )
        release = e["release"]
        if isinstance(release, str):
            release = [release]
        if not isinstance(release, list):
            raise ValueError(f"{where}: release must be a list of strings")
        if mode == "base" and not release:
            raise ValueError(
                f"{where}: mode \"base\" requires at least one release leaf"
            )
        base_like = e.get("base_like", [])
        if isinstance(base_like, str):
            base_like = [base_like]
        acquire = e["acquire"]
        if not isinstance(acquire, str) or not acquire:
            raise ValueError(f"{where}: acquire must be a non-empty string")
        specs.append(
            PairSpec(
                name=name,
                acquire=acquire,
                release=tuple(release),
                base_like=tuple(s.lower() for s in base_like),
                mode=str(mode),
                conditional=bool(e.get("conditional", False)),
                doc=str(e.get("doc", "")),
            )
        )
    return specs


def load_default_pairs() -> List[PairSpec]:
    f = default_pairs_file()
    return parse_pairs(f.read_text(encoding="utf-8"), str(f))


# --------------------------------------------------------------------------
# AST helpers
# --------------------------------------------------------------------------

_NESTED = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _leaf(dn: Optional[str]) -> str:
    return (dn or "").rsplit(".", 1)[-1]


def _call_base(node: ast.Call) -> Optional[str]:
    """For ``a.b.c(...)`` the receiver ``a.b``; None for bare names."""
    if isinstance(node.func, ast.Attribute):
        return _dotted(node.func.value)
    return None


def _own_nodes(fn: ast.AST):
    """Walk a scope's own body, not nested function/lambda bodies."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        n = stack.pop()
        if isinstance(n, _NESTED):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _all_nodes(fn: ast.AST):
    """Everything below ``fn`` including nested defs/lambdas (release
    evidence: a discharge inside a callback defined here still counts)."""
    yield from ast.walk(fn)


def _mentions_name(node: ast.AST, names: Set[str]) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id in names for n in ast.walk(node)
    )


def _self_attr(node: ast.AST) -> Optional[str]:
    """'attr' for a ``self.attr`` expression."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _own_finally_bodies(fn: ast.AST):
    for n in _own_nodes(fn):
        if isinstance(n, ast.Try) and n.finalbody:
            yield n.finalbody
    if isinstance(fn, ast.Try) and fn.finalbody:  # pragma: no cover
        yield fn.finalbody


def _is_generator(fn: ast.AST) -> bool:
    return any(
        isinstance(n, (ast.Yield, ast.YieldFrom)) for n in _own_nodes(fn)
    )


# --------------------------------------------------------------------------
# Path engine: does every path through a region hit the predicate?
# --------------------------------------------------------------------------
# Three-valued sequence status:
#   "hit"  — every path through the sequence discharges the obligation
#   "miss" — some path EXITS (return/raise) without discharging
#   "fall" — some path falls off the end undischarged (keep scanning
#            the continuation)


def _stmt_status(s: ast.stmt, pred) -> str:
    # the statement NODE itself can discharge (a `for f in futures:`
    # loop consuming result handles) — predicates never match compound
    # containers like If/Try, so this cannot over-credit branches
    if pred(s):
        return "hit"
    if isinstance(s, ast.If):
        b = _seq_status(s.body, pred)
        o = _seq_status(s.orelse, pred)
        if "miss" in (b, o):
            return "miss"
        if b == "hit" and o == "hit":
            return "hit"
        return "fall"
    if isinstance(s, ast.Try):
        if _seq_status(s.finalbody, pred) == "hit":
            return "hit"  # finally dominates every exit
        body = _seq_status(list(s.body) + list(s.orelse), pred)
        hs = [_seq_status(h.body, pred) for h in s.handlers]
        if body == "miss" or "miss" in hs:
            return "miss"
        if body == "hit" and hs and all(h == "hit" for h in hs):
            return "hit"
        return "fall"
    if isinstance(s, (ast.With, ast.AsyncWith)):
        return _seq_status(s.body, pred)
    if isinstance(s, (ast.For, ast.AsyncFor, ast.While)):
        body = _seq_status(list(s.body) + list(s.orelse), pred)
        # the loop may run zero times: a body hit cannot promote to
        # "hit", but a body exit-without-release is still a miss
        return "miss" if body == "miss" else "fall"
    # simple statement: predicate anywhere inside discharges (covers
    # `return release(...)` and callback-carrying calls)
    for n in ast.walk(s):
        if pred(n):
            return "hit"
    if isinstance(s, (ast.Return, ast.Raise)):
        return "miss"
    return "fall"


def _seq_status(stmts: Sequence[ast.stmt], pred) -> str:
    for s in stmts:
        st = _stmt_status(s, pred)
        if st in ("hit", "miss"):
            return st
        if isinstance(s, (ast.Return, ast.Raise)):
            return "miss"  # terminal without a hit
    return "fall"


def _segments_hit(segments: Sequence[Sequence[ast.stmt]], pred) -> bool:
    """Fold continuation segments: True iff every path is discharged
    before the function falls off the end."""
    for seg in segments:
        st = _seq_status(seg, pred)
        if st == "hit":
            return True
        if st == "miss":
            return False
    return False  # fell off the function end undischarged


def _locate(
    stmts: Sequence[ast.stmt], target: ast.AST,
    conts: List[List[ast.stmt]],
) -> Optional[Tuple[ast.stmt, List[ast.stmt], List[List[ast.stmt]]]]:
    """Find the statement in (possibly nested) ``stmts`` whose subtree
    contains ``target``; returns (stmt, local tail, outer
    continuations)."""
    for i, s in enumerate(stmts):
        if any(n is target for n in ast.walk(s)):
            tail = list(stmts[i + 1:])
            # nested? descend into compound bodies first
            for fieldname in ("body", "orelse", "finalbody"):
                sub = getattr(s, fieldname, None)
                if isinstance(sub, list) and sub:
                    hit = _locate(sub, target, [tail] + conts)
                    if hit is not None:
                        # only descend when target is in the sub-body,
                        # not e.g. in an If test
                        if any(
                            any(n is target for n in ast.walk(x))
                            for x in sub
                        ):
                            return hit
            for h in getattr(s, "handlers", []) or []:
                if any(
                    any(n is target for n in ast.walk(x)) for x in h.body
                ):
                    hit = _locate(h.body, target, [tail] + conts)
                    if hit is not None:
                        return hit
            return s, tail, conts
    return None


def _success_segments(
    fn: ast.AST, acq: ast.Call, result_var: Optional[str],
    conditional: bool,
) -> Optional[List[List[ast.stmt]]]:
    """The statement segments a *successful* acquire flows through.
    None means the obligation is satisfied structurally (acquire inside
    a return/handed straight onward)."""
    loc = _locate(list(fn.body), acq, [])
    if loc is None:
        return None
    s, tail, conts = loc
    segs: List[List[ast.stmt]] = []
    if isinstance(s, (ast.Return, ast.Yield)) or (
        isinstance(s, ast.Expr)
        and isinstance(s.value, (ast.Yield, ast.YieldFrom))
    ):
        return None  # handed to the caller/consumer
    if (
        isinstance(s, (ast.If, ast.While))
        and any(n is acq for n in ast.walk(s.test))
        and conditional
    ):
        if isinstance(s.test, ast.UnaryOp) and isinstance(
            s.test.op, ast.Not
        ):
            segs = [tail]  # `if not acquire(): bail` — success is after
        else:
            segs = [list(s.body), tail]
    elif (
        conditional
        and result_var is not None
        and tail
        and isinstance(tail[0], ast.If)
        and _mentions_name(tail[0].test, {result_var})
    ):
        guard = tail[0]
        rest = tail[1:]
        test = guard.test
        negated = (
            isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not)
        ) or (
            isinstance(test, ast.Compare)
            and any(isinstance(op, ast.Is) for op in test.ops)
            and any(
                isinstance(c, ast.Constant) and c.value is None
                for c in test.comparators
            )
        )
        if negated:
            segs = [rest]  # `if r is None: bail` / `if not r: bail`
        else:
            segs = [list(guard.body), rest]
    else:
        segs = [[s], tail]
    return segs + conts


# --------------------------------------------------------------------------
# Per-class evidence (threads / resources stored on self)
# --------------------------------------------------------------------------


@dataclass
class ClassFacts:
    node: ast.ClassDef
    #: attrs with a direct ``self.A.join(`` anywhere in the class
    joined_attrs: Set[str] = field(default_factory=set)
    #: attrs iterated by a ``for v in <... self.A ...>: v.join()`` loop
    loop_joined_attrs: Set[str] = field(default_factory=set)
    #: attr -> release leaves seen on ``self.A.<leaf>(`` / rmtree args
    released_attrs: Dict[str, Set[str]] = field(default_factory=dict)
    #: release leaves seen anywhere in the class (pair weak tier)
    release_leaves: Set[str] = field(default_factory=set)


def _collect_class_facts(cls: ast.ClassDef) -> ClassFacts:
    facts = ClassFacts(cls)
    for method in cls.body:
        if not isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # local alias map: name -> self-attrs its RHS mentions
        aliases: Dict[str, Set[str]] = {}
        for n in _all_nodes(method):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 and (
                isinstance(n.targets[0], ast.Name)
            ):
                attrs = {
                    a
                    for sub in ast.walk(n.value)
                    if (a := _self_attr(sub)) is not None
                }
                if attrs:
                    aliases[n.targets[0].id] = attrs
        for n in _all_nodes(method):
            if isinstance(n, ast.Call):
                leaf = _leaf(_dotted(n.func))
                base = _call_base(n)
                facts.release_leaves.add(leaf)
                if base is not None and base.startswith("self."):
                    attr = base[len("self."):].split(".", 1)[0]
                    if leaf == "join":
                        facts.joined_attrs.add(attr)
                    facts.released_attrs.setdefault(attr, set()).add(leaf)
                elif base is not None and "." not in base and (
                    base in aliases
                ):
                    # `t = self._thread; t.join()` — the alias carries
                    # the release to the attr it was read from
                    if leaf == "join":
                        facts.joined_attrs |= aliases[base]
                    for attr in aliases[base]:
                        facts.released_attrs.setdefault(attr, set()).add(
                            leaf
                        )
                if leaf == "rmtree":
                    for arg in n.args:
                        for sub in ast.walk(arg):
                            a = _self_attr(sub)
                            if a is not None:
                                facts.released_attrs.setdefault(
                                    a, set()
                                ).add("rmtree")
            if isinstance(n, (ast.For, ast.AsyncFor)) and isinstance(
                n.target, ast.Name
            ):
                v = n.target.id
                body_joins = any(
                    isinstance(c, ast.Call)
                    and _leaf(_dotted(c.func)) == "join"
                    and _call_base(c) == v
                    for b in n.body
                    for c in ast.walk(b)
                )
                if not body_joins:
                    continue
                iter_attrs: Set[str] = set()
                for sub in ast.walk(n.iter):
                    a = _self_attr(sub)
                    if a is not None:
                        iter_attrs.add(a)
                    if isinstance(sub, ast.Name) and sub.id in aliases:
                        iter_attrs |= aliases[sub.id]
                facts.loop_joined_attrs |= iter_attrs
    return facts


# --------------------------------------------------------------------------
# Per-file analysis
# --------------------------------------------------------------------------


class _FileAnalyzer:
    def __init__(
        self,
        path: str,
        tree: ast.Module,
        pairs: Sequence[PairSpec],
        active: Set[str],
    ) -> None:
        self.path = path
        self.tree = tree
        self.pairs = pairs
        self.active = active
        self.ctx = FileContext(path)
        self.findings: List[Finding] = []
        self.in_pkg = self.ctx.matches(PKG_SCOPE)
        self.on_request_path = self.ctx.matches(REQUEST_SCOPE)
        self._class_facts: Dict[ast.ClassDef, ClassFacts] = {}
        #: names bound at module level — a pair base rooted in one is
        #: owned by the MODULE, so a release anywhere in the file is
        #: its owning-scope evidence (the _POOL_GATE shape)
        self._module_globals: Set[str] = set()
        for stmt in tree.body:
            targets: List[ast.expr] = []
            if isinstance(stmt, ast.Assign):
                targets = list(stmt.targets)
            elif isinstance(stmt, ast.AnnAssign):
                targets = [stmt.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    self._module_globals.add(t.id)

    # -- orchestration ------------------------------------------------------

    def run(self) -> List[Finding]:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                self._class_facts[node] = _collect_class_facts(node)
        scopes: List[Tuple[ast.AST, Optional[ast.ClassDef]]] = [
            (self.tree, None)
        ]
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ClassDef):
                for item in node.body:
                    if isinstance(
                        item, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        scopes.append((item, node))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not any(
                    node in getattr(c, "body", ())
                    for c in self._class_facts
                ):
                    scopes.append((node, None))
        for fn, cls in scopes:
            if self.in_pkg and "thread-unjoined" in self.active:
                self._check_threads(fn, cls)
            if "fd-leak" in self.active:
                self._check_fds(fn, cls)
            if self.in_pkg and "lock-leak" in self.active:
                self._check_locks(fn)
            if self.in_pkg and "pair-imbalance" in self.active:
                self._check_pairs(fn, cls)
            if self.in_pkg and "wire-unclamped" in self.active:
                self._check_wire(fn)
            if self.on_request_path and (
                "blocking-unbudgeted" in self.active
            ):
                self._check_blocking(fn)
        return self.findings

    def _emit(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append(
            Finding(
                rule, self.path, getattr(node, "lineno", 1),
                getattr(node, "col_offset", 0), msg,
            )
        )

    # -- thread-unjoined ----------------------------------------------------

    def _check_threads(
        self, fn: ast.AST, cls: Optional[ast.ClassDef]
    ) -> None:
        facts = self._class_facts.get(cls) if cls is not None else None
        thread_locals: Set[str] = set()
        attr_threads: Dict[str, ast.AST] = {}
        starts: List[Tuple[ast.Call, Optional[str], Optional[str]]] = []
        # (start call, local name or None, attr name or None)
        for n in _own_nodes(fn):
            if isinstance(n, ast.Assign) and isinstance(
                n.value, ast.Call
            ) and _leaf(_dotted(n.value.func)) == "Thread":
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        thread_locals.add(t.id)
                    a = _self_attr(t)
                    if a is not None:
                        attr_threads[a] = n
        for n in _own_nodes(fn):
            if not (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "start"
            ):
                continue
            recv = n.func.value
            if isinstance(recv, ast.Call) and (
                _leaf(_dotted(recv.func)) == "Thread"
            ):
                starts.append((n, None, None))  # unbound: never joinable
            elif isinstance(recv, ast.Name) and recv.id in thread_locals:
                starts.append((n, recv.id, None))
            else:
                a = _self_attr(recv)
                if a is not None and a in attr_threads:
                    starts.append((n, None, a))
        if not starts:
            return

        for site, local, attr in starts:
            if local is not None:
                verdict = self._local_thread_ok(fn, cls, local)
            elif attr is not None:
                verdict = self._attr_thread_ok(facts, attr)
            else:
                verdict = (
                    "an unbound Thread(...).start() can never be joined: "
                    "bind it and join it from the owner's teardown, or "
                    "register it on the owner's thread list"
                )
            if verdict is not None:
                self._emit(
                    "thread-unjoined", site,
                    f"started thread has no reachable join: {verdict}",
                )

    def _local_thread_ok(
        self, fn: ast.AST, cls: Optional[ast.ClassDef], name: str
    ) -> Optional[str]:
        facts = self._class_facts.get(cls) if cls is not None else None
        # alias chain: t = _thread; t.join(...) joins the same thread
        aliases: Set[str] = {name}
        grew = True
        while grew:
            grew = False
            for n in _all_nodes(fn):
                if isinstance(n, ast.Assign) and isinstance(
                    n.value, ast.Name
                ) and n.value.id in aliases:
                    for t in n.targets:
                        if isinstance(t, ast.Name) and t.id not in aliases:
                            aliases.add(t.id)
                            grew = True
        joined_local_containers: Set[str] = set()
        for n in _all_nodes(fn):
            if isinstance(n, (ast.For, ast.AsyncFor)) and isinstance(
                n.target, ast.Name
            ):
                v = n.target.id
                if any(
                    isinstance(c, ast.Call)
                    and _leaf(_dotted(c.func)) == "join"
                    and _call_base(c) == v
                    for b in n.body
                    for c in ast.walk(b)
                ):
                    for sub in ast.walk(n.iter):
                        if isinstance(sub, ast.Name):
                            joined_local_containers.add(sub.id)
        for n in _all_nodes(fn):
            if isinstance(n, ast.Call):
                leaf = _leaf(_dotted(n.func))
                base = _call_base(n)
                if leaf == "join" and base in aliases:
                    return None
                if leaf in ("append", "add", "put") and any(
                    isinstance(a, ast.Name) and a.id == name
                    for a in n.args
                ):
                    # registered on a thread list: the list's join loop
                    # is the join
                    if isinstance(n.func, ast.Attribute):
                        recv = n.func.value
                        a = _self_attr(recv)
                        if a is not None:
                            if facts is not None and (
                                a in facts.loop_joined_attrs
                                or a in facts.joined_attrs
                            ):
                                return None
                            return (
                                f"registered on self.{a} but no method "
                                f"of the owning class joins self.{a}'s "
                                f"elements (stop()/close() must drain "
                                f"the list)"
                            )
                        if (
                            isinstance(recv, ast.Name)
                            and recv.id in joined_local_containers
                        ):
                            return None
                        return (
                            "registered on a container that is never "
                            "join-drained in this function"
                        )
                elif any(
                    isinstance(a, ast.Name) and a.id == name
                    for a in list(n.args)
                    + [k.value for k in n.keywords]
                ) and leaf not in ("start", "Thread"):
                    return None  # handed onward: ownership transferred
            if isinstance(n, (ast.Return, ast.Yield)) and (
                n.value is not None
                and _mentions_name(n.value, {name})
            ):
                return None  # returned/yielded to the caller
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if _self_attr(t) is not None and isinstance(
                        n.value, ast.Name
                    ) and n.value.id == name:
                        a = _self_attr(t)
                        if facts is not None and a is not None and (
                            a in facts.joined_attrs
                            or a in facts.loop_joined_attrs
                        ):
                            return None
                        return (
                            f"stored on self.{a} but no method of the "
                            f"owning class joins it"
                        )
                    if (
                        isinstance(t, ast.Attribute)
                        and _self_attr(t) is None
                        and isinstance(n.value, ast.Name)
                        and n.value.id == name
                    ):
                        return None  # stored on another owner object
                    if isinstance(t, ast.Subscript) and isinstance(
                        n.value, ast.Name
                    ) and n.value.id == name:
                        return None
        return (
            f"local thread {name!r} is neither joined, registered on a "
            f"joined thread list, nor handed onward in this function"
        )

    def _attr_thread_ok(
        self, facts: Optional[ClassFacts], attr: str
    ) -> Optional[str]:
        if facts is not None and (
            attr in facts.joined_attrs or attr in facts.loop_joined_attrs
        ):
            return None
        return (
            f"self.{attr} is started but no method of the owning class "
            f"joins it (the stop()/close()/__exit__ family must)"
        )

    # -- fd-leak ------------------------------------------------------------

    def _acquire_kind(self, call: ast.Call) -> Optional[str]:
        dn = _dotted(call.func)
        leaf = _leaf(dn)
        if dn in ("socket.socket", "socket.create_connection"):
            return "socket"
        if dn in ("open", "io.open"):
            return "file"
        if leaf == "mkdtemp":
            return "tempdir"
        if leaf == "TemporaryDirectory":
            return "tempdirobj"
        return None

    def _check_fds(self, fn: ast.AST, cls: Optional[ast.ClassDef]) -> None:
        acquires: List[Tuple[ast.Call, str]] = []
        for n in _own_nodes(fn):
            if isinstance(n, ast.Call):
                kind = self._acquire_kind(n)
                if kind is None:
                    continue
                if kind in ("socket", "file") and not self.in_pkg:
                    continue  # fd facets pin the package only
                acquires.append((n, kind))
        if not acquires:
            return
        with_items: List[ast.AST] = []
        for n in _own_nodes(fn):
            if isinstance(n, (ast.With, ast.AsyncWith)):
                for item in n.items:
                    with_items.extend(ast.walk(item.context_expr))
        generator = _is_generator(fn)
        facts = self._class_facts.get(cls) if cls is not None else None

        for call, kind in acquires:
            if any(call is w for w in with_items):
                continue
            verdict = self._fd_verdict(fn, cls, facts, call, kind, generator)
            if verdict is not None:
                self._emit("fd-leak", call, verdict)

    def _fd_verdict(
        self,
        fn: ast.AST,
        cls: Optional[ast.ClassDef],
        facts: Optional[ClassFacts],
        call: ast.Call,
        kind: str,
        generator: bool,
    ) -> Optional[str]:
        # find the binding statement
        bound: Set[str] = set()
        attr_target: Optional[str] = None
        for n in _own_nodes(fn):
            if isinstance(n, ast.Assign) and any(
                x is call for x in ast.walk(n.value)
            ):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        bound.add(t.id)
                    a = _self_attr(t)
                    if a is not None:
                        attr_target = a
            if isinstance(n, (ast.Return, ast.Yield, ast.YieldFrom)) and (
                getattr(n, "value", None) is not None
                and any(x is call for x in ast.walk(n.value))
            ):
                return None  # handed straight to the caller/consumer
        noun = {
            "socket": "socket", "file": "file handle",
            "tempdir": "tempdir", "tempdirobj": "TemporaryDirectory",
        }[kind]
        if attr_target is not None:
            rel = (
                facts.released_attrs.get(attr_target, set())
                if facts is not None
                else set()
            )
            ok = {
                "socket": {"close", "shutdown"},
                "file": {"close"},
                "tempdir": {"rmtree"},
                "tempdirobj": {"cleanup"},
            }[kind]
            if rel & ok:
                return None
            return (
                f"{noun} stored on self.{attr_target} but no method of "
                f"the owning class releases it "
                f"({'/'.join(sorted(ok))}) — the teardown family must"
            )
        if not bound:
            if kind in ("socket", "file"):
                return None  # consumed by another call: handed onward
            return (
                f"{noun} created and its path immediately dropped: "
                f"nothing can ever rmtree it — bind the path and "
                f"release it in a finally"
            )
        names = set(bound)
        # alias chains: s2 = s
        for n in _own_nodes(fn):
            if isinstance(n, ast.Assign) and isinstance(
                n.value, ast.Name
            ) and n.value.id in names:
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)

        def released(node: ast.AST) -> bool:
            if not isinstance(node, ast.Call):
                return False
            leaf = _leaf(_dotted(node.func))
            base = _call_base(node)
            if kind in ("socket", "file"):
                return leaf in ("close", "shutdown") and base in names
            if kind == "tempdirobj":
                return leaf == "cleanup" and base in names
            return leaf == "rmtree" and any(
                _mentions_name(a, names) for a in node.args
            )

        for body in _own_finally_bodies(fn):
            if any(released(x) for s in body for x in ast.walk(s)):
                return None
        release_anywhere = any(released(n) for n in _all_nodes(fn))
        if generator and release_anywhere:
            # pytest-fixture idiom: teardown after yield runs on test
            # failure too
            return None
        for n in _all_nodes(fn):
            if isinstance(n, ast.Call):
                leaf = _leaf(_dotted(n.func))
                args = list(n.args) + [k.value for k in n.keywords]
                if leaf in _CLEANUP_REG_LEAVES and any(
                    _mentions_name(a, names) for a in args
                ):
                    return None  # registered cleanup
                if kind in ("socket", "file") and not released(n):
                    if leaf not in ("close", "shutdown") and any(
                        isinstance(a, ast.Name) and a.id in names
                        for a in args
                    ):
                        return None  # fd handed onward: new owner
            if isinstance(n, (ast.Return, ast.Yield)) and (
                n.value is not None and _mentions_name(n.value, names)
            ):
                return None  # ownership to the caller/consumer
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if (
                        _self_attr(t) is not None
                        or isinstance(t, ast.Subscript)
                    ) and _mentions_name(n.value, names):
                        return None  # stored on an owner
        if release_anywhere:
            return (
                f"{noun} is released on the straight-line path only — "
                f"an exception between acquire and release leaks it; "
                f"move the release into a finally (or use with)"
            )
        rel_name = {
            "socket": "close()", "file": "close()",
            "tempdir": "shutil.rmtree(...)", "tempdirobj": "cleanup()",
        }[kind]
        return (
            f"{noun} acquired but never released in this function: "
            f"{rel_name} in a finally, a with block, a registered "
            f"cleanup, or an ownership hand-off is required"
        )

    # -- lock-leak ----------------------------------------------------------

    def _check_locks(self, fn: ast.AST) -> None:
        for n in _own_nodes(fn):
            if not (
                isinstance(n, ast.Call)
                and _leaf(_dotted(n.func)) == "acquire"
                and isinstance(n.func, ast.Attribute)
            ):
                continue
            base = _call_base(n)
            if base is None:
                continue

            def release_pred(x: ast.AST, b=base) -> bool:
                return (
                    isinstance(x, ast.Call)
                    and _leaf(_dotted(x.func)) == "release"
                    and _call_base(x) == b
                )

            in_finally = any(
                any(release_pred(x) for s in body for x in ast.walk(s))
                for body in _own_finally_bodies(fn)
            )
            if not in_finally:
                self._emit(
                    "lock-leak", n,
                    f"bare {base}.acquire() without {base}.release() in "
                    f"a finally in this function — an exception between "
                    f"them wedges every later acquirer (use `with "
                    f"{base}:`)",
                )

    # -- pair-imbalance -----------------------------------------------------

    def _check_pairs(
        self, fn: ast.AST, cls: Optional[ast.ClassDef]
    ) -> None:
        facts = self._class_facts.get(cls) if cls is not None else None
        for n in _own_nodes(fn):
            if not (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
            ):
                continue
            leaf = _leaf(_dotted(n.func))
            base = _call_base(n)
            if base is None:
                continue
            for spec in self.pairs:
                if leaf != spec.acquire:
                    continue
                if spec.base_like and not any(
                    s in base.lower() for s in spec.base_like
                ):
                    continue
                verdict = self._pair_verdict(fn, facts, n, base, spec)
                if verdict is not None:
                    self._emit(
                        "pair-imbalance", n,
                        f"[{spec.name}] {base}.{spec.acquire}(...) "
                        f"{verdict}",
                    )

    def _pair_verdict(
        self,
        fn: ast.AST,
        facts: Optional[ClassFacts],
        acq: ast.Call,
        base: str,
        spec: PairSpec,
    ) -> Optional[str]:
        if spec.mode == "base":
            def pred(x: ast.AST) -> bool:
                return (
                    isinstance(x, ast.Call)
                    and _leaf(_dotted(x.func)) in spec.release
                    and _call_base(x) == base
                )

            for body in _own_finally_bodies(fn):
                if any(pred(x) for s in body for x in ast.walk(s)):
                    return None
            segs = _success_segments(fn, acq, None, spec.conditional)
            if segs is None or _segments_hit(segs, pred):
                return None
            # weakest tier: a split-phase release elsewhere in the
            # owning class (dispatcher hooks, drain paths)
            if any(pred(x) for x in _all_nodes(fn)):
                leak = "a success path misses the release"
            else:
                leak = "no release in this function"
            if facts is not None and (
                set(spec.release) & facts.release_leaves
            ):
                return None
            if base.split(".", 1)[0] in self._module_globals and any(
                pred(x) for x in ast.walk(self.tree)
            ):
                return None  # module-owned base, released in this file
            return (
                f"{leak} and no {'/'.join(spec.release)} anywhere in "
                f"the owning scope: every success path must discharge "
                f"the obligation ({spec.doc})"
            )

        # mode == "result": the returned obligation must be called or
        # handed onward
        result_var: Optional[str] = None
        for n in _own_nodes(fn):
            if isinstance(n, ast.Assign) and any(
                x is acq for x in ast.walk(n.value)
            ):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        result_var = t.id
                if any(_self_attr(t) is not None for t in n.targets):
                    return None  # stored on the owner: split-phase
            if isinstance(n, (ast.Return, ast.Yield)) and (
                getattr(n, "value", None) is not None
                and any(x is acq for x in ast.walk(n.value))
            ):
                return None  # handed straight to the caller
            if (
                isinstance(n, ast.Call)
                and n is not acq
                and any(x is acq for x in ast.walk(n))
            ):
                return None  # consumed by another call
        if result_var is not None:
            for n in ast.walk(fn):
                if isinstance(n, _NESTED) and n is not fn and (
                    _mentions_name(n, {result_var})
                ):
                    # captured by a closure defined here (the
                    # futures-resolved-by-returned-resolve shape):
                    # the closure is the new owner
                    return None
        if result_var is None:
            return (
                f"drops its result: the obligation (resolver/handle) is "
                f"lost the moment it is created ({spec.doc})"
            )

        rv = result_var

        def pred(x: ast.AST) -> bool:
            if isinstance(x, ast.Call):
                if (
                    isinstance(x.func, ast.Name) and x.func.id == rv
                ):
                    return True  # resolver()
                if _leaf(_dotted(x.func)) in spec.release:
                    # a declared release leaf discharges whatever is
                    # outstanding, receiver or bare teardown helper
                    # (shutdown_pool(broken=True) on the failure edge)
                    return True
                if any(
                    isinstance(a, ast.Name) and a.id == rv
                    for a in list(x.args)
                    + [k.value for k in x.keywords]
                ):
                    return True  # handed onward
            if isinstance(x, (ast.Return, ast.Yield)) and (
                getattr(x, "value", None) is not None
                and _mentions_name(x.value, {rv})
            ):
                return True
            if isinstance(x, ast.Assign) and (
                any(
                    _self_attr(t) is not None
                    or isinstance(t, ast.Subscript)
                    for t in x.targets
                )
                and _mentions_name(x.value, {rv})
            ):
                return True
            if isinstance(x, (ast.For, ast.AsyncFor)) and _mentions_name(
                x.iter, {rv}
            ):
                return True  # `for f in futures:` consumes the handles
            if isinstance(x, ast.comprehension) and _mentions_name(
                x.iter, {rv}
            ):
                return True
            return False

        for body in _own_finally_bodies(fn):
            if any(pred(x) for s in body for x in ast.walk(s)):
                return None
        segs = _success_segments(fn, acq, rv, spec.conditional)
        if segs is None or _segments_hit(segs, pred):
            return None
        return (
            f"has a success path where the result is neither called "
            f"nor handed onward ({spec.doc})"
        )

    # -- wire-unclamped -----------------------------------------------------

    def _check_wire(self, fn: ast.AST) -> None:
        tainted: Set[str] = set()

        def is_source(call: ast.Call) -> bool:
            leaf = _leaf(_dotted(call.func))
            return leaf in _WIRE_SOURCE_LEAVES or leaf.startswith(
                "decode_"
            )

        def expr_taint(e: Optional[ast.AST]) -> bool:
            if e is None:
                return False
            if isinstance(e, ast.Call):
                leaf = _leaf(_dotted(e.func))
                if leaf in _WIRE_SANITIZERS:
                    return False  # clamped
                if is_source(e):
                    return True
                return any(expr_taint(a) for a in e.args) or any(
                    expr_taint(k.value) for k in e.keywords
                )
            if isinstance(e, ast.Name):
                return e.id in tainted
            if isinstance(e, ast.Lambda):
                return False
            return any(expr_taint(c) for c in ast.iter_child_nodes(e))

        def flag(node: ast.AST, what: str) -> None:
            self._emit(
                "wire-unclamped", node,
                f"wire-decoded integer flows into {what} without a "
                f"min/clamp: a u32 off the wire must never buy an "
                f"unbounded {what} (the PR 8 retry_after_ms class)",
            )

        for node in _walk_in_order(fn):
            if isinstance(node, ast.Assign):
                t0 = node.targets[0] if len(node.targets) == 1 else None
                if (
                    isinstance(t0, (ast.Tuple, ast.List))
                    and isinstance(node.value, (ast.Tuple, ast.List))
                    and len(t0.elts) == len(node.value.elts)
                ):
                    for t_el, v_el in zip(t0.elts, node.value.elts):
                        if isinstance(t_el, ast.Name):
                            if expr_taint(v_el):
                                tainted.add(t_el.id)
                            else:
                                tainted.discard(t_el.id)
                    continue
                is_t = expr_taint(node.value)
                for t in node.targets:
                    for sub in ast.walk(t):
                        if isinstance(sub, ast.Name) and isinstance(
                            sub.ctx, ast.Store
                        ):
                            if is_t:
                                tainted.add(sub.id)
                            else:
                                tainted.discard(sub.id)
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target, ast.Name) and expr_taint(
                    node.value
                ):
                    tainted.add(node.target.id)
            elif isinstance(node, ast.Call):
                leaf = _leaf(_dotted(node.func))
                if leaf == "sleep" and node.args and expr_taint(
                    node.args[0]
                ):
                    flag(node, "sleep")
                if leaf in _TIMEOUT_POSITION_LEAVES and node.args and (
                    expr_taint(node.args[0])
                ):
                    flag(node, f"{leaf}() timeout")
                if leaf == "get" and len(node.args) >= 2 and expr_taint(
                    node.args[1]
                ):
                    flag(node, "get() timeout")
                if leaf in _ALLOC_LEAVES and any(
                    expr_taint(a) for a in node.args
                ):
                    flag(node, f"{leaf}() allocation size")
                for kw in node.keywords:
                    if kw.arg in _TIMEOUT_KWARGS and expr_taint(kw.value):
                        flag(node, f"{kw.arg}=")
            elif isinstance(node, ast.BinOp) and isinstance(
                node.op, ast.Mult
            ):
                l, r = node.left, node.right
                for const, var in ((l, r), (r, l)):
                    if isinstance(
                        const, (ast.List, ast.Constant)
                    ) and (
                        not isinstance(const, ast.Constant)
                        or isinstance(const.value, (str, bytes))
                    ) and expr_taint(var):
                        flag(node, "sequence-repeat allocation size")
                        break

    # -- blocking-unbudgeted ------------------------------------------------

    def _check_blocking(self, fn: ast.AST) -> None:
        has_bounding = any(
            isinstance(n, ast.Call)
            and _leaf(_dotted(n.func)) in _RECV_BOUNDING_LEAVES
            for n in _all_nodes(fn)
        )
        for n in _own_nodes(fn):
            if not (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
            ):
                continue
            leaf = _leaf(_dotted(n.func))
            if leaf in _RECV_LEAVES:
                if not has_bounding:
                    self._emit(
                        "blocking-unbudgeted", n,
                        f"{leaf}() on a request path with no "
                        f"settimeout/select in the enclosing function: "
                        f"a silent peer stalls this hop forever — "
                        f"every wait must derive from the budget",
                    )
                continue
            if leaf not in _BLOCKING_LEAVES:
                continue
            has_timeout_kw = any(
                kw.arg == "timeout" for kw in n.keywords
            )
            if has_timeout_kw:
                continue
            if not n.args:
                self._emit(
                    "blocking-unbudgeted", n,
                    f"{leaf}() with no timeout on a request path: a "
                    f"wedged peer blocks this hop forever — pass a "
                    f"budget-derived timeout",
                )
            elif (
                len(n.args) == 1
                and isinstance(n.args[0], ast.Constant)
                and n.args[0].value is True
            ):
                self._emit(
                    "blocking-unbudgeted", n,
                    f"{leaf}(True) blocks without a timeout on a "
                    f"request path — pass a budget-derived timeout",
                )


def _walk_in_order(node: ast.AST):
    """Depth-first pre-order (source order) over a scope's OWN body —
    the taint pass needs source order (``ast.walk`` is breadth-first)
    and must not leak taint across nested function boundaries (each
    nested def is its own scope, analyzed separately)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, _NESTED):
            continue
        yield child
        yield from _walk_in_order(child)


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def analyze_sources(
    sources: Dict[str, str],
    rule_ids: Optional[Iterable[str]] = None,
    pairs: Optional[Sequence[PairSpec]] = None,
    collect_suppressed: Optional[List[Finding]] = None,
) -> Tuple[List[Finding], Dict[str, int]]:
    """Analyze {path: source}.  ``pairs`` defaults to the packaged
    ``tools/pairs.toml`` (loud ValueError when missing/malformed)."""
    active = set(rule_ids) if rule_ids is not None else set(RULES)
    for rid in active:
        if rid not in RULES:
            raise ValueError(f"unknown rule id {rid!r}")
    if pairs is None and "pair-imbalance" in active:
        pairs = load_default_pairs()
    pairs = pairs or []

    findings: List[Finding] = []
    n_suppressed = 0
    for path, source in sorted(sources.items()):
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as exc:
            findings.append(
                Finding(
                    "syntax-error", path, exc.lineno or 1,
                    exc.offset or 0, f"cannot parse: {exc.msg}",
                )
            )
            continue
        raw = _FileAnalyzer(path, tree, pairs, active).run()
        supp = toolkit.suppressed_rules(source, "fablife")
        kept, suppressed = toolkit.apply_suppressions(raw, supp)
        findings.extend(kept)
        n_suppressed += len(suppressed)
        if collect_suppressed is not None:
            collect_suppressed.extend(suppressed)
    findings.sort(key=Finding.key)
    stats = {"files": len(sources), "suppressed": n_suppressed}
    return findings, stats


def analyze_source(
    source: str,
    path: str,
    rule_ids: Optional[Iterable[str]] = None,
    pairs: Optional[Sequence[PairSpec]] = None,
) -> Tuple[List[Finding], int]:
    """Single-blob convenience (fixtures/tests)."""
    findings, stats = analyze_sources({path: source}, rule_ids, pairs)
    return findings, stats["suppressed"]


def analyze_paths(
    paths: Sequence[str],
    rule_ids: Optional[Iterable[str]] = None,
    excludes: Sequence[str] = DEFAULT_EXCLUDES,
    pairs: Optional[Sequence[PairSpec]] = None,
) -> Tuple[List[Finding], Dict[str, int]]:
    files = iter_py_files(paths, excludes)
    sources, io_findings = toolkit.read_sources(files)
    findings, stats = analyze_sources(sources, rule_ids, pairs)
    findings.extend(io_findings)
    findings.sort(key=Finding.key)
    stats["files"] = len(files)
    return findings, stats


def live_suppression_keys(
    sources: Dict[str, str], rules: Set[str]
) -> Set[Tuple[str, int, str]]:
    """The toolkit analyzer-registry staleness protocol (consumed by
    fabreg's suppression-stale): (normalized path, line, rule) for
    every fablife suppression that still absorbs a finding."""
    needed = set(RULES) if "all" in rules else (rules & set(RULES))
    if not needed:
        return set()
    suppressed: List[Finding] = []
    analyze_sources(sources, needed, collect_suppressed=suppressed)
    return {
        (toolkit.normalize_path(f.path), f.line, f.rule)
        for f in suppressed
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = toolkit.build_parser(
        "fablife",
        "resource-lifetime + wire-trust analyzer for fabric-tpu "
        "(dependency-free; never imports the analyzed code)",
    )
    parser.add_argument(
        "--pairs",
        metavar="FILE",
        help="acquire/release pair table (default: tools/pairs.toml "
        "next to this module)",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        toolkit.print_rule_list(RULES, width=20)
        return 0

    rc = toolkit.check_paths_exist(args.paths, "fablife", parser)
    if rc:
        return rc
    rule_ids, rc = toolkit.parse_rule_arg(args.rules, RULES, "fablife")
    if rc:
        return rc

    pairs: Optional[List[PairSpec]] = None
    try:
        if args.pairs is not None:
            pairs = parse_pairs(
                Path(args.pairs).read_text(encoding="utf-8"), args.pairs
            )
        else:
            pairs = load_default_pairs()
    except (OSError, ValueError) as exc:
        print(f"fablife: error: pair table: {exc}", file=sys.stderr)
        return 2

    excludes = tuple(DEFAULT_EXCLUDES) + tuple(args.exclude)
    findings, stats = analyze_paths(args.paths, rule_ids, excludes, pairs)

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
            f"fablife: {len(findings)} finding(s) in {stats['files']} "
            f"file(s) ({stats['suppressed']} suppressed)"
        )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
