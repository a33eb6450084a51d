"""fabreg — declarative-contract drift analyzer for fabric-tpu.

fablint/fabdep/fabflow pin code-level invariants; fabreg pins the
*metadata* layer: the declarative tables the runtime and the gates
trust but nothing statically checks.  Four control surfaces drifted
into existence across PRs 6-10 — scattered ``FABRIC_TPU_*`` env reads,
the canonical metric-family table in ``common/fabobs.py``, the
``fault_point`` site set, and the per-line analyzer suppressions — and
each is exactly the config/registry drift that silently breaks the
"every family live on a scrape" and byte-identical-scorecard
guarantees.  Like its siblings, fabreg is pure ``ast`` + ``tokenize``:
it never imports analyzed code and runs without numpy/jax/cryptography.

Rules
-----
env-undeclared    an ``os.environ``/``os.getenv`` read of a
                  ``FABRIC_TPU_*`` name with no row in the central
                  registry ``fabric_tpu/common/envreg.py``.
env-dead          a registry row with no surviving reference anywhere
                  in the scanned tree (tests count as readers —
                  deprecation grace).
metric-unknown    a ``obs_count``/``obs_gauge``/``obs_observe`` emit
                  naming a family absent from ``CANONICAL_METRICS``
                  (the registry swallows it at runtime; the scrape
                  silently loses the series).
metric-label-drift an emit whose label set or sink kind disagrees with
                  the family's declaration.
metric-orphan     a canonical family with no emitter outside fabobs
                  itself (a dead ``# TYPE`` line on every scrape).
fault-site-drift  a ``fault_point(site=...)`` literal missing from the
                  README fault-point table or not exercised by any
                  fabchaos scenario (suppress with a reason to allow a
                  deliberately unexercised site).
suppression-stale a ``# fablint:/fabdep:/fabflow:/fabreg: disable=``
                  comment whose rule no longer fires at that line —
                  fabreg re-runs the owning analyzer scoped to the
                  suppressed rules and requires every comment to still
                  absorb a finding.  Suppressions must not outlive
                  their cause.

The byte-determinism taint rules that used to live here (the
``det-hazard`` rule over chaos scorecards) are fabdet's whole-program
job now — see ``fabric_tpu/tools/fabdet.py`` and ``tools/det.toml``.

Suppression
-----------
Per line, same grammar as the siblings:
``# fabreg: disable=rule-id[,rule-id...]  # <reason>``.  A
``disable=suppression-stale`` comment is never itself reported stale
(the check is one level deep by design).

Usage
-----
    python -m fabric_tpu.tools.fabreg [--json] [--list-rules]
        [--rules a,b] [--readme FILE] PATH...

Exit status: 0 = clean, 1 = findings, 2 = usage/IO error.
"""

from __future__ import annotations

import ast
import io
import json
import re
import sys
import tokenize
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

# --------------------------------------------------------------------------
# Configuration
# --------------------------------------------------------------------------

RULES: Dict[str, str] = {
    "env-undeclared": (
        "os.environ/os.getenv read of a FABRIC_TPU_* name with no row in "
        "the central registry common/envreg.py"
    ),
    "env-dead": (
        "envreg.py row with no surviving reference in the scanned tree "
        "(tests count as readers)"
    ),
    "metric-unknown": (
        "obs_count/obs_gauge/obs_observe emit naming a family absent from "
        "CANONICAL_METRICS (swallowed at runtime, lost on the scrape)"
    ),
    "metric-label-drift": (
        "emit whose label set or sink kind disagrees with the family's "
        "CANONICAL_METRICS declaration"
    ),
    "metric-orphan": (
        "canonical metric family with no emitter outside fabobs itself"
    ),
    "fault-site-drift": (
        "fault_point site literal missing from the README fault-point "
        "table or not exercised by any fabchaos scenario"
    ),
    "suppression-stale": (
        "a fablint/fabdep/fabflow/fabreg disable= comment whose rule no "
        "longer fires at that line"
    ),
}

ENV_PREFIX = "FABRIC_TPU_"
_ENV_NAME_RE = re.compile(r"^FABRIC_TPU_[A-Z0-9_]+$")

#: calls whose string arg is an env *read* (must be declared)
_ENV_READ_CALLS = {
    "os.environ.get", "environ.get",
    "os.getenv", "getenv",
    "os.environ.setdefault", "environ.setdefault",
}
#: env accessors that only *reference* a name (count for liveness)
_ENV_REF_CALLS = {"os.environ.pop", "environ.pop"}
_ENV_REF_LEAVES = {"setenv", "delenv"}  # pytest monkeypatch

#: obs sink -> (declared kind it implies, value-param kwarg to ignore)
_EMIT_SINKS = {
    "obs_count": ("counter", "n"),
    "obs_gauge": ("gauge", "value"),
    "obs_observe": ("histogram", "value"),
}

#: the runtime package scope: metric/fault/suppression discipline
#: applies inside the package; env rules cover everything scanned
#: (tests + bench read env vars too).
PKG_SCOPE = ("*fabric_tpu/*",)
ENVREG_FILE = ("*fabric_tpu/common/envreg.py",)
FABOBS_FILE = ("*fabric_tpu/common/fabobs.py",)
CHAOS_FILE = ("*fabric_tpu/tools/fabchaos.py",)


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
# Collected facts
# --------------------------------------------------------------------------


@dataclass
class EmitSite:
    family: str
    sink_kind: str          # counter | gauge | histogram (from the sink)
    labels: Optional[Set[str]]  # None when **labels defeats static check
    path: str
    line: int
    col: int


@dataclass
class SuppComment:
    tool: str
    path: str
    line: int
    col: int
    rules: Set[str]
    reason: str


@dataclass
class Scan:
    """Everything one pass over the sources collects; rules evaluate
    against this."""

    sources: Dict[str, str] = field(default_factory=dict)
    findings: List[Finding] = field(default_factory=list)  # syntax errors
    env_reads: List[Tuple[str, str, int, int]] = field(default_factory=list)
    env_refs: Set[str] = field(default_factory=set)
    emits: List[EmitSite] = field(default_factory=list)
    fault_sites: List[Tuple[str, str, int, int]] = field(default_factory=list)
    comments: List[SuppComment] = field(default_factory=list)
    #: path -> fabreg suppressions (for applying to our own findings)
    suppressions: Dict[str, Dict[int, Set[str]]] = field(default_factory=dict)
    envreg_path: Optional[str] = None
    envreg_rows: Dict[str, int] = field(default_factory=dict)  # name -> line
    fabobs_path: Optional[str] = None
    #: family -> (kind, labels, line)
    metric_table: Dict[str, Tuple[str, Tuple[str, ...], int]] = field(
        default_factory=dict
    )
    chaos_path: Optional[str] = None
    chaos_source: str = ""


def _extract_envreg(tree: ast.Module, scan: Scan) -> None:
    for node in tree.body:
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        if not any(
            isinstance(t, ast.Name) and t.id == "ENV_VARS" for t in targets
        ):
            continue
        if not isinstance(value, (ast.Tuple, ast.List)):
            continue
        for elt in value.elts:
            if not (
                isinstance(elt, ast.Call)
                and (_dotted(elt.func) or "").rsplit(".", 1)[-1] == "EnvVar"
            ):
                continue
            name: Optional[str] = None
            if elt.args and isinstance(elt.args[0], ast.Constant) and isinstance(
                elt.args[0].value, str
            ):
                name = elt.args[0].value
            for kw in elt.keywords:
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    name = kw.value.value
            if name:
                scan.envreg_rows[name] = elt.lineno


def _extract_metric_table(tree: ast.Module, scan: Scan) -> None:
    for node in tree.body:
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        if not any(
            isinstance(t, ast.Name) and t.id == "CANONICAL_METRICS"
            for t in targets
        ):
            continue
        if not isinstance(value, (ast.Tuple, ast.List)):
            continue
        for elt in value.elts:
            if not (
                isinstance(elt, ast.Call)
                and (_dotted(elt.func) or "").rsplit(".", 1)[-1]
                == "MetricSpec"
            ):
                continue
            fields: Dict[str, ast.expr] = {}
            for i, arg in enumerate(elt.args):
                key = ("name", "kind", "labels")[i] if i < 3 else None
                if key:
                    fields[key] = arg
            for kw in elt.keywords:
                if kw.arg:
                    fields[kw.arg] = kw.value
            name_n = fields.get("name")
            kind_n = fields.get("kind")
            labels_n = fields.get("labels")
            if not (
                isinstance(name_n, ast.Constant)
                and isinstance(name_n.value, str)
                and isinstance(kind_n, ast.Constant)
            ):
                continue
            labels: Tuple[str, ...] = ()
            if isinstance(labels_n, (ast.Tuple, ast.List)):
                labels = tuple(
                    e.value
                    for e in labels_n.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                )
            scan.metric_table[name_n.value] = (
                str(kind_n.value), labels, elt.lineno
            )


def _scan_comments(path: str, source: str, scan: Scan) -> None:
    """Genuine COMMENT tokens only: a ``disable=`` inside a test
    fixture *string* is data, not a suppression, and must not feed the
    stale check."""
    if "disable=" not in source:
        return
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        comments = [
            (tok.start[0], tok.start[1], tok.string)
            for tok in tokens
            if tok.type == tokenize.COMMENT
        ]
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return
    for line, col, text in comments:
        for tool in toolkit.ANALYZER_TOOLS:
            m = toolkit.disable_re(tool).search(text)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            scan.comments.append(
                SuppComment(
                    tool, path, line, col, rules, (m.group(2) or "").strip()
                )
            )


def _first_str_arg(call: ast.Call) -> Optional[str]:
    if call.args and isinstance(call.args[0], ast.Constant) and isinstance(
        call.args[0].value, str
    ):
        return call.args[0].value
    return None


def _scan_file(path: str, source: str, scan: Scan) -> None:
    ctx = FileContext(path)
    scan.sources[path] = source
    scan.suppressions[path] = toolkit.suppressed_rules(source, "fabreg")
    _scan_comments(path, source, scan)
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        scan.findings.append(
            Finding(
                "syntax-error", path, exc.lineno or 1, exc.offset or 0,
                f"cannot parse: {exc.msg}",
            )
        )
        return

    is_envreg = ctx.matches(ENVREG_FILE)
    if is_envreg:
        scan.envreg_path = path
        _extract_envreg(tree, scan)
    if ctx.matches(FABOBS_FILE):
        scan.fabobs_path = path
        _extract_metric_table(tree, scan)
    if ctx.matches(CHAOS_FILE):
        scan.chaos_path = path
        scan.chaos_source = source
    in_pkg = ctx.matches(PKG_SCOPE)
    is_fabobs = ctx.matches(FABOBS_FILE)

    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            # any full env-name string keeps a registry row alive —
            # except inside the registry itself (self-reference)
            if not is_envreg and _ENV_NAME_RE.match(node.value):
                scan.env_refs.add(node.value)
            continue
        if isinstance(node, ast.Subscript):
            base = _dotted(node.value)
            if base in ("os.environ", "environ"):
                sl = node.slice
                if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                    if sl.value.startswith(ENV_PREFIX):
                        scan.env_refs.add(sl.value)
                        if isinstance(node.ctx, ast.Load):
                            scan.env_reads.append(
                                (sl.value, path, node.lineno,
                                 node.col_offset)
                            )
            continue
        if not isinstance(node, ast.Call):
            continue
        dn = _dotted(node.func)
        if dn is None:
            continue
        leaf = dn.rsplit(".", 1)[-1]
        arg0 = _first_str_arg(node)

        if arg0 is not None and _ENV_NAME_RE.match(arg0) and not is_envreg:
            scan.env_refs.add(arg0)
            if dn in _ENV_REF_CALLS or leaf in _ENV_REF_LEAVES:
                pass  # setenv/delenv/pop reference a name, don't read it
            else:
                # a full FABRIC_TPU_* name as a call's first argument is
                # presumed an env read: direct accessors, and helper
                # wrappers like idemix/batch._env_int("FABRIC_TPU_...")
                # — a wrapper must not launder a read past the registry
                scan.env_reads.append(
                    (arg0, path, node.lineno, node.col_offset)
                )

        if in_pkg and not is_fabobs and leaf in _EMIT_SINKS:
            sink_kind, value_param = _EMIT_SINKS[leaf]
            if arg0 is not None:
                labels: Optional[Set[str]] = set()
                for kw in node.keywords:
                    if kw.arg is None:  # **labels — not statically known
                        labels = None
                        break
                    if kw.arg != value_param:
                        labels.add(kw.arg)
                scan.emits.append(
                    EmitSite(
                        arg0, sink_kind, labels, path, node.lineno,
                        node.col_offset,
                    )
                )

        if in_pkg and leaf == "fault_point" and arg0 is not None:
            scan.fault_sites.append(
                (arg0, path, node.lineno, node.col_offset)
            )


# --------------------------------------------------------------------------
# Rule evaluation
# --------------------------------------------------------------------------


def _check_env(scan: Scan, active: Set[str]) -> List[Finding]:
    out: List[Finding] = []
    have_reg = scan.envreg_path is not None
    if "env-undeclared" in active:
        for name, path, line, col in scan.env_reads:
            if name in scan.envreg_rows:
                continue
            where = (
                f"declare it in {scan.envreg_path}"
                if have_reg
                else "no env registry (common/envreg.py) found in the "
                "scanned tree"
            )
            out.append(
                Finding(
                    "env-undeclared", path, line, col,
                    f"read of undeclared env var {name!r}: {where} "
                    f"(name/type/default/consumer/doc)",
                )
            )
    if "env-dead" in active and have_reg:
        for name, line in sorted(scan.envreg_rows.items()):
            if name not in scan.env_refs:
                out.append(
                    Finding(
                        "env-dead", scan.envreg_path or "", line, 0,
                        f"registry row {name!r} has no reader anywhere in "
                        f"the scanned tree (tests count); delete "
                        f"the row or the feature it described",
                    )
                )
    return out


def _check_metrics(scan: Scan, active: Set[str]) -> List[Finding]:
    out: List[Finding] = []
    have_table = scan.fabobs_path is not None
    for e in scan.emits:
        spec = scan.metric_table.get(e.family)
        if spec is None:
            if "metric-unknown" in active:
                where = (
                    f"add it to CANONICAL_METRICS in {scan.fabobs_path}"
                    if have_table
                    else "no CANONICAL_METRICS table (common/fabobs.py) "
                    "found in the scanned tree"
                )
                out.append(
                    Finding(
                        "metric-unknown", e.path, e.line, e.col,
                        f"emit names unknown family {e.family!r}: the "
                        f"registry drops it at runtime; {where}",
                    )
                )
            continue
        if "metric-label-drift" not in active:
            continue
        kind, labels, _line = spec
        if e.sink_kind != kind:
            out.append(
                Finding(
                    "metric-label-drift", e.path, e.line, e.col,
                    f"{e.family!r} is declared a {kind} but emitted via "
                    f"the {e.sink_kind} sink",
                )
            )
        if e.labels is not None and e.labels != set(labels):
            declared = ",".join(labels) or "(none)"
            got = ",".join(sorted(e.labels)) or "(none)"
            out.append(
                Finding(
                    "metric-label-drift", e.path, e.line, e.col,
                    f"{e.family!r} declares labels ({declared}) but this "
                    f"emit passes ({got}); the SPI raises and the sample "
                    f"is swallowed",
                )
            )
    if "metric-orphan" in active and have_table:
        emitted = {e.family for e in scan.emits}
        for family, (_kind, _labels, line) in sorted(
            scan.metric_table.items()
        ):
            if family not in emitted:
                out.append(
                    Finding(
                        "metric-orphan", scan.fabobs_path or "", line, 0,
                        f"canonical family {family!r} has no emitter "
                        f"outside fabobs: a dead # TYPE line on every "
                        f"scrape; emit it or delete the row",
                    )
                )
    return out


def _check_fault_sites(
    scan: Scan, active: Set[str], readme_text: Optional[str]
) -> List[Finding]:
    if "fault-site-drift" not in active:
        return []
    out: List[Finding] = []
    for site, path, line, col in scan.fault_sites:
        problems: List[str] = []
        if readme_text is not None and site not in readme_text:
            problems.append("missing from the README fault-point table")
        if scan.chaos_path is None:
            problems.append(
                "no fabchaos scenario file (tools/fabchaos.py) in the "
                "scanned tree"
            )
        elif site not in scan.chaos_source:
            problems.append(
                "not exercised by any fabchaos scenario"
            )
        if problems:
            out.append(
                Finding(
                    "fault-site-drift", path, line, col,
                    f"fault site {site!r} is {'; '.join(problems)} "
                    f"(document + exercise it, or suppress with a reason)",
                )
            )
    return out


# -- suppression-stale -------------------------------------------------------


# the staleness protocol's shared normalizer (both sides of the
# live-keys comparison must match byte-for-byte)
_norm = toolkit.normalize_path


def _pkg_root_for(path: str) -> Optional[Path]:
    """The topmost package dir containing ``path`` (walk up while
    __init__.py is present) — what fabdep.analyze wants as its root."""
    p = Path(path).resolve()
    if not p.exists():
        return None
    cur = p.parent
    root: Optional[Path] = None
    while (cur / "__init__.py").exists():
        root = cur
        cur = cur.parent
    return root


def _live_keys_fablint(
    comments: List[SuppComment], scan: Scan
) -> Set[Tuple[str, int, str]]:
    from fabric_tpu.tools import fablint

    live: Set[Tuple[str, int, str]] = set()
    by_file: Dict[str, Set[str]] = {}
    for c in comments:
        by_file.setdefault(c.path, set()).update(c.rules)
    for path, rules in by_file.items():
        source = scan.sources.get(path)
        if source is None:
            continue
        needed = set(fablint.RULES) if "all" in rules else (
            rules & set(fablint.RULES)
        )
        if not needed:
            continue
        suppressed: List[Finding] = []
        fablint.lint_source(source, path, needed, suppressed)
        for f in suppressed:
            live.add((_norm(f.path), f.line, f.rule))
    return live


def _live_keys_fabflow(
    comments: List[SuppComment], scan: Scan
) -> Set[Tuple[str, int, str]]:
    from fabric_tpu.tools import fabflow

    needed: Set[str] = set()
    for c in comments:
        needed |= c.rules
    needed = set(fabflow.RULES) if "all" in needed else (
        needed & set(fabflow.RULES)
    )
    if not needed:
        return set()
    # mirror the flow_gate scope: fabflow analyzes the package tree,
    # not tests/bench (and skipping those files saves ~1s per gate run)
    pkg_sources = {
        path: src
        for path, src in scan.sources.items()
        if FileContext(path).matches(PKG_SCOPE)
    }
    suppressed: List[Finding] = []
    fabflow.analyze_sources(pkg_sources, needed, suppressed)
    return {(_norm(f.path), f.line, f.rule) for f in suppressed}


def _live_keys_fabdep(
    comments: List[SuppComment],
) -> Set[Tuple[str, int, str]]:
    from fabric_tpu.tools import fabdep

    live: Set[Tuple[str, int, str]] = set()
    roots: Dict[Path, Set[str]] = {}
    for c in comments:
        root = _pkg_root_for(c.path)
        if root is not None:
            roots.setdefault(root, set()).update(c.rules)
    for root, rules in roots.items():
        needed = set(fabdep.RULES) if "all" in rules else (
            rules & set(fabdep.RULES)
        )
        if not needed:
            continue
        layer_map = None
        layer_file = fabdep.default_layer_file(root)
        if layer_file is not None:
            try:
                layer_map = fabdep.LayerMap.parse(
                    layer_file.read_text(encoding="utf-8"), str(layer_file)
                )
            except (OSError, ValueError):
                layer_map = None
        program, _findings = fabdep.analyze(
            root,
            layer_map,
            fabdep.default_ref_paths(root),
            needed,
            skip_unneeded_passes=True,
        )
        for f in program.suppressed_findings:
            live.add((_norm(f.path), f.line, f.rule))
    return live


def _live_keys_registered(
    tool: str, comments: List[SuppComment], scan: Scan
) -> Set[Tuple[str, int, str]]:
    """Staleness for a registry-declared analyzer: lazily import its
    module and ask its ``live_suppression_keys(sources, rules)``
    protocol hook (see toolkit.AnalyzerSpec)."""
    spec = toolkit.analyzer_spec(tool)
    if spec is None:
        return set()
    try:
        import importlib

        module = importlib.import_module(spec.module)
        hook = getattr(module, "live_suppression_keys")
    except (ImportError, AttributeError):
        # a registry row without a reachable protocol hook judges
        # nothing (its comments are all reported stale — loud, so the
        # drift is fixed, rather than silently un-checked)
        return set()
    needed: Set[str] = set()
    for c in comments:
        needed |= c.rules
    try:
        return set(hook(dict(scan.sources), needed))
    except (OSError, ValueError):
        # unreadable/malformed analyzer config (e.g. pairs.toml gone):
        # judge nothing — the comments all read stale, loudly
        return set()


def _check_suppression_stale(
    scan: Scan, active: Set[str], own_suppressed: List[Finding]
) -> List[Finding]:
    if "suppression-stale" not in active:
        return []
    by_tool: Dict[str, List[SuppComment]] = {}
    for c in scan.comments:
        spec = toolkit.analyzer_spec(c.tool)
        if spec is not None and spec.pkg_scope_only and not (
            FileContext(c.path).matches(PKG_SCOPE)
        ):
            # a gate that only analyzes the package tree never honors
            # comments outside it — they are inert, not stale; tools
            # whose gates also scan tests/ (fabreg, fablife) declare
            # pkg_scope_only=False in the registry and are judged
            # everywhere they are honored
            continue
        by_tool.setdefault(c.tool, []).append(c)

    live: Dict[str, Set[Tuple[str, int, str]]] = {}
    if by_tool.get("fablint"):
        live["fablint"] = _live_keys_fablint(by_tool["fablint"], scan)
    if by_tool.get("fabflow"):
        live["fabflow"] = _live_keys_fabflow(by_tool["fabflow"], scan)
    if by_tool.get("fabdep"):
        live["fabdep"] = _live_keys_fabdep(by_tool["fabdep"])
    live["fabreg"] = {
        (_norm(f.path), f.line, f.rule) for f in own_suppressed
    }
    # post-toolkit analyzers (fablife, and any future registry row):
    # resolved through the toolkit registry's staleness protocol, so a
    # sixth analyzer is picked up without editing this function
    for tool, comments in by_tool.items():
        if tool in toolkit.LEGACY_ANALYZER_TOOLS:
            continue
        live[tool] = _live_keys_registered(tool, comments, scan)

    out: List[Finding] = []
    for tool, comments in sorted(by_tool.items()):
        tool_live = live.get(tool, set())
        tool_rules = None
        if tool == "fabreg":
            tool_rules = set(RULES)
        for c in comments:
            key_path = _norm(c.path)
            fired_any = any(
                k[0] == key_path and k[1] == c.line for k in tool_live
            )
            for rule in sorted(c.rules):
                if tool == "fabreg" and rule == "suppression-stale":
                    continue  # one level deep: never self-report
                if rule == "all":
                    dead = not fired_any
                else:
                    dead = (key_path, c.line, rule) not in tool_live
                    if tool_rules is not None and rule not in tool_rules:
                        # unknown rule id in a fabreg comment: dead by
                        # definition (typo'd suppressions silence nothing)
                        dead = True
                if dead:
                    out.append(
                        Finding(
                            "suppression-stale", c.path, c.line, c.col,
                            f"'# {tool}: disable={rule}' no longer "
                            f"suppresses anything here (the {tool} "
                            f"finding it absorbed is gone); delete the "
                            f"comment so the suppression does not "
                            f"outlive its cause",
                        )
                    )
    return out


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def find_readme(paths: Sequence[str]) -> Optional[str]:
    """Default README resolution: next to or one level above any
    scanned directory."""
    for raw in paths:
        p = Path(raw)
        base = p if p.is_dir() else p.parent
        for cand in (base / "README.md", base.parent / "README.md"):
            if cand.is_file():
                return str(cand)
    return None


def analyze_sources(
    sources: Dict[str, str],
    rule_ids: Optional[Iterable[str]] = None,
    readme_text: Optional[str] = None,
) -> Tuple[List[Finding], Dict[str, int]]:
    """Analyze {path: source}.  Paths that exist on disk additionally
    feed the fabdep half of suppression-stale (fabdep needs a real
    package root); fablint/fabflow/fabreg staleness is computed
    in-memory."""
    active = set(rule_ids) if rule_ids is not None else set(RULES)
    for rid in active:
        if rid not in RULES:
            raise ValueError(f"unknown rule id {rid!r}")
    scan = Scan()
    for path, source in sources.items():
        _scan_file(path, source, scan)

    # suppression-stale judges fabreg's OWN comments by whether their
    # rule fires at that line — that baseline needs every rule
    # evaluated even when the caller asked for a subset (only the
    # active rules are *reported*)
    eval_rules = (
        set(RULES) if "suppression-stale" in active else set(active)
    )
    raw: List[Finding] = list(scan.findings)  # syntax errors
    raw += _check_env(scan, eval_rules)
    raw += _check_metrics(scan, eval_rules)
    raw += _check_fault_sites(scan, eval_rules, readme_text)

    findings: List[Finding] = []
    suppressed_all: List[Finding] = []
    n_suppressed = 0
    for f in raw:
        kept_f, supp_f = toolkit.apply_suppressions(
            [f], scan.suppressions.get(f.path, {})
        )
        findings += [
            k for k in kept_f if k.rule in active or k.rule == "syntax-error"
        ]
        suppressed_all += supp_f
        n_suppressed += sum(1 for s in supp_f if s.rule in active)

    stale = _check_suppression_stale(scan, active, suppressed_all)
    for f in stale:
        kept_f, supp_f = toolkit.apply_suppressions(
            [f], scan.suppressions.get(f.path, {})
        )
        findings += kept_f
        n_suppressed += len(supp_f)

    findings.sort(key=Finding.key)
    stats = {"files": len(sources), "suppressed": n_suppressed}
    return findings, stats


def analyze_source(
    source: str,
    path: str,
    rule_ids: Optional[Iterable[str]] = None,
    readme_text: Optional[str] = None,
) -> Tuple[List[Finding], int]:
    """Single-blob convenience (fixtures/tests)."""
    findings, stats = analyze_sources({path: source}, rule_ids, readme_text)
    return findings, stats["suppressed"]


def analyze_paths(
    paths: Sequence[str],
    rule_ids: Optional[Iterable[str]] = None,
    excludes: Sequence[str] = DEFAULT_EXCLUDES,
    readme: Optional[str] = None,
) -> Tuple[List[Finding], Dict[str, int]]:
    files = iter_py_files(paths, excludes)
    sources, io_findings = toolkit.read_sources(files)
    readme_text: Optional[str] = None
    readme_path = readme if readme is not None else find_readme(paths)
    if readme_path:
        try:
            readme_text = Path(readme_path).read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError) as exc:
            io_findings.append(
                Finding("io-error", readme_path, 1, 0, str(exc))
            )
    findings, stats = analyze_sources(sources, rule_ids, readme_text)
    findings.extend(io_findings)
    findings.sort(key=Finding.key)
    stats["files"] = len(files)
    return findings, stats


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = toolkit.build_parser(
        "fabreg",
        "declarative-contract drift analyzer for fabric-tpu "
        "(dependency-free; never imports the analyzed code)",
    )
    parser.add_argument(
        "--readme",
        metavar="FILE",
        help="README carrying the fault-point table (default: "
        "README.md beside or above a scanned directory)",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        toolkit.print_rule_list(RULES, width=20)
        return 0

    rc = toolkit.check_paths_exist(args.paths, "fabreg", parser)
    if rc:
        return rc
    rule_ids, rc = toolkit.parse_rule_arg(args.rules, RULES, "fabreg")
    if rc:
        return rc
    if args.readme and not Path(args.readme).is_file():
        print(
            f"fabreg: error: no such file: {args.readme}", file=sys.stderr
        )
        return 2

    excludes = tuple(DEFAULT_EXCLUDES) + tuple(args.exclude)
    findings, stats = analyze_paths(
        args.paths, rule_ids, excludes, readme=args.readme
    )

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
            f"fabreg: {len(findings)} finding(s) in {stats['files']} "
            f"file(s) ({stats['suppressed']} suppressed)"
        )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
