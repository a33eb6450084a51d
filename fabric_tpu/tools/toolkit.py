"""toolkit — the shared scaffold under the fabric-tpu static analyzers.

fablint (per-file invariants), fabdep (whole-program layering +
concurrency), fabflow (value-range abstract interpretation) and fabreg
(declarative-contract drift) are four different analyses with one
identical chassis: walk the repo skipping generated artifacts, parse
per-line ``# <tool>: disable=rule  # reason`` suppressions, report
``Finding`` records, and drive it all from a ``--json`` /
``--list-rules`` / ``--rules`` CLI with the shared exit-code convention
(0 = clean, 1 = findings, 2 = usage/IO error).  Before this module each
tool re-implemented that chassis; now they share it, so a fifth
analyzer costs only its rules.

Everything here is dependency-free stdlib (``ast`` isn't even needed —
the tools own their parsing); nothing imports analyzed code, so the
tools keep running in minimal environments without cryptography/jax/
numpy.

Suppression grammar (shared by every tool; ``<tool>`` is the tool
name)::

    # <tool>: disable=rule-id[,rule-id...]  # <reason>

``disable=all`` silences every rule for that line.  The trailing
comment is the justification; :func:`parse_suppressions` returns it so
tools (fabflow's numeric-bound discipline, fabreg's suppression-stale
rule) can hold suppressions to their stated reasons.
"""

from __future__ import annotations

import argparse
import fnmatch
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__version__ = "1.0"

#: Generated / non-source artifacts no analyzer ever parses.
DEFAULT_EXCLUDES = (
    "*_pb2.py",
    "*/__pycache__/*",
    "*/native/*",
    "*/protos/src/*",
    "*/.git/*",
)

@dataclass(frozen=True)
class AnalyzerSpec:
    """One registered analyzer — the single source of truth fabreg's
    suppression-stale rule iterates, so a new analyzer is picked up by
    adding a row HERE (plus implementing the staleness protocol in its
    module) without editing fabreg.

    ``module``: dotted import path.  For post-toolkit analyzers the
    module must expose ``live_suppression_keys(sources, rules) ->
    {(normalized_path, line, rule), ...}`` — the set of suppression
    comments that still absorb a finding.  The three pre-toolkit tools
    (fablint/fabdep/fabflow) predate the protocol; fabreg carries
    legacy adapters for exactly those names and resolves everything
    else through this registry.

    ``pkg_scope_only``: True when the tool's CI gate analyzes only the
    package tree — its suppression comments outside it are inert and
    never judged stale.  Tools whose gates also scan tests/
    (fabreg, fablife) set False."""

    name: str
    module: str
    pkg_scope_only: bool = True


#: The analyzer registry (fabreg's suppression-stale rule scans every
#: row's suppression comments; all share the grammar above).
ANALYZER_SPECS: Tuple["AnalyzerSpec", ...] = (
    AnalyzerSpec("fablint", "fabric_tpu.tools.fablint"),
    AnalyzerSpec("fabdep", "fabric_tpu.tools.fabdep"),
    AnalyzerSpec("fabflow", "fabric_tpu.tools.fabflow"),
    AnalyzerSpec("fabreg", "fabric_tpu.tools.fabreg", pkg_scope_only=False),
    AnalyzerSpec("fablife", "fabric_tpu.tools.fablife", pkg_scope_only=False),
    AnalyzerSpec("fabwire", "fabric_tpu.tools.fabwire"),
    AnalyzerSpec("fabtrace", "fabric_tpu.tools.fabtrace"),
    AnalyzerSpec("fabdet", "fabric_tpu.tools.fabdet"),
)

#: Historical shape: the tool-name tuple (derived from the registry).
ANALYZER_TOOLS = tuple(spec.name for spec in ANALYZER_SPECS)

#: The pre-toolkit tools fabreg adapts by hand; everything else must
#: implement the ``live_suppression_keys`` protocol.
LEGACY_ANALYZER_TOOLS = ("fablint", "fabdep", "fabflow", "fabreg")


def analyzer_spec(name: str) -> Optional["AnalyzerSpec"]:
    for spec in ANALYZER_SPECS:
        if spec.name == name:
            return spec
    return None


def normalize_path(path: str) -> str:
    """The ONE path normalization the suppression-staleness protocol
    keys on: fabreg compares ``live_suppression_keys`` results against
    comment locations, and both sides must normalize identically or
    every suppression silently reads stale."""
    try:
        return Path(path).resolve().as_posix()
    except OSError:
        return Path(path).as_posix()


@dataclass
class Finding:
    """One analyzer finding.  ``key()`` is the canonical sort/dedup
    order shared by every tool's output."""

    rule: str
    path: str
    line: int
    col: int
    message: str

    def key(self) -> Tuple[str, int, int, str]:
        return (self.path, self.line, self.col, self.rule)

    def to_dict(self) -> Dict[str, object]:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
        }


class FileContext:
    """Per-file info shared by rules: posix path + path predicates."""

    def __init__(self, path: str) -> None:
        self.path = path
        self.posix = Path(path).as_posix()

    def matches(self, patterns: Iterable[str]) -> bool:
        return any(fnmatch.fnmatch(self.posix, pat) for pat in patterns)


_DISABLE_RES: Dict[str, "re.Pattern[str]"] = {}


def disable_re(tool: str) -> "re.Pattern[str]":
    """The compiled suppression regex for one tool's comments."""
    pat = _DISABLE_RES.get(tool)
    if pat is None:
        pat = _DISABLE_RES[tool] = re.compile(
            r"#\s*" + re.escape(tool)
            + r":\s*disable=([A-Za-z0-9_\-, ]+)(?:#\s*(.*))?"
        )
    return pat


def parse_suppressions(
    source: str, tool: str
) -> Dict[int, Tuple[Set[str], str]]:
    """1-based line number -> (disabled rule ids, reason text)."""
    pat = disable_re(tool)
    out: Dict[int, Tuple[Set[str], str]] = {}
    for lineno, line in enumerate(source.splitlines(), start=1):
        m = pat.search(line)
        if m:
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            out[lineno] = (rules, (m.group(2) or "").strip())
    return out


def suppressed_rules(
    source: str, tool: str
) -> Dict[int, Set[str]]:
    """:func:`parse_suppressions` without the reasons (fablint/fabdep's
    historical shape)."""
    return {
        line: rules
        for line, (rules, _reason) in parse_suppressions(source, tool).items()
    }


def apply_suppressions(
    findings: Iterable[Finding],
    suppressions: Dict[int, Set[str]],
) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (kept, suppressed) against one file's
    per-line suppression map."""
    kept: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        disabled = suppressions.get(f.line, set())
        if f.rule in disabled or "all" in disabled:
            suppressed.append(f)
        else:
            kept.append(f)
    return kept, suppressed


def iter_py_files(paths: Sequence[str], excludes: Sequence[str]) -> List[str]:
    """Expand files/directories to the sorted ``*.py`` set minus the
    exclusion globs (the shared repo walk)."""
    out: List[str] = []
    for raw in paths:
        p = Path(raw)
        candidates = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in candidates:
            posix = f.as_posix()
            if any(fnmatch.fnmatch(posix, pat) for pat in excludes):
                continue
            out.append(str(f))
    return out


def read_sources(
    files: Sequence[str],
) -> Tuple[Dict[str, str], List[Finding]]:
    """Read every file; unreadable ones become ``io-error`` findings
    instead of exceptions (the gate must report, not crash)."""
    sources: Dict[str, str] = {}
    io_findings: List[Finding] = []
    for f in files:
        try:
            sources[f] = Path(f).read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError) as exc:
            io_findings.append(Finding("io-error", f, 1, 0, str(exc)))
    return sources, io_findings


# --------------------------------------------------------------------------
# CLI plumbing
# --------------------------------------------------------------------------


def build_parser(
    prog: str, description: str, paths_help: str = "files or directories"
) -> argparse.ArgumentParser:
    """The shared argument set: paths + --json/--list-rules/--rules/
    --exclude.  Tools add their extras on top."""
    parser = argparse.ArgumentParser(prog=prog, description=description)
    parser.add_argument("paths", nargs="*", help=paths_help)
    parser.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print rule ids and exit"
    )
    parser.add_argument(
        "--rules",
        metavar="ID[,ID...]",
        help="run only these rule ids (default: all)",
    )
    parser.add_argument(
        "--exclude",
        action="append",
        default=[],
        metavar="GLOB",
        help="extra exclusion globs (added to the built-in generated-code "
        "list)",
    )
    return parser


def print_rule_list(docs: Dict[str, str], width: int) -> None:
    for rid in sorted(docs):
        print(f"{rid:{width}s} {docs[rid]}")


def parse_rule_arg(
    raw: Optional[str], known: Iterable[str], prog: str
) -> Tuple[Optional[List[str]], int]:
    """``--rules a,b`` -> (ids, 0), or (None, 2) after printing the
    shared unknown-rule usage error."""
    if not raw:
        return None, 0
    import sys

    rule_ids = [r.strip() for r in raw.split(",") if r.strip()]
    known_set = set(known)
    unknown = [r for r in rule_ids if r not in known_set]
    if unknown:
        print(
            f"{prog}: error: unknown rule(s): {', '.join(unknown)}",
            file=sys.stderr,
        )
        return None, 2
    return rule_ids, 0


def check_paths_exist(
    paths: Sequence[str], prog: str, parser: argparse.ArgumentParser
) -> int:
    """The shared no-paths / missing-path usage errors (exit code 2)."""
    import sys

    if not paths:
        parser.print_usage(sys.stderr)
        print(f"{prog}: error: no paths given", file=sys.stderr)
        return 2
    missing = [p for p in paths if not Path(p).exists()]
    if missing:
        print(
            f"{prog}: error: no such file or directory: "
            f"{', '.join(missing)}",
            file=sys.stderr,
        )
        return 2
    return 0


def print_findings(findings: Iterable[Finding]) -> None:
    for f in findings:
        print(f"{f.path}:{f.line}:{f.col}: {f.rule}: {f.message}")
