"""fabreg unit tests: a firing fixture + negative control per rule,
suppression semantics, CLI plumbing, the toolkit chassis, and the repo
self-check (the CI gate invariant: ``fabreg fabric_tpu/ tests/``
reports 0 unsuppressed findings).

Fixture code lives in *strings* on purpose: the repo self-check scans
this file too, and only genuine AST calls / genuine comments may feed
the rules (a ``disable=`` inside a string is data — asserted below).
"""

import json
import textwrap
from pathlib import Path

import pytest

from fabric_tpu.tools import fabreg, toolkit

REPO_ROOT = Path(__file__).resolve().parent.parent


def rule_ids(findings):
    return [f.rule for f in findings]


def analyze(sources, rules=None, readme=None):
    findings, _stats = fabreg.analyze_sources(
        {p: textwrap.dedent(s) for p, s in sources.items()},
        rules,
        readme_text=readme,
    )
    return findings


# a minimal env registry fixture (AST-parsed, never imported)
ENVREG = """
    ENV_VARS = (
        EnvVar("FABRIC_TPU_DECLARED", "int", "1", "m.py", "a knob"),
    )
"""
ENVREG_PATH = "fabric_tpu/common/envreg.py"

# a minimal canonical metric table fixture
FABOBS = """
    CANONICAL_METRICS = (
        MetricSpec("fabric_x_total", "counter", ("mode",), "h", "seam"),
        MetricSpec("fabric_y_seconds", "histogram", (), "h", "seam"),
    )
"""
FABOBS_PATH = "fabric_tpu/common/fabobs.py"

EMITTERS = textwrap.dedent(
    """
    def hook():
        obs_count("fabric_x_total", 2, mode="a")
        obs_observe("fabric_y_seconds", 0.1)
    """
)


# ---------------------------------------------------------------------------
# env-undeclared / env-dead
# ---------------------------------------------------------------------------


def test_env_undeclared_fires_on_unregistered_read():
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": """
                import os
                V = os.environ.get("FABRIC_TPU_MYSTERY", "")
            """,
        },
        rules=["env-undeclared"],
    )
    assert rule_ids(findings) == ["env-undeclared"]
    assert "FABRIC_TPU_MYSTERY" in findings[0].message


def test_env_undeclared_covers_getenv_subscript_and_setdefault():
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": """
                import os
                A = os.getenv("FABRIC_TPU_A")
                B = os.environ["FABRIC_TPU_B"]
                os.environ.setdefault("FABRIC_TPU_C", "1")
            """,
        },
        rules=["env-undeclared"],
    )
    assert rule_ids(findings) == ["env-undeclared"] * 3


def test_env_undeclared_sees_reads_through_helper_wrappers():
    # idemix/batch.py's `_env_int("FABRIC_TPU_X", 8)` pattern: a full
    # env name as a call's first argument is a read, wrapper or not —
    # a helper must not launder a read past the registry
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": """
                def f():
                    return _env_int("FABRIC_TPU_WRAPPED", 8)
            """,
        },
        rules=["env-undeclared"],
    )
    assert rule_ids(findings) == ["env-undeclared"]
    # ...while monkeypatch-style setters stay references, not reads
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": """
                def f(monkeypatch):
                    monkeypatch.setenv("FABRIC_TPU_SET_ONLY", "1")
            """,
        },
        rules=["env-undeclared"],
    )
    assert findings == []


def test_env_undeclared_negative_declared_read_is_clean():
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": """
                import os
                V = os.environ.get("FABRIC_TPU_DECLARED", "1")
            """,
        },
        rules=["env-undeclared"],
    )
    assert findings == []


def test_env_undeclared_fires_without_a_registry_at_all():
    findings = analyze(
        {
            "fabric_tpu/m.py": """
                import os
                V = os.environ.get("FABRIC_TPU_MYSTERY", "")
            """
        },
        rules=["env-undeclared"],
    )
    assert rule_ids(findings) == ["env-undeclared"]
    assert "no env registry" in findings[0].message


def test_env_dead_fires_on_readerless_row():
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": "X = 1\n",
        },
        rules=["env-dead"],
    )
    assert rule_ids(findings) == ["env-dead"]
    assert findings[0].path == ENVREG_PATH
    assert "FABRIC_TPU_DECLARED" in findings[0].message


def test_env_dead_negative_any_reference_keeps_a_row_alive():
    # an accessor read...
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": """
                import os
                V = os.environ.get("FABRIC_TPU_DECLARED", "1")
            """,
        },
        rules=["env-dead"],
    )
    assert findings == []
    # ...or a bare string mention (a test exercising the knob)
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": 'NAME = "FABRIC_TPU_DECLARED"\n',
        },
        rules=["env-dead"],
    )
    assert findings == []


def test_env_dead_registry_self_reference_does_not_count():
    # the row's own name literal inside envreg.py must not make it live
    findings = analyze({ENVREG_PATH: ENVREG}, rules=["env-dead"])
    assert rule_ids(findings) == ["env-dead"]


# ---------------------------------------------------------------------------
# metric-unknown / metric-label-drift / metric-orphan
# ---------------------------------------------------------------------------


def test_metric_unknown_fires_on_unregistered_family():
    findings = analyze(
        {
            FABOBS_PATH: FABOBS,
            "fabric_tpu/serve/m.py": EMITTERS + textwrap.dedent(
                """
                def bad():
                    obs_count("fabric_zzz_total")
                """
            ),
        },
        rules=["metric-unknown"],
    )
    assert rule_ids(findings) == ["metric-unknown"]
    assert "fabric_zzz_total" in findings[0].message


def test_metric_unknown_negative_canonical_emit_is_clean():
    findings = analyze(
        {FABOBS_PATH: FABOBS, "fabric_tpu/serve/m.py": EMITTERS},
        rules=["metric-unknown"],
    )
    assert findings == []


def test_metric_label_drift_fires_on_missing_and_extra_labels():
    findings = analyze(
        {
            FABOBS_PATH: FABOBS,
            "fabric_tpu/serve/m.py": """
                def bad():
                    obs_count("fabric_x_total")
                    obs_observe("fabric_y_seconds", 0.1, stage="x")
            """,
        },
        rules=["metric-label-drift"],
    )
    assert rule_ids(findings) == ["metric-label-drift"] * 2


def test_metric_label_drift_fires_on_kind_mismatch():
    findings = analyze(
        {
            FABOBS_PATH: FABOBS,
            "fabric_tpu/serve/m.py": """
                def bad():
                    obs_gauge("fabric_x_total", 1.0, mode="a")
            """,
        },
        rules=["metric-label-drift"],
    )
    assert rule_ids(findings) == ["metric-label-drift"]
    assert "counter" in findings[0].message


def test_metric_label_drift_negative_exact_labels_clean():
    findings = analyze(
        {FABOBS_PATH: FABOBS, "fabric_tpu/serve/m.py": EMITTERS},
        rules=["metric-label-drift"],
    )
    assert findings == []


def test_metric_orphan_fires_without_an_emitter():
    findings = analyze({FABOBS_PATH: FABOBS}, rules=["metric-orphan"])
    assert rule_ids(findings) == ["metric-orphan"] * 2
    assert all(f.path == FABOBS_PATH for f in findings)


def test_metric_orphan_negative_emitted_families_clean():
    findings = analyze(
        {FABOBS_PATH: FABOBS, "fabric_tpu/serve/m.py": EMITTERS},
        rules=["metric-orphan"],
    )
    assert findings == []


def test_metric_rules_ignore_code_outside_the_package():
    # tests deliberately emit unknown families (exercising the runtime
    # swallow); only fabric_tpu/ files are held to the table
    findings = analyze(
        {
            FABOBS_PATH: FABOBS,
            "fabric_tpu/serve/m.py": EMITTERS,
            "tests/test_x.py": """
                def probe():
                    obs_count("fabric_not_canonical_total")
            """,
        },
        rules=["metric-unknown"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# fault-site-drift
# ---------------------------------------------------------------------------

CHAOS_WITH_SITE = """
    PLAN = "x.seam=raise:0.5"
"""
CHAOS_PATH = "fabric_tpu/tools/fabchaos.py"

FAULT_MODULE = """
    def f():
        fault_point("x.seam")
"""


def test_fault_site_drift_fires_when_missing_from_readme():
    findings = analyze(
        {"fabric_tpu/m.py": FAULT_MODULE, CHAOS_PATH: CHAOS_WITH_SITE},
        rules=["fault-site-drift"],
        readme="no sites here",
    )
    assert rule_ids(findings) == ["fault-site-drift"]
    assert "README" in findings[0].message


def test_fault_site_drift_fires_when_no_scenario_exercises_it():
    findings = analyze(
        {"fabric_tpu/m.py": FAULT_MODULE, CHAOS_PATH: "PLAN = 'other'\n"},
        rules=["fault-site-drift"],
        readme="| `x.seam` |",
    )
    assert rule_ids(findings) == ["fault-site-drift"]
    assert "not exercised" in findings[0].message


def test_fault_site_drift_negative_documented_and_exercised():
    findings = analyze(
        {"fabric_tpu/m.py": FAULT_MODULE, CHAOS_PATH: CHAOS_WITH_SITE},
        rules=["fault-site-drift"],
        readme="| `x.seam` |",
    )
    assert findings == []


def test_fault_site_drift_without_readme_checks_scenarios_only():
    # no README text available -> only the fabchaos-coverage half runs
    findings = analyze(
        {"fabric_tpu/m.py": FAULT_MODULE, CHAOS_PATH: CHAOS_WITH_SITE},
        rules=["fault-site-drift"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# suppression-stale
# ---------------------------------------------------------------------------


def test_suppression_stale_fires_on_dead_fablint_comment():
    findings = analyze(
        {
            "fabric_tpu/m.py": (
                "X = 1  # fablint: disable=broad-except  # nothing here\n"
            )
        },
        rules=["suppression-stale"],
    )
    assert rule_ids(findings) == ["suppression-stale"]
    assert "disable=broad-except" in findings[0].message


def test_suppression_stale_negative_live_fablint_comment():
    findings = analyze(
        {
            "fabric_tpu/m.py": (
                "def f(x=[]):  # fablint: disable=mutable-default  # ok\n"
                "    return x\n"
            )
        },
        rules=["suppression-stale"],
    )
    assert findings == []


def test_suppression_stale_own_fabreg_comments():
    # dead: nothing to suppress on that line
    findings = analyze(
        {
            ENVREG_PATH: ENVREG,
            "fabric_tpu/m.py": (
                "X = 1  # fabreg: disable=env-undeclared  # nothing\n"
            ),
        },
        rules=["suppression-stale"],
    )
    assert rule_ids(findings) == ["suppression-stale"]
    # live: the comment really suppresses an env-undeclared finding
    live_sources = {
        ENVREG_PATH: ENVREG,
        "fabric_tpu/m.py": (
            "import os\n"
            'V = os.environ.get("FABRIC_TPU_GHOST", "")'
            "  # fabreg: disable=env-undeclared  # migration grace\n"
        ),
    }
    findings = analyze(
        live_sources, rules=["env-undeclared", "suppression-stale"]
    )
    assert findings == []
    # ...and staleness judges the FULL rule set even when the caller
    # runs suppression-stale alone: the live comment stays unreported
    findings = analyze(live_sources, rules=["suppression-stale"])
    assert findings == []


def test_suppression_stale_covers_fabreg_comments_outside_the_package():
    # sibling-tool comments outside fabric_tpu/ are inert (their gates
    # never look there) — but fabreg's own gate scans tests/, so its
    # comments are judged wherever they are honored
    findings = analyze(
        {
            "tests/test_x.py": (
                "X = 1  # fabreg: disable=env-undeclared  # nothing\n"
                "Y = 2  # fablint: disable=broad-except  # inert there\n"
            )
        },
        rules=["suppression-stale"],
    )
    assert rule_ids(findings) == ["suppression-stale"]
    assert "fabreg" in findings[0].message


def test_suppression_inside_a_string_is_data_not_a_comment():
    findings = analyze(
        {
            "fabric_tpu/m.py": (
                'S = "x = 1  # fablint: disable=broad-except"\n'
            )
        },
        rules=["suppression-stale"],
    )
    assert findings == []


def test_suppression_stale_fabdep_leg_runs_on_disk(tmp_path):
    pkg = tmp_path / "fabric_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    # dead comment: no shared write anywhere near it
    (pkg / "mod.py").write_text(
        "X = 1  # fabdep: disable=unguarded-shared-write  # nothing\n"
    )
    findings, _stats = fabreg.analyze_paths(
        [str(pkg)], rule_ids=["suppression-stale"]
    )
    assert rule_ids(findings) == ["suppression-stale"]
    # live comment: the write really races and the comment absorbs it
    (pkg / "mod.py").write_text(
        textwrap.dedent(
            """
            import threading

            class Worker:
                def __init__(self):
                    self.count = 0
                    self._t = threading.Thread(target=self._run, daemon=True)
                    self._t.start()

                def _run(self):
                    while True:
                        self.count += 1  # fabdep: disable=unguarded-shared-write  # fixture

                def poke(self):
                    self.count = 0  # fabdep: disable=unguarded-shared-write  # fixture
            """
        )
    )
    findings, _stats = fabreg.analyze_paths(
        [str(pkg)], rule_ids=["suppression-stale"]
    )
    assert findings == []


# ---------------------------------------------------------------------------
# suppression application + CLI
# ---------------------------------------------------------------------------


def test_findings_respect_fabreg_suppressions():
    findings, suppressed = fabreg.analyze_source(
        "import os\n"
        'V = os.environ.get("FABRIC_TPU_GHOST", "")'
        "  # fabreg: disable=env-undeclared  # grace\n",
        "fabric_tpu/m.py",
        rule_ids=["env-undeclared"],
    )
    assert findings == []
    assert suppressed == 1


def test_cli_list_rules_and_json(tmp_path, capsys):
    assert fabreg.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in fabreg.RULES:
        assert rid in out

    target = tmp_path / "m.py"
    target.write_text('import os\nV = os.environ.get("FABRIC_TPU_X", "")\n')
    rc = fabreg.main(["--json", "--rules", "env-undeclared", str(target)])
    assert rc == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["files"] == 1
    assert [f["rule"] for f in payload["findings"]] == ["env-undeclared"]


def test_cli_usage_errors(tmp_path):
    assert fabreg.main([]) == 2
    assert fabreg.main([str(tmp_path / "missing.py")]) == 2
    assert fabreg.main(["--rules", "no-such", str(tmp_path)]) == 2
    assert (
        fabreg.main(["--readme", str(tmp_path / "no.md"), str(tmp_path)])
        == 2
    )


def test_unknown_rule_id_raises_in_api():
    with pytest.raises(ValueError):
        fabreg.analyze_sources({"m.py": "X = 1\n"}, rule_ids=["bogus"])


# ---------------------------------------------------------------------------
# the toolkit chassis (the port contract: one Finding, one walker, one
# suppression grammar across all four analyzers)
# ---------------------------------------------------------------------------


def test_all_four_tools_share_the_toolkit_chassis():
    from fabric_tpu.tools import fabdep, fabflow, fablint

    for tool in (fablint, fabdep, fabflow, fabreg):
        assert tool.Finding is toolkit.Finding
        assert tool.DEFAULT_EXCLUDES == toolkit.DEFAULT_EXCLUDES
    assert fablint.iter_py_files is toolkit.iter_py_files
    assert fabflow.iter_py_files is toolkit.iter_py_files


def test_toolkit_suppression_grammar_reasons_and_all():
    sup = toolkit.parse_suppressions(
        "x = 1  # fabreg: disable=env-dead,metric-orphan  # the why\n",
        "fabreg",
    )
    assert sup == {1: ({"env-dead", "metric-orphan"}, "the why")}
    kept, suppressed = toolkit.apply_suppressions(
        [toolkit.Finding("anything", "m.py", 2, 0, "m")],
        {2: {"all"}},
    )
    assert kept == [] and len(suppressed) == 1


# ---------------------------------------------------------------------------
# the repo self-check: the gate invariant
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def repo_findings():
    return fabreg.analyze_paths(
        [
            str(REPO_ROOT / "fabric_tpu"),
            str(REPO_ROOT / "tests"),
        ],
        readme=str(REPO_ROOT / "README.md"),
    )


def test_repo_self_check_is_clean(repo_findings):
    findings, stats = repo_findings
    assert findings == [], "\n".join(
        f"{f.path}:{f.line}:{f.col}: {f.rule}: {f.message}" for f in findings
    )
    assert stats["files"] > 200  # the walk actually covered the tree


def test_repo_env_registry_matches_the_tree(repo_findings):
    # every var the registry declares is used, and (via the clean
    # self-check above) every read is declared — the two directions of
    # the env contract.  Spot-pin the PR motivator: the cache-debug
    # forensics knob conftest reads is declared.
    from fabric_tpu.common import envreg

    assert "FABRIC_TPU_CACHE_DEBUG" in envreg.ENV_BY_NAME
    assert len(envreg.ENV_VARS) >= 24
    assert len({v.name for v in envreg.ENV_VARS}) == len(envreg.ENV_VARS)
    for var in envreg.ENV_VARS:
        assert var.name.startswith("FABRIC_TPU_")
        assert var.type and var.default and var.consumer and var.doc
