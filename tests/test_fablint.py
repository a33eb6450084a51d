"""fablint: one firing fixture per rule, negative controls, suppression,
generated-file exclusion, CLI plumbing, and the repo self-check (the CI
gate invariant: ``fablint fabric_tpu/`` reports 0 violations)."""

import json
import textwrap
from pathlib import Path

import pytest

from fabric_tpu.tools import fablint

REPO_ROOT = Path(__file__).resolve().parent.parent


def lint(src: str, path: str = "fabric_tpu/common/fixture.py", rules=None):
    findings, _ = fablint.lint_source(textwrap.dedent(src), path, rules)
    return findings


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# rule fixtures: each rule fires on its minimal counterexample
# ---------------------------------------------------------------------------


def test_module_import_fires_on_unguarded_heavy_import():
    findings = lint("import jax\n", path="fabric_tpu/msp/fixture.py",
                    rules=["module-import"])
    assert rule_ids(findings) == ["module-import"]
    assert findings[0].line == 1


def test_module_import_from_form_and_submodule():
    findings = lint(
        "from cryptography.hazmat.primitives import hashes\n",
        path="fabric_tpu/msp/fixture.py", rules=["module-import"],
    )
    assert rule_ids(findings) == ["module-import"]


def test_module_import_guarded_or_lazy_is_clean():
    src = """
    try:
        import grpc
    except ImportError:
        grpc = None

    def lazy():
        import jax
        return jax
    """
    assert lint(src, path="fabric_tpu/msp/fixture.py",
                rules=["module-import"]) == []


def test_module_import_allowlist():
    # the kernel layer imports jax at module scope by design
    assert lint("import jax\n", path="fabric_tpu/ops/fixture.py",
                rules=["module-import"]) == []


def test_broad_except_bare_fires_anywhere():
    src = """
    try:
        x = 1
    except:
        pass
    """
    findings = lint(src, path="fabric_tpu/gossip/fixture.py",
                    rules=["broad-except"])
    assert rule_ids(findings) == ["broad-except"]


def test_broad_except_swallow_fires_in_mask_critical_path():
    src = """
    try:
        verify()
    except Exception:
        pass
    """
    findings = lint(src, path="fabric_tpu/crypto/fixture.py",
                    rules=["broad-except"])
    assert rule_ids(findings) == ["broad-except"]


def test_broad_except_that_logs_or_reraises_is_clean():
    src = """
    try:
        verify()
    except Exception as exc:
        logger.warning("verify failed: %s", exc)
    try:
        verify()
    except Exception:
        raise
    """
    assert lint(src, path="fabric_tpu/validation/fixture.py",
                rules=["broad-except"]) == []


def test_broad_except_unrelated_log_leaf_still_fires():
    # math.log()/obj.error() must not be mistaken for logging
    src = """
    try:
        verify()
    except Exception:
        y = math.log(2)
    try:
        verify()
    except Exception:
        obj.error()
    """
    findings = lint(src, path="fabric_tpu/crypto/fixture.py",
                    rules=["broad-except"])
    assert rule_ids(findings) == ["broad-except", "broad-except"]


def test_broad_except_logger_factory_chain_counts_as_logging():
    src = """
    try:
        verify()
    except Exception as exc:
        flogging.must_get_logger("validation").warning("no: %s", exc)
    try:
        verify()
    except Exception as exc:
        self._log.debug("no: %s", exc)
    """
    assert lint(src, path="fabric_tpu/validation/fixture.py",
                rules=["broad-except"]) == []


def test_broad_except_outside_mask_critical_path_is_clean():
    src = """
    try:
        tick()
    except Exception:
        pass
    """
    assert lint(src, path="fabric_tpu/gossip/fixture.py",
                rules=["broad-except"]) == []


def test_mutable_default_fires():
    findings = lint("def f(x=[], *, y={}):\n    return x, y\n",
                    rules=["mutable-default"])
    assert rule_ids(findings) == ["mutable-default", "mutable-default"]


def test_mutable_default_none_sentinel_is_clean():
    assert lint("def f(x=None, y=()):\n    return x\n",
                rules=["mutable-default"]) == []


# The jit-impure firing fixtures moved to tests/test_fabtrace.py in
# PR 18 (behavior-pinned) when the rule migrated to fabtrace's
# traced-body dataflow.


def test_jit_impure_is_retired_from_fablint():
    assert "jit-impure" not in fablint.RULES
    assert lint(
        "@jax.jit\ndef kernel(x):\n    print(x)\n    return x\n",
        path="fabric_tpu/ops/fixture.py",
    ) == []


def test_limb_dtype_fires_without_dtype():
    findings = lint("x = jnp.array([0xFFFFFFFF00000001])\n",
                    rules=["limb-dtype"])
    assert rule_ids(findings) == ["limb-dtype"]


def test_limb_dtype_explicit_dtype_or_small_literal_is_clean():
    src = """
    x = jnp.array([0xFFFFFFFF00000001], dtype=jnp.uint64)
    y = np.array([0xFFFF])
    z = np.array([0xFFFFFFFF00000001], np.uint64)  # positional dtype
    w = np.array([0xFFFFFFFF00000001], object)
    """
    assert lint(src, rules=["limb-dtype"]) == []


def test_assert_security_fires_in_crypto():
    findings = lint("assert sig_ok\n", path="fabric_tpu/crypto/fixture.py",
                    rules=["assert-security"])
    assert rule_ids(findings) == ["assert-security"]


def test_assert_security_outside_scope_is_clean():
    assert lint("assert cache_ok\n", path="fabric_tpu/gossip/fixture.py",
                rules=["assert-security"]) == []


def test_digest_compare_fires():
    findings = lint("ok = computed_digest == expected\n",
                    rules=["digest-compare"])
    assert rule_ids(findings) == ["digest-compare"]


def test_digest_compare_none_check_and_plain_names_are_clean():
    src = """
    a = digest == None
    b = count == other_count
    """
    assert lint(src, rules=["digest-compare"]) == []


def test_shell_injection_fires():
    src = """
    subprocess.run("ls /", shell=True)
    os.system("ls /")
    """
    findings = lint(src, rules=["shell-injection"])
    assert rule_ids(findings) == ["shell-injection", "shell-injection"]


def test_shell_injection_argv_list_is_clean():
    assert lint('subprocess.run(["ls", "/"], check=True)\n',
                rules=["shell-injection"]) == []


def test_fork_start_fires():
    src = """
    ctx = multiprocessing.get_context("fork")
    multiprocessing.set_start_method("fork")
    """
    findings = lint(src, rules=["fork-start"])
    assert rule_ids(findings) == ["fork-start", "fork-start"]


def test_fork_start_forkserver_is_clean():
    assert lint('ctx = multiprocessing.get_context("forkserver")\n',
                rules=["fork-start"]) == []


def test_all_drift_fires_on_phantom_export():
    src = """
    from fabric_tpu.crypto import der

    A = 1

    __all__ = ["A", "der", "Missing"]
    """
    findings = lint(src, path="fabric_tpu/crypto/__init__.py",
                    rules=["all-drift"])
    assert rule_ids(findings) == ["all-drift"]
    assert "Missing" in findings[0].message


def test_all_drift_guarded_import_and_non_init_are_clean():
    src = """
    try:
        from fabric_tpu.crypto import fastec
    except ImportError:
        fastec = None

    __all__ = ["fastec"]
    """
    assert lint(src, path="fabric_tpu/crypto/__init__.py",
                rules=["all-drift"]) == []
    # the rule only applies to package __init__ files
    assert lint('__all__ = ["Missing"]\n',
                path="fabric_tpu/crypto/other.py", rules=["all-drift"]) == []


def test_syntax_error_is_reported_not_raised():
    findings = lint("def broken(:\n")
    assert rule_ids(findings) == ["syntax-error"]


# ---------------------------------------------------------------------------
# suppression + exclusion
# ---------------------------------------------------------------------------


def test_per_line_suppression():
    src = (
        "try:\n"
        "    verify()\n"
        "except Exception:  # fablint: disable=broad-except  # reason\n"
        "    pass\n"
    )
    findings, suppressed = fablint.lint_source(
        src, "fabric_tpu/crypto/fixture.py", ["broad-except"]
    )
    assert findings == []
    assert suppressed == 1


def test_suppression_is_rule_specific_and_all_works():
    src = "def f(x=[]):  # fablint: disable=broad-except\n    return x\n"
    findings, suppressed = fablint.lint_source(
        src, "fabric_tpu/crypto/fixture.py", ["mutable-default"]
    )
    assert rule_ids(findings) == ["mutable-default"]  # wrong id: still fires
    src = "def f(x=[]):  # fablint: disable=all\n    return x\n"
    findings, suppressed = fablint.lint_source(
        src, "fabric_tpu/crypto/fixture.py", ["mutable-default"]
    )
    assert findings == [] and suppressed == 1


def test_generated_and_artifact_files_are_excluded(tmp_path):
    pkg = tmp_path / "fabric_tpu"
    (pkg / "protos").mkdir(parents=True)
    (pkg / "__pycache__").mkdir()
    (pkg / "native").mkdir()
    bad = "def f(x=[]):\n    return x\n"
    (pkg / "protos" / "thing_pb2.py").write_text(bad)
    (pkg / "__pycache__" / "stale.py").write_text(bad)
    (pkg / "native" / "gen.py").write_text(bad)
    (pkg / "real.py").write_text(bad)
    findings, stats = fablint.lint_paths([str(tmp_path)])
    assert stats["files"] == 1  # only real.py survives the exclusions
    assert rule_ids(findings) == ["mutable-default"]


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------


def test_cli_json_output_and_exit_codes(tmp_path, capsys):
    f = tmp_path / "bad.py"
    f.write_text("def f(x=[]):\n    return x\n")
    rc = fablint.main(["--json", str(f)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["files"] == 1
    assert [x["rule"] for x in out["findings"]] == ["mutable-default"]
    f.write_text("def f(x=None):\n    return x\n")
    assert fablint.main([str(f)]) == 0


def test_cli_list_rules_and_bad_rule(capsys):
    assert fablint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in fablint.RULES:
        assert rid in out
    assert len(fablint.RULES) >= 9
    assert fablint.main(["--rules", "no-such-rule", "x.py"]) == 2
    assert fablint.main([]) == 2
    assert fablint.main(["no/such/dir"]) == 2  # usage error, not a finding


# ---------------------------------------------------------------------------
# the gate invariant
# ---------------------------------------------------------------------------


def test_repo_self_check_is_clean():
    findings, stats = fablint.lint_paths([str(REPO_ROOT / "fabric_tpu")])
    assert findings == [], "\n".join(
        f"{f.path}:{f.line}:{f.col}: {f.rule}: {f.message}" for f in findings
    )
    assert stats["files"] > 100  # the walk actually covered the tree


def test_toolkit_port_changed_nothing():
    """The PR 11 toolkit extraction is behavior-pinned: same chassis
    objects, same rule ids, and the repo's suppressed count exactly as
    before the port (every comment still absorbing the same finding —
    fabreg's suppression-stale rule keeps this number honest)."""
    from fabric_tpu.tools import toolkit

    assert fablint.Finding is toolkit.Finding
    assert fablint.DEFAULT_EXCLUDES == toolkit.DEFAULT_EXCLUDES
    # jit-impure left for fabtrace in PR 18 (behavior-pinned there)
    assert sorted(fablint.RULES) == [
        "all-drift", "assert-security", "broad-except", "digest-compare",
        "fork-start", "limb-dtype", "module-import",
        "mutable-default", "shell-injection",
    ]
    _findings, stats = fablint.lint_paths([str(REPO_ROOT / "fabric_tpu")])
    # 19 from the PR 11 port + the PR 13 fabcrash digest-compare
    # suppression (JSON scorecard equality, not a MAC) - the PR 22
    # removal of p256_kernel._kernel_variant's swallowed backend-init
    # error (deleted with its code: on the chip path it now raises)
    assert stats["suppressed"] == 19
