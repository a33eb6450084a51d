"""fabflow: interval-domain unit tests, one firing fixture + negative
control per rule, suppression semantics, CLI plumbing, and the repo
self-check (the CI gate invariant: ``fabflow fabric_tpu/`` reports 0
unsuppressed findings and every suppression reason states a computed
bound)."""

import json
import re
import textwrap
from pathlib import Path

import pytest

from fabric_tpu.tools import fabflow
from fabric_tpu.tools.fabflow import Interval

REPO_ROOT = Path(__file__).resolve().parent.parent


def flow(src: str, path: str = "fabric_tpu/ops/fixture.py", rules=None):
    findings, _ = fabflow.analyze_source(textwrap.dedent(src), path, rules)
    return findings


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# interval domain
# ---------------------------------------------------------------------------


def test_interval_add_mul_widen_exactly():
    a = Interval(0, fabflow.LIMB_MASK)
    assert a.add(a) == Interval(0, 2 * fabflow.LIMB_MASK)
    # products of canonical limbs stay under 2^26: the CIOS premise
    assert a.mul(a) == Interval(0, fabflow.LIMB_MASK ** 2)
    assert a.mul(a).hi < 1 << 26


def test_interval_lshift_widens_and_mask_clamps():
    a = Interval(0, fabflow.LIMB_MASK)
    assert a.lshift(Interval(13, 13)) == Interval(0, fabflow.LIMB_MASK << 13)
    # & LIMB_MASK clamps anything — including negative int32 borrows
    wide = Interval(-(1 << 31), (1 << 31) - 1)
    assert wide.and_(Interval(fabflow.LIMB_MASK, fabflow.LIMB_MASK)) == (
        Interval(0, fabflow.LIMB_MASK)
    )


def test_interval_rshift_carry_bound():
    acc = Interval(0, 20 << 27)
    assert acc.rshift(Interval(13, 13)).hi == (20 << 27) >> 13


def test_interval_widen_terminates_on_thresholds():
    cur = Interval(0, 1)
    for _ in range(64):
        nxt = cur.widen(cur.add(Interval(1, 1)))
        if nxt == cur:
            break
        cur = nxt
    else:
        pytest.fail("widening did not reach a fixpoint")
    assert cur.hi is None  # topped out, not oscillating


def test_widening_loop_terminates_in_analysis():
    # unknown-trip loop accumulating into a uint32 lane: the fixpoint
    # must terminate (widening) AND report the overflow it widens into
    findings = flow(
        """
        import numpy as np
        def count(a, flags):
            t = a
            while flags.any():
                t = t + np.uint32(1)
            return t
        """
    )
    assert "limb-overflow" in rule_ids(findings)


# ---------------------------------------------------------------------------
# limb-overflow
# ---------------------------------------------------------------------------


def test_limb_overflow_fires_on_deep_accumulation():
    # 71 products of canonical limbs: 71 * 8191^2 > 2^32
    findings = flow(
        """
        def acc(a, b):
            t = a * b
            for _ in range(70):
                t = t + a * b
            return t
        """,
        rules=["limb-overflow"],
    )
    assert rule_ids(findings) == ["limb-overflow"]
    assert "exceeds uint32" in findings[0].message


def test_limb_overflow_negative_control_headroom_holds():
    # 31 products stay far below 2^32 — the lazy-carry discipline
    findings = flow(
        """
        def acc(a, b):
            t = a * b
            for _ in range(30):
                t = t + a * b
            return t
        """,
        rules=["limb-overflow"],
    )
    assert findings == []


def test_limb_overflow_cios_proof_sensitivity():
    # the real recurrence at radix 2^13 passes (see the repo self-check);
    # widening the per-iteration term past the headroom must fire
    # per-iteration terms: a*b <= 8191^2 ~ 2^26, a*2^14 ~ 2^27; three of
    # them over 20 iterations is ~6.7e9 > 2^32 — one fewer is ~4.03e9,
    # inside the container (the same margin the real CIOS loop lives on)
    src = """
        import jax.numpy as jnp

        def cios_like(a, b):
            t = jnp.zeros_like(a)
            for i in range(20):
                t = t + a * b + a * jnp.uint32(1 << 14) + a * jnp.uint32(1 << 14)
            return t
        """
    assert rule_ids(flow(src, rules=["limb-overflow"])) == ["limb-overflow"]
    ok = """
        import jax.numpy as jnp

        def cios_like(a, b):
            t = jnp.zeros_like(a)
            for i in range(20):
                t = t + a * b + a * jnp.uint32(1 << 14)
            return t
        """
    assert flow(ok, rules=["limb-overflow"]) == []


def test_limb_overflow_int32_borrow_is_clean():
    # the cond_sub idiom: int32 reinterpretation + borrow stays in range
    findings = flow(
        """
        import jax.numpy as jnp

        def cond_sub(x, m):
            d = x.astype(jnp.int32) - m.astype(jnp.int32)
            return d >> 13
        """,
        rules=["limb-overflow", "dtype-narrowing"],
    )
    assert findings == []


def test_host_python_ints_never_flagged():
    # host big-int files work in Python ints: no container, no overflow
    findings = flow(
        """
        P = 2**256 - 189

        def mul(a: int, b: int) -> int:
            return (a * b * a * b) % P
        """,
        path="fabric_tpu/common/p256.py",
        rules=["limb-overflow"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# dtype-narrowing
# ---------------------------------------------------------------------------


def test_dtype_narrowing_fires_on_truncating_astype():
    findings = flow(
        """
        import jax.numpy as jnp

        def f(a, b):
            return (a + b).astype(jnp.uint8)
        """,
        rules=["dtype-narrowing"],
    )
    assert rule_ids(findings) == ["dtype-narrowing"]


def test_dtype_narrowing_negative_control_masked_first():
    findings = flow(
        """
        import jax.numpy as jnp
        import numpy as np

        def f(a):
            return (a & np.uint32(255)).astype(jnp.uint8)
        """,
        rules=["dtype-narrowing"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# float-contamination
# ---------------------------------------------------------------------------


def test_float_contamination_fires_on_float_operand_and_div():
    assert rule_ids(
        flow("def f(a):\n    return a * 1.5\n",
             rules=["float-contamination"])
    ) == ["float-contamination"]
    assert rule_ids(
        flow("def f(a, b):\n    return a / b\n",
             rules=["float-contamination"])
    ) == ["float-contamination"]


def test_float_contamination_negative_control():
    findings = flow(
        "def f(a, b):\n    return (a * 2) >> 1\n",
        rules=["float-contamination"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# const-drift
# ---------------------------------------------------------------------------


def test_const_drift_fires_on_hardcoded_limb_constants():
    findings = flow(
        """
        def f(x):
            return (x >> 13) & 8191
        """,
        rules=["const-drift"],
    )
    assert set(rule_ids(findings)) == {"const-drift"}
    assert any("LIMB_BITS" in f.message for f in findings)
    assert any("LIMB_MASK" in f.message for f in findings)


def test_const_drift_range_and_pow_forms():
    findings = flow(
        """
        def g(xs):
            out = 0
            for i in range(20):
                out += xs[i] % (2 ** 13)
            return out
        """,
        rules=["const-drift"],
    )
    assert "const-drift" in rule_ids(findings)


def test_const_drift_negative_control_imported_names():
    findings = flow(
        """
        from fabric_tpu.ops.bignum import LIMB_BITS, LIMB_MASK, NLIMBS

        def f(x):
            return (x >> LIMB_BITS) & LIMB_MASK

        def g(table):
            return table[13] + table[20]  # data indices, not limb math
        """,
        rules=["const-drift"],
    )
    assert findings == []


def test_const_drift_only_in_limb_tier():
    findings = flow(
        "def f(x):\n    return x >> 13\n",
        path="fabric_tpu/gossip/fixture.py",
        rules=["const-drift"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# mask-fail-open
# ---------------------------------------------------------------------------

MASK_PATH = "fabric_tpu/validation/fixture.py"


def test_mask_fail_open_fires_on_swallowing_handler():
    findings = flow(
        """
        from fabric_tpu.common.txflags import TxValidationCode

        def parse(tx, data):
            try:
                tx.code = decode(data)
            except ValueError:
                pass
        """,
        path=MASK_PATH,
        rules=["mask-fail-open"],
    )
    assert rule_ids(findings) == ["mask-fail-open"]


def test_mask_fail_open_fires_on_valid_in_handler():
    findings = flow(
        """
        from fabric_tpu.common.txflags import TxValidationCode

        def assemble(flags, i, data):
            try:
                check(data)
            except ValueError:
                flags.set_flag(i, TxValidationCode.VALID)
        """,
        path=MASK_PATH,
        rules=["mask-fail-open"],
    )
    assert rule_ids(findings) == ["mask-fail-open"]
    assert "VALID" in findings[0].message


def test_mask_fail_open_fires_on_early_valid_return():
    findings = flow(
        """
        from fabric_tpu.common.txflags import TxValidationCode

        def classify(tx):
            if tx.fast_path:
                return TxValidationCode.VALID
            return compute_code(tx)
        """,
        path=MASK_PATH,
        rules=["mask-fail-open"],
    )
    assert rule_ids(findings) == ["mask-fail-open"]


def test_mask_fail_open_negative_controls():
    # INVALID-family assignment, raise, delegation, exception handoff,
    # and the narrow-typed retry idiom are all fail-closed
    src = """
        import queue
        from fabric_tpu.common.txflags import TxValidationCode

        def parse(tx, data):
            try:
                tx.code = decode(data)
            except ValueError:
                tx.code = TxValidationCode.BAD_PAYLOAD

        def assemble(flags, i, data):
            try:
                check(data)
            except ValueError as e:
                raise RuntimeError("abort block") from e

        def resolve(flags, q, on_error, block, exc=None):
            while True:
                try:
                    item = q.get(timeout=0.2)
                except queue.Empty:
                    continue
                try:
                    flags = commit(item)
                except Exception as exc:
                    on_error(block, exc)

        def fallback(tx, data):
            try:
                tx.code = decode(data)
            except ValueError:
                out = host_decode(data)
                return out
        """
    assert flow(src, path=MASK_PATH, rules=["mask-fail-open"]) == []


def test_mask_fail_open_is_path_sensitive_on_guarded_delegation():
    # the pipeline's pre-fix silent-drop shape: the error callback only
    # runs under `if cb is not None:` with no else — the empty branch
    # swallows the exception, so the handler must FIRE
    guarded = """
        def _commit_loop(self):
            while True:
                block, prepared = self._prepared.get()
                try:
                    flags = self.channel.store_block(block, prepared=prepared)
                except Exception as exc:
                    if self.on_error is not None:
                        self.on_error(block, exc)
        """
    findings = flow(
        guarded, path="fabric_tpu/parallel/fixture.py",
        rules=["mask-fail-open"],
    )
    assert rule_ids(findings) == ["mask-fail-open"]
    # the post-fix shape — BOTH branches hand the exception onward —
    # is fail-closed
    closed = guarded.rstrip() + (
        "\n                    else:"
        "\n                        log.error('commit failed: %s', exc)\n"
    )
    assert flow(
        closed, path="fabric_tpu/parallel/fixture.py",
        rules=["mask-fail-open"],
    ) == []


def test_tool_constants_match_canonical_limbparams():
    # fabflow never imports analyzed code at gate time, so it carries
    # its own copies of the limb constants; this pins them to the
    # canonical source so the proof can never silently describe a
    # different radix than the kernels run
    from fabric_tpu.common import limbparams

    assert fabflow.LIMB_BITS == limbparams.LIMB_BITS
    assert fabflow.NLIMBS == limbparams.NLIMBS
    assert fabflow.LIMB_MASK == limbparams.LIMB_MASK
    assert fabflow.RADIX_BITS == limbparams.RADIX_BITS


def test_mask_fail_open_ignores_non_flag_functions():
    findings = flow(
        """
        def probe(registry, name):
            try:
                return registry.get(name)
            except KeyError:
                pass
        """,
        path=MASK_PATH,
        rules=["mask-fail-open"],
    )
    assert findings == []


def test_mask_fail_open_only_in_mask_tier():
    findings = flow(
        """
        from fabric_tpu.common.txflags import TxValidationCode

        def parse(tx, data):
            try:
                tx.code = decode(data)
            except ValueError:
                pass
        """,
        path="fabric_tpu/gossip/fixture.py",
        rules=["mask-fail-open"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------


def test_suppression_silences_named_rule_and_counts():
    src = (
        "def f(x):\n"
        "    return x >> 13  # fabflow: disable=const-drift  "
        "# shift is the wire format's 13, bound [0, 8191]\n"
    )
    findings, suppressed = fabflow.analyze_source(
        src, "fabric_tpu/ops/fixture.py", ["const-drift"]
    )
    assert findings == []
    assert suppressed == 1


def test_suppression_other_rule_does_not_silence():
    src = (
        "def f(x):\n"
        "    return x >> 13  # fabflow: disable=limb-overflow  # wrong id\n"
    )
    findings, suppressed = fabflow.analyze_source(
        src, "fabric_tpu/ops/fixture.py", ["const-drift"]
    )
    assert rule_ids(findings) == ["const-drift"]
    assert suppressed == 0


def test_suppression_reason_is_parsed():
    sup = fabflow.parse_suppressions(
        "x = 1  # fabflow: disable=limb-overflow  # bound [0, 2**27]\n"
    )
    assert sup[1][0] == {"limb-overflow"}
    assert "2**27" in sup[1][1]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_json_output(tmp_path, capsys):
    f = tmp_path / "fabric_tpu" / "ops" / "fixture.py"
    f.parent.mkdir(parents=True)
    f.write_text("def f(x):\n    return x >> 13\n")
    rc = fabflow.main(["--json", str(f)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["files"] == 1
    assert [x["rule"] for x in out["findings"]] == ["const-drift"]


def test_cli_list_rules_and_bad_rule(capsys):
    assert fabflow.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in fabflow.RULES:
        assert rid in out
    assert fabflow.main(["--rules", "bogus", "x.py"]) == 2


def test_cli_missing_path(capsys):
    assert fabflow.main(["/nonexistent/nope.py"]) == 2


# ---------------------------------------------------------------------------
# the repo self-check: the gate invariant
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def repo_findings():
    return fabflow.analyze_paths([str(REPO_ROOT / "fabric_tpu")])


def test_repo_is_clean(repo_findings):
    findings, stats = repo_findings
    assert findings == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in findings
    )


def test_toolkit_port_changed_nothing(repo_findings):
    """The PR 11 toolkit extraction is behavior-pinned: same chassis
    objects, same rule ids, and no suppression anywhere in the repo."""
    from fabric_tpu.tools import toolkit

    assert fabflow.Finding is toolkit.Finding
    assert fabflow.DEFAULT_EXCLUDES == toolkit.DEFAULT_EXCLUDES
    assert sorted(fabflow.RULES) == [
        "const-drift", "dtype-narrowing", "float-contamination",
        "limb-overflow", "mask-fail-open",
    ]
    _findings, stats = repo_findings
    assert stats["suppressed"] == 0
    collected = []
    fabflow.analyze_sources(
        {
            "fabric_tpu/ops/fixture.py": (
                "import numpy as np\n"
                "def f(x):\n"
                "    acc = np.uint64(2**63) + np.uint64(2**63)"
                "  # fabflow: disable=limb-overflow  # fixture: 2**64\n"
                "    return acc\n"
            )
        },
        ["limb-overflow"],
        collected,
    )
    assert [f.rule for f in collected] == ["limb-overflow"]


def test_repo_suppressions_state_computed_bounds(repo_findings):
    _, stats = repo_findings
    reasons = fabflow.suppression_reasons([str(REPO_ROOT / "fabric_tpu")])
    for path, line, rules, reason in reasons:
        assert reason, f"{path}:{line}: suppression without a reason"
        assert re.search(r"\d", reason), (
            f"{path}:{line}: suppression reason must state the computed "
            f"worst-case bound: {reason!r}"
        )


def test_limb_overflow_bn_pair_kernel_sensitivity():
    """PR 7 (hostbn) sensitivity fixture: the pair-radix Montgomery MAC
    + generic-REDC recurrence at the hostbn tier under the PairMat
    contracts is clean (11 rows of L32·L4 products + q·m rows < 2^62.5,
    the hostec_np proof with the BN modulus' m0inv multiply), and ONE
    extra 4x-widened per-iteration product term pushes the accumulator
    past uint64 and must fire."""
    src_ok = """
        import numpy as np

        NPAIRS = 11
        PAIR_BITS = 2 * 13
        PAIR_MASK = (1 << PAIR_BITS) - 1

        def bn_kernel(a: "PairMatL32", b: "PairMatL4", m_col: "PairMat", m0inv: int):
            lanes = 4
            t = np.zeros((2 * NPAIRS, lanes), dtype=np.uint64)
            for i in range(NPAIRS):
                t[i : i + NPAIRS] += a[i] * b
            for i in range(NPAIRS):
                q = ((t[i] & PAIR_MASK) * m0inv) & PAIR_MASK
                t[i : i + NPAIRS - 1] += q * m_col[0 : NPAIRS - 1]
                t[i + 1] += t[i] >> PAIR_BITS
            return t
        """
    assert flow(
        src_ok, path="fabric_tpu/crypto/hostbn.py", rules=["limb-overflow"]
    ) == []
    src_bad = src_ok.replace(
        "t[i : i + NPAIRS] += a[i] * b",
        "t[i : i + NPAIRS] += a[i] * b + (a[i] << np.uint64(2)) * b",
    )
    findings = flow(
        src_bad, path="fabric_tpu/crypto/hostbn.py", rules=["limb-overflow"]
    )
    assert "limb-overflow" in rule_ids(findings)
    assert any("exceeds uint64" in f.message for f in findings)


def test_hostbn_is_in_the_limb_tier():
    """crypto/hostbn.py carries the pair-limb contracts (the PR 7
    tier-extension satellite): the tier glob must match it."""
    ctx = fabflow.FileContext("fabric_tpu/crypto/hostbn.py")
    assert ctx.matches(fabflow.LIMB_TIER)


def test_bignum_cios_proof_holds_standalone():
    """The headline proof: bignum.py alone, under the canonical-limb
    contract, has no unsuppressed overflow — the 20-iteration CIOS
    accumulator stays below 2^32."""
    findings, stats = fabflow.analyze_paths(
        [str(REPO_ROOT / "fabric_tpu" / "ops" / "bignum.py")],
        rule_ids=["limb-overflow", "dtype-narrowing", "float-contamination"],
    )
    assert findings == []
    assert stats["suppressed"] == 0


_CIOS_STEP = "nt = t[1:] + ai * b_s[1:] + q * m_s[1:]\n"


@pytest.mark.parametrize(
    "extra_products,fires", [(0, False), (1, False), (2, True)]
)
def test_bignum_cios_proof_reaches_the_loop_body(extra_products, fires):
    """The proof above is about the loop, not about nothing: the body of
    `mont_mul_l`'s `lax.fori_loop` reads its operands through a closure,
    and fabflow must follow them there.  The real source with one more
    product a step still fits (~0.94 * 2^32); with two it must fire, on
    the lines of the loop body."""
    path = REPO_ROOT / "fabric_tpu" / "ops" / "bignum.py"
    src = path.read_text(encoding="utf-8")
    assert src.count(_CIOS_STEP) == 1
    widened = _CIOS_STEP.rstrip("\n") + " + ai * b_s[1:]" * extra_products
    src = src.replace(_CIOS_STEP, widened + "\n")
    findings, _ = fabflow.analyze_source(
        src, "fabric_tpu/ops/bignum.py", ["limb-overflow"]
    )
    if not fires:
        assert findings == []
        return
    assert rule_ids(findings) and set(rule_ids(findings)) == {"limb-overflow"}
    lines = src.splitlines()
    start = next(i for i, l in enumerate(lines, 1) if "def body(i, t):" in l)
    end = next(i for i, l in enumerate(lines, 1) if "lax.fori_loop(" in l and i > start)
    assert all(start < f.line < end for f in findings), [f.line for f in findings]


# ---------------------------------------------------------------------------
# fabchaos interplay: fault-injection wrappers must not be able to hide
# a fail-open handler from the analyzer (pinned firing fixture, PR 6)
# ---------------------------------------------------------------------------


def test_mask_fail_open_fires_on_fail_open_injection_wrapper():
    """A *genuinely fail-open* chaos wrapper — swallowing InjectedFault
    around a flag write and moving on — must still fire: fault_point
    sites in the mask tier may only appear inside handlers that settle
    an INVALID-family code, raise, or hand the exception onward (the
    shapes the real batcher/pipeline seams use)."""
    findings = flow(
        """
        from fabric_tpu.common.faults import InjectedFault, fault_point
        from fabric_tpu.common.txflags import TxValidationCode

        def settle(flags, i, data):
            try:
                fault_point("pipeline.commit", key=i)
                flags.set_flag(i, compute_code(data))
            except InjectedFault:
                pass  # swallowed: the lane's flag is left unset
        """,
        path=MASK_PATH,
        rules=["mask-fail-open"],
    )
    assert rule_ids(findings) == ["mask-fail-open"]


def test_mask_fail_open_accepts_fail_closed_injection_wrapper():
    """The real seam shape: an injected fault settles the lane with an
    INVALID-family code (fail-closed) — no finding."""
    findings = flow(
        """
        from fabric_tpu.common.faults import InjectedFault, fault_point
        from fabric_tpu.common.txflags import TxValidationCode

        def settle(flags, i, data):
            try:
                fault_point("pipeline.commit", key=i)
                flags.set_flag(i, compute_code(data))
            except InjectedFault:
                flags.set_flag(i, TxValidationCode.INVALID_OTHER_REASON)
        """,
        path=MASK_PATH,
        rules=["mask-fail-open"],
    )
    assert findings == []
