#!/usr/bin/env bash
# fabreg gate: declarative-contract drift check — every FABRIC_TPU_*
# env read declared in common/envreg.py (and every row live), every
# fabobs emit site named + labeled per CANONICAL_METRICS (and every
# family emitted), every fault_point site in the README table and
# exercised by a fabchaos scenario, and every analyzer suppression
# still absorbing a finding.  (Det-surface taint, formerly the
# det-hazard rule here, is det_gate.sh / fabdet's whole-program job.)
#
# Dependency-free and import-free: fabreg parses source with
# ast/tokenize (re-running fablint/fabdep/fabflow rule subsets for the
# suppression-stale check), it never imports the analyzed modules, so
# this gate passes/fails identically in minimal environments (no
# cryptography, no jax, no numpy).  Runs in ~8s.
set -uo pipefail

cd "$(dirname "$0")/.."

timeout -k 5 60 python -m fabric_tpu.tools.fabreg \
    --readme README.md fabric_tpu/ tests/
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "reg_gate: FAIL (fabreg rc=$rc)" >&2
    exit 1
fi
echo "reg_gate: OK"
