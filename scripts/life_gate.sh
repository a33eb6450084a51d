#!/usr/bin/env bash
# fablife gate: resource-lifetime + wire-trust check — every started
# thread join-reachable from its owner's teardown, every
# socket/file/tempdir release guaranteed on exception edges, every bare
# lock acquire paired in a finally, every pairs.toml acquire
# (ClassLedger lanes, pool shards, CooldownGate verdicts, batcher
# admissions) discharged on every success path, no wire-decoded integer
# reaching a sleep/timeout/allocation unclamped, and no unbudgeted
# blocking call on the serve/router/batcher request paths.
#
# Dependency-free and import-free: fablife parses source with ast on
# the shared toolkit chassis — it never imports the analyzed modules,
# so this gate passes/fails identically in minimal environments (no
# cryptography, no jax, no numpy).  Scans tests/ too: a
# leaked tempdir in a test helper accumulates across CI runs exactly
# like one in the serving plane.  Runs in ~5s.
set -uo pipefail

cd "$(dirname "$0")/.."

timeout -k 5 60 python -m fabric_tpu.tools.fablife \
    fabric_tpu/ tests/
rc=$?

if [ "$rc" -ne 0 ]; then
    echo "life_gate: FAIL (fablife rc=$rc)" >&2
    exit 1
fi
echo "life_gate: OK"
