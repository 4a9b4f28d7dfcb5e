#!/usr/bin/env bash
# The one-command static-analysis gate (ISSUE 1 tentpole + ISSUE 2 flow tier):
#   1. ruff       — generic Python hygiene (pyproject.toml config); skipped
#                   with a message when not installed (the container doesn't
#                   ship it; CI images may).
#   2. graftlint  — the pattern analyzers: taxonomy soundness, jit/trace
#                   hygiene, native lock discipline.
#   3. graftcheck — the CFG/dataflow tier (lint/flow/): kernel
#                   contracts, nemesis fault↔heal pairing, resource leaks
#                   across exception paths; gated on the checked-in
#                   baseline (lint/baseline.json) so only REGRESSIONS fail.
#   4. graftsync  — the concurrency + crash-consistency tier (ISSUE 16):
#                   guarded_by lock discipline, lock-order cycles against
#                   the documented hierarchy, WAL fsync/atomic-publish
#                   protocol, and the JGRAFT_* env-knob registry (emitted
#                   as build/knob_registry.json).
#   5. graftgate  — the verdict-integrity dataflow tier (ISSUE 17):
#                   fingerprint completeness, degraded-result quarantine,
#                   routing/verdict knob separation, tier-stamp totality,
#                   and the duplicated-certifier lock-step tripwire.
#   6. make tidy  — curated clang-tidy over native/src (self-skipping when
#                   clang-tidy is absent, same pattern as SKIP_TSAN=1).
# Stages 2-5 are pure stdlib (no jax import) so they never need skipping.
# Exit nonzero on any finding. tests/test_lint.py + tests/test_lint_flow.py
# keep stages 2-3 green by construction (self-hosting: the suite lints the
# repo that contains it).
set -euo pipefail
cd "$(dirname "$0")/.."

if command -v ruff >/dev/null 2>&1; then
    echo "== ruff =="
    ruff check .
else
    echo "== ruff: not installed — skipping (graftlint still runs) =="
fi

echo "== graftlint (pattern tier) =="
python -m jepsen_jgroups_raft_tpu.lint --rules taxonomy,jit,lock

echo "== graftcheck (CFG/dataflow tier) =="
python -m jepsen_jgroups_raft_tpu.lint --rules kernel,heal,resource \
    --baseline jepsen_jgroups_raft_tpu/lint/baseline.json

echo "== graftsync (concurrency + crash-consistency tier) =="
mkdir -p build
python -m jepsen_jgroups_raft_tpu.lint \
    --rules guarded,lockorder,crashproto,envknobs \
    --baseline jepsen_jgroups_raft_tpu/lint/baseline.json \
    --knob-registry build/knob_registry.json
test -s build/knob_registry.json  # the registry artifact must exist

echo "== graftgate (verdict-integrity tier) =="
python -m jepsen_jgroups_raft_tpu.lint \
    --rules fingerprint,degraded,knobclass,tierstamp,lockstep \
    --baseline jepsen_jgroups_raft_tpu/lint/baseline.json --timing

echo "== clang-tidy =="
make -C native tidy
