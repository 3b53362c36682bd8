#!/usr/bin/env bash
# Repo lint gate: d4pglint (repo-specific invariants, zero findings
# required — per-file AST checks, the whole-program pass [lock-order
# graph, protocol conformance, thread lifecycle, unused suppressions],
# the docs-catalog drift check, and the shape-aware partition-rule
# coverage gate in a subprocess) + the JSON schema check of the
# committed static-analysis and soak artifacts and of metrics logs (which
# also pins benchmarks/lock_order_graph.json acyclic and fresh). Wired into tier-1 both directly (scripts/tier1.sh runs this
# first) and as tests (tests/test_d4pglint.py::test_repo_lints_clean,
# tests/test_wholeprog.py), so the driver's verbatim ROADMAP pytest
# command enforces it too.
#
# The gate is also CLOCK-GUARDED (the tier-1 convention): the per-file
# pass fans out over a process pool (D4PGLINT_JOBS overrides the core
# count) and the whole run must finish inside LINT_BUDGET_S wall
# seconds — a lint gate nobody waits for is a lint gate nobody runs.
# Measured ~6s single-core; the default budget leaves slack for cold
# caches and loaded CI hosts.
#
# Usage: scripts/lint.sh            # lint the product-code manifest
#        scripts/lint.sh --show-suppressed   # audit the justifications
set -euo pipefail
cd "$(dirname "$0")/.."

LINT_BUDGET_S="${LINT_BUDGET_S:-120}"
SECONDS=0
python -m tools.d4pglint "$@"
python -m tools.d4pglint.schema_check
if (( SECONDS > LINT_BUDGET_S )); then
    echo "LINT_BUDGET_EXCEEDED: ${SECONDS}s > ${LINT_BUDGET_S}s — see the" \
         "[lint-timing] slowest-files line above" >&2
    exit 1
fi
echo "LINT_OK"
echo "LINT_WALL_S=${SECONDS} budget=${LINT_BUDGET_S}"
