#!/usr/bin/env bash
# Tier-1 verify — static gate first, then the ROADMAP.md test command,
# verbatim, so humans and tooling run the exact same pytest gate. Prints
# DOTS_PASSED=<n> at the end and exits with pytest's status (lint
# failures exit immediately before pytest runs).
#
# Usage: scripts/tier1.sh   (from the repo root or anywhere inside it)
cd "$(dirname "$0")/.." || exit 1
scripts/lint.sh || exit 1
# Clock guard: report the 20 slowest tests every run (via PYTEST_ADDOPTS so
# the ROADMAP gate line below stays verbatim). PR 9 measured 560 s of the
# 870 s budget — when a suite creeps, this names the offender; anything new
# past ~10 s belongs behind the `slow` marker.
export PYTEST_ADDOPTS="--durations=20 ${PYTEST_ADDOPTS:-}"
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
