#!/usr/bin/env bash
# Tier-1 CI gate, what every push runs.
#
#   ./ci.sh [extra pytest args]
#       static analysis (repro.analysis, then ruff when installed), the
#       whole pytest suite, fail-fast, suite-wide per-test timeout, then
#       tests/test_sharding.py again in its own process under an
#       8-fake-device CPU backend (the XLA flag must not leak into the
#       main suite's numerics, see tests/test_sharding.py).
#
# Speed is measured on the chip only: BENCHMARK.json and chipbench/.
set -euo pipefail
cd "$(dirname "$0")"

run_py() { PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python "$@"; }

# static analysis first: NK01-NK04 (lock/clock/tracing/registry
# discipline) against the committed baseline — cheaper than any test and
# fatal, so a lint regression fails before the suite spends minutes
# compiling pipelines
run_py -m repro.analysis src
# generic lint rides along when ruff is installed (dev extra); the
# container image does not ship it, so absence is not an error
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests
fi

run_py -m pytest -x -q "$@"

# sharding tests, second pass in a dedicated 8-fake-device process: the
# device-count flag must land before jax initialises and must NOT leak
# into the main suite (it perturbs XLA CPU numerics enough to break the
# bit-exact split-invariance tests), so the multi-device cases skip above
# and run for real here
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    run_py -m pytest -x -q tests/test_sharding.py
