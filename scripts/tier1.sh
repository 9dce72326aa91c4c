#!/usr/bin/env bash
# Tier-1 verify: the gate every PR must keep green (see ROADMAP.md).
#
# Order: docs link check -> lint -> test suite -> static analysis gate ->
# benchmark smoke. The test suite includes the streaming-parity harness in
# tests/test_streaming_parity.py — the bit-for-bit XLA-vs-Pallas gate —
# and the fixed-point hardware-twin gates: tests/test_fixed.py carrier
# parity + the EXACT-match integer golden fixtures in tests/test_golden.py
# (the `pallas` marker selects just the kernel-path subset). The analysis
# gate (scripts/analyze.py, full config) statically PROVES the deployed
# integer programs multiplierless and int32-overflow-free (docs/
# analysis.md). bench_smoke.sh also censuses the int32 jaxpr and fails on
# any multiply, so benchmark bit-rot is caught here, not at release time.
#
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# docs gate: broken intra-repo links in README/ROADMAP/docs fail tier-1
python scripts/check_docs.py

# lint gate: conventional linter alongside the domain-specific passes
# (config in pyproject.toml; this container has no ruff — skip loudly)
if command -v ruff >/dev/null 2>&1; then
  ruff check .
else
  echo "tier1: WARNING: ruff not installed; skipping lint gate" >&2
fi

# the whole suite in one pytest call, on the CPU (Pallas in interpret
# mode; tests/test_chip_compile.py compiles the hot path for a described
# v5e); extra args are passed through
JAX_PLATFORMS=cpu python -m pytest -x -q "$@"

# static verification gate: op-legality + worst-case interval proof +
# determinism lint over the deployed integer programs (full config;
# refreshes the committed ANALYSIS.json artifact)
python scripts/analyze.py

# artifact-drift gate: analyze.py rewrites ANALYSIS.json in place, so a
# stale committed report would otherwise pass silently — the diff IS the
# review signal, make it a failure, not a dirty working tree to notice
if git -C . rev-parse --is-inside-work-tree >/dev/null 2>&1 \
    && ! git diff --exit-code -- ANALYSIS.json; then
  echo "tier1: ANALYSIS.json drifted from the committed copy —" \
       "commit the refreshed artifact (diff above)" >&2
  exit 1
fi

# hardware-artifact drift gate: regenerate the IR-derived C/Verilog/ROM/
# register artifacts (full config, deterministic) and fail if they moved —
# emit_ir.py also re-proves, per executable target, that the freshly
# emitted netlist replays the IR interpreter bit-for-bit (iverilog when
# installed, the in-repo cycle simulator otherwise) before writing — a PR
# that changes the deployed datapath must commit the new artifacts/ir
# tree, and artifact drift without a source change is a bug in the
# emitters, not noise
python scripts/emit_ir.py
if git -C . rev-parse --is-inside-work-tree >/dev/null 2>&1 \
    && ! git diff --exit-code -- artifacts/ir; then
  echo "tier1: artifacts/ir drifted from the committed tree —" \
       "commit the regenerated hardware artifacts (diff above)" >&2
  exit 1
fi

scripts/bench_smoke.sh
