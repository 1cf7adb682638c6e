#!/usr/bin/env bash
# CI gate: fast test subset + bounded mapping-DSE smoke.
#
#   scripts/ci.sh            # what CI runs
#
# The slow suites (whole-ResNet interp equivalence, the CNN reference
# sweeps) stay out of the gate; run the full tier-1 sweep with
# `PYTHONPATH=src python -m pytest -x -q` before release.  Performance is
# measured on the chip by the benchmark in bench/, not here.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q -m "not slow"
# bounded mapping-DSE smoke: tiny fixed-seed space, winners bitwise-
# validated against the snake baseline (<30 s; exits non-zero on mismatch)
python -m repro.dse --smoke --seed 0
