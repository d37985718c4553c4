#!/usr/bin/env bash
# Tier-1 verify: the full test suite.  Performance is measured on the chip
# by the benchmark in bench/ (bench/run.py), not here.
#
#   scripts/verify.sh            # full tests
#   scripts/verify.sh --fast     # the same
#   scripts/verify.sh --quick    # tier-1 minus `slow` markers
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MODE="${1:-}"

echo "== tier-1: pytest =="
if [[ "$MODE" == "--quick" ]]; then
  # fail fast on the cascade accuracy/parity suite, the cluster parity +
  # chaos/failover suites, the deadline/admission-control suite, the
  # observability suite (tracing parity + PROFILE + metrics views), and the
  # kNN hot path (batched index + PQ/ADC + kernel dispatch), then the rest
  # of the tier-1 suite minus `slow` markers
  python -m pytest -x -q tests/test_cascade.py \
      tests/test_cluster.py tests/test_replication.py \
      tests/test_overload.py tests/test_obs.py \
      tests/test_vector_index.py \
      tests/test_pq_index.py tests/test_kernels.py -m "not slow"
  python -m pytest -x -q -m "not slow" \
      --ignore=tests/test_cascade.py \
      --ignore=tests/test_cluster.py --ignore=tests/test_replication.py \
      --ignore=tests/test_overload.py --ignore=tests/test_obs.py \
      --ignore=tests/test_vector_index.py \
      --ignore=tests/test_pq_index.py --ignore=tests/test_kernels.py
else
  python -m pytest -x -q
fi

echo
echo "verify OK${MODE:+ (${MODE#--})}"
