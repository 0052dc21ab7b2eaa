#!/usr/bin/env bash
# Tier-1 gate + smoke targets.
#
#   scripts/check.sh            tier-1: full default suite (slow deselected
#                               via pytest.ini), no pytest cache, hard
#                               wall-clock guard
#   scripts/check.sh smoke      fast executor/engine subset
#   scripts/check.sh threaded-stress
#                               threaded serving runtime: 8 producer
#                               threads against one replica, id-parity
#                               with run(), out-of-order retirement
#                               probe, zero leaked pending futures
#   scripts/check.sh router-stress
#                               multi-replica routing: policy id-parity,
#                               8 producers across 2 replicas, JSQ
#                               saturation bypass, sub-mesh scan parity,
#                               deterministic fault injection
#   scripts/check.sh async-stress
#                               unified client API: Backend protocol
#                               conformance, 4-path id parity, 200
#                               concurrent asyncio coroutines over a
#                               2-replica router, awaited-admission
#                               backpressure, zero leaked futures
#   scripts/check.sh kernels    kernel parity tests: Pallas (the CPU
#                               interpreter) vs the pure-jnp oracles
#   scripts/check.sh edge-stress
#                               HTTP edge + autoscaler: auth/rate-limit/
#                               error codes over a real socket, coalesced
#                               burst = one backend submit, 200-connection
#                               soak, deterministic load-ramp (scale up
#                               under burst, drain on scale-down, zero
#                               leaked futures at router AND edge level)
#   scripts/check.sh filter-stress
#                               filtered + multi-tenant search: filtered
#                               top-k vs the exact post-filter oracle
#                               (selectivity sweep, delta-only matches,
#                               tombstones, snapshots), tenant quota
#                               enforcement and base-predicate stamping,
#                               socket-level cross-tenant isolation, and
#                               the deadline-adaptive resolver — all
#                               under LINT_LOCKS=1 witnesses
#   scripts/check.sh mutate-stress
#                               updates-while-serving: insert/delete
#                               bursts + background compaction against
#                               the threaded service and a 2-replica
#                               router with a snapshot-hydrated newcomer;
#                               bit-identical ids vs a quiesced serial
#                               replay, snapshot->restore parity, zero
#                               leaked futures, zero witnessed lock-order
#                               violations (LINT_LOCKS=1)
#   scripts/check.sh lint       concurrency static analysis over src/:
#                               guarded-by checker (GB*), lock-order
#                               deadlock detector (LO*), jit/hot-path
#                               purity lints (PU*).  Zero findings or
#                               non-zero exit.  See DESIGN.md §9.
#   scripts/check.sh full       everything, including @slow system tests
#
# CHECK_TIMEOUT overrides the guard (seconds).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MODE="${1:-tier1}"
case "$MODE" in
  smoke)
    exec timeout "${CHECK_TIMEOUT:-420}" \
      python -m pytest -x -q -p no:cacheprovider \
        tests/test_executor.py tests/test_futures.py tests/test_engine.py \
        tests/test_updates.py tests/test_threaded.py tests/test_client.py
    ;;
  lint)
    exec timeout "${CHECK_TIMEOUT:-120}" \
      python -m repro.analysis.concurrency --check src/
    ;;
  threaded-stress)
    # LINT_LOCKS=1: serving-stack locks become OrderedLock witnesses —
    # any runtime lock-order inversion fails the offending test
    export LINT_LOCKS="${LINT_LOCKS:-1}"
    exec timeout "${CHECK_TIMEOUT:-300}" \
      python -m pytest -x -q -p no:cacheprovider tests/test_threaded.py
    ;;
  async-stress)
    exec timeout "${CHECK_TIMEOUT:-300}" \
      python -m pytest -x -q -p no:cacheprovider tests/test_client.py
    ;;
  router-stress)
    export LINT_LOCKS="${LINT_LOCKS:-1}"
    exec timeout "${CHECK_TIMEOUT:-600}" \
      python -m pytest -x -q -p no:cacheprovider tests/test_router.py \
        tests/test_faults.py
    ;;
  mutate-stress)
    export LINT_LOCKS="${LINT_LOCKS:-1}"
    exec timeout "${CHECK_TIMEOUT:-600}" \
      python -m pytest -x -q -p no:cacheprovider \
        tests/test_mutate_stress.py tests/test_segments.py \
        tests/test_updates.py
    ;;
  filter-stress)
    export LINT_LOCKS="${LINT_LOCKS:-1}"
    exec timeout "${CHECK_TIMEOUT:-600}" \
      python -m pytest -x -q -p no:cacheprovider tests/test_filters.py \
        tests/test_tenants.py tests/test_edge.py
    ;;
  kernels)
    exec timeout "${CHECK_TIMEOUT:-600}" \
      python -m pytest -x -q -p no:cacheprovider tests/test_kernels.py \
        tests/test_kernel_props.py
    ;;
  edge-stress)
    export LINT_LOCKS="${LINT_LOCKS:-1}"
    exec timeout "${CHECK_TIMEOUT:-600}" \
      python -m pytest -x -q -p no:cacheprovider tests/test_edge.py \
        tests/test_autoscaler.py tests/test_coalesce.py
    ;;
  tier1)
    exec timeout "${CHECK_TIMEOUT:-600}" \
      python -m pytest -x -q -p no:cacheprovider
    ;;
  full)
    exec timeout "${CHECK_TIMEOUT:-1800}" \
      python -m pytest -x -q -p no:cacheprovider -m ""
    ;;
  *)
    echo "usage: scripts/check.sh [tier1|smoke|lint|threaded-stress|router-stress|async-stress|mutate-stress|filter-stress|kernels|edge-stress|full]" >&2
    exit 2
    ;;
esac
