#!/usr/bin/env bash
# CI entry, three stages over the unified AgentService API:
#
#   1. smokes   — the serving launcher on BOTH backends, single and
#                 multi-replica (ReplicatedBackend + router), ~40s CPU;
#   2. tier-1   — the cross-backend event-conformance suite first (its
#                 own named gate: the lifecycle-grammar contract every
#                 backend must satisfy), then the default pytest tier
#                 (slow-marked model-zoo sweeps are deselected via
#                 addopts; the full tier re-runs the conformance file —
#                 cheap, and -x keeps one red gate from hiding behind
#                 another); the frozen-oracle gates (optimized sim and
#                 engine cores against their references) are tier-1
#                 tests; tier-1 additionally re-runs the concurrency
#                 suites under PYTHONDEVMODE=1 + faulthandler
#                 (thread-safety);
#   3. slow     — `pytest -m slow`: the full-model consistency and
#                 per-architecture forward sweeps on reduced configs.
#
#   scripts/ci.sh            # smokes + tier-1 (the gating stages)
#   scripts/ci.sh --smoke    # smokes only
#   scripts/ci.sh --slow     # all three stages
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# Smokes first: a pre-existing test failure must not mask a broken
# serving path.
echo "== smoke: repro.launch.serve --backend sim =="
python -m repro.launch.serve --backend sim --n-agents 4 --window-s 10

echo "== smoke: repro.launch.serve --backend sim --replicas 3 =="
python -m repro.launch.serve --backend sim --n-agents 6 --window-s 10 \
    --replicas 3 --router memory_cost_aware

echo "== smoke: repro.launch.serve --backend engine =="
python -m repro.launch.serve --backend engine --n-agents 3 --window-s 10 \
    --pool-tokens 2048 --max-batch 2

echo "== smoke: repro.launch.serve --backend engine --replicas 2 =="
python -m repro.launch.serve --backend engine --n-agents 4 --window-s 10 \
    --pool-tokens 1024 --max-batch 2 --replicas 2 --router round_robin

if [[ "${1:-}" == "--smoke" ]]; then
    echo "CI OK (smokes)"
    exit 0
fi

echo "== tier-1 gate: cross-backend event conformance =="
python -m pytest -x -q tests/test_event_conformance.py

echo "== tier-1: pytest (slow tier deselected) =="
python -m pytest -x -q

# Re-run the concurrency-sensitive suites in dev mode: PYTHONDEVMODE
# surfaces unjoined threads / unclosed resources and faulthandler dumps
# every thread on a hang — the concurrent fleet drive (fleet_workers)
# must stay clean under both.
echo "== tier-1 thread-safety: concurrency suites under PYTHONDEVMODE =="
PYTHONDEVMODE=1 PYTHONFAULTHANDLER=1 python -m pytest -x -q \
    tests/test_fleet_concurrent.py tests/test_faults.py \
    tests/test_suspend.py

if [[ "${1:-}" == "--slow" ]]; then
    echo "== slow tier: pytest -m slow =="
    python -m pytest -q -m slow
fi

echo "CI OK"
