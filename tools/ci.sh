#!/usr/bin/env bash
# Single CI entrypoint (ISSUE 8 satellite).  Runs, in order:
#
#   0. dslint        — tools/dslint static contract checks (ISSUE 15):
#                      hot-path d2h/sync lint, config parity, lock
#                      discipline, disabled-path cost, catalog closure
#                      (metrics + chaos sites + flight events + DS_*
#                      env docs).  Strict: any unsuppressed finding or
#                      stale baseline entry fails BEFORE the test
#                      tiers, so a contract break is named fast
#   1. tier-1        — the ROADMAP verify tier (-m 'not slow'; includes
#                      the heavy tier and the chaos suite)
#   2. chaos tier    — every fault-injection test alone (-m chaos), so
#                      a chaos regression is named even when tier-1's
#                      summary is long
#   3. replay smoke  — tools/replay_trace.py --check over the first 32
#                      requests of the checked-in sample trace: a
#                      captured workload must replay with matching
#                      request count / lengths / share structure; the
#                      --spec pass replays the same workload with
#                      speculative decoding on and checks the SAME
#                      structural parity (speculation may change only
#                      throughput/metrics, ISSUE 10); a second arm
#                      replays with --drafter model (ISSUE 17) so the
#                      in-program draft head passes the same parity bar
#   3a. shard smoke  — tools/replay_trace.py --tp 2 --check
#                      (ISSUE 18): the same 32 requests replayed on a
#                      2-way simulated tensor-parallel mesh (host
#                      device count forced before jax loads); asserts
#                      the base structural parity PLUS zero on-path
#                      compiles and zero structured errors — sharding
#                      may change wire bytes, nothing the user sees
#   4. fleet smoke   — tools/fleetctl.py --smoke (ISSUE 11): spin two
#                      debug serving replicas on ephemeral metrics
#                      ports, scrape both, and assert the federated
#                      /fleet view is EXACTLY the sum of its parts
#                      (counters and histogram bucket counts)
#   5. pool smoke    — tools/fleetctl.py --pool-smoke (ISSUE 12): two
#                      in-process replicas behind the prefix-affinity
#                      router replay the first 32 requests of the
#                      checked-in trace; one replica is drain-migrated
#                      away mid-replay; asserts exact gen-length parity
#                      and ZERO lost requests
#   3b. tier smoke   — tools/replay_trace.py --tier --check
#                      (ISSUE 16): the first 24 requests replayed
#                      TWICE on one device-starved engine (a 4-page
#                      device cache request, clamped to the smallest
#                      schedulable pool) backed by a tiny host ring
#                      spilling to a disk tier; asserts structural
#                      parity, demotions + disk spills + promotions
#                      actually happened, warm-from-tier tokens ==
#                      cold tokens (keyed sampling), and the store's
#                      host+disk+inflight == indexed accounting
#   5b. disagg smoke — tools/replay_trace.py --disagg --check
#                      (ISSUE 13): the same 32 requests through the
#                      two-pool prefill/decode scheduler with
#                      committed-page KV streaming handoffs; asserts
#                      structural parity AND zero lost requests
#   5d. journey smoke — tools/replay_trace.py --disagg --journeys
#                      --check (ISSUE 19): the same 32 requests with
#                      request journeys on; asserts every completed
#                      request reconstructs a GAP-FREE segment chain
#                      whose segments sum to its measured e2e latency,
#                      and that zero handoff fragments were orphaned
#   5c. cold-start smoke — tools/coldstart_smoke.py --check
#                      (ISSUE 14): process A mines a lattice artifact
#                      from the checked-in trace, precompiles it into
#                      a persistent compile cache, and snapshots a
#                      partially-served run; a COLD process B restores
#                      with lattice="auto:…" against the warm cache
#                      and replays — asserting tokenwise parity,
#                      compile_on_path_total == 0, and ZERO true
#                      compiles (cache loads only)
#
# Usage: tools/ci.sh [extra pytest args for the tier-1 leg]
# Environment: JAX_PLATFORMS defaults to cpu (the CI mesh);
#              DS_CI_TIMEOUT (seconds, default 870) bounds tier-1.

set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
TIMEOUT="${DS_CI_TIMEOUT:-870}"

echo "== dslint static contract checks =="
python -m tools.dslint --strict

echo "== tier-1 (timeout ${TIMEOUT}s) =="
timeout -k 10 "$TIMEOUT" python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly "$@"

echo "== chaos tier =="
python -m pytest tests/ -q -m chaos -p no:cacheprovider

echo "== workload replay smoke (incl. speculative pass) =="
python tools/replay_trace.py --trace tools/traces/sample_200.jsonl \
    --limit 32 --spec --check > /dev/null

echo "== model-drafted speculative replay smoke (ISSUE 17) =="
python tools/replay_trace.py --trace tools/traces/sample_200.jsonl \
    --limit 32 --spec --drafter model --check > /dev/null

echo "== sharded replay smoke (tp=2 simulated mesh, ISSUE 18) =="
python tools/replay_trace.py --trace tools/traces/sample_200.jsonl \
    --limit 32 --tp 2 --check > /dev/null

echo "== tiered-KV smoke (4-page device cache forcing demotion) =="
python tools/replay_trace.py --trace tools/traces/sample_200.jsonl \
    --limit 24 --tier --tier-device-pages 4 --check > /dev/null

echo "== fleetctl federation smoke =="
python tools/fleetctl.py --smoke

echo "== replica-pool router smoke (migrate mid-replay) =="
python tools/fleetctl.py --pool-smoke

echo "== disaggregated two-pool smoke (KV-streaming handoffs) =="
python tools/replay_trace.py --trace tools/traces/sample_200.jsonl \
    --limit 32 --disagg --check > /dev/null

echo "== request-journey smoke (gap-free chains, 0 orphans) =="
python tools/replay_trace.py --trace tools/traces/sample_200.jsonl \
    --limit 32 --disagg --journeys --check > /dev/null

echo "== cold-start smoke (persistent compile cache + auto lattice) =="
python tools/coldstart_smoke.py --check --limit 16 > /dev/null

echo "== memory observatory smoke (ledger validate + OOM forensics) =="
python tools/plan_capacity.py --trace tools/traces/sample_200.jsonl \
    --limit 20 --validate --oom-smoke --check > /dev/null

# (the former standalone metric-lint leg is leg 0's metric-catalog
# rule now; tools/check_metrics.py remains as a local/CI-transition
# shim over the same implementation)

echo "ci.sh: all gates green"
