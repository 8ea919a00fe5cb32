#!/usr/bin/env bash
# Reproducible tier-1 entry point.
#
#   scripts/ci.sh               fast tier-1: the @sharded suite
#                               (mesh-native engines, subprocesses with
#                               4 forced host devices) first, then the
#                               @mixed suite (unified mixed-batch
#                               plane), then the @paged property suite
#                               (block allocator + cache surgery), then
#                               the full suite minus @slow model cases,
#                               then the benchmark smoke
#                               (microbench + quick e2e_pd emitting
#                               BENCH_e2e.json) guarded against the
#                               committed baseline (>25% TTFT-p99 or
#                               throughput regression fails)
#   scripts/ci.sh --full        everything, including @slow cases (the
#                               cross-plane sim/real × padded/paged
#                               equivalence sweep lives here;
#                               equivalent to the ROADMAP tier-1 command
#                               `pytest -x -q`)
#   scripts/ci.sh --real-smoke  real-engine smoke: examples/serve_e2e.py
#                               through the REAL P/D ClusterRuntime plane
#                               with the paged KV cache, compared against
#                               padded slots at equal memory — fails on
#                               any unfinished request or if paged does
#                               not sustain strictly higher concurrent
#                               decode; records the result in
#                               BENCH_e2e.json [real_plane].  Then the
#                               prefix-cache A/B [real_plane_prefix] and
#                               the SLO-overload A/B — page-level
#                               preemption must post strictly higher
#                               goodput than drain-only at equal KV
#                               memory [real_plane_overload].  Finally
#                               the unified mixed-batch A/B — chunked
#                               prefill piggybacked into the decode
#                               steps must post a strictly lower ITL p99
#                               at equal-or-higher throughput than the
#                               disjoint (prefill-prioritizing) ablation
#                               [real_plane_mixed].  Finally the sharded
#                               DP+EP A/B on a 4-device forced-host
#                               mesh — with the EP all-to-all verified
#                               in the compiled step HLO, sbs-la's
#                               aligned batch formation must post a
#                               strictly lower TTFT p99 than immediate
#                               dispatch at equal-or-higher throughput,
#                               and the measured per-step sync time
#                               calibrates CostModel.t_sync
#                               [real_plane_sharded]
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--real-smoke" ]]; then
    echo "== real-engine smoke (serve_e2e paged vs padded, 150s budget) =="
    PYTHONPATH=src timeout 150 python examples/serve_e2e.py \
        --arch granite-moe-1b-a400m --requests 10 --max-new 12 \
        --max-batch-per-dp 1 --arrival-spacing 0 \
        --schedulers sbs-la --timeout 110 --compare-padded \
        --bench-json BENCH_e2e.json \
        || { echo "real smoke FAILED (unfinished requests, paged <= padded" \
                  "concurrency, or >150s)" >&2
             exit 1; }
    echo "== real-plane prefix-cache A/B (shared tenants, 300s budget) =="
    PYTHONPATH=src timeout 300 python examples/serve_e2e.py \
        --requests 10 --max-new 4 --timeout 150 \
        --prefix-bench --bench-json BENCH_e2e.json \
        || { echo "prefix smoke FAILED (no FLOPs saved, cached ttft_p99" \
                  "not lower, unfinished requests, or >300s)" >&2
             exit 1; }
    echo "== real-plane SLO-overload A/B (preempt vs drain-only, 300s budget) =="
    PYTHONPATH=src timeout 300 python examples/serve_e2e.py \
        --timeout 150 --overload-bench --bench-json BENCH_e2e.json \
        || { echo "overload smoke FAILED (preempting goodput not strictly" \
                  "above drain-only, no preemptions, unfinished requests," \
                  "or >300s)" >&2
             exit 1; }
    echo "== real-plane mixed-batch A/B (piggyback vs disjoint, 600s budget) =="
    PYTHONPATH=src timeout 600 python examples/serve_e2e.py \
        --timeout 150 --mixed-bench --bench-json BENCH_e2e.json \
        || { echo "mixed smoke FAILED (piggyback itl_p99 not strictly" \
                  "below disjoint at equal-or-higher throughput," \
                  "unfinished requests, or >600s)" >&2
             exit 1; }
    echo "== real-plane sharded DP+EP A/B (sbs-la vs immediate, 600s budget) =="
    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        timeout 600 python examples/serve_e2e.py \
        --arch granite-moe-1b-a400m --timeout 150 \
        --sharded-bench --bench-json BENCH_e2e.json \
        || { echo "sharded smoke FAILED (EP all-to-all absent from step" \
                  "HLO, sbs-la ttft_p99 not strictly below immediate at" \
                  "equal-or-higher throughput, unfinished requests, or" \
                  ">600s)" >&2
             exit 1; }
    echo "REAL SMOKE OK"
    exit 0
fi

echo "== tier-1 tests =="
if [[ "${1:-}" == "--full" ]]; then
    PYTHONPATH=src python -m pytest -q
else
    # sharded mesh-native suite first (fail fast on the newest
    # subsystem; its multi-device cases subprocess with their own
    # forced host devices), then mixed-batch, then the paged KV
    # property suite, then everything else; @slow — including the
    # heavyweight cross-plane equivalence sweep — stays behind --full
    PYTHONPATH=src python -m pytest -q -m "sharded and not slow"
    PYTHONPATH=src python -m pytest -q -m "mixed and not slow and not sharded"
    PYTHONPATH=src python -m pytest -q \
        -m "paged and not slow and not mixed and not sharded"
    PYTHONPATH=src python -m pytest -q \
        -m "not slow and not paged and not mixed and not sharded"
fi

echo "== benchmark smoke (microbench) =="
PYTHONPATH=src:. python benchmarks/run.py --only microbench \
    || { echo "benchmark smoke FAILED" >&2; exit 1; }

echo "== benchmark smoke (e2e_pd --quick --json -> BENCH_e2e.json) =="
baseline=""
if git show HEAD:BENCH_e2e.json >/tmp/bench_baseline.json 2>/dev/null; then
    baseline=/tmp/bench_baseline.json
fi
if ! PYTHONPATH=src:. python benchmarks/run.py --only e2e_pd --quick --json \
        || [[ ! -s BENCH_e2e.json ]]; then
    echo "e2e_pd smoke FAILED (no BENCH_e2e.json)" >&2
    exit 1
fi

echo "== bench regression guard (fresh --quick vs committed baseline) =="
if [[ -n "$baseline" ]]; then
    # --section e2e_quick: only the rows this --quick run regenerated are
    # judged (e2e_full rows in the working tree are passthrough data)
    python scripts/check_bench_regression.py "$baseline" BENCH_e2e.json \
        --threshold 0.25 --section e2e_quick \
        || { echo "bench regression guard FAILED" >&2; exit 1; }
else
    echo "no committed BENCH_e2e.json baseline; guard skipped"
fi
echo "CI OK"
