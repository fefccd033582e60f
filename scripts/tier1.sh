#!/usr/bin/env bash
# Tier-1 verification: the full test suite plus three smoke benches —
#  * cache-ablation (~30 s): >= 2x feature-byte reduction at a 20% cache
#    fraction and cached/uncached loss equivalence,
#  * cache-refresh (~30 s): on a drifting-hub trace the dynamic refresh
#    policy's steady-state hit rate >= the static policy's with strictly
#    fewer shipped bytes, and trainer losses bit-identical with refresh
#    on/off (versioned in-flight consistency),
#  * out-of-core (~60 s): mmap gather parity with the dense backend in a
#    tempdir (cleaned up on exit), the spill writer's one-partition
#    buffered-rows bound, a bounded gather working set, and mmap/dense
#    loss bit-identity,
#  * background I/O (~60 s): window-prefetch on/off disk-tier sweep —
#    prefetch on must show strictly lower load-stage stall, page-cache
#    residency stays under the window-LRU bound, and trainer losses are
#    bit-identical across the {prefetch, async_refresh} 4-config matrix,
#  * sharded plane (~60 s): sharded vs replicated feature cache at equal
#    per-device capacity — the union gather must ship strictly fewer
#    host->device bytes than per-trainer dedup at n_accel >= 2, the
#    n_accel=4 cell must clear the >= 1.5x shipped-byte reduction, and
#    sharded/replicated losses must be bit-identical,
#
#  * autotune (~90 s): closed-DRM-loop gate — a knob-misconfigured run
#    (no prefetch, one-window LRU, skewed stage threads) with the
#    model-predictive knob autotuner ON must converge to within 15% of
#    the hand-tuned steady-state iteration time, with losses
#    bit-identical to the static-knob twin and >= 1 accepted move,
#
#  * chaos suite (~30 s, hard 300 s timeout): deterministic fault
#    injection against the whole trainer — transient storage faults with
#    bit-identical losses, prefetcher death with graceful degradation,
#    and the pipeline stage-watchdog.  Runs as its OWN step so a
#    fault-handling regression that wedges cannot hang the main suite:
#    the timeout converts a hang into a failure.
#
#   ./scripts/tier1.sh            # everything
#   ./scripts/tier1.sh --fast     # skip the 'slow' subprocess-compile tests
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MARK=(-m "not chaos")
if [[ "${1:-}" == "--fast" ]]; then
    MARK=(-m "not slow and not chaos")
fi

# static analysis first: a lock-discipline or kernel-invariant finding is
# cheaper to surface than the test failure (or silent race) it predicts
./scripts/lint.sh

# ${MARK[@]+...} guards the empty-array expansion under `set -u` on bash < 4.4
python -m pytest -x -q ${MARK[@]+"${MARK[@]}"}
timeout 300 python -m pytest -x -q -m chaos
python -m benchmarks.fig_cache_ablation --smoke
python -m benchmarks.fig_cache_ablation --smoke-refresh
python -m benchmarks.bench_outofcore --smoke
python -m benchmarks.bench_outofcore --smoke-prefetch
python -m benchmarks.bench_shard --smoke
python -m benchmarks.bench_autotune --smoke
echo "tier1: OK"
