#!/usr/bin/env bash
# Tier-1 verify: the whole suite, one command, locally and in CI.
#
#   scripts/ci.sh            # full tier-1 run (fails fast, quiet)
#   scripts/ci.sh -k fused   # extra pytest args pass through
#
# The main pytest process stays on the real single-device CPU view — the
# distributed/differential tests (tests/test_distributed.py,
# tests/test_group_average_fused.py) each spawn subprocesses with
# XLA_FLAGS=--xla_force_host_platform_device_count=8, so the 8-device
# host-platform CPU mesh is exercised without ever forcing the flag
# globally (it must not leak into unrelated compilation caches).
set -euo pipefail
cd "$(dirname "$0")/.."

# Belt and braces: never inherit a stray device-forcing flag or GPU pick-up.
unset XLA_FLAGS
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# Guard: compiled bytecode must never be tracked (it once was; .gitignore
# covers new files, this catches anything force-added or historical).
if git ls-files | grep -qE '(^|/)__pycache__/|\.py[co]$'; then
  echo "ci.sh: tracked __pycache__/*.pyc files found:" >&2
  git ls-files | grep -E '(^|/)__pycache__/|\.py[co]$' >&2
  exit 1
fi

PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m pytest -x -q "$@"

# Modeled-perf gate: overlapped < serial for transformer_wmt AND the
# hierarchical (2-link-class pod x data) per-class bucket budgets beat the
# single global budget (distinct per-class choices).  Writes the tracked
# BENCH_group_average.json; model-only, a few seconds.
python benchmarks/bench_group_average.py --check

# FSDP-within-pod smoke (DESIGN.md §10): compile the sharded train step on
# an 8-device (pod=2, data=4, model=1) host mesh with the hierarchical
# topology and cross-check the plan — the run exits non-zero if the plan's
# per-class ppermute expectation mismatches the compiled HLO or any
# parameter all-gather / gradient reduce-scatter leaks off the intra-pod
# shard axis onto a DCN (pod) axis.
XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
  --smoke --sharding fsdp --hierarchical --mesh-shape 2,4,1 \
  --out experiments/dryrun-ci

# Layer-streamed FSDP smoke (DESIGN.md §11): compile the streamed train
# step and cross-check the schedule against the HLO — the run exits
# non-zero if any gather leaves the intra-pod axis, any single all-gather
# exceeds one layer-span bucket (a gather-all regression), or the
# shard-axis gather count mismatches the streamed fwd+bwd expectation
# (a CSE'd backward re-gather that would silently pin forward buffers).
XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
  --smoke --sharding fsdp --streamed --hierarchical --mesh-shape 2,4,1 \
  --out experiments/dryrun-ci

# Elastic kill/rejoin smoke (DESIGN.md §12): scripted preemption on the
# 8-device host mesh — a worker leaves mid-training, the dp mesh shrinks
# and the averaging plan recompiles in place (no restart), the worker
# rejoins at the tau-sync barrier, and the run exits non-zero unless the
# rejoiner's replica row is bit-identical to the survivors' at the first
# post-rejoin tau-sync (and the dead topology's plan-cache entries were
# evicted).  Same code path as tests/test_elastic.py's subprocess test.
XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
  python -m repro.launch.elastic

# Chaos smoke (DESIGN.md §13): detector-driven fault tolerance on the
# 8-device host mesh — a fixed FaultSchedule (one hang that wakes, one
# crash that rejoins) silences workers on the virtual clock; NOTHING is
# scripted.  The heartbeat failure detector must suspect each silent
# worker past the collective deadline, shrink the world in place, charge
# the skipped contributions to the staleness ledger (never past
# max_staleness_bound(tau)), and re-admit recovered workers bit-identical
# at the tau-sync barrier — the run exits non-zero on any violation.
XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
  python -m repro.launch.elastic --chaos

# Elastic churn gate (DESIGN.md §12): discrete-event preemption trace,
# elastic recovery (in-place recompile + host-side handoff) vs the
# checkpoint-restart baseline — exits non-zero if the elastic overhead
# fraction is unbounded (>=10% of wall clock) or restart wins on goodput.
PYTHONPATH=src python benchmarks/cluster_sim.py --churn

# Link-constant calibration scaffold smoke (ROADMAP: measured
# alpha/beta/gamma): microbench ppermute/all-gather per mesh axis on the
# 8-device CPU mesh and round-trip the JSON through
# Topology.with_measured.  Tiny payloads — a few seconds.  The scratch
# output name is deliberately NOT LINK_CONSTANTS.json: the one canonical
# copy lives at the repo root (plan.DEFAULT_LINK_CONSTANTS_PATH) and is
# regenerated manually with full payloads.
python benchmarks/calibrate_links.py --smoke \
  --out experiments/LINK_CONSTANTS.smoke.json

# Serving gate (DESIGN.md §14): request-level simulator over the analytic
# cost model — continuous-batching decode loop with inline prefill stalls
# (colocated) vs split prefill/decode pods with DCN KV transfer
# (disaggregated).  Writes the tracked BENCH_serving.json; exits non-zero
# unless disaggregation wins p99 inter-token latency AND holds goodput at
# the modeled operating point.  Model-only, a few seconds.
python benchmarks/serve_sim.py --check
