#!/usr/bin/env bash
# Tier-1 test entry point.
#
# Runs on the CPU, also on a machine with a TPU: the golden pins hold CPU
# numerics, and the chip belongs to chip_smoke.py.  Forces 8 virtual CPU
# devices BEFORE jax initializes so the multi-device shard_map tests
# (clients sharded over a real >1-device mesh) actually exercise
# cross-shard psum aggregation on a laptop/CI box (olmax idiom).
#
#   ./test.sh                 # fast default suite (slow tests deselected)
#                             # + 1-round streaming-scalability bench smoke
#   ./test.sh -m slow         # only the slow sweeps
#   ./test.sh -m ""           # everything
#   ./test.sh tests/test_server_opt.py -k shard_map
set -euo pipefail
cd "$(dirname "$0")"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# flcheck smoke first: AST lint over src/ + vmap taint proof that no raw
# client delta reaches the aggregation boundary unsanitized — fails fast
# before the (slower) pytest run.  Full topology matrix: tools/flcheck --all
echo "== flcheck smoke (lint + quick taint proof)"
tools/flcheck --quick-taint src/

# level-3 cost-audit smoke: the statically derived wire bytes / stage FLOPs
# must match the committed baseline (the 8-virtual-device geometry above
# covers the flat8/hier2x4 paths) — docs/static_analysis.md
echo "== flcheck cost-audit smoke (wire bytes + stage FLOPs vs baseline)"
tools/flcheck --no-lint --cost --baseline src/repro/analysis/baselines/round_costs.json

python -m pytest -q "$@"

# Default run also smokes the streaming client-window path (1 round over a
# 1000-client population, O(m) per round) so 10k+ scaling can't silently rot,
# then the full pipeline: DP clip + noise + RING-masked int8 deltas (masking
# + quantization compose in the quantizer's integer ring — the secure-agg
# wire stays int8+scale, asserted by the audited byte table the smoke
# prints) aggregated edge->region->cloud over the 2x4 (region, clients) mesh.
if [ "$#" -eq 0 ]; then
  echo "== bench_scalability smoke (streaming provider, 1 round)"
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/bench_scalability.py \
      --clients 1000 --rounds 1 --clients-per-round 16 --days 30 --smoke
  echo "== bench_scalability smoke (DP + ring-masked int8 + hierarchical, 1 round)"
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/bench_scalability.py \
      --clients 1000 --rounds 1 --clients-per-round 16 --days 30 --smoke \
      --dp-clip 1.0 --dp-noise 0.5 --quantize 8 --hier --regions 2 \
      --secure-agg
  echo "== bench_scalability smoke (semi-sync buffered rounds, lognormal stragglers)"
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/bench_scalability.py \
      --clients 200 --rounds 3 --clients-per-round 8 --days 30 --smoke \
      --mode semi_sync --stragglers lognormal --over-select 1.5
  # churn axis: nonzero dropout with secure-agg cohort re-key on the RING
  # wire (--quantize 8 + --dp-clip: the rekey mask correction runs mod 2^b).
  # buffer_k is pinned to m' = ceil(1.5*8) = 12 (wait-for-cohort) because
  # cohort-atomic folds at a k-th-arrival clock need >=4 rounds AND a
  # full-cohort flush threshold to complete any fold in a smoke-sized run.
  echo "== bench_scalability smoke (client churn + dropout, ring-masked re-key)"
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/bench_scalability.py \
      --clients 200 --rounds 4 --clients-per-round 8 --days 30 --smoke \
      --mode semi_sync --stragglers lognormal --over-select 1.5 \
      --buffer-k 12 --secure-agg --quantize 8 --dp-clip 1.0 \
      --churn 0,0.2 --timeout-rounds 1
  # serving smoke: replay a small Poisson trace through the padded-bucket
  # engine with cluster routing + a mid-replay hot-swap; asserts zero
  # steady-state recompiles (jit-cache probe) on fp32 AND int8 weights.
  echo "== bench_serving smoke (replayed trace, hot-swap + routing)"
  PYTHONPATH="src:.${PYTHONPATH:+:$PYTHONPATH}" \
    python benchmarks/bench_serving.py --smoke
fi
