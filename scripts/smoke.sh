#!/usr/bin/env bash
# Tier-1 smoke: the repo's own test suite + an import-level check of the
# benchmark driver (catches dispatch/API breakage without the multi-minute
# full benchmark run).
set -euo pipefail
cd "$(dirname "$0")/.."

# Streaming-session + edge-set + device convex + hierarchy + serving +
# runtime gates: the newest engine paths fail fast and loudly before the
# multi-minute full suite below.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q -m "not slow" \
    --durations=20 \
    tests/test_session.py tests/test_edges.py tests/test_device_convex.py \
    tests/test_hierarchy.py tests/test_serving.py tests/test_runtime.py

# The fast gate must not silently shrink: @slow markings, marker typos
# and bad deselects all surface as a collected-count drift here.
# Update the expected count when tests are added/removed on purpose.
EXPECTED_FAST_GATE_TESTS=420
collected=$(PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q \
    -m "not slow" --collect-only 2>/dev/null | tail -1 | grep -oE '[0-9]+' | head -1)
if [ "$collected" != "$EXPECTED_FAST_GATE_TESTS" ]; then
    echo "fast gate collected $collected tests, expected" \
         "$EXPECTED_FAST_GATE_TESTS (update scripts/smoke.sh if intended)" >&2
    exit 1
fi

# Fast gate first: the full suite minus the @slow large-C engine runs.
# Deselected: failures already present at the seed commit (c788f4d) —
# kept visible here so a future fix can re-enable them.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q -m "not slow" \
    --durations=20 \
    --deselect tests/test_dryrun_integration.py::test_dryrun_single_combo \
    --deselect tests/test_federated.py::test_one_shot_aggregate_recovers_clusters \
    --deselect tests/test_federated.py::test_aggregation_improves_or_matches_local \
    --deselect tests/test_theory_and_baselines.py::test_ifca_needs_many_rounds_where_odcl_needs_one

PYTHONPATH=src python - <<'PY'
import benchmarks.run  # imports every benchmark module
from repro.core import ODCL, get_algorithm, list_algorithms, list_methods
from repro.core.clustering import is_device_algorithm
from repro.core.engine import AggregationSession, HierarchicalSession
from repro.core.engine import list_aggregators, list_edge_sets, make_aggregator
from repro.core.federated_methods import list_federated_methods
from repro.scenarios import build_scenario, list_scenarios
from repro.serving import BackpressureError, RouteServer, RouteTimeout
from repro import runtime

assert len(list_algorithms()) >= 8, list_algorithms()
assert "odcl" in list_methods()
get_algorithm("kmeans++")
assert is_device_algorithm(get_algorithm("kmeans-device"))
assert is_device_algorithm(get_algorithm("convex-device"))
assert is_device_algorithm(get_algorithm("clusterpath-device"))
assert is_device_algorithm(get_algorithm("gradient-device"))
assert {"complete", "knn", "knn-approx"} <= set(list_edge_sets())
assert callable(AggregationSession)
assert callable(HierarchicalSession)
assert {"odcl", "ifca", "fedavg", "local-only"} <= set(list_federated_methods())
assert {"mean", "trimmed_mean", "median",
        "geometric_median"} <= set(list_aggregators())
assert make_aggregator("trimmed_mean", beta=0.2).beta == 0.2
assert make_aggregator("geometric_median").breakdown == 0.5
assert callable(RouteServer) and issubclass(RouteTimeout, Exception)
assert issubclass(BackpressureError, Exception)
assert callable(runtime.apply_env_presets)
assert {"drift", "longtail", "byzantine", "dp"} <= set(list_scenarios())
assert build_scenario("longtail+byzantine", frac=0.1).transforms_sketches is False
print("benchmark driver imports OK;",
      f"{len(list_algorithms())} clustering algorithms,",
      f"{len(list_methods())} federated methods,",
      f"{len(list_federated_methods())} LM-scale federated methods,",
      f"{len(list_edge_sets())} edge sets,",
      f"{len(list_aggregators())} aggregators,",
      f"{len(list_scenarios())} scenarios registered")
PY

# reduced large-C simulation: the device aggregation engine end-to-end
# (wave-batched client gen + local ERMs -> sketch -> kmeans-device ->
# cluster mean, one jitted program)
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 512 --clusters 8 --wave 256 --samples 32 --init spectral

# the same federation through the two-level hierarchical round (4 shard
# sessions, then the shard centers clustered at the top level)
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 512 --clusters 8 --wave 128 --samples 32 --shards 4

# adversity gate: 10% sign-flip Byzantine clients survived by the
# trimmed-mean aggregator (robust center update + step-3 reduction +
# trimmed-objective restart selection, all inside the jitted round)
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 256 --clusters 4 --wave 128 --samples 32 \
    --init random --restarts 4 \
    --scenario byzantine --byzantine-frac 0.1 \
    --aggregator trimmed_mean --trim-beta 0.25

# mutable serving: keyed drifted re-uploads + churned-in joiners under
# the sliding-window staleness policy, drift-triggered warm re-finalize
# and the one-program batched route
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 256 --clusters 4 --wave 128 --samples 32 \
    --route-probes 32 --finalize-repeats 3 \
    --reupload-frac 0.25 --churn 32 --max-age 2 --refinalize-threshold 1.5

# same federation through the iterative baseline (sketch-assign rounds)
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 256 --clusters 4 --wave 128 --samples 32 --init spectral \
    --method ifca --rounds 3

# the convex family on the same federation (K-free exact-lambda ODCL-CC
# through the device AMA + fusion-graph components, one jitted round)
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 128 --clusters 4 --wave 64 --samples 32 \
    --algorithm convex --sketch-dim 32

# the same convex round over the sparse mutual-kNN fusion graph (the
# EdgeSet registry path that scales ODCL-CC past the C=4k edge wall)
PYTHONPATH=src python -m repro.launch.simulate \
    --clients 128 --clusters 4 --wave 64 --samples 32 \
    --algorithm convex-device --edges knn --knn-k 6 --sketch-dim 32

# reduced deep-model drivers through the FederatedMethod registry:
# the one-shot round on the device engine, and IFCA's round loop
PYTHONPATH=src python -m repro.launch.train --reduced --clients 4 \
    --clusters 2 --local-steps 4 --post-steps 0 --batch 2 --seq-len 16 \
    --method odcl --engine device --sketch-dim 32

# same reduced train run, but clustered by the device convex family
PYTHONPATH=src python -m repro.launch.train --reduced --clients 4 \
    --clusters 2 --local-steps 4 --post-steps 0 --batch 2 --seq-len 16 \
    --method odcl --engine device --algo convex --sketch-dim 32
PYTHONPATH=src python -m repro.launch.train --reduced --clients 4 \
    --clusters 2 --local-steps 3 --batch 2 --seq-len 16 \
    --method ifca --rounds 2 --warmup-steps 3 --sketch-dim 32 \
    --ifca-carry-opt

# sketch-routed serving: train a reduced federation to a checkpoint,
# then serve the cluster model the client's sketch routes to (the
# AggregationSession rebuilt from the stacked checkpoint)
SMOKE_CKPT="$(mktemp -d)"
trap 'rm -rf "$SMOKE_CKPT"' EXIT
PYTHONPATH=src python -m repro.launch.train --reduced --clients 4 \
    --clusters 2 --local-steps 4 --post-steps 0 --batch 2 --seq-len 16 \
    --method odcl --engine device --sketch-dim 32 --ckpt-dir "$SMOKE_CKPT"
PYTHONPATH=src python -m repro.launch.serve --reduced --batch 2 \
    --prompt-len 8 --gen 4 --ckpt-dir "$SMOKE_CKPT" --route-by-sketch \
    --clusters 2 --client 3 --route-sketch-dim 32

# concurrent serving gate: tiny closed-loop load generation through the
# RouteServer (cross-caller batching, bounded queue, request timeouts)
# with a floor on sustained route throughput.  No --require-criterion:
# at 2 callers there is not enough concurrency for batching to win; the
# full-size criterion lives in the committed BENCH_serving.json and is
# validated by the check_bench_regression gate at the bottom.
PYTHONPATH=src python -m repro.serving.loadgen \
    --clients 256 --clusters 4 --sketch-dim 32 --callers 2 --duration 2 \
    --max-batch 16 --no-ingest --floor-qps 50 \
    --out "$SMOKE_CKPT/BENCH_serving.json"

# reduced robustness bench: Byzantine x aggregator + DP-epsilon sweeps
# end-to-end, written to a throwaway path (the committed
# BENCH_robustness.json comes from the full-size run)
PYTHONPATH=src python -m benchmarks.bench_robustness --reduced \
    --out "$SMOKE_CKPT/BENCH_robustness.json"
python - "$SMOKE_CKPT/BENCH_robustness.json" <<'PY'
import json, sys
report = json.load(open(sys.argv[1]))
assert report["bench"] == "robustness" and report["rows"]
for row in report["rows"]:
    assert {"scenario", "aggregator", "purity"} <= set(row), sorted(row)
print(f"bench_robustness --reduced OK ({len(report['rows'])} rows)")
PY

# benchmark regression gate: BENCH_*.json schema validation + a re-run
# of the cheapest engine row compared against the committed baseline
PYTHONPATH=src python scripts/check_bench_regression.py --quick
