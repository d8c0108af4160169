"""Aggregation-engine scaling: per-algorithm C-sweep of the streaming
one-shot round.

For each (algorithm, edge set, federation size C) cell the full pipeline
of ``launch/simulate.py`` runs — wave-batched local ERMs streamed into
an ``AggregationSession`` (``ingest`` sketches each wave on device into
the fixed-capacity buffer), then ``finalize`` (registered clustering +
cluster mean, one jitted program) — and the per-phase wall clock plus
peak memory are recorded to ``BENCH_engine.json``: the perf trajectory
the next optimization PRs measure against.  The phases are disjoint:
``ingest_s`` is the streaming-upload dispatch inside the wave loop,
``local_erm_s`` the wave ERMs without it (comparable with pre-session
rows), ``aggregate_s`` the finalize round.

Schema_version 3 adds the mutable-serving columns to the kmeans rows:
the sweep re-runs each federation with keyed drifted re-uploads +
churned-in joiners (``reupload_frac`` / ``churn``), measures the
drift-triggered warm re-finalize (``refinalize_warm_p50_ms`` — the
number to compare against the cold ``finalize_p50_ms``) and the
one-program batched route (``route_batch_ms`` / ``batched_routes_per_s``
over the drifted probe batch), and records the eviction/live-slot
accounting.  The convex rows keep these columns null (the complete-graph
rows are too slow to re-run mutated, and the warm AMA dual only applies
at unchanged client count).

Each row also carries (since schema_version 2):

  * serving columns — ``route_p50_ms`` / ``route_p99_ms`` /
    ``routes_per_s`` from 256 fresh probe clients routed through the
    session, ``finalize_p50_ms`` / ``finalize_p99_ms`` from warm
    re-finalizes, and the session ``drift`` gauge.  The serving
    exercise runs OUTSIDE the phase timings, so ``total_s`` stays
    comparable with schema-1 rows.
  * ``kernels`` — achieved-vs-peak roofline rows
    (``roofline.engine_costs``): ``probes`` AOT-times the
    per-iteration kernel at the row's shapes.
  * ``device_peak_bytes`` — the TPU allocator's peak
    (``memory_stats()["peak_bytes_in_use"]``), ``None`` off the TPU;
    ``peak_rss_bytes`` is the host process's peak RSS.  Both are
    process-wide high-water marks, so later rows upper-bound earlier
    peaks rather than resetting per row.

The kmeans family sweeps to C=16k flat, then rides the two-level
hierarchical round (``shards=`` -> ``engine/hierarchy.py``) to
C=100k-1M.  The convex family's complete fusion graph is E = C(C-1)/2
edges (the AMA state is O(E * sketch_dim)), which walls at C=4k — the
``edges=knn`` rows swap in the sparse mutual-kNN graph (E = C*k via
the tiled top-k over the ``pairwise_l2`` kernel) and carry the family
to C=16k, and the ``edges=knn-approx`` row replaces even that build's
O(C^2) distance sweep with the projection-LSH candidate stage.

Schema_version 4 adds the scale columns: ``shards`` (1 = the flat
session) and ``comm_level_bytes`` (per-level upload bytes of the
hierarchical round, null for flat rows) on every row, and
``edge_build_s`` on the convex rows — the standalone warm wall-clock
of the registered edge builder at the row's (C, sketch_dim), the
number the ``knn`` vs ``knn-approx`` comparison reads.
"""
from __future__ import annotations

import json
import resource
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.engine.edges import get_edge_set
from repro.launch.simulate import simulate
from repro.roofline.engine_costs import (
    detect_hardware,
    engine_kernel_report,
    hardware_info,
)

CLUSTERS = 8
OUT = "BENCH_engine.json"
SCHEMA_VERSION = 4
# (algorithm, C grid, simulate overrides).  The kmeans rows carry the
# mutation knobs, so each row ALSO measures the mutable-serving path
# (keyed drifted re-uploads + churn, warm re-finalize, batched route)
# after the scored run; the row key (algorithm, edges, C, shards) is
# unchanged.
SWEEPS = (
    ("kmeans-device", (256, 1024, 4096, 16384),
     {"finalize_repeats": 5, "route_probes": 256,
      "reupload_frac": 0.25, "churn": 64, "refinalize_threshold": 1.5}),
    # two-level hierarchical rounds: the million-client path (S shards
    # of the fused round, then the S*k shard centers at the top level)
    ("kmeans-device", (102400,),
     {"shards": 8, "wave": 8192, "route_probes": 256}),
    ("kmeans-device", (1048576,),
     {"shards": 32, "wave": 8192, "route_probes": 256}),
    ("convex-device", (256, 1024),
     {"sketch_dim": 32, "cc_iters": 200,
      "finalize_repeats": 3, "route_probes": 256}),
    # the complete-graph wall row: one finalize is already ~15 min, so
    # its finalize histogram is the single (compile-heavy) run
    ("convex-device", (4096,),
     {"sketch_dim": 32, "cc_iters": 200,
      "finalize_repeats": 1, "route_probes": 256}),
    # sparse kNN fusion graph: past the complete-graph C=4k edge wall
    ("convex-device", (4096, 16384),
     {"sketch_dim": 32, "cc_iters": 200, "edges": "knn", "knn_k": 8,
      "finalize_repeats": 2, "route_probes": 256}),
    # approximate kNN: the LSH candidate stage drops the edge build's
    # O(C^2) distance sweep (compare edge_build_s with the knn row)
    ("convex-device", (16384,),
     {"sketch_dim": 32, "cc_iters": 200, "edges": "knn-approx", "knn_k": 8,
      "finalize_repeats": 2, "route_probes": 256}),
)


def edge_build_seconds(c: int, sketch_dim: int, edges: str, knn_k: int,
                       repeats: int = 3) -> float:
    """Standalone warm wall-clock of the registered edge builder at the
    row's shapes — isolates the fusion-graph build from the AMA solve so
    the exact-vs-approximate kNN comparison is apples to apples."""
    pts = jax.random.normal(jax.random.PRNGKey(0), (c, sketch_dim),
                            jnp.float32)
    builder = get_edge_set(edges)
    fn = jax.jit(lambda p: builder(p, knn_k=knn_k))
    jax.block_until_ready(fn(pts))                  # compile + warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(pts))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _peak_bytes() -> dict:
    """The device allocator's peak on the TPU, where ``memory_stats()``
    must answer; ``None`` elsewhere.  The process's peak RSS is recorded
    beside it under its own name."""
    dev = jax.local_devices()[0]
    peak = None
    if dev.platform == "tpu":
        peak = int(dev.memory_stats()["peak_bytes_in_use"])
    return {"device_peak_bytes": peak,
            "peak_rss_bytes":
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}


def run(sweeps=SWEEPS, out: str = OUT):
    hw = detect_hardware() if jax.devices()[0].platform == "tpu" else None
    rows = []
    for algorithm, c_grid, overrides in sweeps:
        tag = algorithm
        if overrides.get("edges", "complete") != "complete":
            tag = f"{algorithm}+{overrides['edges']}"
        if overrides.get("shards", 1) > 1:
            tag = f"{tag}@S{overrides['shards']}"
        for c in c_grid:
            summary = simulate(clients=c, clusters=CLUSTERS,
                               algorithm=algorithm,
                               **{"wave": 4096, **overrides})
            summary.pop("obs")
            serving = summary.pop("serving") or {}
            # hierarchical rows probe at the per-shard level-0 shapes —
            # that is the program the round actually compiles
            probe_c = -(-c // summary.get("shards", 1))
            probes = engine_kernel_report(
                probe_c, summary["sketch_dim"], CLUSTERS, algorithm,
                edges=summary.get("edges") or "complete",
                knn_k=summary.get("knn_k") or 8, hw=hw)
            edge_build_s = None
            if summary.get("edges") is not None:
                edge_build_s = edge_build_seconds(
                    c, summary["sketch_dim"], summary["edges"],
                    summary.get("knn_k") or 8)
            row = {**summary, **serving, **_peak_bytes(),
                   "edge_build_s": edge_build_s,
                   "kernels": {"probes": probes}}
            rows.append(row)
            ph = summary["phases"]
            emit(f"bench_engine/{tag}/C{c}", ph["aggregate_s"] * 1e6,
                 f"erm_s={ph['local_erm_s']:.2f};"
                 f"ingest_s={ph['ingest_s']:.2f};"
                 f"purity={summary['purity']:.3f};"
                 f"route_p50_ms={serving.get('route_p50_ms')};"
                 f"refinalize_warm_p50_ms={serving.get('refinalize_warm_p50_ms')};"
                 f"route_batch_ms={serving.get('route_batch_ms')};"
                 f"rss={row['peak_rss_bytes']}")
    report = {"bench": "engine_scale", "schema_version": SCHEMA_VERSION,
              "backend": jax.default_backend(), "clusters": CLUSTERS,
              "hw": hardware_info(hw), "rows": rows}
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    emit("bench_engine/report", 0.0, out)
    return report


def main():
    run()


if __name__ == "__main__":
    main()
