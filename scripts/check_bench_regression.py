#!/usr/bin/env python
"""Benchmark regression gate.

Two layers, both exiting non-zero on violation so CI/smoke can gate on
them:

  * schema validation (always): ``BENCH_engine.json`` must be
    schema_version 4 with the serving / mutable-serving / roofline /
    peak-memory columns present in every row (the mutation columns —
    warm re-finalize, batched route, evictions — are nullable: convex
    rows don't run the mutated sweep) plus the scale columns —
    ``shards`` / ``comm_level_bytes`` / ``edge_build_s``; the report
    must carry at least one hierarchical row (shards > 1, C >= 100k,
    purity >= 0.99, per-level comm bytes) and the C=16384
    ``knn-approx`` convex row must match the exact ``knn`` row's
    purity within slack while beating its edge-build wall-clock;
    ``BENCH_robustness.json`` must be schema_version 1 with the
    robustness row keys; ``BENCH_serving.json`` must be schema_version
    1 with the loadgen row keys, >= 2 closed-loop concurrency points,
    and a passing batched-beats-direct criterion at every point the
    loadgen marked ``pass``.
  * ``--quick``: re-run the cheapest engine row (kmeans-device, C=256)
    through the real ``bench_engine_scale`` path into a temp file and
    compare it against the committed baseline row under per-metric
    tolerances — exact for protocol invariants (comm bytes, recovered
    K'), a small slack for quality (purity), and generous multipliers
    for wall clock / memory (CI containers are noisy; the gate exists
    to catch order-of-magnitude regressions and schema drift, not 10%
    jitter).

Run from anywhere:  python scripts/check_bench_regression.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

ENGINE_JSON = os.path.join(ROOT, "BENCH_engine.json")
ROBUSTNESS_JSON = os.path.join(ROOT, "BENCH_robustness.json")
SERVING_JSON = os.path.join(ROOT, "BENCH_serving.json")

ENGINE_SCHEMA_VERSION = 4
ROBUSTNESS_SCHEMA_VERSION = 1
SERVING_SCHEMA_VERSION = 1

ENGINE_ROW_KEYS = {
    "clients", "algorithm", "phases", "purity", "n_clusters_recovered",
    "comm_bytes", "device_peak_bytes", "peak_rss_bytes",
    "route_probes", "route_p50_ms", "route_p99_ms", "routes_per_s",
    "finalize_repeats", "finalize_p50_ms", "finalize_p99_ms", "kernels",
    # schema 3: mutable-serving columns (nullable on non-mutated rows)
    "reupload_frac", "churn", "live_clients", "evictions",
    "drift_after_mutation", "refinalize_threshold", "refinalize_fired",
    "refinalize_warm_p50_ms", "route_batch_ms", "batched_routes_per_s",
    # schema 4: hierarchical / approximate-edge scale columns
    # (comm_level_bytes is null on flat rows, edge_build_s on non-convex)
    "shards", "comm_level_bytes", "edge_build_s",
}

HIER_MIN_CLIENTS = 100_000
HIER_MIN_PURITY = 0.99
ROBUSTNESS_ROW_KEYS = {"sweep", "scenario", "aggregator", "purity"}

SERVING_ROW_KEYS = {
    "mode", "batched", "callers", "rate", "max_batch", "max_wait_ms",
    "queue_depth", "ingest_waves", "backpressure", "flush_size_p50",
    "flush_size_p95", "flush_size_max", "queue_depth_p95",
    "staleness_at_serve_p95", "refinalize_under_load_ms", "drops",
    "n_requests", "n_errors", "timeouts", "qps", "route_p50_ms",
    "route_p99_ms", "duration_s", "clients", "clusters", "sketch_dim",
}
SERVING_MIN_CLOSED_POINTS = 2

# --quick tolerances vs the committed baseline row
PURITY_SLACK = 0.02          # absolute purity drop allowed
TIME_MULT, TIME_SLACK_S = 2.5, 2.0
MEM_MULT, MEM_SLACK_B = 4.0, 2 << 30
ROUTE_MULT, ROUTE_SLACK_MS = 4.0, 10.0


def _load(path: str) -> dict:
    if not os.path.exists(path):
        print(f"[bench-gate] FAIL: missing {path}")
        raise SystemExit(1)
    with open(path) as f:
        return json.load(f)


def _check(failures: list, ok: bool, msg: str) -> None:
    print(f"[bench-gate] {'ok  ' if ok else 'FAIL'} {msg}")
    if not ok:
        failures.append(msg)


def validate_engine(report: dict, failures: list) -> None:
    _check(failures,
           report.get("schema_version") == ENGINE_SCHEMA_VERSION,
           f"engine schema_version == {ENGINE_SCHEMA_VERSION} "
           f"(got {report.get('schema_version')})")
    rows = report.get("rows") or []
    _check(failures, bool(rows), "engine report has rows")
    for i, row in enumerate(rows):
        missing = ENGINE_ROW_KEYS - set(row)
        _check(failures, not missing,
               f"engine row {i} ({row.get('algorithm')}/C{row.get('clients')})"
               f" has required keys" + (f"; missing {sorted(missing)}"
                                        if missing else ""))
        if missing:
            continue
        if report.get("backend") == "tpu":
            _check(failures, row["device_peak_bytes"] is not None
                   and row["device_peak_bytes"] > 0,
                   f"engine row {i} device_peak_bytes non-null on the TPU "
                   f"({row['device_peak_bytes']})")
    _validate_hierarchical(rows, failures)
    _validate_knn_approx(rows, failures)


def _validate_hierarchical(rows: list, failures: list) -> None:
    """Schema 4: the report must prove the million-client path — at
    least one two-level row at C >= 100k recovering the planted
    clusters, with the per-level comm accounting filled in."""
    hier = [r for r in rows
            if r.get("shards", 1) > 1 and r["clients"] >= HIER_MIN_CLIENTS]
    _check(failures, bool(hier),
           f"engine report has a hierarchical row (shards > 1, "
           f"C >= {HIER_MIN_CLIENTS})")
    for row in hier:
        tag = (f"{row['algorithm']}@S{row['shards']}/C{row['clients']}")
        _check(failures, row["purity"] >= HIER_MIN_PURITY,
               f"hierarchical row {tag} purity {row['purity']:.4f} >= "
               f"{HIER_MIN_PURITY}")
        clb = row.get("comm_level_bytes") or {}
        ok = (clb.get("level0") and clb.get("level1")
              and clb["level1"] < clb["level0"])
        _check(failures, bool(ok),
               f"hierarchical row {tag} comm_level_bytes present with "
               f"level1 < level0 (got {clb})")


def _validate_knn_approx(rows: list, failures: list) -> None:
    """Schema 4: the C=16384 knn-approx convex row must match the exact
    knn row's purity (within the quick-check slack) while beating its
    standalone edge-build wall-clock."""
    def find(edges):
        for r in rows:
            if (r["algorithm"].startswith("convex")
                    and r.get("edges") == edges and r["clients"] == 16384):
                return r
        return None
    exact, approx = find("knn"), find("knn-approx")
    _check(failures, approx is not None,
           "engine report has the convex knn-approx C=16384 row")
    if approx is None or exact is None:
        if exact is None:
            _check(failures, False,
                   "engine report has the convex knn C=16384 row")
        return
    _check(failures, approx["purity"] >= exact["purity"] - PURITY_SLACK,
           f"knn-approx purity {approx['purity']:.3f} >= knn "
           f"{exact['purity']:.3f} - {PURITY_SLACK}")
    eb_exact, eb_approx = exact.get("edge_build_s"), approx.get("edge_build_s")
    _check(failures,
           eb_exact is not None and eb_approx is not None
           and eb_approx < eb_exact,
           f"knn-approx edge_build_s {eb_approx} < knn {eb_exact}")


def validate_robustness(report: dict, failures: list) -> None:
    _check(failures,
           report.get("schema_version") == ROBUSTNESS_SCHEMA_VERSION,
           f"robustness schema_version == {ROBUSTNESS_SCHEMA_VERSION} "
           f"(got {report.get('schema_version')})")
    rows = report.get("rows") or []
    _check(failures, bool(rows), "robustness report has rows")
    for i, row in enumerate(rows):
        missing = ROBUSTNESS_ROW_KEYS - set(row)
        _check(failures, not missing,
               f"robustness row {i} has required keys"
               + (f"; missing {sorted(missing)}" if missing else ""))


def validate_serving(report: dict, failures: list) -> None:
    """Schema 1 of the RouteServer loadgen report: full row schema,
    >= 2 closed-loop concurrency points whose batched rows beat their
    per-request twins, an ingest-while-serving row proving the
    double-buffered refinalize ran under route traffic, and zero
    dropped requests anywhere."""
    _check(failures,
           report.get("schema_version") == SERVING_SCHEMA_VERSION,
           f"serving schema_version == {SERVING_SCHEMA_VERSION} "
           f"(got {report.get('schema_version')})")
    rows = report.get("rows") or []
    _check(failures, bool(rows), "serving report has rows")
    for i, row in enumerate(rows):
        missing = SERVING_ROW_KEYS - set(row)
        _check(failures, not missing,
               f"serving row {i} ({row.get('mode')}/"
               f"batched={row.get('batched')}/callers={row.get('callers')})"
               f" has required keys" + (f"; missing {sorted(missing)}"
                                        if missing else ""))
        if not missing:
            _check(failures, row["drops"] == 0 and row["n_errors"] == 0,
                   f"serving row {i} drops == 0 and n_errors == 0 "
                   f"(got {row['drops']}/{row['n_errors']})")
    crit = report.get("criterion") or {}
    _check(failures, len(crit) >= SERVING_MIN_CLOSED_POINTS,
           f"serving criterion has >= {SERVING_MIN_CLOSED_POINTS} "
           f"closed-loop concurrency points (got {len(crit)})")
    for point, c in crit.items():
        _check(failures, bool(c.get("pass")),
               f"serving criterion {point}: batched "
               f"{c.get('batched_qps', 0):.0f}/s beats per-request "
               f"{c.get('direct_qps', 0):.0f}/s")
    under = [r for r in rows if r.get("ingest_waves")]
    ok = bool(under) and all(r["refinalize_under_load_ms"] is not None
                             for r in under)
    _check(failures, ok,
           "serving report has an ingest-while-serving row with a "
           "measured refinalize_under_load_ms")


def _row_key(row: dict):
    return (row["algorithm"], row.get("edges") or "complete",
            row["clients"], row.get("shards", 1))


def quick_check(baseline: dict, failures: list) -> None:
    """Re-run the C=256 kmeans-device row and compare against baseline."""
    from benchmarks.bench_engine_scale import run

    sweeps = (("kmeans-device", (256,),
               {"finalize_repeats": 5, "route_probes": 256,
                "reupload_frac": 0.25, "churn": 64,
                "refinalize_threshold": 1.5}),)
    with tempfile.TemporaryDirectory() as td:
        report = run(sweeps=sweeps, out=os.path.join(td, "quick.json"))
    row = report["rows"][0]
    base_rows = {_row_key(r): r for r in baseline.get("rows", [])}
    base = base_rows.get(_row_key(row))
    if base is None:
        _check(failures, False,
               f"baseline row {_row_key(row)} present in BENCH_engine.json")
        return

    _check(failures, row["purity"] >= base["purity"] - PURITY_SLACK,
           f"purity {row['purity']:.3f} >= "
           f"{base['purity']:.3f} - {PURITY_SLACK}")
    _check(failures,
           row["n_clusters_recovered"] == base["n_clusters_recovered"],
           f"n_clusters_recovered {row['n_clusters_recovered']} == "
           f"{base['n_clusters_recovered']}")
    _check(failures, row["comm_bytes"] == base["comm_bytes"],
           f"comm_bytes {row['comm_bytes']:g} == {base['comm_bytes']:g}")
    for phase in ("aggregate_s", "total_s"):
        cap = base["phases"][phase] * TIME_MULT + TIME_SLACK_S
        _check(failures, row["phases"][phase] <= cap,
               f"{phase} {row['phases'][phase]:.2f}s <= {cap:.2f}s "
               f"(baseline {base['phases'][phase]:.2f}s)")
    if base.get("device_peak_bytes") and row["device_peak_bytes"]:
        cap = base["device_peak_bytes"] * MEM_MULT + MEM_SLACK_B
        _check(failures, row["device_peak_bytes"] <= cap,
               f"device_peak_bytes {row['device_peak_bytes']} <= {cap:.0f}")
    if base.get("route_p50_ms"):
        cap = base["route_p50_ms"] * ROUTE_MULT + ROUTE_SLACK_MS
        _check(failures, row["route_p50_ms"] <= cap,
               f"route_p50_ms {row['route_p50_ms']:.3f} <= {cap:.3f}")
    if base.get("refinalize_warm_p50_ms"):
        cap = base["refinalize_warm_p50_ms"] * ROUTE_MULT + ROUTE_SLACK_MS
        _check(failures,
               row.get("refinalize_warm_p50_ms") is not None
               and row["refinalize_warm_p50_ms"] <= cap,
               f"refinalize_warm_p50_ms {row.get('refinalize_warm_p50_ms')} "
               f"<= {cap:.3f}")
    if base.get("route_batch_ms"):
        cap = base["route_batch_ms"] * ROUTE_MULT + ROUTE_SLACK_MS
        _check(failures,
               row.get("route_batch_ms") is not None
               and row["route_batch_ms"] <= cap,
               f"route_batch_ms {row.get('route_batch_ms')} <= {cap:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="re-run the kmeans-device C=256 row and compare "
                         "against the committed baseline")
    ap.add_argument("--validate-only", action="store_true",
                    help="schema validation only (explicit alias of the "
                         "no-flag default)")
    ap.add_argument("--engine-json", default=ENGINE_JSON)
    ap.add_argument("--robustness-json", default=ROBUSTNESS_JSON)
    ap.add_argument("--serving-json", default=SERVING_JSON)
    args = ap.parse_args(argv)

    failures: list = []
    engine = _load(args.engine_json)
    validate_engine(engine, failures)
    validate_robustness(_load(args.robustness_json), failures)
    validate_serving(_load(args.serving_json), failures)
    if args.quick and not args.validate_only:
        quick_check(engine, failures)

    if failures:
        print(f"[bench-gate] {len(failures)} check(s) failed")
        return 1
    print("[bench-gate] all checks passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
