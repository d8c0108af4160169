"""Large-C client simulation driving the streaming aggregation session.

Where ``launch/train.py`` runs the paper's protocol on a handful of
deep-model clients (heavy step 1, C ~ 10), this driver targets the
opposite regime the one-shot guarantee is actually about: C = 10k-100k
*shallow* clients (the paper's ridge / logistic settings, Section 5 /
Appendix E.2), IFCA- and k-FED-scale federations.

Clients are synthesized and solved in batched vmap **waves** — each
wave draws ``wave`` clients' covariates, responses, and closed-form /
Newton local ERMs in one jitted call, then feeds the wave straight into
``engine.session.AggregationSession.ingest`` (the step-1 upload): the
session sketches the wave on device and accumulates the (C, sketch_dim)
matrix in its fixed-capacity buffer, so peak memory is bounded by the
wave and nothing federation-sized crosses to host.  The one-shot server
round is then ``session.finalize()`` — the registered clustering +
cluster mean over the streamed-in sketches, bit-exact with the fused
``one_shot_aggregate(engine="device")`` round.  Iterative baselines
(``--method ifca|fedavg``) run over ``session.state()``, the same
streamed-in federation as a stacked ``FederatedState``.

  PYTHONPATH=src python -m repro.launch.simulate --clients 4096 --clusters 8

  # the convex family past the complete-graph wall: sparse kNN edges
  PYTHONPATH=src python -m repro.launch.simulate --clients 16384 \
      --algorithm convex-device --edges knn --knn-k 8 --sketch-dim 32
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, runtime
from repro.core.clustering import (
    device_twin,
    get_algorithm,
    is_device_algorithm,
    lambda_interval,
    list_algorithms,
)
from repro.core.engine import list_edge_sets, make_staleness_policy
from repro.core.engine.aggregators import list_aggregators, make_aggregator
from repro.core.engine.hierarchy import HierarchicalSession
from repro.core.engine.session import AggregationSession
from repro.core.erm import batched_ridge_erm, logistic_erm
from repro.core.federated_methods import (
    build_federated_method,
    cluster_agreement,
    list_federated_methods,
    params_bytes_per_client,
    sketch_round_bytes,
)
from repro.scenarios import build_scenario, list_scenarios


def staggered_optima(key, K: int, d: int):
    """Well-separated cluster optima in the style of Appendix E.1:
    cluster k draws coordinate magnitudes from U([k + 1, k + 2]) with an
    independent random sign per coordinate.  Staggered magnitudes keep
    min pairwise separation >= sqrt(d); the random signs scatter the
    optima across orthants (collinear centers are a Lloyd's-algorithm
    pathology, not the paper's setting)."""
    ks, ku = jax.random.split(key)
    signs = jax.random.rademacher(ks, (K, d), jnp.float32)
    base = jnp.arange(1.0, K + 1.0, dtype=jnp.float32)[:, None]
    return signs * (base + jax.random.uniform(ku, (K, d)))


@functools.partial(jax.jit, static_argnames=("wave", "n", "d", "task",
                                             "newton_iters"))
def _wave_erm(key, optima, labels, *, wave: int, n: int, d: int,
              task: str = "ridge", noise: float = 1.0, reg: float = 1e-6,
              newton_iters: int = 8):
    """One vmap wave of step 1: draw ``wave`` clients' data from their
    cluster's population model and solve every local ERM. Returns the
    (wave, d[+1]) stack of local models, device-resident."""
    kx, ke = jax.random.split(key)
    x = jax.random.normal(kx, (wave, n, d), jnp.float32)
    z = jnp.einsum("wnd,wd->wn", x, optima[labels])
    if task == "ridge":
        y = z + noise * jax.random.normal(ke, (wave, n), jnp.float32)
        return batched_ridge_erm(x, y, reg)                    # (wave, d)
    if task == "logistic":
        y = 2.0 * (jax.random.uniform(ke, (wave, n)) <
                   jax.nn.sigmoid(z)).astype(jnp.float32) - 1.0
        return jax.vmap(
            lambda xx, yy: logistic_erm(xx, yy, reg, newton_iters)
        )(x, y)                                                # (wave, d+1)
    raise ValueError(f"unknown task {task!r}")  # pragma: no cover - static


def simulate(*, clients: int, clusters: int, dim: int = 16, samples: int = 64,
             wave: int = 4096, task: str = "ridge", sketch_dim: int = 64,
             shards: int = 1,
             algorithm: str = "kmeans-device", init: str = "kmeans++",
             kmeans_iters: int = 50, restarts: int = 1, cc_iters: int = 300,
             edges: str = "complete", knn_k: int = 8,
             scenario=None, scenario_options: dict | None = None,
             aggregator: str = "mean", trim_beta: float = 0.1,
             seed: int = 0, method: str = "odcl", rounds: int = 5,
             trace: str | None = None, route_probes: int = 0,
             finalize_repeats: int = 1,
             reupload_frac: float = 0.0, churn: int = 0,
             max_age: int | None = None,
             refinalize_threshold: float | None = None,
             mutation_rounds: int = 3, drift_scale: float = 2.0,
             qps_callers: int = 0, qps_duration: float = 2.0,
             mesh=None) -> dict:
    """Generate a K-cluster federation of ``clients`` users, stream the
    wave-solved local ERMs into an ``AggregationSession``, run the
    requested federated method over it (default: the session's own
    streaming one-shot round), and return a summary dict (per-phase wall
    clock, recovered clustering quality).  Iterative methods run with
    zero per-round local steps — the shallow clients are already at
    their local ERMs — so IFCA here is pure sketch-assign/re-average
    rounds over ``session.state()``.

    ``algorithm`` selects the admissible clustering family: the Lloyd
    device loop by default (``init``/``kmeans_iters``/``restarts``
    apply), or the convex family — ``convex``/``convex-device`` runs
    the paper's E.1 exact-lambda ODCL-CC (the recovery bounds (17) on
    the true clustering are a host-side driver setup pass over the
    local models; the aggregation round itself stays one jitted device
    program), ``clusterpath``/``clusterpath-device`` the K-free ladder.
    ``edges``/``knn_k`` select the convex family's fusion graph
    (``knn`` breaks the complete graph's C=4k edge wall).

    ``scenario`` runs the federation through an adversity scenario
    (``repro.scenarios``): its population/drift hooks reshape the
    effective cluster labels (which become the scored truth), its
    ``corrupt_uploads`` hook attacks the wave ERMs before upload, and
    its sketch-channel hooks (DP release, colluding spoof) run inside
    the session's jitted ingest.  ``aggregator`` selects the robust
    step-3 reduction (``trim_beta`` specializes ``trimmed_mean``); a
    non-mean aggregator also drives the device Lloyd center update, so
    Byzantine rows stop dragging the recovered partition.

    ``trace`` attaches a JSONL event sink for the run (every obs span /
    event lands there).  ``route_probes``/``finalize_repeats`` exercise
    the serving path AFTER the scored run — fresh probe clients routed
    through ``session.route`` and warm finalize re-runs — so the
    summary's ``serving`` section gets real route/finalize latency
    histograms without touching the phase timings.

    The mutation knobs drive the drifted-population serving loop, also
    after the scored run: ``reupload_frac`` re-uploads that fraction of
    clients per mutation round with local ERMs re-solved against a
    SHIFTED set of cluster optima (in-place keyed replacement),
    ``churn`` joins that many fresh clients per round (``max_age``
    arms the sliding-window staleness policy so silent clients age
    out), drifted probes push the ``drift`` gauge, and
    ``refinalize_threshold`` arms ``session.maybe_refinalize`` — the
    summary's ``serving`` section then reports the drift value, the
    eviction count, warm re-finalize p50 vs the cold finalize column,
    and the batched-``route()`` throughput.
    """
    obs.reset()                       # per-run aggregates; sinks survive
    trace_sink = None
    if trace is not None:
        trace_sink = obs.JsonlSink(trace)
        obs.add_sink(trace_sink)
    key = jax.random.PRNGKey(seed)
    k_opt, k_data = jax.random.split(key)
    optima = staggered_optima(k_opt, clusters, dim)

    scen = (build_scenario(scenario, **(scenario_options or {}))
            if scenario is not None else None)
    scen_key = jax.random.fold_in(key, 0x5ce0)
    if scen is not None:
        base_labels = jnp.asarray(scen.population(scen_key, clients, clusters),
                                  jnp.int32)
        # drift hooks are per-global-index deterministic, so applying
        # them to the full index range once equals the per-wave calls
        true_labels = jnp.asarray(scen.wave_labels(
            scen_key, base_labels, 0, clients, clusters), jnp.int32)
        honest = np.asarray(scen.honest_mask(scen_key, clients), bool)
    else:
        true_labels = jnp.arange(clients, dtype=jnp.int32) % clusters
        honest = np.ones(clients, bool)

    agg = make_aggregator(aggregator, beta=trim_beta)
    sketch_hook = (
        (lambda sk, off: scen.sketch_transform(scen_key, sk, off))
        if scen is not None and scen.transforms_sketches else None)

    # mutation mode: keyed slots (stable int client ids), headroom for
    # the churned-in joiners, and the sliding-window staleness policy
    mutated = (reupload_frac > 0 or churn > 0 or max_age is not None
               or refinalize_threshold is not None)
    if shards > 1:
        # the hierarchical server is anonymous-only and one-shot-only:
        # keyed mutation and the iterative baselines both need the flat
        # session's single buffer
        if mutated:
            raise ValueError("--shards > 1 is incompatible with the "
                             "mutation knobs (--reupload-frac/--churn/"
                             "--max-age/--refinalize-threshold): keyed "
                             "slots need the flat session")
        if method != "odcl":
            raise ValueError(f"--shards > 1 only runs the one-shot round "
                             f"(method='odcl'), got method={method!r}")
    capacity = clients + (churn * mutation_rounds if mutated else 0)
    # the staleness window opens at the mutation loop (below), so the
    # initial federation — streamed in over clients/wave ingest waves —
    # counts as one snapshot rather than aging itself out
    if shards > 1:
        session = HierarchicalSession(capacity, shards=shards,
                                      sketch_dim=sketch_dim, seed=seed,
                                      sketch_transform=sketch_hook, mesh=mesh)
    else:
        session = AggregationSession(capacity, sketch_dim=sketch_dim,
                                     seed=seed, sketch_transform=sketch_hook,
                                     mesh=mesh)
    t0 = time.perf_counter()
    t_ingest = 0.0
    for start in range(0, clients, wave):
        w = min(wave, clients - start)
        lab_w = jax.lax.dynamic_slice_in_dim(true_labels, start, w)
        theta_w = _wave_erm(
            jax.random.fold_in(k_data, start), optima, lab_w,
            wave=w, n=samples, d=dim, task=task)
        if scen is not None:
            # step-1 attack: Byzantine clients replace their upload
            theta_w = scen.corrupt_uploads(scen_key, theta_w, lab_w,
                                           start, clients)
        ti = time.perf_counter()
        ids = range(start, start + w) if mutated else None
        session.ingest({"theta": theta_w},     # step-1 upload of the wave
                       client_ids=ids)
        t_ingest += time.perf_counter() - ti
    jax.block_until_ready(session.sketches)
    # disjoint phases: local_erm_s excludes the ingest dispatch measured
    # inside the same loop, so the columns stay comparable with the
    # pre-session BENCH_engine.json rows and sum to the loop wall clock
    t_erm = time.perf_counter() - t0 - t_ingest

    convex_family = algorithm.startswith(("convex", "clusterpath"))
    if algorithm.startswith("convex"):
        # paper E.1 exact-lambda selection: recovery bounds (17) on the
        # true clustering (the JL sketch is near-isometric, so the
        # theta-space midpoint lands inside the sketch-space interval)
        thetas = session.state().params["theta"]
        lo, hi = lambda_interval(np.asarray(thetas), np.asarray(true_labels))
        lam = 0.5 * (lo + hi) if lo < hi else lo
        algo_options = {"lam": lam, "iters": cc_iters}
    elif algorithm.startswith("clusterpath"):
        algo_options = {"iters": cc_iters}
    else:
        algo_options = {"init": init, "iters": kmeans_iters,
                        "restarts": restarts}
        if agg.name != "mean":
            # robust Lloyd: the same aggregator replaces the center
            # update inside device_kmeans — sign-flip sketch rows stop
            # dragging the centers, which is what keeps purity under
            # Byzantine fractions (post-hoc robust averaging alone
            # cannot fix an already-poisoned partition)
            algo_options["aggregator"] = agg
    if convex_family:
        algo_options.update({"edges": edges, "knn_k": knn_k})
    elif edges != "complete":
        print(f"[warn] --edges {edges} only applies to the convex family; "
              f"ignored for --algorithm {algorithm}")

    t1 = time.perf_counter()
    if method == "odcl":
        # the streaming server round: registered clustering + cluster
        # mean over the session's accumulated sketch matrix (bit-exact
        # with one_shot_aggregate(engine="device") on the same clients)
        new_state, labels, info = session.finalize(
            algorithm=algorithm, k=clusters, algo_options=algo_options,
            engine="device", aggregator=agg)
        jax.block_until_ready(new_state.params)
        comm_rounds = 1.0
        comm_bytes = sketch_round_bytes(
            clients, sketch_dim, params_bytes_per_client(new_state))
        n_clusters = info["n_clusters"]
        meta = {"engine": info["engine"], **info["meta"]}
        comm_level_bytes = info.get("comm_level_bytes")
    else:
        # iterative methods loop sketch-space rounds over the streamed-in
        # federation (C=10k+ states stay wholly on device)
        fed_method = build_federated_method(
            method, algorithm=algorithm, engine="device", k=clusters,
            algo_options=algo_options, aggregator=agg,
            sketch_dim=sketch_dim, seed=seed, local_steps=0, rounds=rounds,
            assign="sketch", init="clients")
        res = fed_method.run(jax.random.PRNGKey(seed), session.state(),
                             None, None, mesh=mesh)
        jax.block_until_ready(res.state.params)
        new_state = res.state
        labels = res.labels
        comm_rounds, comm_bytes = res.comm_rounds, res.comm_bytes
        n_clusters, meta = res.n_clusters, res.meta
        comm_level_bytes = None
    t_agg = time.perf_counter() - t1

    truth = np.asarray(true_labels)
    labels_np = np.asarray(labels)
    purity_all = cluster_agreement(labels_np, truth)
    # the score that matters under attack: agreement on the honest
    # clients only (attackers have no "right" cluster)
    purity = (cluster_agreement(labels_np[honest], truth[honest])
              if honest.any() else purity_all)
    mse = None
    if task == "ridge":
        # personalization error of the served models on honest clients:
        # per-coordinate MSE against each client's population optimum
        served = np.asarray(new_state.params["theta"])
        target = np.asarray(optima)[truth]
        mse = float(np.mean((served[honest] - target[honest]) ** 2))

    # serving exercise: deliberately OUTSIDE the phase timings (total_s
    # stays comparable with pre-serving bench rows); the latencies land
    # in the session.route.ms / session.finalize.ms histograms
    serving = None
    if method == "odcl" and (mutated or route_probes > 0
                             or finalize_repeats > 1):
        for _ in range(max(0, finalize_repeats - 1)):
            session.finalize(algorithm=algorithm, k=clusters,
                             algo_options=algo_options, engine="device",
                             aggregator=agg)
        routes_per_s = None
        if route_probes > 0:
            # fresh never-seen clients from the same population — the
            # paper's serving-time arrivals
            probe_labels = jnp.arange(route_probes, dtype=jnp.int32) % clusters
            theta_p = _wave_erm(
                jax.random.fold_in(k_data, 0x9e3779b9), optima, probe_labels,
                wave=route_probes, n=samples, d=dim, task=task)
            jax.block_until_ready(theta_p)
            session.route(params={"theta": theta_p[0]})        # warmup
            tr = time.perf_counter()
            for i in range(route_probes):
                session.route(params={"theta": theta_p[i]})
            routes_per_s = route_probes / (time.perf_counter() - tr)

        # drifted-population mutation loop: keyed re-uploads + churn-in
        # joiners against SHIFTED optima, then drifted probes to push
        # the drift gauge, then the drift-triggered warm re-finalize
        drift_after = None
        refinalize_fired = None
        route_batch_ms = None
        batched_routes_per_s = None
        if mutated:
            if max_age is not None:
                session.staleness = make_staleness_policy(
                    f"max_age={max_age}")
            k_mut = jax.random.fold_in(key, 0xd21f7)
            shifted = optima + drift_scale * jax.random.normal(
                k_mut, optima.shape, jnp.float32)
            n_re = int(round(reupload_frac * clients))
            for r in range(mutation_rounds):
                if n_re > 0:
                    sel = (np.arange(n_re) + r * n_re) % clients
                    lab_m = jnp.asarray(np.asarray(true_labels)[sel])
                    theta_m = _wave_erm(
                        jax.random.fold_in(k_mut, 100 + r), shifted, lab_m,
                        wave=n_re, n=samples, d=dim, task=task)
                    session.ingest({"theta": theta_m},
                                   client_ids=[int(i) for i in sel])
                if churn > 0:
                    lab_c = jnp.arange(churn, dtype=jnp.int32) % clusters
                    theta_c = _wave_erm(
                        jax.random.fold_in(k_mut, 200 + r), shifted, lab_c,
                        wave=churn, n=samples, d=dim, task=task)
                    session.ingest(
                        {"theta": theta_c},
                        client_ids=[("joiner", r, i) for i in range(churn)])
            # batched route() over drifted probes: one fused program per
            # request batch (the per-request loop above is the per-call
            # latency column; this is the throughput column)
            n_probe = min(max(route_probes, 256), 4096)
            lab_p = jnp.arange(n_probe, dtype=jnp.int32) % clusters
            theta_p2 = _wave_erm(
                jax.random.fold_in(k_mut, 300), shifted, lab_p,
                wave=n_probe, n=samples, d=dim, task=task)
            sk_p = session.sketch_params({"theta": theta_p2})
            jax.block_until_ready(sk_p)
            session.route(sk_p)                                # warmup
            reps = 10
            tb = time.perf_counter()
            for _ in range(reps):
                session.route(sk_p)
            batch_s = (time.perf_counter() - tb) / reps
            route_batch_ms = batch_s * 1e3
            batched_routes_per_s = n_probe / batch_s
            drift_after = session.drift
            if refinalize_threshold is not None:
                out = session.maybe_refinalize(
                    threshold=refinalize_threshold)
                refinalize_fired = out is not None
                # warm re-finalize repeats feed the refinalize histogram
                # (the warm-vs-cold p50 comparison column)
                for _ in range(max(0, finalize_repeats - 1)):
                    session.refinalize()
        snap = obs.snapshot()
        hists = snap["histograms"]
        h_route = hists.get("session.route.ms", {})
        h_fin = hists.get("session.finalize.ms", {})
        h_ref = hists.get("session.refinalize.ms", {})
        serving = {
            "route_probes": route_probes,
            "route_p50_ms": h_route.get("p50"),
            "route_p99_ms": h_route.get("p99"),
            "routes_per_s": routes_per_s,
            "finalize_repeats": finalize_repeats,
            "finalize_p50_ms": h_fin.get("p50"),
            "finalize_p99_ms": h_fin.get("p99"),
            "drift": getattr(session, "drift", None),
            # mutable-serving columns (None outside mutation mode)
            "reupload_frac": reupload_frac if mutated else None,
            "churn": churn if mutated else None,
            "max_age": max_age,
            "live_clients": session.count if mutated else None,
            "evictions": (int(snap["counters"].get("session.evictions", 0))
                          if mutated else None),
            "drift_after_mutation": drift_after,
            "refinalize_threshold": refinalize_threshold,
            "refinalize_fired": refinalize_fired,
            "refinalize_warm_p50_ms": h_ref.get("p50"),
            "route_batch_ms": route_batch_ms,
            "batched_routes_per_s": batched_routes_per_s,
        }

    # concurrent QPS serving: the RouteServer front-end over the same
    # finalized session — M closed-loop caller threads through the
    # cross-caller batcher vs the same callers on the per-request path
    qps_server = None
    if qps_callers > 0:
        if shards > 1 or method != "odcl":
            raise ValueError("--qps-callers needs the flat session's "
                             "one-shot round (shards=1, method='odcl')")
        from repro.serving.loadgen import closed_loop, warm_route_buckets
        from repro.serving.server import RouteServer
        n_probe = min(1024, clients)
        lab_q = jnp.arange(n_probe, dtype=jnp.int32) % clusters
        theta_q = _wave_erm(
            jax.random.fold_in(k_data, 0x9195), optima, lab_q,
            wave=n_probe, n=samples, d=dim, task=task)
        probes = np.asarray(session.sketch_params({"theta": theta_q}))
        warm_route_buckets(session, probes[0], 64)
        server = RouteServer(session, max_batch=64, max_wait_ms=0.5)
        server.start()
        try:
            direct = closed_loop(server, probes, callers=qps_callers,
                                 duration_s=qps_duration, batched=False)
            batched = closed_loop(server, probes, callers=qps_callers,
                                  duration_s=qps_duration, batched=True)
        finally:
            server.stop()
        qps_server = {
            "callers": int(qps_callers),
            "duration_s": float(qps_duration),
            "direct_qps": direct["qps"],
            "batched_qps": batched["qps"],
            "batched_p50_ms": batched["route_p50_ms"],
            "batched_p99_ms": batched["route_p99_ms"],
            "timeouts": batched["timeouts"] + direct["timeouts"],
            "errors": batched["n_errors"] + direct["n_errors"],
        }

    if trace_sink is not None:
        obs.remove_sink(trace_sink)
        trace_sink.close()

    return {
        "clients": clients, "clusters": clusters, "dim": dim,
        "samples": samples, "wave": wave, "task": task,
        "sketch_dim": sketch_dim, "seed": seed, "method": method,
        "algorithm": algorithm, "restarts": restarts, "shards": shards,
        "comm_level_bytes": comm_level_bytes,
        "edges": edges if convex_family else None,
        "knn_k": knn_k if (convex_family and edges.startswith("knn"))
                 else None,
        "scenario": getattr(scen, "name", None),
        "scenario_options": scenario_options or None,
        "aggregator": agg.name,
        "honest_frac": float(np.mean(honest)),
        "comm_rounds": comm_rounds, "comm_bytes": comm_bytes,
        "phases": {"local_erm_s": t_erm, "ingest_s": t_ingest,
                   "aggregate_s": t_agg,
                   "total_s": t_erm + t_ingest + t_agg},
        "n_clusters_recovered": n_clusters,
        "purity": purity,
        "purity_all": purity_all,
        "mse": mse,
        "meta": meta,
        "serving": serving,
        "qps_server": qps_server,
        "obs": obs.snapshot(),
    }


def _device_runnable_algorithms() -> list:
    """Registry names the device engine can actually run: device-capable
    algorithms, names with a registered '-device' twin, and the Lloyd
    host names the shared resolver maps onto kmeans-device inits."""
    lloyd = {"kmeans", "kmeans++", "spectral"}
    return [n for n in list_algorithms()
            if n in lloyd
            or is_device_algorithm(get_algorithm(n))
            or device_twin(get_algorithm(n)) is not None]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4096)
    ap.add_argument("--clusters", type=int, default=8)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--samples", type=int, default=64,
                    help="data points per client (n)")
    ap.add_argument("--wave", type=int, default=4096,
                    help="clients generated+solved+ingested per vmap wave")
    ap.add_argument("--task", choices=("ridge", "logistic"), default="ridge")
    ap.add_argument("--sketch-dim", type=int, default=64)
    ap.add_argument("--shards", type=int, default=1,
                    help="level-0 shards of the two-level hierarchical "
                         "round (1 = the flat bit-exact session; >1 "
                         "clusters per shard, then the S*k shard centers)")
    ap.add_argument("--algorithm", default="kmeans-device",
                    choices=_device_runnable_algorithms(),
                    help="admissible clustering family for the one-shot "
                         "round (device-runnable names only); convex/"
                         "clusterpath (and their -device twins) run the "
                         "K-free ODCL-CC path on device")
    ap.add_argument("--init", choices=("kmeans++", "spectral", "random"),
                    default="kmeans++")
    ap.add_argument("--kmeans-iters", type=int, default=50)
    ap.add_argument("--restarts", type=int, default=1,
                    help="multi-restart Lloyd: keep the best-inertia "
                         "clustering of this many vmapped inits")
    ap.add_argument("--cc-iters", type=int, default=300,
                    help="max AMA iterations for the convex family")
    ap.add_argument("--edges", default="complete",
                    choices=list(list_edge_sets()),
                    help="fusion graph for the convex family: 'complete' "
                         "(paper default, E=C(C-1)/2) or 'knn' (sparse "
                         "mutual-kNN, E=C*k — the C >> 4k edge set)")
    ap.add_argument("--knn-k", type=int, default=8,
                    help="neighbours per client for --edges knn")
    ap.add_argument("--scenario", default=None,
                    help="adversity scenario over the client population: "
                         f"one of {list(list_scenarios())} or a "
                         "'+'-composed spec (e.g. 'longtail+byzantine')")
    ap.add_argument("--byzantine-frac", type=float, default=None,
                    help="attacker fraction for --scenario byzantine")
    ap.add_argument("--byzantine-attack", default=None,
                    choices=("sign_flip", "noise", "spoof"),
                    help="attack mode for --scenario byzantine")
    ap.add_argument("--byzantine-scale", type=float, default=None,
                    help="noise/spoof magnitude for --scenario byzantine")
    ap.add_argument("--dp-epsilon", type=float, default=None,
                    help="privacy budget for --scenario dp")
    ap.add_argument("--dp-delta", type=float, default=None,
                    help="delta for --scenario dp")
    ap.add_argument("--dp-clip", type=float, default=None,
                    help="sketch L2 clip (sensitivity) for --scenario dp")
    ap.add_argument("--drift-frac", type=float, default=None,
                    help="migrating-client fraction for --scenario drift")
    ap.add_argument("--zipf-a", type=float, default=None,
                    help="Zipf exponent for --scenario longtail")
    ap.add_argument("--aggregator", default="mean",
                    choices=list(list_aggregators()),
                    help="per-cluster step-3 reduction (robust variants "
                         "also drive the device Lloyd center update)")
    ap.add_argument("--trim-beta", type=float, default=0.1,
                    help="trim fraction for --aggregator trimmed_mean")
    ap.add_argument("--method", default="odcl",
                    choices=list(list_federated_methods()),
                    help="registered federated method to run over the "
                         "streamed-in federation")
    ap.add_argument("--rounds", type=int, default=5,
                    help="communication rounds (ifca / fedavg)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write every obs span/event of the run as JSONL")
    ap.add_argument("--route-probes", type=int, default=0,
                    help="route this many fresh probe clients after the "
                         "round (serving latency histograms)")
    ap.add_argument("--finalize-repeats", type=int, default=1,
                    help="total finalize runs (warm re-finalizes feed the "
                         "finalize latency histogram)")
    ap.add_argument("--reupload-frac", type=float, default=0.0,
                    help="fraction of clients re-uploading drifted models "
                         "each mutation round (keyed slot replacement)")
    ap.add_argument("--churn", type=int, default=0,
                    help="fresh clients joining each mutation round")
    ap.add_argument("--max-age", type=int, default=None,
                    help="sliding-window staleness: evict slots older than "
                         "this many waves")
    ap.add_argument("--refinalize-threshold", type=float, default=None,
                    help="drift ratio above which maybe_refinalize() warm-"
                         "starts a re-finalize after the mutation rounds")
    ap.add_argument("--qps-callers", type=int, default=0,
                    help="run the RouteServer QPS probe: this many "
                         "closed-loop caller threads, per-request vs "
                         "cross-caller batched (0 = off)")
    ap.add_argument("--qps-duration", type=float, default=2.0,
                    help="seconds per QPS measurement loop")
    ap.add_argument("--out", default=None, help="write the summary JSON here")
    args = ap.parse_args(argv)
    runtime.use_compile_cache()

    # flat option superset -> per-scenario dataclass fields, filtered by
    # build_scenario exactly like build_federated_method filters methods
    scenario_options = {k: v for k, v in {
        "frac": args.byzantine_frac, "attack": args.byzantine_attack,
        "scale": args.byzantine_scale, "epsilon": args.dp_epsilon,
        "delta": args.dp_delta, "clip": args.dp_clip,
        "drift_frac": args.drift_frac, "zipf_a": args.zipf_a,
    }.items() if v is not None}

    summary = simulate(
        clients=args.clients, clusters=args.clusters, dim=args.dim,
        samples=args.samples, wave=args.wave, task=args.task,
        sketch_dim=args.sketch_dim, shards=args.shards,
        algorithm=args.algorithm,
        init=args.init, kmeans_iters=args.kmeans_iters,
        restarts=args.restarts, cc_iters=args.cc_iters,
        edges=args.edges, knn_k=args.knn_k,
        scenario=args.scenario, scenario_options=scenario_options or None,
        aggregator=args.aggregator, trim_beta=args.trim_beta,
        seed=args.seed, method=args.method, rounds=args.rounds,
        trace=args.trace, route_probes=args.route_probes,
        finalize_repeats=args.finalize_repeats,
        reupload_frac=args.reupload_frac, churn=args.churn,
        max_age=args.max_age,
        refinalize_threshold=args.refinalize_threshold,
        qps_callers=args.qps_callers, qps_duration=args.qps_duration)
    ph = summary["phases"]
    print(f"[simulate] C={summary['clients']} K={summary['clusters']} "
          f"task={summary['task']} wave={summary['wave']} "
          f"algo={summary['algorithm']} "
          f"shards={summary['shards']} "
          f"edges={summary['edges'] or '-'} "
          f"scenario={summary['scenario'] or '-'} "
          f"agg={summary['aggregator']} "
          f"method={summary['method']} rounds={summary['comm_rounds']:g}")
    print(f"[simulate] local ERMs {ph['local_erm_s']:.2f}s  "
          f"ingest {ph['ingest_s']:.2f}s  "
          f"server rounds {ph['aggregate_s']:.2f}s "
          f"({summary['comm_bytes'] / 1e6:.2f}MB moved)")
    clb = summary["comm_level_bytes"]
    if clb is not None:
        print(f"[simulate] hierarchy: level0 {clb['level0'] / 1e6:.2f}MB "
              f"(client uploads)  level1 {clb['level1'] / 1e6:.4f}MB "
              f"(shard centers)")
    mse = summary["mse"]
    print(f"[simulate] recovered K'={summary['n_clusters_recovered']} "
          f"purity={summary['purity']:.3f} "
          f"(all={summary['purity_all']:.3f}, "
          f"honest={summary['honest_frac']:.2f}) "
          f"mse={mse if mse is None else format(mse, '.3g')} "
          f"inertia={summary['meta'].get('inertia', float('nan')):.3g}")
    sv = summary["serving"]
    if sv is not None:
        rp50 = sv["route_p50_ms"]
        print(f"[simulate] serving: route p50="
              f"{'-' if rp50 is None else format(rp50, '.3f')}ms "
              f"p99={'-' if sv['route_p99_ms'] is None else format(sv['route_p99_ms'], '.3f')}ms "
              f"({'-' if sv['routes_per_s'] is None else format(sv['routes_per_s'], '.0f')}/s)  "
              f"finalize p50={'-' if sv['finalize_p50_ms'] is None else format(sv['finalize_p50_ms'], '.1f')}ms  "
              f"drift={'-' if sv['drift'] is None else format(sv['drift'], '.3f')}")
        if sv.get("live_clients") is not None:
            rw = sv["refinalize_warm_p50_ms"]
            bb = sv["route_batch_ms"]
            print(f"[simulate] mutation: live={sv['live_clients']} "
                  f"evictions={sv['evictions']} "
                  f"drift(after)={'-' if sv['drift_after_mutation'] is None else format(sv['drift_after_mutation'], '.3f')} "
                  f"refinalize={'fired' if sv['refinalize_fired'] else ('-' if sv['refinalize_fired'] is None else 'held')} "
                  f"warm p50={'-' if rw is None else format(rw, '.1f')}ms  "
                  f"batched route={'-' if bb is None else format(bb, '.2f')}ms "
                  f"({'-' if sv['batched_routes_per_s'] is None else format(sv['batched_routes_per_s'], '.0f')}/s)")
    qs = summary["qps_server"]
    if qs is not None:
        print(f"[simulate] qps: {qs['callers']} callers  "
              f"direct {qs['direct_qps']:.0f}/s  "
              f"batched {qs['batched_qps']:.0f}/s "
              f"({qs['batched_qps'] / max(qs['direct_qps'], 1e-9):.2f}x)  "
              f"p50={qs['batched_p50_ms']:.2f}ms "
              f"p99={qs['batched_p99_ms']:.2f}ms")
    if args.trace:
        print(f"[simulate] trace -> {args.trace}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"[simulate] wrote {args.out}")
    return summary


if __name__ == "__main__":
    main()
