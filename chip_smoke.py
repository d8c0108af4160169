"""Bring-up smoke run of the aggregation server on one TPU chip.

Drives the server's main path once, at the sizes its users run, through
the entry points the README documents, and checks every phase against a
plain host reference (numpy means, planted labels, the jnp kernel
oracles of ``repro.kernels.ref`` run on the same chip):

  A  flat ODCL-KM.  ``AggregationSession`` with 16,384 keyed clients from
     8 planted clusters, each uploading 16,384 float32 parameters (a
     1 GiB parameter buffer), ``sketch_dim=64``, ingested in waves of
     4,096; ``finalize(algorithm="kmeans-device", k=8)``, then a warm
     ``refinalize()`` after 25% of the clients upload again.
  B  ODCL-CC.  A session on the same population with ``sketch_dim=32``,
     ``finalize(algorithm="convex-device")`` over the ``knn`` fusion
     graph (``knn_k=8``: 131,072 edge slots) with 200 AMA iterations.
  C  Serving.  ``RouteServer`` on A's session (``max_batch=64``, 8 caller
     threads, 512 requests), then one direct ``session.route`` of 4,096
     probes.
  D  Hierarchy.  ``HierarchicalSession`` with C=1,048,576 clients over
     32 shards, ``sketch_dim=64`` (a 256 MiB sketch buffer) and 64-float
     uploads, then ``finalize(k=8)``.

Each phase fails loudly: purity against the planted labels, per-cluster
models against a numpy mean over the same partition, 1,024 ingested
clients' own sketches routed back to their own label, the on-chip
kernels against ``kernels/ref.py`` at the phase's shapes, and every
clustering and route program compiled with its Pallas kernels.

Usage::

    python chip_smoke.py             # phases A-D on one chip
    python chip_smoke.py --chips 4   # phase A's session sharded over
                                     # four chips against one chip

The script exits non-zero, printing no result, unless the first JAX
device is a TPU.  Its last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
CLUSTERS = 8
NOISE = 0.02          # per-coordinate noise around unit-scale centers:
#                       the planted clusters are far apart in every
#                       sketch, so kmeans++ seeding finds all of them
MEAN_ATOL = 1e-4      # float32 mean over ~2k rows; a bfloat16 round of
#                       the served model would be off by ~1e-2
FLAT = dict(clients=16384, width=16384, wave=4096)
HIER = dict(clients=1 << 20, width=64, wave=1 << 16, shards=32)
ROUTE_CHECKS = 1024


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ data

def planted(rng, clients: int, width: int):
    """Unit-scale centers, labels and float32 uploads around them."""
    centers = rng.standard_normal((CLUSTERS, width), dtype=np.float32)
    labels = rng.integers(0, CLUSTERS, clients)
    return centers, labels, draw(rng, centers, labels)


def draw(rng, centers, labels):
    x = rng.standard_normal((len(labels), centers.shape[1]),
                            dtype=np.float32)
    x *= NOISE
    for j in range(CLUSTERS):
        x[labels == j] += centers[j]
    return x


# ------------------------------------------------------------ references

def cluster_map(found, truth) -> dict:
    """Planted label -> found label; raises unless it is a bijection
    with every client on its planted cluster's image (purity 1.0)."""
    found, truth = np.asarray(found), np.asarray(truth)
    purity = sum(np.bincount(truth[found == f]).max()
                 for f in np.unique(found)) / len(found)
    mapping = {int(t): int(np.bincount(found[truth == t]).argmax())
               for t in np.unique(truth)}
    if purity != 1.0 or len(set(mapping.values())) != len(mapping):
        raise AssertionError(f"partition differs from the planted one: "
                             f"purity {purity}, map {mapping}")
    return mapping


def check_means(session, labels, x, n_clusters: int) -> float:
    """Every served cluster model against a numpy mean of its members."""
    worst = 0.0
    for j in range(n_clusters):
        got = np.asarray(session.cluster_model(j)["w"], np.float64)
        want = x[labels == j].mean(axis=0, dtype=np.float64)
        err = float(np.max(np.abs(got - want)))
        worst = max(worst, err)
        if not err <= MEAN_ATOL:
            raise AssertionError(f"cluster {j} model off its numpy mean "
                                 f"by {err} > {MEAN_ATOL}")
    return worst


def check_own_routes(session, sketches, labels, rng) -> None:
    import jax.numpy as jnp

    idx = np.sort(rng.choice(len(labels), ROUTE_CHECKS, replace=False))
    got = session.route(sketches[jnp.asarray(idx)])
    bad = int(np.sum(got != labels[idx]))
    if bad:
        raise AssertionError(f"{bad} of {ROUTE_CHECKS} ingested clients' "
                             "own sketches route away from their label")


def check_close(name, got, want, rtol, atol) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.allclose(got, want, rtol=rtol, atol=atol):
        err = float(np.max(np.abs(got.astype(np.float64) - want)))
        raise AssertionError(f"{name}: kernel off the reference by {err}")


def check_assign_kernel(points, centers) -> None:
    """``kmeans_assign`` on the chip against its jnp oracle, the oracle
    at float32 matmul precision (XLA's TPU default rounds to bf16)."""
    import jax
    from repro.kernels import ref
    from repro.kernels.kmeans_assign import kmeans_assign_pallas

    points, centers = jax.device_put((points, centers), jax.devices()[0])
    got = kmeans_assign_pallas(points, centers)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.kmeans_assign)(points, centers)
    shape = f"kmeans_assign {points.shape}x{centers.shape}"
    if not np.array_equal(np.asarray(got[0]), np.asarray(want[0])):
        raise AssertionError(f"{shape}: labels differ from the reference")
    scale = float(np.max(np.abs(np.asarray(want[1])))) + 1.0
    check_close(f"{shape} sums", got[1], want[1], 1e-5, 1e-5 * scale)
    check_close(f"{shape} counts", got[2], want[2], 0, 0)


def check_convex_kernels(sketches, n_edges: int, rng) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.group_prox import group_ball_proj_batched_pallas
    from repro.kernels.pairwise_l2 import pairwise_sqdist_pallas

    tile = sketches[:512]
    got = pairwise_sqdist_pallas(tile, sketches)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.pairwise_sqdist)(tile, sketches)
    scale = float(jnp.max(want))
    check_close(f"pairwise_sqdist {tile.shape}x{sketches.shape}", got, want,
                1e-4, 1e-5 * scale)
    d = sketches.shape[1]
    v = jnp.asarray(rng.standard_normal((1, n_edges, d), dtype=np.float32))
    r = jnp.asarray(rng.uniform(0.1, 2.0, (1, n_edges)).astype(np.float32))
    check_close(f"group_ball_proj_batched {v.shape}",
                group_ball_proj_batched_pallas(v, r),
                jax.jit(ref.group_ball_proj_batched)(v, r), 1e-5, 1e-6)


# ------------------------------------------------------------- telemetry

# programs whose every compile must hold a Pallas kernel on the chip
KERNEL_PROGRAMS = ("session.finalize.cluster", "session.refinalize.cluster",
                   "session.route.batch")


def program_report(phase: str, compiled) -> dict:
    """Compiles and Pallas kernels of the programs this phase compiled.
    Raises unless each of ``compiled`` was compiled here, and unless
    every clustering and route program compiled here holds a Pallas
    kernel."""
    import jax
    from repro import obs

    snap = obs.snapshot()
    hists, gauges = snap["histograms"], snap["gauges"]
    compiles = {n[:-len(".compile.ms")]: int(h["count"])
                for n, h in hists.items() if n.endswith(".compile.ms")}
    kernels = {n[:-len(".pallas_kernels")]: int(v)
               for n, v in gauges.items() if n.endswith(".pallas_kernels")}
    missing = set(compiled) - set(compiles)
    if missing:
        raise AssertionError(f"phase {phase}: {sorted(missing)} not compiled")
    for label in set(compiles) & set(KERNEL_PROGRAMS):
        if kernels.get(label, 0) < 1:
            raise AssertionError(f"phase {phase}: program {label!r} ran "
                                 "without a compiled Pallas kernel "
                                 f"({kernels})")
    dev = jax.devices()[0]
    # a TPU must report its allocator's peak; a CPU rehearsal has none
    peak = (dev.memory_stats()["peak_bytes_in_use"]
            if dev.platform == "tpu" else None)
    report = {"compiles": compiles, "pallas_kernels": kernels,
              "peak_bytes_in_use": peak}
    log(f"[{phase}] programs {json.dumps(report, sort_keys=True)}")
    obs.reset()
    return report


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------- phases

def ingest_flat(session, x, wave: int, keyed: bool) -> None:
    for s in range(0, len(x), wave):
        ids = range(s, min(s + wave, len(x))) if keyed else None
        session.ingest({"w": x[s:s + wave]}, client_ids=ids)


def phase_a(rng, pop, *, clients, width, wave, mesh=None):
    """Flat ODCL-KM: keyed ingest, cold finalize, 25% re-upload, warm
    refinalize — each round checked against the host reference."""
    from repro.core.engine import AggregationSession

    centers, truth, x = pop
    session = AggregationSession(clients, sketch_dim=64, seed=SEED,
                                 mesh=mesh)
    _, t_ingest = timed(lambda: ingest_flat(session, x, wave, keyed=True))
    (_, labels, info), t_cold = timed(
        lambda: session.finalize(algorithm="kmeans-device", k=CLUSTERS))
    mapping = cluster_map(labels, truth)
    err = check_means(session, labels, x, info["n_clusters"])
    check_own_routes(session, session.sketches, labels, rng)
    check_assign_kernel(session.sketches, session.route_centers)
    log(f"[A] ingest {clients} clients x {width} floats {t_ingest:.3f}s; "
        f"finalize cold {t_cold:.3f}s; clusters {info['n_clusters']}; "
        f"purity 1.0; max |model - numpy mean| {err:.3g}")

    again = np.sort(rng.choice(clients, clients // 4, replace=False))
    x[again] = draw(rng, centers, truth[again])
    for s in range(0, len(again), wave):
        ids = again[s:s + wave]
        session.ingest({"w": x[ids]}, client_ids=ids.tolist())
    (_, labels, info), t_warm = timed(session.refinalize)
    if info["refinalize"] != "warm":
        raise AssertionError(f"refinalize ran {info['refinalize']!r}")
    if cluster_map(labels, truth) != mapping:
        raise AssertionError("refinalize relabelled the planted clusters")
    err = check_means(session, labels, x, info["n_clusters"])
    log(f"[A] re-upload {len(again)} clients; refinalize warm "
        f"{t_warm:.3f}s; purity 1.0; max |model - numpy mean| {err:.3g}")
    return session, labels, mapping


def phase_b(rng, pop, *, clients, width, wave, iters=200, knn_k=8):
    """ODCL-CC over the sparse kNN fusion graph."""
    from repro.core.clustering.convex import lambda_interval
    from repro.core.engine import AggregationSession

    _, truth, x = pop
    session = AggregationSession(clients, sketch_dim=32, seed=SEED)
    ingest_flat(session, x, wave, keyed=False)
    # the paper's exact-lambda choice (appendix E.1): the midpoint of the
    # recovery interval (17) of the planted partition in sketch space
    lo, hi = lambda_interval(np.asarray(session.sketches), truth)
    lam = 0.5 * (lo + hi) if lo < hi else lo
    options = {"lam": lam, "iters": iters, "edges": "knn", "knn_k": knn_k}
    (_, labels, info), t_cold = timed(lambda: session.finalize(
        algorithm="convex-device", algo_options=options))
    cluster_map(labels, truth)
    if info["n_clusters"] != CLUSTERS:
        raise AssertionError(f"convex found {info['n_clusters']} clusters")
    err = check_means(session, labels, x, info["n_clusters"])
    check_own_routes(session, session.sketches, labels, rng)
    check_convex_kernels(session.sketches, clients * knn_k, rng)
    (_, labels2, _), t_warm = timed(lambda: session.finalize(
        algorithm="convex-device", algo_options=options))
    if not np.array_equal(labels, labels2):
        raise AssertionError("a second convex finalize changed the labels")
    log(f"[B] convex knn lambda {lam:.4g} (interval [{lo:.4g}, {hi:.4g}]): "
        f"finalize cold {t_cold:.3f}s warm {t_warm:.3f}s; AMA iterations "
        f"{info['meta'].get('n_iter')}; clusters {info['n_clusters']}; "
        f"purity 1.0; max |model - numpy mean| {err:.3g}")


def phase_c(rng, pop, session, mapping, *, requests=512, callers=8,
            max_batch=64, direct=4096):
    """RouteServer under concurrent callers, then one large direct
    batch; every probe is a fresh client of a planted cluster."""
    from repro.serving import RouteServer

    centers, _, _ = pop
    truth = rng.integers(0, CLUSTERS, requests + direct)
    probes = np.asarray(session.sketch_params(
        {"w": draw(rng, centers, truth)}))
    want = np.array([mapping[int(t)] for t in truth])
    got = np.full(requests, -1)
    per = requests // callers
    errors = []

    def caller(c):
        try:
            for i in range(c * per, (c + 1) * per):
                got[i] = srv.route(probes[i], timeout=120.0)
        except Exception as exc:   # noqa: BLE001 - re-raised below
            errors.append(exc)

    t0 = time.perf_counter()
    with RouteServer(session, max_batch=max_batch) as srv:
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        if any(t.is_alive() for t in threads):
            raise AssertionError("route callers did not finish")
    t_server = time.perf_counter() - t0
    if errors:
        raise errors[0]
    if not np.array_equal(got, want[:requests]):
        raise AssertionError(f"{int(np.sum(got != want[:requests]))} server "
                             "routes miss the planted cluster")
    batch = probes[requests:]
    labels, t_cold = timed(lambda: session.route(batch))
    labels, t_warm = timed(lambda: session.route(batch))
    if not np.array_equal(labels, want[requests:]):
        raise AssertionError("direct batch routes miss the planted cluster")
    check_assign_kernel(probes[:max_batch], session.route_centers)
    check_assign_kernel(batch, session.route_centers)
    log(f"[C] RouteServer {requests} requests from {callers} callers "
        f"{t_server:.3f}s; direct route of {direct} cold {t_cold:.3f}s "
        f"warm {t_warm:.3f}s; all on their planted cluster")


def phase_d(rng, *, clients, width, wave, shards):
    """Two-level hierarchical round at a million clients."""
    from repro.core.engine import HierarchicalSession

    _, truth, x = planted(rng, clients, width)
    hier = HierarchicalSession(clients, shards=shards, sketch_dim=64,
                               seed=SEED)
    _, t_ingest = timed(lambda: ingest_flat(hier, x, wave, keyed=False))
    (_, labels, info), t_cold = timed(lambda: hier.finalize(k=CLUSTERS))
    cluster_map(labels, truth)
    err = check_means(hier, labels, x, info["n_clusters"])
    sketches = hier.sketches
    check_own_routes(hier, sketches, labels, rng)
    check_assign_kernel(sketches[:clients // shards], hier.route_centers)
    (_, labels2, _), t_warm = timed(lambda: hier.finalize(k=CLUSTERS))
    if not np.array_equal(labels, labels2):
        raise AssertionError("a second hierarchical finalize changed labels")
    log(f"[D] ingest {clients} clients over {shards} shards "
        f"{t_ingest:.3f}s; finalize cold {t_cold:.3f}s warm {t_warm:.3f}s; "
        f"clusters {info['n_clusters']}; purity 1.0; "
        f"max |model - numpy mean| {err:.3g}")


def four_chips(rng, pop, *, clients, width, wave):
    """Phase A's session with its client axis sharded over four chips,
    against the same session on one chip."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))
    centers, truth, x = pop
    one = (centers, truth, x.copy())
    sharded, labels4, _ = phase_a(np.random.default_rng(1), pop,
                                  clients=clients, width=width, wave=wave,
                                  mesh=mesh)
    for name, buf in (("sketch", sharded._sketches),
                      ("param", sharded._params["w"])):
        rows = sorted((s.device.id, s.data.shape[0])
                      for s in buf.addressable_shards)
        if len(rows) != 4 or any(r != clients // 4 for _, r in rows):
            raise AssertionError(f"{name} buffer not split 4 ways: {rows}")
        log(f"[4] {name} buffer rows per device {rows}")
    single, labels1, _ = phase_a(np.random.default_rng(1), one,
                                 clients=clients, width=width, wave=wave)
    if not np.array_equal(labels4, labels1):
        raise AssertionError("sharded partition differs from one chip")
    worst = 0.0
    for j in range(sharded.n_clusters):
        a = np.asarray(sharded.cluster_model(j)["w"])
        b = np.asarray(single.cluster_model(j)["w"])
        worst = max(worst, float(np.max(np.abs(a - b))))
    if not worst <= 1e-5:
        raise AssertionError(f"sharded means off one chip's by {worst}")
    log(f"[4] partition identical to one chip; max |mean diff| {worst:.3g}")


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro import obs, runtime

    log(f"[setup] compile cache {runtime.use_compile_cache()}")
    rng = np.random.default_rng(SEED)
    pop, t_data = timed(lambda: planted(rng, FLAT["clients"], FLAT["width"]))
    log(f"[setup] planted population {pop[2].shape} in {t_data:.3f}s on "
        f"{dev.device_kind} x{len(jax.devices())}")
    obs.reset()
    if args.chips == 4:
        four_chips(rng, pop, **FLAT)
        program_report("4", ["session.finalize.cluster"])
    else:
        session, _, mapping = phase_a(rng, pop, **FLAT)
        program_report("A", ["session.finalize.cluster",
                             "session.refinalize.cluster",
                             "session.route.batch"])
        phase_b(rng, pop, **FLAT)
        program_report("B", ["session.finalize.cluster"])
        phase_c(rng, pop, session, mapping)
        program_report("C", ["session.route.batch"])
        del session, pop
        phase_d(rng, **HIER)
        program_report("D", ["session.finalize.cluster"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
