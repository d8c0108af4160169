"""The one-shot round as a single jitted device program (Algorithm 1).

``one_shot_aggregate_device`` fuses the whole server side —

    sketch every client's parameters (JL projection, step 1 upload)
    -> cluster the (C, sketch_dim) sketch matrix on device (step 2)
    -> per-cluster masked parameter mean (steps 3-4)

— into one ``jax.jit`` program.  Sketches, centers and the averaged
parameters never cross the host boundary; the only host outputs are the
(C,) label vector and a handful of scalar diagnostics.  Pass
``return_sketches=True`` to additionally pull the sketch matrix to host
(small-C debugging only — large-C runs must not pay that transfer).

The cluster->average stage is shared with the streaming server API
(``engine/session.py``): the session's finalize runs the same stage as
two AOT programs (``_cluster_program`` + ``_mean_program``, split so
the obs layer can time the cluster vs mean phases separately) over the
sketch matrix it accumulated wave-by-wave — the paths stay bit-exact
because both trace the identical ``device_call`` /
``_average_clusters`` bodies (pinned by ``tests/test_session.py``).
Every program here is a ``_Program``: AOT ``lower().compile()`` per
input shape with compile-vs-execute spans recorded to ``repro.obs``.

Under a mesh the client axis shards over ``data`` (the same stacked
layout as ``federated.py``): the label/center reductions inside the
device clustering loop and the one-hot contraction of the cluster mean
both lower to psums over the client shards, so the round runs without
any host-driven collective.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.clustering.api import (
    get_algorithm,
    is_device_algorithm,
    meta_to_host,
)
from repro.core.engine.aggregators import (
    cluster_aggregate_tree,
    get_aggregator,
    matmul_f32,
)
from repro.core.federated import FederatedState, _router_invariant_filter
from repro.core.sketch import sketch_tree
from repro.kernels import ops as kops
from repro.optim import adamw_init


def _constrainer(mesh, client_axis):
    def constrain(x):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(client_axis)))

    return constrain


def _average_clusters(constrain, labels, centers, params, aggregator):
    """Steps 3-4: the per-cluster parameter reduction (traceable).

    The single source of truth for the averaging stage: the fused round
    traces it through ``_cluster_and_average`` and the session's split
    finalize traces it alone (``_mean_program``) — same body, which is
    what keeps the two bit-exact on identical inputs."""
    kk = centers.shape[0]
    onehot = jax.nn.one_hot(labels, kk, dtype=jnp.float32)      # (C, K)
    counts = jnp.sum(onehot, axis=0)                            # (K,) raw
    return jax.tree_util.tree_map(
        constrain, cluster_aggregate_tree(params, labels, onehot,
                                          counts, aggregator))


def _cluster_and_average(algo, options, k, constrain, cluster_key,
                         sketches, params, aggregator="mean"):
    """Steps 2-4 on an already-materialized sketch matrix (traceable).

    ``aggregator`` selects the per-cluster reduction from the registry
    (``engine/aggregators.py``); the default ``mean`` traces the
    identical contraction as before the registry existed.
    """
    res = algo.device_call(cluster_key, sketches, k=k, **options)
    new_params = _average_clusters(constrain, res.labels, res.centers,
                                   params, aggregator)
    return new_params, res


# how a compiled Pallas kernel appears in the HLO of a TPU program
_PALLAS_CALL = 'custom_call_target="tpu_custom_call"'


class _Program:
    """AOT-compiled program with compile-vs-execute telemetry.

    Wraps a traceable function: the first call per input-shape
    signature runs ``jit(fn).lower(*args).compile()`` under a
    ``"<label>.compile"`` span and records the compiled module's number
    of compiled Pallas kernels as the ``"<label>.pallas_kernels"``
    gauge (0 off the TPU, where the kernels dispatch to their jnp
    oracles); every call then executes (blocking to completion) under a
    ``"<label>.execute"`` span.  This is what splits the historically
    conflated "first round is slow" wall clock into trace/compile vs
    execute.
    """

    def __init__(self, label: str, fn):
        self.label = label
        self._fn = fn
        self._cache = {}

    @staticmethod
    def _signature(args):
        return tuple((l.shape, str(l.dtype))
                     for l in jax.tree_util.tree_leaves(args))

    def __call__(self, *args):
        sig = self._signature(args)
        compiled = self._cache.get(sig)
        if compiled is None:
            with obs.span(f"{self.label}.compile"):
                compiled = jax.jit(self._fn).lower(*args).compile()
            obs.gauge(f"{self.label}.pallas_kernels",
                      float(compiled.as_text().count(_PALLAS_CALL)))
            self._cache[sig] = compiled
        with obs.span(f"{self.label}.execute"):
            out = compiled(*args)
            jax.block_until_ready(out)
        return out


@functools.lru_cache(maxsize=16)
def _round_program(algo, k, opts, sketch_dim, leaf_filter, mesh, client_axis,
                   aggregator="mean"):
    """Build the fused end-to-end round for one static configuration.

    Cached on the static pieces (``aggregator`` resolves to a frozen
    registry instance, so it joins the key) so repeated rounds (sweeps,
    parity tests, multi-round drivers) reuse the compiled program
    instead of retracing a fresh closure every call.  Returns a
    ``_Program`` — AOT-compiled per shape with compile/execute spans
    under the ``"engine.round"`` label.
    """
    options = dict(opts)
    constrain = _constrainer(mesh, client_axis)

    def round_fn(sketch_key, cluster_key, params):
        sketches = jax.vmap(
            lambda p: sketch_tree(sketch_key, p, sketch_dim,
                                  leaf_filter=leaf_filter)
        )(params)                                        # (C, sketch_dim)
        sketches = constrain(sketches)
        new_params, res = _cluster_and_average(
            algo, options, k, constrain, cluster_key, sketches, params,
            aggregator)
        return new_params, res, sketches

    return _Program("engine.round", round_fn)


@functools.lru_cache(maxsize=16)
def _cluster_program(algo, k, opts):
    """Step 2 alone — the session finalize's clustering phase.

    Same ``device_call`` trace as inside the fused round; splitting it
    from the mean program gives the cluster/mean latency breakdown
    (``session.finalize.cluster`` vs ``session.finalize.mean`` spans)
    that decides *what* an incremental re-finalize would need to re-run.
    The bit-exactness property tests in ``tests/test_session.py`` pin
    that the split stays identical to the fused round."""
    options = dict(opts)

    def cluster_fn(cluster_key, sketches):
        return algo.device_call(cluster_key, sketches, k=k, **options)

    return _Program("session.finalize.cluster", cluster_fn)


@functools.lru_cache(maxsize=16)
def _mean_program(mesh, client_axis, aggregator="mean"):
    """Steps 3-4 alone — the session finalize's averaging phase (the
    shared ``_average_clusters`` body, fed the cluster program's
    labels/centers, which stay on device between the two programs)."""
    constrain = _constrainer(mesh, client_axis)

    def mean_fn(labels, centers, params):
        return _average_clusters(constrain, labels, centers, params,
                                 aggregator)

    return _Program("session.finalize.mean", mean_fn)


@functools.lru_cache(maxsize=16)
def _warm_cluster_program(algo, k, opts):
    """Step 2 warm-started — the session's incremental re-finalize.

    Same static configuration as ``_cluster_program`` but traced through
    the family's ``device_warm_call``: the warm state (previous centers
    for Lloyd, the AMA dual for the convex family) enters as a TRACED
    argument, so re-finalizes with fresh warm states reuse one compiled
    program instead of retracing per state."""
    options = dict(opts)

    def cluster_fn(cluster_key, sketches, warm):
        return algo.device_warm_call(cluster_key, sketches, warm, k=k,
                                     **options)

    return _Program("session.refinalize.cluster", cluster_fn)


@functools.lru_cache(maxsize=16)
def _weighted_mean_program(mesh, client_axis):
    """Steps 3-4 with per-client weights — the exponential-decay
    staleness policy's averaging phase.  The per-cluster reduction is
    the normalized weighted mean ``sum_i w_i x_i / sum_i w_i`` (uniform
    weights reduce to the plain mean on non-empty clusters); robust
    aggregators have no weighted form here, which the session enforces."""
    constrain = _constrainer(mesh, client_axis)

    def mean_fn(labels, centers, params, weights):
        kk = centers.shape[0]
        onehot = jax.nn.one_hot(labels, kk, dtype=jnp.float32)     # (C, K)
        weighted = onehot * weights.astype(jnp.float32)[:, None]   # (C, K)
        denom = jnp.maximum(jnp.sum(weighted, axis=0), 1e-12)[:, None]

        def back(leaf):
            flat = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
            means = matmul_f32(weighted.T, flat) / denom           # (K, n)
            return constrain(matmul_f32(onehot, means).reshape(
                leaf.shape).astype(leaf.dtype))

        return jax.tree_util.tree_map(back, params)

    return _Program("session.finalize.mean", mean_fn)


@functools.lru_cache(maxsize=4)
def _route_program():
    """Serving-time step 4 over a request batch, as ONE program: the
    fused nearest-center assignment plus the drift accumulator (total
    squared distance of the batch to its assigned centers).  The
    per-request host round-trips of the old route path (a label pull,
    then a separate ``float()`` sync for the drift gauge) collapse into
    a single execute with one host sync per batch."""

    def route_fn(pts, centers):
        labels, _, _ = kops.kmeans_assign(pts, centers)
        assigned = centers[labels]
        d2 = jnp.sum((pts - assigned) ** 2)
        return labels, d2

    return _Program("session.route.batch", route_fn)


@functools.lru_cache(maxsize=4)
def _gather_rows_program():
    """Live-row gather: compact a holey fixed-capacity buffer (sketches
    or a stacked params pytree) down to the surviving rows before a
    finalize.  Sessions with a contiguous live prefix never call this —
    they keep the bit-exact slice path."""

    def gather_fn(buf, rows):
        return jax.tree_util.tree_map(lambda l: l[rows], buf)

    return _Program("session.gather", gather_fn)


def cached_program(builder, *key):
    """Call an ``lru_cache``d program builder, falling back to the
    uncached build when a key piece (algorithm instance, options dict,
    mesh) is unhashable — shared by the fused round and the session."""
    try:
        return builder(*key)
    except TypeError:
        return builder.__wrapped__(*key)


def resolve_device_algorithm(algorithm):
    """Registry lookup + the hard device-capability check of the fused
    round (the session resolves engine='auto' fallbacks itself)."""
    algo = get_algorithm(algorithm)
    if not is_device_algorithm(algo):
        raise ValueError(
            f"algorithm {getattr(algo, 'name', algo)!r} is host-only; the "
            "device engine needs a DeviceClusteringAlgorithm "
            "(e.g. 'kmeans-device'), or use engine='host'")
    return algo


def compact_labels(raw_labels):
    """Host-side label compaction: device clusterings may emit
    non-contiguous ids (empty Lloyd clusters, convex root ids).  Returns
    (labels in [0, K'), uniq raw ids, first index per compact id)."""
    raw = np.asarray(raw_labels)
    uniq, first, labels = np.unique(raw, return_index=True,
                                    return_inverse=True)
    return labels.astype(np.int32), uniq, first


@functools.lru_cache(maxsize=8)
def _opt_init_program(sharding):
    """Per-client AdamW state laid out by ``sharding``, the per-client
    parameters' own: under a mesh its client axis is sharded as theirs
    is, where zeros made outside such a program would all land on one
    device."""
    return jax.jit(jax.vmap(adamw_init), out_shardings=sharding)


def materialize_round(new_params, res, state: FederatedState):
    """Host materialization of a device round: compacted labels + scalar
    meta are the ONLY transfers; params/opt state stay device pytrees,
    the opt state laid out as the params are.
    Returns ``(new_state, labels, info, uniq, first)`` — ``uniq`` the raw
    ids behind each compact label, ``first`` one member index per compact
    id (the session's routing/serving handles)."""
    labels, uniq, first = compact_labels(res.labels)
    meta = meta_to_host(res.meta)
    sharding = jax.tree_util.tree_leaves(new_params)[0].sharding
    new_state = FederatedState(
        params=new_params,
        opt_state=_opt_init_program(sharding)(new_params),
        n_clients=state.n_clients, step=state.step)
    info = {"n_clusters": int(len(uniq)), "meta": meta, "engine": "device"}
    return new_state, labels, info, uniq, first


def one_shot_aggregate_device(state: FederatedState, cfg=None, *,
                              algorithm="kmeans-device",
                              k: Optional[int] = None,
                              algo_options: Optional[dict] = None,
                              sketch_dim: int = 256, seed: int = 0,
                              cluster_seed: Optional[int] = None,
                              mesh=None, client_axis: str = "data",
                              aggregator="mean",
                              return_sketches: bool = False):
    """Device-resident one-shot aggregation. Returns (state, labels, info).

    ``algorithm`` must be device-capable (a ``DeviceClusteringAlgorithm``,
    e.g. the registered ``"kmeans-device"``).  ``cfg`` is optional and
    only consulted for the MoE router-invariant sketch filter — pass
    ``None`` for shallow per-client models (``launch/simulate.py``).
    ``seed`` drives the JL sketch; ``cluster_seed`` (default: ``seed``)
    drives the clustering init, mirroring the host path's seed split.
    ``aggregator`` names a registered per-cluster reduction (or passes
    an ``Aggregator`` instance) — the robust step-3 variants run inside
    the same jitted program.  With ``mesh`` given, the client axis of
    sketches and parameters is constrained to ``client_axis`` and XLA
    shards the round over it.
    """
    algo = resolve_device_algorithm(algorithm)
    aggregator = get_aggregator(aggregator)
    leaf_filter = (_router_invariant_filter
                   if cfg is not None and getattr(cfg, "is_moe", False)
                   else None)
    opts = tuple(sorted((algo_options or {}).items()))
    round_fn = cached_program(_round_program, algo, k, opts, sketch_dim,
                              leaf_filter, mesh, client_axis, aggregator)

    sketch_key = jax.random.PRNGKey(seed)
    cluster_key = jax.random.PRNGKey(
        seed if cluster_seed is None else cluster_seed)
    with obs.span("engine.one_shot", clients=state.n_clients,
                  algorithm=getattr(algo, "name", str(algo))):
        new_params, res, sketches = round_fn(sketch_key, cluster_key,
                                             state.params)

    new_state, labels, info, _, _ = materialize_round(new_params, res, state)
    if return_sketches:
        info["sketches"] = np.asarray(sketches)
    return new_state, labels, info
