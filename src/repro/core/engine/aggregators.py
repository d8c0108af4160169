"""Pluggable robust per-cluster aggregation — step 3 of Algorithm 1 as
a registry.

The paper's server averages models within each recovered cluster; under
a hostile client population (Byzantine uploads at fraction f, the
clustered-FL robustness setting of Ghosh et al.) the plain mean has a
breakdown point of zero — one colluding client drags its cluster's
model arbitrarily far.  This module makes the per-cluster reduction a
plugin, mirroring the clustering / edge-set registries
(``register_aggregator`` / ``get_aggregator`` / ``list_aggregators`` /
``unregister_aggregator``):

  * ``mean``          — the paper's step 3 (bit-exact with the
                        pre-registry ``cluster_average_tree`` path).
  * ``trimmed_mean``  — coordinate-wise beta-trimmed mean: per cluster
                        and coordinate, drop the t = floor(beta * cnt)
                        smallest and largest values and average the
                        rest.  Breakdown point beta.
  * ``median``        — coordinate-wise median per cluster.
  * ``geometric_median`` — fixed-iteration Weiszfeld in the full sketch
                        space: row-wise (not coordinate-wise) robust,
                        the defense against colluding spoof blobs that
                        beat coordinate-wise trims.  Breakdown 1/2.

Every aggregator is jit-traceable with static shapes: the segment-wise
order statistics run as ONE column-parallel ``jax.lax.sort`` keyed on
the cluster label (stable, two keys), so the reduction stays inside the
single jitted one-shot round — sketches, parameters, and per-cluster
aggregates never cross the host boundary, exactly like the mean path it
generalizes.

Signature contract (what a registered aggregator implements)::

    agg(flat, labels, onehot, counts) -> (K, n) float32

``flat`` is the (C, n) float32 stack of one flattened leaf, ``labels``
the (C,) int32 cluster ids in [0, K), ``onehot`` the (C, K) float32
indicator, ``counts`` the RAW (K,) float32 cluster sizes (empty
clusters are 0; aggregators clamp internally).  Empty clusters must
aggregate to 0 (the masked-matmul convention of the mean path — the
gather-back never reads them).

The tree-level wrappers ``cluster_reduce_tree`` (to (K, ...) cluster
representatives) and ``cluster_aggregate_tree`` (gather-back to
(C, ...) per-client models) are the shapes the engine, the streaming
session, and IFCA's round loop consume.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp


@runtime_checkable
class Aggregator(Protocol):
    """A per-cluster reduction usable inside the jitted round.

    ``breakdown`` is the aggregator's breakdown point (the largest
    in-cluster corruption fraction it tolerates): 0 for the mean, beta
    for the trimmed mean, 1/2 for the median.  The device Lloyd loop
    also reads it to make multi-restart *selection* robust — restarts
    are scored by the breakdown-trimmed inertia (the trimmed k-means
    objective of Cuesta-Albertos et al.), because a robust center
    update is worthless if the plain inertia still rewards the restart
    whose center was captured by a coherent attacker blob.
    """
    name: str
    breakdown: float = 0.0

    def __call__(self, flat: jnp.ndarray, labels: jnp.ndarray,
                 onehot: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray: ...


def matmul_f32(a, b):
    """float32 matmul on every backend.  The one-hot contractions below
    are exact selections and sums; at the TPU's default precision XLA
    rounds float32 operands to bfloat16, which would put a ~0.4%
    relative error on every served cluster model."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------- segment order statistics

def _segment_sort(flat, labels):
    """Column-wise stable sort of ``flat`` keyed on the cluster label.

    Returns ``(vals, sorted_labels, perm)``: ``vals[i, j]`` the i-th
    value of column j in (label, value) order, ``sorted_labels`` the
    (C,) ascending label of each sorted slot (identical across columns
    — the label is the primary key), ``perm[i, j]`` the original row
    behind sorted slot i of column j.
    """
    c, n = flat.shape
    lab_b = jnp.broadcast_to(labels[:, None].astype(jnp.int32), (c, n))
    row_b = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[:, None], (c, n))
    sl, vals, perm = jax.lax.sort((lab_b, flat, row_b), dimension=0,
                                  num_keys=2)
    return vals, sl[:, 0], perm


def _cluster_ranks(flat, labels):
    """(C, n) rank of every coordinate within its cluster's column.

    Ranks are scattered back to the ORIGINAL row layout, so masks built
    from them compose with the same ``onehot.T @ masked`` contraction as
    the mean — at trim budget 0 the masked matrix IS ``flat`` and the
    reduction is bit-exact with the mean aggregator.
    """
    c, n = flat.shape
    _, sl, perm = _segment_sort(flat, labels)
    pos = jnp.arange(c, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sl[1:] != sl[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
    rank_sorted = jnp.broadcast_to((pos - seg_start)[:, None], (c, n))
    cols = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (c, n))
    return jnp.zeros((c, n), jnp.int32).at[perm, cols].set(rank_sorted)


# ------------------------------------------------------------- aggregators

@dataclasses.dataclass(frozen=True)
class MeanAggregator:
    """The paper's step 3: masked per-cluster mean (breakdown point 0)."""
    name: str = "mean"
    breakdown = 0.0

    def __call__(self, flat, labels, onehot, counts):
        return (matmul_f32(onehot.T, flat)
                / jnp.maximum(counts, 1.0)[:, None])


@dataclasses.dataclass(frozen=True)
class TrimmedMeanAggregator:
    """Coordinate-wise beta-trimmed mean (breakdown point beta).

    Per cluster of size cnt the trim budget is
    ``t = min(floor(beta * cnt), (cnt - 1) // 2)`` — degenerate clusters
    (size 1, or smaller than the trim window) clamp t so at least one
    value always survives; at t = 0 the keep-mask is all-ones and the
    reduction is bit-exact with ``mean``.
    """
    beta: float = 0.1
    name: str = "trimmed_mean"

    @property
    def breakdown(self) -> float:
        return self.beta

    def __post_init__(self):
        if not 0.0 <= self.beta < 0.5:
            raise ValueError(f"trim fraction beta must be in [0, 0.5), "
                             f"got {self.beta}")

    def __call__(self, flat, labels, onehot, counts):
        cnt_i = counts.astype(jnp.int32)                          # (K,)
        t = jnp.minimum(jnp.floor(self.beta * counts).astype(jnp.int32),
                        jnp.maximum((cnt_i - 1) // 2, 0))
        rank = _cluster_ranks(flat, labels)                       # (C, n)
        t_row = t[labels][:, None]
        cnt_row = cnt_i[labels][:, None]
        keep = (rank >= t_row) & (rank < cnt_row - t_row)
        masked = jnp.where(keep, flat, jnp.zeros((), flat.dtype))
        denom = jnp.maximum(counts - 2.0 * t.astype(counts.dtype), 1.0)
        return matmul_f32(onehot.T, masked) / denom[:, None]


@dataclasses.dataclass(frozen=True)
class GeometricMedianAggregator:
    """Per-cluster geometric median by fixed-iteration Weiszfeld
    (breakdown point 1/2 — and, unlike the coordinate-wise trims, a
    GENUINELY multivariate notion of center).

    A colluding-spoof attacker that concentrates every corrupted row on
    ONE shared point beats coordinate-wise trimming at fractions below
    the trim budget's bite (the blob survives partially in every
    coordinate and drags the mean of the survivors); the geometric
    median weights whole ROWS by inverse distance, so a coherent blob
    of fraction < 1/2 holds no leverage regardless of its geometry.

    ``iters`` fixed Weiszfeld steps run inside the jitted round (no
    host sync, no dynamic shapes): ``y <- sum_i w_i x_i / sum_i w_i``
    with ``w_i = [label_i == k] / max(||x_i - y||, eps)``.  Init is the
    masked per-cluster mean; size-1 clusters converge to their single
    member in one step; empty clusters aggregate to 0 per the registry
    contract.
    """
    iters: int = 16
    eps: float = 1e-8
    name: str = "geometric_median"
    breakdown = 0.5

    def __post_init__(self):
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1, got {self.iters}")
        if self.eps <= 0:
            raise ValueError(f"eps must be > 0, got {self.eps}")

    def __call__(self, flat, labels, onehot, counts):
        denom = jnp.maximum(counts, 1.0)[:, None]                 # (K, 1)
        y0 = matmul_f32(onehot.T, flat) / denom                   # (K, n)
        sq = jnp.sum(flat * flat, axis=1)                         # (C,)

        def step(_, y):
            # (C, K) pairwise distances via the expanded square (one
            # matmul; never materializes a (C, K, n) difference tensor)
            d2 = (sq[:, None] - 2.0 * matmul_f32(flat, y.T)
                  + jnp.sum(y * y, axis=1)[None, :])
            d = jnp.sqrt(jnp.maximum(d2, 0.0))
            w = onehot / jnp.maximum(d, self.eps)                 # (C, K)
            return matmul_f32(w.T, flat) / jnp.maximum(
                jnp.sum(w, axis=0), self.eps)[:, None]

        y = jax.lax.fori_loop(0, self.iters, step, y0)
        return jnp.where(counts[:, None] > 0, y,
                         jnp.zeros((), flat.dtype))


@dataclasses.dataclass(frozen=True)
class MedianAggregator:
    """Coordinate-wise per-cluster median (breakdown point 1/2).

    Gathers the two middle order statistics of every (cluster, column)
    segment from the stable segment sort; size-1 and size-2 clusters
    reduce bit-exactly to ``mean`` (a and (a + b) / 2).
    """
    name: str = "median"
    breakdown = 0.5

    def __call__(self, flat, labels, onehot, counts):
        c, _ = flat.shape
        cnt_i = counts.astype(jnp.int32)
        vals, _, _ = _segment_sort(flat, labels)
        starts = jnp.cumsum(cnt_i) - cnt_i                        # (K,)
        lo = jnp.clip(starts + (cnt_i - 1) // 2, 0, c - 1)
        hi = jnp.clip(starts + cnt_i // 2, 0, c - 1)
        med = 0.5 * (vals[lo] + vals[hi])                         # (K, n)
        return jnp.where(counts[:, None] > 0, med,
                         jnp.zeros((), flat.dtype))


# --------------------------------------------------------- tree wrappers

def _reduce_leaf(leaf, labels, onehot, counts, aggregator):
    flat = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
    return aggregator(flat, labels, onehot, counts)


def cluster_reduce_tree(params, labels, onehot, counts, aggregator):
    """Step 3 alone through an aggregator: (K', ...) per-cluster
    representatives of a stacked pytree (the server-side state iterative
    methods carry between rounds)."""
    agg = get_aggregator(aggregator)
    k = onehot.shape[1]

    def red(leaf):
        means = _reduce_leaf(leaf, labels, onehot, counts, agg)
        return means.reshape((k,) + leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree_util.tree_map(red, params)


def cluster_aggregate_tree(params, labels, onehot, counts, aggregator):
    """Steps 3-4 through an aggregator: per-cluster reduction of every
    leaf, gathered back per client (``onehot @ reduced``).  With the
    ``mean`` aggregator this is bit-exact with the pre-registry
    ``federated.cluster_average_tree`` path."""
    agg = get_aggregator(aggregator)

    def back(leaf):
        means = _reduce_leaf(leaf, labels, onehot, counts, agg)
        return matmul_f32(onehot, means).reshape(leaf.shape).astype(
            leaf.dtype)

    return jax.tree_util.tree_map(back, params)


# ------------------------------------------------------------- registry

_AGGREGATORS: dict[str, Aggregator] = {}


def register_aggregator(agg: Aggregator, *, name: Optional[str] = None,
                        overwrite: bool = False) -> Aggregator:
    """Register a per-cluster aggregator. Returns it (decorator-safe)."""
    key = name if name is not None else agg.name
    if not key:
        raise ValueError("aggregator needs a non-empty name")
    if key in _AGGREGATORS and not overwrite:
        raise ValueError(f"aggregator {key!r} already registered "
                         "(pass overwrite=True to replace)")
    _AGGREGATORS[key] = agg
    return agg


def unregister_aggregator(name: str) -> None:
    """Remove a registered aggregator (used by tests/plugins)."""
    _AGGREGATORS.pop(name, None)


def get_aggregator(name) -> Aggregator:
    """Resolve a name (or pass through an instance) to an aggregator."""
    if not isinstance(name, str):
        return name
    try:
        return _AGGREGATORS[name]
    except KeyError:
        raise KeyError(f"unknown aggregator {name!r}; "
                       f"registered: {sorted(_AGGREGATORS)}") from None


def list_aggregators() -> tuple[str, ...]:
    """Names of every registered per-cluster aggregator."""
    return tuple(sorted(_AGGREGATORS))


def make_aggregator(name, **options: Any) -> Aggregator:
    """Resolve ``name`` and specialize its dataclass fields from
    ``options`` (unknown keys are ignored, like ``build_federated_method``
    — drivers pass one flat option superset)::

        make_aggregator("trimmed_mean", beta=0.2)
    """
    agg = get_aggregator(name)
    if options and dataclasses.is_dataclass(agg):
        fields = {f.name for f in dataclasses.fields(agg) if f.init}
        kept = {k: v for k, v in options.items()
                if k in fields and k != "name" and v is not None}
        if kept:
            agg = dataclasses.replace(agg, **kept)
    return agg


for _agg in (MeanAggregator(), TrimmedMeanAggregator(), MedianAggregator(),
             GeometricMedianAggregator()):
    register_aggregator(_agg)
del _agg
