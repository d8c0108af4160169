"""AggregationSession — the server side of Algorithm 1 as a long-lived,
streaming, *mutable* service.

The paper's server is not a function call: clients upload sketches over
time, the server clusters once enough arrived, and later traffic is
*routed* — a fresh client is assigned to its nearest recovered cluster
and served that cluster's model (IFCA's serving loop, k-FED's one-shot
estimate).  ``one_shot_aggregate`` compresses all of that into a single
invocation that needs every client's parameters in one stacked pytree;
this module is the stateful redesign:

  * ``ingest(wave)`` / ``ingest(sketches=...)`` — step-1 uploads, wave
    by wave.  Parameter waves are sketched on device (the same vmapped
    JL projection as the fused round) and written into a fixed-capacity
    (capacity, sketch_dim) device buffer; nothing federation-sized ever
    crosses to host.  With ``client_ids=`` the wave is KEYED: a
    host-side slot table maps stable client ids to buffer rows, so a
    returning client's row is replaced in place (sketch and params
    buffers both) instead of appended — ``count`` means live clients,
    not uploads.  Contiguous writes keep the ``dynamic_update_slice``
    fast path; keyed replacements, free-list reuse and every wave into
    buffers sharded over a mesh go through a row-scatter program.
    ``ingest`` returns once the wave is on the device and its program
    is queued, so the next wave's transfer runs under it; up to
    ``capacity`` rows of wave programs stay in flight.
  * staleness — the session advances a logical clock per wave and
    stamps every written row; a pluggable policy
    (``engine/staleness.py``: ``none`` | ``max_age`` sliding window |
    ``exp_decay`` weighting) evicts aged rows back onto a free list
    (masked out of every later finalize) or fades their weight in the
    per-cluster parameter mean.
  * ``finalize(algorithm=..., engine=...)`` — steps 2-4 over the LIVE
    rows: the registered clustering + per-cluster parameter mean.  The
    device path traces the exact ``_cluster_and_average`` body of the
    fused round (``engine/aggregate.py``), so a session fed any wave
    partition of a federation is **bit-exact** with
    ``one_shot_aggregate(engine="device")`` on the same clients — the
    property tests in ``tests/test_session.py`` pin this, re-uploads
    and evictions included.
  * ``maybe_refinalize(threshold=...)`` — the drift gauge (routed
    traffic's inertia over the finalized clustering's own) triggers an
    INCREMENTAL re-finalize: device Lloyd warm-starts from the previous
    round's centers (``init="warm"``), the convex family warm-starts
    its AMA dual — measured as ``session.refinalize.*`` spans vs the
    cold ``session.finalize.*`` ones.
  * ``route(sketch | params)`` — serving: nearest recovered cluster in
    sketch space through ONE fused program per request batch (label
    assignment + drift accumulation, one host sync per batch);
    ``cluster_model(cid)`` hands back that cluster's averaged model.
    Serving keeps working from the last finalized clustering while the
    buffers mutate underneath — that staleness is exactly what the
    drift gauge measures and ``maybe_refinalize`` repairs.

The session is deliberately dumb about *which* clustering runs: it
resolves ``algorithm`` through the admissible registry exactly like
``one_shot_aggregate`` (device twins upgrade host names under
``engine='auto'|'device'``; explicit device names downgrade to their
host base under ``engine='host'``), so every registered family —
including ``convex-device`` with the sparse ``edges="knn"`` fusion
graph — streams the same way.
"""
from __future__ import annotations

from collections import deque
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.clustering.api import (
    device_twin,
    get_algorithm,
    is_device_algorithm,
    meta_to_host,
    resolve_device_request,
    resolve_host_request,
)
from repro.core.engine.aggregate import (
    _cluster_program,
    _gather_rows_program,
    _mean_program,
    _route_program,
    _warm_cluster_program,
    _weighted_mean_program,
    cached_program,
    compact_labels,
    materialize_round,
)
from repro.core.engine.aggregators import (
    cluster_aggregate_tree,
    get_aggregator,
)
from repro.core.engine.staleness import make_staleness_policy
from repro.core.federated import FederatedState
from repro.core.sketch import sketch_tree
from repro.optim import adamw_init


@jax.jit
def _sum_sq_to_assigned(pts, centers, labels):
    """Sum over rows of ||pt - centers[label]||^2 — the inertia of a
    point set against an existing clustering (drift bookkeeping)."""
    return jnp.sum((pts - centers[labels]) ** 2)


@jax.jit
def _mean_row_scale(pts):
    """Mean squared deviation of the rows from their centroid — the
    absolute scale the drift gauge falls back to when the finalized
    inertia itself is degenerate (~0)."""
    centred = pts - jnp.mean(pts, axis=0, keepdims=True)
    return jnp.mean(jnp.sum(centred * centred, axis=1))


class SessionSnapshot(NamedTuple):
    """An immutable view of the live rows at one logical clock tick.

    ``snapshot()`` gathers the live sketch/param rows into standalone
    device arrays; jnp arrays are immutable and every later ingest
    rebinds the session's buffers functionally, so the snapshot stays
    valid while ingest keeps mutating the live buffers underneath —
    the double-buffer half of ingest-while-finalize.  ``clock`` keys
    the serialized-replay equivalence contract: a round computed from
    this snapshot is bit-exact with a sequential replay that finalizes
    right after the ``clock``-th ingested wave.
    """
    sketches: jnp.ndarray          # (count, sketch_dim), live rows only
    params: Optional[object]       # stacked live-params pytree or None
    weights: Optional[object]      # staleness weights or None
    count: int                     # live clients at snapshot time
    clock: int                     # session clock at snapshot time


class ServedRound(NamedTuple):
    """Everything the serving paths read, bundled so a finalize can
    publish its result as ONE attribute write — atomic under the GIL,
    which is what lets a background finalize swap the served round
    while concurrent ``route()`` callers keep reading the old one."""
    out: tuple                     # (state | None, labels, info)
    centers: jnp.ndarray           # (K', sketch_dim) active centers
    first_idx: np.ndarray          # (K',) one member index per cluster
    n_clusters: int
    finalized_d2: float            # mean row d^2 at finalize (drift anchor)
    finalized_scale: float         # mean row scale (degenerate fallback)
    clock: int                     # snapshot clock this round was built from
    count: int                     # snapshot live-client count


class AggregationSession:
    """Streaming, mutable server-side aggregation over a fixed capacity.

    Args:
      capacity: maximum number of live clients (the sketch buffer is
        allocated once at this size; evicted slots are reused).
      sketch_dim: JL sketch width (step-1 upload size per client).
      cfg: optional ``ModelConfig`` — only consulted for the MoE
        router-invariant sketch filter, exactly as in
        ``one_shot_aggregate``.
      seed / cluster_seed: drive the shared JL projection and the
        clustering init (same split as the fused round).
      sketch_transform: optional traceable ``(sk, offset) -> sk`` hook
        applied to every wave's (w, sketch_dim) rows INSIDE the jitted
        ingest — the scenario subsystem's sketch-channel hooks (DP
        Gaussian release, colluding spoof) run here.  ``offset`` is the
        wave's first target row.
      staleness: a policy instance from ``engine/staleness.py`` or a
        spec string (``"none"`` | ``"max_age=3"`` | ``"exp_decay=2.0"``).
      mesh / client_axis: shard the client axis of the buffers, and of
        each upload wave whose rows the axis divides.
    """

    def __init__(self, capacity: int, *, sketch_dim: int = 256, cfg=None,
                 seed: int = 0, cluster_seed: Optional[int] = None,
                 sketch_transform=None, staleness="none",
                 mesh=None, client_axis: str = "data"):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.sketch_dim = int(sketch_dim)
        self.seed = int(seed)
        self.cluster_seed = self.seed if cluster_seed is None else int(
            cluster_seed)
        self.mesh, self.client_axis = mesh, client_axis
        self.staleness = make_staleness_policy(staleness)
        from repro.core.federated import _router_invariant_filter
        self._leaf_filter = (_router_invariant_filter
                             if cfg is not None
                             and getattr(cfg, "is_moe", False) else None)
        self._sketch_key = jax.random.PRNGKey(self.seed)
        self._sketches = self._constrain(
            jnp.zeros((self.capacity, self.sketch_dim), jnp.float32))
        self._params = None            # stacked buffer, lazily allocated
        self._mode: Optional[str] = None    # 'params' | 'sketches'
        # ---- slot table: host-side row bookkeeping -------------------
        self._slots: dict = {}         # client id -> buffer row
        self._row_ids: dict = {}       # buffer row -> client id (keyed only)
        self._live = np.zeros(self.capacity, bool)
        self._stamps = np.zeros(self.capacity, np.int64)
        self._free: list = []          # evicted rows, ready for reuse
        self._high = 0                 # high-water mark of ever-written rows
        self._count = 0                # LIVE clients (not uploads)
        self._clock = 0                # logical time, +1 per ingested wave
        # wave programs queued and not yet known to have run: (handle,
        # rows) in dispatch order, bounded to ``capacity`` rows
        self._in_flight: deque = deque()
        # ---- finalize / serving state --------------------------------
        self._final = None             # round of the CURRENT buffer contents
        self._served: Optional[ServedRound] = None  # atomically-swapped
        self._finalize_kwargs = None   # replayed by refinalize()
        # warm-start cache for the incremental re-finalize
        self._warm_algo_name = None
        self._warm_state = None
        self._warm_count = 0
        # drift bookkeeping: the finalized anchor lives in the served
        # round; these accumulate routed traffic's inertia since the
        # last install — the gauge maybe_refinalize() triggers on
        self._routed_d2_sum = 0.0      # accumulated routed row d^2
        self._routed_n = 0

        def _sketch_wave(wave, offset):
            sk = jax.vmap(
                lambda p: sketch_tree(self._sketch_key, p, self.sketch_dim,
                                      leaf_filter=self._leaf_filter))(wave)
            if sketch_transform is not None:
                sk = sketch_transform(sk, offset)
            return sk

        # every ingest program also returns a small output that no later
        # wave donates, the wave's sketch rows: the session's handle on
        # whether the program has run (``_in_flight``)
        def _ingest(sk_buf, p_buf, wave, offset):
            sk = _sketch_wave(wave, offset)
            sk_buf = self._constrain(
                jax.lax.dynamic_update_slice_in_dim(sk_buf, sk, offset, 0))
            p_buf = jax.tree_util.tree_map(
                lambda b, w: self._constrain(
                    jax.lax.dynamic_update_slice_in_dim(b, w, offset, 0)),
                p_buf, wave)
            return sk_buf, p_buf, sk

        def _ingest_scatter(sk_buf, p_buf, wave, rows):
            sk = _sketch_wave(wave, rows[0])
            sk_buf = self._constrain(sk_buf.at[rows].set(sk))
            p_buf = jax.tree_util.tree_map(
                lambda b, w: self._constrain(b.at[rows].set(w)),
                p_buf, wave)
            return sk_buf, p_buf, sk

        # the sketch-only handle is read back from the written buffer:
        # without a transform the wave's rows are the program's input,
        # which JAX would hand back as it is, ready before the write
        def _ingest_sk(sk_buf, sk, offset):
            if sketch_transform is not None:
                sk = sketch_transform(sk, offset)
            sk_buf = self._constrain(
                jax.lax.dynamic_update_slice_in_dim(sk_buf, sk, offset, 0))
            return sk_buf, jax.lax.dynamic_slice_in_dim(
                sk_buf, offset, sk.shape[0], 0)

        def _ingest_sk_scatter(sk_buf, sk, rows):
            if sketch_transform is not None:
                sk = sketch_transform(sk, rows[0])
            sk_buf = self._constrain(sk_buf.at[rows].set(sk))
            return sk_buf, sk_buf[rows]

        # donate the capacity-sized buffers so XLA updates them in place
        # (a fresh full-size copy per wave would defeat the streaming
        # design); the CPU backend can't donate and would warn per wave
        donate = jax.default_backend() != "cpu"
        self._ingest_fn = jax.jit(_ingest,
                                  donate_argnums=(0, 1) if donate else ())
        self._ingest_scatter_fn = jax.jit(
            _ingest_scatter, donate_argnums=(0, 1) if donate else ())
        self._ingest_sk_fn = jax.jit(_ingest_sk,
                                     donate_argnums=(0,) if donate else ())
        self._ingest_sk_scatter_fn = jax.jit(
            _ingest_sk_scatter, donate_argnums=(0,) if donate else ())
        self._sketch_one = jax.jit(
            lambda p: sketch_tree(self._sketch_key, p, self.sketch_dim,
                                  leaf_filter=self._leaf_filter))

    # ------------------------------------------------------------ ingest

    def _constrain(self, x):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(self.client_axis)))

    def _upload(self, wave, index):
        """Start the host-to-device transfer of an upload: the wave (its
        leaves, or its sketch rows) and its row index.  With no mesh both
        go to the default device, uncommitted, where the jitted ingest
        takes host arrays.  Under a mesh the index is copied to every
        device, and a wave whose leading (wave) axis the client axis
        divides is sharded over it: each device receives its share of
        the rows over its own link, and the ingest program gathers the
        rest over the interconnect.  Any other wave is copied whole to
        every device."""
        if self.mesh is None:
            return jax.device_put((wave, index), None)
        w = jax.tree_util.tree_leaves(wave)[0].shape[0]
        split = w % self.mesh.shape[self.client_axis] == 0
        return jax.device_put((wave, index), (
            NamedSharding(self.mesh, P(self.client_axis) if split else P()),
            NamedSharding(self.mesh, P())))

    def _to_cluster_device(self, sketches):
        """Under a mesh, the clustering and route programs run on the
        mesh's first device: they hold Pallas kernels, which XLA cannot
        partition across devices.  What moves there is a (rows,
        sketch_dim) matrix, small next to the sharded parameter
        buffer; the ``session.mesh.bytes`` counter adds the bytes that
        were not on that device yet."""
        if self.mesh is None:
            return sketches
        first = self.mesh.devices.flat[0]
        obs.count("session.mesh.bytes", sketches.nbytes - sum(
            s.data.nbytes for s in sketches.addressable_shards
            if s.device == first))
        return jax.device_put(sketches, first)

    def _replicate(self, x):
        """Under a mesh, copy a clustering output to every device so the
        sharded parameter reduction can read it; the
        ``session.mesh.bytes`` counter adds the bytes of the copies that
        did not exist yet."""
        if self.mesh is None:
            return x
        n = self.mesh.devices.size
        obs.count("session.mesh.bytes", sum(
            leaf.nbytes * n - sum(s.data.nbytes
                                  for s in leaf.addressable_shards)
            for leaf in jax.tree_util.tree_leaves(x)))
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    @property
    def count(self) -> int:
        """Live clients currently held (re-uploads replace, evictions
        subtract — not a lifetime upload counter)."""
        return self._count

    @property
    def clients(self) -> dict:
        """Copy of the live slot table: client id -> buffer row (keyed
        ingests only; anonymous waves don't appear)."""
        return dict(self._slots)

    def _live_rows(self) -> np.ndarray:
        """Sorted buffer rows currently holding live clients."""
        return np.flatnonzero(self._live[:self._high])

    @property
    def sketches(self) -> jnp.ndarray:
        """Device-resident (count, sketch_dim) view of the live sketch
        rows (a slice while the live set is contiguous, a gather after
        evictions punch holes)."""
        rows = self._live_rows()
        if rows.size == self._high:
            return self._sketches[:self._high]
        return self._sketches[jnp.asarray(rows, jnp.int32)]

    def _validate_params_wave(self, wave, leaves):
        """Structure/shape validation BEFORE any bookkeeping mutates —
        a rejected wave must leave count, buffers, and the finalized
        round exactly as they were."""
        w = int(leaves[0].shape[0])
        if w < 1:
            raise ValueError("empty wave")
        if any(l.shape[0] != w for l in leaves):
            raise ValueError("parameter wave leaves disagree on the "
                             "leading (client) axis")
        if self._params is not None:
            buf_def = jax.tree_util.tree_structure(self._params)
            wave_def = jax.tree_util.tree_structure(wave)
            if buf_def != wave_def:
                raise ValueError(
                    f"wave tree structure {wave_def} does not match the "
                    f"session's first wave {buf_def}")
            for b, l in zip(jax.tree_util.tree_leaves(self._params), leaves):
                if tuple(l.shape[1:]) != tuple(b.shape[1:]):
                    raise ValueError(
                        f"wave leaf shape {tuple(l.shape[1:])} does not "
                        f"match the session's {tuple(b.shape[1:])}")
        return w

    def _alloc_rows(self, w: int, client_ids) -> tuple[np.ndarray, int]:
        """Map a wave onto buffer rows (no mutation on failure).

        Returning client ids keep their row (in-place replace); new ids
        (and anonymous waves) take evicted rows from the free list
        first, then extend the high-water mark.  Returns ``(rows,
        n_new)``; raises on duplicate ids or capacity exhaustion."""
        if client_ids is not None:
            ids = list(client_ids)
            if len(ids) != w:
                raise ValueError(f"client_ids has {len(ids)} entries for a "
                                 f"wave of {w}")
            if len(set(ids)) != len(ids):
                raise ValueError("duplicate client ids within one wave")
        else:
            ids = [None] * w
        rows = np.empty(w, np.int64)
        new_at = []
        for i, cid in enumerate(ids):
            row = self._slots.get(cid) if cid is not None else None
            if row is None:
                new_at.append(i)
            else:
                rows[i] = row
        n_new = len(new_at)
        headroom = len(self._free) + (self.capacity - self._high)
        if n_new > headroom:
            raise ValueError(
                f"session capacity exceeded: {self._count} live + "
                f"{n_new} new clients > capacity {self.capacity}")
        free = list(self._free)
        high = self._high
        for i in new_at:
            if free:
                rows[i] = free.pop()
            else:
                rows[i] = high
                high += 1
        return rows, n_new

    def _commit_rows(self, rows: np.ndarray, client_ids) -> None:
        """Post-write bookkeeping: slot table, free list, stamps, clock."""
        ids = list(client_ids) if client_ids is not None else [None] * len(rows)
        self._clock += 1
        for row, cid in zip(rows, ids):
            row = int(row)
            if not self._live[row]:
                self._count += 1
            self._live[row] = True
            if row in self._free:
                self._free.remove(row)
            if cid is not None:
                self._slots[cid] = row
                self._row_ids[row] = cid
        self._high = max(self._high, int(rows.max()) + 1)
        self._stamps[rows] = self._clock
        self._final = None             # buffer contents left the round
        self.evict_stale()

    def _contiguous(self, rows: np.ndarray) -> bool:
        """Whether a wave takes the ``dynamic_update_slice`` programs:
        its rows are one run, and the buffers are not sharded.  Under a
        mesh XLA partitions a dynamic slice of the sharded client axis
        by gathering the whole buffer onto every device, where the
        row-scatter program writes each shard in place."""
        return self.mesh is None and bool(np.array_equal(
            rows, np.arange(rows[0], rows[0] + len(rows))))

    def ingest(self, wave=None, *, sketches=None, client_ids=None):
        """Ingest one wave of step-1 uploads.

        ``wave`` is a stacked parameter pytree (every leaf has leading
        axis w) or a ``FederatedState``; ``sketches=`` takes an already
        projected (w, sketch_dim) matrix instead (sketch-only servers).
        Modes cannot be mixed within one session: parameter averaging in
        ``finalize`` needs every client's parameters.

        ``client_ids=`` (length-w sequence of stable hashable ids) keys
        the wave: a returning id's buffer row is replaced in place, a
        new id takes a free (possibly previously evicted) row.  Returns
        the (w,) row assignment for keyed waves, the wave's offset for
        anonymous ones.

        Returning acknowledges the wave: it is on the device and its
        ingest program is queued, so its write is ordered before any
        later ``snapshot()`` or read of the buffers (programs on a
        device run in dispatch order, and the buffers are rebound to
        the program's outputs).  The caller may reuse its host arrays.
        The program itself may still be running; a failure of it
        surfaces at the next wait on its outputs (a later wave's wait
        on the in-flight window, a consumer of the next snapshot, or a
        round program), not here.  Programs in flight are bounded to
        ``capacity`` rows: a wave first waits for the oldest until it
        fits, which the ``session.ingest.program`` span times, and the
        ``session.ingest.in_flight`` histogram records the programs
        still in flight, this one included, as it returns.
        """
        if (wave is None) == (sketches is None):
            raise ValueError("pass exactly one of wave= or sketches=")
        if sketches is not None:
            return self._ingest_sketches(sketches, client_ids)
        if isinstance(wave, FederatedState):
            wave = wave.params
        if self._mode == "sketches":
            raise ValueError("session already holds sketch-only waves; "
                             "cannot mix in parameter waves")
        leaves = jax.tree_util.tree_leaves(wave)
        if not leaves:
            raise ValueError("empty parameter wave")
        w = self._validate_params_wave(wave, leaves)
        rows, _ = self._alloc_rows(w, client_ids)
        self._mode = "params"      # only after validation: a rejected
        #                            wave must not lock the mode in
        if self._params is None:
            # the stacked buffer shards its client axis like the sketch
            # buffer: per-device memory stays bounded by the shard
            self._params = jax.tree_util.tree_map(
                lambda l: self._constrain(
                    jnp.zeros((self.capacity,) + l.shape[1:], l.dtype)),
                wave)
        offset = int(rows[0])
        contiguous = self._contiguous(rows)
        ingest_fn = self._ingest_fn if contiguous else self._ingest_scatter_fn

        def queue():
            # the program is queued before the wave lands, as a jitted
            # call on host arrays queues it, so it starts on the
            # transfer's heels
            uploaded = self._upload(
                wave, np.int32(offset) if contiguous
                else np.asarray(rows, np.int32))
            self._sketches, self._params, handle = ingest_fn(
                self._sketches, self._params, *uploaded)
            return uploaded, handle

        placed = self._write_wave(w, offset, "params", queue)
        self._count_wave(sum(l.size * l.dtype.itemsize for l in leaves),
                         placed)
        self._commit_rows(rows, client_ids)
        return rows if client_ids is not None else offset

    def _ingest_sketches(self, sketches, client_ids=None):
        if self._mode == "params":
            raise ValueError("session already holds parameter waves; "
                             "cannot mix in sketch-only waves")
        shape = np.shape(sketches)
        if len(shape) != 2 or shape[1] != self.sketch_dim:
            raise ValueError(f"sketch wave must be (w, {self.sketch_dim}), "
                             f"got {shape}")
        w = int(shape[0])
        if w < 1:
            raise ValueError("empty wave")
        rows, _ = self._alloc_rows(w, client_ids)
        self._mode = "sketches"    # only after validation, as above
        offset = int(rows[0])
        contiguous = self._contiguous(rows)
        ingest_fn = (self._ingest_sk_fn if contiguous
                     else self._ingest_sk_scatter_fn)

        # host rows stay on the host until ``_upload`` places them, so
        # under a mesh each device's share crosses its own link
        sketches = (sketches.astype(jnp.float32)
                    if isinstance(sketches, jax.Array)
                    else np.asarray(sketches, np.float32))

        def queue():                                      # as above
            uploaded = self._upload(
                sketches,
                np.int32(offset) if contiguous
                else np.asarray(rows, np.int32))
            self._sketches, handle = ingest_fn(self._sketches, *uploaded)
            return uploaded, handle

        placed = self._write_wave(w, offset, "sketches", queue)
        self._count_wave(w * self.sketch_dim * 4, placed)
        self._commit_rows(rows, client_ids)
        return rows if client_ids is not None else offset

    def _count_wave(self, nbytes: int, placed) -> None:
        """A wave's bytes, and under a mesh the bytes its transfer placed
        on the devices, summed over the shards of the uploaded wave
        ``placed``: the wave's bytes once where ``_upload`` shards it
        over the devices, its bytes times the devices where it copies
        the wave whole to each."""
        obs.count("session.ingest.bytes", nbytes)
        if self.mesh is not None:
            obs.count("session.ingest.device_bytes", sum(
                s.data.nbytes for leaf in jax.tree_util.tree_leaves(placed)
                for s in leaf.addressable_shards))

    def _write_wave(self, w: int, offset: int, mode: str, queue):
        """The timed part of a wave: wait until the programs in flight
        leave room for ``w`` more rows, then ``queue()`` starts the
        transfer, queues the ingest program and returns ``(uploaded
        arrays, handle)``.  Returns the uploaded wave once every shard
        of it has landed; the program's handle joins ``_in_flight``."""
        with obs.span("session.ingest", wave=w, offset=offset, mode=mode):
            with obs.span("session.ingest.program"):
                self._await_window(w)
            with obs.span("session.ingest.transfer"):
                uploaded, handle = queue()
                jax.block_until_ready(uploaded)
        self._in_flight.append((handle, w))
        self._forget_finished()
        obs.observe("session.ingest.in_flight", len(self._in_flight))
        return uploaded[0]

    def _forget_finished(self) -> None:
        self._in_flight = deque(e for e in self._in_flight
                                if not e[0].is_ready())

    def _await_window(self, w: int) -> None:
        """Bound the wave programs in flight to ``capacity`` rows, one
        full buffer of waves: block on the oldest while they and a wave
        of ``w`` more rows would exceed it."""
        self._forget_finished()
        held = sum(n for _, n in self._in_flight)
        while self._in_flight and held + w > self.capacity:
            handle, n = self._in_flight.popleft()
            handle.block_until_ready()
            held -= n

    # --------------------------------------------------------- staleness

    def evict_stale(self) -> list:
        """Apply the staleness policy's eviction mask to the live rows.

        Evicted rows return to the free list and are masked out of
        every later finalize; returns the evicted client ids (``None``
        placeholders for anonymous rows).  Runs automatically after
        every ingest and before every finalize."""
        rows = self._live_rows()
        if rows.size == 0:
            return []
        ages = self._clock - self._stamps[rows]
        mask = np.asarray(self.staleness.evict(ages), bool)
        evicted = rows[mask]
        if evicted.size == 0:
            return []
        out = []
        for row in evicted:
            row = int(row)
            cid = self._row_ids.pop(row, None)
            if cid is not None:
                del self._slots[cid]
            self._live[row] = False
            self._free.append(row)
            out.append(cid)
        self._count -= len(out)
        self._final = None
        obs.count("session.evictions", len(out))
        return out

    def _live_weights(self, rows: np.ndarray):
        """Per-row staleness weights in live-row (gathered) order, or
        ``None`` for unweighted policies."""
        ages = self._clock - self._stamps[rows]
        return self.staleness.weights(ages)

    # ---------------------------------------------------------- finalize

    def snapshot(self) -> SessionSnapshot:
        """Atomically capture the live rows at the current clock.

        The returned arrays are standalone (immutable jnp values; the
        session rebinds its buffers functionally on every ingest), so a
        finalize computed from a snapshot on a background thread stays
        bit-exact even while ingest keeps mutating the live buffers —
        the double-buffer half of ingest-while-finalize.  Callers that
        ingest from multiple threads must serialize ``ingest`` and
        ``snapshot`` against each other (``serving.RouteServer`` does)
        so the snapshot lands between wave commits at a definite clock.
        """
        self.evict_stale()
        if self._count == 0:
            raise ValueError("nothing ingested")
        rows = self._live_rows()
        if rows.size == self._high:
            sketches = self._sketches[:self._high]
            params = (None if self._params is None else
                      jax.tree_util.tree_map(lambda l: l[:self._high],
                                             self._params))
        else:
            rows_j = jnp.asarray(rows, jnp.int32)
            sketches, params = cached_program(_gather_rows_program)(
                (self._sketches, self._params), rows_j)
        if jax.default_backend() != "cpu":
            # ingest donates the capacity buffers on accelerator
            # backends; force materialized copies so the snapshot never
            # aliases memory a later wave is allowed to overwrite
            sketches = jnp.array(sketches, copy=True)
            if params is not None:
                params = jax.tree_util.tree_map(
                    lambda l: jnp.array(l, copy=True), params)
        return SessionSnapshot(sketches=sketches, params=params,
                               weights=self._live_weights(rows),
                               count=self._count, clock=self._clock)

    def finalize(self, *, algorithm="kmeans-device", k: Optional[int] = None,
                 algo_options: Optional[dict] = None,
                 engine: str = "device", aggregator="mean"):
        """Steps 2-4 over the live rows: cluster the accumulated sketch
        matrix, average parameters per recovered cluster.

        Returns ``(new_state, labels, info)`` with the same contract as
        ``one_shot_aggregate`` (``new_state is None`` for sketch-only
        sessions, which have nothing to average — labels/centers still
        come back and routing becomes available).  The device path is
        bit-exact with the fused round on the same clients.
        ``aggregator`` selects the per-cluster parameter reduction from
        the registry (``mean`` | ``trimmed_mean`` | ``median`` |
        ``geometric_median`` | an ``Aggregator`` instance) on both
        engines.  The call's arguments are remembered: ``refinalize()``
        / ``maybe_refinalize()`` replay them warm-started.

        Equivalent to ``finalize_snapshot(self.snapshot(), ...)`` —
        concurrent servers take the snapshot under their ingest lock
        and run the compute off-thread instead.
        """
        return self.finalize_snapshot(
            self.snapshot(), algorithm=algorithm, k=k,
            algo_options=algo_options, engine=engine, aggregator=aggregator)

    def refinalize(self):
        """Re-run the last ``finalize`` configuration over the current
        live rows, warm-starting the clustering from the previous
        round's state when the family supports it (Lloyd restarts from
        the old centers, AMA from its old dual; cold fallback
        otherwise).  Requires a prior ``finalize()``."""
        if self._finalize_kwargs is None:
            raise ValueError("refinalize() needs a prior finalize()")
        return self.finalize_snapshot(self.snapshot(), warm=True,
                                      **self._finalize_kwargs)

    def maybe_refinalize(self, threshold: float = 1.5):
        """Drift-triggered incremental re-finalize: when the ``drift``
        gauge (routed-traffic inertia over finalized inertia) exceeds
        ``threshold``, replay the last finalize warm-started and
        re-anchor the gauge.  Returns the new round, or ``None`` when
        drift is below threshold (or unmeasured)."""
        d = self.drift
        if d is None or d <= threshold:
            return None
        obs.count("session.refinalize.triggered")
        return self.refinalize()

    def finalize_snapshot(self, snap: SessionSnapshot, *, warm: bool = False,
                          **kwargs):
        """Compute a round from ``snap`` and publish it: the synchronous
        compose of ``compute_round`` + ``install_round``.  Accepts the
        same keyword arguments as ``finalize``."""
        out, served = self.compute_round(snap, warm=warm, **kwargs)
        return self.install_round(out, served)

    def compute_round(self, snap: SessionSnapshot, *, warm: bool = False,
                      algorithm="kmeans-device", k: Optional[int] = None,
                      algo_options: Optional[dict] = None,
                      engine: str = "device", aggregator="mean"):
        """Steps 2-4 over a snapshot WITHOUT touching the serving state.

        Returns ``(out, served)`` where ``out`` is the usual round tuple
        and ``served`` is the ``ServedRound`` that ``install_round``
        publishes.  Safe to run on a background thread while ingest and
        route keep going (the warm-start cache is the one piece of
        shared mutable state — concurrent ``compute_round`` calls must
        be serialized by the caller, as ``RouteServer`` does with its
        finalize lock)."""
        if engine not in ("auto", "host", "device"):
            raise ValueError(f"engine must be auto|host|device, got "
                             f"{engine!r}")
        kwargs = dict(algorithm=algorithm, k=k, algo_options=algo_options,
                      engine=engine, aggregator=aggregator)
        if engine == "host":
            # explicit device names downgrade to their host base (or
            # raise for twin-less device-only families) instead of
            # silently running the device loop under engine='host'
            algorithm, algo_options = resolve_host_request(
                algorithm, algo_options)
        else:
            # the legacy Lloyd-name mapping (kmeans++ -> kmeans-device
            # with init='kmeans++'), shared with ODCLFederated; raises
            # for host-only no-twin names under engine='device'
            algorithm, algo_options = resolve_device_request(
                algorithm, algo_options, strict=engine == "device")
        algo = get_algorithm(algorithm)
        dev = algo if is_device_algorithm(algo) else device_twin(algo)
        use_device = engine != "host" and dev is not None
        if use_device:
            algo = dev
        k_eff = k if algo.requires_k else None
        span = "session.refinalize" if warm else "session.finalize"
        with obs.span(span, count=snap.count,
                      algorithm=getattr(algo, "name", str(algo)),
                      engine="device" if use_device else "host"):
            if use_device:
                out, served = self._finalize_device(
                    algo, k_eff, algo_options, snap, aggregator, warm)
            else:
                out, served = self._finalize_host(
                    algo, k_eff, algo_options, snap, aggregator)
        self._finalize_kwargs = kwargs
        return out, served

    def install_round(self, out, served: ServedRound):
        """Publish a computed round: ONE attribute write swaps what
        ``route()`` / ``cluster_model()`` serve (atomic under the GIL),
        and the drift gauge re-anchors on the new round.  ``_final``
        (the this-round-matches-the-buffer marker) is only set when the
        snapshot's clock is still current — a round computed while
        ingest kept mutating stays served but is known stale."""
        self._served = served
        self._routed_d2_sum = 0.0
        self._routed_n = 0
        if served.clock == self._clock:
            self._final = out
        return out

    def _warm_usable(self, algo, warm: bool, count: int) -> bool:
        if not warm or self._warm_state is None:
            return False
        if getattr(algo, "name", None) != self._warm_algo_name:
            return False
        if not callable(getattr(algo, "device_warm_call", None)):
            return False
        if (getattr(algo, "warm_requires_same_count", False)
                and count != self._warm_count):
            obs.count("session.refinalize.cold_fallback")
            return False
        return True

    def _cache_warm_state(self, algo, res, count: int) -> None:
        if not callable(getattr(algo, "device_warm_call", None)):
            return
        state = algo.warm_state(res)
        if state is not None:
            self._warm_algo_name = getattr(algo, "name", None)
            self._warm_state = state
            self._warm_count = count

    def _average_params(self, res, params, aggregator, weights):
        """The finalize's parameter-averaging phase: the shared
        unweighted mean program (bit-exact with the fused round) unless
        the staleness policy supplies decay weights."""
        labels, centers = self._replicate((res.labels, res.centers))
        if weights is None:
            return cached_program(_mean_program, self.mesh,
                                  self.client_axis,
                                  get_aggregator(aggregator))(
                labels, centers, params)
        if get_aggregator(aggregator).name != "mean":
            raise ValueError(
                "staleness weighting (exp_decay) requires the 'mean' "
                f"aggregator, got {get_aggregator(aggregator).name!r}")
        return cached_program(_weighted_mean_program, self.mesh,
                              self.client_axis)(
            labels, centers, params,
            self._replicate(jnp.asarray(weights, jnp.float32)))

    def _finalize_device(self, algo, k, algo_options, snap, aggregator,
                         warm):
        sketches, params = self._to_cluster_device(snap.sketches), snap.params
        cluster_key = jax.random.PRNGKey(self.cluster_seed)
        opts = tuple(sorted((algo_options or {}).items()))
        # the cluster and mean phases run as two AOT programs (labels /
        # centers stay on device between them) so the obs layer sees the
        # finalize latency split; the warm path swaps only the cluster
        # program (the mean phase is identical either way)
        if self._warm_usable(algo, warm, snap.count):
            res = cached_program(_warm_cluster_program, algo, k, opts)(
                cluster_key, sketches, self._warm_state)
            mode = "warm"
        else:
            res = cached_program(_cluster_program, algo, k, opts)(
                cluster_key, sketches)
            mode = "cold"
        self._cache_warm_state(algo, res, snap.count)
        if params is not None:
            new_params = self._average_params(res, params, aggregator,
                                              snap.weights)
        # the round's host tail: label compaction, meta pull, opt-state
        # init and drift anchor, each sync waiting on queued device work
        with obs.span("session.materialize"):
            if params is None:
                new_state = None
                labels, uniq, first = compact_labels(res.labels)
                info = {"n_clusters": int(len(uniq)),
                        "meta": meta_to_host(res.meta), "engine": "device"}
            else:
                state = FederatedState(params=params, opt_state=None,
                                       n_clients=snap.count, step=0)
                new_state, labels, info, uniq, first = materialize_round(
                    new_params, res, state)
            info["count"] = snap.count
            info["refinalize"] = mode if warm else None
            info["snapshot_clock"] = snap.clock
            out = (new_state, labels, info)
            served = self._make_served(out, res.centers[jnp.asarray(uniq)],
                                       first, int(len(uniq)), sketches,
                                       res.centers, res.labels, snap)
        return out, served

    def _make_served(self, out, centers, first_idx, n_clusters, sketches,
                     all_centers, labels, snap) -> ServedRound:
        """Bundle a computed round with its drift anchor (the finalized
        clustering's mean per-row inertia, plus the absolute row scale
        as the degenerate-inertia fallback) into the one value
        ``install_round`` swaps in."""
        finalized_d2 = float(
            _sum_sq_to_assigned(sketches, all_centers, jnp.asarray(labels))
        ) / max(snap.count, 1)
        return ServedRound(out=out, centers=centers,
                           first_idx=np.asarray(first_idx),
                           n_clusters=int(n_clusters),
                           finalized_d2=finalized_d2,
                           finalized_scale=float(_mean_row_scale(sketches)),
                           clock=snap.clock, count=snap.count)

    def _finalize_host(self, algo, k, algo_options, snap, aggregator):
        from repro.core.odcl import run_clustering

        sketches, params, weights = snap.sketches, snap.params, snap.weights
        with obs.span("session.finalize.cluster", engine="host"):
            result = run_clustering(jax.random.PRNGKey(self.cluster_seed),
                                    np.asarray(sketches), algo, k=k,
                                    **(algo_options or {}))
        labels, _, first = compact_labels(result.labels)
        info = {"n_clusters": result.n_clusters, "meta": result.meta,
                "engine": "host", "count": snap.count,
                "snapshot_clock": snap.clock}
        centers = jnp.asarray(result.centers, jnp.float32)
        labels_j = jnp.asarray(labels)
        if params is None:
            out = (None, labels, info)
            served = self._make_served(out, centers, first,
                                       result.n_clusters, sketches, centers,
                                       labels_j, snap)
            return out, served
        with obs.span("session.finalize.mean", engine="host"):
            if weights is not None:
                if get_aggregator(aggregator).name != "mean":
                    raise ValueError(
                        "staleness weighting (exp_decay) requires the "
                        "'mean' aggregator")
                new_params = cached_program(
                    _weighted_mean_program, self.mesh, self.client_axis)(
                    labels_j, centers, params,
                    jnp.asarray(weights, jnp.float32))
            else:
                onehot = jax.nn.one_hot(labels_j, result.n_clusters,
                                        dtype=jnp.float32)
                counts = jnp.sum(onehot, axis=0)
                new_params = cluster_aggregate_tree(params, labels_j, onehot,
                                                    counts, aggregator)
            jax.block_until_ready(new_params)
        new_state = FederatedState(
            params=new_params, opt_state=jax.vmap(adamw_init)(new_params),
            n_clients=snap.count, step=0)
        out = (new_state, labels, info)
        served = self._make_served(out, centers, first, result.n_clusters,
                                   sketches, centers, labels_j, snap)
        return out, served

    # ------------------------------------------------------------- serve

    def route(self, sketch=None, *, params=None):
        """Assign a (possibly never-seen) client to its nearest recovered
        cluster — the serving-time step 4.

        Pass either a (sketch_dim,) / (n, sketch_dim) sketch or a raw
        parameter pytree (sketched with the session's own projection).
        The whole batch runs as ONE fused program (nearest-center
        assignment + the drift accumulator), with a single host sync per
        batch; returns an int (or (n,) int array).  Serving stays on the
        LAST finalized clustering even while later ingests/evictions
        mutate the buffers — ``drift`` measures how stale that is, and
        ``maybe_refinalize`` repairs it.
        """
        served = self._served
        if served is None:
            raise ValueError("route() needs finalize() first")
        if (sketch is None) == (params is None):
            raise ValueError("pass exactly one of sketch or params=")
        if params is not None:
            sketch = self._sketch_one(params)
        sketch = jnp.asarray(sketch, jnp.float32)
        single = sketch.ndim == 1
        pts = self._to_cluster_device(sketch[None] if single else sketch)
        n = int(pts.shape[0])
        if n == 0:
            # tracing a zero-row assign program would succeed and cache
            # a useless signature; fail loudly instead
            raise ValueError("route() needs at least one probe "
                             "(got an empty batch)")
        with obs.span("session.route", n=n):
            labels, batch_d2 = cached_program(_route_program)(
                pts, served.centers)
            # one transfer for both outputs — the route hot path's only
            # host sync (asserted by tests/test_session_mutation.py)
            out, batch_d2 = jax.device_get((labels, batch_d2))
            out = np.asarray(out)
            batch_d2 = float(batch_d2)
        obs.count("session.route.requests", n)
        # drift gauge: routed traffic's mean d^2 to its assigned center,
        # relative to the finalized clustering's own mean d^2 — the
        # trigger signal of maybe_refinalize(); accumulated on device
        # inside the route program, synced once per batch
        self._routed_d2_sum += batch_d2
        self._routed_n += n
        d = self.drift
        if d is not None:
            obs.gauge("session.drift", d)
        return int(out[0]) if single else out

    def sketch_params(self, wave):
        """Sketch a stacked parameter wave (leading axis = clients) with
        the session's own JL projection, WITHOUT ingesting — the input
        shape batched ``route()`` consumes for request batches."""
        leaves = jax.tree_util.tree_leaves(wave)
        if not leaves:
            raise ValueError("empty parameter wave")
        if int(leaves[0].shape[0]) == 0:
            raise ValueError("sketch_params() needs at least one client "
                             "row (got an empty wave)")
        return jax.vmap(self._sketch_one)(wave)

    def cluster_model(self, cluster_id: int):
        """The averaged model of one recovered cluster (a single-model
        pytree, no leading client axis) — what a routed client is served.
        """
        served = self._served
        if served is None:
            raise ValueError("cluster_model() needs finalize() first")
        state = served.out[0]
        if state is None:
            raise ValueError("sketch-only session holds no parameters")
        cid = int(cluster_id)
        if not 0 <= cid < served.n_clusters:
            # a negative id would silently wrap to another cluster's row
            raise IndexError(
                f"cluster id {cid} out of range for {served.n_clusters} "
                "recovered clusters")
        idx = int(served.first_idx[cid])
        return jax.tree_util.tree_map(lambda l: l[idx], state.params)

    @property
    def clock(self) -> int:
        """Logical session time: +1 per ingested wave.  The key of the
        serialized-replay equivalence contract — a snapshot at clock t
        replays as 'finalize right after the t-th wave'."""
        return self._clock

    @property
    def served_round(self) -> Optional[ServedRound]:
        """The ``ServedRound`` route() currently reads (``None`` before
        the first finalize) — one immutable value, so concurrent readers
        see a consistent centers/first_idx/drift-anchor bundle."""
        return self._served

    @property
    def finalize_config(self) -> Optional[dict]:
        """The last finalize()'s arguments (what refinalize replays),
        or ``None`` before any finalize."""
        return (None if self._finalize_kwargs is None
                else dict(self._finalize_kwargs))

    @property
    def n_clusters(self) -> int:
        """Recovered cluster count of the clustering currently served."""
        served = self._served
        if served is None:
            raise ValueError("finalize() first")
        return served.n_clusters

    @property
    def route_centers(self) -> jnp.ndarray:
        """(K', sketch_dim) active cluster centers (device-resident)."""
        served = self._served
        if served is None:
            raise ValueError("finalize() first")
        return served.centers

    @property
    def drift(self) -> Optional[float]:
        """Routed-traffic inertia relative to the finalized clustering's
        own inertia: (mean routed row d^2) / (mean finalized row d^2).

        ~1.0 means serving traffic looks like the federation that was
        clustered; growth means the recovered centers are going stale —
        the signal ``maybe_refinalize`` triggers on.  A degenerate
        finalize (zero inertia: duplicate/tight sketches, k == count)
        falls back to the absolute sketch-row scale as denominator so
        the gauge cannot explode to ~1e12 and mis-trigger.  ``None``
        until at least one finalize and one route happened.
        """
        served = self._served
        if served is None or self._routed_n == 0:
            return None
        routed = self._routed_d2_sum / self._routed_n
        scale = served.finalized_scale or 0.0
        if served.finalized_d2 > 1e-9 * max(scale, 1e-30):
            return routed / served.finalized_d2
        return routed / max(scale, 1e-12)

    # ------------------------------------------------------------- state

    def state(self) -> FederatedState:
        """The live federation as a stacked ``FederatedState`` — feeds
        any registered ``FederatedMethod`` (how ``simulate.py`` runs
        iterative baselines over a streamed-in federation)."""
        if self._mode != "params":
            raise ValueError("state() needs parameter waves")
        rows = self._live_rows()
        if rows.size == self._high:
            params = jax.tree_util.tree_map(lambda l: l[:self._high],
                                            self._params)
        else:
            params = jax.tree_util.tree_map(
                lambda l: l[jnp.asarray(rows, jnp.int32)], self._params)
        return FederatedState(params=params,
                              opt_state=jax.vmap(adamw_init)(params),
                              n_clients=self._count)
