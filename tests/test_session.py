"""The streaming AggregationSession (core/engine/session.py).

Pins down the server-API redesign's contracts: wave-partition
invariance (finalize is bit-exact with the fused
``one_shot_aggregate(engine="device")`` round no matter how the same
clients were chunked into ingest waves), sketch-routed serving
(``route`` sends every ingested client to its own recovered cluster and
``cluster_model`` hands back that cluster's averaged model), the
sketch-only ingest mode, and the buffer/mode guard rails.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import AggregationSession
from repro.core.federated import FederatedState, one_shot_aggregate
from repro.optim import adamw_init

from conftest import gathered_rows, same_partition


def make_blobs(seed, sizes, d, sep=25.0, noise=0.25):
    rng = np.random.default_rng(seed)
    k = len(sizes)
    centers = rng.normal(size=(k, d))
    if k > 1:
        dists = np.linalg.norm(centers[:, None] - centers[None], axis=-1)
        np.fill_diagonal(dists, np.inf)
        centers *= sep / dists.min()
    pts = np.concatenate([
        c + noise * rng.normal(size=(n, d)) for c, n in zip(centers, sizes)])
    labels = np.repeat(np.arange(k), sizes)
    return pts.astype(np.float32), labels


def blob_state(pts):
    params = {"theta": jnp.asarray(pts)}
    return FederatedState(params=params,
                          opt_state=jax.vmap(adamw_init)(params),
                          n_clients=len(pts))


def ingest_in_waves(session, pts, pattern):
    """Chunk the client stack into waves by cycling ``pattern``."""
    off, i = 0, 0
    while off < len(pts):
        w = min(pattern[i % len(pattern)], len(pts) - off)
        session.ingest({"theta": jnp.asarray(pts[off:off + w])})
        off += w
        i += 1
    return session


# ------------------------------------- streaming ≡ fused one-shot round

def test_session_finalize_bit_exact_with_fused_round():
    pts, true = make_blobs(0, [9, 7, 11], 8)
    ref_state, ref_labels, ref_info = one_shot_aggregate(
        blob_state(pts), None, algorithm="kmeans-device", k=3,
        sketch_dim=32, seed=3, engine="device")

    sess = AggregationSession(len(pts), sketch_dim=32, seed=3)
    ingest_in_waves(sess, pts, [5, 9, 2])
    new_state, labels, info = sess.finalize(algorithm="kmeans-device", k=3)

    np.testing.assert_array_equal(labels, ref_labels)
    np.testing.assert_array_equal(np.asarray(new_state.params["theta"]),
                                  np.asarray(ref_state.params["theta"]))
    assert info["n_clusters"] == ref_info["n_clusters"]
    assert info["engine"] == "device"
    assert same_partition(labels, true)


def test_session_finalize_convex_family_with_knn_edges():
    pts, true = make_blobs(1, [10, 8, 9], 6, sep=30.0, noise=0.1)
    sess = AggregationSession(len(pts), sketch_dim=24, seed=1)
    ingest_in_waves(sess, pts, [6])
    _, labels, info = sess.finalize(
        algorithm="clusterpath-device",
        algo_options={"edges": "knn", "knn_k": 5, "iters": 300})
    assert info["n_clusters"] == 3
    assert same_partition(labels, true)


def test_session_resolves_lloyd_host_names():
    pts, true = make_blobs(2, [8, 8], 5)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    sess.ingest({"theta": jnp.asarray(pts)})
    _, labels, info = sess.finalize(algorithm="kmeans++", k=2,
                                    engine="device")
    assert info["engine"] == "device"
    assert same_partition(labels, true)


def test_session_host_finalize():
    pts, true = make_blobs(3, [7, 9], 5)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    sess.ingest({"theta": jnp.asarray(pts)})
    new_state, labels, info = sess.finalize(algorithm="kmeans++", k=2,
                                            engine="host")
    assert info["engine"] == "host"
    assert same_partition(labels, true)
    theta = np.asarray(new_state.params["theta"])
    for c in np.unique(labels):
        members = np.where(labels == c)[0]
        np.testing.assert_allclose(
            theta[members],
            np.broadcast_to(pts[members].mean(0), theta[members].shape),
            rtol=1e-5, atol=1e-5)


# --------------------------------------------------- sketch-routed serving

def test_route_self_consistency_and_cluster_model():
    pts, _ = make_blobs(4, [8, 6, 7], 8)
    sess = AggregationSession(len(pts), sketch_dim=32, seed=5)
    ingest_in_waves(sess, pts, [4, 7])
    new_state, labels, _ = sess.finalize(algorithm="kmeans-device", k=3)
    # every ingested client routes to its own recovered cluster
    routed = sess.route(sess.sketches)
    np.testing.assert_array_equal(routed, labels)
    # single-sketch route returns a plain int
    cid = sess.route(sess.sketches[0])
    assert cid == int(labels[0])
    # routing raw parameters sketches them with the session's projection
    cid_p = sess.route(params={"theta": jnp.asarray(pts[0])})
    assert cid_p == int(labels[0])
    # the served cluster model is the routed cluster's averaged model
    model = sess.cluster_model(cid)
    np.testing.assert_array_equal(np.asarray(model["theta"]),
                                  np.asarray(new_state.params["theta"][0]))


def test_mesh_session_matches_single_device_session():
    """The sharded client axis (``mesh=``) serves the same round as a
    session without a mesh: buffers shard over the mesh, clustering and
    routing run on its first device."""
    from jax.sharding import Mesh

    pts, _ = make_blobs(6, [9, 8, 7], 8)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    plain = ingest_in_waves(AggregationSession(len(pts), sketch_dim=16),
                            pts, [5, 9])
    sharded = ingest_in_waves(
        AggregationSession(len(pts), sketch_dim=16, mesh=mesh), pts, [5, 9])
    assert sharded._params["theta"].sharding.spec == ("data",)
    want = plain.finalize(algorithm="kmeans-device", k=3)
    got = sharded.finalize(algorithm="kmeans-device", k=3)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(np.asarray(got[0].params["theta"]),
                               np.asarray(want[0].params["theta"]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(sharded.route(sharded.sketches), want[1])


def test_route_unseen_client_goes_to_nearest_cluster():
    pts, true = make_blobs(5, [10, 10], 6, sep=30.0, noise=0.2)
    # hold out the last client of each cluster
    seen = np.ones(len(pts), bool)
    seen[[9, 19]] = False
    sess = AggregationSession(int(seen.sum()), sketch_dim=24, seed=7)
    sess.ingest({"theta": jnp.asarray(pts[seen])})
    _, labels, _ = sess.finalize(algorithm="kmeans-device", k=2)
    for held in (9, 19):
        cid = sess.route(params={"theta": jnp.asarray(pts[held])})
        neighbours = labels[true[seen] == true[held]]
        assert cid == neighbours[0]          # routed with its own blob


# ------------------------------------------------ modes and guard rails

def test_sketch_only_session_clusters_and_routes_but_has_no_models():
    pts, true = make_blobs(6, [8, 9], 5)
    full = AggregationSession(len(pts), sketch_dim=16, seed=0)
    full.ingest({"theta": jnp.asarray(pts)})
    sk = np.asarray(full.sketches)

    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    sess.ingest(sketches=sk[:5])
    sess.ingest(sketches=sk[5:])
    state, labels, info = sess.finalize(algorithm="kmeans-device", k=2)
    assert state is None
    assert same_partition(labels, true)
    np.testing.assert_array_equal(sess.route(sess.sketches), labels)
    with pytest.raises(ValueError, match="sketch-only"):
        sess.cluster_model(0)
    with pytest.raises(ValueError, match="parameter waves"):
        sess.state()


def test_session_guard_rails():
    sess = AggregationSession(8, sketch_dim=16)
    with pytest.raises(ValueError, match="nothing ingested"):
        sess.finalize()
    with pytest.raises(ValueError, match="finalize"):
        sess.route(np.zeros(16, np.float32))
    with pytest.raises(ValueError, match="exactly one"):
        sess.ingest()
    sess.ingest({"theta": jnp.zeros((3, 4))})
    with pytest.raises(ValueError, match="cannot mix"):
        sess.ingest(sketches=np.zeros((2, 16), np.float32))
    with pytest.raises(ValueError, match="capacity exceeded"):
        sess.ingest({"theta": jnp.zeros((6, 4))})
    with pytest.raises(ValueError, match=r"\(w, 16\)"):
        AggregationSession(8, sketch_dim=16).ingest(
            sketches=np.zeros((2, 8), np.float32))
    assert sess.count == 3
    assert sess.sketches.shape == (3, 16)


def test_empty_batch_guards():
    """Zero-row waves and probes fail loudly instead of tracing a
    zero-size program (or silently serving nothing)."""
    pts, _ = make_blobs(2, [6, 6], 5)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    sess.ingest({"theta": jnp.asarray(pts)})
    sess.finalize(algorithm="kmeans-device", k=2)
    with pytest.raises(ValueError, match="at least one probe"):
        sess.route(np.zeros((0, 16), np.float32))
    with pytest.raises(ValueError, match="at least one client row"):
        sess.sketch_params({"theta": jnp.zeros((0, 5))})
    with pytest.raises(ValueError, match="empty parameter wave"):
        sess.sketch_params({})


def test_snapshot_compute_install_composes_to_finalize():
    """The split server API (snapshot -> compute_round -> install_round)
    is exactly finalize() taken apart: same round bit-for-bit, and the
    snapshot is immune to ingests that land between compute and
    install."""
    pts, _ = make_blobs(9, [10, 8, 9], 6)
    sess = AggregationSession(32, sketch_dim=16, seed=0)
    sess.ingest({"theta": jnp.asarray(pts[:20])})

    ref = AggregationSession(32, sketch_dim=16, seed=0)
    ref.ingest({"theta": jnp.asarray(pts[:20])})
    ref_out = ref.finalize(algorithm="kmeans-device", k=3)

    snap = sess.snapshot()
    assert snap.count == 20 and snap.clock == sess.clock
    out, served = sess.compute_round(snap, algorithm="kmeans-device", k=3)
    # the live buffer moves on BEFORE install: the round stays the
    # snapshot's, and the session knows it is stale (clock mismatch)
    sess.ingest({"theta": jnp.asarray(pts[20:])})
    sess.install_round(out, served)
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.asarray(ref_out[1]))
    np.testing.assert_array_equal(np.asarray(sess.served_round.centers),
                                  np.asarray(ref.served_round.centers))
    assert out[2]["snapshot_clock"] == served.clock < sess.clock
    assert sess.served_round.count == 20
    # finalize_config was captured by compute_round: refinalize covers
    # the grown buffer with the same algorithm/k
    _, labels, info = sess.refinalize()
    assert labels.shape == (len(pts),)
    assert info["snapshot_clock"] == sess.clock


def test_snapshot_requires_data_and_clock_ticks_per_wave():
    sess = AggregationSession(8, sketch_dim=16)
    with pytest.raises(ValueError, match="nothing ingested"):
        sess.snapshot()
    assert sess.clock == 0
    sess.ingest(sketches=np.zeros((2, 16), np.float32))
    sess.ingest(sketches=np.ones((3, 16), np.float32))
    assert sess.clock == 2
    snap = sess.snapshot()
    assert snap.count == 5 and snap.clock == 2
    assert snap.params is None                  # sketch-only session
    np.testing.assert_array_equal(np.asarray(snap.sketches)[:2], 0.0)


def test_rejected_wave_does_not_lock_ingest_mode():
    """A wave that fails validation must leave the session untouched —
    in particular an invalid sketch wave on a fresh session must not
    lock out parameter ingestion (and vice versa)."""
    sess = AggregationSession(8, sketch_dim=16)
    with pytest.raises(ValueError, match=r"\(w, 16\)"):
        sess.ingest(sketches=np.zeros((2, 4), np.float32))
    sess.ingest({"theta": jnp.zeros((2, 4))})      # still allowed
    assert sess.count == 2

    sess2 = AggregationSession(8, sketch_dim=16)
    with pytest.raises(ValueError, match="empty parameter wave"):
        sess2.ingest({})
    sess2.ingest(sketches=np.zeros((2, 16), np.float32))   # still allowed
    assert sess2.count == 2


def test_ingest_after_finalize_serves_stale_round():
    """A mutable server keeps serving the last finalized round while the
    buffer moves on (stale-serving); the next finalize covers the full
    buffer.  Routing before ANY finalize still raises."""
    pts, _ = make_blobs(7, [6, 6], 5)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    sess.ingest({"theta": jnp.asarray(pts[:8])})
    with pytest.raises(ValueError, match="finalize"):
        sess.route(np.zeros(16, np.float32))
    sess.finalize(algorithm="kmeans-device", k=2)
    k_before = sess.n_clusters
    sess.ingest({"theta": jnp.asarray(pts[8:])})
    cid = sess.route(params={"theta": jnp.asarray(pts[0])})
    assert 0 <= cid < k_before                  # stale round still serves
    _, labels, _ = sess.finalize(algorithm="kmeans-device", k=2)
    assert labels.shape == (len(pts),)


def test_session_state_round_trips_into_one_shot():
    """session.state() is the exact stacked federation — feeding it to
    the fused round matches finalize (the simulate.py iterative path)."""
    pts, _ = make_blobs(8, [7, 9], 6)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=2)
    ingest_in_waves(sess, pts, [3, 5])
    st = sess.state()
    assert st.n_clients == len(pts)
    np.testing.assert_array_equal(np.asarray(st.params["theta"]), pts)
    ref_state, ref_labels, _ = one_shot_aggregate(
        st, None, algorithm="kmeans-device", k=2, sketch_dim=16, seed=2,
        engine="device")
    new_state, labels, _ = sess.finalize(algorithm="kmeans-device", k=2)
    np.testing.assert_array_equal(labels, ref_labels)
    np.testing.assert_array_equal(np.asarray(new_state.params["theta"]),
                                  np.asarray(ref_state.params["theta"]))


# ------------------------------------------- hypothesis wave partitions

try:
    import hypothesis  # noqa: F401
    _HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - env-dependent
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(seed=st.integers(0, 10_000),
           sizes=st.lists(st.integers(2, 7), min_size=2, max_size=4),
           d=st.integers(2, 8),
           sketch_dim=st.sampled_from([8, 16, 24]),
           pattern=st.lists(st.integers(1, 7), min_size=1, max_size=5))
    def test_any_wave_partition_is_bit_exact_with_fused_round(
            seed, sizes, d, sketch_dim, pattern):
        """The acceptance property: ANY wave partition of the same
        clients makes finalize() bit-exact with the fused device round —
        same labels, same averaged parameters, bit for bit."""
        pts, _ = make_blobs(seed, sizes, d)
        k = len(sizes)
        ref_state, ref_labels, ref_info = one_shot_aggregate(
            blob_state(pts), None, algorithm="kmeans-device", k=k,
            sketch_dim=sketch_dim, seed=seed % 97, engine="device")

        sess = AggregationSession(len(pts), sketch_dim=sketch_dim,
                                  seed=seed % 97)
        ingest_in_waves(sess, pts, pattern)
        assert sess.count == len(pts)
        new_state, labels, info = sess.finalize(algorithm="kmeans-device",
                                                k=k)
        np.testing.assert_array_equal(labels, ref_labels)
        assert info["n_clusters"] == ref_info["n_clusters"]
        np.testing.assert_array_equal(
            np.asarray(new_state.params["theta"]),
            np.asarray(ref_state.params["theta"]))
        # route() self-consistency rides along on every drawn federation
        np.testing.assert_array_equal(sess.route(sess.sketches), labels)


# ------------------------------------------------------------ obs / drift

def test_session_drift_gauge_and_route_histogram():
    """The drift gauge anchors at finalize and tracks routed traffic:
    routing the session's own members gives drift ~= 1; routing points
    far from every center inflates it.  Route latencies land in the
    ``session.route.ms`` histogram."""
    from repro import obs

    obs.reset()
    pts, _ = make_blobs(3, (12, 12, 12), 8)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    sess.ingest({"theta": jnp.asarray(pts)})
    assert sess.drift is None                  # nothing finalized yet
    sess.finalize(algorithm="kmeans-device", k=3)
    assert sess.drift is None                  # nothing routed yet

    sess.route(sess.sketches)                  # members of the clustering
    assert sess.drift == pytest.approx(1.0, rel=1e-4)

    far = jnp.asarray(np.full((4, 16), 1e3, np.float32))
    sess.route(far)
    assert sess.drift > 1.0

    snap = obs.snapshot()
    h = snap["histograms"]["session.route.ms"]
    assert h["count"] == 2
    assert snap["gauges"]["session.drift"] == pytest.approx(sess.drift)
    assert snap["histograms"]["session.finalize.ms"]["count"] == 1

    # a re-finalize re-anchors: the routed accumulator starts over
    sess.finalize(algorithm="kmeans-device", k=3)
    assert sess.drift is None


def _ingest_case(kind):
    """A session and the one wave under test: ``(session, ingest)``."""
    pts, _ = make_blobs(4, [6, 6], 5)
    if kind == "scatter":
        # the first block ages out, so the wave refills evicted rows
        # out of order and goes through the row-scatter program
        sess = AggregationSession(12, sketch_dim=16, staleness="max_age=1")
        for ids in (range(0, 4), range(4, 8), range(8, 10)):
            sess.ingest({"theta": jnp.asarray(pts[list(ids)])},
                        client_ids=ids)
        return sess, lambda: sess.ingest(
            {"theta": jnp.asarray(pts[:3])}, client_ids=["x", "y", "z"])
    sess = AggregationSession(12, sketch_dim=16)
    if kind == "sketches":
        return sess, lambda: sess.ingest(
            sketches=np.ones((4, 16), np.float32))
    ids = range(4) if kind == "keyed" else None
    return sess, lambda: sess.ingest({"theta": pts[:4]}, client_ids=ids)


@pytest.mark.parametrize("kind", ["keyed", "anonymous", "scatter",
                                  "sketches"])
def test_ingest_wave_splits_into_transfer_and_program(kind):
    """Every wave times its host-to-device transfer and its ingest
    program as two children of ``session.ingest``, which encloses
    both."""
    from repro import obs

    sess, ingest = _ingest_case(kind)
    obs.reset()
    sink = obs.add_sink(obs.ListSink())
    try:
        rows = ingest()
    finally:
        obs.remove_sink(sink)
    if kind == "scatter":
        assert list(rows) != sorted(rows)       # not one contiguous run
    hists = obs.snapshot()["histograms"]
    spans = {e["name"]: e for e in sink.events if e["event"] == "span"}
    for child in ("session.ingest.transfer", "session.ingest.program"):
        assert hists[f"{child}.ms"]["count"] == 1
        assert spans[child]["parent"] == "session.ingest"
    assert hists["session.ingest.ms"]["count"] == 1
    assert (spans["session.ingest.transfer"]["ms"]
            + spans["session.ingest.program"]["ms"]
            <= spans["session.ingest"]["ms"])


@pytest.mark.parametrize("mode", ["params", "sketches"])
def test_finalize_and_refinalize_time_their_host_tail(mode):
    """The round's host tail (label compaction, meta pull, drift
    anchor) is one ``session.materialize`` span per round, cold and
    warm, with or without parameters."""
    from repro import obs

    pts, _ = make_blobs(5, [7, 7], 5)
    sess = AggregationSession(len(pts), sketch_dim=16, seed=0)
    if mode == "params":
        sess.ingest({"theta": jnp.asarray(pts)})
    else:
        sess.ingest(sketches=pts @ np.eye(5, 16, dtype=np.float32))
    obs.reset()
    sess.finalize(algorithm="kmeans-device", k=2)
    sess.refinalize()
    snap = obs.snapshot()["histograms"]
    assert snap["session.materialize.ms"]["count"] == 2
    assert snap["session.refinalize.ms"]["count"] == 1


def _ordering_waves(kind, rng, n_waves=24, w=4, clients=12):
    """``(capacity, waves)`` for the no-sync ordering test: each wave is
    ``(values, client_ids)``, written back to back on one ingest path."""
    cap = n_waves * w if kind == "anonymous" else clients
    waves = []
    for i in range(n_waves):
        if kind == "anonymous":
            ids = None
        elif kind == "keyed":             # whole blocks: contiguous rows
            lo = (i % (clients // w)) * w
            ids = list(range(lo, lo + w))
        else:                             # any clients: scattered rows
            ids = [int(c) for c in rng.choice(clients, w, replace=False)]
        waves.append((rng.normal(size=(w, 16)).astype(np.float32), ids))
    return cap, waves


@pytest.mark.parametrize("kind", ["keyed", "anonymous", "scatter",
                                  "sketches"])
def test_unsynced_waves_land_before_the_snapshot(kind):
    """Waves ingested back to back, with no wait on their programs, are
    all in the next snapshot: every live row holds its last write."""
    cap, waves = _ordering_waves(kind, np.random.default_rng(5))
    sess = AggregationSession(cap, sketch_dim=16, seed=3)
    last = {}
    for values, ids in waves:
        if kind == "sketches":
            rows = sess.ingest(sketches=values, client_ids=ids)
        else:
            rows = sess.ingest({"theta": values}, client_ids=ids)
        if ids is None:
            rows = range(rows, rows + len(values))
        for row, v in zip(rows, values):
            last[int(row)] = v
    snap = sess.snapshot()
    ref = np.stack([last[r] for r in sorted(last)])
    assert snap.count == len(last)
    if kind == "sketches":
        np.testing.assert_array_equal(np.asarray(snap.sketches), ref)
    else:
        np.testing.assert_array_equal(np.asarray(snap.params["theta"]), ref)
        np.testing.assert_allclose(
            np.asarray(snap.sketches),
            np.asarray(sess.sketch_params({"theta": jnp.asarray(ref)})),
            rtol=1e-5, atol=1e-5)


class _Handle:
    """A wave program's handle whose readiness the test sets."""

    def __init__(self):
        self.ready = self.waited = False

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited = self.ready = True
        return self


@pytest.mark.parametrize("mode", ["params", "sketches"])
def test_ingest_waits_only_when_capacity_rows_are_in_flight(mode):
    """With ``capacity = 3w`` and programs that never finish on their
    own, three waves go in without a wait, the fourth waits on the
    oldest program, and a program that finishes makes room again; the
    rows in flight never exceed ``capacity``."""
    from repro import obs

    w = 4
    sess = AggregationSession(3 * w, sketch_dim=16)
    attr = "_ingest_fn" if mode == "params" else "_ingest_sk_fn"
    real, handles = getattr(sess, attr), []

    def program(*args):
        *bufs, _ = real(*args)
        held = w * sum(not h.ready for h in handles)
        assert held + w <= sess.capacity
        handles.append(_Handle())
        return (*bufs, handles[-1])

    setattr(sess, attr, program)

    def wave(block):
        ids = range(block * w, block * w + w)
        values = np.full((w, 16), float(block), np.float32)
        if mode == "params":
            return sess.ingest({"theta": values}, client_ids=ids)
        return sess.ingest(sketches=values, client_ids=ids)

    obs.reset()
    for block in range(3):
        wave(block)
    assert not any(h.waited for h in handles)
    wave(0)                                   # a fourth wave: waits
    assert [h.waited for h in handles] == [True, False, False, False]
    handles[1].ready = True                   # finishes on its own
    wave(1)
    assert not any(h.waited for h in handles[1:])
    hist = obs.snapshot()["histograms"]["session.ingest.in_flight"]
    assert hist["count"] == 5
    assert (hist["min"], hist["max"], hist["sum"]) == (1, 3, 12)


@pytest.mark.parametrize("kind", ["keyed", "anonymous", "scatter",
                                  "sketches"])
def test_ingest_observes_the_programs_in_flight_once_per_wave(kind):
    """``session.ingest.in_flight`` takes one value a wave: the session's
    wave programs still in flight as it returns, this one included."""
    from repro import obs

    sess, ingest = _ingest_case(kind)
    obs.reset()
    ingest()
    ingest()
    hist = obs.snapshot()["histograms"]["session.ingest.in_flight"]
    assert hist["count"] == 2
    assert 0 <= hist["min"] <= hist["max"] <= sess.clock


# ------------------------------------------------ four-device mesh sessions

def _on_four_devices(code: str) -> dict:
    """Run ``code`` in a fresh interpreter on four virtual CPU devices
    (the device count is fixed when JAX starts, so not in this process)
    and return the JSON object it prints last."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(sys.path))
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_session_serves_the_last_uploads_of_back_to_back_keyed_waves():
    """Keyed waves sent back to back, with no wait on their programs, to
    a session sharded over four devices: runs that straddle the shards,
    then re-uploads of scattered clients.  The served models are the
    float64 per-cluster means of every row's last upload, the partition
    is the planted one, and both equal what a session without a mesh
    serves; the opt state stays sharded as the params are."""
    out = _on_four_devices("""
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core.engine import AggregationSession

        rng = np.random.default_rng(11)
        clients, k, d = 48, 3, 12
        truth = rng.permutation(np.arange(clients) % k)
        centres = rng.normal(size=(k, d)) * 20
        draws = [(centres[truth] + rng.normal(size=(clients, d)))
                 .astype(np.float32) for _ in range(2)]
        waves, lo = [], 0
        for w in [5, 7] * 4:
            waves.append(list(range(lo, lo + w)))
            lo += w
        waves += [[int(c) for c in rng.choice(clients, 8, replace=False)]
                  for _ in range(4)]
        mesh = Mesh(np.array(jax.devices()), ("data",))
        out = {}
        for name, m in (("mesh", mesh), ("plain", None)):
            sess = AggregationSession(clients, sketch_dim=16, seed=2,
                                      mesh=m)
            last = np.zeros((clients, d), np.float64)
            for i, ids in enumerate(waves):
                values = draws[i >= 8][ids]
                rows = sess.ingest({"theta": values}, client_ids=ids)
                last[rows] = values
            state, labels, _ = sess.finalize(algorithm="kmeans-device", k=k)
            out[name] = (state, np.asarray(labels), last)
        state, labels, last = out["mesh"]
        means = np.stack([last[truth == c].mean(0) for c in range(k)])
        theta = np.asarray(state.params["theta"])
        plain, plain_labels, _ = out["plain"]
        print(json.dumps({
            "pairs": [[int(a), int(b)] for a, b in zip(labels, truth)],
            "model_err": float(np.abs(theta - means[truth]).max()),
            "labels_as_plain": bool((labels == plain_labels).all()),
            "gap_to_plain": float(np.abs(
                theta - np.asarray(plain.params["theta"])).max()),
            "specs": [str(state.params["theta"].sharding.spec),
                      str(state.opt_state["mu"]["theta"].sharding.spec),
                      str(state.opt_state["nu"]["theta"].sharding.spec)]}))
    """)
    labels, truth = np.array(out["pairs"]).T
    assert same_partition(labels, truth)
    assert out["model_err"] < 1e-4
    assert out["labels_as_plain"]
    assert out["gap_to_plain"] < 1e-5
    assert out["specs"] == ["PartitionSpec('data',)"] * 3


def test_mesh_session_shards_a_wave_the_devices_divide_and_copies_others():
    """A parameter or sketch wave whose rows the four devices divide
    lands as one shard of a quarter of its rows on each device; a wave
    of 6 rows lands whole on each.  The row index lands whole on each."""
    out = _on_four_devices("""
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core.engine import AggregationSession

        mesh = Mesh(np.array(jax.devices()), ("data",))
        placed = []

        def recorded(sess):
            upload = sess._upload

            def record(wave, index):
                out = upload(wave, index)
                placed.append([
                    [[s.device.id, list(s.data.shape)]
                     for s in leaf.addressable_shards]
                    for leaf in jax.tree_util.tree_leaves(out)])
                return out

            sess._upload = record
            return sess

        rng = np.random.default_rng(0)
        values = lambda *shape: rng.normal(size=shape).astype(np.float32)
        params = recorded(AggregationSession(32, sketch_dim=16, mesh=mesh))
        params.ingest({"b": values(8, 3), "w": values(8, 5, 2)},
                      client_ids=range(8))
        params.ingest({"b": values(6, 3), "w": values(6, 5, 2)},
                      client_ids=range(8, 14))
        sketches = recorded(AggregationSession(32, sketch_dim=16, mesh=mesh))
        sketches.ingest(sketches=values(12, 16), client_ids=range(12))
        sketches.ingest(sketches=values(6, 16), client_ids=range(12, 18))
        print(json.dumps(placed))
    """)
    devices = [0, 1, 2, 3]

    def on_each(shape):
        return [[d, list(shape)] for d in devices]

    assert out == [
        [on_each((2, 3)), on_each((2, 5, 2)), on_each((8,))],
        [on_each((6, 3)), on_each((6, 5, 2)), on_each((6,))],
        [on_each((3, 16)), on_each((12,))],
        [on_each((6, 16)), on_each((6,))],
    ]


def test_mesh_session_serves_the_last_uploads_of_sharded_keyed_waves():
    """Keyed waves the four devices divide, each sharded over them and
    sent back to back: runs of rows that straddle the shards, then
    re-uploads of scattered clients.  The served models are the float64
    per-cluster means of every row's last upload, and the partition is
    the planted one."""
    out = _on_four_devices("""
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro import obs
        from repro.core.engine import AggregationSession

        rng = np.random.default_rng(12)
        clients, k, d = 48, 3, 12
        truth = rng.permutation(np.arange(clients) % k)
        centres = rng.normal(size=(k, d)) * 20
        draws = [(centres[truth] + rng.normal(size=(clients, d)))
                 .astype(np.float32) for _ in range(2)]
        waves, lo = [], 0
        for w in [4, 12, 8, 4, 12, 8]:
            waves.append(list(range(lo, lo + w)))
            lo += w
        waves += [[int(c) for c in rng.choice(clients, 8, replace=False)]
                  for _ in range(4)]
        mesh = Mesh(np.array(jax.devices()), ("data",))
        sess = AggregationSession(clients, sketch_dim=16, seed=2, mesh=mesh)
        last = np.zeros((clients, d), np.float64)
        obs.reset()
        for i, ids in enumerate(waves):
            values = draws[i >= 6][ids]
            rows = sess.ingest({"theta": values}, client_ids=ids)
            last[rows] = values
        counters = obs.snapshot()["counters"]
        state, labels, _ = sess.finalize(algorithm="kmeans-device", k=k)
        means = np.stack([last[truth == c].mean(0) for c in range(k)])
        theta = np.asarray(state.params["theta"])
        print(json.dumps({
            "pairs": [[int(a), int(b)] for a, b in zip(labels, truth)],
            "model_err": float(np.abs(theta - means[truth]).max()),
            "placed_per_uploaded": counters["session.ingest.device_bytes"]
                                   / counters["session.ingest.bytes"]}))
    """)
    labels, truth = np.array(out["pairs"]).T
    assert same_partition(labels, truth)
    assert out["model_err"] < 1e-4
    assert out["placed_per_uploaded"] == 1.0


@pytest.mark.parametrize("w,copies,moved", [(16, 1, 3 / 4), (6, 4, 0)])
def test_mesh_session_counts_the_bytes_it_places_and_moves(w, copies, moved):
    """Under a mesh, ``session.ingest.device_bytes`` is what the transfer
    placed: each wave's bytes once where the four devices divide its
    rows and it is sharded over them, its bytes times four where they do
    not and it is copied whole to each.  ``session.mesh.bytes`` is what
    a round moves between them: the sketch rows not on the first device
    yet (three of four shards of 32 live rows; none of 12, whose slice
    of the buffer every device holds whole), and the labels and centres
    copied from it to the other three."""
    out = _on_four_devices("""
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro import obs
        from repro.core.engine import AggregationSession

        w = %d
        mesh = Mesh(np.array(jax.devices()), ("data",))
        sess = AggregationSession(32, sketch_dim=16, mesh=mesh)
        rng = np.random.default_rng(0)
        obs.reset()
        for lo in (0, w):
            sess.ingest({"theta": rng.normal(size=(w, 10))
                         .astype(np.float32)},
                        client_ids=range(lo, lo + w))
        waves = obs.snapshot()["counters"]
        sess.finalize(algorithm="kmeans-device", k=2)
        print(json.dumps({"waves": waves,
                          "after": obs.snapshot()["counters"]}))
    """ % w)
    wave = w * 10 * 4
    assert out["waves"]["session.ingest.bytes"] == 2 * wave
    assert out["waves"]["session.ingest.device_bytes"] == 2 * wave * copies
    assert "session.mesh.bytes" not in out["waves"]
    sketches, labels, centres = 2 * w * 16 * 4, 2 * w * 4, 2 * 16 * 4
    assert out["after"]["session.mesh.bytes"] == (
        sketches * moved + (labels + centres) * 3)


def test_a_session_without_a_mesh_records_no_mesh_counter():
    from repro import obs

    pts, _ = make_blobs(7, [8, 8], 6)
    sess = AggregationSession(len(pts), sketch_dim=16)
    obs.reset()
    sess.ingest({"theta": jnp.asarray(pts)}, client_ids=range(len(pts)))
    sess.finalize(algorithm="kmeans-device", k=2)
    counters = obs.snapshot()["counters"]
    assert counters["session.ingest.bytes"] == pts.nbytes
    assert "session.ingest.device_bytes" not in counters
    assert "session.mesh.bytes" not in counters


def test_mesh_session_writes_waves_without_gathering_the_buffers():
    """Every wave program a session sharded over four devices runs
    (a keyed run of rows, an anonymous wave, scattered keyed rows, a
    sketch wave) writes each shard in place: none gathers a buffer
    across the devices, as XLA does to partition a dynamic slice of the
    sharded client axis.  What a program gathers is the wave's own rows,
    at most: a wave sharded over the devices reaches the shards that own
    its rows that way."""
    out = _on_four_devices("""
        import json
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core.engine import AggregationSession

        mesh = Mesh(np.array(jax.devices()), ("data",))
        ran = []

        def recorded(sess, attr):
            fn = getattr(sess, attr)

            def program(*args):
                ran.append((attr, fn, args))
                return fn(*args)

            setattr(sess, attr, program)

        programs = ("_ingest_fn", "_ingest_scatter_fn", "_ingest_sk_fn",
                    "_ingest_sk_scatter_fn")
        params = AggregationSession(32, sketch_dim=16, mesh=mesh)
        sketches = AggregationSession(32, sketch_dim=16, mesh=mesh)
        for sess in (params, sketches):
            for attr in programs:
                recorded(sess, attr)
        rng = np.random.default_rng(0)
        values = lambda w, d: rng.normal(size=(w, d)).astype(np.float32)
        params.ingest({"theta": values(8, 10)}, client_ids=range(8))
        params.ingest({"theta": values(8, 10)})
        params.ingest({"theta": values(6, 10)}, client_ids=[7, 2, 5, 0, 3, 1])
        sketches.ingest(sketches=values(8, 16), client_ids=range(8))
        sketches.ingest(sketches=values(4, 16), client_ids=[6, 1, 4, 3])
        print(json.dumps([[attr, len(args[-1]),
                           fn.lower(*args).compile().as_text()]
                          for attr, fn, args in ran]))
    """)
    assert [attr for attr, _, _ in out] == [
        "_ingest_scatter_fn"] * 3 + ["_ingest_sk_scatter_fn"] * 2
    for attr, w, hlo in out:
        rows = gathered_rows(hlo, [(10,), (16,)])
        assert 32 not in rows, (attr, rows)
        assert all(r <= w for r in rows), (attr, w, rows)
        assert (w in rows) == (w % 4 == 0), (attr, w, rows)
