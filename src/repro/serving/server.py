"""RouteServer — ``AggregationSession`` behind a thread-safe, batching
serving frontend.

The session (``core/engine/session.py``) is a single-threaded object;
this module is what makes it a *server*: concurrent callers submit
sketch / parameter route requests, a batcher thread coalesces them into
ONE fused batched ``route()`` program per flush, and finalize runs on
an atomically-snapshotted buffer in a background worker while ingest
keeps mutating the live one (double-buffered ingest-while-finalize).

Locking model — three locks, never nested except as noted:

* ``_ingest_lock`` serializes ``ingest`` against ``snapshot``: every
  snapshot lands between wave commits at a definite session clock,
  which is what makes the serialized-replay contract hold (any
  interleaving of ingest/route/finalize serves a round bit-exact with
  the sequential replay "same keyed ingests in clock order, finalize
  right after wave ``snapshot_clock``").
* ``_serve_lock`` serializes the batcher's ``session.route`` call
  against ``install_round`` — the served-round swap and the drift
  accumulators stay consistent; route callers themselves never hold it
  (they only wait on futures).
* ``_finalize_lock`` admits ONE finalize/refinalize at a time (the
  warm-start cache is shared mutable state); ``maybe_refinalize`` uses
  a non-blocking acquire so the drift-triggered path is a no-op while
  a round is already in flight.

Example — serving while uploading::

    from repro.core.engine import AggregationSession
    from repro.serving import RouteServer

    session = AggregationSession(capacity=4096, sketch_dim=64)
    session.ingest(sketches=first_wave)
    session.finalize(algorithm="kmeans-device", k=8)

    with RouteServer(session, max_batch=64, max_wait_ms=2.0) as srv:
        fut = srv.submit(probe_sketch)           # non-blocking
        cid = fut.result(timeout=1.0)            # -> cluster id
        cid2 = srv.route(another_sketch)         # submit + wait
        srv.ingest(sketches=next_wave,           # safe during routing
                   client_ids=ids)
        srv.refinalize(background=True)          # ingest keeps going
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from repro import obs
from repro.serving.batching import (
    BackpressureError,
    RequestQueue,
    RouteFuture,
    RouteTimeout,
    ServerClosed,
    ServingError,
    _Request,
)

__all__ = [
    "RouteServer",
    "RouteFuture",
    "BackpressureError",
    "RouteTimeout",
    "ServerClosed",
    "ServingError",
]


class RouteServer:
    """Concurrent serving frontend over one ``AggregationSession``.

    Args:
      session: the session to serve (finalized or not — routes fail
        with the session's own ``ValueError`` until a round exists).
      max_batch: largest number of requests fused into one route
        program dispatch.
      max_wait_ms: micro-batching window — how long a flush waits past
        its head request for stragglers.  ``0`` flushes immediately
        (per-arrival batching only under concurrency).
      queue_depth: bound of the request queue; a full queue applies
        backpressure.
      block_on_full: full-queue behavior of ``submit`` — block until
        space (default) or raise ``BackpressureError`` immediately.
    """

    def __init__(self, session, *, max_batch: int = 64,
                 max_wait_ms: float = 2.0, queue_depth: int = 256,
                 block_on_full: bool = True, pad_buckets: bool = True):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if max_wait_ms < 0:
            raise ValueError("max_wait_ms must be >= 0")
        self.session = session
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_ms) / 1e3
        self.block_on_full = bool(block_on_full)
        # the route program AOT-compiles per (batch, dim) signature; a
        # flush of every size 1..max_batch would recompile continuously,
        # so pad flushes up to the next power of two (repeating the last
        # probe; extra labels are discarded) — at most log2(max_batch)+1
        # signatures ever compile
        self.pad_buckets = bool(pad_buckets)
        self._queue = RequestQueue(queue_depth)
        self._ingest_lock = threading.Lock()
        self._serve_lock = threading.Lock()
        self._finalize_lock = threading.Lock()
        self._batcher: Optional[threading.Thread] = None
        self._closed = False

    # ---------------------------------------------------------- lifecycle

    def start(self) -> "RouteServer":
        """Start the batcher thread (idempotent)."""
        if self._closed:
            raise ServerClosed("server already stopped")
        if self._batcher is None:
            self._batcher = threading.Thread(
                target=self._batcher_loop, name="repro-route-batcher",
                daemon=True)
            self._batcher.start()
        return self

    def stop(self, *, drain: bool = True) -> None:
        """Stop taking requests and shut the batcher down.

        ``drain=True`` (default) flushes the queued backlog first;
        ``drain=False`` fails queued requests with ``ServerClosed``.
        Waits for any in-flight background finalize to land either way.
        """
        self._closed = True
        dropped = self._queue.stop(drop=not drain)
        for req in dropped:
            req.future.set_error(
                ServerClosed("server stopped before this request ran"))
        if self._batcher is not None:
            self._batcher.join()
            self._batcher = None
        # wait out an in-flight background finalize so stop() leaves no
        # worker mutating the session behind the caller's back
        self._finalize_lock.acquire()
        self._finalize_lock.release()

    def __enter__(self) -> "RouteServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=True)

    # ------------------------------------------------------------- routes

    def submit(self, sketch=None, *, params=None,
               timeout: Optional[float] = None) -> RouteFuture:
        """Enqueue one route request; returns its ``RouteFuture``.

        Pass a ``(sketch_dim,)`` sketch or a single parameter pytree
        (sketched with the session's own projection).  ``timeout``
        bounds BOTH the backpressure wait (when ``block_on_full``) and
        the request's serving deadline — an expired request resolves
        with ``RouteTimeout`` instead of occupying a flush.
        """
        if self._closed:
            raise ServerClosed("server already stopped")
        if (sketch is None) == (params is None):
            raise ValueError("pass exactly one of sketch or params=")
        if params is not None:
            import jax
            wave = jax.tree_util.tree_map(lambda l: l[None], params)
            sketch = self.session.sketch_params(wave)[0]
        sk = np.asarray(sketch, np.float32)
        if sk.shape != (self.session.sketch_dim,):
            raise ValueError(
                f"route sketch must be ({self.session.sketch_dim},), "
                f"got {sk.shape}")
        now = time.monotonic()
        future = RouteFuture()
        req = _Request(sk, future, now,
                       None if timeout is None else now + timeout)
        self._queue.put(req, block=self.block_on_full, timeout=timeout)
        obs.count("serving.requests")
        return future

    def route(self, sketch=None, *, params=None,
              timeout: Optional[float] = None) -> int:
        """Submit one request and wait for its cluster id — what a
        serving caller thread runs in a loop."""
        return self.submit(sketch, params=params,
                           timeout=timeout).result(timeout)

    def route_direct(self, sketch):
        """Per-request baseline: one route program dispatch for this
        caller alone, bypassing the queue/batcher — what the loadgen
        compares cross-caller batching against."""
        with self._serve_lock:
            return self.session.route(sketch)

    # ------------------------------------------------------------- ingest

    def ingest(self, wave=None, *, sketches=None, client_ids=None):
        """Thread-safe ingest; returns ``(rows_or_offset, clock)`` where
        ``clock`` is the session clock right after this wave's commit —
        the replay key of the serialized-equivalence contract.

        The return acknowledges the wave as ``AggregationSession.ingest``
        does: it is on the device and its write is ordered before every
        later snapshot, while its ingest program may still run; a failed
        program surfaces at the next wait on its outputs.  So the lock
        is held for the wait on the in-flight window and the transfer
        (the ``session.ingest.program`` and ``.transfer`` spans), not for
        the program."""
        with self._ingest_lock:
            result = self.session.ingest(wave, sketches=sketches,
                                         client_ids=client_ids)
            return result, self.session.clock

    # ----------------------------------------------------------- finalize

    def finalize(self, *, background: bool = False, **kwargs):
        """Snapshot-and-finalize.  Synchronous by default (returns the
        round tuple); with ``background=True`` the compute runs on a
        worker thread while ingest/route continue, and a ``RouteFuture``
        resolving to the round is returned.  Raises ``ServingError`` if
        another finalize is already in flight."""
        return self._start_round(warm=False, kwargs=kwargs,
                                 background=background)

    def refinalize(self, *, background: bool = False):
        """Replay the last finalize configuration warm-started (same
        sync/background split as ``finalize``)."""
        cfg = self.session.finalize_config
        if cfg is None:
            raise ValueError("refinalize() needs a prior finalize()")
        return self._start_round(warm=True, kwargs=cfg,
                                 background=background)

    def maybe_refinalize(self, threshold: float = 1.5, *,
                         background: bool = True):
        """Drift-triggered warm re-finalize; ``None`` when drift is
        below threshold, unmeasured, or a finalize is already running
        (non-blocking — safe to call from a periodic ticker)."""
        d = self.session.drift
        if d is None or d <= threshold:
            return None
        cfg = self.session.finalize_config
        if cfg is None:
            return None
        obs.count("session.refinalize.triggered")
        return self._start_round(warm=True, kwargs=cfg,
                                 background=background, non_blocking=True)

    def _start_round(self, *, warm: bool, kwargs: dict, background: bool,
                     non_blocking: bool = False):
        if not self._finalize_lock.acquire(blocking=not non_blocking):
            return None
        try:
            with self._ingest_lock:
                snap = self.session.snapshot()
        except BaseException:
            self._finalize_lock.release()
            raise
        if not background:
            try:
                return self._run_round(snap, warm, kwargs)
            finally:
                self._finalize_lock.release()
        future = RouteFuture()
        worker = threading.Thread(
            target=self._round_worker, args=(snap, warm, kwargs, future),
            name="repro-finalize-worker", daemon=True)
        worker.start()
        return future

    def _round_worker(self, snap, warm, kwargs, future):
        try:
            future.set_result(self._run_round(snap, warm, kwargs))
        except BaseException as exc:       # noqa: BLE001 — relayed
            future.set_error(exc)
        finally:
            self._finalize_lock.release()

    def _run_round(self, snap, warm, kwargs):
        t0 = time.perf_counter()
        out, served = self.session.compute_round(snap, warm=warm, **kwargs)
        with self._serve_lock:
            self.session.install_round(out, served)
        name = ("serving.refinalize_under_load.ms" if warm
                else "serving.finalize_under_load.ms")
        obs.observe(name, (time.perf_counter() - t0) * 1e3)
        return out

    # ------------------------------------------------------------ batcher

    def _batcher_loop(self) -> None:
        while True:
            batch = self._queue.next_batch(self.max_batch, self.max_wait_s)
            if batch is None:
                return
            now = time.monotonic()
            live = []
            for req in batch:
                if req.deadline is not None and now > req.deadline:
                    obs.count("serving.timeouts")
                    req.future.set_error(RouteTimeout(
                        "request expired before a flush served it "
                        f"({(now - req.enqueued_at) * 1e3:.1f}ms queued)"))
                else:
                    live.append(req)
            if not live:
                continue
            pts = np.stack([r.sketch for r in live])
            n = len(live)
            if self.pad_buckets and n < self.max_batch:
                bucket = 1
                while bucket < n:
                    bucket *= 2
                bucket = min(bucket, self.max_batch)
                if bucket > n:
                    pts = np.concatenate(
                        [pts, np.repeat(pts[-1:], bucket - n, axis=0)])
            try:
                with self._serve_lock:
                    served = self.session.served_round
                    labels = self.session.route(pts)
                    staleness = (None if served is None
                                 else self.session.clock - served.clock)
            except Exception as exc:       # e.g. "route() needs finalize()"
                obs.count("serving.flush_errors")
                for req in live:
                    req.future.set_error(exc)
                continue
            obs.observe("serving.flush_size", float(n))
            if staleness is not None:
                obs.observe("serving.staleness_at_serve", float(staleness))
            labels = np.atleast_1d(np.asarray(labels))
            done = time.monotonic()
            for req, label in zip(live, labels):
                obs.observe("serving.request.ms",
                            (done - req.enqueued_at) * 1e3)
                req.future.set_result(int(label))
