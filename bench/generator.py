"""The traffic generator.  A mix (``bench/traffic/<mix>.json``) is data:
it names a ``driver`` and that driver's parameters.  A driver is a
module of its own, ``bench/traffic/<driver>.py``, found by name as the
per-layer readers are, whose ``make(config, mix, seed)`` returns an
object with:

``setup()``          build the federation, load it, warm every shape the
                     window uses (all of it counts as set-up);
``fed``              the ``Federation`` it drives;
``window(seconds)``  offer the traffic for ``seconds`` and return
                     ``{"metrics", "attempted", "failed", "errors",
                     "notes"}``;
``output()``         what the timed path produced, for
                     ``reference.compare``.

A new mix of an existing driver is a data file alone; a new kind of
traffic is a new driver file.  This module holds what the drivers
share: the ``Federation`` of a configuration, its session, its uploads
and its served rounds.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

from bench import population
from bench.reference import Schedule

# How long after the window closes an answer may still come: it is
# late, not wrong, and its latency counts the wait.
GRACE_S = 60.0
SKETCH_SAMPLE = 256
# Rounds whose served models are kept for the comparison: a uniform
# sample drawn from the seed over every round recorded, so that the
# copies held on the device do not grow with the window.
MODEL_SAMPLE = 32


def annotate(name: str):
    """A host span in the profiler's trace, so an idle gap on the device
    can be put down to what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def session_seed(seed: int) -> int:
    """The session's JL / clustering seed, drawn from the run's seed."""
    return int(population.key_data(seed, 7)[0] >> 1)


def lambda_midpoint(points: np.ndarray, labels: np.ndarray) -> float:
    """The paper's exact-lambda choice (appendix E.1): the midpoint of
    the recovery interval (17) of the planted partition, as
    ``chip_smoke.py`` phase B picks it."""
    points = np.asarray(points, np.float64)
    ks = np.unique(labels)
    n = len(points)
    lo, cents, sizes = 0.0, [], []
    for k in ks:
        pk = points[labels == k]
        d2 = max(float(((pk[s:s + 256, None] - pk[None]) ** 2).sum(-1).max())
                 for s in range(0, len(pk), 256))
        lo = max(lo, np.sqrt(d2) / len(pk))
        cents.append(pk.mean(axis=0))
        sizes.append(len(pk))
    hi = min(np.linalg.norm(cents[a] - cents[b]) / (2 * n - sizes[a] - sizes[b])
             for a in range(len(ks)) for b in range(a + 1, len(ks)))
    return 0.5 * (lo + hi) if lo < hi else lo


class Federation:
    """A configuration's session on one chip, its uploads and its served
    rounds."""

    def __init__(self, config: dict, seed: int, mix: dict):
        import jax
        from repro.core.engine import AggregationSession
        from repro.serving import RouteServer

        self.config, self.seed = config, int(seed)
        fed, ses = config["federation"], config["session"]
        self.clients, self.clusters = int(fed["clients"]), int(fed["clusters"])
        self.sketch_dim = int(ses["sketch_dim"])
        self.schedule = Schedule(self.clients,
                                 int(round(mix["wave_frac"] * self.clients)),
                                 int(mix.get("draws", 1)))
        self.devices = jax.devices()[:1]
        self.session = AggregationSession(
            self.clients, sketch_dim=self.sketch_dim,
            seed=session_seed(seed))
        self.server = RouteServer(self.session)
        self.truth = None
        self.draws = []
        self.waves = 0
        self._gather = jax.jit(lambda params, idx: jax.tree_util.tree_map(
            lambda leaf: leaf[idx], params))
        self.reset_rounds()

    # ------------------------------------------------------------ set-up

    def make_draws(self, n: int) -> None:
        for d in range(n):
            labels, params = population.make_draw(self.config, self.seed, d)
            self.truth = labels
            self.draws.append(params)

    def ingest_next(self) -> None:
        g = self.waves
        lo, hi = self.schedule.wave_rows(g)
        draw = self.draws[self.schedule.wave_draw(g)]
        wave = {k: v[lo:hi] for k, v in draw.items()}
        with annotate("bench.ingest"):
            self.server.ingest(wave, client_ids=range(lo, hi))
        self.waves += 1

    def finalize_kwargs(self) -> dict:
        algo = dict(self.config["finalize"])
        options = dict(algo.pop("algo_options", {}))
        if options.get("lam") == "interval_midpoint":
            options["lam"] = lambda_midpoint(
                np.asarray(self.session.sketches), self.truth)
        if options:
            algo["algo_options"] = options
        return algo

    def load(self) -> None:
        """The whole federation from draw 0, then the first round."""
        for _ in range(self.schedule.blocks):
            self.ingest_next()
        self.server.finalize(**self.finalize_kwargs())

    # ------------------------------------------------------------ rounds

    def reset_rounds(self) -> None:
        """Forget the rounds recorded so far (those of the warm-up)."""
        self.rounds = []
        self._sample_rng = np.random.default_rng(
            population.key_data(self.seed, 17))
        self._sampled = []

    def record_round(self, clock_req: int) -> None:
        """What the round just installed serves: its snapshot clock and
        labels (copied to the host without a wait), and, for a seeded
        uniform sample of at most ``MODEL_SAMPLE`` rounds (reservoir
        sampling), its K' cluster models gathered on the device."""
        served = self.session.served_round
        state, labels, info = served.out
        if hasattr(labels, "copy_to_host_async"):
            labels.copy_to_host_async()
        i = len(self.rounds)
        rnd = {"clock_req": clock_req, "clock": served.clock,
               "labels": labels,
               "n_iter": (info.get("meta") or {}).get("n_iter"),
               "models": None}
        self.rounds.append(rnd)
        slot = (len(self._sampled) if i < MODEL_SAMPLE
                else int(self._sample_rng.integers(0, i + 1)))
        if slot >= MODEL_SAMPLE:
            return
        idx = np.asarray(served.first_idx, np.int32)
        rnd["models"] = self._gather(state.params, idx)
        if slot == len(self._sampled):
            self._sampled.append(rnd)
        else:
            self._sampled[slot]["models"] = None
            self._sampled[slot] = rnd

    # ------------------------------------------------------------ output

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def sketch_sample(self) -> tuple:
        rng = np.random.default_rng(population.key_data(self.seed, 11))
        rows = np.sort(rng.choice(self.clients, min(SKETCH_SAMPLE,
                                                    self.clients),
                                  replace=False))
        return rows, np.asarray(self.session.sketches)[rows], \
            self.session.clock

    def output(self, rounds: list) -> dict:
        import jax

        rows, sketches, clock = self.sketch_sample()
        return {"truth": self.truth, "sketch_rows": rows,
                "sketches": sketches, "sketch_clock": clock,
                "rounds": [dict(r, labels=np.asarray(r["labels"]),
                                n_iter=None if r["n_iter"] is None
                                else int(r["n_iter"]),
                                models=None if r["models"] is None
                                else jax.device_get(r["models"]))
                           for r in rounds]}

    def reference_inputs(self) -> dict:
        return {"draws": self.draws, "schedule": self.schedule,
                "seed": session_seed(self.seed),
                "sketch_dim": self.sketch_dim, "clusters": self.clusters}

    def close(self) -> None:
        self.server.stop(drain=True)
        self.session = self.server = None
        self.rounds = self._sampled = []


def driver(root: str, name: str):
    """The driver module ``bench/traffic/<name>.py`` under ``root``."""
    path = os.path.join(root, "bench", "traffic", name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no traffic driver {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_traffic_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make(root: str, config: dict, mix: dict, seed: int):
    """The driver of a mix, over the federation of a configuration."""
    return driver(root, mix["driver"]).make(config, mix, seed)
