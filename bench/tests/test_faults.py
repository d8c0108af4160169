"""What ``correct`` catches: the control, and the timed path broken
underneath a run, each read at a tiny size on the CPU.

The control is the plain reference one precision step below what the
configuration states, put in the program's place; each fault is planted
in the program for one run and taken out after it."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT, cpu_chip

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [c["name"] for c in BENCH["workloads"] if c["chips"] == 1]


def measure(root, cell, *, control=False, seed="3123456789"):
    from bench import run

    args = run.parse(["--workload", cell, "--seed", seed, "--seconds", "2",
                      "--trace", "0"])
    return run.measure(args, root=root, require=cpu_chip, control=control)


def limits(root, cell):
    from bench import run

    return run.load_cell(root, cell)[2]["limits"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(tiny_root, cell):
    from bench import reference

    result = measure(tiny_root, cell, control=True)
    assert result["line"]["correct"] is True
    failed = [name for name, *_, ok in
              reference.checks(result["control"], limits(tiny_root, cell))
              if not ok]
    assert "model_err" in failed and "sketch_rel_err" in failed


@pytest.fixture
def fresh_programs():
    """Planted faults reach only programs traced after them."""
    from repro.core.engine import aggregate

    def clear():
        for build in (aggregate._mean_program, aggregate._route_program,
                      aggregate._cluster_program,
                      aggregate._warm_cluster_program):
            build.cache_clear()

    clear()
    yield
    clear()


@pytest.mark.parametrize("cell", CELLS)
def test_a_round_that_leaves_the_served_state_unchanged(tiny_root, cell,
                                                        monkeypatch):
    from repro.core.engine.session import AggregationSession

    install = AggregationSession.install_round

    def keep_first(self, out, served):
        if self._served is None:
            return install(self, out, served)
        return out

    monkeypatch.setattr(AggregationSession, "install_round", keep_first)
    result = measure(tiny_root, cell)
    assert result["line"]["correct"] is False
    assert result["line"]["checks"]["stale_rounds"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_an_ingest_that_leaves_the_buffers_unchanged(tiny_root, cell,
                                                     monkeypatch):
    from repro.core.engine.session import AggregationSession

    ingest = AggregationSession.ingest

    def dropped(self, wave=None, **kw):
        if self._final is None or self._served is None:
            return ingest(self, wave, **kw)      # the first pass loads
        self._clock += 1                          # acknowledged, not written
        return None

    monkeypatch.setattr(AggregationSession, "ingest", dropped)
    result = measure(tiny_root, cell)
    assert result["line"]["correct"] is False
    checks = result["line"]["checks"]
    assert checks["sketch_rel_err"]["value"] > checks["sketch_rel_err"][
        "limit"]


def _half_mean(params, labels, onehot, counts, aggregator):
    """The per-cluster mean over the first half of the clients only."""
    keep = (jnp.arange(onehot.shape[0]) < onehot.shape[0] // 2)[:, None]
    kept = onehot * keep
    n = jnp.maximum(kept.sum(0), 1.0)[:, None]

    def back(leaf):
        flat = leaf.reshape(leaf.shape[0], -1)
        means = jnp.dot(kept.T, flat, precision="highest") / n
        return jnp.dot(onehot, means,
                       precision="highest").reshape(leaf.shape)

    return jax.tree_util.tree_map(back, params)


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_of_the_mean(tiny_root, cell,
                                                monkeypatch, fresh_programs):
    from repro.core.engine import aggregate

    monkeypatch.setattr(aggregate, "cluster_aggregate_tree", _half_mean)
    result = measure(tiny_root, cell)
    assert result["line"]["correct"] is False
    checks = result["line"]["checks"]
    assert checks["model_err"]["value"] > checks["model_err"]["limit"]
