"""The ODCL-CC configuration's AMA iterations against the hardest fusion
graph of its sketch geometry.

The AMA step is 1/(2 x the kNN graph's largest degree), and in 64-wide
sketches a few clients are the nearest neighbour of very many others, so
the iterations a cold solve needs to recover the planted partition grow
with the seed's largest hub.  The stand-in is the cell's geometry in
sketch space (2,400 clients, K=4 centres of norm about 399 apart, unit
noise per sketch coordinate: the 159,010-float uploads with noise 0.02
under the JL projection); its seed draws the largest hub of 400 seeds
tried."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from conftest import ROOT

HUB_SEED = 1292


def planted_sketches(seed: int):
    rng = np.random.default_rng(seed)
    m, k, d = 2400, 4, 64
    labels = rng.permutation(np.arange(m) % k)
    centres = rng.normal(size=(k, d)) * 399 / 8
    return labels, centres[labels] + rng.normal(size=(m, d))


def solve(points, labels, iters: int) -> int:
    from bench.generator import lambda_midpoint
    from bench.reference import match
    from repro.core.engine.device_convex import device_convex_cluster

    res = device_convex_cluster(
        jax.random.PRNGKey(0), jnp.asarray(points, jnp.float32),
        lam=lambda_midpoint(points, labels), iters=iters, edges="knn",
        knn_k=8)
    return match(np.asarray(res.labels), labels)[1]


def test_the_configured_iterations_recover_the_hub_seeds_partition():
    with open(os.path.join(ROOT, "bench", "configs",
                           "rmnist-mlp-cc.json")) as f:
        options = json.load(f)["finalize"]["algo_options"]
    assert (options["edges"], options["knn_k"]) == ("knn", 8)
    labels, points = planted_sketches(HUB_SEED)
    assert solve(points, labels, 200) > 0       # 200 leave it unfused
    assert solve(points, labels, int(options["iters"])) == 0
