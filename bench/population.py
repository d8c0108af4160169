"""The planted federation, made on the device from the run's seed.

K unit-scale centres for every coordinate of the upload pytree and
per-coordinate Gaussian noise around them, as in ``chip_smoke.py``.  One
jitted program makes one draw of every client's upload; it is called
once per draw (the federation's upload draws, which share their
labels), and each draw is pulled to the host,
where the traffic holds it as uploads arriving from the network would
be held.
"""
from __future__ import annotations

import functools

import numpy as np

def key_data(seed: int, stream: int = 0) -> np.ndarray:
    """Two uint32 words of key material from a seed of any size."""
    return np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)


@functools.lru_cache(maxsize=4)
def _draw_program(leaves: tuple, clients: int, clusters: int, noise: float):
    import jax
    import jax.numpy as jnp

    def draw(key_words, stream):
        key = jax.random.wrap_key_data(key_words)
        labels = jax.random.permutation(
            jax.random.fold_in(key, 1), jnp.arange(clients, dtype=jnp.int32) % clusters)
        out = {}
        for i, (name, shape) in enumerate(leaves):
            centres = jax.random.normal(jax.random.fold_in(key, 100 + i),
                                        (clusters,) + shape, jnp.float32)
            eps = jax.random.normal(
                jax.random.fold_in(jax.random.fold_in(key, 200 + i), stream),
                (clients,) + shape, jnp.float32)
            out[name] = centres[labels] + jnp.float32(noise) * eps
        return labels, out

    return jax.jit(draw)


def leaf_shapes(config: dict) -> tuple:
    return tuple((name, tuple(shape))
                 for name, shape in sorted(config["upload"].items()))


def make_draw(config: dict, seed: int, stream: int):
    """One draw as host arrays: ``(labels (m,), {leaf: (m, ...)})``."""
    import jax

    fed = config["federation"]
    program = _draw_program(leaf_shapes(config), int(fed["clients"]),
                            int(fed["clusters"]), float(fed["noise"]))
    labels, params = program(key_data(seed), stream)
    labels, params = jax.device_get((labels, params))
    return np.asarray(labels), {k: np.asarray(v) for k, v in params.items()}
