"""AOT compiles of the server's Pallas kernels for a described TPU v5e.

The kernels run on the chip only; interpret mode on CPU cannot see what
the TPU compiler refuses (block shapes that do not match the HBM tiling,
the (8, 128) block rule).  The TPU compiler is installed with jax and
compiles for a chip that is described, not attached, so these tests
compile each kernel at the shapes of the server's main path and check
that the program holds the compiled kernel.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and each
test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.group_prox import (
    group_ball_proj_batched_pallas,
    group_ball_proj_pallas,
)
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.pairwise_l2 import pairwise_sqdist_pallas

PALLAS_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under the temp dir
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,d", [
    (16384, 8, 64),     # finalize over phase A's sketch matrix
    (16384, 64, 64),    # many clusters: the (k, 1) counts block
    (64, 8, 64),        # a route flush
])
def test_kmeans_assign_compiles_for_v5e(one_chip, m, k, d):
    assert PALLAS_CALL in _compile(kmeans_assign_pallas, one_chip,
                                   (m, d), (k, d))


def test_pairwise_sqdist_compiles_for_v5e(one_chip):
    # one row tile of the kNN edge build at C=16384, sketch_dim=32
    assert PALLAS_CALL in _compile(pairwise_sqdist_pallas, one_chip,
                                   (512, 32), (16384, 32))


def test_group_ball_proj_compiles_for_v5e(one_chip):
    assert PALLAS_CALL in _compile(group_ball_proj_pallas, one_chip,
                                   (131072, 64), (131072,))


@pytest.mark.parametrize("b", [1, 8])
def test_group_ball_proj_batched_compiles_for_v5e(one_chip, b):
    # the AMA dual over C=16384 x knn_k=8 edge slots, L lambda rungs
    assert PALLAS_CALL in _compile(group_ball_proj_batched_pallas, one_chip,
                                   (b, 131072, 32), (b, 131072))
