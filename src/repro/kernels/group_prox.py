"""Pallas TPU kernel: row-wise L2-ball projection (group prox).

The AMA solver for convex clustering (repro.core.clustering.convex and
its device twin repro.core.engine.device_convex) projects every edge's
dual variable onto the ball of radius lambda each iteration: for
E edges and sketch dim d this is an (E, d) row-normalization — memory
bound, so we tile rows through VMEM in (be, d) blocks and fuse the norm
and the rescale.

The kernel runs over a leading batch axis — the lambda-ladder sweep of
the device clusterpath advances all L solves in lock-step, so its dual
state is (L, E, d) with a per-(l, e) radius; the unbatched projection
is the L=1 case.  E is padded to a multiple of ``be`` (pad radius 1.0
=> pad rows pass through unscaled and are sliced off).

The radius travels as a lane-dense (L, 1, E) array in (1, 1, be)
blocks and is turned into a (be, 1) column inside the kernel: a 1-D
(be,) or (1, be) radius block does not match the TPU's HBM tiling and
Mosaic refuses it, and an (E, 1) column would pad every radius to a
full 128-lane row in HBM.

  grid = (L, E/be)
  V tile: (1, be, d)    radius tile: (1, 1, be)    out: (1, be, d)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _proj_kernel(v_ref, r_ref, o_ref):
    v = v_ref[0].astype(jnp.float32)                      # (be, d)
    r = r_ref[0].astype(jnp.float32).T                    # (be, 1)
    n = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))   # (be, 1)
    scale = jnp.where(n > r, r / jnp.maximum(n, 1e-30), 1.0)
    o_ref[0] = v * scale


@functools.partial(jax.jit, static_argnames=("be", "interpret"))
def group_ball_proj_pallas(v, radius, *, be: int = 512,
                           interpret: bool = False):
    """Row-wise ball projection: v (e, d), radius scalar or (e,)."""
    e = v.shape[0]
    radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (e,))
    return group_ball_proj_batched_pallas(v[None], radius[None], be=be,
                                          interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("be", "interpret"))
def group_ball_proj_batched_pallas(v, radius, *, be: int = 512,
                                   interpret: bool = False):
    """Batched row-wise ball projection: v (b, e, d), radius (b, e).

    On TPU ``be`` must be a multiple of 128 unless one block spans all
    of ``e``."""
    b, e, d = v.shape
    if e == 0:          # degenerate edge set (m=1): nothing to project
        return jnp.zeros((b, 0, d), jnp.float32)
    radius = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (b, e))
    be = min(be, _rup(e, 8))
    ep = _rup(e, be)
    vp = jnp.pad(v, ((0, 0), (0, ep - e), (0, 0)))
    rp = jnp.pad(radius, ((0, 0), (0, ep - e)),
                 constant_values=1.0)[:, None, :]         # (b, 1, ep)
    out = pl.pallas_call(
        _proj_kernel,
        grid=(b, ep // be),
        in_specs=[
            pl.BlockSpec((1, be, d), lambda l, i: (l, i, 0)),
            pl.BlockSpec((1, 1, be), lambda l, i: (l, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, be, d), lambda l, i: (l, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ep, d), jnp.float32),
        interpret=interpret,
    )(vp, rp)
    return out[:, :e]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
