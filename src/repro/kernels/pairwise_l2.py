"""Pallas TPU kernel: pairwise squared-Euclidean distance matrix.

This is the compute hot spot of the ODCL server clustering step: for
``m`` clients and sketch dimension ``d`` the K-means / convex-clustering
inner loops need the (m, k) (or (m, m)) distance matrix every iteration.

TPU mapping: one MXU matmul per (bm, bk) output tile using the
``||a-b||^2 = ||a||^2 + ||b||^2 - 2 a.b`` decomposition.  The d
(feature) axis is blocked as the innermost *reduction* grid dimension
with an accumulator held in the output VMEM tile, so arbitrarily large
sketch dims stream through VMEM:

  grid = (m/bm, k/bk, d/bd)
  A tile: (bm, bd) VMEM     B tile: (bk, bd) VMEM     O tile: (bm, bk)

All tile sizes are MXU-aligned multiples of 128 (8 for the sublane dim
would suffice for fp32 but 128 keeps the matmul shapes square).  The
matmul runs at HIGHEST precision: float32 on the MXU, as the docstring
of ``pairwise_sqdist_pallas`` promises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pairwise_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.float32)          # (bm, bd)
    b = b_ref[...].astype(jnp.float32)          # (bk, bd)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)  # (bm, 1)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)  # (bk, 1)
    ab = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32
    )                                           # (bm, bk)
    o_ref[...] += a2 + b2.T - 2.0 * ab


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bd", "interpret"))
def pairwise_sqdist_pallas(a, b, *, bm: int = 128, bk: int = 128, bd: int = 512,
                           interpret: bool = False):
    """(m,d) x (k,d) -> (m,k) squared distances, fp32 accumulate."""
    m, d = a.shape
    k, _ = b.shape
    bm = min(bm, _rup(m, 8))
    bk = min(bk, _rup(k, 128))
    bd = min(bd, _rup(d, 128))
    mp, kp, dp = _rup(m, bm), _rup(k, bk), _rup(d, bd)
    a = jnp.pad(a, ((0, mp - m), (0, dp - d)))
    b = jnp.pad(b, ((0, kp - k), (0, dp - d)))
    grid = (mp // bm, kp // bk, dp // bd)
    out = pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bd), lambda i, j, l: (j, l)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), jnp.float32),
        interpret=interpret,
    )(a, b)
    return jnp.maximum(out[:m, :k], 0.0)


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
