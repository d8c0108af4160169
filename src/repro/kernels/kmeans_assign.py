"""Pallas TPU kernel: fused K-means assign + accumulate.

One pass over the points computes, per grid step of ``bm`` points:
  * nearest-center labels (argmin over the (k, bm) distance tile), and
  * the per-cluster running sums / counts, accumulated across grid steps
    into a single (k, d) / (k,) VMEM-resident output block.

Fusing the scatter-add into the distance pass removes the separate
one-hot matmul of the reference implementation (which materializes an
(m, k) one-hot in HBM).  Centers are small enough (k <= a few hundred,
d = sketch dim) to keep the whole (k, d) accumulator in VMEM.

The distance tile is laid out (k, bm), centers on sublanes and points on
lanes, so the labels come out as a lane-dense (1, bm) row: a 1-D (bm,)
block does not match the TPU's HBM tiling of the (m,) label vector and
Mosaic refuses it.  Both contractions run at HIGHEST precision, so the
kernel accumulates in float32 on the MXU as the jnp oracle does on CPU.

  grid = (m/bm,)
  P tile: (bm, d)   C tile: (k, d)   outs: labels (1, bm), sums (k, d), counts (k, 1)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _assign_kernel(p_ref, c_ref, lab_ref, sum_ref, cnt_ref, *, m: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    p = p_ref[...].astype(jnp.float32)           # (bm, d)
    c = c_ref[...].astype(jnp.float32)           # (k, d)
    k, bm = c.shape[0], p.shape[0]
    p2 = jnp.sum(p * p, axis=1, keepdims=True).T  # (1, bm)
    c2 = jnp.sum(c * c, axis=1, keepdims=True)    # (k, 1)
    d2 = p2 + c2 - 2.0 * jax.lax.dot_general(
        c, p, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)      # (k, bm)
    # first index of the column minimum == jnp.argmin's tie-break
    row = jax.lax.broadcasted_iota(jnp.int32, (k, bm), 0)
    dmin = jnp.min(d2, axis=0, keepdims=True)
    labels = jnp.min(jnp.where(d2 == dmin, row, k), axis=0,
                     keepdims=True)              # (1, bm)
    lab_ref[...] = labels
    col = i * bm + jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
    onehot = ((row == labels) & (col < m)).astype(jnp.float32)  # (k, bm)
    sum_ref[...] += jax.lax.dot_general(
        onehot, p, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)      # (k, d)
    cnt_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def kmeans_assign_pallas(points, centers, *, bm: int = 256, interpret: bool = False):
    """(m,d) points x (k,d) centers -> (labels (m,), sums (k,d), counts (k,)).

    On TPU ``bm`` must be a multiple of 128 unless one block spans all
    of ``m`` (the labels block is a lane-dense ``(1, bm)`` row)."""
    m, d = points.shape
    k, _ = centers.shape
    bm = min(bm, _rup(m, 8))
    mp = _rup(m, bm)
    # zero pad rows are masked out of the sums and counts in the kernel
    pts = jnp.pad(points, ((0, mp - m), (0, 0)))
    labels, sums, counts = pl.pallas_call(
        functools.partial(_assign_kernel, m=m),
        grid=(mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm), lambda i: (0, i)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, mp), jnp.int32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pts, centers)
    return labels[0, :m], sums, counts[:, 0]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
