"""Jit'd public wrappers for the Pallas kernels.

On TPU the compiled Pallas kernels run, with no fallback; on other
backends the wrappers dispatch to the pure-jnp oracles in ``ref.py`` so
the rest of the framework is backend-agnostic.  Tests call the
``*_pallas(..., interpret=True)`` entry points directly to validate the
kernel bodies against the oracles, and ``tests/test_tpu_compile.py``
compiles them for a described TPU.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.pairwise_l2 import pairwise_sqdist_pallas
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.group_prox import (
    group_ball_proj_batched_pallas,
    group_ball_proj_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas

# Force-enable pallas-in-interpret-mode everywhere (slow; tests only).
_FORCE_PALLAS = os.environ.get("REPRO_FORCE_PALLAS", "0") == "1"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pairwise_sqdist(a, b):
    """(m,d) x (k,d) -> (m,k) squared Euclidean distances."""
    if _on_tpu():
        return pairwise_sqdist_pallas(a, b)
    if _FORCE_PALLAS:
        return pairwise_sqdist_pallas(a, b, interpret=True)
    return ref.pairwise_sqdist(a, b)


def kmeans_assign(points, centers):
    """Fused Lloyd assign+accumulate: (labels, sums, counts)."""
    if _on_tpu():
        return kmeans_assign_pallas(points, centers)
    if _FORCE_PALLAS:
        return kmeans_assign_pallas(points, centers, interpret=True)
    return ref.kmeans_assign(points, centers)


def group_ball_proj(v, radius):
    """Row-wise projection onto the L2 ball (convex-clustering dual prox)."""
    if _on_tpu():
        return group_ball_proj_pallas(v, radius)
    if _FORCE_PALLAS:
        return group_ball_proj_pallas(v, radius, interpret=True)
    return ref.group_ball_proj(v, radius)


def group_ball_proj_batched(v, radius):
    """Batched ball projection (b,e,d) — the lambda-ladder dual prox."""
    if _on_tpu():
        return group_ball_proj_batched_pallas(v, radius)
    if _FORCE_PALLAS:
        return group_ball_proj_batched_pallas(v, radius, interpret=True)
    return ref.group_ball_proj_batched(v, radius)


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None):
    """Block attention. q (b,h,sq,dh), k/v (b,hkv,skv,dh)."""
    if _on_tpu():
        return flash_attention_pallas(q, k, v, causal=causal, window=window)
    return ref.flash_attention(q, k, v, causal=causal, window=window)
