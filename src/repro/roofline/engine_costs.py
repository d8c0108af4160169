"""Achieved-vs-peak roofline numbers for the aggregation engine.

``roofline/analysis.py`` predicts LM train/serve step times from a
compiled dry run; this module closes the loop for the two engine
kernels (``kmeans_assign``, ``group_ball_proj_batched``) by pairing a
kernel's XLA ``cost_analysis()`` (flops / bytes accessed) with its
*measured* execute time: ``kernel_probe`` / ``engine_kernel_report``
are a standalone AOT compile+time of the per-iteration kernels at a
given problem size, for the bench rows' ``kernels`` section.

Peaks come from ``PEAKS``, keyed by the ``device_kind`` jax reports.
A device missing from that table is an error, not a default: a CPU run
has no peak to be a fraction of, so callers pass ``hw=None`` there and
the rows carry no ``*_frac_of_peak`` columns.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.roofline.analysis import HW_V5E, Hardware

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#   "TPU v5 lite": Google Cloud documentation, "TPU v5e" page — 197
#   TFLOP/s bf16, 16 GB HBM at 819 GB/s (``analysis.HW_V5E``).
PEAKS = {"TPU v5 lite": HW_V5E}


def detect_hardware(device=None) -> Hardware:
    """Published peaks of ``device`` (default: the first jax device).
    Raises for a device kind missing from ``PEAKS``."""
    dev = device if device is not None else jax.devices()[0]
    hw = PEAKS.get(dev.device_kind)
    if hw is None:
        raise ValueError(
            f"no published peaks for device kind {dev.device_kind!r} "
            f"(platform {dev.platform!r}); add them to "
            "roofline.engine_costs.PEAKS with their source")
    return hw


def achieved_vs_peak(cost: dict, seconds: float,
                     hw: Hardware | None) -> dict:
    """One program's roofline row: cost_analysis dict + measured wall
    seconds -> achieved rates, and fraction-of-peak when ``hw`` has
    published peaks (``None`` off the TPU)."""
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    s = max(float(seconds), 1e-12)
    row = {
        "flops": flops,
        "bytes": nbytes,
        "exec_s": float(seconds),
        "achieved_flops_per_s": flops / s,
        "achieved_bytes_per_s": nbytes / s,
    }
    if hw is not None:
        row["flops_frac_of_peak"] = flops / s / hw.peak_flops
        row["bytes_frac_of_peak"] = nbytes / s / hw.hbm_bw
    return row


def kernel_probe(name: str, fn, args, hw: Hardware | None,
                 iters: int = 3) -> dict:
    """AOT-compile ``fn`` at the shapes of ``args`` and time warm
    executions; returns an achieved-vs-peak row tagged with the arg
    shapes."""
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    jax.block_until_ready(compiled(*args))            # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.block_until_ready(out)
    per_iter = (time.perf_counter() - t0) / iters
    row = achieved_vs_peak(cost, per_iter, hw)
    row["name"] = name
    row["shapes"] = [list(jnp.shape(a)) for a in args]
    return row


def engine_kernel_report(clients: int, sketch_dim: int, k: int,
                         algorithm: str, *, edges: str = "complete",
                         knn_k: int = 8, max_edges: int = 1 << 21,
                         hw: Hardware | None) -> list[dict]:
    """Probe the per-iteration kernel(s) a bench row's algorithm drives.

    Lloyd-family rows probe ``kmeans_assign`` at the row's (C, s) x
    (k, s); convex rows probe ``group_ball_proj_batched`` at the fusion
    graph's edge count (C*knn_k for knn, C(C-1)/2 complete, capped at
    ``max_edges`` with a ``capped`` flag so huge-C rows don't allocate
    an O(C^2) probe tensor).
    """
    from repro.kernels import ops as kops

    key = jax.random.PRNGKey(0)
    rows = []
    if algorithm.startswith("kmeans"):
        pts = jax.random.normal(key, (clients, sketch_dim), jnp.float32)
        ctr = pts[:max(k, 1)]
        rows.append(kernel_probe("kmeans_assign", kops.kmeans_assign,
                                 (pts, ctr), hw))
    else:
        n_edges = (clients * knn_k if edges == "knn"
                   else clients * (clients - 1) // 2)
        capped = n_edges > max_edges
        e = min(n_edges, max_edges)
        v = jax.random.normal(key, (1, e, sketch_dim), jnp.float32)
        radius = jnp.ones((1, e), jnp.float32)
        row = kernel_probe("group_ball_proj_batched",
                           kops.group_ball_proj_batched, (v, radius), hw)
        row["edges"] = int(e)
        row["edges_capped"] = bool(capped)
        rows.append(row)
    return rows


def hardware_info(hw: Hardware | None) -> dict:
    """The device a report ran on, as jax names it, plus its peaks
    (``None`` where the device has none in ``PEAKS``)."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "peaks": None if hw is None else {
                "name": hw.name, "peak_flops": hw.peak_flops,
                "hbm_bw": hw.hbm_bw, "link_bw": hw.link_bw}}
