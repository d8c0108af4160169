"""The work an operation needs, from its shapes, and the chip's peaks.

Each function gives the floating-point operations and the bytes that
must cross HBM for one call of an operation, whatever implements it: a
later change to the implementation (a structured projection, a fused
kernel) is measured against the same work.  A roofline share is the
least time the chip could take, max(ops / peak FLOP/s, bytes / peak
bytes/s), over the device time the trace measured; ``bound`` says which
of the two sets it.
"""
from __future__ import annotations

F32 = 4

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#   "TPU v5 lite": Google Cloud documentation, "TPU v5e" page: 197 TFLOP/s
#   in bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peaks of a device kind; a kind missing from ``PEAKS`` is an
    error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/costs.py "
                       "with their source") from None


def kmeans_assign(m: int, d: int, k: int) -> dict:
    """Nearest-centre labels of m points in d dimensions against k
    centres, and the per-centre sums and counts of the points assigned.

    ops: the m x k cross products (2mkd), the norms and the distance
    combine (2md + 2kd + 3mk), the argmin (mk) and the sums (md + m).
    bytes: read points and centres, write labels, sums and counts."""
    ops = 2 * m * k * d + 2 * m * d + 2 * k * d + 4 * m * k + m * d + m
    nbytes = F32 * (m * d + k * d + m + k * d + k)
    return {"ops": float(ops), "bytes": float(nbytes)}


def group_ball_proj_batched(b: int, e: int, d: int) -> dict:
    """Projection of each of b x e rows of width d onto the ball of its
    radius: the row norm (2d), the rescale (d) and the compare.
    bytes: read the rows and radii, write the rows."""
    ops = b * e * (3 * d + 3)
    nbytes = F32 * (2 * b * e * d + b * e)
    return {"ops": float(ops), "bytes": float(nbytes)}


def ingest_wave(w: int, n: int, s: int) -> dict:
    """One upload wave of w clients of n floats each: a dense JL
    projection to s floats (2wns), written with the uploads into the
    session's buffers.  bytes: read the wave, write its rows and its
    sketches."""
    ops = 2 * w * n * s
    nbytes = F32 * (w * n + w * n + w * s)
    return {"ops": float(ops), "bytes": float(nbytes)}


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the least time the chip could take for
    ``work`` and which of its peaks sets it."""
    t_ops = work["ops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def roofline_pct(works: list, device_s: float, peak: dict):
    """Share of the roofline, in percent, of calls whose summed device
    time is ``device_s``: their summed least time over that time.
    ``None`` where there is nothing to read."""
    if not works or device_s <= 0:
        return None
    least = sum(least_time(w, peak)[0] for w in works)
    return 100.0 * least / device_s
