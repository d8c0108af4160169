"""Megabytes (1e6 bytes) that one upload wave's transfer places on the
devices: the obs counter ``session.ingest.device_bytes`` (the wave's
bytes times the devices it is copied to, recorded only by a session on a
mesh) over the waves of the span ``session.ingest``.  A program without
that counter reports nothing."""


def read(run):
    moved = run.obs["counters"].get("session.ingest.device_bytes")
    h = run.obs["histograms"].get("session.ingest.ms")
    if moved is None or not h or not h.get("count"):
        return None
    return moved / h["count"] / 1e6
