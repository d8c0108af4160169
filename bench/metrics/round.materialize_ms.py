"""Mean wall time of a round's host tail after its mean program: label
compaction, the meta pull, the optimizer-state init and the drift
anchor's two scalar pulls, with the waits those syncs make on device
work queued before them (obs span ``session.materialize``)."""


def read(run):
    h = run.obs["histograms"].get("session.materialize.ms")
    return h["mean"] if h and h.get("count") else None
