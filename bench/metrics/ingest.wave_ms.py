"""Mean wall time of one keyed upload wave inside the session: the
transfer, the JL sketch and the buffer write, blocking (obs span
``session.ingest``)."""


def read(run):
    h = run.obs["histograms"].get("session.ingest.ms")
    return h["mean"] if h and h.get("count") else None
