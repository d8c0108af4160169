"""Mean execute time of a round's per-cluster mean program (obs span
``session.finalize.mean.execute``; the refreshed rounds run the same
program as the first finalize)."""


def read(run):
    h = run.obs["histograms"].get("session.finalize.mean.execute.ms")
    return h["mean"] if h and h.get("count") else None
