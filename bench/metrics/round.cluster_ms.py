"""Mean execute time of the warm clustering program of a refreshed
round (obs span ``session.refinalize.cluster.execute``, which blocks on
the program's result)."""


def read(run):
    h = run.obs["histograms"].get("session.refinalize.cluster.execute.ms")
    return h["mean"] if h and h.get("count") else None
