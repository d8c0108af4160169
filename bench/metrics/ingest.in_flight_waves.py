"""Mean number of the session's wave programs still in flight as an
upload wave's ``ingest`` returns, that wave's own included (obs
histogram ``session.ingest.in_flight``, one value a wave).  Above 1, a
wave's transfer ran while an earlier wave's program, or the device work
queued before it, had not finished; a program without such a histogram
reports nothing."""


def read(run):
    h = run.obs["histograms"].get("session.ingest.in_flight")
    return h["mean"] if h and h.get("count") else None
