"""Mean wall time of the ingest program of one upload wave after the
wave has landed on the device, to the updated buffers (obs span
``session.ingest.program``, inside ``session.ingest``; the program is
queued during the transfer and starts when the wave lands).  It
includes the wait behind device work queued before it: under ODCL-CC
the round's cluster and mean programs run while the waves go in, and
the wave's program runs after them."""


def read(run):
    h = run.obs["histograms"].get("session.ingest.program.ms")
    return h["mean"] if h and h.get("count") else None
