"""Mean wall time of the host-to-device transfer of one upload wave: the
wave's leaves put on the device, blocking until they have landed (obs
span ``session.ingest.transfer``, inside ``session.ingest``)."""


def read(run):
    h = run.obs["histograms"].get("session.ingest.transfer.ms")
    return h["mean"] if h and h.get("count") else None
