"""The reader of the wave programs in flight as an ``ingest`` returns:
the mean of the session's histogram, and nothing where the run holds
none, as a program without the histogram does."""
from conftest import ROOT


def view(histograms):
    from bench.run import RunView

    return RunView(obs={"counters": {}, "gauges": {},
                        "histograms": histograms})


def test_reader_returns_the_in_flight_mean():
    from bench.run import reader

    read = reader(ROOT, "ingest.in_flight_waves")
    hist = {"count": 4, "sum": 10.0, "mean": 2.5, "min": 1.0, "max": 4.0,
            "p50": 2.5, "p95": 3.85, "p99": 3.97}
    assert read(view({"session.ingest.in_flight": hist})) == 2.5
    assert read(view({"session.ingest.in_flight": {"count": 0}})) is None
    assert read(view({"session.ingest.ms": hist})) is None
