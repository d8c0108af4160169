"""The readers of the program's spans inside a wave and a round: the
mean of their histogram, and nothing where the run holds none."""
import pytest

from conftest import ROOT

SPANS = {"ingest.transfer_ms": "session.ingest.transfer.ms",
         "ingest.program_ms": "session.ingest.program.ms",
         "round.materialize_ms": "session.materialize.ms"}


def view(histograms):
    from bench.run import RunView

    return RunView(obs={"counters": {}, "gauges": {},
                        "histograms": histograms})


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_reader_returns_the_span_mean(metric):
    from bench.run import reader

    read = reader(ROOT, metric)
    hist = {"count": 3, "sum": 60.0, "mean": 20.0, "min": 19.0,
            "max": 21.0, "p50": 20.0, "p95": 20.9, "p99": 20.98}
    assert read(view({SPANS[metric]: hist})) == 20.0
    assert read(view({SPANS[metric]: {"count": 0}})) is None
    assert read(view({})) is None
