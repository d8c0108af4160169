"""The telemetry spine (``repro.obs``): spans, histograms, sinks.

Pins the contracts the instrumented engine relies on: span nesting and
timing land in the right places, histogram percentiles match numpy's
default convention exactly, the JSONL sink round-trips events, and
counter/histogram merges are order-independent (so per-worker
registries can be folded together in any order).
"""
from __future__ import annotations

import math
import time

import numpy as np
import pytest

from repro import obs
from repro.obs.core import Histogram, Registry


# ------------------------------------------------------------------ spans

def test_span_records_duration_and_histogram():
    reg = Registry()
    with reg.span("work") as info:
        time.sleep(0.01)
    assert info["ms"] >= 10.0 * 0.5          # coarse clocks: half slack
    h = reg.histograms["work.ms"]
    assert h.count == 1
    assert h.values[0] == info["ms"]


def test_span_nesting_parent_depth_and_monotone_timing():
    reg = Registry()
    events = reg.add_sink(obs.ListSink())
    with reg.span("outer") as outer:
        with reg.span("inner") as inner:
            time.sleep(0.005)
    spans = {e["name"]: e for e in events.events if e["event"] == "span"}
    assert spans["inner"]["parent"] == "outer"
    assert spans["inner"]["depth"] == 1
    assert "parent" not in spans["outer"]
    assert spans["outer"]["depth"] == 0
    # an enclosing span can never be shorter than what it encloses
    assert outer["ms"] >= inner["ms"]


def test_span_survives_exceptions_and_pops_stack():
    reg = Registry()
    with pytest.raises(RuntimeError):
        with reg.span("boom"):
            raise RuntimeError("x")
    assert reg.histograms["boom.ms"].count == 1
    with reg.span("after") as info:
        pass
    assert "parent" not in info              # stack was popped on the error


def test_span_reaches_the_profiler_trace(tmp_path):
    """A span opened under a profiler session lands on a host plane of
    the trace, by name, beside the device's events."""
    import jax

    reg = Registry()
    with jax.profiler.trace(str(tmp_path)):
        with reg.span("obs.test.traced"):
            jax.block_until_ready(jax.numpy.arange(8.0) * 2.0)
    path, = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events}
    assert "obs.test.traced" in names
    assert reg.histograms["obs.test.traced.ms"].count == 1


def test_event_without_sinks_builds_nothing(monkeypatch):
    """With no sink attached an event costs no clock read and no dict;
    a span still records its histogram."""
    reg = Registry()

    def no_clock():
        raise AssertionError("time.time() called with no sink attached")

    monkeypatch.setattr(time, "time", no_clock)
    assert reg.event("fed.round", round=0) is None
    with reg.span("quiet"):
        pass
    assert reg.histograms["quiet.ms"].count == 1


# -------------------------------------------------------------- histograms

@pytest.mark.parametrize("n", [1, 2, 5, 17, 100])
@pytest.mark.parametrize("p", [0.0, 50.0, 95.0, 99.0, 100.0])
def test_histogram_percentiles_match_numpy(n, p):
    rng = np.random.default_rng(n)
    vals = rng.normal(size=n) * 10.0
    h = Histogram(vals.tolist())
    assert h.percentile(p) == pytest.approx(float(np.percentile(vals, p)),
                                            rel=1e-12, abs=1e-12)


def test_histogram_summary_fields():
    h = Histogram([3.0, 1.0, 2.0])
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 1.0 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(2.0) and s["p50"] == 2.0
    assert Histogram().summary() == {"count": 0}
    assert math.isnan(Histogram().percentile(50.0))


# ------------------------------------------------------------------- sinks

def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "trace.jsonl"
    reg = Registry()
    sink = reg.add_sink(obs.JsonlSink(str(path)))
    reg.event("fed.round", method="odcl", round=0, bytes=128.0)
    with reg.span("phase", wave=4):
        pass
    reg.close_sinks()
    events = obs.read_jsonl(str(path))
    assert [e["event"] for e in events] == ["fed.round", "span"]
    assert events[0]["method"] == "odcl" and events[0]["bytes"] == 128.0
    assert events[1]["name"] == "phase" and events[1]["wave"] == 4
    assert events[1]["ms"] >= 0.0


def test_snapshot_shape_and_reset_keeps_sinks():
    reg = Registry()
    sink = reg.add_sink(obs.ListSink())
    reg.count("c", 2.0)
    reg.count("c", 3.0)
    reg.gauge("g", 7.0)
    reg.observe("h", 1.5)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5.0
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["h"]["count"] == 1
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}
    reg.event("still-here")
    assert sink.events[-1]["event"] == "still-here"


# ------------------------------------------------------------------- merge

def _apply(reg: Registry, op):
    kind, name, value = op
    if kind == "count":
        reg.count(name, value)
    else:
        reg.observe(name, value)


def test_counter_merge_order_independent_smoke():
    ops = [("count", "a", 1.0), ("count", "b", 2.5), ("obs", "h", 3.0),
           ("count", "a", -4.0), ("obs", "h", 1.0)]
    r1, r2 = Registry(), Registry()
    for op in ops:
        _apply(r1, op)
    for op in reversed(ops):
        _apply(r2, op)
    s1, s2 = r1.snapshot(), r2.snapshot()
    assert s1["counters"] == s2["counters"]
    assert s1["histograms"] == s2["histograms"]


def test_merge_order_independent_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    op = st.tuples(st.sampled_from(["count", "obs"]),
                   st.sampled_from(["a", "b", "c"]),
                   st.floats(-100, 100, allow_nan=False))

    @settings(max_examples=50, deadline=None)
    @given(st.lists(op, max_size=30), st.lists(op, max_size=30))
    def check(ops1, ops2):
        def build(ops):
            r = Registry()
            for o in ops:
                _apply(r, o)
            return r

        ab, ba = Registry(), Registry()
        ab.merge(build(ops1))
        ab.merge(build(ops2))
        ba.merge(build(ops2))
        ba.merge(build(ops1))
        sa, sb = ab.snapshot(), ba.snapshot()
        assert set(sa["counters"]) == set(sb["counters"])
        for k in sa["counters"]:
            assert sa["counters"][k] == pytest.approx(sb["counters"][k],
                                                      abs=1e-9)
        # histogram value multisets are identical -> equal summaries
        for k in set(sa["histograms"]) | set(sb["histograms"]):
            ha, hb = sa["histograms"][k], sb["histograms"][k]
            assert ha["count"] == hb["count"]
            for f in ("min", "max", "p50", "p95", "p99"):
                assert ha[f] == pytest.approx(hb[f], abs=1e-9)

    check()


def test_detect_hardware_reads_peaks_by_device_kind():
    """Peaks come from the published table keyed by ``device_kind``; a
    kind missing from it (the CPU included) is an error, and rows made
    without peaks carry no fraction-of-peak columns."""
    from types import SimpleNamespace

    from repro.roofline import HW_V5E, achieved_vs_peak, detect_hardware

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert detect_hardware(v5e) is HW_V5E
    for kind in ("TPU v99", "cpu"):
        with pytest.raises(ValueError, match="no published peaks"):
            detect_hardware(SimpleNamespace(platform="tpu", device_kind=kind))
    with pytest.raises(ValueError):
        detect_hardware()                # this process runs on the CPU
    cost = {"flops": 2e9, "bytes accessed": 1e9}
    assert "flops_frac_of_peak" not in achieved_vs_peak(cost, 1.0, None)
    row = achieved_vs_peak(cost, 1.0, HW_V5E)
    assert row["bytes_frac_of_peak"] == pytest.approx(1e9 / HW_V5E.hbm_bw)
