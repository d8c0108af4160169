"""The shape functions and the peaks table, at hand-worked shapes."""
import pytest

from bench import costs


def test_kmeans_assign_hand_worked():
    # m=2, d=3, k=2: cross products 2*2*2*3=24, norms 2*2*3=12 and
    # 2*2*3=12, combine and argmin 4*2*2=16, sums 2*3=6, counts 2
    w = costs.kmeans_assign(2, 3, 2)
    assert w["ops"] == 24 + 12 + 12 + 16 + 6 + 2
    # read 6 point and 6 centre floats, write 2 labels, 6 sums, 2 counts
    assert w["bytes"] == 4 * (6 + 6 + 2 + 6 + 2)


def test_group_prox_hand_worked():
    w = costs.group_ball_proj_batched(1, 4, 2)
    assert w["ops"] == 4 * (3 * 2 + 3)
    assert w["bytes"] == 4 * (2 * 4 * 2 + 4)


def test_ingest_wave_counts_the_dense_projection():
    w = costs.ingest_wave(240, 159010, 64)
    assert w["ops"] == 2 * 240 * 159010 * 64
    assert w["bytes"] == 4 * (2 * 240 * 159010 + 240 * 64)


def test_least_time_names_its_bound():
    peak = costs.peaks("TPU v5 lite")
    t, bound = costs.least_time({"ops": 197e12, "bytes": 1.0}, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = costs.least_time({"ops": 1.0, "bytes": 819e9}, peak)
    assert (t, bound) == (1.0, "memory")


def test_roofline_pct_sums_calls_and_reads_nothing_as_none():
    peak = costs.peaks("TPU v5 lite")
    work = {"ops": 0.0, "bytes": 819e9 * 1e-3}     # 1 ms at peak bytes
    assert costs.roofline_pct([work, work], 4e-3, peak) == pytest.approx(50)
    assert costs.roofline_pct([], 1.0, peak) is None
    assert costs.roofline_pct([work], 0.0, peak) is None


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        costs.peaks("cpu")
