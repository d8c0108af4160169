"""The trace reduction, checked on a trace recorded on a v5e (kept in
``data/chip.xplane.pb``; ``data/chip.json`` says what the program was
asked to run inside it; ``record_trace.py`` records the pair),
and on HLO texts taken from a chip trace."""
import json
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
XPLANE = os.path.join(DATA, "chip.xplane.pb")

KERNEL_OP = (
    '%kmeans_assign_pallas.8 = (s32[1,2560]{1,0:T(1,128)}, '
    'f32[4,64]{1,0:T(4,128)S(1)}, f32[4,1]{1,0:T(4,128)S(1)}) '
    'custom-call(f32[2560,64]{1,0:T(8,128)S(1)} %pad.11, '
    'f32[4,64]{1,0:T(4,128)S(1)} %get-tuple-element.120), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{f32[2560,64]{1,0}, f32[4,64]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}')
COPY_OP = ('%copy.1 = f32[2400,784,200]{0,2,1:T(8,128)} copy('
           'f32[2400,784,200]{0,2,1:T(8,128)} %args_0_.1)')


def test_a_pallas_kernel_is_named_and_its_operands_read():
    assert trace.kernel_of(KERNEL_OP) == "kmeans_assign_pallas"
    assert trace.operand_shapes(KERNEL_OP) == [(2560, 64), (4, 64)]
    assert trace.kernel_of(COPY_OP) is None
    assert trace.op_name(COPY_OP) == "copy f32[2400,784,200]"
    assert trace.module_name("jit__ingest(2821487998888354679)") == \
        "jit__ingest"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "chip.json")) as f:
        return trace.reduce(XPLANE), json.load(f)


def test_programs_are_counted_under_stable_names(recorded):
    reduced, asked = recorded
    assert reduced["modules"]["jit__ingest"]["n"] == asked["ingest_waves"]
    assert reduced["modules"]["jit_route_fn"]["n"] == asked["route_batches"]
    assert reduced["modules"]["jit__ingest"]["s"] > 0


def test_kernel_calls_carry_their_shapes(recorded):
    reduced, asked = recorded
    calls = trace.kernel_calls(reduced, "kmeans_assign")
    route = [c for c in calls
             if c["operands"][0] == (asked["route_kernel_rows"], 64)]
    assert len(route) == asked["route_batches"]
    assert all(c["operands"][1] == (asked["clusters"], 64) for c in calls)
    # the round's Lloyd loop calls the kernel over all the clients, padded
    # to whole blocks
    lloyd = [c for c in calls
             if c["operands"][0] == (asked["lloyd_kernel_rows"], 64)]
    assert len(lloyd) >= 1
    assert len(lloyd) + len(route) == len(calls)
    assert all(c["s"] > 0 for c in calls)


def test_busy_is_the_union_of_the_device_ops(recorded):
    import jax

    reduced, _ = recorded
    data = jax.profiler.ProfileData.from_file(XPLANE)
    plane = [p for p in data.planes if p.name == "/device:TPU:0"][0]
    events = [(e.start_ns, e.start_ns + e.duration_ns)
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events]
    # an independent union: sweep over the sorted edges, a start before
    # an end at the same instant
    edges = sorted([(a, -1) for a, _ in events] + [(b, 1) for _, b in events])
    depth, since, busy = 0, None, 0.0
    for t, end in edges:
        if depth == 0 and end == -1:
            since = t
        depth -= end
        if depth == 0:
            busy += t - since
    assert reduced["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0 < reduced["busy_s"] < (max(b for _, b in events)
                                    - min(a for a, _ in events)) * 1e-9


def test_breakdown_names_ops_and_gaps(recorded):
    reduced, _ = recorded
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(":" in name and s > 0 for name, s in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert any(name.startswith("bench.") for name, _ in gaps)
    assert np.all(np.diff([s for _, s in gaps]) <= 0)
