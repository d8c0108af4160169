"""The reference's own arithmetic: which draw a client holds at a clock,
the partition match, and the JL sketch's block layout."""
import numpy as np

from bench import reference


def test_schedule_tracks_each_blocks_last_wave():
    s = reference.Schedule(clients=6, wave=2, draws=2)
    assert s.blocks == 3
    assert list(s.block_draws(3)) == [0, 0, 0]        # the first pass
    assert list(s.block_draws(4)) == [1, 0, 0]        # wave 3: block 0, draw 1
    assert list(s.block_draws(7)) == [0, 1, 1]        # wave 6: block 0, draw 0
    assert list(s.row_draws(5)) == [1, 1, 1, 1, 0, 0]
    assert (s.wave_rows(4), s.wave_draw(4)) == ((2, 4), 1)


def test_match_counts_clients_off_their_cluster():
    truth = np.array([0, 0, 1, 1, 2, 2])
    mapping, errors = reference.match(np.array([5, 5, 3, 3, 4, 4]), truth)
    assert mapping == {0: 5, 1: 3, 2: 4} and errors == 0
    _, errors = reference.match(np.array([5, 5, 3, 5, 4, 4]), truth)
    assert errors == 1
    # two planted clusters merged into one: one of them is all wrong
    _, errors = reference.match(np.array([1, 1, 1, 1, 2, 2]), truth)
    assert errors == 2


def test_jl_sketch_is_the_blockwise_projection():
    rng = np.random.default_rng(0)
    vec = rng.standard_normal((3, 300)).astype(np.float32)
    blocks = reference.projection(seed=5, n=300, sketch_dim=8)
    assert reference.jl_block(300) == 512 and len(blocks) == 1
    want = vec.astype(np.float64) @ blocks[0][:300].astype(np.float64)
    got = reference.jl_sketch(vec, blocks, 8)
    np.testing.assert_allclose(got, want / np.sqrt(8), rtol=1e-12)
    assert reference.jl_block(159010) == 1 << 16
    assert len(reference.projection(seed=5, n=159010, sketch_dim=2)) == 3
