"""The generator: same seed same data, the text round-trips through the
program's parser, the Zipf is bounded."""

import numpy as np

from benchmark import gen
from benchmark.run import feed_config, make_dataset
from benchmark.tests.toy import TOY_MIX, toy_cell


def test_same_seed_same_passes_and_large_seed():
    a = gen.make_passes(TOY_MIX, 6, 3, 2 ** 31 + 11)
    b = gen.make_passes(TOY_MIX, 6, 3, 2 ** 31 + 11)
    c = gen.make_passes(TOY_MIX, 6, 3, 5)
    assert len(a) == TOY_MIX["distinct_passes"]
    for x, y in zip(a, b):
        assert np.array_equal(x.keys, y.keys)
        assert np.array_equal(x.labels, y.labels)
        assert np.array_equal(x.dense, y.dense)
    assert not np.array_equal(a[0].keys, c[0].keys)
    counts = (a[0].keys != 0).sum(axis=2)
    assert counts.min() >= 1 and counts.max() <= 3


def test_bounded_zipf_has_no_pile_up_on_the_last_key():
    mix = dict(TOY_MIX, slot_vocab=100, instances_per_pass=20000,
               distinct_passes=1)
    cdf = gen._zipf_cdf(mix, 100)
    u = np.random.default_rng(0).random(200000) * cdf[-1]
    ranks = np.minimum(np.searchsorted(cdf, u, side="right"), 99)
    hist = np.bincount(ranks, minlength=100)
    assert hist[0] > hist[1] > hist[5] > hist[50]
    assert hist[99] <= hist[90] * 1.5  # the tail keeps falling


def test_text_round_trips_through_the_parser(tmp_path):
    cell = toy_cell("ctr_dnn_criteo")
    p = gen.make_passes(cell.mix, 6, 3, 9)[0]
    conf = feed_config(cell.cfg)
    ds = make_dataset(conf, p, str(tmp_path), "p", 3)
    try:
        assert np.array_equal(ds.unique_keys(), p.census())
        got = list(ds.batches())
        B = cell.cfg["batch_size"]
        assert len(got) == p.n // B
        for i, b in enumerate(got):
            want = p.rows(i * B, (i + 1) * B)
            k = want.keys[want.keys != 0]
            assert np.array_equal(np.sort(b.keys[: b.n_keys]), np.sort(k))
            assert np.array_equal(b.labels, want.labels)
            assert np.array_equal(b.dense, want.dense)
    finally:
        ds.close()


def test_initial_rows_are_counters_and_small_weights():
    rows = gen.initial_rows(1000, 3, 16)
    assert rows.shape == (1000, 19) and rows.dtype == np.float32
    assert np.array_equal(rows[:, :2], np.floor(rows[:, :2]))
    assert (rows[:, 1] <= rows[:, 0]).all() and (rows[:, -1] == 0).all()
    assert np.abs(rows[:, 2:-1]).max() <= 0.02
    assert np.array_equal(rows, gen.initial_rows(1000, 3, 16))


def test_every_seed_has_the_same_census_and_cycle_sizes():
    mix = dict(TOY_MIX, census_keys=440, cycle_keys=600)
    for seed in (1, 2, 3, 2 ** 31 + 5):
        passes = gen.make_passes(mix, 6, 3, seed)
        cs = [p.census() for p in passes]
        assert [c.shape[0] for c in cs] == [440, 440]
        assert np.unique(np.concatenate(cs)).shape[0] == 600
        counts = (passes[0].keys != 0).sum(axis=2)
        assert counts.min() >= 1  # fitting replaces keys, it drops none
    import pytest

    with pytest.raises(ValueError, match="out of reach"):
        gen.make_passes(dict(TOY_MIX, census_keys=5000), 6, 3, 1)


def test_key_space_holds_every_key_a_pass_can_draw():
    space = gen.key_space(TOY_MIX, 6)
    assert space.shape[0] == sum(TOY_MIX["slot_vocab"])  # no collision here
    assert (space[1:] > space[:-1]).all()
    for seed in (4, 2 ** 31 + 9):
        for p in gen.make_passes(TOY_MIX, 6, 3, seed):
            assert np.isin(p.census(), space).all()


def test_initial_rows_do_not_depend_on_how_many_are_drawn():
    big = gen.initial_rows(gen._ROW_BLOCK + 50, 8, 10)
    assert np.array_equal(big[:700], gen.initial_rows(700, 8, 10))
    assert not np.array_equal(big[:50], big[gen._ROW_BLOCK:])
