"""The batch builder's native key pack (_native/slot_parser.cpp
pbx_pack_batch) against its numpy form (data/feed.py _pack_keys_numpy):
the whole HostBatch byte for byte, and the dropped count, on every shape of
batch the builder meets.  tests/test_feed_batch_cost.py holds both forms to
a per-instance loop; this file holds them to each other at sizes the loop
is too slow for."""

import dataclasses

import numpy as np
import pytest

from paddlebox_tpu._native import get_lib
from paddlebox_tpu.config import DataFeedConfig, SlotConfig
from paddlebox_tpu.data import BatchBuilder, RecordBlock, feed

N, S, B = 3000, 7, 256


@pytest.fixture(autouse=True)
def _needs_library():
    if get_lib() is None:
        pytest.skip("the data layer's native library did not build")


def _conf(**kw):
    slots = [SlotConfig("click", type="float", is_dense=True, shape=(1,))]
    slots += [SlotConfig(f"s{i}", type="uint64") for i in range(S)]
    slots.append(SlotConfig("dense_x", type="float", is_dense=True, shape=(3,)))
    kw.setdefault("max_feasigns_per_ins", 40)
    return DataFeedConfig(slots=slots, batch_size=B, **kw)


def _block(seed, hi=4, long_slot=None, meta=False, empty_ins=()):
    """``0..hi-1`` keys a slot; ``long_slot`` holds runs of 0..39 keys;
    the instances of ``empty_ins`` hold none at all."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, hi, size=(N, S))
    if long_slot is not None:
        lens[:, long_slot] = rng.integers(0, 40, size=N)
    lens[list(empty_ins)] = 0
    offsets = np.zeros(N * S + 1, dtype=np.int64)
    np.cumsum(lens.reshape(-1), out=offsets[1:])
    extra = {}
    if meta:
        extra = dict(
            ins_ids=[f"ins-{i}" for i in range(N)],
            ranks=rng.integers(0, 5, size=N).astype(np.int32),
            cmatches=rng.choice([222, 223, 7], size=N).astype(np.int32),
            task_labels=rng.random((N, 2)).astype(np.float32),
        )
    return RecordBlock(
        n_ins=N, n_sparse_slots=S,
        keys=rng.integers(1, 2**48, size=int(offsets[-1])).astype(np.uint64),
        key_offsets=offsets,
        dense=rng.random((N, 3)).astype(np.float32),
        labels=rng.integers(0, 2, size=N).astype(np.float32),
        **extra,
    )


_SHUFFLED = np.random.default_rng(3).permutation(N)

# name -> (ids, DataFeedConfig overrides, block options)
CASES = {
    "ascending_ids": (np.arange(B, 2 * B), {}, {}),
    "shuffled_ids": (_SHUFFLED[:B], {}, {}),
    "short_last_batch": (np.arange(N - 37, N), {}, {}),
    "empty_batch": (np.arange(0), {}, {}),
    "all_empty_instance": (np.arange(B), {},
                           dict(empty_ins=(0, 17, B - 1))),
    "every_slot_empty": (_SHUFFLED[:B], {}, dict(hi=1)),
    "over_capacity": (_SHUFFLED[:B], dict(batch_key_capacity=1001), {}),
    "over_capacity_mid_run": (np.arange(B), dict(batch_key_capacity=203),
                              dict(long_slot=2)),
    "capacity_of_one_key": (np.arange(B), dict(batch_key_capacity=1), {}),
    "sequence_runs_over_max_seq_len": (
        _SHUFFLED[:B], dict(sequence_slot="s2", max_seq_len=8),
        dict(long_slot=2)),
    "sequence_slot_clipped_tail": (
        np.arange(N - 50, N),
        dict(sequence_slot="s6", max_seq_len=5, batch_key_capacity=301),
        dict(long_slot=6)),
    "task_labels_and_logkeys": (_SHUFFLED[:B - 5], {}, dict(meta=True)),
}


def _build(conf, block, ids, monkeypatch, native, pv_bounds=None):
    with monkeypatch.context() as m:
        if not native:
            m.setattr(feed, "pack_batch_native", lambda *a: None)
        bb = BatchBuilder(conf)
        batch = (bb.build(block, ids) if pv_bounds is None
                 else bb.build_pv(block, ids, pv_bounds))
    return batch, bb.dropped_keys


def _assert_same(native, numpy):
    for f in dataclasses.fields(native):
        a, b = getattr(native, f.name), getattr(numpy, f.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, f.name
            assert a.tobytes() == b.tobytes(), f.name
        else:
            assert a == b, f.name


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_pack_equals_numpy_form(case, monkeypatch):
    ids, conf_kw, block_kw = CASES[case]
    conf, block = _conf(**conf_kw), _block(31, **block_kw)
    native, dropped = _build(conf, block, ids, monkeypatch, native=True)
    numpy, dropped_np = _build(conf, block, ids, monkeypatch, native=False)
    _assert_same(native, numpy)
    assert dropped == dropped_np
    assert (dropped > 0) == ("capacity" in case or "clipped" in case)
    assert native.n_keys <= native.keys.shape[0]
    if "sequence" in case:
        assert (native.seq_pos < native.keys.shape[0]).any()
        # a run longer than max_seq_len fills its row and stops there
        assert (native.seq_pos[:len(ids)] < native.keys.shape[0]).all(1).any()


@pytest.mark.parametrize("cmatch_filter", [(222, 223), None])
def test_native_pack_equals_numpy_form_pv(cmatch_filter, monkeypatch):
    conf = _conf(rank_cmatch_filter=cmatch_filter)
    block = _block(32, meta=True)
    ids = _SHUFFLED[:B - 2]
    pv_bounds = np.arange(0, B - 1, 2, dtype=np.int64)
    native, _ = _build(conf, block, ids, monkeypatch, True, pv_bounds)
    numpy, _ = _build(conf, block, ids, monkeypatch, False, pv_bounds)
    _assert_same(native, numpy)
    assert (native.rank_offset[:, 0] > 0).any()


def test_each_batch_gets_fresh_arrays():
    """The prefetch queue holds two feeds and the step a third: a later
    ``build`` must not write into an earlier batch's buffers."""
    conf, block = _conf(), _block(33)
    bb = BatchBuilder(conf)
    first = bb.build(block, np.arange(B))
    keys, segs = first.keys.copy(), first.key_segments.copy()
    second = bb.build(block, np.arange(B, 2 * B))
    assert not np.shares_memory(first.keys, second.keys)
    assert not np.shares_memory(first.key_segments, second.key_segments)
    np.testing.assert_array_equal(first.keys, keys)
    np.testing.assert_array_equal(first.key_segments, segs)


@pytest.mark.parametrize("bad", [-1, N])
def test_ids_outside_the_block_are_refused(bad):
    """The native loop reads where ``ids`` point: they are checked first."""
    with pytest.raises(IndexError):
        BatchBuilder(_conf()).build(_block(34), np.array([0, bad, 5]))


def test_a_block_of_another_layout_takes_the_numpy_form():
    """A pointer is only passed for the contiguous uint64 / int64 arrays
    the parser makes; anything else is packed by numpy, and counted so."""
    from paddlebox_tpu import telemetry

    block = _block(35)
    strided = dataclasses.replace(block, keys=np.repeat(block.keys, 2)[::2])
    assert not strided.keys.flags.c_contiguous
    c = telemetry.counter("data.batches_built")
    before = c.value(by="numpy")
    got = BatchBuilder(_conf()).build(strided, _SHUFFLED[:B])
    assert c.value(by="numpy") == before + 1
    _assert_same(got, BatchBuilder(_conf()).build(block, _SHUFFLED[:B]))
