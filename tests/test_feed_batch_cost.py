"""BatchBuilder.build: what a batch holds, and what it costs.

(a) ``build`` / ``build_pv`` are bit-identical to a straightforward
per-instance packer (a Python loop over instances and slots, the oracle
kept here) on seeded ragged blocks.  (b) A batch's host cost is O(the
batch), independent of the block it is taken from: the allocation peak of
one ``build`` of the same ids is the same on a block ten times as long.
Both hold for each form of the key pack (``pack_path``): the native pass
and the numpy form it falls back to.  (c) Over a dataset's pass no batch is
packed by numpy where the library is loaded (``data.batches_built``).
Counts and equality only; nothing here reads a clock."""

import dataclasses
import tracemalloc

import numpy as np
import pytest

from paddlebox_tpu import telemetry
from paddlebox_tpu._native import get_lib
from paddlebox_tpu.config import DataFeedConfig, SlotConfig
from paddlebox_tpu.data import BatchBuilder, RecordBlock, feed
from paddlebox_tpu.data.feed import build_rank_offset
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files


def built():
    """``data.batches_built`` as {path: count}."""
    c = telemetry.counter("data.batches_built")
    return {by: c.value(by=by) for by in ("native", "numpy")}


@pytest.fixture(params=["native", "numpy"])
def pack_path(request, monkeypatch):
    """Runs the test once on each form of the key pack and, after it,
    holds every ``build`` of the test to have been counted under it."""
    if request.param == "numpy":
        monkeypatch.setattr(feed, "pack_batch_native", lambda *a: None)
    elif get_lib() is None:
        pytest.skip("the data layer's native library did not build")
    before = built()
    yield request.param
    other = "numpy" if request.param == "native" else "native"
    after = built()
    assert after[request.param] > before[request.param]
    assert after[other] == before[other]


def feed_conf(n_sparse, batch_size, **kw):
    slots = [SlotConfig("click", type="float", is_dense=True, shape=(1,))]
    slots += [SlotConfig(f"s{i}", type="uint64") for i in range(n_sparse)]
    slots.append(SlotConfig("dense_x", type="float", is_dense=True, shape=(3,)))
    return DataFeedConfig(slots=slots, batch_size=batch_size, **kw)


def ragged_block(seed, n_ins, n_sparse, lo=0, hi=4, meta=False):
    """Seeded block with ``lo..hi-1`` keys a slot (``lo=0``: empty slots)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, size=n_ins * n_sparse)
    offsets = np.zeros(n_ins * n_sparse + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    extra = {}
    if meta:
        extra = dict(
            ins_ids=[f"ins-{i}" for i in range(n_ins)],
            ranks=rng.integers(0, 5, size=n_ins).astype(np.int32),
            cmatches=rng.choice([222, 223, 7], size=n_ins).astype(np.int32),
            task_labels=rng.random((n_ins, 2)).astype(np.float32),
        )
    return RecordBlock(
        n_ins=n_ins,
        n_sparse_slots=n_sparse,
        keys=rng.integers(1, 2**48, size=int(offsets[-1])).astype(np.uint64),
        key_offsets=offsets,
        dense=rng.random((n_ins, 3)).astype(np.float32),
        labels=rng.integers(0, 2, size=n_ins).astype(np.float32),
        **extra,
    )


def reference_pack(conf, block, ids, seq_slot=None):
    """One instance and one slot at a time; keys past the capacity are
    dropped in order.  Returns the fields ``build`` must reproduce."""
    B, S = conf.batch_size, block.n_sparse_slots
    K = conf.batch_key_capacity or B * conf.max_feasigns_per_ins
    T = conf.max_seq_len
    keys = np.zeros(K, dtype=np.uint64)
    segs = np.full(K, B * S, dtype=np.int32)
    seq_pos = None if seq_slot is None else np.full((B, T), K, dtype=np.int32)
    dense = np.zeros((B, block.dense.shape[1]), dtype=np.float32)
    labels = np.zeros(B, dtype=np.float32)
    mask = np.zeros(B, dtype=np.float32)
    used = dropped = 0
    for i, ins in enumerate(ids):
        ins = int(ins)
        for s in range(S):
            vals = block.slot_slice(ins, s)
            take = min(len(vals), K - used)
            dropped += len(vals) - take
            keys[used:used + take] = vals[:take]
            segs[used:used + take] = i * S + s
            if s == seq_slot:
                n = min(take, T)
                seq_pos[i, :n] = np.arange(used, used + n)
            used += take
        dense[i] = block.dense[ins]
        labels[i] = block.labels[ins]
        mask[i] = 1.0
    out = dict(keys=keys, key_segments=segs, n_keys=used, seq_pos=seq_pos,
               dense=dense, labels=labels, ins_mask=mask)
    if block.task_labels is not None:
        tl = np.zeros((B, 1 + block.task_labels.shape[1]), dtype=np.float32)
        for i, ins in enumerate(ids):
            tl[i, 0] = block.labels[ins]
            tl[i, 1:] = block.task_labels[ins]
        out["task_labels"] = tl
    for name in ("cmatches", "ranks"):
        col = getattr(block, name)
        if col is not None:
            full = np.full(B, -1, dtype=np.int32)
            for i, ins in enumerate(ids):
                full[i] = col[ins]
            out[name] = full
    if block.ins_ids is not None:
        out["ins_ids"] = [block.ins_ids[int(i)] for i in ids]
    return out, dropped


def assert_same_batch(got, want):
    for name, value in want.items():
        have = getattr(got, name)
        if isinstance(value, np.ndarray):
            assert have.dtype == value.dtype, name
            np.testing.assert_array_equal(have, value, err_msg=name)
        else:
            assert have == value, name


N, S, B = 257, 5, 32

# name -> (ids of the batch, DataFeedConfig overrides, block options)
CASES = {
    "full_batch_with_empty_slots": (np.arange(40, 40 + B), {}, {}),
    "block_last_instance": (np.arange(N - B, N), {}, {}),
    "tail_batch": (np.arange(N - 7, N), {}, {}),
    "single_instance": (np.array([N - 1]), {}, {}),
    "shuffled_ids": (np.random.default_rng(5).permutation(N)[:B], {}, {}),
    "repeated_ids": (np.array([3, 3, 200, 3, N - 1, 200]), {}, {}),
    "overflow_clip": (np.random.default_rng(6).permutation(N)[:B],
                      dict(batch_key_capacity=97), {}),
    "overflow_clip_mid_slot": (np.arange(B), dict(batch_key_capacity=10),
                               dict(lo=3, hi=4)),
    "sequence_slot": (np.random.default_rng(7).permutation(N)[:B],
                      dict(sequence_slot="s2", max_seq_len=2), {}),
    "sequence_slot_tail_overflow": (
        np.arange(N - 9, N),
        dict(sequence_slot="s4", max_seq_len=3, batch_key_capacity=41), {}),
    "logkey_metadata": (np.random.default_rng(8).permutation(N)[:B - 3], {},
                        dict(meta=True)),
    "all_slots_empty": (np.arange(B), {}, dict(lo=0, hi=1)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_build_matches_per_instance_packer(case, pack_path):
    ids, conf_kw, block_kw = CASES[case]
    conf = feed_conf(S, B, max_feasigns_per_ins=16, **conf_kw)
    block = ragged_block(11, N, S, **block_kw)
    bb = BatchBuilder(conf)
    got = bb.build(block, ids)
    want, dropped = reference_pack(conf, block, ids, bb.seq_slot_idx)
    assert_same_batch(got, want)
    assert bb.dropped_keys == dropped
    assert (dropped > 0) == ("overflow" in case)
    assert got.rank_offset is None
    assert got.batch_size == B and got.n_sparse_slots == S


@pytest.mark.parametrize("cmatch_filter", [(222, 223), None])
def test_build_pv_matches_per_instance_packer(cmatch_filter, pack_path):
    """Same packing, plus the rank matrix of ``build_rank_offset`` (which
    tests/test_host_vectorized.py holds to its own loop oracle) under this
    configuration's batch size, max rank and cmatch filter."""
    conf = feed_conf(S, B, max_feasigns_per_ins=16,
                     rank_cmatch_filter=cmatch_filter)
    block = ragged_block(12, N, S, meta=True)
    ids = np.random.default_rng(9).permutation(N)[:B - 2]
    pv_bounds = np.array([0, 1, 5, 6, 14, 20, B - 2], dtype=np.int64)
    got = BatchBuilder(conf).build_pv(block, ids, pv_bounds)
    want, _ = reference_pack(conf, block, ids)
    assert_same_batch(got, want)
    np.testing.assert_array_equal(
        got.rank_offset,
        build_rank_offset(block, ids, pv_bounds, B, conf.max_rank,
                          cmatch_filter))
    assert (got.rank_offset[:, 0] > 0).any()


# ---------------------------------------------------------------- (b) cost
PASS_SLOTS, PASS_BATCH = 26, 2048


@pytest.fixture(scope="module")
def pass_blocks():
    """A 200,000-instance block at the benchmark cell's shape (26 slots,
    1-3 keys a slot) and its first 20,000 instances as a block of its own,
    so the same ids select the same keys from both."""
    big = ragged_block(21, 200_000, PASS_SLOTS, lo=1, hi=4, meta=True)
    n = 20_000
    end = n * PASS_SLOTS
    small = dataclasses.replace(
        big, n_ins=n, keys=big.keys[:big.key_offsets[end]],
        key_offsets=big.key_offsets[:end + 1], dense=big.dense[:n],
        labels=big.labels[:n], ins_ids=big.ins_ids[:n], ranks=big.ranks[:n],
        cmatches=big.cmatches[:n], task_labels=big.task_labels[:n])
    return small, big


def build_peak_bytes(method, block, ids):
    conf = feed_conf(PASS_SLOTS, PASS_BATCH, max_feasigns_per_ins=104)
    bb = BatchBuilder(conf)
    args = (block, ids)
    if method == "build_pv":
        args += (np.arange(0, ids.shape[0] + 1, 4, dtype=np.int64),)
    getattr(bb, method)(*args)  # lazy imports and caches, outside the peak
    tracemalloc.start()
    try:
        batch = getattr(bb, method)(*args)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    return peak, batch


@pytest.mark.parametrize("method", ["build", "build_pv"])
def test_build_cost_is_independent_of_block_size(pass_blocks, method,
                                                 pack_path):
    small, big = pass_blocks
    ids = np.random.default_rng(22).permutation(small.n_ins)[:PASS_BATCH]
    peak_small, from_small = build_peak_bytes(method, small, ids)
    peak_big, from_big = build_peak_bytes(method, big, ids)
    np.testing.assert_array_equal(from_small.keys, from_big.keys)
    assert from_small.n_keys == from_big.n_keys > PASS_BATCH * PASS_SLOTS
    # a difference over the whole block would add 8 bytes an offset:
    # 37 MB between these two blocks, against a batch's few MB
    assert peak_small > 1_000_000
    assert abs(peak_big - peak_small) <= 0.1 * peak_small, (
        peak_small, peak_big)


# ------------------------------------------------------- (c) a pass's path
def test_no_batch_of_a_pass_is_packed_by_numpy(tmp_path):
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset

    if get_lib() is None:
        pytest.skip("the data layer's native library did not build")

    conf = make_synth_config(n_sparse_slots=3, dense_dim=2, batch_size=16,
                             max_feasigns_per_ins=16)
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(write_synth_files(
        str(tmp_path / "data"), n_files=3, ins_per_file=40, n_sparse_slots=3,
        vocab_per_slot=50, dense_dim=2, seed=5))
    ds.load_into_memory()
    ds.local_shuffle()
    before = built()
    n_batches = sum(1 for _ in ds.batches())
    after = built()
    assert n_batches == 8  # 120 instances in batches of 16
    assert after["native"] - before["native"] == n_batches
    assert after["numpy"] == before["numpy"]
