"""Parity of the vectorized host hot paths vs straightforward loop oracles."""

import numpy as np

from paddlebox_tpu.config import DataFeedConfig
from paddlebox_tpu.data.feed import build_rank_offset
from paddlebox_tpu.data.record import RecordBlock
from paddlebox_tpu.data.shuffle import _FNV_OFFSET, _FNV_PRIME, _hash_ins_ids


def _fnv_oracle(s: str) -> int:
    h = int(_FNV_OFFSET)
    for b in s.encode():
        h = ((h ^ b) * int(_FNV_PRIME)) & 0xFFFFFFFFFFFFFFFF
    return h


def test_hash_ins_ids_matches_fnv_oracle():
    ids = ["", "a", "ins-000123", "αβγ", "x" * 100, "ins-000123"]
    got = _hash_ins_ids(ids)
    want = np.asarray([_fnv_oracle(s) for s in ids], dtype=np.uint64)
    np.testing.assert_array_equal(got, want)


def test_hash_native_and_numpy_agree():
    """Routing must not depend on whether the native lib built."""
    from paddlebox_tpu import _native
    from paddlebox_tpu.data import shuffle as sh

    ids = [f"ins-{i:08d}" for i in range(500)] + ["", "漢字", "a b c"]
    native = _native.hash_ids_native(ids)
    if native is None:
        import pytest

        pytest.skip("native lib unavailable")
    orig = _native.hash_ids_native
    try:
        _native.hash_ids_native = lambda _ids: None  # force numpy path
        pure = sh._hash_ins_ids(ids)
    finally:
        _native.hash_ids_native = orig
    np.testing.assert_array_equal(native, pure)


def _random_block(rng, n_ins, s, with_logkey=True):
    lens = rng.integers(0, 5, size=(n_ins, s))
    offsets = np.zeros(n_ins * s + 1, dtype=np.int64)
    np.cumsum(lens.reshape(-1), out=offsets[1:])
    keys = rng.integers(1, 1 << 40, size=int(offsets[-1])).astype(np.uint64)
    return RecordBlock(
        n_ins=n_ins,
        n_sparse_slots=s,
        keys=keys,
        key_offsets=offsets,
        dense=rng.normal(size=(n_ins, 2)).astype(np.float32),
        labels=rng.integers(0, 2, size=n_ins).astype(np.float32),
        ranks=rng.integers(0, 5, size=n_ins).astype(np.int32)
        if with_logkey else None,
        cmatches=rng.choice(
            np.array([222, 223, 111], dtype=np.int32), size=n_ins
        ) if with_logkey else None,
    )


def _shuffle_slots_oracle(block, slot_idxs, rng):
    """The pre-vectorization per-instance loop, kept as the oracle."""
    s = block.n_sparse_slots
    lens = np.diff(block.key_offsets).reshape(block.n_ins, s).copy()
    new_vals = {}
    for si in slot_idxs:
        perm = rng.permutation(block.n_ins)
        rows = np.arange(block.n_ins) * s + si
        starts = block.key_offsets[rows][perm]
        plens = lens[:, si][perm]
        new_vals[si] = (starts, plens)
        lens[:, si] = plens
    new_offsets = np.zeros(block.n_ins * s + 1, dtype=np.int64)
    np.cumsum(lens.reshape(-1), out=new_offsets[1:])
    keys = np.empty(int(new_offsets[-1]), dtype=np.uint64)
    for i in range(block.n_ins):
        for si in range(s):
            r = i * s + si
            lo, hi = new_offsets[r], new_offsets[r + 1]
            if si in new_vals:
                st, pl = new_vals[si]
                keys[lo:hi] = block.keys[st[i] : st[i] + pl[i]]
            else:
                olo = block.key_offsets[r]
                keys[lo:hi] = block.keys[olo : olo + (hi - lo)]
    return keys, new_offsets


def test_shuffle_slots_matches_loop_oracle():
    from paddlebox_tpu.data.dataset import _shuffle_slots

    rng = np.random.default_rng(0)
    block = _random_block(rng, 200, 4)
    got = _shuffle_slots(block, [1, 3], np.random.default_rng(42))
    want_keys, want_offs = _shuffle_slots_oracle(
        block, [1, 3], np.random.default_rng(42)
    )
    np.testing.assert_array_equal(got.key_offsets, want_offs)
    np.testing.assert_array_equal(got.keys, want_keys)


def _rank_offset_oracle(block, ids, pv_bounds, batch_size, max_rank,
                        cmatch_filter=None):
    """The pre-vectorization per-PV loop, kept as the oracle."""
    cols = 2 * max_rank + 1
    mat = np.full((batch_size, cols), -1, dtype=np.int32)
    if block.ranks is None:
        return mat
    ranks = block.ranks[ids]
    cmatches = (
        block.cmatches[ids] if block.cmatches is not None
        else np.zeros_like(ranks)
    )
    ok = (ranks > 0) & (ranks <= max_rank)
    if cmatch_filter is not None:
        ok &= np.isin(cmatches, np.asarray(list(cmatch_filter)))
    eff = np.where(ok, ranks, -1)
    for p in range(pv_bounds.shape[0] - 1):
        lo, hi = int(pv_bounds[p]), int(pv_bounds[p + 1])
        members = np.arange(lo, hi)
        mat[members, 0] = eff[lo:hi]
        ranked = members[eff[lo:hi] > 0]
        for j in members:
            if eff[j] <= 0:
                continue
            for k in ranked:
                m = eff[k] - 1
                mat[j, 2 * m + 1] = eff[k]
                mat[j, 2 * m + 2] = k
    return mat


def test_build_rank_offset_matches_loop_oracle():
    rng = np.random.default_rng(1)
    n = 64
    block = _random_block(rng, n, 2)
    ids = rng.permutation(n)
    # random PV partition of the 64 ids
    cuts = np.sort(rng.choice(np.arange(1, n), size=12, replace=False))
    pv_bounds = np.concatenate([[0], cuts, [n]]).astype(np.int64)
    for filt in (None, (222, 223)):
        got = build_rank_offset(block, ids, pv_bounds, 80, 3, filt)
        want = _rank_offset_oracle(block, ids, pv_bounds, 80, 3, filt)
        np.testing.assert_array_equal(got, want)


def test_build_rank_offset_no_ranked():
    rng = np.random.default_rng(2)
    block = _random_block(rng, 8, 2)
    block = RecordBlock(
        **{**block.__dict__, "ranks": np.zeros(8, dtype=np.int32)}
    )
    ids = np.arange(8)
    pv_bounds = np.asarray([0, 4, 8], dtype=np.int64)
    got = build_rank_offset(block, ids, pv_bounds, 8, 3)
    assert (got[:, 1:] == -1).all()


def test_vectorized_paths_scale(capsys):
    """Micro-bench at meaningful scale.  Fails only on gross (>60s) regression; prints throughput."""
    import time

    n = 200_000
    ids = [f"ins-{i:012d}" for i in range(n)]
    t0 = time.perf_counter()
    _hash_ins_ids(ids)
    t_hash = time.perf_counter() - t0

    from paddlebox_tpu.data.dataset import _shuffle_slots

    rng = np.random.default_rng(3)
    block = _random_block(rng, n, 4)
    t0 = time.perf_counter()
    _shuffle_slots(block, [0, 2], rng)
    t_shuf = time.perf_counter() - t0

    ids_arr = np.arange(n)
    pv_bounds = np.arange(0, n + 1, 4, dtype=np.int64)  # 4-ad PVs
    t0 = time.perf_counter()
    build_rank_offset(block, ids_arr, pv_bounds, n, 3, (222, 223))
    t_rank = time.perf_counter() - t0
    print(
        f"\n[host-bench n={n}] hash {n/t_hash:,.0f}/s  "
        f"slots_shuffle {n/t_shuf:,.0f} ins/s  rank_offset {n/t_rank:,.0f} ins/s"
    )
    assert t_hash < 60 and t_shuf < 60 and t_rank < 60
