"""The pass's key census: made once, where the block of records is made
(the dataset's load), byte-equal to ``np.unique(block.keys)`` on every
path, and served from the block afterwards (``data.census_served``)."""

import threading

import numpy as np
import pytest

from paddlebox_tpu import telemetry
from paddlebox_tpu.data.dataset import PadBoxSlotDataset
from paddlebox_tpu.data.record import RecordBlock
from paddlebox_tpu.data.shuffle import InProcessShuffleGroup
from paddlebox_tpu.data.synth import make_synth_config, write_synth_files

S = 3


def _served():
    c = telemetry.counter("data.census_served")
    return np.array([c.value(**{"from": "load"}), c.value(**{"from": "scan"})])


def _hist_count(name):
    series = telemetry.histogram(name).series()
    return sum(s.count for s in series.values())


def _dataset(tmp_path, n_files=3, ins_per_file=40, **kw):
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=2, batch_size=16,
        max_feasigns_per_ins=16, **kw)
    files = write_synth_files(
        str(tmp_path / "data"), n_files=n_files, ins_per_file=ins_per_file,
        n_sparse_slots=S, vocab_per_slot=50, dense_dim=2, seed=5,
        with_logkey=bool(kw.get("parse_logkey")))
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(files)
    return ds


def _check(census, keys):
    assert census.dtype == np.uint64
    assert census.tobytes() == np.unique(keys).tobytes()
    assert not census.flags.writeable  # shared: nobody sorts it in place


def _load(tmp_path):
    ds = _dataset(tmp_path)
    ds.load_into_memory()
    return ds


def _load_one_file(tmp_path):
    ds = _dataset(tmp_path, n_files=1)
    ds.load_into_memory()
    return ds


def _preload(tmp_path):
    ds = _dataset(tmp_path)
    ds.preload_into_memory()
    ds.wait_preload_done()
    return ds


def _reload(tmp_path):
    """A second load replaces the block, and the census with it."""
    ds = _dataset(tmp_path)
    ds.load_into_memory()
    first = ds.unique_keys()
    ds.set_filelist(ds.filelist[:1])
    ds.load_into_memory()
    assert ds.unique_keys().shape[0] < first.shape[0]
    return ds


def _local_shuffle(tmp_path):
    ds = _load(tmp_path)
    ds.local_shuffle(seed=1)
    return ds


def _pv_merge(tmp_path):
    ds = _dataset(tmp_path, parse_logkey=True, enable_pv_merge=True)
    ds.load_into_memory()
    ds.preprocess_instance()
    assert ds.pv_mode
    return ds


def _exchanged(tmp_path):
    """Two workers exchange records at load: each census is of what the
    worker HOLDS afterwards, not of the files it parsed."""
    group = InProcessShuffleGroup(2, mode="random", seed=3)
    out, errs = [None, None], []

    def load(i):
        try:
            ds = _dataset(tmp_path / f"w{i}", n_files=2)
            ds.shuffler = group.shuffler(i)
            parsed = np.unique(np.concatenate(
                [ds.parser.parse_file(f).keys for f in ds.filelist]))
            ds.load_into_memory()
            out[i] = (ds, parsed)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=load, args=(i,)) for i in range(2)]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    assert not errs, errs
    (ds, parsed), (other, _) = out
    assert ds.unique_keys().tobytes() != parsed.tobytes()  # it did exchange
    _check(other.unique_keys(), other._block.keys)
    other.close()
    return ds


@pytest.mark.parametrize("make", [
    _load, _load_one_file, _preload, _reload, _local_shuffle, _pv_merge,
    _exchanged,
], ids=lambda f: f.__name__.strip("_"))
def test_loaded_dataset_holds_its_census(tmp_path, make):
    """Every load path leaves a block that already holds its census: no
    unique_keys() call scans the keys."""
    before = _served()
    ds = make(tmp_path)
    mid = _served()
    census = ds.unique_keys()
    _check(census, ds._block.keys)
    assert ds.unique_keys() is census  # the same array at every boundary
    assert _served()[1] == before[1]  # from=scan never moved
    assert _served()[0] == mid[0] + 2  # from=load: the two calls above
    ds.close()


def _by_hand(block):
    return RecordBlock(
        n_ins=block.n_ins, n_sparse_slots=block.n_sparse_slots,
        keys=block.keys[::-1].copy(), key_offsets=block.key_offsets,
        dense=block.dense, labels=block.labels)


def _slots_shuffled(ds):
    ds.slots_shuffle(["slot0", "slot2"], seed=4)
    return ds._block


@pytest.mark.parametrize("make", [
    lambda ds: ds._block.select(np.arange(0, ds._block.n_ins, 3)),
    lambda ds: RecordBlock.concat(
        [ds._block.select(np.arange(10)), ds._block.select(np.arange(50, 70))]),
    lambda ds: _by_hand(ds._block),
    _slots_shuffled,
], ids=["select", "concat", "by_hand", "slots_shuffle"])
def test_block_made_otherwise_counts_once(tmp_path, make):
    """A block that was not loaded computes its census at the first
    request (from=scan) and keeps it (from=load after that)."""
    ds = _load(tmp_path)
    loaded_block, loaded = ds._block, ds.unique_keys()
    block = make(ds)
    before = _served()
    census = block.unique_keys()
    _check(census, block.keys)
    assert (_served() - before).tolist() == [0, 1]
    assert block.unique_keys() is census
    assert (_served() - before).tolist() == [1, 1]
    # the loaded block keeps its own
    assert loaded_block.unique_keys() is loaded
    _check(loaded, loaded_block.keys)
    ds.close()


def test_empty_dataset_yields_empty_uint64(tmp_path):
    conf = make_synth_config(n_sparse_slots=S, dense_dim=2, batch_size=16)
    f = tmp_path / "empty"
    f.write_text("")
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist([str(f), str(f)])
    ds.load_into_memory()
    census = ds.unique_keys()
    assert census.dtype == np.uint64 and census.shape == (0,)
    ds.close()


def test_census_is_timed_where_it_is_made(tmp_path):
    """The load times its pieces (one per file + the merge) as
    data.census_build_seconds; unique_keys() stays data.census_seconds."""
    ds = _dataset(tmp_path, n_files=3)
    b0, c0 = (_hist_count("data.census_build_seconds"),
              _hist_count("data.census_seconds"))
    ds.load_into_memory()
    assert _hist_count("data.census_build_seconds") == b0 + 4
    assert _hist_count("data.census_seconds") == c0
    ds.unique_keys()
    assert _hist_count("data.census_build_seconds") == b0 + 4
    assert _hist_count("data.census_seconds") == c0 + 1
    ds.close()


class _Recorder:
    """Table and trainer in one, for AucRunner: records the census each
    evaluation began its pass with and the keys it then read."""

    def __init__(self):
        self.seen = []

    def begin_pass(self, keys):
        self._keys = keys

    def end_pass(self):
        pass

    def evaluate(self, dataset, table):
        self.seen.append((self._keys, dataset._block.keys.copy()))
        return {"auc": 0.5}


def test_auc_runner_swapped_block_yields_its_own_census(tmp_path):
    from paddlebox_tpu.train.auc_runner import AucRunner

    ds = _load(tmp_path)
    original = ds.unique_keys()
    rec = _Recorder()
    AucRunner(rec, rec, seed=3).run(
        ds, {"g0": ["slot0"], "g_all": ["slot0", "slot1", "slot2"]})
    assert len(rec.seen) == 3  # baseline + two groups
    for census, keys in rec.seen:
        _check(census, keys)
    assert rec.seen[0][0] is original
    # redrawn slots are other keys: a census carried over would be wrong
    assert rec.seen[2][0].tobytes() != original.tobytes()
    # the original block came back, and its census with it
    before = _served()
    assert ds.unique_keys() is original
    assert (_served() - before).tolist() == [1, 0]
    ds.close()
