"""Pass-scoped in-memory dataset with the BoxPS pass lifecycle.

Replaces ``PadBoxSlotDataset`` / ``BoxPSDataset`` (reference:
framework/data_set.h:348-474, python/paddle/fluid/dataset.py:1081-1302) and the
feed-pass half of ``BoxHelper`` (reference: fleet/box_wrapper.h:815-1084):

    ds.set_date(...)
    ds.preload_into_memory()        # parallel read + key census, overlaps
                                    # the prior pass's training
    ds.wait_preload_done()          # join
    table.begin_pass(ds.unique_keys())   # the census the load made
    for batch in ds.batches(): train_step(...)
    table.end_pass()
    ds.release_memory()

The key census is taken once, where the pass loads (reference:
MergeInsKeys -> PSAgentBase::AddKeys, data_set.cc:1786-1795): every reader
thread counts the file it parsed, the load merges the counts, and the
loaded ``RecordBlock`` carries the result, so ``unique_keys()`` at a pass
boundary returns an array instead of sorting every key occurrence again.

Multi-node global shuffle (reference: data_set.cc:1916-2090 via
boxps::PaddleShuffler) plugs in through the ``shuffler`` hook — see
paddlebox_tpu/data/shuffle.py.
"""

from __future__ import annotations

import concurrent.futures as futures
import dataclasses
import logging
import os
import threading
from typing import Iterator, Optional, Sequence

import numpy as np

from paddlebox_tpu.config import DataFeedConfig, flags
from paddlebox_tpu.data.feed import BatchBuilder, HostBatch
from paddlebox_tpu.data.record import RecordBlock
from paddlebox_tpu.data.slot_parser import SlotParser
from paddlebox_tpu.utils.monitor import stats
from paddlebox_tpu.utils.profiler import START, timed
from paddlebox_tpu.utils.retry import retry_call

logger = logging.getLogger(__name__)


def _census_build():
    """Times a piece of the census a load makes (a file's scan on its
    reader thread, the merge after the concat) as
    ``data.census_build_seconds`` / ``pbox.data.census_build``."""
    return timed("data.census_build_seconds", "data.census_build",
                 "key census made at load (per-file scans + their merge)")


@dataclasses.dataclass
class _DiskSpill:
    """Pass data spilled to local disk as binary archives (reference:
    PreLoadIntoDisk, data_set.cc:1577 + BinaryArchiveWriter)."""

    paths: list[str]
    unique_keys: np.ndarray
    n_ins: int


class PadBoxSlotDataset:
    def __init__(self, conf: DataFeedConfig, read_threads: Optional[int] = None):
        self.conf = conf
        self.parser = SlotParser(conf)
        self.builder = BatchBuilder(conf)
        self.read_threads = read_threads or flags.dataset_shuffle_thread_num
        self.filelist: list[str] = []
        self.date: Optional[str] = None
        self._block: Optional[RecordBlock] = None
        self._order: Optional[np.ndarray] = None
        self._spill: Optional[_DiskSpill] = None
        self._preload: Optional[futures.Future] = None
        self._pool = futures.ThreadPoolExecutor(max_workers=self.read_threads)
        self._preload_pool = futures.ThreadPoolExecutor(max_workers=1)
        self._rng = np.random.default_rng(0)
        self.shuffler = None  # optional multi-host shuffler (data/shuffle.py)

    # -- filelist / date ------------------------------------------------ #
    def set_filelist(self, files: Sequence[str]) -> None:
        self.filelist = list(files)

    def set_date(self, date: str) -> None:
        """Reference: BoxHelper::SetDate -> day-granular model/pass keying."""
        self.date = date

    # -- load ----------------------------------------------------------- #
    def _parse_with_retry(self, path: str) -> RecordBlock:
        """One file read through the unified retry helper: transient fs
        failures (OSError, a failed `hadoop fs -cat` pipe) retry; parse
        errors (ValueError) never do.  Timed as ``start.read_parse`` on
        the reader thread it runs on: the stage's seconds are the sum of
        the threads' wall, over ``dataset_load``'s where they overlap."""
        with START.stage("read_parse"):
            return retry_call(self.parser.parse_file, path, site="data.read")

    def _parse_with_census(self, path: str) -> tuple:
        """... and the file's keys counted on the same reader thread, which
        has just written every one of them (the sort releases the GIL)."""
        block = self._parse_with_retry(path)
        with _census_build():
            return block, np.unique(block.keys)

    def _check_quarantine(self, q0: int, p0: int) -> None:
        """Abort the load when the quarantined fraction of this load's
        lines exceeds the configured threshold — pervasive corruption is
        an upstream incident, not line noise to skip past."""
        q = self.parser.quarantined_lines - q0
        total = q + (self.parser.parsed_lines - p0)
        limit = self.conf.quarantine_abort_frac
        if q and total and q / total > limit:
            stats.add("data.quarantine_aborts")
            raise RuntimeError(
                f"pass aborted: {q}/{total} input lines ({q / total:.2%}) "
                f"quarantined, over quarantine_abort_frac={limit:.2%}"
            )

    @START.wrap("dataset_load")
    def _read_all(self) -> RecordBlock:
        """Files -> the usable block (``start.dataset_load``: the reader
        threads' ``read_parse``, then ``merge``)."""
        if not self.filelist:
            raise RuntimeError("set_filelist before loading")
        q0, p0 = self.parser.quarantined_lines, self.parser.parsed_lines
        if self.shuffler is None:
            blocks, censuses = zip(*self._pool.map(
                self._parse_with_census, self.filelist))
        else:
            # the exchange hands back other records than were parsed
            # here: the census is of the exchanged block, whole
            blocks, censuses = list(self._pool.map(
                self._parse_with_retry, self.filelist)), ()
        self._check_quarantine(q0, p0)
        with START.stage("merge"):
            block = RecordBlock.concat(blocks)
            if self.shuffler is not None:
                block = self.shuffler.exchange(block)
            with _census_build():
                block.set_census(censuses)
        return block

    def load_into_memory(self) -> None:
        self._block = self._read_all()
        self._order = np.arange(self._block.n_ins)
        self._spill = None

    def preload_into_memory(self) -> None:
        """Overlap next-pass reading with current-pass training (reference:
        BoxHelper::PreLoadIntoMemory, box_wrapper.h:921-941)."""
        if self._preload is not None:
            raise RuntimeError("preload already in flight")
        self._preload = self._preload_pool.submit(self._read_all)

    # -- disk spill ------------------------------------------------------- #
    @START.wrap("dataset_load")
    def _read_to_disk(self, spill_dir: str) -> _DiskSpill:
        """Parse -> archive each input file to local disk *incrementally*:
        at most ``read_threads`` parsed blocks are in flight at any moment,
        and only the growing key census stays resident — so a pass larger
        than host RAM actually loads (reference: PreLoadIntoDisk streams to
        BinaryArchive files while reading, data_set.cc:1577-1650;
        ``batches()`` then streams them back).

        With a multi-host ``shuffler`` attached, the exchange is a
        once-per-pass collective over the whole block, so that path falls
        back to whole-pass-in-memory parsing (its memory win applies only
        at train time).
        """
        from collections import deque

        from paddlebox_tpu.data.archive import write_archive

        os.makedirs(spill_dir, exist_ok=True)
        if not self.filelist:
            raise RuntimeError("set_filelist before loading")
        q0, p0 = self.parser.quarantined_lines, self.parser.parsed_lines
        if self.shuffler is not None:
            blocks = list(
                self._pool.map(self._parse_with_retry, self.filelist)
            )
            self._check_quarantine(q0, p0)
            block = RecordBlock.concat(blocks)
            block = self.shuffler.exchange(block)
            # chunk the exchanged pass so train-time _disk_batches
            # streams one chunk at a time instead of the whole pass
            n_chunks = max(len(self.filelist), 1)
            chunk = max((block.n_ins + n_chunks - 1) // n_chunks, 1)
            paths = []
            for i, lo in enumerate(range(0, block.n_ins, chunk)):
                out = os.path.join(spill_dir, f"spill-{i:05d}.bin")
                write_archive(
                    out,
                    [block.select(
                        np.arange(lo, min(lo + chunk, block.n_ins))
                    )],
                )
                paths.append(out)
            return _DiskSpill(paths, np.unique(block.keys), block.n_ins)

        high_water = max(int(self.read_threads), 1)
        inflight: deque = deque()
        paths: list[str] = []
        key_chunks: list[np.ndarray] = []
        n_ins = 0
        self.spill_peak_inflight = 0  # observability (tested)

        def drain_one() -> None:
            nonlocal n_ins
            block = inflight.popleft().result()
            i = len(paths)
            out = os.path.join(spill_dir, f"spill-{i:05d}.bin")
            write_archive(out, [block])
            paths.append(out)
            key_chunks.append(np.unique(block.keys))
            n_ins += block.n_ins
            # block goes out of scope here: peak residency is bounded by
            # the in-flight window, never the whole pass

        for f in self.filelist:
            inflight.append(self._pool.submit(self._parse_with_retry, f))
            self.spill_peak_inflight = max(
                self.spill_peak_inflight, len(inflight)
            )
            if len(inflight) >= high_water:
                drain_one()
        while inflight:
            drain_one()
        self._check_quarantine(q0, p0)
        uniq = (
            np.unique(np.concatenate(key_chunks))
            if key_chunks
            else np.empty(0, dtype=np.uint64)
        )
        return _DiskSpill(paths, uniq, n_ins)

    def preload_into_disk(self, spill_dir: str) -> None:
        """Background parse-to-disk (PreLoadIntoDisk analog): the pass data
        waits as binary archives; training streams them batch by batch
        without holding the whole pass in memory."""
        if self._preload is not None:
            raise RuntimeError("preload already in flight")
        self._preload = self._preload_pool.submit(self._read_to_disk, spill_dir)

    def wait_preload_done(self) -> None:
        if self._preload is None:
            raise RuntimeError("no preload in flight")
        result = self._preload.result()
        self._preload = None
        if isinstance(result, _DiskSpill):
            self._spill = result
            self._block = None
            self._order = None
        else:
            self._block = result
            self._order = np.arange(self._block.n_ins)
            self._spill = None

    def release_memory(self) -> None:
        self._block = None
        self._order = None
        if self._spill is not None:
            logged = False
            for p in self._spill.paths:
                try:
                    os.remove(p)
                except OSError as e:
                    # leaked spill files silently eat local disk across
                    # day-scale runs: count every failure, log the first
                    stats.add("dataset.spill_rm_failed")
                    if not logged:
                        logged = True
                        logger.warning(
                            "failed to remove spill file %s: %s "
                            "(further failures counted to "
                            "dataset.spill_rm_failed only)", p, e,
                        )
            self._spill = None

    def close(self) -> None:
        """Shut down reader threads; the dataset stays usable for in-memory
        iteration but can no longer load."""
        self._pool.shutdown(wait=True)
        self._preload_pool.shutdown(wait=True)

    def __enter__(self) -> "PadBoxSlotDataset":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- shuffle -------------------------------------------------------- #
    def local_shuffle(self, seed: Optional[int] = None) -> None:
        if self._block is None:
            raise RuntimeError("load before shuffle")
        rng = np.random.default_rng(seed) if seed is not None else self._rng
        if self.pv_mode:
            # PV mode shuffles whole page-views; ads inside a PV stay together
            self._pv_perm = rng.permutation(self._pv_perm.shape[0])
            return
        self._order = rng.permutation(self._block.n_ins)

    def global_shuffle(self, seed: Optional[int] = None) -> None:
        """Single-host degenerate case == local shuffle; with a shuffler
        attached, records were already exchanged at load time (reference:
        ShuffleData routes by search_id/ins_id/random, data_set.cc:1934-1942)."""
        self.local_shuffle(seed)

    def slots_shuffle(self, slot_names: Sequence[str], seed: int = 0) -> None:
        """Shuffle the given sparse slots' values across instances, keeping all
        other slots fixed (AUC-runner feature-importance mode; reference:
        SlotsShuffle box_wrapper.h:1077, data_set.h slots_shuffle)."""
        if self._block is None:
            raise RuntimeError("load before slots_shuffle")
        names = [s.name for s in self.conf.sparse_slots()]
        idxs = [names.index(n) for n in slot_names]
        self._block = _shuffle_slots(self._block, idxs, np.random.default_rng(seed))

    # -- PV merge --------------------------------------------------------- #
    def preprocess_instance(self) -> None:
        """Group instances into page-views by search_id (reference:
        BoxPSDataset.preprocess_instance -> PadBoxSlotDataset PV merge,
        data_feed.h:756-774; requires parse_logkey data).  After this,
        ``batches()`` emits PV-aligned batches carrying ``rank_offset``."""
        if self._block is None:
            raise RuntimeError("load before preprocess_instance")
        if not self.conf.enable_pv_merge:
            raise RuntimeError("enable_pv_merge is off in the config")
        if self._block.search_ids is None:
            raise RuntimeError("PV merge needs parse_logkey (search_ids)")
        sid = self._block.search_ids
        order = np.argsort(sid, kind="stable")
        bounds = np.nonzero(np.diff(sid[order]) != 0)[0] + 1
        starts = np.concatenate([[0], bounds, [order.shape[0]]]).astype(np.int64)
        self._pv_order = order
        self._pv_starts = starts  # PV p = order[starts[p]:starts[p+1]]
        self._pv_perm = np.arange(starts.shape[0] - 1)

    def postprocess_instance(self) -> None:
        """Back to flat instance mode (reference: BoxPSDataset.postprocess_instance)."""
        self._pv_order = None
        self._pv_starts = None
        self._pv_perm = None

    def pv_state(self) -> tuple:
        """Opaque snapshot of the PV grouping (including any shuffle order)
        for restore_pv_state — lets a caller drop to instance mode and come
        back WITHOUT re-deriving the grouping (which would reset the PV
        permutation a local/global shuffle established).  Used by the
        two-phase trainer's per-phase PV gating (train/two_phase.py)."""
        return (self._pv_order, self._pv_starts, self._pv_perm)

    def restore_pv_state(self, state: tuple) -> None:
        (self._pv_order, self._pv_starts, self._pv_perm) = state

    @property
    def pv_mode(self) -> bool:
        return getattr(self, "_pv_order", None) is not None

    def get_pv_data_size(self) -> int:
        if not self.pv_mode:
            return 0
        return self._pv_starts.shape[0] - 1

    def _pv_batches(self, drop_last: bool) -> Iterator[HostBatch]:
        """Pack whole PVs into fixed-capacity batches: up to pv_batch_size
        PVs and at most batch_size instances per batch (static shapes)."""
        B = self.conf.batch_size
        max_pvs = self.conf.pv_batch_size
        ids: list[np.ndarray] = []
        bounds = [0]

        def emit():
            flat = np.concatenate(ids)
            yield self.builder.build_pv(
                self._block, flat, np.asarray(bounds, dtype=np.int64)
            )

        count = 0
        for p in self._pv_perm:
            lo, hi = self._pv_starts[p], self._pv_starts[p + 1]
            pv = self._pv_order[lo:hi]
            if pv.shape[0] > B:
                raise ValueError(
                    f"PV of {pv.shape[0]} ads exceeds batch_size {B}"
                )
            if ids and (count + pv.shape[0] > B or len(ids) >= max_pvs):
                yield from emit()
                ids, bounds, count = [], [0], 0
            ids.append(pv)
            count += pv.shape[0]
            bounds.append(count)
        if ids and not (drop_last and count < B):
            yield from emit()

    # -- pass / batches -------------------------------------------------- #
    def get_memory_data_size(self) -> int:
        if self._spill is not None:
            return self._spill.n_ins
        return 0 if self._block is None else self._block.n_ins

    def unique_keys(self) -> np.ndarray:
        """The pass's key census: sorted, distinct, uint64.  Both load
        paths make it while they load (memory: ``_read_all`` leaves it on
        the block, timed as ``data.census_build_seconds``; spill: merged
        per archive at spill time), so this call returns an array.  Only a
        block that replaced the loaded one without a census of its own
        (``AucRunner``'s redrawn slots) is counted here, once.  Timed as
        ``data.census_seconds`` / ``pbox.data.census``: the examples' loop
        calls it inside every pass boundary."""
        with timed("data.census_seconds", "data.census",
                   "dataset key census (unique_keys) wall time"):
            if self._spill is not None:
                return self._spill.unique_keys
            if self._block is None:
                raise RuntimeError("load before key census")
            return self._block.unique_keys()

    def _disk_batches(self, drop_last: bool) -> Iterator[HostBatch]:
        """Stream batches from spill archives, carrying partial-batch
        remainders across archive boundaries."""
        from paddlebox_tpu.data.archive import read_archive

        B = self.conf.batch_size
        pending: Optional[RecordBlock] = None
        for path in self._spill.paths:
            for block in read_archive(path):
                pending = (
                    block if pending is None
                    else RecordBlock.concat([pending, block])
                )
                n_full = pending.n_ins // B
                for i in range(n_full):
                    yield self.builder.build(
                        pending, np.arange(i * B, (i + 1) * B)
                    )
                rem = pending.n_ins - n_full * B
                pending = (
                    pending.select(np.arange(n_full * B, pending.n_ins))
                    if rem
                    else None
                )
        if pending is not None and not drop_last:
            yield self.builder.build(pending, np.arange(pending.n_ins))

    def batches(self, drop_last: bool = False) -> Iterator[HostBatch]:
        if self._spill is not None:
            if self.pv_mode:
                raise RuntimeError(
                    "PV merge needs in-memory data (use preload_into_memory)"
                )
            yield from self._disk_batches(drop_last)
            return
        if self._block is None:
            raise RuntimeError("load before iterating")
        if self.pv_mode:
            yield from self._pv_batches(drop_last)
            return
        B = self.conf.batch_size
        n = self._block.n_ins
        for lo in range(0, n, B):
            ids = self._order[lo : lo + B]
            if drop_last and ids.shape[0] < B:
                return
            yield self.builder.build(self._block, ids)


def _shuffle_slots(block: RecordBlock, slot_idxs, rng) -> RecordBlock:
    """Permute the chosen slots' (values, length) pairs across instances,
    fully vectorized: one CSR gather builds the new key array — no per-
    instance Python loop."""
    s = block.n_sparse_slots
    n = block.n_ins
    lens = np.diff(block.key_offsets).reshape(n, s)
    # source start per (ins, slot) row: default = own row; shuffled slots
    # read the permuted instance's row instead
    src_starts = block.key_offsets[:-1].reshape(n, s).copy()
    new_lens = lens.copy()
    for si in slot_idxs:
        perm = rng.permutation(n)
        src_starts[:, si] = src_starts[perm, si]
        new_lens[:, si] = lens[perm, si]
    new_offsets = np.zeros(n * s + 1, dtype=np.int64)
    np.cumsum(new_lens.reshape(-1), out=new_offsets[1:])
    total = int(new_offsets[-1])
    # CSR gather: position t in row r reads block.keys[src_starts[r] + t]
    lens_flat = new_lens.reshape(-1)
    within = np.arange(total, dtype=np.int64) - np.repeat(
        new_offsets[:-1], lens_flat
    )
    keys = block.keys[np.repeat(src_starts.reshape(-1), lens_flat) + within]
    return RecordBlock(
        n_ins=block.n_ins,
        n_sparse_slots=s,
        keys=keys,
        key_offsets=new_offsets,
        dense=block.dense,
        labels=block.labels,
        ins_ids=block.ins_ids,
        search_ids=block.search_ids,
        ranks=block.ranks,
        cmatches=block.cmatches,
        task_labels=block.task_labels,
    )


class DatasetFactory:
    """Reference: framework/dataset_factory.cc:61-64 + python dataset.py:65."""

    _KINDS = {"PadBoxSlotDataset": PadBoxSlotDataset, "BoxPSDataset": PadBoxSlotDataset}

    def create_dataset(self, kind: str, conf: DataFeedConfig, **kw) -> PadBoxSlotDataset:
        if kind not in self._KINDS:
            raise ValueError(f"unknown dataset kind {kind!r}")
        return self._KINDS[kind](conf, **kw)
