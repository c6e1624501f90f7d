"""Columnar instance storage.

The reference stores parsed instances as per-record `SlotRecord` structs
(CSR `SlotValues` per record, reference: framework/data_feed.h:778-870) drawn
from a recycling object pool (SlotObjPool, data_feed.h:897-1085) because
per-record malloc churn was their bottleneck.  The TPU-native design goes one
step further: a whole file/chunk of instances is parsed straight into one
columnar CSR block (arrow-style), so batch assembly is pure array slicing and
the padded device batch is one contiguous copy.  No per-record objects exist
at all — the object pool becomes unnecessary.

A block also carries its pass's key census (the sorted distinct keys that
``begin_pass`` promotes): the dataset takes it once, where it loads the
block (reference: MergeInsKeys -> PSAgentBase::AddKeys, data_set.cc:1786-
1795, which counts keys while it drains the reader channels), and every
``unique_keys()`` after that returns the same array.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np

from paddlebox_tpu.telemetry import metrics as _tm

_SERVED = _tm.counter(
    "data.census_served",
    "unique_keys() calls by how they were answered: from=load, the block "
    "held its census already; from=scan, np.unique over every occurrence "
    "at the call")


@dataclasses.dataclass
class RecordBlock:
    """A block of N instances over S sparse slots and D dense floats.

    CSR layout: ``keys[key_offsets[i*S+s] : key_offsets[i*S+s+1]]`` are the
    uint64 feasigns of instance ``i``, sparse slot ``s``.

    A block is immutable: every transformation (``concat``, ``select``,
    a slot shuffle or replacement) returns a new block and none writes
    into the arrays of an old one.  That is what lets a block keep its key
    census (``unique_keys``): made once, at load or at the first request,
    and true for as long as the block lives.  A new block starts without
    one unless the code that made it hands one on (``set_census``).
    """

    n_ins: int
    n_sparse_slots: int
    keys: np.ndarray  # uint64 [total_keys]
    key_offsets: np.ndarray  # int64 [n_ins * n_sparse_slots + 1]
    dense: np.ndarray  # float32 [n_ins, dense_width] (may be width 0)
    labels: np.ndarray  # float32 [n_ins]
    # optional per-instance metadata (PV merge / shuffle routing / dump)
    ins_ids: Optional[list[str]] = None
    search_ids: Optional[np.ndarray] = None  # uint64 [n_ins]
    ranks: Optional[np.ndarray] = None  # int32 [n_ins]
    cmatches: Optional[np.ndarray] = None  # int32 [n_ins]
    task_labels: Optional[np.ndarray] = None  # float32 [n_ins, n_extra_tasks]
    # sorted distinct uint64 keys, read-only; None until made (never an
    # __init__ argument: a block built from arrays has yet to count them)
    _census: Optional[np.ndarray] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def __post_init__(self):
        assert self.key_offsets.shape[0] == self.n_ins * self.n_sparse_slots + 1
        assert self.dense.shape[0] == self.n_ins
        assert self.labels.shape[0] == self.n_ins

    @property
    def n_keys(self) -> int:
        return int(self.keys.shape[0])

    def slot_slice(self, ins: int, slot: int) -> np.ndarray:
        s = self.n_sparse_slots
        lo = self.key_offsets[ins * s + slot]
        hi = self.key_offsets[ins * s + slot + 1]
        return self.keys[lo:hi]

    @staticmethod
    def concat(blocks: Sequence["RecordBlock"]) -> "RecordBlock":
        """Merge blocks into one (reference: PadBoxSlotDataset::MergeInsKeys,
        data_set.cc:1786 drains reader channels into input_records_)."""
        if not blocks:
            raise ValueError("nothing to concat")
        nonempty = [b for b in blocks if b.n_ins > 0]
        if not nonempty:
            return blocks[0]  # empty dataset (all parts empty) is legal
        blocks = nonempty
        if len(blocks) == 1:
            return blocks[0]
        s = blocks[0].n_sparse_slots
        n_ins = sum(b.n_ins for b in blocks)
        keys = np.concatenate([b.keys for b in blocks])
        # rebase offsets
        offs = [blocks[0].key_offsets]
        base = blocks[0].key_offsets[-1]
        for b in blocks[1:]:
            offs.append(b.key_offsets[1:] + base)
            base = base + b.key_offsets[-1]
        key_offsets = np.concatenate(offs)
        dense = np.concatenate([b.dense for b in blocks])
        labels = np.concatenate([b.labels for b in blocks])

        def _cat_opt(field):
            vals = [getattr(b, field) for b in blocks]
            if any(v is None for v in vals):
                return None
            if field == "ins_ids":
                out = []
                for v in vals:
                    out.extend(v)
                return out
            return np.concatenate(vals)

        return RecordBlock(
            n_ins=n_ins,
            n_sparse_slots=s,
            keys=keys,
            key_offsets=key_offsets,
            dense=dense,
            labels=labels,
            ins_ids=_cat_opt("ins_ids"),
            search_ids=_cat_opt("search_ids"),
            ranks=_cat_opt("ranks"),
            cmatches=_cat_opt("cmatches"),
            task_labels=_cat_opt("task_labels"),
        )

    def select(self, order: np.ndarray) -> "RecordBlock":
        """Gather instances by index (shuffle / shard / PV regroup)."""
        s = self.n_sparse_slots
        order = np.asarray(order, dtype=np.int64)
        # per-(ins,slot) lengths of the selected instances, in new order
        lens = np.diff(self.key_offsets)
        sel_rows = (order[:, None] * s + np.arange(s)[None, :]).reshape(-1)
        new_lens = lens[sel_rows]
        new_offsets = np.zeros(order.shape[0] * s + 1, dtype=np.int64)
        np.cumsum(new_lens, out=new_offsets[1:])
        # gather keys: build source index ranges
        starts = self.key_offsets[sel_rows]
        total = int(new_offsets[-1])
        src_idx = np.empty(total, dtype=np.int64)
        # vectorized ragged range: for each row r, src_idx[new_offsets[r]:new_offsets[r+1]] = starts[r] + arange(len)
        pos = np.arange(total, dtype=np.int64) - np.repeat(new_offsets[:-1], new_lens)
        src_idx = np.repeat(starts, new_lens) + pos
        return RecordBlock(
            n_ins=int(order.shape[0]),
            n_sparse_slots=s,
            keys=self.keys[src_idx],
            key_offsets=new_offsets,
            dense=self.dense[order],
            labels=self.labels[order],
            ins_ids=[self.ins_ids[i] for i in order] if self.ins_ids is not None else None,
            search_ids=self.search_ids[order] if self.search_ids is not None else None,
            ranks=self.ranks[order] if self.ranks is not None else None,
            cmatches=self.cmatches[order] if self.cmatches is not None else None,
            task_labels=self.task_labels[order] if self.task_labels is not None else None,
        )

    def set_census(self, parts: Sequence[np.ndarray] = ()) -> None:
        """Make the census now, ahead of its first request: the union of
        ``parts`` (sorted distinct uint64 key arrays that together hold
        exactly the keys of ``keys``: one per block this one was
        concatenated from), else a scan of ``keys``."""
        census = np.unique(np.concatenate(parts) if parts else self.keys)
        census.flags.writeable = False  # shared from here on
        self._census = census

    def unique_keys(self) -> np.ndarray:
        """Key census for the pass: sorted, distinct, uint64, read-only —
        ``np.unique(self.keys)`` to the byte (reference:
        PSAgentBase::AddKeys via MergeInsKeys, data_set.cc:1795; consumed
        by FeedPass).  Made on the first request where the block came
        without one, and kept."""
        if self._census is None:
            _SERVED.inc(**{"from": "scan"})
            self.set_census()
        else:
            _SERVED.inc(**{"from": "load"})
        return self._census
