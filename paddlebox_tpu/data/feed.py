"""Batch assembly: columnar records -> fixed-shape padded host batches.

This is the TPU-native replacement for ``MiniBatchGpuPack`` +
``BuildSlotBatchGPU`` (reference: framework/data_feed.h:1380-1539,
data_feed.cc:2585, data_feed.cu:97-208): instead of scattering into per-slot
ragged LoDTensors on device, the host packs one padded CSR batch with
*static* shapes (XLA requirement) —

    keys          uint64 [K]      all feasigns of the batch (padded with 0)
    key_segments  int32  [K]      segment id = ins_in_batch * S + slot,
                                  padding rows get segment B*S (overflow bin)
    dense         f32    [B, D]
    labels        f32    [B]
    ins_mask      f32    [B]      0 for padding instances of a partial batch

Pooling on device is then a single ``segment_sum`` over ``key_segments``
(see ops/seqpool_cvm.py), which XLA fuses with the CVM transform.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from paddlebox_tpu._native import pack_batch_native
from paddlebox_tpu.config import DataFeedConfig
from paddlebox_tpu.data.record import RecordBlock
from paddlebox_tpu.telemetry import metrics as _tm

_BUILT = _tm.counter(
    "data.batches_built",
    "BatchBuilder.build calls by what packed the keys: by=native, one pass "
    "of the data layer's library; by=numpy, the fallback where it did not "
    "build")

_beat = None  # resolved once: liveness stage beat, or a no-op


def _liveness_beat(stage: str) -> None:
    """Report feed-assembly progress to the active liveness watchdog.
    Lazy + guarded: the data plane must import (and run) on builds where
    the parallel package cannot."""
    global _beat
    if _beat is None:
        try:
            from paddlebox_tpu.parallel.watchdog import beat as b
        # pbox-lint: ignore[swallowed-exception] gated-import fallback: a
        # build without the parallel package is the handled case
        except Exception:
            import sys

            mod = sys.modules.get("paddlebox_tpu.parallel.watchdog")
            b = mod.beat if mod is not None else (lambda stage: None)
        _beat = b
    _beat(stage)


@dataclasses.dataclass
class HostBatch:
    keys: np.ndarray  # uint64 [K]
    key_segments: np.ndarray  # int32 [K]; padding -> batch_size * n_slots
    n_keys: int  # real key count
    dense: np.ndarray  # float32 [B, D]
    labels: np.ndarray  # float32 [B]
    ins_mask: np.ndarray  # float32 [B]
    batch_size: int
    n_sparse_slots: int
    rank_offset: Optional[np.ndarray] = None  # int32 [B, C] (PV merge mode)
    # ordered per-instance positions (into the key buffer) of the
    # configured sequence_slot's keys; padding = key capacity K (in the
    # step's feed the plan's length L: train/trainer.py _host_batch_dict)
    seq_pos: Optional[np.ndarray] = None  # int32 [B, max_seq_len]
    # multi-task labels [B, T]: col 0 = primary label, cols 1.. = the
    # configured task_label_slots (present only when those are configured)
    task_labels: Optional[np.ndarray] = None
    # per-instance logkey metadata for mask/cmatch-rank metric variants
    cmatches: Optional[np.ndarray] = None  # int32 [B]
    ranks: Optional[np.ndarray] = None  # int32 [B]
    # instance ids of the real rows (len == n_real_ins), for field dumping
    ins_ids: Optional[list] = None

    @property
    def n_real_ins(self) -> int:
        return int(self.ins_mask.sum())


def empty_like(batch: HostBatch) -> HostBatch:
    """An all-padding batch with the same static shapes (ins_mask zero, every
    key slot pointing at the overflow segment) — used to pad ragged device
    groups in multi-chip training."""
    B, S = batch.batch_size, batch.n_sparse_slots
    return HostBatch(
        keys=np.zeros_like(batch.keys),
        key_segments=np.full_like(batch.key_segments, B * S),
        n_keys=0,
        dense=np.zeros_like(batch.dense),
        labels=np.zeros_like(batch.labels),
        ins_mask=np.zeros_like(batch.ins_mask),
        batch_size=B,
        n_sparse_slots=S,
        rank_offset=None if batch.rank_offset is None
        else np.zeros_like(batch.rank_offset),
        seq_pos=None if batch.seq_pos is None
        else np.full_like(batch.seq_pos, batch.keys.shape[0]),
        task_labels=None if batch.task_labels is None
        else np.zeros_like(batch.task_labels),
        cmatches=None if batch.cmatches is None else np.zeros_like(batch.cmatches),
        ranks=None if batch.ranks is None else np.zeros_like(batch.ranks),
        ins_ids=None if batch.ins_ids is None else [],
    )


def key_classes(
    keys: np.ndarray,  # uint64 [K] padded key buffer of one batch
    n_keys: int,  # its real keys
    vocab_keys: np.ndarray,  # sorted distinct uint64: the model's classes
    inverse: Optional[np.ndarray] = None,  # BatchPlan.inverse
) -> np.ndarray:
    """int32 [K]: each key occurrence's class -- its key's rank in the
    model's fixed vocabulary (``model.vocab_keys``: a tokenizer's vocabulary
    does not grow) -- padding -1.  The device never sees 64-bit keys: a
    loss over the vocabulary takes its targets from here, as it takes the
    order from ``seq_pos``.  A key outside the vocabulary is an error,
    never a silent class.

    With the plan's ``inverse`` (occurrence -> distinct slot) the search
    runs once per distinct key and is expanded by it; without (a sharded
    plan has no such map) once per occurrence."""
    out = np.full(keys.shape[0], -1, dtype=np.int32)
    if not n_keys:
        return out
    real = keys[:n_keys]
    if inverse is not None:
        inv = inverse[:n_keys]
        distinct = np.empty(int(inv.max()) + 1, dtype=np.uint64)
        distinct[inv] = real  # every occurrence of a slot writes its key
    else:
        inv, distinct = slice(None), real
    rank = np.searchsorted(vocab_keys, distinct)
    hit = vocab_keys[np.minimum(rank, vocab_keys.shape[0] - 1)] == distinct
    if not hit.all():
        raise ValueError(
            f"{int((~hit).sum())} keys of the batch are outside the model's "
            f"vocabulary of {vocab_keys.shape[0]} (first: "
            f"{int(distinct[~hit][0])})")
    out[:n_keys] = rank[inv]
    return out


def build_rank_offset(
    block: RecordBlock,
    ids: np.ndarray,
    pv_bounds: np.ndarray,  # int [n_pvs+1]: PV boundaries within ids
    batch_size: int,
    max_rank: int,
    cmatch_filter=None,
) -> np.ndarray:
    """The PV rank matrix [B, 2*max_rank+1] with batch-local peer indices
    (reference: CopyRankOffsetKernel, data_feed.cu:208-258; -1 fill).

    Row layout per ad instance: col 0 = own rank (1-based; -1 unranked);
    for peer-rank slot m: col 2m+1 = peer's rank, col 2m+2 = peer's row in
    this batch.  A PV's ads see each other (self included, as in the
    reference).  Instances fail ranking when their cmatch is filtered out or
    rank is 0 / > max_rank.
    """
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
    eff_rank = np.where(ok, ranks, -1).astype(np.int32)
    n = ids.shape[0]
    mat[:n, 0] = eff_rank
    # vectorized (ranked j, ranked k) same-PV pair expansion — no per-PV
    # Python loop.  Pairs are tiny (<= max_rank^2 per
    # PV) but PVs number in the millions at pass scale.
    n_pvs = pv_bounds.shape[0] - 1
    pv_of = np.repeat(np.arange(n_pvs), np.diff(pv_bounds))  # [n]
    ranked_pos = np.nonzero(eff_rank > 0)[0]
    if ranked_pos.shape[0] == 0:
        return mat
    pv_r = pv_of[ranked_pos]  # sorted (positions are PV-contiguous)
    counts = np.bincount(pv_r, minlength=n_pvs)  # ranked members per PV
    group_start = np.zeros(n_pvs, dtype=np.int64)
    np.cumsum(counts[:-1], out=group_start[1:])
    sq = counts.astype(np.int64) ** 2
    total = int(sq.sum())
    if total == 0:
        return mat
    pair_start = np.zeros(n_pvs, dtype=np.int64)
    np.cumsum(sq[:-1], out=pair_start[1:])
    # j: each ranked member of a c-sized group appears c times consecutively
    j = ranked_pos[np.repeat(np.arange(ranked_pos.shape[0]),
                             np.repeat(counts, counts))]
    # k: group members tiled c times, reconstructed from pair position
    pair_pos = np.arange(total, dtype=np.int64) - np.repeat(pair_start, sq)
    k_within = pair_pos % np.repeat(counts, sq).astype(np.int64)
    k = ranked_pos[np.repeat(group_start, sq) + k_within]
    m = eff_rank[k] - 1
    mat[j, 2 * m + 1] = eff_rank[k]
    mat[j, 2 * m + 2] = k
    return mat


def _pack_keys_numpy(block: RecordBlock, ids: np.ndarray, S: int, K: int,
                     pad_seg: int):
    """``pack_batch_native``'s numpy form, byte for byte: the fallback
    where the library did not build, and the oracle its parity test holds
    it to (tests/test_native_pack.py)."""
    # lengths from the batch's own rows: O(b*S) offsets read, never a
    # difference over the whole block (BatchBuilder's invariant)
    sel_rows = (ids[:, None] * S + np.arange(S)[None, :]).reshape(-1)
    starts = block.key_offsets[sel_rows]
    lens = block.key_offsets[sel_rows + 1] - starts
    total = int(lens.sum())
    dropped = 0
    if total > K:  # clip overflowing tail rows
        cum = np.cumsum(lens)
        lens = np.minimum(lens, np.maximum(K - (cum - lens), 0))
        dropped = total - int(lens.sum())
        total -= dropped
    new_off = np.cumsum(lens) - lens
    pos = np.arange(total, dtype=np.int64) - np.repeat(new_off, lens)
    keys = np.zeros(K, dtype=np.uint64)
    keys[:total] = block.keys[np.repeat(starts, lens) + pos]
    segs = np.full(K, pad_seg, dtype=np.int32)
    # row r = ins_in_batch * S + slot is its own segment id
    segs[:total] = np.repeat(np.arange(sel_rows.shape[0], dtype=np.int32),
                             lens)
    return keys, segs, lens, total, dropped


class BatchBuilder:
    """Packs instance index ranges of a RecordBlock into HostBatches.

    Invariant (pinned by tests/test_feed_batch_cost.py): a batch's host
    cost is O(its own keys and instances), independent of the block's
    size — ``build`` reads the block only at the selected rows.  The keys
    and their segment ids are packed in one native pass where the data
    layer's library is loaded (``_native/slot_parser.cpp pbx_pack_batch``),
    by ``_pack_keys_numpy`` where it is not; ``data.batches_built`` says
    which.  Every batch gets fresh arrays: the prefetch queue and the step
    hold earlier ones."""

    def __init__(self, conf: DataFeedConfig):
        self.conf = conf
        self.key_capacity = conf.batch_key_capacity or (
            conf.batch_size * conf.max_feasigns_per_ins
        )
        self.dropped_keys = 0  # overflow counter (observability)
        self.seq_slot_idx: Optional[int] = None
        if conf.sequence_slot:
            names = [s.name for s in conf.sparse_slots()]
            if conf.sequence_slot not in names:
                raise ValueError(
                    f"sequence_slot {conf.sequence_slot!r} is not a sparse "
                    f"slot (have {names})"
                )
            self.seq_slot_idx = names.index(conf.sequence_slot)

    def build_pv(
        self, block: RecordBlock, ids: np.ndarray, pv_bounds: np.ndarray
    ) -> HostBatch:
        """A PV-merged batch: same packing plus the rank_offset matrix."""
        batch = self.build(block, ids)
        batch.rank_offset = build_rank_offset(
            block, np.asarray(ids, dtype=np.int64), pv_bounds,
            self.conf.batch_size, self.conf.max_rank,
            self.conf.rank_cmatch_filter,
        )
        return batch

    def build(self, block: RecordBlock, ids: np.ndarray) -> HostBatch:
        _liveness_beat("feed")
        conf = self.conf
        B = conf.batch_size
        S = block.n_sparse_slots
        K = self.key_capacity
        ids = np.asarray(ids, dtype=np.int64)
        b = int(ids.shape[0])
        assert b <= B

        if b and not 0 <= int(ids.min()) <= int(ids.max()) < block.n_ins:
            raise IndexError(
                f"batch ids outside the block's {block.n_ins} instances")

        packed = pack_batch_native(
            block.keys, block.key_offsets, ids, S, K, B * S)
        by = "native"
        if packed is None:
            packed = _pack_keys_numpy(block, ids, S, K, B * S)
            by = "numpy"
        _BUILT.inc(by=by)
        keys, segs, lens, total, dropped = packed
        # clipped tail rows are counted; raise the capacity if it matters
        self.dropped_keys += dropped

        seq_pos = None
        if self.seq_slot_idx is not None:
            # ordered positions of the sequence slot's keys in the buffer:
            # instance i's slot run is [new_off[r], new_off[r]+lens[r]) with
            # r = i*S + slot (file order == behavior order); pad with K
            T = self.conf.max_seq_len
            seq_pos = np.full((B, T), K, dtype=np.int32)
            new_off = np.cumsum(lens) - lens
            rr = np.arange(b, dtype=np.int64) * S + self.seq_slot_idx
            col = np.arange(T, dtype=np.int64)[None, :]
            seq_pos[:b] = np.where(
                col < np.minimum(lens[rr], T)[:, None],
                new_off[rr][:, None] + col,
                K,
            ).astype(np.int32)

        dense = np.zeros((B, block.dense.shape[1]), dtype=np.float32)
        dense[:b] = block.dense[ids]
        labels = np.zeros(B, dtype=np.float32)
        labels[:b] = block.labels[ids]
        mask = np.zeros(B, dtype=np.float32)
        mask[:b] = 1.0

        task_labels = None
        if block.task_labels is not None and block.task_labels.shape[1]:
            task_labels = np.zeros(
                (B, 1 + block.task_labels.shape[1]), dtype=np.float32
            )
            task_labels[:b, 0] = block.labels[ids]
            task_labels[:b, 1:] = block.task_labels[ids]
        cmatches = ranks_arr = None
        if block.cmatches is not None:
            cmatches = np.full(B, -1, dtype=np.int32)
            cmatches[:b] = block.cmatches[ids]
        if block.ranks is not None:
            ranks_arr = np.full(B, -1, dtype=np.int32)
            ranks_arr[:b] = block.ranks[ids]

        return HostBatch(
            keys=keys,
            key_segments=segs,
            n_keys=total,
            seq_pos=seq_pos,
            dense=dense,
            labels=labels,
            ins_mask=mask,
            batch_size=B,
            n_sparse_slots=S,
            task_labels=task_labels,
            cmatches=cmatches,
            ranks=ranks_arr,
            ins_ids=(
                [block.ins_ids[i] for i in ids]
                if block.ins_ids is not None
                else None
            ),
        )
