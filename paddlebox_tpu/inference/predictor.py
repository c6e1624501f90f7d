"""Serving-side predictor: loads an export_model artifact and scores batches.

The AnalysisPredictor analog (reference:
/root/reference/paddle/fluid/inference/api/analysis_predictor.cc — load
frozen program + params, feed named tensors, fetch outputs), reduced to the
TPU-native essentials: deserialize the StableHLO program(s) (params inside),
resolve sparse keys against the table snapshot on the host, run.

Shape flexibility: XLA programs are static-shaped, so the reference's
freely-resizable feed tensors become a ladder of exported shape buckets
(export_model ``batch_buckets``).  ``predict`` pads any batch whose REAL
instance/key counts fit some bucket up to that bucket's shapes — padding
rows are zero and padding segment ids are out of range (dropped by the
pooling segment_sum), so bucket choice never changes the scores.

The embedding resolve duplicates training's pull semantics exactly
(sparse/table.py pull_rows): missing/padding keys read zero rows,
create_threshold hides embeddings of under-shown features, and
pull_embedx_scale descales a quantized table.  For fp32 artifacts all of
that happens here on the host gather; for per-row-scale quantized
artifacts (``embedding_dtype`` int8/fp8) the host gathers quantized
bytes + scales and the exported program applies dequant + threshold +
descale on device — fp32 rows never materialize host-side, so predictor
memory, gather bandwidth and delta-publish bytes all shrink ~4x
(DLRM inference is embedding-bandwidth-bound, PAPERS.md).
"""

from __future__ import annotations

import glob
import json
import os
from typing import Iterator, Optional

import numpy as np

from paddlebox_tpu.data.feed import HostBatch
from paddlebox_tpu.inference import quant


class EmbeddingDtypeMismatch(ValueError):
    """A delta's embedding dtype does not match the live artifact's — a
    merge would corrupt the table (fp32 rows spliced into int8 storage or
    vice versa).  Structured so the Syncer's fallback ladder catches it
    and full-reloads instead of applying."""


class Predictor:
    def __init__(self, meta: dict, keys: np.ndarray,
                 values: Optional[np.ndarray], artifact_dir: str,
                 bucket_files: list, *, head: Optional[np.ndarray] = None,
                 embedx_q: Optional[np.ndarray] = None,
                 scales: Optional[np.ndarray] = None) -> None:
        """bucket_files: [(batch_size, key_capacity, filename), ...].
        Programs deserialize lazily on first use (each embeds the full
        frozen dense params — eager loading would scale serving-host
        startup with ladder size, not traffic).

        Exactly one storage form is populated: ``values`` ([n, W] f32,
        fp32 artifacts) or the quantized triple ``head`` ([n, co+1] f32)
        + ``embedx_q`` ([n, E] int8/fp8) + ``scales`` ([n] f32)."""
        self.meta = meta
        self._keys = keys  # sorted uint64
        self._values = values  # [n, W] f32 (fp32 artifacts only)
        self._head = head
        self._q = embedx_q
        self._scales = scales
        self._dir = artifact_dir
        self._buckets = bucket_files
        self._programs: dict = {}  # filename -> deserialized exported

    @property
    def n_features(self) -> int:
        """Features in the loaded sparse snapshot."""
        return int(self._keys.shape[0])

    @property
    def bucket_shapes(self) -> list:
        """[(batch_size, key_capacity), ...] of the exported ladder."""
        return [(b, k) for b, k, _ in self._buckets]

    @property
    def embedding_dtype(self) -> str:
        """The dtype serving the embedding payload ("fp32" for legacy
        global-scale artifacts too: those dequantize at load, so their
        in-memory and on-device form IS f32)."""
        return self.meta.get("embedding_dtype", "fp32")

    @property
    def _quantized(self) -> bool:
        return self._values is None

    @property
    def artifact_bytes(self) -> int:
        """In-memory sparse payload bytes — the footprint/bandwidth the
        quantized format shrinks; surfaces in /models and the fleet view
        so the win is observable end to end."""
        n = int(self._keys.nbytes)
        if self._quantized:
            n += int(self._head.nbytes + self._q.nbytes
                     + self._scales.nbytes)
        else:
            n += int(self._values.nbytes)
        return n

    def _program(self, fname: str):
        import jax

        from paddlebox_tpu.telemetry.compiles import install_compile_listener

        install_compile_listener()
        if fname not in self._programs:
            with open(os.path.join(self._dir, fname), "rb") as f:
                self._programs[fname] = jax.export.deserialize(f.read())
        return self._programs[fname]

    @classmethod
    def load(cls, artifact_dir: str) -> "Predictor":
        with open(os.path.join(artifact_dir, "meta.json")) as f:
            meta = json.load(f)
        sp = os.path.join(artifact_dir, "sparse")
        key_files = sorted(glob.glob(os.path.join(sp, "keys-*.npy")))
        keys = np.concatenate([np.load(p) for p in key_files])
        edtype = meta.get("embedding_dtype", "fp32")
        order = np.argsort(keys)  # per-process shards -> one sorted table
        keys = keys[order]
        head = embedx_q = scales = values = None
        if edtype != "fp32":
            # per-row-scale quantized artifact: rows stay quantized in
            # memory; the serving program dequantizes on gather
            heads, qs, scs = [], [], []
            for kf in key_files:
                pid = kf[-9:-4]
                heads.append(np.load(os.path.join(sp, f"head-{pid}.npy")))
                qs.append(quant.load_q(
                    np.load(os.path.join(sp, f"embedx_q-{pid}.npy")),
                    edtype,
                ))
                scs.append(np.load(os.path.join(sp, f"scales-{pid}.npy")))
            head = np.concatenate(heads)[order]
            embedx_q = np.concatenate(qs)[order]
            scales = np.concatenate(scs)[order]
        elif meta.get("quantized"):
            # legacy per-shard global scale: [head f32 | embedx int8 *
            # scale] dequantized to f32 rows at load time
            shards = []
            for kf in key_files:
                pid = kf[-9:-4]
                h = np.load(os.path.join(sp, f"head-{pid}.npy"))
                q = np.load(os.path.join(sp, f"embedx_q-{pid}.npy"))
                scale = float(np.load(os.path.join(sp, f"scale-{pid}.npy")))
                shards.append(
                    np.concatenate(
                        [h, q.astype(np.float32) * scale], axis=1
                    )
                )
            values = (np.concatenate(shards) if shards else np.empty(
                (0, meta["row_width"]), np.float32
            ))[order]
        else:
            val_files = sorted(glob.glob(os.path.join(sp, "values-*.npy")))
            values = np.concatenate([np.load(p) for p in val_files])[order]
        # pre-bucket artifacts carry no "buckets" entry: synthesize one
        bucket_meta = meta.get("buckets") or [{
            "batch_size": meta["batch_size"],
            "key_capacity": meta["key_capacity"],
            "file": "serving.stablehlo",
        }]
        bucket_files = [
            (int(bm["batch_size"]), int(bm["key_capacity"]), bm["file"])
            for bm in bucket_meta
        ]
        return cls(meta, keys, values, artifact_dir, bucket_files,
                   head=head, embedx_q=embedx_q, scales=scales)

    # -- delta hot-apply (build-aside) -------------------------------------- #
    def with_delta(self, keys: np.ndarray, values: np.ndarray = None,
                   program_dir: str = None,
                   bucket_meta: list = None, *,
                   head: np.ndarray = None, embedx_q: np.ndarray = None,
                   scales: np.ndarray = None,
                   embedding_dtype: str = "fp32") -> "Predictor":
        """A NEW Predictor with delta rows merged in; ``self`` is never
        mutated, so in-flight predict() calls keep a consistent snapshot
        and the caller swaps the returned object in atomically (the
        serving_sync syncer's hot-apply path).

        keys: uint64 delta keys (need not be sorted; deduped by last
        occurrence order after sort).  For an fp32 artifact pass
        ``values`` ([n, row_width] f32); for a quantized one pass the
        quantized triple (``head`` + ``embedx_q`` + ``scales``) with the
        matching ``embedding_dtype``.  Existing keys are REPLACED (delta
        rows carry the full current row, not an increment, matching
        SparseTable.pop_delta), genuinely new keys are inserted
        preserving the sorted-keys invariant the searchsorted resolve
        depends on.  A dtype that does not match the live artifact's is
        a :class:`EmbeddingDtypeMismatch` — a structured refusal, never
        a corrupt merge; the Syncer answers it with a full reload.

        program_dir/bucket_meta: when the delta shipped re-frozen serving
        programs (publisher publish_delta with model+params), point the
        new predictor at them; otherwise the existing programs (and their
        deserialization cache) are shared — sparse-only freshness.
        """
        quant.validate_dtype(embedding_dtype)
        if embedding_dtype != self.embedding_dtype:
            raise EmbeddingDtypeMismatch(
                f"delta rows are {embedding_dtype} but the live artifact "
                f"serves {self.embedding_dtype}: chains cannot mix "
                "embedding dtypes — republish a base"
            )
        dk = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        if self._quantized:
            dvs = self._check_quant_delta(dk, head, embedx_q, scales)
        else:
            dvs = (self._check_fp32_delta(dk, values),)
        order = np.argsort(dk, kind="stable")
        dk = dk[order]
        dvs = [d[order] for d in dvs]
        if dk.shape[0] and np.any(dk[1:] == dk[:-1]):
            # keep the LAST row per duplicate key (newest write wins)
            last = np.ones(dk.shape[0], bool)
            last[:-1] = dk[1:] != dk[:-1]
            dk = dk[last]
            dvs = [d[last] for d in dvs]
        n = self._keys.shape[0]
        if n and dk.shape[0]:
            pos = np.searchsorted(self._keys, dk)
            pos_c = np.minimum(pos, n - 1)
            found = self._keys[pos_c] == dk
        else:
            pos = np.zeros(dk.shape[0], np.int64)
            found = np.zeros(dk.shape[0], bool)
        olds = ((self._head, self._q, self._scales) if self._quantized
                else (self._values,))
        news = []
        for old, dv in zip(olds, dvs):
            new = old.copy()
            if found.any():
                new[pos[found]] = dv[found]
            if (~found).any():
                # insertion points keep the sort order
                new = np.insert(new, pos[~found], dv[~found], axis=0)
            news.append(new)
        if (~found).any():
            new_keys = np.insert(self._keys, pos[~found], dk[~found])
        else:
            new_keys = self._keys
        kw = (dict(head=news[0], embedx_q=news[1], scales=news[2])
              if self._quantized else {})
        new_values = None if self._quantized else news[0]
        if program_dir is not None:
            bm = bucket_meta or self.meta.get("buckets") or []
            buckets = [
                (int(b["batch_size"]), int(b["key_capacity"]), b["file"])
                for b in bm
            ] or list(self._buckets)
            out = Predictor(self.meta, new_keys, new_values, program_dir,
                            buckets, **kw)
        else:
            out = Predictor(self.meta, new_keys, new_values, self._dir,
                            list(self._buckets), **kw)
            out._programs = self._programs  # share the deserialized cache
        return out

    def _check_fp32_delta(self, dk: np.ndarray,
                          values: np.ndarray) -> np.ndarray:
        if values is None:
            raise ValueError("fp32 artifact: with_delta needs `values`")
        dv = np.asarray(values, dtype=np.float32)
        w = int(self.meta["row_width"])
        if dv.ndim != 2 or dv.shape[1] < w:
            raise ValueError(
                f"delta values are {dv.shape}, artifact row_width is {w}"
            )
        dv = dv[:, :w]
        if dk.shape[0] != dv.shape[0]:
            raise ValueError(
                f"delta keys/values disagree: {dk.shape[0]} vs {dv.shape[0]}"
            )
        return dv

    def _check_quant_delta(self, dk: np.ndarray, head, embedx_q, scales):
        if head is None or embedx_q is None or scales is None:
            raise ValueError(
                "quantized artifact: with_delta needs head + embedx_q + "
                "scales"
            )
        co = int(self.meta["cvm_offset"])
        e = int(self.meta["row_width"]) - co - 1
        dh = np.asarray(head, dtype=np.float32)
        dq = np.asarray(embedx_q)
        ds = np.asarray(scales, dtype=np.float32)
        if dh.shape != (dk.shape[0], co + 1) \
                or dq.shape != (dk.shape[0], e) \
                or ds.shape != (dk.shape[0],):
            raise ValueError(
                f"quantized delta shapes disagree with the artifact: head "
                f"{dh.shape} q {dq.shape} scales {ds.shape} for "
                f"{dk.shape[0]} keys (co={co}, embedx={e})"
            )
        if dq.dtype != self._q.dtype:
            raise EmbeddingDtypeMismatch(
                f"delta embedx dtype {dq.dtype} != artifact {self._q.dtype}"
            )
        return dh, dq, ds

    # -- feature resolve (host) -------------------------------------------- #
    def _find(self, batch_keys: np.ndarray, n_keys: int):
        bk = batch_keys[:n_keys]
        pos = np.searchsorted(self._keys, bk)
        pos_c = np.minimum(pos, self._keys.shape[0] - 1)
        found = self._keys[pos_c] == bk
        return pos_c, found

    def _resolve_rows(self, batch_keys: np.ndarray, n_keys: int,
                      key_capacity: int) -> np.ndarray:
        m = self.meta
        rows = np.zeros((key_capacity, m["row_width"]), dtype=np.float32)
        if n_keys and self._keys.shape[0]:
            pos_c, found = self._find(batch_keys, n_keys)
            got = self._values[pos_c] * found[:, None]
            co = m["cvm_offset"]
            if m["pull_embedx_scale"] != 1.0:
                got[:, co + 1 :] *= m["pull_embedx_scale"]
            if m["create_threshold"] > 0.0:
                visible = got[:, 0] >= m["create_threshold"]
                got[:, co:] *= visible[:, None]
            rows[:n_keys] = got
        return rows

    def _resolve_rows_quant(self, batch_keys: np.ndarray, n_keys: int,
                            key_capacity: int):
        """Quantized gather: (head, embedx_q, scales) padded to the
        bucket's key capacity.  No dequant, no threshold, no descale —
        all three are fused into the serving program; missing keys read
        zero head + zero scale, so their dequantized row is zero exactly
        like the fp32 path's."""
        m = self.meta
        co = int(m["cvm_offset"])
        e = int(m["row_width"]) - co - 1
        head = np.zeros((key_capacity, co + 1), np.float32)
        q = np.zeros((key_capacity, e), self._q.dtype)
        sc = np.zeros((key_capacity,), np.float32)
        if n_keys and self._keys.shape[0]:
            pos_c, found = self._find(batch_keys, n_keys)
            head[:n_keys] = self._head[pos_c] * found[:, None]
            got_q = self._q[pos_c].copy()
            got_q[~found] = 0
            q[:n_keys] = got_q
            sc[:n_keys] = self._scales[pos_c] * found
        return head, q, sc

    def _pick_bucket(self, b: int, nk: int):
        """Cheapest fitting bucket by padded work (B * K), not first-fit —
        a non-monotone ladder like [(64, 65536), (128, 1024)] must send a
        tiny request to the small program, not the huge-capacity one."""
        fits = [(B * K, B, K, f) for B, K, f in self._buckets
                if b <= B and nk <= K]
        if fits:
            _, B, K, fname = min(fits)
            return B, K, self._program(fname)
        raise ValueError(
            f"no exported shape bucket fits a batch with {b} instances / "
            f"{nk} keys: artifact buckets (batch_size, key_capacity) = "
            f"{self.bucket_shapes} — re-export with batch_buckets covering "
            "this shape"
        )

    # -- scoring ------------------------------------------------------------ #
    def predict(self, batch: HostBatch) -> np.ndarray:
        """Probabilities for the batch's REAL instances: [b] (primary task)
        or [b, n_tasks].  The batch may come from ANY feed shape whose real
        instance/key counts fit an exported bucket."""
        m = self.meta
        # feed/artifact schema must agree BEFORE any resolve: a batch built
        # under a different slot config produces segment ids (ins * S + slot)
        # under the wrong S and would score garbage silently (ADVICE r4)
        S = m["n_sparse_slots"]
        if batch.n_sparse_slots != S:
            raise ValueError(
                f"batch was built with {batch.n_sparse_slots} sparse slots "
                f"but the artifact serves {S}: feed config and exported "
                "model disagree — re-export or fix DataFeedConfig.slots"
            )
        if batch.dense.shape[1] != m["dense_dim"]:
            raise ValueError(
                f"batch dense width {batch.dense.shape[1]} != artifact "
                f"dense_dim {m['dense_dim']}: feed config and exported "
                "model disagree"
            )
        b = int(batch.ins_mask.sum())
        if b and not batch.ins_mask[:b].all():
            raise ValueError(
                "batch real instances are not front-packed; cannot re-bucket"
            )
        nk = int(batch.n_keys)
        B, K, exported = self._pick_bucket(b, nk)

        # segments: the real keys' ids are ins * S + slot with ins < b <= B,
        # valid under bucket B too; padding ids land out of range (B * S)
        # and are dropped by the pooling segment_sum
        segs = np.full(K, B * S, np.int32)
        segs[:nk] = np.asarray(batch.key_segments[:nk], np.int32)
        dense = np.zeros((B, m["dense_dim"]), np.float32)
        dense[:b] = np.asarray(batch.dense[:b], np.float32)
        if self._quantized:
            head, q, sc = self._resolve_rows_quant(batch.keys, nk, K)
            args = [head, q, sc, segs, dense]
        else:
            rows = self._resolve_rows(batch.keys, nk, K)
            args = [rows, segs, dense]
        if m.get("rank_offset_cols", 0):
            if batch.rank_offset is None:
                raise ValueError(
                    "artifact serves a rank_offset model: feed PV-merged "
                    "batches (enable_pv_merge + preprocess_instance)"
                )
            ro = np.zeros((B, m["rank_offset_cols"]), np.int32)
            ro_src = np.asarray(batch.rank_offset, np.int32)
            if ro_src.shape[1] != m["rank_offset_cols"]:
                raise ValueError(
                    f"batch rank_offset has {ro_src.shape[1]} columns but "
                    f"the artifact serves {m['rank_offset_cols']}: set "
                    "DataFeedConfig.rank_offset_cols to the exported width"
                )
            ro[:b] = ro_src[:b]
            args.append(ro)
        if m.get("seq_len", 0):
            if batch.seq_pos is None:
                raise ValueError(
                    "artifact serves a sequence model: set "
                    "DataFeedConfig.sequence_slot so batches carry seq_pos"
                )
            T = m["seq_len"]
            src = np.asarray(batch.seq_pos, np.int32)
            if src.shape[1] > T:
                # a WIDER feed would silently drop behavior history at
                # serving time, skewing scores vs training (which raises on
                # the same mismatch — LongSeqCtrDnn.apply); match it (ADVICE)
                raise ValueError(
                    f"batch max_seq_len {src.shape[1]} > artifact seq_len "
                    f"{T}: set DataFeedConfig.max_seq_len to the exported "
                    "length"
                )
            # re-bucket: real positions (< this batch's real key count) are
            # valid under the bucket's key buffer too; everything else
            # becomes the bucket's pad marker K.  A NARROWER feed pads its
            # tail with the marker — the exported tower already treats
            # marker positions as absent history, so a client configured
            # with a shorter max_seq_len scores identically to one padded
            # to the artifact length
            Ts = src.shape[1]
            sp = np.full((B, T), K, np.int32)
            sp[:b, :Ts] = np.where(src[:b] < nk, src[:b], K)
            args.append(sp)
        # each exported bucket program compiles exactly once (warmup);
        # the stage scope attributes that compile — and any unexpected
        # steady-state retrace — to serve.predict in jit.compiles
        from paddlebox_tpu.telemetry.compiles import stage_scope

        with stage_scope("serve.predict"):
            preds = np.asarray(exported.call(*args))
        return preds[:b]

    def predict_dataset(self, dataset) -> Iterator[np.ndarray]:
        """Score every batch of a loaded dataset (drop_last=False)."""
        for batch in dataset.batches(drop_last=False):
            yield self.predict(batch)
