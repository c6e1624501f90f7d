"""Model export: a self-contained serving artifact.

The reference ships a full C++ inference stack
(/root/reference/paddle/fluid/inference/, ~37k LoC: analysis passes, a
NativePredictor/AnalysisPredictor pair, C/Go/R client bindings) because its
serving path must re-execute the fluid graph outside the trainer.  On TPU
the trained step is already one compiled XLA program, so export collapses
to:

  * ``serving.stablehlo`` — the forward function, lowered and serialized
    with ``jax.export``.  Dense params are closed over as constants, so the
    blob is self-contained: serving needs NO Python model code, only JAX (or
    any StableHLO runtime) — the analog of the reference's frozen
    ``__model__`` + param files (save_inference_model,
    python/paddle/fluid/io.py).
  * ``sparse/keys.npy + values.npy`` — the embedding table snapshot (the
    xbox-base dump the reference's serving-side PS loads); show/clk
    counters are kept so feature-admission (create_threshold) behaves
    exactly as in training.
  * ``meta.json`` — shapes + CVM layout the predictor needs to resolve
    batches.

Layout-stable: everything is numpy + JSON + StableHLO; no pickled pytrees.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu.inference import quant

FORMAT_VERSION = 1


def resolve_embedding_dtype(embedding_dtype, row_width: int,
                             cvm_offset: int) -> str:
    """Normalize the artifact dtype choice: None reads the flag shim
    (PBOX_EMBEDDING_DTYPE), and a row with no embedx columns has nothing
    to quantize — the decision is config-global so every rank of a
    multi-host export writes the same shard layout."""
    from paddlebox_tpu.config import flags

    dtype = quant.validate_dtype(
        flags.embedding_dtype if embedding_dtype is None else embedding_dtype
    )
    if dtype != "fp32" and row_width - int(cvm_offset) - 1 <= 0:
        dtype = "fp32"
    return dtype


def export_serving_programs(
    model,
    params,
    out_dir: str,
    *,
    batch_size: int,
    key_capacity: int,
    dense_dim: int,
    row_width: int,
    rank_offset_cols: int = 0,
    batch_buckets=None,
    feed_conf=None,
    embedding_dtype=None,
    cvm_offset: int = 2,
    create_threshold: float = 0.0,
    pull_embedx_scale: float = 1.0,
) -> list:
    """Lower + serialize the serving program ladder for ``model`` with
    ``params`` frozen in, writing ``serving*.stablehlo`` files into
    ``out_dir``.  Returns the bucket metadata list
    (``[{"batch_size", "key_capacity", "file"}, ...]``).

    Split out of :func:`export_model` so the online delivery plane
    (serving_sync.Publisher) can re-freeze the DENSE side per pass —
    programs are small (dense params + lowered HLO) while the sparse
    snapshot is the multi-GB part, so a per-pass delta publish ships
    fresh programs + touched sparse rows and never the whole table.

    embedding_dtype ("fp32" | "int8" | "fp8"; None reads
    PBOX_EMBEDDING_DTYPE): with a quantized dtype the program takes
    ``(head f32, embedx_q, scales f32)`` instead of f32 rows and fuses
    the dequantization INTO the gathered-rows assembly on device — f32
    rows never materialize host-side, and create_threshold /
    pull_embedx_scale (host-resolve semantics of the f32 path) fold into
    the same fused compute so pull parity holds either way.
    """
    uses_rank = getattr(model, "uses_rank_offset", False)
    uses_seq = getattr(model, "uses_seq_pos", False)
    seq_len = int(getattr(model, "max_seq_len", 0)) if uses_seq else 0
    if uses_rank and rank_offset_cols <= 0:
        raise ValueError(
            "model consumes rank_offset: pass rank_offset_cols "
            "(DataFeedConfig.rank_offset_cols) so the serving program can "
            "take the PV-merged rank matrix as input"
        )
    edtype = resolve_embedding_dtype(embedding_dtype, row_width, cvm_offset)
    co = int(cvm_offset)
    n_embedx = row_width - co - 1
    if edtype == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        raise ValueError(
            "embedding_dtype='fp8' needs jax float8_e4m3fn support, which "
            "this jax build lacks — use 'int8' or 'fp32'"
        )
    os.makedirs(out_dir, exist_ok=True)
    frozen = jax.tree.map(jnp.asarray, params)
    buckets = [(int(batch_size), int(key_capacity))]
    for bb, bk in batch_buckets or ():
        if (int(bb), int(bk)) not in buckets:
            buckets.append((int(bb), int(bk)))
    if feed_conf is not None and not any(
        feed_conf.batch_size <= bb for bb, _ in buckets
    ):
        # fail BEFORE the expensive lowering loop: the server chunks
        # requests by feed_conf.batch_size, so some bucket must fit a full
        # chunk or the artifact is inherently un-servable
        raise ValueError(
            f"feed_conf.batch_size={feed_conf.batch_size} fits no "
            f"exported bucket (batch sizes {[b for b, _ in buckets]}): "
            "add a bucket via batch_buckets or lower the feed batch"
        )
    bucket_meta = []
    for B, K in buckets:
        # extras ride in a fixed order after the core inputs:
        # rank_offset (when used), then seq_pos (when used) — the
        # Predictor assembles args in the same order
        def model_kw(extras):
            kw = {}
            i = 0
            if uses_rank:
                kw["rank_offset"] = extras[i]
                i += 1
            if uses_seq:
                kw["seq_pos"] = extras[i]
            return kw

        def serve(rows, key_segments, dense, *extras, B=B):
            logits = model.apply(frozen, rows, key_segments, dense, B,
                                 **model_kw(extras))
            return jax.nn.sigmoid(logits)

        def serve_quant(head, embedx_q, scales, key_segments, dense,
                        *extras, B=B):
            # dequant FUSED into the program's row assembly: the host
            # gathers quantized bytes + per-row scales, the device does
            # `q * scale` — with pull_embedx_scale folded into the scale
            # and create_threshold's visibility mask applied to
            # embed_w + embedx exactly as the f32 host resolve does
            emb = embedx_q.astype(jnp.float32) \
                * (scales * pull_embedx_scale)[:, None]
            if create_threshold > 0.0:
                visible = (head[:, 0] >= create_threshold).astype(
                    jnp.float32)[:, None]
                emb = emb * visible
                head = jnp.concatenate(
                    [head[:, :co], head[:, co:] * visible], axis=1)
            rows = jnp.concatenate([head, emb], axis=1)
            logits = model.apply(frozen, rows, key_segments, dense, B,
                                 **model_kw(extras))
            return jax.nn.sigmoid(logits)

        # lower for both serving platforms: a TPU-trained artifact must run
        # on a CPU-only serving host too
        if edtype == "fp32":
            fn = serve
            in_shapes = [
                jax.ShapeDtypeStruct((K, row_width), jnp.float32),
                jax.ShapeDtypeStruct((K,), jnp.int32),
                jax.ShapeDtypeStruct((B, dense_dim), jnp.float32),
            ]
        else:
            fn = serve_quant
            qdt = jnp.int8 if edtype == "int8" else jnp.float8_e4m3fn
            in_shapes = [
                jax.ShapeDtypeStruct((K, co + 1), jnp.float32),
                jax.ShapeDtypeStruct((K, n_embedx), qdt),
                jax.ShapeDtypeStruct((K,), jnp.float32),
                jax.ShapeDtypeStruct((K,), jnp.int32),
                jax.ShapeDtypeStruct((B, dense_dim), jnp.float32),
            ]
        if uses_rank:
            in_shapes.append(
                jax.ShapeDtypeStruct((B, rank_offset_cols), jnp.int32)
            )
        if uses_seq:
            in_shapes.append(
                jax.ShapeDtypeStruct((B, seq_len), jnp.int32)
            )
        # pbox-lint: ignore[jit-retrace-hazard] one-time artifact build:
        # each shape bucket AOT-exports its own frozen program here;
        # serving dispatches the deserialized programs, never this jit
        exp = jax.export.export(jax.jit(fn), platforms=("cpu", "tpu"))(
            *in_shapes
        )
        # the primary bucket keeps the legacy filename so pre-bucket
        # artifacts and loaders stay interchangeable
        fname = (
            "serving.stablehlo"
            if (B, K) == buckets[0]
            else f"serving-b{B}-k{K}.stablehlo"
        )
        with open(os.path.join(out_dir, fname), "wb") as f:
            f.write(exp.serialize())
        bucket_meta.append(
            {"batch_size": B, "key_capacity": K, "file": fname}
        )
    return bucket_meta


def export_model(
    model,
    params,
    table,
    out_dir: str,
    *,
    batch_size: int,
    key_capacity: int,
    dense_dim: int,
    quantize: bool = False,
    embedding_dtype=None,
    rank_offset_cols: int = 0,
    batch_buckets=None,
    feed_conf=None,
) -> None:
    """Write a serving artifact for ``model`` + ``table`` to ``out_dir``.

    params: the trained dense pytree (e.g. ``trainer.params``; for a
    MultiChipTrainer pass ``trainer.dense_state()[0]``).
    table: SparseTable/ShardedSparseTable OUTSIDE a pass (end_pass first) —
    its host store is snapshotted.  Multi-host callers export per-process
    shard files (rank in the filename) and merge at load.
    quantize: LEGACY int8 snapshot with one global scale per shard,
    dequantized host-side at load (~4x smaller artifact — the reference's
    quantized xbox model publish, box_wrapper.cu
    FeaturePullValueGpuQuant; counters + embed_w stay f32 exactly as
    there).  Superseded by embedding_dtype, which wins when both are set.
    embedding_dtype ("fp32" | "int8" | "fp8"; None reads
    PBOX_EMBEDDING_DTYPE): per-ROW-scale quantized artifact whose rows
    stay quantized end to end — on disk, in predictor memory, across the
    host gather — with dequant fused into the serving program (see
    export_serving_programs) and delta publishes shipping quantized rows
    + scales (the multi-TB path shrinks ~4x).
    rank_offset_cols: for rank_offset-consuming models (RankCtrDnn), the
    feed's rank-offset matrix column count (DataFeedConfig.rank_offset_cols)
    — exported as a fourth program input.
    batch_buckets: extra (batch_size, key_capacity) shape buckets to lower
    alongside the primary one.  XLA programs have static shapes, so
    "arbitrary batch size" serving (the reference's AnalysisPredictor
    resizes feed tensors freely, analysis_predictor.cc) becomes the
    standard TPU recipe instead: export a ladder of shape buckets and let
    the Predictor pad each request up to the smallest bucket that fits.
    feed_conf: the training DataFeedConfig — serialized into the artifact
    (feed.json) so a serving host can parse request lines from the
    artifact ALONE (ScoringServer.register without a Python-side config),
    the way the reference's __model__ dir carries its feed schema
    (save_inference_model, python/paddle/fluid/io.py).
    """
    uses_rank = getattr(model, "uses_rank_offset", False)
    uses_seq = getattr(model, "uses_seq_pos", False)
    seq_len = int(getattr(model, "max_seq_len", 0)) if uses_seq else 0
    if uses_rank and rank_offset_cols <= 0:
        raise ValueError(
            "model consumes rank_offset: pass rank_offset_cols "
            "(DataFeedConfig.rank_offset_cols) so the serving program can "
            "take the PV-merged rank matrix as input"
        )
    conf = table.conf
    os.makedirs(out_dir, exist_ok=True)
    os.makedirs(os.path.join(out_dir, "sparse"), exist_ok=True)

    # sparse snapshot (sorted keys + full value rows, g2sum dropped: the
    # optimizer state has no serving meaning)
    state = table.state_dict()
    w = conf.row_width
    pid = jax.process_index()
    np.save(os.path.join(out_dir, "sparse", f"keys-{pid:05d}.npy"),
            np.asarray(state["keys"], dtype=np.uint64))
    vals = np.asarray(state["values"], dtype=np.float32)[:, :w]
    co = conf.cvm_offset
    # the artifact format must be GLOBAL (every rank writes the same shard
    # layout or Predictor.load breaks): decide off config, never off this
    # rank's row count — rows with no embedx columns have nothing to quantize
    edtype = resolve_embedding_dtype(embedding_dtype, w, co)
    quantize = quantize and edtype == "fp32" and (w - co - 1) > 0
    if edtype != "fp32":
        # per-row-scale quantized snapshot: rows stay quantized all the
        # way to the serving program (dequant-on-gather); empty shards
        # write empty arrays so the loader sees a uniform format
        head, q, scales = quant.quantize_rows(vals, co, edtype)
        np.save(os.path.join(out_dir, "sparse", f"head-{pid:05d}.npy"), head)
        np.save(os.path.join(out_dir, "sparse", f"embedx_q-{pid:05d}.npy"),
                quant.store_q(q))
        np.save(os.path.join(out_dir, "sparse", f"scales-{pid:05d}.npy"),
                scales)
    elif quantize:
        # embedx columns (everything past embed_w) -> int8 with one scale
        # PER SHARD FILE (each process knows only its own rows); counters +
        # embed_w stay f32 (reference quant layout).  Empty shards write
        # empty arrays so the loader sees a uniform format.
        embedx = vals[:, co + 1 :]
        amax = float(np.abs(embedx).max()) if embedx.size else 0.0
        scale = (amax / 127.0) if amax > 0 else 1.0
        q = np.clip(np.round(embedx / scale), -127, 127).astype(np.int8)
        np.save(os.path.join(out_dir, "sparse", f"embedx_q-{pid:05d}.npy"), q)
        np.save(os.path.join(out_dir, "sparse", f"head-{pid:05d}.npy"),
                np.ascontiguousarray(vals[:, : co + 1]))
        np.save(os.path.join(out_dir, "sparse", f"scale-{pid:05d}.npy"),
                np.float32(scale))
    else:
        np.save(os.path.join(out_dir, "sparse", f"values-{pid:05d}.npy"), vals)

    if pid != 0:
        return  # replicated artifacts are rank 0's to write (multi-host:
        # every rank contributed its sparse shard above; the program and
        # meta are identical everywhere — same convention as checkpoint.py)

    bucket_meta = export_serving_programs(
        model, params, out_dir,
        batch_size=batch_size, key_capacity=key_capacity,
        dense_dim=dense_dim, row_width=w,
        rank_offset_cols=rank_offset_cols, batch_buckets=batch_buckets,
        feed_conf=feed_conf,
        embedding_dtype=edtype, cvm_offset=co,
        create_threshold=conf.create_threshold,
        pull_embedx_scale=conf.pull_embedx_scale,
    )

    B = bucket_meta[0]["batch_size"]
    K = bucket_meta[0]["key_capacity"]
    n_tasks = int(getattr(model, "n_tasks", 1))
    meta = {
        "format_version": FORMAT_VERSION,
        "model_class": type(model).__name__,
        "batch_size": B,
        "key_capacity": K,
        "buckets": bucket_meta,
        "dense_dim": dense_dim,
        "n_sparse_slots": int(getattr(model, "n_sparse_slots", 0)),
        "n_tasks": n_tasks,
        "row_width": w,
        "cvm_offset": conf.cvm_offset,
        "create_threshold": conf.create_threshold,
        "pull_embedx_scale": conf.pull_embedx_scale,
        "quantized": bool(quantize),
        "embedding_dtype": edtype,
        "rank_offset_cols": rank_offset_cols if uses_rank else 0,
        "seq_len": seq_len,
    }
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)

    if feed_conf is not None:
        with open(os.path.join(out_dir, "feed.json"), "w") as f:
            json.dump(feed_conf.to_dict(), f, indent=1)
