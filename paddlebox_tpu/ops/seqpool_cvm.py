"""Fused sequence sum-pool + CVM transform, with the full variant family.

TPU-native redesign of ``fused_seqpool_cvm`` and its variants (reference:
paddle/fluid/operators/fused/fused_seqpool_cvm_op.cu:34-369,
fused_seqpool_cvm_with_conv_op.cu:1-449,
fused_seqpool_cvm_with_diff_thres_op.cu:1-558,
fused_seqpool_cvm_with_pcoc_op.cu:1-517; Python wrappers
python/paddle/fluid/contrib/layers/nn.py:1580-1860): the reference ships one
CUDA kernel per (variant × filter × quant) combination, each walking N
per-slot ragged LoDTensors.  Here the host feed already packed the whole
batch as one padded CSR (HostBatch.key_segments, segment id = ins * S + slot,
padding -> B*S overflow bin), so every variant decomposes into three fusable
stages on static shapes:

  1. per-occurrence prep (``_prepool``): show/clk-score filter (scalar or
     per-slot thresholds), embed-norm filter, quantization — the reference's
     KernelQuantFilter/KernelEmbedQuantFilter loops, expressed as row masks.
  2. ONE ``jax.ops.segment_sum`` over all slots (MXU/VPU friendly).
  3. a row-layout CVM transform (``default`` / ``conv`` / ``pcoc``).

Row layouts of a pulled value (reference CVM layouts, box_wrapper.h:523-534
cvm_offset 2/3/4+p dispatch, box_wrapper.cu PullCopy*):

  default: [show, click,           embed...]           cvm_offset = 2
  conv:    [show, click, conv,     embed...]           cvm_offset = 3
  pcoc:    [show, click, d0, d1, q_0..q_{p-1}, embed...]  cvm_offset = 4+p

Gradient semantics match the reference kernels: counters are
stop-gradient'd, filtered occurrences contribute no gradient, and
quantization is straight-through (the reference grad kernels scatter the
pooled cotangent back to every surviving occurrence unchanged).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def seqpool(rows: jax.Array, key_segments: jax.Array, batch_size: int,
            n_slots: int) -> jax.Array:
    """Sum-pool pulled rows into per-(instance, slot) vectors.

    rows: [K, W] pulled value rows, one per feasign occurrence.
    key_segments: int32 [K]; segment id = ins * n_slots + slot; padding keys
        carry segment id batch_size * n_slots and fall into an overflow bin
        that is dropped, so padding contributes nothing (and receives zero
        gradient, which keeps the dead table row clean).
    Returns [batch_size, n_slots, W].
    """
    pooled = jax.ops.segment_sum(
        rows, key_segments, num_segments=batch_size * n_slots + 1
    )
    return pooled[: batch_size * n_slots].reshape(batch_size, n_slots, -1)


def _quant_round(v: jax.Array, quant_ratio: int) -> jax.Array:
    """Reference quantization (fused_seqpool_cvm_op.cu:110):
    ``int(v * ratio + 0.5) / ratio`` — C truncation toward zero.  Straight-
    through gradient (the reference grad kernel ignores the rounding)."""
    q = jnp.trunc(v * quant_ratio + 0.5) / quant_ratio
    return v + jax.lax.stop_gradient(q - v)


def _prepool(
    rows: jax.Array,
    key_segments: jax.Array,
    n_slots: int,
    cvm_offset: int,
    need_filter: bool,
    show_coeff: float,
    clk_coeff: float,
    threshold: float,
    threshold_vec,
    embed_threshold: float,
    quant_ratio: int,
) -> jax.Array:
    """Per-occurrence filter + quant stage (all reference pre-pool loops).

    An occurrence survives when
        (show - click) * show_coeff + click * clk_coeff >= thr[slot]
    (fused_seqpool_cvm_op.cu:104; thr is the scalar ``threshold`` or the
    per-slot ``threshold_vec`` — the _with_diff_thres variant,
    fused_seqpool_cvm_with_diff_thres_op.cu:100-127) and, when
    ``embed_threshold`` > 0, additionally
        |embed_w| + ||embedx||_2 >= embed_threshold
    (KernelEmbedQuantFilter, fused_seqpool_cvm_op.cu:137-150).  Filtered
    occurrences contribute nothing at all — counters included.
    """
    if need_filter:
        show, click = rows[:, 0], rows[:, 1]
        if threshold_vec is not None:
            thr_vec = jnp.asarray(threshold_vec, dtype=rows.dtype)
            thr = jnp.take(thr_vec, key_segments % n_slots)
        else:
            thr = threshold
        keep = (show - click) * show_coeff + click * clk_coeff >= thr
        if embed_threshold > 0.0:
            embed_w = rows[:, cvm_offset]
            embedx = rows[:, cvm_offset + 1:]
            score = jnp.sqrt((embedx * embedx).sum(axis=1)) + jnp.abs(embed_w)
            keep &= score >= embed_threshold
        rows = rows * jax.lax.stop_gradient(
            keep.astype(rows.dtype)[:, None]
        )
    if quant_ratio > 0:
        rows = jnp.concatenate(
            [rows[:, :cvm_offset], _quant_round(rows[:, cvm_offset:], quant_ratio)],
            axis=1,
        )
    return rows


def pooled_width(
    emb_width: int,
    cvm_offset: int = 2,
    use_cvm: bool = True,
    layout: str = "default",
    show_filter: bool = False,
) -> int:
    """Per-slot output width of the fused seqpool-CVM family — THE width
    contract model input_dim accounting must use.

    default layout CVM emits 2 counter columns ([log_show, ctr]); the conv
    layout emits 3 ([log_show, log_clk, cvr], minus one with show_filter);
    without use_cvm all counter columns are dropped.
    """
    embed = emb_width - cvm_offset
    if not use_cvm:
        return embed
    if layout == "conv":
        return 3 + embed - (1 if show_filter else 0)
    return 2 + embed


def _cvm_transform(pooled: jax.Array, cvm_offset: int) -> jax.Array:
    """Default log-CVM on the pooled show/click columns; counters carry no
    gradient (the reference's cvm_grad writes the CVM values, not d/dshow of
    the log, into the show/click grad slots — i.e. counters are not
    learned)."""
    show = jax.lax.stop_gradient(pooled[..., 0:1])
    click = jax.lax.stop_gradient(pooled[..., 1:2])
    log_show = jnp.log(show + 1.0)
    ctr = jnp.log(click + 1.0) - log_show
    return jnp.concatenate([log_show, ctr, pooled[..., cvm_offset:]], axis=-1)


def _scoped(fn):
    """Run ``fn`` under ``jax.named_scope("seqpool_cvm")``: the name every
    operation of the pool + CVM stage (and of its transpose) carries in a
    device trace.  Metadata only."""

    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope("seqpool_cvm"):
            return fn(*args, **kwargs)

    return scoped


@_scoped
def fused_seqpool_cvm(
    rows: jax.Array,
    key_segments: jax.Array,
    batch_size: int,
    n_slots: int,
    use_cvm: bool = True,
    cvm_offset: int = 2,
    clk_coeff: float = 1.0,
    need_filter: bool = False,
    show_coeff: float = 0.2,
    threshold: float = 0.0,
    threshold_vec=None,
    embed_threshold: float = 0.0,
    quant_ratio: int = 0,
) -> jax.Array:
    """Pool + CVM for all slots at once; returns [B, n_slots * out_width],
    out_width = 2 + W - cvm_offset with use_cvm (the CVM transform emits
    exactly [log_show, ctr] whatever cvm_offset is) else W - cvm_offset
    (counters dropped) — see ``pooled_width()`` for the one authoritative
    formula.

    ``threshold_vec`` (length n_slots) switches the show/clk filter to
    per-slot thresholds — this IS the _with_diff_thres variant
    (fused_seqpool_cvm_with_diff_thres_op.cu ``xbox_diff_thres_filter``).
    ``quant_ratio`` > 0 quantizes embed columns per occurrence before
    pooling (the Quant kernels).
    """
    rows = _prepool(
        rows, key_segments, n_slots, cvm_offset, need_filter, show_coeff,
        clk_coeff, threshold, threshold_vec, embed_threshold, quant_ratio,
    )
    pooled = seqpool(rows, key_segments, batch_size, n_slots)
    if use_cvm:
        out = _cvm_transform(pooled, cvm_offset)
    else:
        out = pooled[..., cvm_offset:]
    return out.reshape(batch_size, -1)


def fused_seqpool_cvm_with_diff_thres(
    rows: jax.Array,
    key_segments: jax.Array,
    batch_size: int,
    n_slots: int,
    threshold_vec,
    use_cvm: bool = True,
    cvm_offset: int = 2,
    show_coeff: float = 0.2,
    clk_coeff: float = 1.0,
    quant_ratio: int = 0,
) -> jax.Array:
    """Per-slot-threshold variant (reference:
    fused_seqpool_cvm_with_diff_thres_op.cu) — sugar over the fused op."""
    return fused_seqpool_cvm(
        rows, key_segments, batch_size, n_slots, use_cvm=use_cvm,
        cvm_offset=cvm_offset, need_filter=True, show_coeff=show_coeff,
        clk_coeff=clk_coeff, threshold_vec=threshold_vec,
        quant_ratio=quant_ratio,
    )


@_scoped
def fused_seqpool_cvm_with_conv(
    rows: jax.Array,
    key_segments: jax.Array,
    batch_size: int,
    n_slots: int,
    use_cvm: bool = True,
    cvm_offset: int = 3,
    show_filter: bool = False,
    need_filter: bool = False,
    show_coeff: float = 0.2,
    clk_coeff: float = 1.0,
    threshold: float = 0.0,
    quant_ratio: int = 0,
) -> jax.Array:
    """Conv-feature variant: rows [show, click, conv, embed...] (reference:
    fused_seqpool_cvm_with_conv_op.cu FusedCVMWithConvKernelNormal:63-83).

    CVM columns:  [log(show+1), log(click+1), log(conv+1) - log(click+1)]
    (conversion rate conditioned on click — NOT the default variant's ctr).
    ``show_filter`` drops the show column from the output (the
    KernelWithOutShow path, cu:86-112), giving width W - 1.
    """
    rows = _prepool(
        rows, key_segments, n_slots, cvm_offset, need_filter, show_coeff,
        clk_coeff, threshold, None, 0.0, quant_ratio,
    )
    pooled = seqpool(rows, key_segments, batch_size, n_slots)
    if use_cvm:
        show = jax.lax.stop_gradient(pooled[..., 0:1])
        click = jax.lax.stop_gradient(pooled[..., 1:2])
        conv = jax.lax.stop_gradient(pooled[..., 2:3])
        log_click = jnp.log(click + 1.0)
        cols = [
            jnp.log(show + 1.0),
            log_click,
            jnp.log(conv + 1.0) - log_click,
            pooled[..., cvm_offset:],
        ]
        if show_filter:
            cols = cols[1:]
        out = jnp.concatenate(cols, axis=-1)
    else:
        out = pooled[..., cvm_offset:]
    return out.reshape(batch_size, -1)


@_scoped
def fused_seqpool_cvm_with_pcoc(
    rows: jax.Array,
    key_segments: jax.Array,
    batch_size: int,
    n_slots: int,
    pclk_num: int,
    use_cvm: bool = True,
    need_filter: bool = False,
    show_coeff: float = 0.2,
    clk_coeff: float = 1.0,
    threshold: float = 0.0,
    quant_ratio: int = 0,
) -> jax.Array:
    """PCOC (predicted-click-over-click q-value) variant: rows
    ``[show, click, d0, d1, q_0..q_{p-1}, embed...]`` with max_cvm_offset =
    4 + pclk_num (reference: fused_seqpool_cvm_with_pcoc_op.cu
    FusedCVMWithPCOCKernelWithCVM:120-155).

    Output CVM block (width 2 + 2 * pclk_num):
        [ log(show+1),
          log(click+1) - log(show+1),
          { log(q_i+1) - log(d0+1) } for each i,   # q vs denominator 0
          { log(q_i+1) - log(d1+1) } for each i ]  # q vs denominator 1
    followed by the pooled embeds (the kernel's embed_index_diff shift).
    """
    max_cvm_offset = 4 + pclk_num
    rows = _prepool(
        rows, key_segments, n_slots, max_cvm_offset, need_filter, show_coeff,
        clk_coeff, threshold, None, 0.0, quant_ratio,
    )
    pooled = seqpool(rows, key_segments, batch_size, n_slots)
    if not use_cvm:
        out = pooled[..., max_cvm_offset:]
        return out.reshape(batch_size, -1)
    cnt = jax.lax.stop_gradient(pooled[..., :max_cvm_offset])
    show, click = cnt[..., 0:1], cnt[..., 1:2]
    d0, d1 = cnt[..., 2:3], cnt[..., 3:4]
    q = cnt[..., 4 : 4 + pclk_num]
    log_show = jnp.log(show + 1.0)
    log_q = jnp.log(q + 1.0)
    out = jnp.concatenate(
        [
            log_show,
            jnp.log(click + 1.0) - log_show,
            log_q - jnp.log(d0 + 1.0),
            log_q - jnp.log(d1 + 1.0),
            pooled[..., max_cvm_offset:],
        ],
        axis=-1,
    )
    return out.reshape(batch_size, -1)


@_scoped
def fused_seqpool_cvm_extended(
    rows: jax.Array,
    key_segments: jax.Array,
    batch_size: int,
    n_slots: int,
    expand_dim: int,
    use_cvm: bool = True,
    cvm_offset: int = 2,
) -> tuple[jax.Array, jax.Array]:
    """Pool rows carrying base + expand embeddings and return the two feature
    blocks separately (reference: pull_box_extended_sparse's dual Out/OutExtend
    outputs, operators/pull_box_extended_sparse_op.{cc,cu,h}, pooled by the
    fused_seqpool_cvm variants).

    rows: [K, cvm_offset + emb + expand]; returns
      base   [B, n_slots * (cvm_offset + emb)]  (CVM-transformed if use_cvm)
      expand [B, n_slots * expand]              (plain pooled values)
    """
    if expand_dim <= 0:
        raise ValueError(
            "fused_seqpool_cvm_extended needs expand_dim > 0 "
            "(use fused_seqpool_cvm for plain rows)"
        )
    pooled = seqpool(rows, key_segments, batch_size, n_slots)
    base, expand = pooled[..., :-expand_dim], pooled[..., -expand_dim:]
    if use_cvm:
        base = _cvm_transform(base, cvm_offset)
    else:
        base = base[..., cvm_offset:]
    return base.reshape(batch_size, -1), expand.reshape(batch_size, -1)
