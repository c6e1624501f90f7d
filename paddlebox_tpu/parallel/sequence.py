"""Sequence/context parallelism: ring attention + all-to-all (Ulysses) SP.

The CTR reference has no long-sequence path (SURVEY.md §5.7: its "sequences"
are unordered slot key-sets pooled by segment-sum, and rank_attention tops
out at max_rank=3) — but sequence parallelism is a first-class capability of
this framework so user models that DO consume long behavior sequences
(e.g. search/browse history towers feeding the CTR net) scale past one
chip's memory.  Two TPU-native strategies over one ``seq`` mesh axis:

  * ``ring_attention`` — every device holds one contiguous sequence chunk of
    Q/K/V; K/V blocks circulate the ICI ring via ``ppermute`` while each
    device folds one block per tick into a numerically-stable online-softmax
    accumulator (the flash/ring-attention recursion: running max ``m``,
    normalizer ``l``, weighted sum ``acc``).  Peak memory is O(T_local²)
    per device and the ring transfer overlaps the matmuls under XLA.
    Causal masking uses global chunk offsets (device j's block after t
    shifts came from chunk (j - t) mod P).
  * ``ulysses_attention`` — two ``all_to_all``s trade the sequence axis for
    the head axis: each device attends over the FULL sequence for H/P of
    the heads, so any dense-attention kernel drops in unchanged between the
    two collectives.  Cheaper collectives for moderate T; needs H % P == 0.

Both are pure shard_map bodies (jit + autodiff through scan/ppermute/
all_to_all work out of the box) and reduce to plain attention at P=1.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

SEQ_AXIS = "seq"


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    key_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain softmax attention (the single-device reference semantics).

    q/k/v: [B, T, H, D]; returns [B, T, H, D].
    key_valid: optional bool [B, Tk] — padded key positions read zero
    attention weight (variable-length sequences); a query whose keys are
    ALL masked reads a zero vector, not NaN.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    if key_valid is not None:
        s = jnp.where(key_valid[:, None, None, :], s, -jnp.inf)
    # masked-stable softmax: exp(-inf)=0 rows normalize against a floored
    # denominator instead of producing NaN
    m = jnp.max(s, axis=-1, keepdims=True)
    w = jnp.exp(s - jnp.where(jnp.isneginf(m), 0.0, m))
    p = w / jnp.maximum(w.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    key_valid: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Ring attention over sequence chunks (call INSIDE shard_map over
    ``axis_name``; every array is this device's chunk [B, T_local, H, D],
    chunks laid out contiguously in mesh order).

    key_valid: optional bool [B, T_local] — this chunk's key validity; it
    rides the ring with its K/V block so padded positions are masked
    wherever the block is folded.
    positions: optional int32 [T_local] — this chunk's GLOBAL sequence
    positions.  They ride the ring with their K/V block, so causal masking
    needs no ``axis_index`` — which also makes the body legal inside an
    OUTER shard_map (composed data x seq meshes), where axis_index of a
    nested axis does not lower.  Default: derived from axis_index
    (standalone use).
    """
    p_axis = jax.lax.axis_size(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / jnp.sqrt(float(d))
    # positions are only consumed by causal masking: derive (axis_index) and
    # ring-carry them ONLY then, so a non-causal call never pays the carry
    # and stays free of axis_index — legal inside an outer shard_map with no
    # positions passed at all
    if causal and positions is None:
        idx = jax.lax.axis_index(axis_name)
        positions = idx * t + jnp.arange(t, dtype=jnp.int32)
    q_pos = positions  # global positions of local queries (None: non-causal)

    def fold(args):
        """One online-softmax fold (flash recursion) in f32 accumulators."""
        k_blk, v_blk, valid_blk, pos_blk, acc, m, l = args
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            mask = q_pos[:, None] >= pos_blk[None, :]  # [Tq, Tk]
            s = jnp.where(mask[None, None], s, -jnp.inf)
        s = jnp.where(valid_blk[:, None, None, :], s, -jnp.inf)
        s_max = s.max(axis=-1)  # [B, H, Tq]
        m_new = jnp.maximum(m, s_max)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        w = jnp.exp(s - m_safe[..., None])  # exp(-inf)=0 handles masked
        l = l * alpha + w.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", w, v_blk.astype(jnp.float32)
        )
        return acc, m_new, l

    def tick(carry, j):
        k_blk, v_blk, valid_blk, pos_blk, acc, m, l = carry
        if causal:
            # a block entirely in the causal future folds to a no-op: skip
            # its matmuls at runtime (the ring shift still happens below).
            # "entirely in the future" reads off the riding positions, so
            # no axis_index is needed.
            acc, m, l = jax.lax.cond(
                pos_blk.min() <= q_pos.max(),
                fold,
                lambda args: (args[4], args[5], args[6]),
                (k_blk, v_blk, valid_blk, pos_blk, acc, m, l),
            )
        else:
            acc, m, l = fold((k_blk, v_blk, valid_blk, pos_blk, acc, m, l))
        # the last tick's rotation would be discarded: skip it (the scan
        # counter is replicated, so every device takes the same branch and
        # the collective stays coherent)
        ring = (k_blk, v_blk, valid_blk) + ((pos_blk,) if causal else ())
        ring = jax.lax.cond(
            j < p_axis - 1,
            lambda kv: jax.lax.ppermute(
                kv, axis_name,
                [(i, (i + 1) % p_axis) for i in range(p_axis)],
            ),
            lambda kv: kv,
            ring,
        )
        k_blk, v_blk, valid_blk = ring[:3]
        pos_blk = ring[3] if causal else pos_blk
        return (k_blk, v_blk, valid_blk, pos_blk, acc, m, l), None

    # accumulate in f32 whatever the input dtype (flash-attention practice:
    # bf16 inputs, f32 running max/normalizer/weighted-sum)
    # over every axis q varies over: nested in an outer shard_map that is
    # the outer (data) axis as well as axis_name
    vary = lambda x: jax.lax.pcast(x, tuple(jax.typeof(q).vma), to="varying")
    # the synthesized all-ones mask is replicated; the ring shift needs it
    # device-varying like the K/V blocks it rides with
    kv_valid = (
        vary(jnp.ones((b, t), bool)) if key_valid is None else key_valid
    )
    pos0 = (
        positions if causal
        else jnp.zeros((), jnp.int32)  # placeholder, never read or shifted
    )
    acc0 = jnp.zeros((b, h, t, d), jnp.float32)
    m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    (_, _, _, _, acc, _, l), _ = jax.lax.scan(
        tick,
        (k, v, kv_valid, pos0, vary(acc0), vary(m0), vary(l0)),
        jnp.arange(p_axis),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, H, T, D] f32
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    axis_name: str = SEQ_AXIS,
    key_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """All-to-all sequence parallelism (call INSIDE shard_map over
    ``axis_name``): trade T-sharding for H-sharding, run full attention,
    trade back.  q/k/v: [B, T_local, H, D] with H divisible by the axis
    size; returns [B, T_local, H, D].
    key_valid: optional bool [B, T_local] — local chunk's key validity,
    allgathered to the full sequence for the head-sharded attention.
    """
    p_axis = jax.lax.axis_size(axis_name)
    b, t, h, d = q.shape
    if h % p_axis != 0:
        raise ValueError(f"heads {h} not divisible by seq axis size {p_axis}")
    valid_full = (
        None
        if key_valid is None
        else jax.lax.all_gather(key_valid, axis_name, axis=1, tiled=True)
    )

    def seq_to_heads(x):
        # [B, T_local, H, D] -> [B, P*T_local, H/P, D]: give every device
        # the FULL sequence for its H/P heads (one tiled all_to_all)
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    out = full_attention(
        seq_to_heads(q), seq_to_heads(k), seq_to_heads(v), causal=causal,
        key_valid=valid_full,
    )
    return heads_to_seq(out)
