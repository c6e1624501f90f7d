"""Long-sequence CTR model: behavior-sequence attention tower + CTR net.

The reference has NO long-sequence path (SURVEY.md §5.7: its "sequences"
are unordered slot key-sets pooled by segment-sum) — this model is the
beyond-parity integration that makes the framework's sequence parallelism
(parallel/sequence.py) a consumable capability instead of shelf inventory: a user-behavior slot (e.g. click history, file order
== behavior order) is embedded as an ORDERED sequence, run through
multi-head self-attention, and mean-pooled into one feature vector next to
the standard pooled-CVM slot features — the DIN/DIEN-family shape on top
of the BoxPS-style sparse table.

TPU-first: the attention is one einsum chain on the MXU; long sequences
shard over a ``seq`` mesh axis with ring attention (K/V blocks ride the
ICI ring; O(T_local^2) memory) or Ulysses all-to-all (head-sharded full
attention).  At mesh size 1 both reduce to plain attention, so the SAME
model runs single-chip and sequence-parallel with identical math —
sharded-vs-single parity is pinned by test_longseq.py.

Data contract: DataFeedConfig.sequence_slot names the behavior slot;
HostBatch.seq_pos [B, T] carries each instance's ordered key-buffer
positions (padding = key capacity), built by the feed with zero extra
parsing.  The slot still contributes its normal pooled feature.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddlebox_tpu.models.layers import init_mlp, mlp, resolve_compute_dtype
from paddlebox_tpu.ops import fused_seqpool_cvm, pooled_width
from paddlebox_tpu.parallel.mesh import inherit_shard_map
from paddlebox_tpu.parallel.sequence import (
    SEQ_AXIS,
    full_attention,
    ring_attention,
    ulysses_attention,
)


class LongSeqCtrDnn:
    """CtrDnn + an attention tower over one ordered behavior slot.

    apply() matches the framework model contract with one extra feed input
    (``seq_pos``, declared via ``uses_seq_pos``), so Trainer / metrics /
    prefetch / scan / export work unchanged.
    """

    uses_seq_pos = True

    def __init__(
        self,
        n_sparse_slots: int,
        emb_width: int,  # pulled row width (cvm_offset + embedding_dim)
        dense_dim: int = 0,
        hidden: Sequence[int] = (512, 256, 128),
        use_cvm: bool = True,
        cvm_offset: int = 2,
        max_seq_len: int = 64,
        n_heads: int = 2,
        head_dim: int = 16,
        seq_mesh=None,  # Mesh | "inherit" | None (single-device)
        seq_impl: str = "ring",  # "ring" | "ulysses" (with seq_mesh)
        compute_dtype: str = "",
    ):
        self.compute_dtype = resolve_compute_dtype(compute_dtype)
        if seq_impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq_impl {seq_impl!r}")
        if seq_mesh is not None and seq_mesh != "inherit":
            if SEQ_AXIS not in seq_mesh.axis_names:
                raise ValueError(
                    f"seq_mesh needs a {SEQ_AXIS!r} axis, has "
                    f"{seq_mesh.axis_names}"
                )
            p = int(seq_mesh.shape[SEQ_AXIS])
            if max_seq_len % p:
                raise ValueError(
                    f"max_seq_len {max_seq_len} not divisible by the "
                    f"{SEQ_AXIS!r} axis size {p}"
                )
            if seq_impl == "ulysses" and n_heads % p:
                raise ValueError(
                    f"ulysses needs n_heads ({n_heads}) divisible by the "
                    f"seq axis size ({p})"
                )
        self.n_sparse_slots = n_sparse_slots
        self.emb_width = emb_width
        self.dense_dim = dense_dim
        self.hidden = tuple(hidden)
        self.use_cvm = use_cvm
        self.cvm_offset = cvm_offset
        self.max_seq_len = max_seq_len
        self.n_heads = n_heads
        self.head_dim = head_dim
        self.seq_mesh = seq_mesh
        self.seq_impl = seq_impl
        self.emb_dim = emb_width - cvm_offset
        if self.emb_dim <= 0:
            raise ValueError("emb_width leaves no embedding columns")
        pooled_w = pooled_width(emb_width, cvm_offset, use_cvm)
        self.seq_feat_dim = n_heads * head_dim
        self.input_dim = (
            n_sparse_slots * pooled_w + self.seq_feat_dim + dense_dim
        )

    # -- params ------------------------------------------------------------ #
    def init(self, key: jax.Array) -> dict:
        k_qkv, k_tower = jax.random.split(key)
        hd = self.n_heads * self.head_dim
        scale = 1.0 / np.sqrt(self.emb_dim)
        return {
            "qkv": jax.random.normal(
                k_qkv, (self.emb_dim, 3 * hd), jnp.float32
            ) * scale,
            "tower": init_mlp(k_tower, self.input_dim, self.hidden, 1),
        }

    # -- forward ----------------------------------------------------------- #
    def _attend(self, q, k, v, valid):
        """[B, T, H, D] attention, sequence-sharded when a mesh is given."""
        if self.seq_mesh is None:
            return full_attention(q, k, v, key_valid=valid)

        impl = ring_attention if self.seq_impl == "ring" else ulysses_attention
        T, H, name = self.max_seq_len, self.n_heads, self.seq_impl

        def body(q, k, v, valid):
            # trace-time shape validation for the "inherit" mode, where no
            # concrete mesh exists at __init__ (axis_size is static here)
            p = jax.lax.axis_size(SEQ_AXIS)
            if T % p:
                raise ValueError(
                    f"max_seq_len {T} not divisible by the {SEQ_AXIS!r} "
                    f"axis size {p}"
                )
            if name == "ulysses" and H % p:
                raise ValueError(
                    f"ulysses needs n_heads ({H}) divisible by the seq "
                    f"axis size ({p})"
                )
            # non-causal: ring attention carries no positions and uses no
            # axis_index, so the body nests inside an outer shard_map
            # (composed data x seq meshes) as-is
            return impl(q, k, v, key_valid=valid)

        sspec = P(None, SEQ_AXIS)
        in_specs = (sspec, sspec, sspec, sspec)
        if self.seq_mesh == "inherit":
            sm = inherit_shard_map(
                body, in_specs=in_specs, out_specs=sspec, axis_name=SEQ_AXIS,
            )
        else:
            sm = jax.shard_map(
                body, mesh=self.seq_mesh, in_specs=in_specs, out_specs=sspec,
            )
        return sm(q, k, v, valid)

    def apply(
        self,
        params: dict,
        rows: jax.Array,  # [K, emb_width]
        key_segments: jax.Array,  # [K]
        dense: jax.Array,  # [B, dense_dim]
        batch_size: int,
        seq_pos: jax.Array,  # int32 [B, T] into the key buffer (pad = K)
    ) -> jax.Array:
        """Returns logits [B]."""
        B, T = batch_size, self.max_seq_len
        K = rows.shape[0]
        if seq_pos.shape[-1] != T:
            raise ValueError(
                f"seq_pos width {seq_pos.shape[-1]} != model max_seq_len "
                f"{T}: set DataFeedConfig.max_seq_len and "
                "LongSeqCtrDnn(max_seq_len=...) to the same value"
            )
        pooled = fused_seqpool_cvm(
            rows, key_segments, B, self.n_sparse_slots,
            use_cvm=self.use_cvm, cvm_offset=self.cvm_offset,
        )
        # ordered behavior embeddings: pad positions (== K) read the
        # appended zero row; their cotangent lands on it and is dropped
        rows_pad = jnp.concatenate(
            [rows, jnp.zeros((1, rows.shape[1]), rows.dtype)]
        )
        seq = jnp.take(rows_pad, seq_pos, axis=0)[..., self.cvm_offset:]
        valid = seq_pos < K  # [B, T]

        cdt = self.compute_dtype
        qkv_w = params["qkv"]
        if cdt is not None:
            seq = seq.astype(cdt)
            qkv_w = qkv_w.astype(cdt)
        qkv = seq @ qkv_w  # [B, T, 3*H*D]
        q, k, v = jnp.split(
            qkv.reshape(B, T, 3, self.n_heads, self.head_dim), 3, axis=2
        )
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]  # [B, T, H, D]
        out = self._attend(q, k, v, valid)  # [B, T, H, D]
        out = out.reshape(B, T, self.seq_feat_dim)
        out = out * valid[..., None].astype(out.dtype)
        denom = jnp.maximum(valid.sum(axis=1, keepdims=True), 1)
        seq_feat = (out.sum(axis=1) / denom).astype(jnp.float32)  # [B, HD]

        x = jnp.concatenate([pooled, seq_feat, dense], axis=1) \
            if self.dense_dim else jnp.concatenate([pooled, seq_feat], axis=1)
        return mlp(params["tower"], x, cdt)[:, 0]
