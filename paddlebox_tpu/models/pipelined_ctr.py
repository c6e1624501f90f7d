"""Pipeline-parallel CTR model: the real dense tower over a ``pipe`` mesh.

The round-3 ``parallel/pipeline.py`` demonstrated the
GPipe loop-skew schedule on a hardcoded uniform MLP; here the SAME schedule
runs the actual CTR model family's tower, as a drop-in *model*:
``PipelinedCtrDnn`` keeps ``CtrDnn``'s apply() contract (rows in, logits
out), so the unmodified single-chip ``Trainer`` drives it end-to-end —
stage 0 consumes the pooled sparse features exactly as the reference's
first pipeline section consumes the BoxPS pull
(reference: pipeline_trainer.cc runs arbitrary ProgramDesc sections;
test_paddlebox_datafeed.py:96-102 wraps the BoxPS CTR program with
PipelineOptimizer the same way).

Heterogeneous layer widths vs SPMD: shard_map needs every stage to run
the same program on same-shaped arrays, but a CTR tower narrows
(e.g. 173 -> 512 -> 256 -> 128 -> 1).  Every layer is therefore padded to
[A, A] (A = widest activation) with zero rows/cols, and activations ride
the ring at width A.  Zero padding is exact, not approximate: padded
input columns are zero, so padded weight entries see zero inputs and zero
upstream gradients — they stay zero under any gradient optimizer, and the
computed logits equal the unpadded tower's bit-for-bit math (appending
zero terms to a dot product changes nothing).  The price is padded-matmul
FLOPs, paid to keep ONE compiled SPMD program; per-stage-shape programs
would trade that for P distinct programs and manual p2p.

Schedule: classic GPipe fill/drain over M microbatches (bubble
(P-1)/(M+P-1)); activations move stage-to-stage by ``ppermute`` (ICI
ring) and logits return from the last stage by psum.  Backward is plain
``jax.grad`` through the scan (the ppermute transpose is the reverse
shift), as in parallel/pipeline.py.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.models.layers import (
    cast_tree,
    init_mlp,
    resolve_compute_dtype,
)
from paddlebox_tpu.ops import fused_seqpool_cvm, pooled_width
from paddlebox_tpu.parallel.pipeline import PIPE_AXIS, gpipe_run


def _split_stages(n_layers: int, n_stages: int) -> list[list[int]]:
    """Contiguous layer ranges per stage (early stages take the remainder —
    they hold the wider, costlier layers less often than late ones)."""
    if n_layers < n_stages:
        raise ValueError(
            f"tower has {n_layers} layers but the pipe mesh has {n_stages} "
            "stages: every stage needs at least one layer"
        )
    base, rem = divmod(n_layers, n_stages)
    out, i = [], 0
    for s in range(n_stages):
        take = base + (1 if s < rem else 0)
        out.append(list(range(i, i + take)))
        i += take
    return out


class PipelinedCtrDnn:
    """CtrDnn with its ReLU tower executed as a GPipe pipeline.

    Same apply() contract as CtrDnn (default layout, no expand/conv), so
    Trainer/metrics/prefetch/scan all work unchanged.  ``microbatches``
    must divide the batch size.
    """

    def __init__(
        self,
        mesh: Mesh,
        n_sparse_slots: int,
        emb_width: int,
        dense_dim: int = 0,
        hidden: Sequence[int] = (512, 256, 128),
        use_cvm: bool = True,
        cvm_offset: int = 2,
        microbatches: Optional[int] = None,
        compute_dtype: str = "",  # "" -> flags.compute_dtype
    ):
        if PIPE_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh needs a {PIPE_AXIS!r} axis, has {mesh.axis_names}"
            )
        # same cast policy as CtrDnn (f32 params/pooling, compute-dtype
        # tower, f32 logits) so TrainerConfig.compute_dtype works unchanged
        self.compute_dtype = resolve_compute_dtype(compute_dtype)
        self.mesh = mesh
        self.n_stages = int(mesh.shape[PIPE_AXIS])
        self.n_sparse_slots = n_sparse_slots
        self.emb_width = emb_width
        self.dense_dim = dense_dim
        self.hidden = tuple(hidden)
        self.use_cvm = use_cvm
        self.cvm_offset = cvm_offset
        pooled_w = pooled_width(emb_width, cvm_offset, use_cvm)
        self.input_dim = n_sparse_slots * pooled_w + dense_dim
        self.microbatches = microbatches or 2 * self.n_stages
        # layer l maps dims[l] -> dims[l+1]; the last layer is the head
        self.dims = [self.input_dim, *self.hidden, 1]
        self.A = max(self.dims)
        self.stage_layers = _split_stages(len(self.dims) - 1, self.n_stages)
        self.depth_max = max(len(ls) for ls in self.stage_layers)
        # static per-(stage, layer-slot) flags — structure, not parameters
        live = np.zeros((self.n_stages, self.depth_max), np.bool_)
        head = np.zeros((self.n_stages, self.depth_max), np.bool_)
        for s, ls in enumerate(self.stage_layers):
            for j, l in enumerate(ls):
                live[s, j] = True
                head[s, j] = l == len(self.dims) - 2
        self._live = live
        self._head = head

    # -- params ------------------------------------------------------------ #
    def init(self, key: jax.Array) -> dict:
        """CtrDnn-identical tower init (init_mlp), packed into padded
        stacked stages — so a PipelinedCtrDnn and a CtrDnn seeded alike
        start from the SAME function."""
        layers = init_mlp(key, self.input_dim, self.hidden, 1)
        return {"stages": self.pack_tower(layers)}

    def pack_tower(self, layers: list) -> dict:
        """[{'w','b'}, ...] unpadded tower -> stacked [P, dmax, A, A] /
        [P, dmax, A] padded stage params (zero-padded, see module doc)."""
        A, dmax = self.A, self.depth_max
        w = np.zeros((self.n_stages, dmax, A, A), np.float32)
        b = np.zeros((self.n_stages, dmax, A), np.float32)
        for s, ls in enumerate(self.stage_layers):
            for j, l in enumerate(ls):
                lw = np.asarray(layers[l]["w"], np.float32)
                lb = np.asarray(layers[l]["b"], np.float32).reshape(-1)
                w[s, j, : lw.shape[0], : lw.shape[1]] = lw
                b[s, j, : lb.shape[0]] = lb
        return {"w": jnp.asarray(w), "b": jnp.asarray(b)}

    def unpack_tower(self, params: dict) -> list:
        """Inverse of pack_tower (checkpoint interchange with CtrDnn)."""
        w = np.asarray(params["stages"]["w"])
        b = np.asarray(params["stages"]["b"])
        out = []
        for s, ls in enumerate(self.stage_layers):
            for j, l in enumerate(ls):
                din, dout = self.dims[l], self.dims[l + 1]
                out.append({"w": w[s, j, :din, :dout].copy(),
                            "b": b[s, j, :dout].copy()})
        return out

    # -- forward ----------------------------------------------------------- #
    def _pipeline_logits(self, stages: dict, x_pad: jax.Array) -> jax.Array:
        """x_pad: [M, mb, A] padded microbatches -> logits [M*mb]
        (replicated).  Runs inside shard_map over the pipe axis."""
        # this device's stage: strip the sharded leading axis
        sw = stages["w"][0]  # [dmax, A, A]
        sb = stages["b"][0]  # [dmax, A]
        if self.compute_dtype is not None:
            sw, sb = cast_tree((sw, sb), self.compute_dtype)
        live = jnp.asarray(self._live)
        head = jnp.asarray(self._head)
        M, mb, A = x_pad.shape
        p_axis = jax.lax.axis_size(PIPE_AXIS)
        idx = jax.lax.axis_index(PIPE_AXIS)

        def stage_fn(m_in, act, is_first):
            h = jnp.where(is_first, x_pad[m_in], act)

            def layer(h, inp):
                w, b, lv, hd = inp
                out = h @ w + b
                out = jnp.where(hd, out, jax.nn.relu(out))
                # dead layer slots (stage shorter than dmax) pass through
                return jnp.where(lv, out, h), None

            h, _ = jax.lax.scan(layer, h, (sw, sb, live[idx], head[idx]))
            return h, h[:, 0]  # activation out; head's logit rides col 0

        def emit_fn(logit_col, m_out, valid):
            del m_out
            return jnp.where(valid, logit_col, 0.0)

        emits = gpipe_run(
            stage_fn, emit_fn, M, jnp.zeros((mb, A), x_pad.dtype)
        )  # [T, mb]
        # ticks P-1..T-1 carry microbatches 0..M-1 (on the last stage only)
        logits = emits[p_axis - 1 :].reshape(M * mb)
        logits = logits.astype(jnp.float32)  # upcast before the reduction
        return jax.lax.psum(logits, PIPE_AXIS)  # zeros elsewhere

    def apply(
        self,
        params: dict,
        rows: jax.Array,  # [K, emb_width]
        key_segments: jax.Array,  # [K]
        dense: jax.Array,  # [B, dense_dim]
        batch_size: int,
    ) -> jax.Array:
        """Returns logits [B].  Pooling (the sparse half) runs replicated —
        it is the data-parallel path's output; only the tower pipelines."""
        pooled = fused_seqpool_cvm(
            rows, key_segments, batch_size, self.n_sparse_slots,
            use_cvm=self.use_cvm, cvm_offset=self.cvm_offset,
        )
        x = (
            jnp.concatenate([pooled, dense], axis=1)
            if self.dense_dim
            else pooled
        )
        B = batch_size
        M = self.microbatches
        if B % M:
            raise ValueError(
                f"batch size {B} not divisible by microbatches {M}"
            )
        x_pad = jnp.zeros((B, self.A), x.dtype).at[:, : self.input_dim].set(x)
        if self.compute_dtype is not None:
            x_pad = x_pad.astype(self.compute_dtype)
        x_mb = x_pad.reshape(M, B // M, self.A)

        mapped = jax.shard_map(
            self._pipeline_logits,
            mesh=self.mesh,
            in_specs=(P(PIPE_AXIS), P()),
            out_specs=P(),
        )
        return mapped(params["stages"], x_mb)
