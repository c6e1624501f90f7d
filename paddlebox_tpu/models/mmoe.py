"""MMoE: multi-gate mixture-of-experts multi-task model
(BASELINE.json configs[4]: "MMoE multi-task recommender — shared sparse
table, multi-tower dense").

All tasks share the sparse table and the pooled features; E expert MLPs feed
T softmax gates and T task towers.  Task 0's label is the primary label
slot; tasks 1.. read the configured ``task_label_slots``
(DataFeedConfig.task_label_slots — the reference names a label var per
MetricMsg, box_wrapper.cc:1222-1270).

Expert parallelism: with ``expert_mesh`` the expert bank shards over an
``expert`` mesh axis (parallel/expert.py layout: each device runs its E/P
experts on the replicated batch; per-task mixing takes the LOCAL gate
columns and one psum reduces the weighted sum — collective-light for dense
gating, where every instance consumes every expert).  Identical math to
the serial bank; sharded-vs-single parity is pinned by test_moe_ep.  The
reference replicates experts per GPU (no EP engine) — this is a TPU-design
capability, not a port.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddlebox_tpu.models.layers import (
    cast_tree,
    init_linear,
    init_mlp,
    linear,
    mlp,
    resolve_compute_dtype,
)
from paddlebox_tpu.ops import fused_seqpool_cvm, pooled_width
from paddlebox_tpu.parallel.expert import EXPERT_AXIS, expert_parallel_mlp_mix
from paddlebox_tpu.parallel.mesh import inherit_shard_map


class MMoE:
    def __init__(
        self,
        n_sparse_slots: int,
        emb_width: int,
        dense_dim: int = 0,
        n_tasks: int = 2,
        n_experts: int = 4,
        expert_hidden: Sequence[int] = (128,),
        expert_dim: int = 64,
        tower_hidden: Sequence[int] = (32,),
        use_cvm: bool = True,
        cvm_offset: int = 2,
        compute_dtype: str = "",
        expert_mesh=None,  # Mesh | "inherit" (inside an outer shard_map)
    ):
        self.compute_dtype = resolve_compute_dtype(compute_dtype)
        if expert_mesh is not None and expert_mesh != "inherit":
            if EXPERT_AXIS not in expert_mesh.axis_names:
                raise ValueError(
                    f"expert_mesh needs an {EXPERT_AXIS!r} axis, has "
                    f"{expert_mesh.axis_names}"
                )
            p = int(expert_mesh.shape[EXPERT_AXIS])
            if n_experts % p:
                raise ValueError(
                    f"n_experts {n_experts} not divisible by the "
                    f"{EXPERT_AXIS!r} axis size {p}"
                )
        self.expert_mesh = expert_mesh
        self.n_sparse_slots = n_sparse_slots
        self.emb_width = emb_width
        self.dense_dim = dense_dim
        self.n_tasks = n_tasks
        self.n_experts = n_experts
        self.expert_hidden = tuple(expert_hidden)
        self.expert_dim = expert_dim
        self.tower_hidden = tuple(tower_hidden)
        self.use_cvm = use_cvm
        self.cvm_offset = cvm_offset
        pooled_w = pooled_width(emb_width, cvm_offset, use_cvm)
        self.input_dim = n_sparse_slots * pooled_w + dense_dim

    def init(self, key: jax.Array) -> dict:
        ke, kg, kt = jax.random.split(key, 3)
        experts = [
            init_mlp(k, self.input_dim, self.expert_hidden, self.expert_dim)
            for k in jax.random.split(ke, self.n_experts)
        ]
        gates = [
            init_linear(k, self.input_dim, self.n_experts)
            for k in jax.random.split(kg, self.n_tasks)
        ]
        towers = [
            init_mlp(k, self.expert_dim, self.tower_hidden, 1)
            for k in jax.random.split(kt, self.n_tasks)
        ]
        return {"experts": experts, "gates": gates, "towers": towers}

    def apply(self, params, rows, key_segments, dense, batch_size):
        """Returns logits [B, n_tasks]."""
        feats = fused_seqpool_cvm(
            rows, key_segments, batch_size, self.n_sparse_slots,
            use_cvm=self.use_cvm, cvm_offset=self.cvm_offset,
        )
        if self.dense_dim:
            feats = jnp.concatenate([feats, dense], axis=1)
        dt = self.compute_dtype
        gates = jnp.stack(
            [
                jax.nn.softmax(linear(g, feats, dt), axis=-1)
                for g in params["gates"]
            ]
        )  # [T, B, E]
        if self.expert_mesh is None:
            expert_out = jnp.stack(
                [mlp(e, feats, dt) for e in params["experts"]], axis=1
            )  # [B, E, expert_dim]
            mixed = jnp.einsum("tbe,bed->tbd", gates, expert_out)
        else:
            mixed = self._ep_mixed(params["experts"], feats, gates)
        logits = [
            mlp(tower, mixed[t], dt)[:, 0]
            for t, tower in enumerate(params["towers"])
        ]
        return jnp.stack(logits, axis=1)

    # -- expert parallelism ------------------------------------------------ #
    def _ep_mixed(self, experts: list, feats: jax.Array,
                  gates: jax.Array) -> jax.Array:
        """[T, B, expert_dim] gate-mixed expert outputs with the expert bank
        sharded over the ``expert`` mesh axis — the shard_map body is
        parallel/expert.py's expert_parallel_mlp_mix (replicated batch,
        local experts, local gate columns, one psum; mlp() cast policy, so
        serial == sharded under any compute dtype)."""
        dt = self.compute_dtype
        # stacked bank: leaves [E, d_in, d_out] / [E, d_out], sharded on E
        stacked = [
            {
                "w": jnp.stack([e[li]["w"] for e in experts]),
                "b": jnp.stack([e[li]["b"] for e in experts]),
            }
            for li in range(len(experts[0]))
        ]
        if dt is not None:
            feats = feats.astype(dt)
            stacked = cast_tree(stacked, dt)

        E = self.n_experts

        def checked_mix(stacked, feats, gates):
            # trace-time validation for "inherit" mode (no concrete mesh at
            # __init__): axis_size is static here, so raise the same clear
            # error the Mesh path raises instead of an opaque shard error
            p_ax = jax.lax.axis_size(EXPERT_AXIS)
            if E % p_ax:
                raise ValueError(
                    f"n_experts {E} not divisible by the {EXPERT_AXIS!r} "
                    f"axis size {p_ax}"
                )
            return expert_parallel_mlp_mix(stacked, feats, gates)

        in_specs = (P(EXPERT_AXIS), P(), P(None, None, EXPERT_AXIS))
        if self.expert_mesh == "inherit":
            # composed mode: an OUTER shard_map (e.g. MultiChipTrainer on a
            # data x expert mesh) already established the context mesh; bind
            # only the expert axis here and let the rest stay as-is
            sm = inherit_shard_map(
                checked_mix, in_specs=in_specs, out_specs=P(),
                axis_name=EXPERT_AXIS,
            )
        else:
            sm = jax.shard_map(
                expert_parallel_mlp_mix, mesh=self.expert_mesh,
                in_specs=in_specs, out_specs=P(),
            )
        return sm(stacked, feats, gates)
