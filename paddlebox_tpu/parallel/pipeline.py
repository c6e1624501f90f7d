"""Pipeline parallelism: microbatched stage pipeline over a ``pipe`` mesh axis.

The ``PipelineTrainer``/``SectionWorker`` analog (reference:
framework/pipeline_trainer.cc + section_worker.cc — program sections run in
microbatch-scoped scopes, activations move stage-to-stage via send_v2/recv_v2
ops; python PipelineOptimizer wraps even the single-GPU BoxPS program,
test_paddlebox_datafeed.py:96-102).  SURVEY.md §2.9 scopes the TPU answer:
"jax pipeline via shard_map stages".

TPU-native design — no p2p ops, no per-stage processes:

  * each device owns ONE stage's params (leading ``stage`` axis sharded over
    the pipe mesh axis);
  * one jitted ``shard_map`` body runs the classic loop-skew schedule: a
    ``lax.scan`` over ``M + P - 1`` ticks where every tick computes the local
    stage on its in-flight microbatch and ``ppermute``s the activation to
    the next device — XLA lowers that to the ICI ring;
  * stage 0 injects microbatch t at tick t, the last stage emits microbatch
    ``t-(P-1)``'s logits/loss at tick t — the fill/drain bubble is
    ``(P-1)/(M+P-1)``, amortized by choosing M >> P (GPipe discipline);
  * backward is plain ``jax.grad`` THROUGH the scan+ppermute (the ppermute
    transpose is the reverse shift), so fwd+bwd stay one compiled program —
    no hand-written 1F1B schedule is needed for correctness, and XLA
    overlaps the collective with compute where profitable.

The pipelined network is a uniform-width residual-free MLP tower: stage 0
projects d_in -> width, every stage applies ``depth_per_stage`` width->width
relu layers, the last stage adds the scalar head.  All stages run the same
program (a dead proj/head where unused) so the shard_map body is SPMD.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddlebox_tpu.telemetry.compiles import counted_jit

PIPE_AXIS = "pipe"


def init_pipeline_params(
    key: jax.Array, d_in: int, width: int, depth_per_stage: int, n_stages: int
) -> dict:
    """Per-stage params, stacked on a leading [n_stages, ...] axis.

    Every stage carries a proj and head block so the stage program is
    uniform; only stage 0's proj and stage P-1's head are live.
    """
    ks = jax.random.split(key, n_stages)

    def one_stage(k):
        kp, kh, *kb = jax.random.split(k, 2 + depth_per_stage)
        s_in = 1.0 / np.sqrt(d_in)
        s_w = 1.0 / np.sqrt(width)
        return {
            "proj_w": jax.random.uniform(kp, (d_in, width), minval=-s_in, maxval=s_in),
            "proj_b": jnp.zeros((width,)),
            "blocks_w": jnp.stack([
                jax.random.uniform(kb[i], (width, width), minval=-s_w, maxval=s_w)
                for i in range(depth_per_stage)
            ]),
            "blocks_b": jnp.zeros((depth_per_stage, width)),
            "head_w": jax.random.uniform(kh, (width, 1), minval=-s_w, maxval=s_w),
            "head_b": jnp.zeros((1,)),
        }

    stages = [one_stage(k) for k in ks]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stages)


def _stage_apply(p: dict, x_inject: jax.Array, carry: jax.Array,
                 is_first: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One stage's compute: pick the injected input (stage 0) or the carried
    activation, run the blocks, and also compute the head (live only on the
    last stage).  Returns (activation_out, logits)."""
    h0 = jnp.dot(x_inject, p["proj_w"]) + p["proj_b"]
    h = jnp.where(is_first, h0, carry)

    def block(h, wb):
        w, b = wb
        return jax.nn.relu(jnp.dot(h, w) + b), None

    h, _ = jax.lax.scan(block, h, (p["blocks_w"], p["blocks_b"]))
    logits = (jnp.dot(h, p["head_w"]) + p["head_b"])[:, 0]
    return h, logits


def gpipe_run(stage_fn, emit_fn, n_microbatches: int, act0: jax.Array):
    """The GPipe loop-skew schedule skeleton, shared by
    ``pipeline_forward_loss`` (uniform demo tower) and
    ``models/pipelined_ctr.py`` (the real CTR tower) so the subtle
    collective code — T = M+P-1 ticks, clip-injection, ppermute edge list,
    the pcast-varying carry workaround — lives exactly once.

    Call INSIDE shard_map over the pipe axis.
      stage_fn(m_in, act, is_first) -> (act_out, aux): this device's stage
          on tick input (m_in = clipped microbatch index for stage 0's
          injection; act = carried activation).
      emit_fn(aux, m_out, valid) -> pytree emitted each tick (m_out = the
          microbatch the LAST stage completes this tick, clipped; valid =
          is_last & tick within range).
    Returns emissions stacked [T, ...].
    """
    p_axis = jax.lax.axis_size(PIPE_AXIS)
    idx = jax.lax.axis_index(PIPE_AXIS)
    M = n_microbatches
    T = M + p_axis - 1
    is_first = idx == 0
    is_last = idx == p_axis - 1

    def tick(act, t):
        m_in = jnp.clip(t, 0, M - 1)  # stage 0's injected microbatch
        act_out, aux = stage_fn(m_in, act, is_first)
        # last stage: tick t completes microbatch t - (P-1)
        m_out = t - (p_axis - 1)
        valid = is_last & (m_out >= 0)
        em = emit_fn(aux, jnp.clip(m_out, 0, M - 1), valid)
        # shift activations one stage down the ring (last stage's output
        # falls off the end — the emit already consumed it)
        act_next = jax.lax.ppermute(
            act_out, PIPE_AXIS, [(i, i + 1) for i in range(p_axis - 1)]
        )
        return act_next, em

    # the carry becomes device-varying after the first tick: mark it so up
    # front (shard_map's varying-axes typing requires carry in/out to match)
    vary = lambda v: jax.lax.pcast(v, (PIPE_AXIS,), to="varying")
    _, emits = jax.lax.scan(tick, vary(act0), jnp.arange(T))
    return emits


def pipeline_forward_loss(
    stage_params: dict,
    x: jax.Array,  # [M, mb, d_in] microbatches (replicated; stage 0 reads)
    y: jax.Array,  # [M, mb] labels in {0,1}
    mask: jax.Array,  # [M, mb] 1.0 for real instances
) -> jax.Array:
    """Mean sigmoid-BCE over all real instances — call INSIDE shard_map over
    the pipe axis; stage_params are this device's (leading axis stripped)."""
    M, mb, _ = x.shape
    width = stage_params["proj_b"].shape[0]

    def stage_fn(m_in, act, is_first):
        return _stage_apply(stage_params, x[m_in], act, is_first)

    def emit_fn(logits, m_out, valid):
        lab, msk = y[m_out], mask[m_out] * valid
        per = optax.sigmoid_binary_cross_entropy(logits, lab) * msk
        return per.sum(), msk.sum()

    losses, cnts = gpipe_run(
        stage_fn, emit_fn, M, jnp.zeros((mb, width), x.dtype)
    )
    # only the last stage accumulated: share with everyone
    loss_sum = jax.lax.psum(losses.sum(), PIPE_AXIS)
    cnt_sum = jax.lax.psum(cnts.sum(), PIPE_AXIS)
    return loss_sum / jnp.maximum(cnt_sum, 1.0)


class PipelineTrainer:
    """Drives a pipelined dense tower over a pipe mesh (PipelineTrainer +
    SectionWorker analog; pairs with the data-parallel sparse path by
    feeding it pooled features).  One jitted step = fwd + bwd through the
    schedule + per-stage adam (stage params are disjoint, so the optimizer
    needs no cross-stage communication)."""

    def __init__(
        self,
        mesh: Mesh,
        d_in: int,
        width: int = 64,
        depth_per_stage: int = 2,
        lr: float = 1e-3,
        seed: int = 0,
        params: Optional[dict] = None,
        optimizer=None,
    ):
        """optimizer: any optax transform (default ``optax.adam(lr)``);
        the grads-equivalence test injects plain SGD here, which is
        linear in the gradient, so reduction-order float noise stays
        noise-sized instead of being amplified through adam's
        first-step normalization."""
        if PIPE_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh needs a {PIPE_AXIS!r} axis, has {mesh.axis_names}")
        self.mesh = mesh
        self.n_stages = int(mesh.shape[PIPE_AXIS])
        self.d_in, self.width = d_in, width
        self.optimizer = optimizer if optimizer is not None \
            else optax.adam(lr)
        self._sharding = NamedSharding(mesh, P(PIPE_AXIS))
        if params is None:
            params = init_pipeline_params(
                jax.random.PRNGKey(seed), d_in, width, depth_per_stage,
                self.n_stages,
            )
        got_stages = int(jax.tree.leaves(params)[0].shape[0])
        if got_stages != self.n_stages:
            raise ValueError(
                f"params carry {got_stages} stages but the pipe mesh has "
                f"{self.n_stages} devices — a divisible mismatch would "
                "silently drop stages"
            )
        self.params = jax.device_put(params, self._sharding)
        opt0 = [
            self.optimizer.init(jax.tree.map(lambda l: l[s], params))
            for s in range(self.n_stages)
        ]
        self.opt_state = jax.device_put(
            jax.tree.map(lambda *xs: jnp.stack(xs), *opt0), self._sharding
        )
        self._step_fn = None

    def _build_step(self):
        optimizer = self.optimizer

        def body(params, opt_state, x, y, mask):
            unstack = lambda t: jax.tree.map(lambda l: l[0], t)
            p, o = unstack(params), unstack(opt_state)

            loss, grads = jax.value_and_grad(pipeline_forward_loss)(
                p, x, y, mask
            )
            # value_and_grad runs INSIDE the shard_map body; with varying-
            # axes typing on (check_vma, the default) the psum'd loss is
            # invariant over the pipe axis and its transpose seeds each
            # stage's cotangent once, so the per-stage grad is exact
            updates, o = optimizer.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            restack = lambda t: jax.tree.map(lambda l: l[None], t)
            return restack(p), restack(o), loss[None]

        spec = P(PIPE_AXIS)
        rep = P()  # microbatches replicated across stages

        mapped = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(spec, spec, rep, rep, rep),
            out_specs=(spec, spec, spec),
        )
        return counted_jit(
            mapped, stage="pipeline.step", donate_argnums=(0, 1))

    def train_step(self, x_mb: np.ndarray, y_mb: np.ndarray,
                   mask_mb: Optional[np.ndarray] = None) -> float:
        """x_mb: [M, mb, d_in] microbatches; returns the step loss."""
        if self._step_fn is None:
            self._step_fn = self._build_step()
        if mask_mb is None:
            mask_mb = np.ones(y_mb.shape, np.float32)
        self.params, self.opt_state, loss = self._step_fn(
            self.params, self.opt_state,
            jnp.asarray(x_mb), jnp.asarray(y_mb), jnp.asarray(mask_mb),
        )
        from paddlebox_tpu.parallel.multiprocess import read_replicated

        return float(read_replicated(loss).reshape(-1)[0])


def reference_forward_loss(stage_params: dict, x: jax.Array, y: jax.Array,
                           mask: jax.Array) -> jax.Array:
    """Unpipelined evaluation of the SAME stacked params (test oracle):
    run every stage sequentially on the full batch."""
    n_stages = stage_params["proj_b"].shape[0]
    M, mb, _ = x.shape
    flat = x.reshape(M * mb, -1)
    h = jnp.dot(flat, stage_params["proj_w"][0]) + stage_params["proj_b"][0]
    for s in range(n_stages):
        p = jax.tree.map(lambda l: l[s], stage_params)
        for d in range(p["blocks_w"].shape[0]):
            h = jax.nn.relu(jnp.dot(h, p["blocks_w"][d]) + p["blocks_b"][d])
        if s == n_stages - 1:
            logits = (jnp.dot(h, p["head_w"]) + p["head_b"])[:, 0]
    per = optax.sigmoid_binary_cross_entropy(
        logits, y.reshape(-1)
    ) * mask.reshape(-1)
    return per.sum() / jnp.maximum(mask.sum(), 1.0)
