"""In-batch sampled-softmax trainer for two-tower retrieval models.

A :class:`~paddlebox_tpu.train.trainer.Trainer` whose fused step swaps
the pointwise logloss for the standard in-batch negative objective:
``sim = user @ item.T / temperature``, each clicked instance's own item
is its positive (the diagonal) and every other REAL instance's item in
the batch is a negative — cross-entropy over the batch's item columns,
weighted to clicked rows.  Everything else — pull_rows admission,
push_and_update scatter, per-slot participation gating, counter
updates, AUC state, grad-norm stream, nan policies — is the ranking
step's plumbing verbatim, so ``train_from_dataset`` and the
multi-scenario interleave drive it unchanged.

AUC here reads the diagonal score through a sigmoid: clicked pairs
should outscore unclicked ones, so the familiar per-scenario AUC stream
still says whether the retrieval tower is learning.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from paddlebox_tpu.metrics.auc import update_auc_state
from paddlebox_tpu.sparse.table import pull_rows, push_and_update
from paddlebox_tpu.telemetry.compiles import counted_jit
from paddlebox_tpu.train import pass_loop
from paddlebox_tpu.train.trainer import Trainer
from paddlebox_tpu.train.slot_policy import slot_participation_vec


class RetrievalTrainer(Trainer):
    """Trainer over a model exposing ``apply_towers`` (models/two_tower)."""

    def __init__(self, model, table_conf, trainer_conf=None, seed: int = 0,
                 metric_group=None, slot_mask=None):
        if not hasattr(model, "apply_towers"):
            raise ValueError(
                "RetrievalTrainer needs a two-tower model exposing "
                "apply_towers(params, rows, key_segments, dense, batch_size)"
            )
        if metric_group is not None:
            raise ValueError(
                "metric groups are per-instance ranking metrics; the "
                "retrieval objective has no per-variant logloss split"
            )
        super().__init__(model, table_conf, trainer_conf, seed=seed,
                         slot_mask=slot_mask)
        if self.n_tasks > 1:
            raise ValueError("retrieval models are single-task")

    def _build_step(self):
        model = self.model
        tconf = self.table_conf
        optimizer = self.optimizer
        check_nan = self._check_nan
        temperature = float(getattr(model, "temperature", 1.0))
        part_vec = slot_participation_vec(
            self.slot_mask, model.n_sparse_slots
        )

        def step(params, opt_state, values, g2sum, mstate, batch):
            rows = pull_rows(
                values, batch["idx"],
                create_threshold=tconf.create_threshold,
                cvm_offset=tconf.cvm_offset,
                pull_embedx_scale=tconf.pull_embedx_scale,
            )
            bsz = batch["labels"].shape[0]
            if part_vec is not None:
                key_part = part_vec[batch["key_segments"] % part_vec.shape[0]]
            else:
                key_part = None

            def loss_fn(p, r):
                if key_part is not None:
                    r = r * key_part[:, None]
                user, item = model.apply_towers(
                    p, r, batch["key_segments"], batch["dense"], bsz
                )
                sim = (user @ item.T) / temperature  # [B, B]
                # negatives are the batch's REAL items only: padding
                # instances' (zero) item vectors must not dilute the
                # softmax denominator
                col_ok = batch["ins_mask"][None, :] > 0
                sim = jnp.where(col_ok, sim, -1e9)
                logp = sim - jax.nn.logsumexp(sim, axis=1, keepdims=True)
                diag = jnp.diagonal(sim)
                # positive pairs: clicked real instances
                w = batch["labels"] * batch["ins_mask"]
                denom = jnp.maximum(w.sum(), 1.0)
                loss = -(jnp.diagonal(logp) * w).sum() / denom
                return loss, jax.nn.sigmoid(diag)

            (loss, preds), (pgrads, row_grads) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True
            )(params, rows)

            updates, opt_state = optimizer.update(pgrads, opt_state, params)
            params = optax.apply_updates(params, updates)
            key_mask = batch["key_mask"]
            key_clicks = batch["key_clicks"]
            key_extras = batch.get("key_extras")
            if key_part is not None:
                key_mask = key_mask * key_part
                key_clicks = key_clicks * key_part
                if key_extras is not None:
                    key_extras = key_extras * key_part[:, None]
            values, g2sum = push_and_update(
                values, g2sum, row_grads, batch["idx"], batch["uniq_idx"],
                batch["inverse"], key_mask, key_clicks, tconf,
                key_extras=key_extras,
                uniq_lr=batch.get("uniq_lr"),
            )
            mstate, finite = pass_loop.step_metrics(
                dict(mstate), batch, loss, preds, preds, pgrads, row_grads,
                n_tasks=1, has_group=False, check_nan=check_nan)
            return params, opt_state, values, g2sum, mstate, loss, finite, preds

        self._step_body = step
        if check_nan and self.conf.nan_policy == "skip_batch":
            body = step

            def guarded(params, opt_state, values, g2sum, mstate, batch):
                out = body(params, opt_state, values, g2sum, mstate, batch)
                new_state, (loss, finite, primary) = out[:5], out[5:]
                old_state = (params, opt_state, values, g2sum, mstate)
                state = jax.lax.cond(
                    finite, lambda _: new_state, lambda _: old_state, None
                )
                return (*state, loss, finite, primary)

            return counted_jit(
                guarded, stage="train.step", donate_argnums=(0, 1, 2, 3, 4))
        return counted_jit(
            step, stage="train.step", donate_argnums=(0, 1, 2, 3, 4))

    def _build_eval_step(self):
        model = self.model
        tconf = self.table_conf
        temperature = float(getattr(model, "temperature", 1.0))

        def step(params, values, auc, batch):
            rows = pull_rows(
                values, batch["idx"],
                create_threshold=tconf.create_threshold,
                cvm_offset=tconf.cvm_offset,
                pull_embedx_scale=tconf.pull_embedx_scale,
            )
            bsz = batch["labels"].shape[0]
            user, item = model.apply_towers(
                params, rows, batch["key_segments"], batch["dense"], bsz
            )
            preds = jax.nn.sigmoid(
                (user * item).sum(axis=1) / temperature
            )
            auc = update_auc_state(auc, preds, batch["labels"],
                                   batch["ins_mask"])
            return auc

        return counted_jit(step, stage="train.eval", donate_argnums=(2,))
