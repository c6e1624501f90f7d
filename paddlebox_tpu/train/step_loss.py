"""The model half of the training step, shared by both trainers.

A step is pull -> model -> push, dense optimizer, metrics.  The model half
maps the dense parameters and the pulled occurrence rows to a scalar loss
and one prediction an instance; the trainers differentiate it by both and
own everything else.  ``make_model_loss`` builds it from the model object:

  * a model that defines ``loss(params, rows, batch) -> (loss, preds[B])``
    (or ``(loss, preds, counts)``, see ``counter_names``) supplies it
    whole: a loss the CTR branch cannot express (models/decoder_lm.py: a
    softmax cross-entropy at every position of a sequence).  ``batch`` is
    the step's device feed as the trainer built it;
  * every other model gets today's branch: ``apply`` -> logits -> mean
    sigmoid cross-entropy over the real instances against ``labels`` (or,
    for ``n_tasks`` > 1, against ``task_labels``, averaged over tasks),
    predictions ``sigmoid(logits)``.

A leaf module like train/slot_policy.py: it imports neither trainer, and
the models package only when a step is built (models -> parallel ->
parallel/trainer.py -> here is a cycle at import time).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def counter_names(model) -> tuple:
    """Names of the per-step sums a model's ``loss`` returns as its third
    value (float32 [n]): they ride the donated metric state beside ``gn``
    and are published as telemetry counters at the pass's read-back."""
    return tuple(getattr(model, "step_counters", ()))


def make_model_loss(model, n_tasks: int,
                    mean_axis: Optional[str] = None) -> Callable:
    """``f(params, rows, batch) -> (loss, (preds, counts))``; ``counts`` is
    None for a model without ``step_counters``.

    mean_axis: inside a shard_map whose gradients are psummed over that
    axis, the loss is the mean over the axis' devices too: the default
    branch divides by the psummed count of real instances, a model's own
    ``loss`` (a mean over what this device holds) by the axis size."""
    custom = getattr(model, "loss", None)
    if custom is not None:
        n_out = 2 + bool(counter_names(model))

        def model_loss(params, rows, batch):
            out = custom(params, rows, batch)
            if len(out) != n_out:
                raise ValueError(
                    f"{type(model).__name__}.loss returned {len(out)} values;"
                    f" with step_counters {counter_names(model)} it returns "
                    f"{n_out}: (loss, preds{', counts' * (n_out - 2)})")
            loss = out[0]
            if mean_axis is not None:
                loss = loss / jax.lax.psum(1, mean_axis)
            return loss, (out[1], out[2] if n_out == 3 else None)

        return model_loss

    from paddlebox_tpu.models.layers import bce_with_logits

    uses_rank = getattr(model, "uses_rank_offset", False)
    uses_seq = getattr(model, "uses_seq_pos", False)

    def apply_bce(params, rows, batch):
        bsz = batch["labels"].shape[0]
        extra = {"rank_offset": batch["rank_offset"]} if uses_rank else {}
        if uses_seq:
            extra["seq_pos"] = batch["seq_pos"]
        logits = model.apply(
            params, rows, batch["key_segments"], batch["dense"], bsz, **extra
        )
        mask = batch["ins_mask"]
        count = mask.sum()
        if mean_axis is not None:
            count = jax.lax.psum(count, mean_axis)
        denom = jnp.maximum(count, 1.0)
        if n_tasks > 1:
            # [B, T] logits vs [B, T] task labels; mean over tasks
            per_ins = (
                bce_with_logits(logits, batch["task_labels"]).mean(axis=1)
                * mask
            )
        else:
            per_ins = bce_with_logits(logits, batch["labels"]) * mask
        return per_ins.sum() / denom, (jax.nn.sigmoid(logits), None)

    return apply_bce


def add_counts(mstate: dict, counts) -> dict:
    """Fold a step's ``counts`` into the metric state (a no-op without)."""
    if counts is not None:
        mstate["counters"] = mstate["counters"] + counts.astype(jnp.float32)
    return mstate


def publish_counters(model, now, base) -> dict:
    """At the read-back: the pass's growth of each named sum (``now`` -
    ``base``, host arrays) goes to the telemetry counter of that name and
    into the returned metrics."""
    from paddlebox_tpu import telemetry

    out = {}
    for name, value in zip(counter_names(model), now - base):
        telemetry.counter(name, "per-step sum reported by the model's loss "
                          "(train/step_loss.py)").inc(float(value))
        out[name] = float(value)
    return out
