"""Plain float32 reference of one training step, shared by the models.

Nothing here imports the program.  A step is written the way the system's
documents state it, with no plan, no dedup of the gather and no fusion:

  pull      every key occurrence reads its own copy of its row
  model     the model module's ``loss(cfg, ops, params, rows_occ, batch)``
            where it defines one (``batch_arrays`` says what ``batch``
            holds and in which order); else the three lines every pooled
            CTR model shares (``pooled_loss``):
  pool      sum over the occurrences of one (instance, slot); the pooled
            show and click become [log(show+1), log(click+1)-log(show+1)]
            and carry no gradient (ops/seqpool_cvm.py's default layout)
  tower     the model module's ``logits``
  loss      mean sigmoid cross-entropy over the batch
  dense     Adam (optax defaults: b1 0.9, b2 0.999, eps 1e-8, no decay)
  sparse    per distinct key: gradients of its occurrences summed, then
            g <- clip(g, +-c); g2sum += mean(g*g);
            w -= lr * sqrt(g0 / (g0 + g2sum)) * g   (sparse/optimizer.py),
            show += occurrences, click += clicked occurrences

All matmuls run under ``jax.default_matmul_precision("highest")``: on a TPU
a float32 product is otherwise a single bfloat16 pass.  Shapes are padded
to the configuration's key capacity so one compiled step serves every batch.

What the check holds on the device is what a trainer holds: the step
donates its state (``make_step``) and ``run_steps`` keeps nothing else
there, so a model's reference has the chip less 16 bytes a dense parameter
(parameters, gradient, Adam's ``mu`` and ``nu``) for its own temporaries.
A new ``loss`` is sized by that rule: rematerialise (``jax.checkpoint``)
what a sequence, a layer, a head or a block of logits would otherwise
keep for the backward pass, as mellum2.py and kanana2.py do, and read
the compiled step's ``memory_analysis()`` for a described chip before the
first run (PERF.md section 4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def xavier(key, in_dim: int, out_dim: int) -> dict:
    bound = float(np.sqrt(6.0 / (in_dim + out_dim)))
    return {
        "w": jax.random.uniform(key, (in_dim, out_dim), jnp.float32,
                                -bound, bound),
        "b": jnp.zeros((out_dim,), jnp.float32),
    }


def init_mlp(key, in_dim: int, hidden, out_dim: int) -> list:
    dims = [in_dim, *hidden, out_dim]
    keys = jax.random.split(key, len(dims) - 1)
    return [xavier(k, dims[i], dims[i + 1]) for i, k in enumerate(keys)]


def _rounded(x, dtype):
    """``x`` rounded to ``dtype``.  float8 rounds under a per-tensor scale
    that puts the largest magnitude at the type's largest finite value
    (the scaling every float8 training recipe uses: without it the loss's
    cotangent, 1/batch, is under float8's smallest subnormal and the whole
    backward pass flushes to zero); bfloat16 has float32's range and
    needs none."""
    if dtype == jnp.bfloat16:
        # reduce_precision and not astype there and back: a compiler that
        # allows excess precision may drop such a pair of converts
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_operand(x, dtype):
    return _rounded(x, dtype)


_round_operand.defvjp(
    lambda x, dtype: (_rounded(x, dtype), None),
    lambda dtype, _, g: (g,))  # the cotangent passes straight through


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(y, dtype):
    return y


_round_cotangent.defvjp(
    lambda y, dtype: (y, None),
    lambda dtype, _, g: (_rounded(g, dtype),))


class Ops:
    """The products of a step, in one of three precisions.

    ``Ops()`` is the reference: float32 operands, ``highest`` precision.

    ``Ops("bfloat16")`` is the precision the configurations state,
    written out: every product's operands, and on the way back the
    cotangent of every product's result, rounded to bfloat16, sums in
    float32 -- what a float32 product at the TPU's default precision does.
    How far it lies from the reference on a seed is the yardstick the
    program's own distance is measured in (check.py ``row_step_excess``).

    ``Ops("float8")`` is the control of the output check, the nearest
    precision below, as float8 training is done: operands rounded to
    float8_e4m3fn and cotangents to float8_e5m2, each under a per-tensor
    scale, sums in float32.  So all three products of a layer (forward,
    input gradient, weight gradient) have float8 operands, and the
    backward pass survives."""

    _TYPES = {"bfloat16": (jnp.bfloat16, jnp.bfloat16),
              "float8": (jnp.float8_e4m3fn, jnp.float8_e5m2)}

    def __init__(self, precision: str = ""):
        if precision and precision not in self._TYPES:
            raise ValueError(f"unknown precision {precision!r}")
        self.types = self._TYPES.get(precision)

    def _product(self, f, xs):
        if self.types is None:
            return f(*xs)
        operand, cotangent = self.types
        y = f(*[_round_operand(x, operand) for x in xs])
        return _round_cotangent(y, cotangent)

    def dot(self, a, b):
        return self._product(jnp.matmul, (a, b))

    def einsum(self, spec: str, *xs):
        return self._product(lambda *q: jnp.einsum(spec, *q), xs)


def mlp(ops: Ops, layers: list, x):
    for layer in layers[:-1]:
        x = jax.nn.relu(ops.dot(x, layer["w"]) + layer["b"])
    return ops.dot(x, layers[-1]["w"]) + layers[-1]["b"]


def bce(logits, labels):
    return (jnp.maximum(logits, 0.0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def pooled_features(rows_occ, seg, batch: int, n_slots: int):
    """[K, 2+D] occurrence rows -> [B, S, 2+D]: counters through the log
    transform (no gradient), embeddings summed."""
    pooled = jax.ops.segment_sum(rows_occ, seg,
                                 num_segments=batch * n_slots + 1)
    pooled = pooled[: batch * n_slots].reshape(batch, n_slots, -1)
    show = jax.lax.stop_gradient(pooled[..., 0:1])
    click = jax.lax.stop_gradient(pooled[..., 1:2])
    log_show = jnp.log(show + 1.0)
    return jnp.concatenate(
        [log_show, jnp.log(click + 1.0) - log_show, pooled[..., 2:]], axis=-1)


def batch_arrays(data, capacity: int, table_keys: np.ndarray):
    """Host side of one batch (``data``: a PassData slice of one step):
    its distinct keys (sorted uint64, stay on the host) and ``batch``, what
    the step and a model's ``loss`` get, arrays padded to ``capacity``.

    One entry per key occurrence, in (instance, slot, position in slot)
    order -- the file's order, which ``np.nonzero`` yields row-major:

      ins, slot, pos  the occurrence's instance, slot and place inside
                      that slot's keys (padding: B, S, 0)
      seg             ins * S + slot (padding: B * S, the overflow segment)
      inv             which distinct key (padding: capacity - 1, a zero row)
      mask            1.0 for an occurrence, 0.0 for padding
      click           its instance's label (the click counter's increment)

    per distinct key: ``key_rank``, its rank among the table's sorted keys
    (a per-position loss over the vocabulary takes its classes from it;
    padding: -1), and their count ``n_keys``; per instance: ``labels``,
    ``dense`` and, where the pass has them, ``task_labels`` [B, n]."""
    keys = data.keys
    B, S, _ = keys.shape
    ins, slot, k = np.nonzero(keys)
    uniq, inv = np.unique(keys[ins, slot, k], return_inverse=True)
    n_occ, n_uniq = ins.shape[0], uniq.shape[0]
    if n_occ > capacity:
        raise ValueError(f"{n_occ} keys in a batch of capacity {capacity}")
    rank = np.searchsorted(table_keys, uniq)
    if not np.array_equal(table_keys[np.minimum(rank, len(table_keys) - 1)],
                          uniq):
        raise ValueError("a batch key has no initial row")
    seg = ins * S + slot

    def padded(values, fill, dtype=np.int32, n=n_occ):
        out = np.full(capacity, fill, dtype)
        out[:n] = values
        return out

    batch = {
        "ins": padded(ins, B), "slot": padded(slot, S),
        # seg ascends, so searchsorted finds each run's first occurrence
        "pos": padded(np.arange(n_occ) - np.searchsorted(seg, seg), 0),
        "seg": padded(seg, B * S), "inv": padded(inv, capacity - 1),
        "mask": padded(1.0, 0.0, np.float32),
        "click": padded(data.labels[ins], 0.0, np.float32),
        "key_rank": padded(rank, -1, n=n_uniq),
        "n_keys": np.int32(n_uniq),
        "labels": data.labels, "dense": data.dense,
    }
    if data.task_labels is not None:
        batch["task_labels"] = data.task_labels
    return uniq, batch


def pooled_loss(model, cfg: dict, ops: Ops, params, rows_occ, batch: dict):
    """The model half of the step for a module with ``logits`` only."""
    feats = pooled_features(rows_occ, batch["seg"], batch["B"], batch["S"])
    logits = model.logits(cfg, ops, params, feats, batch["dense"])
    return bce(logits, batch["labels"]).mean()


def make_step(model, cfg: dict, ops: Ops):
    """The jitted reference step for ``model``, a reference module with
    ``loss(cfg, ops, params, rows_occ, batch)`` -> scalar, or with
    ``logits(cfg, ops, params, feats, dense)`` under ``pooled_loss``.
    ``rows_occ`` [capacity, 2 + D] is one row an occurrence, in ``batch``'s
    order; ``batch`` is ``batch_arrays``' with ``B`` and ``S`` as Python
    ints.  Counters and both optimizers are the step's own: a ``loss``
    replaces the model half only.

    The step donates ``params``, ``mu``, ``nu``, ``t`` and ``rows``: Adam's
    update lands in the buffers it read, as a trainer's does, so a caller
    holds four copies of the parameters across a call (the three and the
    gradient that comes back) and hands in arrays that are its own to
    lose.  Donation changes the lowered step's aliasing and nothing of its
    arithmetic (``jax.jit(step.__wrapped__)`` is the same step without it:
    tests/test_reference_state.py holds the two equal bit for bit on the
    CPU; the TPU's compiler schedules the two otherwise, and their results
    differ in the last bits: PERF.md section 6, PR 33)."""
    opt = cfg["optimizers"]
    lr_d, b1, b2, eps = (opt["dense_adam_lr"], opt["dense_adam_b1"],
                         opt["dense_adam_b2"], opt["dense_adam_eps"])
    lr_s, g0, clip = (opt["sparse_adagrad_lr"], opt["sparse_initial_g2sum"],
                      opt["sparse_grad_clip"])
    S = cfg["n_sparse_slots"]
    model_loss = getattr(model, "loss", None) or functools.partial(
        pooled_loss, model)

    def step(params, mu, nu, t, rows, batch):
        batch = dict(batch, B=batch["labels"].shape[0], S=S)
        inv = batch["inv"]
        emb_rows, g2 = rows[:, :-1], rows[:, -1]

        def loss_fn(p, r):
            return model_loss(cfg, ops, p, r[inv], batch)

        loss, (gp, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, emb_rows)
        t = t + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, gp)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, gp)
        params = jax.tree.map(
            lambda p, m, v: p - lr_d * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
        g = jnp.clip(gr[:, 2:], -clip, clip)
        add = jnp.mean(g * g, axis=-1)
        g2 = g2 + add
        emb = emb_rows[:, 2:] - (lr_s * jnp.sqrt(g0 / (g0 + g2)))[:, None] * g
        n_rows = rows.shape[0]
        show = emb_rows[:, 0] + jax.ops.segment_sum(batch["mask"], inv, n_rows)
        click = emb_rows[:, 1] + jax.ops.segment_sum(batch["click"], inv,
                                                     n_rows)
        rows = jnp.concatenate(
            [show[:, None], click[:, None], emb, g2[:, None]], axis=1)
        return params, mu, nu, t, rows, loss, gp, g

    def highest(*a):
        with jax.default_matmul_precision("highest"):
            return step(*a)

    return jax.jit(highest, donate_argnums=(0, 1, 2, 3, 4))


def leaf_norms(tree) -> list:
    return [float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for x in jax.tree.leaves(tree)]


def run_steps(model, cfg: dict, params, table_keys: np.ndarray,
              table_rows: np.ndarray, batches: list, capacity: int,
              precision: str = "") -> dict:
    """Follow ``batches`` (PassData slices of one batch each) from
    ``params`` and the rows ``table_rows`` of sorted ``table_keys``.
    Returns per-step loss, the first step's gradient norm per leaf (dense
    leaves, then the embedding rows as one leaf), the norm of each leaf's
    change after the last step, and the rows of every touched key at the
    start, after the first step and after the last.

    It owns what the step donates: ``params`` (a host or a device tree,
    the caller's and left as it is) is copied to the device, the start is
    kept on the host, and each step's gradient is dropped before the next
    call.  So the device holds the parameters, Adam's two moments and one
    gradient -- 16 bytes a parameter, a trainer's state -- beside the
    model's own temporaries, and nothing of it once this returns: what
    comes back is on the host."""
    step = make_step(model, cfg, Ops(precision))
    params0 = jax.tree.map(np.asarray, params)
    params = jax.tree.map(jnp.array, params0)  # a copy: the step donates it
    seeded = cfg["seeded_state"]  # Adam as a job some passes old has it
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(
        lambda x: jnp.full_like(x, seeded["adam_nu"]), params)
    t = jnp.asarray(seeded["adam_count"], jnp.float32)
    rows_now = {}  # key -> row, for keys a step has touched
    losses, grad_norms, grads = [], None, None
    for b in batches:
        uniq, batch = batch_arrays(b, capacity, table_keys)
        rows = np.zeros((capacity, table_rows.shape[1]), np.float32)
        rows[:uniq.shape[0]] = table_rows[batch["key_rank"][:uniq.shape[0]]]
        for i, k in enumerate(uniq.tolist()):
            r = rows_now.get(k)
            if r is not None:
                rows[i] = r
        params, mu, nu, t, new_rows, loss, gp, g_rows = step(
            params, mu, nu, t, jnp.array(rows), batch)
        new_rows = np.asarray(new_rows)
        for i, k in enumerate(uniq.tolist()):
            rows_now[k] = new_rows[i]
        losses.append(float(loss))
        if grad_norms is None:
            # copies: on the CPU np.asarray is a view that keeps gp alive
            grads = [np.array(x) for x in jax.tree.leaves(gp)]
            grad_norms = leaf_norms(gp) + leaf_norms([g_rows])
            step1 = dict(rows_now)
        del gp, g_rows  # the next call has room for its own
    touched = np.array(sorted(rows_now), dtype=np.uint64)
    final = np.stack([rows_now[k] for k in touched.tolist()])
    first = table_rows[np.searchsorted(table_keys, touched)]
    after_step1 = np.stack([step1.get(k, first[i])
                            for i, k in enumerate(touched.tolist())])
    # leaf by leaf on the host: no second tree on the device
    delta = jax.tree.map(lambda a, b: np.asarray(a) - b, params, params0)
    return {
        "loss": losses,
        "grad_norms": grad_norms,
        "grads": grads,
        "first_rows": first,
        "step1_rows": after_step1,
        "update_norms": leaf_norms(delta)
        + leaf_norms([final[:, 2:-1] - first[:, 2:-1]]),
        "touched_keys": touched,
        "final_rows": final,
    }
