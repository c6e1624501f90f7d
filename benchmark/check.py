"""The comparison that decides ``correct``: the program's first steps
against the float32 reference's (``want``), each number beside its limit;
``base`` is the same reference at the precision the configuration states.

Numbers (limits in the configuration's file, PERF.md says what they were
set from):

  loss_gap         worst step: |loss - reference| / |reference|
  grad_norm_gap    worst leaf of the first gradient as the optimizers got
                   it (dense leaves from Adam's first moment, the rows as
                   one leaf from the growth of g2sum): the gap between the
                   two norms over the reference's norm of that leaf or of
                   the median leaf, whichever is larger
  update_norm_gap  the same on each leaf's change after the last step
  grad_diff        worst dense leaf of the first gradient: the norm of the
                   difference from the reference's leaf over that leaf's
                   norm (or the median leaf's): a gradient of the right
                   size in the wrong direction
  row_step_diff    norm of (program's - reference's) embedding columns of
                   the touched rows after the first step, over the norm of
                   the reference's change of them in that step: the first
                   gradient of the rows as one large leaf, before three
                   steps of a lively optimizer amplify anything.  It
                   swings twofold from seed to seed, and with it whatever
                   a lower precision reads, so it has no limit of its own:
  row_step_excess  row_step_diff over the row_step_diff that the stated
                   precision itself reads on the same seed (``base``: the
                   reference with bfloat16 products).  The sound program
                   reads 1.00, float8 four and more: this is the number a
                   lower precision fails
  row_diff         row_step_diff's like after the last step, over the
                   reference's change in all three
  counter_gap      largest difference in show or click of any touched row
                   (whole numbers: the limit is 0)

On one seed in thirty or so the seeded optimizer amplifies any rounding
three- to tenfold within the three steps, in the program and in ``base``
alike.  So the limit of loss_gap, grad_norm_gap, update_norm_gap,
grad_diff and row_diff on a seed is the configuration's, or three times
what ``base`` itself reads there, whichever is larger: the program is not
asked to lie closer to float32 than the precision it states.
"""

from __future__ import annotations

import numpy as np


def leaf_norms(tree) -> list:
    import jax

    return [float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
            for x in jax.tree.leaves(tree)]


def first_device(tree, chips: int):
    """A one-chip trainer's tree as it is; the first device's copy of a
    multi-chip trainer's stacked [devices, ...] tree."""
    if chips == 1:
        return tree
    import jax

    return jax.tree.map(lambda x: np.asarray(x[0]), tree)


def adam_mu(opt_state):
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam first moment in the optimizer state")


def seeded_adam_state(opt_state, seeded: dict):
    """The program's optimizer state with Adam's step count and second
    moment set as a job some passes old has them (first moment zero): the
    first updates are then smooth in the gradient.  From a fresh state
    Adam's first step is lr * sign(g), which turns rounding noise in
    near-zero gradients into whole +-lr differences and makes every later
    number of the comparison swing (PERF.md section 2)."""
    import jax
    import jax.numpy as jnp

    out = []
    for part in opt_state:
        if hasattr(part, "mu"):
            part = part._replace(
                count=jnp.asarray(seeded["adam_count"], part.count.dtype),
                nu=jax.tree.map(
                    lambda x: jnp.full_like(x, seeded["adam_nu"]), part.nu))
        out.append(part)
    return tuple(out)


def same_structure(program_params, params) -> None:
    import jax

    a = [(p, np.shape(x)) for p, x in
         jax.tree_util.tree_flatten_with_path(program_params)[0]]
    b = [(p, np.shape(x)) for p, x in
         jax.tree_util.tree_flatten_with_path(params)[0]]
    if a != b:
        raise SystemExit(
            "the reference's parameter tree is not the program's:\n"
            f"  program   {a}\n  reference {b}")


def _worst_leaf_gap(got: list, want: list) -> float:
    if len(got) != len(want):
        raise ValueError(f"{len(got)} leaves against {len(want)}")
    floor = float(np.median(want))
    return max(abs(g - w) / max(w, floor, 1e-30) for g, w in zip(got, want))


def _worst_leaf_diff(got: list, want: list) -> float:
    """Worst leaf of |got - want| over the reference leaf's norm (or the
    median leaf's, whichever is larger)."""
    norms = [float(np.linalg.norm(np.asarray(w, np.float64))) for w in want]
    floor = float(np.median(norms))
    return max(
        float(np.linalg.norm(np.asarray(g, np.float64) - w))
        / max(n, floor, 1e-30) for g, w, n in zip(got, want, norms))


def _rows_diff(got, want, first) -> float:
    """Norm of the embedding columns' difference over the norm of the
    reference's change of them."""
    emb = slice(2, -1)
    return float(
        np.linalg.norm(got[:, emb].astype(np.float64) - want[:, emb])
        / np.linalg.norm(want[:, emb].astype(np.float64) - first[:, emb]))


SEED_SCALED = ("loss_gap", "grad_norm_gap", "update_norm_gap", "grad_diff",
               "row_diff")


def _numbers(got: dict, want: dict) -> dict:
    return {
        "loss_gap": max(abs(g - w) / abs(w)
                        for g, w in zip(got["loss"], want["loss"])),
        "grad_norm_gap": _worst_leaf_gap(got["grad_norms"],
                                         want["grad_norms"]),
        "update_norm_gap": _worst_leaf_gap(got["update_norms"],
                                           want["update_norms"]),
        "grad_diff": _worst_leaf_diff(got["grads"], want["grads"]),
        "row_step_diff": _rows_diff(got["step1_rows"], want["step1_rows"],
                                    want["first_rows"]),
        "row_diff": _rows_diff(got["final_rows"], want["final_rows"],
                               want["first_rows"]),
        "counter_gap": float(np.max(np.abs(
            got["final_rows"][:, :2].astype(np.float64)
            - want["final_rows"][:, :2]))),
    }


def compare(got: dict, want: dict, base: dict, limits: dict | None) -> list:
    if not np.array_equal(got["touched_keys"], want["touched_keys"]):
        raise ValueError("program and reference touched different keys")
    values, stated = _numbers(got, want), _numbers(base, want)
    values["row_step_excess"] = values["row_step_diff"] / max(
        stated["row_step_diff"], 1e-30)
    if limits is None:  # a probe reads every number
        limits = dict.fromkeys(values, float("inf"))
    if set(limits) - set(values):
        raise ValueError(f"no such number: {sorted(set(limits) - set(values))}")
    out = []
    for name, value in values.items():
        if name not in limits:  # the configuration is not held to it
            continue
        limit = limits[name]
        if name in SEED_SCALED:
            limit = max(limit, 3.0 * stated[name])
        ok = bool(np.isfinite(value) and value <= limit)
        out.append({"name": name, "value": float(value), "limit": limit,
                    "ok": ok})
    return out
