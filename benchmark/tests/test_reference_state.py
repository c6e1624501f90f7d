"""What the reference step holds on the device: ``make_step`` donates its
state, ``run_steps`` owns what it donates.  Donation may change the
lowered step's aliasing and nothing else, so the donated step is held to
the undonated one bit for bit, at toy size, for a pooled CTR reference and
for references with a ``loss`` of their own, in all three precisions."""

import contextlib
import gc
import importlib
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run
from benchmark.reference import common
from benchmark.tests import test_kanana2_cpu, test_mellum2_cpu
from benchmark.tests.toy import seq_cell, toy_cell

CELLS = {
    "ctr_dnn": lambda mp: toy_cell("ctr_dnn_criteo"),
    "seq_cell": seq_cell,
    "mellum2": lambda mp: test_mellum2_cpu.toy_cell(),
    "kanana2": lambda mp: test_kanana2_cpu.toy_cell(),
}
SEED = 2 ** 31 + 33


@contextlib.contextmanager
def seeded(cell):
    """(reference module, run_steps' arguments after it) of ``cell``, as a
    run hands them over: the parameters a host tree."""
    cfg = cell.cfg
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    with contextlib.ExitStack() as stack:
        work = stack.enter_context(tempfile.TemporaryDirectory())
        data = run.prepare_data(cell, SEED, work, stack, n_passes=1)
        params, rows0 = run.seeded_weights(cell, SEED, data.all_keys)
        yield ref, (cfg, jax.tree.map(np.asarray, params), data.all_keys,
                    rows0, data.step_data, run.key_capacity(cfg))


def first_step_args(steps):
    """A fresh set of the first step's arguments, on the device, as
    ``run_steps`` builds them."""
    cfg, params0, table_keys, table_rows, batches, capacity = steps
    uniq, batch = common.batch_arrays(batches[0], capacity, table_keys)
    rows = np.zeros((capacity, table_rows.shape[1]), np.float32)
    rows[:uniq.shape[0]] = table_rows[batch["key_rank"][:uniq.shape[0]]]
    params = jax.tree.map(jnp.array, params0)
    return (params, jax.tree.map(jnp.zeros_like, params),
            jax.tree.map(lambda x: jnp.full_like(
                x, cfg["seeded_state"]["adam_nu"]), params),
            jnp.asarray(cfg["seeded_state"]["adam_count"], jnp.float32),
            jnp.array(rows), batch)


def nbytes(tree) -> int:
    return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("precision", ["", "bfloat16", "float8"])
@pytest.mark.parametrize("name", list(CELLS))
def test_the_donated_step_equals_the_undonated_step_bit_for_bit(
        monkeypatch, name, precision):
    """Loss, every leaf of params, mu, nu and the gradient, the rows and
    their gradient.  No tolerance here: the CPU's compiler makes the same
    program of both.  The TPU's does not -- with the outputs aliased it
    schedules and places the model half otherwise, and the two differ in
    their last bits (PERF.md section 6, PR 33) -- so this pins the
    arithmetic as lowered, not a backend's rounding."""
    with seeded(CELLS[name](monkeypatch)) as (ref, steps):
        step = common.make_step(ref, steps[0], common.Ops(precision))
        args = first_step_args(steps)
        want = jax.jit(step.__wrapped__)(*args)
        assert not any(x.is_deleted() for x in jax.tree.leaves(args[:5]))
        got = step(*args)
        assert all(x.is_deleted() for x in jax.tree.leaves(args[:5]))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    assert float(got[5]) > 0.0 and any(  # and it is a step: a gradient
        np.any(np.asarray(x) != 0.0) for x in jax.tree.leaves(got[6]))


@pytest.mark.parametrize("name", ["ctr_dnn", "mellum2"])
def test_the_compiled_step_aliases_its_state(monkeypatch, name):
    """``memory_analysis`` of the step as compiled here: parameters and
    both moments come back in the buffers they came in, so what a call
    holds is four copies (the fourth the gradient) beside rows and batch."""
    with seeded(CELLS[name](monkeypatch)) as (ref, steps):
        args = first_step_args(steps)
        m = common.make_step(ref, steps[0], common.Ops()).lower(
            *args).compile().memory_analysis()
    if m is None:
        pytest.skip("this backend reports no memory analysis")
    copy, rows, batch = nbytes(args[0]), nbytes(args[4]), nbytes(args[5])
    assert m.alias_size_in_bytes >= 3 * copy + rows
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)
    # the rows and their gradient (narrower than the rows), t, the loss,
    # and what the compiler pads a buffer by
    pad = 64 * 2 * len(jax.tree.leaves(args))
    assert held <= 4 * copy + 2 * rows + batch + pad


@pytest.mark.parametrize("given", ["numpy", "device"])
def test_run_steps_leaves_the_callers_params_and_none_of_its_own(given):
    with seeded(toy_cell("ctr_dnn_criteo")) as (ref, steps):
        cfg, params0, *rest = steps
        params = params0 if given == "numpy" else jax.tree.map(
            jnp.array, params0)
        kept = jax.tree.map(np.array, params0)
        gc.collect()
        before = {id(x) for x in jax.live_arrays()}
        out = common.run_steps(ref, cfg, params, *rest)
        gc.collect()
        left = [x.shape for x in jax.live_arrays() if id(x) not in before]
        assert left == []
        for p, k in zip(jax.tree.leaves(params), jax.tree.leaves(kept)):
            assert given == "numpy" or not p.is_deleted()
            assert np.array_equal(np.asarray(p), k)
        # and it did train: the state it returns is the host's
        assert max(out["update_norms"]) > 0.0 and all(
            isinstance(g, np.ndarray) for g in out["grads"])
        again = common.run_steps(ref, cfg, params, *rest)
    assert again["loss"] == out["loss"]
    assert again["update_norms"] == out["update_norms"]


def test_the_probe_holds_no_copy_beside_run_steps_own():
    """limits_probe at toy size, the control's arm alone: it reads float8
    as not correct, and when it is done nothing is left on the device."""
    from benchmark.tests import limits_probe

    gc.collect()
    before = {id(x) for x in jax.live_arrays()}
    recs = limits_probe.probe("toy", [SEED], 0, require_chip=False,
                              cell=toy_cell("ctr_dnn_criteo"))
    gc.collect()
    assert [x.shape for x in jax.live_arrays() if id(x) not in before] == []
    assert [r["arm"] for r in recs] == [limits_probe.CONTROL]
    assert recs[0]["row_step_excess"] > 2.5 and recs[0]["counter_gap"] == 0.0
