"""The harness end to end at toy sizes on the CPU: it skips the look for a
chip and drives the rest of a run.  A CPU run gives counts and ``correct``,
never a metric."""

import jax
import numpy as np
import pytest

from benchmark import run
from benchmark.tests.toy import toy_cell


@pytest.mark.parametrize("config", ["ctr_dnn_criteo", "xdeepfm_criteo"])
def test_toy_run_is_correct_and_reports_no_rate(config):
    r = run.run_cell(toy_cell(config), 2 ** 31 + 3, 1.0, False,
                     require_chip=False)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"] == {}  # no rate under a device metric's name
    assert r["device"]["platform"] == "cpu"
    assert r["counts"]["passes"] == r["attempted"] >= 2
    assert r["counts"]["window_compile_requests"] == 0
    assert {c["name"] for c in r["checks"]} == set(
        toy_cell(config).cfg["limits"])
    assert all(c["ok"] for c in r["checks"])


def test_the_key_space_is_admitted_in_chunks_and_all_of_it_is_resident():
    """table_prefill: every key of the mix's vocabularies has a row in the
    device's row cache when the window opens, whatever the chunking; a
    cache that cannot hold them is refused."""
    import contextlib

    from benchmark import gen

    cell = toy_cell("ctr_dnn_criteo")
    space = gen.key_space(cell.mix, cell.cfg["n_sparse_slots"])
    cell.cfg["table_prefill"] = {"keys": "key_space",
                                 "admission_chunk_keys": 1500}
    with contextlib.ExitStack() as stack:
        keys = run.table_keys(cell, space[::7])
        assert np.array_equal(keys, space)
        _, table, _, _, rows0 = run.fresh_system(
            cell, jax.devices()[:1], 5, keys, stack)
        run.admit(cell, table, keys)
        cache = table._get_cache()
        assert cache.resident == space.shape[0]
        got = np.asarray(cache.gather_rows(cache.lookup(keys).hit_slots))
        assert np.array_equal(got, rows0)
    cell.cfg["hbm_cache_rows"] = space.shape[0] - 1
    with pytest.raises(SystemExit, match="does not fit"):
        run.table_keys(cell, space[::7])


def test_the_measured_path_raises_without_a_chip():
    with pytest.raises((RuntimeError, SystemExit)):
        run.main(["--workload", "ctr_dnn_steady", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])


@pytest.mark.parametrize("config", ["ctr_dnn_criteo", "xdeepfm_criteo"])
def test_control_the_reference_in_float8_is_not_correct(config):
    """The control of PERF.md section 2 at a size a test can hold: the
    reference put in the program's place and computed as float8 training
    is done (scaled, so its backward pass survives), compared as a run
    compares the program."""
    import contextlib
    import importlib
    import tempfile

    from benchmark import check
    from benchmark.reference import common
    from benchmark.tests.limits_probe import CONTROL

    cell = toy_cell(config)
    cfg = cell.cfg
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    with contextlib.ExitStack() as stack:
        work = stack.enter_context(tempfile.TemporaryDirectory())
        data = run.prepare_data(cell, 7, work, stack, n_passes=1)
        params, rows0 = run.seeded_weights(cell, 7, data.all_keys)
        steps = (ref, cfg, jax.tree.map(np.asarray, params), data.all_keys,
                 rows0, data.step_data, run.key_capacity(cfg))
        want = common.run_steps(*steps)
        base = common.run_steps(*steps,
                                precision=cfg["precision"]["products"])
        sound = check.compare(common.run_steps(*steps), want, base,
                              cfg["limits"])
        stated = {c["name"]: c["value"]
                  for c in check.compare(base, want, base, None)}
        control = check.compare(
            common.run_steps(*steps, precision=CONTROL), want, base,
            cfg["limits"])
    assert all(c["ok"] for c in sound)
    got = {c["name"]: c["value"] for c in control}
    over = {c["name"] for c in control if not c["ok"]}
    assert "row_step_excess" in over and "counter_gap" not in over
    assert 0.0 < got["grad_norm_gap"] < 0.9  # the backward pass survived
    # the stated precision is its own yardstick; float8 is well beyond it
    assert stated["row_step_excess"] == 1.0 and got["row_step_excess"] > 2.5


def frozen_step(trainer, table):
    """Break the timed path underneath: a step that computes its loss and
    returns its state unchanged."""
    trainer._build_step()  # sets _step_body
    body = trainer._step_body

    def frozen(params, opt_state, values, g2sum, mstate, batch):
        out = body(params, opt_state, values, g2sum, mstate, batch)
        return (params, opt_state, values, g2sum, mstate) + tuple(out[5:])

    trainer._step_fn = jax.jit(frozen)


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    r = run.run_cell(toy_cell("ctr_dnn_criteo"), 11, 0.5, False,
                     require_chip=False, sabotage=frozen_step)
    assert r["correct"] is False
    over = {c["name"] for c in r["checks"] if not c["ok"]}
    assert {"update_norm_gap", "grad_norm_gap"} <= over


def half_the_batch(trainer, table):
    """Break the feed: the second half of every batch is masked out."""
    real = trainer._build_step()
    body = trainer._step_body

    def halved(params, opt_state, values, g2sum, mstate, batch):
        n = batch["ins_mask"].shape[0]
        batch = dict(batch, ins_mask=batch["ins_mask"].at[n // 2:].set(0.0))
        return body(params, opt_state, values, g2sum, mstate, batch)

    del real
    trainer._step_fn = jax.jit(halved)


def test_a_part_of_the_batch_left_out_is_not_correct():
    r = run.run_cell(toy_cell("ctr_dnn_criteo"), 13, 0.5, False,
                     require_chip=False, sabotage=half_the_batch)
    assert r["correct"] is False
    assert "loss_gap" in {c["name"] for c in r["checks"] if not c["ok"]}


def test_the_sharded_cell_on_four_virtual_devices():
    """make_mesh(4) + ShardedSparseTable + MultiChipTrainer through the
    same harness; the reference follows the group-step as one step over
    the four devices' batches."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (benchmark/tests/conftest.py sets them)")
    r = run.run_cell(
        toy_cell("ctr_dnn_criteo", chips=4, instances_per_pass=512), 31, 0.5,
        False, require_chip=False)
    assert r["correct"] is True and r["device"]["count"] == 4
    assert r["counts"]["steps"] == r["counts"]["passes"] * 4
