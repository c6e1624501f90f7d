"""chip_smoke.py's legs at toy sizes on the CPU mesh, and its refusal to run
without a chip.  The chip run itself is the chip tool's: this pins that the
legs stay importable functions of the sizes and keep passing their own
assertions."""

import os
import subprocess
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = chip_smoke.Sizes(
    slots=4, dense=3, emb=4, hidden=(16, 8), batch=32, vocab=200, steps=4,
    passes=3, requests=(3, 8, 20, 32), small_bucket=8,
)


@pytest.fixture(scope="module")
def toy_data(tmp_path_factory):
    work = str(tmp_path_factory.mktemp("smoke"))
    conf, files, ds = chip_smoke.make_dataset(TOY, work)
    yield work, conf, files, ds
    ds.close()


def test_train_serve_and_sparse_ops_legs(toy_data):
    work, conf, files, ds = toy_data
    report, model, table, trainer = chip_smoke.leg_train(TOY, ds)
    assert [p["steps"] for p in report["passes"]] == [TOY.steps] * TOY.passes
    assert report["passes"][0]["compiles"]  # the listener is live
    serve = chip_smoke.leg_serve(TOY, conf, files, model, table, trainer,
                                 work)
    assert serve["served"]["count"] == sum(TOY.requests)
    ops = chip_smoke.leg_sparse_ops(
        TOY, report["passes"][-1]["capacity_rows"], report["row_width"])
    assert ops["K"] == TOY.key_capacity(TOY.batch)
    table.close()


def test_attention_leg_at_toy_shapes():
    """The leg's table at a toy length; on the CPU both forms are the
    strips, so the leg's own distance check reads 0."""
    toy = {name: (1, 128, 8, hkv * 8 // h, d // 8, dv // 8)
           for name, (_, _, h, hkv, d, dv) in chip_smoke.ATTENTION.items()}
    report = chip_smoke.leg_attention(toy, block_q=32, repeats=1)
    assert list(report) == list(chip_smoke.ATTENTION)
    for line in report.values():
        assert line["form"] == "strips"
        assert set(line["gap"]) == {"out", "dq", "dk", "dv"}
        assert max(line["gap"].values()) == 0.0


def test_four_chip_leg_on_four_of_the_fake_devices(toy_data):
    """The leg's own assertions are the test: 4 shards of values/g2sum on
    4 distinct devices, each shard's cache rows on that shard's device,
    the hot block replicated on all 4, no steady-state compile."""
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    _, _, _, ds = toy_data
    rep = chip_smoke.leg_four_chips(TOY, ds)
    assert rep["devices"] == 4
    for arm in ("hash", "hybrid"):
        assert rep[arm]["cache_shards"] == 4
    assert rep["hash"]["hot_rows"] == 0 and rep["hybrid"]["hot_rows"] > 0


def test_result_line_has_exactly_the_keys_the_chip_check_reads():
    line = chip_smoke.result_line(jax.devices())
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_script_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""  # no result line
    assert "tpu" in r.stderr.lower()
