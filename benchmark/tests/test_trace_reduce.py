"""trace_reduce on the checked-in trace (a toy CTR-DNN cell recorded on a
TPU v5e, the run's trace copied out by hand: two traced passes of 8
steps) and on
intervals worked by hand."""

import os

import pytest

from benchmark import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "toy_ctr_dnn.xplane.pb")


def test_union_clip_gaps_covering_by_hand():
    iv = [(0, 10), (5, 12), (20, 30), (25, 26)]
    assert tr.union_seconds(iv) == 22
    assert tr.union_seconds([]) == 0
    assert tr.clip(iv, 8, 22) == [(8, 10), (8, 12), (20, 22)]
    assert tr.gaps(iv, -5, 40) == [(-5, 0), (12, 20), (30, 40)]
    assert tr.gaps([], 0, 3) == [(0, 3)]
    spans = [("train", 0, 100), ("inner", 10, 20), ("end_pass", 100, 130)]
    assert tr.covering(spans, 15) == "inner"
    assert tr.covering(spans, 50) == "train"
    assert tr.covering(spans, 120) == "end_pass"
    assert tr.covering(spans, 500) == "none"


def test_recorded_trace_reduces_to_the_recorded_numbers():
    r = tr.reduce(TRACE, n_devices=1)
    assert r["n_ops"] == 2660
    assert r["busy_s"] == pytest.approx(0.001632464, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.163923334, rel=1e-9)
    assert r["step_busy_s"] == pytest.approx(0.001525849, rel=1e-9)
    assert 0 < r["step_busy_s"] <= r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0.0
    assert len(r["top_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert r["top_ops"][0][0].startswith("%fusion.9 = u32[1048576]")
    assert all(len(n) <= tr.NAME_CHARS for n, _ in r["top_ops"])
    secs = [t for _, t in r["top_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert {n for n, _ in r["idle_gaps"]} <= {
        "begin_pass", "train", "end_pass", "none"}
    gaps = [t for _, t in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_spans_of_two_traced_passes():
    import jax

    spans = tr.bench_spans(jax.profiler.ProfileData.from_file(TRACE))
    assert [n for n, _, _ in spans] == [
        "begin_pass", "train", "end_pass"] * 2
    assert all(e > s for _, s, e in spans)


def test_a_cell_with_more_chips_than_the_trace_is_refused():
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce(TRACE, n_devices=4)
