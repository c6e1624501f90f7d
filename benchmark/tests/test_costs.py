"""costs.py against numbers worked by hand for one shape."""

import pytest

from benchmark import costs
from benchmark.models import ctr_dnn, xdeepfm


def test_sparse_step_by_hand():
    # 1000 distinct keys, row [show, click, 16 embed] + g2sum = 19 floats:
    # read once, written once
    c = costs.sparse_step(1000, 18)
    assert c["bytes"] == 2 * 1000 * 19 * 4 == 152000
    assert c["flops"] == 8 * 1000 * 18


def test_mlp_by_hand():
    # one layer 10 -> 4, batch 8: 2*8*10*4 = 640 flops forward, 1920 trained;
    # 44 weights * 4 B * 9 touches = 1584 B; activations 2*8*14*4 = 896 B
    c = costs.mlp_train(8, [10, 4])
    assert c["flops"] == 1920
    assert c["bytes"] == 1584 + 896


def test_cin_layer_by_hand():
    # B=2, H=3, H_prev=5, m=7, D=11: 2*2*3*5*7*11 = 4620 forward
    c = costs.cin_layer_train(2, 3, 5, 7, 11)
    assert c["flops"] == 3 * 4620
    assert c["bytes"] == 105 * 4 * 9 + 2 * 2 * 11 * (3 + 5 + 7) * 4


def test_models_add_their_parts():
    cfg = {"batch_size": 2048, "n_sparse_slots": 26, "embedding_dim": 16,
           "dense_dim": 13, "hidden": [512, 256, 128]}
    c = ctr_dnn.step_cost(cfg, 22000.0)
    d_in = 26 * 18 + 13
    tower = 3 * 2 * 2048 * (d_in * 512 + 512 * 256 + 256 * 128 + 128)
    assert c["flops"] == pytest.approx(tower + 8 * 22000 * 18)
    x = dict(cfg, embedding_dim=10, batch_size=4096, hidden=[400, 400],
             cin_layers=[200, 200, 200])
    cx = xdeepfm.step_cost(x, 40000.0)
    cin = 3 * 2 * 4096 * 10 * 26 * (200 * 26 + 200 * 200 + 200 * 200)
    assert cx["flops"] > cin > 0.5 * cx["flops"]  # CIN is most of the work


def test_peaks_and_roofline():
    peaks = costs.load_peaks("TPU v5 lite")
    assert peaks["matmul_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.load_peaks("TPU v9 imaginary")
    t, bound = costs.roofline_seconds({"flops": 197e12, "bytes": 1.0}, peaks)
    assert (t, bound) == (1.0, "flops")
    t, bound = costs.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peaks)
    assert (t, bound) == (1.0, "bytes")
