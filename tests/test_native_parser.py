"""Native C++ parser: builds, and produces byte-identical RecordBlocks to the
pure-Python reference implementation on every feature (labels, task labels,
dense, sparse, skip slots, ins_id, logkey, gz, errors)."""

import gzip
import os

import numpy as np
import pytest

from paddlebox_tpu.config import DataFeedConfig, SlotConfig
from paddlebox_tpu.data.slot_parser import SlotParser

pytestmark = pytest.mark.skipif(
    __import__("paddlebox_tpu._native", fromlist=["get_lib"]).get_lib() is None,
    reason="native parser unavailable (no g++?)",
)


def _conf(**kw):
    slots = [
        SlotConfig(name="click", type="float", is_dense=True, shape=(1,)),
        SlotConfig(name="conv", type="float", is_dense=True, shape=(1,)),
        SlotConfig(name="sa", type="uint64"),
        SlotConfig(name="unused", type="uint64", is_used=False),
        SlotConfig(name="sb", type="uint64"),
        SlotConfig(name="dx", type="float", is_dense=True, shape=(3,)),
    ]
    return DataFeedConfig(
        slots=slots, label_slot="click", task_label_slots=("conv",), **kw
    )


LINES = [
    "1 1 1 0 2 11 12 1 5 1 21 3 0.1 -0.2 3e-1",
    "1 0 1 1 1 13 0 0 3 0.4 0.5 0.6",
    "1 1 1 0 3 14 15 18446744073709551615 2 9 9 2 22 23 3 -0.7 0.8 0.9",
]


def _both(conf, text):
    p_native = SlotParser(conf)
    native = p_native._native_parser()
    assert native is not None
    got = native.parse_bytes(text.encode())
    p_py = SlotParser(conf)
    want = p_py.parse_lines(text.splitlines())
    return got, want


def _assert_same(got, want):
    assert got.n_ins == want.n_ins
    np.testing.assert_array_equal(got.keys, want.keys)
    np.testing.assert_array_equal(got.key_offsets, want.key_offsets)
    np.testing.assert_allclose(got.dense, want.dense, rtol=1e-6)
    np.testing.assert_allclose(got.labels, want.labels, rtol=1e-6)
    if want.task_labels is None:
        assert got.task_labels is None or got.task_labels.shape[1] == 0
    else:
        np.testing.assert_allclose(got.task_labels, want.task_labels, rtol=1e-6)
    for f in ("search_ids", "ranks", "cmatches"):
        w = getattr(want, f)
        g = getattr(got, f)
        if w is None:
            assert g is None
        else:
            np.testing.assert_array_equal(g, w)
    assert got.ins_ids == want.ins_ids


def test_parity_plain():
    got, want = _both(_conf(), "\n".join(LINES) + "\n")
    _assert_same(got, want)
    # uint64 extremes survive
    assert got.keys.max() == np.uint64(18446744073709551615)


def test_parity_ins_id_logkey():
    conf = _conf(parse_ins_id=True, parse_logkey=True)
    lines = [
        f"id-{i} {1000 + i}:{i % 3}:{222 + (i % 2)} {l}"
        for i, l in enumerate(LINES)
    ]
    got, want = _both(conf, "\n".join(lines) + "\n")
    _assert_same(got, want)


def test_parity_blank_lines_and_no_trailing_newline():
    got, want = _both(_conf(), LINES[0] + "\n\n  \n" + LINES[1])
    _assert_same(got, want)
    assert got.n_ins == 2


def test_native_errors_match_python():
    conf = _conf()
    bad = [
        "1 1 1 0 2 11",  # truncated sparse
        "1 1 1 0 2 11 x 1 5 1 21 3 0.1 0.2 0.3",  # bad feasign
        "2 1 1 0 1 11 1 5 1 21 3 0.1 0.2 0.3",  # label width mismatch
        LINES[0] + " 9 9",  # trailing tokens
    ]
    for line in bad:
        p = SlotParser(conf)
        native = p._native_parser()
        with pytest.raises(ValueError):
            native.parse_bytes((line + "\n").encode())
        with pytest.raises(ValueError):
            SlotParser(conf).parse_lines([line])


def test_gz_and_dataset_path(tmp_path):
    conf = _conf()
    text = "\n".join(LINES) + "\n"
    gz = tmp_path / "part-0.gz"
    with gzip.open(gz, "wt") as f:
        f.write(text)
    block = SlotParser(conf).parse_file(str(gz))
    want = SlotParser(conf).parse_lines(LINES)
    _assert_same(block, want)


def test_empty_input():
    got, want = _both(_conf(), "")
    assert got.n_ins == 0 == want.n_ins


def test_library_is_keyed_by_source_not_by_what_came_with_the_tree(tmp_path):
    """A .so that arrived with the checkout — the old fixed name, or a
    build of another source — is never the one loaded: the library's file
    name is a hash of source + flags, anything else is rebuilt from the
    .cpp and swept away."""
    import shutil

    from paddlebox_tpu import _native

    src = str(tmp_path / "slot_parser.cpp")
    shutil.copy(_native._SRC, src)
    foreign = [tmp_path / "_slot_parser.so",
               tmp_path / "_slot_parser.0123456789abcdef.so"]
    for f in foreign:
        f.write_bytes(b"built on another machine")
    so = _native._build_so(src)
    assert so is not None and os.path.dirname(so) == str(tmp_path)
    assert [f.name for f in tmp_path.glob("*.so")] == [os.path.basename(so)]
    assert _native._build_so(src) == so  # cached: same source, same file
    with open(src, "a") as f:
        f.write("\n// edited\n")
    assert _native._build_so(src) != so  # new source, new library
