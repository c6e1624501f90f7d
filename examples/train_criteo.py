#!/usr/bin/env python
"""Criteo CTR-DNN end to end: raw TSV -> convert -> train -> AUC.

With real Criteo data (day_0, day_1, ... or train.txt, optionally .gz):

    python examples/train_criteo.py --input day_0 --passes 2

Without (this environment has no network, so the real files cannot be
fetched): a spec-exact synthetic sample is generated first (real FORMAT, synthetic
VALUES, planted learnable signal), so the full path — Criteo TSV parse,
categorical hashing, log1p dense transform, native slot parse, pass loop,
AUC — runs and is measured either way:

    python examples/train_criteo.py --lines 8192 --passes 3

Reference analog: the dist-CTR e2e tier (ctr_dataset_reader.py), which
downloads its click data at test time; the model/feature recipe here is
the published Criteo one (26 hashed categorical + 13 log1p ints).
"""

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--input", nargs="*", default=None,
                    help="real Criteo TSV file(s); omit to synthesize")
    ap.add_argument("--lines", type=int, default=8192,
                    help="synthetic sample size when no --input")
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=2048)
    ap.add_argument("--emb", type=int, default=8)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds")
    args = ap.parse_args()
    if args.passes < 1:
        ap.error("--passes must be >= 1")

    from paddlebox_tpu.utils.backend import setup_backend

    setup_backend(cpu=args.cpu)

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.criteo import (
        CRITEO_N_CAT,
        CRITEO_N_DENSE,
        convert_criteo_files,
        criteo_feed_config,
        write_criteo_format_sample,
    )
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    with tempfile.TemporaryDirectory() as td:
        inputs = args.input
        kind = "real"
        if not inputs:
            kind = "criteo-format synthetic"
            inputs = [write_criteo_format_sample(
                os.path.join(td, "sample.tsv"), n_lines=args.lines)]
        t0 = time.perf_counter()
        shards = convert_criteo_files(inputs, os.path.join(td, "slots"),
                                      batch_size=args.batch_size)
        t_conv = time.perf_counter() - t0
        conf = criteo_feed_config(args.batch_size)
        ds = PadBoxSlotDataset(conf, read_threads=4)
        ds.set_filelist(shards)
        t0 = time.perf_counter()
        ds.load_into_memory()
        t_parse = time.perf_counter() - t0

        tconf = SparseTableConfig(embedding_dim=args.emb)
        model = CtrDnn(CRITEO_N_CAT, tconf.row_width,
                       dense_dim=CRITEO_N_DENSE, hidden=(512, 256, 128))
        table = SparseTable(tconf, seed=0)
        trainer = Trainer(model, tconf,
                          TrainerConfig(auc_buckets=1 << 16), seed=0)
        m = None
        t_train = 0.0
        for p in range(args.passes):
            table.begin_pass(ds.unique_keys())
            t0 = time.perf_counter()
            m = trainer.train_from_dataset(
                ds, table, auc_state=trainer.last_metric_state)
            t_train += time.perf_counter() - t0
            table.end_pass()
            print(f"pass {p}: loss={m['loss']:.4f} auc={m['auc']:.4f} "
                  f"count={m['count']:.0f}")
        n_total = int(m["count"])
        ds.close()
        print(f"data: {kind}")
        print(f"convert: {t_conv:.2f}s  parse: {t_parse:.2f}s  "
              f"features: {table.n_features:,}")
        print(f"train: {n_total} samples in {t_train:.2f}s = "
              f"{n_total / t_train:,.0f} samples/s  final AUC {m['auc']:.4f}")


if __name__ == "__main__":
    main()
