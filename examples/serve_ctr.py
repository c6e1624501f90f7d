#!/usr/bin/env python
"""Train -> export -> serve: the serving-side story end to end.

The reference ships a C++ AnalysisPredictor + HTTP/Go/R clients
(/root/reference/paddle/fluid/inference/); here the equivalent loop is a
few lines over the exported StableHLO artifact: the packaged
``ScoringServer`` (inference/server.py — POST /score with slot-text
lines, /healthz, multi-model routing), driven end to end.

    python examples/serve_ctr.py            # train + export + demo request
    python examples/serve_ctr.py --port 0   # pick a free port and stay up
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_artifact(work: str) -> tuple[str, "object"]:
    """Quick synth training run, then export; returns (artifact_dir, conf)."""
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import export_model
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    S, DENSE, B = 4, 4, 32
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B, max_feasigns_per_ins=16
    )
    files = write_synth_files(
        os.path.join(work, "data"), n_files=2, ins_per_file=512,
        n_sparse_slots=S, vocab_per_slot=1000, dense_dim=DENSE, seed=1,
    )
    ds = PadBoxSlotDataset(conf, read_threads=2)
    ds.set_filelist(files)
    ds.load_into_memory()
    tconf = SparseTableConfig(embedding_dim=8)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(64, 32))
    table = SparseTable(tconf)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 16))
    table.begin_pass(ds.unique_keys())
    metrics = trainer.train_from_dataset(ds, table)
    table.end_pass()
    print(f"trained: auc={metrics['auc']:.4f}")
    art = os.path.join(work, "artifact")
    kcap = conf.batch_key_capacity or (B * conf.max_feasigns_per_ins)
    export_model(
        model, trainer.params, table, art,
        batch_size=B, key_capacity=kcap, dense_dim=DENSE,
        feed_conf=conf,  # self-contained artifact: serving needs no config
    )
    ds.close()
    return art, conf


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=None,
                    help="serve forever on this port (0 = pick free)")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds")
    args = ap.parse_args()

    from paddlebox_tpu.utils.backend import setup_backend

    setup_backend(cpu=args.cpu)

    from paddlebox_tpu.data.synth import write_synth_files
    from paddlebox_tpu.inference import ScoringServer

    work = tempfile.mkdtemp(prefix="pbox_serve_")
    art, _conf = build_artifact(work)  # feed schema rides IN the artifact
    server = ScoringServer()
    server.register("ctr", art)  # feed schema comes from the artifact
    port = server.start(port=args.port or 0)
    print(f"serving on http://127.0.0.1:{port}/score "
          f"(also /score/ctr, /healthz, /models)")

    if args.port is None:
        # demo mode: fire one request against ourselves, print, exit
        import urllib.request

        demo_files = write_synth_files(
            os.path.join(work, "demo"), n_files=1, ins_per_file=8,
            n_sparse_slots=4, vocab_per_slot=1000, dense_dim=4, seed=9,
        )
        with open(demo_files[0], "rb") as f:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/score", data=f.read(), method="POST"
            )
        with urllib.request.urlopen(req, timeout=30) as resp:
            print("scores:", json.load(resp)["scores"])
        server.stop()
    else:
        server.wait()  # foreground until killed


if __name__ == "__main__":
    main()
