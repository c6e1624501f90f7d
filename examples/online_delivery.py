#!/usr/bin/env python
"""The online model delivery loop, end to end: train → publish → sync →
score, with the server staying up and minutes-fresh the whole time.

This is the serving half of a BandaryGithub/PaddleBox production day
(the reference's xbox base/delta publish + the online PS consuming it):

  pass 0:  publish_base   — full artifact (programs + sparse snapshot)
  pass k:  publish_delta  — rows touched this pass + re-frozen dense
                            programs (KBs/MBs, never the whole table)
  serving: a Syncer follows the donefile and hot-applies each delta into
           the LIVE model between requests — no restart, no reload, and
           scores equal a full export at the same pass bit-for-bit.

    python examples/online_delivery.py [--passes 3]
"""

import argparse
import json
import os
import sys
import tempfile
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", type=int, default=3,
                    help="delta passes to publish after the base")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds")
    args = ap.parse_args()

    from paddlebox_tpu.utils.backend import setup_backend

    setup_backend(cpu=args.cpu)

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.inference import ScoringServer
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.serving_sync import Publisher, Syncer
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    S, DENSE, B = 4, 2, 32
    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE,
                             batch_size=B, max_feasigns_per_ins=8)
    tconf = SparseTableConfig(embedding_dim=8)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(16, 8))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 12),
                      seed=0)

    work = tempfile.mkdtemp(prefix="pbox_delivery_")
    root = os.path.join(work, "publish")
    kcap = B * conf.max_feasigns_per_ins

    def train_pass(i):
        files = write_synth_files(
            os.path.join(work, f"d{i}"), n_files=1, ins_per_file=256,
            n_sparse_slots=S, vocab_per_slot=200, dense_dim=DENSE,
            seed=10 + i,
        )
        ds = PadBoxSlotDataset(conf, read_threads=1)
        ds.set_filelist(files)
        ds.load_into_memory()
        table.begin_pass(ds.unique_keys())
        metrics = trainer.train_from_dataset(ds, table)
        table.end_pass()
        ds.close()
        return metrics

    # -- trainer side: base, then the serving plane ------------------------- #
    pub = Publisher(root, staging_dir=os.path.join(work, "staging"))
    m = train_pass(0)
    pub.publish_base("pass0", model, trainer.params, table,
                     lineage="pass0",
                     batch_size=B, key_capacity=kcap, dense_dim=DENSE,
                     feed_conf=conf)
    print(f"pass 0: auc={m['auc']:.4f} -> published base "
          f"({table.n_features} features)")

    # -- serving side: live server + sync agent ----------------------------- #
    server = ScoringServer()
    syncer = Syncer(root, server, "live",
                    cache_dir=os.path.join(work, "cache"),
                    poll_interval_s=0.2)
    syncer.poll_once()
    port = server.start(port=0)
    body = b"1 0 2 7 9 2 11 3 2 5 1 1 8 2 0.5 0.25\n"

    def score():
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/score/live", data=body, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())["scores"][0]

    def models():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/models", timeout=30) as r:
            return json.loads(r.read())["models"]["live"]

    print(f"serving on :{port}; first score = {score():.6f}")

    # -- the freshness loop: train, publish a delta, watch it hot-apply ----- #
    for i in range(1, args.passes + 1):
        m = train_pass(i)
        entry = pub.publish_delta(f"pass{i}", table, model,
                                  trainer.params, lineage=f"pass{i}")
        applied = syncer.poll_once()  # in production the agent thread polls
        info = models()
        print(
            f"pass {i}: auc={m['auc']:.4f} -> delta {entry.n_rows} rows "
            f"(applied {applied}); live = base {info['base_tag']} + "
            f"{info['deltas_applied']} deltas, age "
            f"{info['age_seconds']:.1f}s; score = {score():.6f}"
        )

    server.stop()
    print("delivery loop done;", work)


if __name__ == "__main__":
    main()
