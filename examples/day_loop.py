#!/usr/bin/env python
"""The full production day loop, end to end.

This is the shape a BandaryGithub/PaddleBox production job has — the
reference spreads it across BoxHelper (pass driver), the join/update phase
flip (box_wrapper.h:627-630), ShrinkTable at day boundaries
(box_wrapper.cc:496-499), SaveBase/SaveDelta (cc:1411-1460), donefile
publication (fleet_util/fs), and operator-side monitoring — here it is one
readable loop over this framework's pieces:

  day d:
    pass p:                       (preload pass p+1 while p trains)
      join phase  -> update phase (two programs, one shared sparse table)
      monitor.observe(metrics)    (AUC floor/drop, loss, calibration)
      save_delta                  (incremental checkpoint)
    shrink()                      (decay show/clk, evict cold features)
    save_base + publish gate      (only a healthy model ships)

    python examples/day_loop.py [--days 2] [--passes 2]
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--days", type=int, default=2)
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds")
    args = ap.parse_args()

    from paddlebox_tpu.utils.backend import setup_backend

    setup_backend(cpu=args.cpu)
    if args.days < 1 or args.passes < 1:
        ap.error("--days and --passes must be >= 1")

    from paddlebox_tpu.checkpoint import CheckpointManager
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.two_phase import PhaseSpec, TwoPhaseTrainer
    from paddlebox_tpu.utils.fleet_util import (
        HealthPolicy,
        ModelMonitor,
        check_model,
    )

    S, DENSE, B = 6, 4, 128
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=16,
    )
    tconf = SparseTableConfig(
        embedding_dim=8, learning_rate=0.5, initial_range=0.05,
        show_decay_rate=0.9, delete_threshold=0.5,  # day-boundary shrink
    )
    trconf = TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 16)

    # join phase trains the user/context slots, update phase all slots —
    # two dense programs over ONE shared sparse table
    join_model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(64, 32))
    update_model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(64, 32))
    tp = TwoPhaseTrainer(
        [
            PhaseSpec("join", join_model, slots=tuple(range(S // 2))),
            PhaseSpec("update", update_model, slots=None),
        ],
        tconf, trconf,
    )
    table = SparseTable(tconf, seed=0)
    monitor = ModelMonitor(HealthPolicy(min_auc=0.5, max_auc_drop=0.2))

    work = tempfile.mkdtemp(prefix="pbox_dayloop_")
    cm = CheckpointManager(os.path.join(work, "ckpt"))
    rng_seed = 0

    for day in range(args.days):
        date = f"202607{28 + day:02d}"
        print(f"== day {date}")
        for p in range(args.passes):
            with tempfile.TemporaryDirectory() as td:
                files = write_synth_files(
                    td, n_files=2, ins_per_file=512, n_sparse_slots=S,
                    vocab_per_slot=300, dense_dim=DENSE, seed=rng_seed,
                )
                rng_seed += 1
                ds = PadBoxSlotDataset(conf, read_threads=2)
                ds.set_filelist(files)
                ds.set_date(date)
                ds.load_into_memory()
                table.begin_pass(ds.unique_keys())
                metrics = tp.train_pass(ds, table)
                table.end_pass()
                ds.close()
            up = metrics["update"]
            report = monitor.observe(up)
            print(
                f"  pass {p}: join auc={metrics['join']['auc']:.4f} "
                f"update auc={up['auc']:.4f} loss={up['loss']:.4f} "
                f"healthy={bool(report)}"
            )
            cm.save_delta(f"{date}-p{p}", table)
        evicted = table.shrink()
        rep = check_model(table, tp.trainers["update"])
        print(
            f"  shrink evicted {evicted}; features={rep['n_features']} "
            f"sparse={rep['sparse_bytes'] / 1e6:.1f}MB finite={rep['sparse_finite']}"
        )
        if monitor.should_publish(up):
            params, opt = tp.trainers["update"].dense_state()
            path = cm.save_base(f"{date}-base", table, params, opt)
            print(f"  published base checkpoint: {os.path.basename(path)}")
        else:
            print("  publish gate held the model back")
    print("day loop done;", work)


if __name__ == "__main__":
    main()
