#!/usr/bin/env python
"""Long-sequence CTR training example: DIN-style behavior attention.

One behavior slot (click history: file order == behavior order) feeds an
attention tower next to the standard pooled-CVM features; long sequences
shard over a ``seq`` mesh axis with ring attention.  The reference has no
long-sequence path (SURVEY.md §5.7) — this is the framework's beyond-parity
capability, driven through the SAME Dataset/Trainer lifecycle as every
other model.

    python examples/train_longseq.py [--seq-mesh N] [--impl ring|ulysses]

(--seq-mesh needs N devices: on CPU export
 XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu)
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-mesh", type=int, default=0,
                    help="shard the sequence axis over N devices (0 = off)")
    ap.add_argument("--impl", default="ring", choices=["ring", "ulysses"])
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--max-seq-len", type=int, default=64)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds")
    args = ap.parse_args()

    from paddlebox_tpu.utils.backend import setup_backend

    setup_backend(cpu=args.cpu)

    import jax
    import numpy as np

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.synth import make_synth_config, write_synth_files
    from paddlebox_tpu.models import LongSeqCtrDnn
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.train.trainer import Trainer

    S, DENSE, B = 8, 8, 256
    MAX_KEYS_PER_SLOT = 24
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B,
        max_feasigns_per_ins=args.max_seq_len + 16,
        # capacity must cover the worst batch (B * S * keys-per-slot) or the
        # feed silently clips tail keys — the behavior sequences included
        batch_key_capacity=B * S * MAX_KEYS_PER_SLOT,
        sequence_slot="slot0",  # slot0's keys double as the behavior sequence
        max_seq_len=args.max_seq_len,
    )

    seq_mesh = None
    if args.seq_mesh:
        from jax.sharding import Mesh

        from paddlebox_tpu.parallel.sequence import SEQ_AXIS

        devs = jax.devices()
        if len(devs) < args.seq_mesh:
            raise SystemExit(
                f"--seq-mesh {args.seq_mesh} needs {args.seq_mesh} devices, "
                f"have {len(devs)}"
            )
        seq_mesh = Mesh(np.array(devs[: args.seq_mesh]), (SEQ_AXIS,))

    tconf = SparseTableConfig(embedding_dim=16, learning_rate=0.5,
                              initial_range=0.05)
    model = LongSeqCtrDnn(
        S, tconf.row_width, dense_dim=DENSE, hidden=(256, 128),
        max_seq_len=args.max_seq_len, n_heads=4, head_dim=16,
        seq_mesh=seq_mesh, seq_impl=args.impl,
    )
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(
        model, tconf, TrainerConfig(dense_lr=3e-3, auc_buckets=1 << 16),
        seed=0,
    )

    with tempfile.TemporaryDirectory() as td:
        files = write_synth_files(
            td, n_files=2, ins_per_file=2048, n_sparse_slots=S,
            vocab_per_slot=5000, dense_dim=DENSE, seed=7,
            max_keys_per_slot=MAX_KEYS_PER_SLOT,
        )
        ds = PadBoxSlotDataset(conf, read_threads=2)
        ds.set_filelist(files)
        ds.load_into_memory()
        for p in range(args.passes):
            ds.local_shuffle(seed=p)
            table.begin_pass(ds.unique_keys())
            m = trainer.train_from_dataset(ds, table)
            table.end_pass()
            mesh_note = (
                f" [seq-mesh {args.seq_mesh}x {args.impl}]" if seq_mesh else ""
            )
            print(
                f"pass {p}{mesh_note}: loss={m['loss']:.4f} "
                f"auc={m['auc']:.4f} steps={m['steps']}"
            )
        ds.close()


if __name__ == "__main__":
    main()
