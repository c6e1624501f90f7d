"""Rank program for the multi-process parity test (not a pytest module).

Launched by ``paddlebox_tpu.launch`` with N ranks x K virtual CPU devices;
each rank trains the SAME global batch stream but feeds only its own slice
of every device group — so the N-process run must reproduce the
single-process n-device run exactly (the reference's localhost-subprocess
distributed tier, test_dist_base.py:642 "dist loss == local loss").

argv: data_dir out_json [lrmap]
"""

import glob
import itertools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from paddlebox_tpu.parallel.mesh import initialize_distributed  # noqa: E402

initialize_distributed()  # joins the launcher's coordinator

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main() -> None:
    data_dir, out_path = sys.argv[1], sys.argv[2]
    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.dataset import PadBoxSlotDataset
    from paddlebox_tpu.data.feed import empty_like
    from paddlebox_tpu.data.synth import make_synth_config
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.parallel import (
        MultiChipTrainer,
        ShardedSparseTable,
        make_mesh,
    )
    from paddlebox_tpu.parallel.multiprocess import host_allgather

    S, DENSE, B = 3, 2, 8
    conf = make_synth_config(
        n_sparse_slots=S, dense_dim=DENSE, batch_size=B, max_feasigns_per_ins=16
    )
    ds = PadBoxSlotDataset(conf, read_threads=1)
    ds.set_filelist(sorted(glob.glob(os.path.join(data_dir, "*"))))
    ds.load_into_memory()

    mesh = make_mesh()
    # "lrmap=<json>" arm: per-slot LR map over the sharded path — its slot
    # lrs ride the packed want-matrix allgather on the host-plane KV
    # channel.  The map itself comes from the test via argv so the
    # reference run and this child can never drift.
    lr_map = ()
    for a in sys.argv[3:]:
        if a.startswith("lrmap="):
            lr_map = tuple(tuple(p) for p in json.loads(a[6:]))
    tconf = SparseTableConfig(embedding_dim=8, slot_learning_rates=lr_map)
    trconf = TrainerConfig(auc_buckets=1 << 10)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(32, 16))
    trainer = MultiChipTrainer(model, tconf, mesh, trconf, seed=0)
    table = ShardedSparseTable(tconf, mesh, seed=0)
    table.begin_pass(ds.unique_keys())

    pid, n_local, n_dev = jax.process_index(), trainer.n_local, trainer.n_dev

    def local_groups():
        """Global groups of n_dev batches, sliced to this rank's devices —
        same padding discipline as the single-process _group_batches."""
        it = iter(ds.batches(drop_last=False))
        while True:
            group = list(itertools.islice(it, n_dev))
            if not group:
                return
            if len(group) < n_dev:
                group += [empty_like(group[0])] * (n_dev - len(group))
            yield group[pid * n_local : (pid + 1) * n_local]

    metrics = trainer.train_groups(table, local_groups())
    table.end_pass()
    ds.close()

    params, _ = trainer.dense_state()
    param_abs_sum = float(
        sum(np.abs(np.asarray(l)).sum() for l in jax.tree.leaves(params))
    )
    total_features = int(
        host_allgather(np.asarray([table.n_features], np.int64)).sum()
    )
    if pid == 0:
        with open(out_path, "w") as f:
            json.dump(
                {
                    "loss": metrics["loss"],
                    "auc": metrics["auc"],
                    "count": metrics["count"],
                    "steps": metrics["steps"],
                    "param_abs_sum": param_abs_sum,
                    "total_features": total_features,
                },
                f,
            )


if __name__ == "__main__":
    main()
