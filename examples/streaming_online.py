#!/usr/bin/env python
"""Streaming online learning, end to end: live feed → served scores with
second-level, MEASURED freshness.

A writer appends slot-text records to a stream directory (the live feed).
A TailingFileSource follows it; a MiniPassScheduler cuts mini-pass
windows and computes each census off-thread; StreamingTrainer trains
window by window (metric state carried, pass boundaries overlapped); a
DeadlinePublishPolicy ships sparse deltas on a max-staleness deadline;
a Syncer'd ScoringServer hot-applies them; and a confirmation poller
records the true event-time→served-score latency
(`stream.freshness_seconds`).

Halfway through, the writer FLIPS the label of a hot key pattern — watch
the served score move within seconds.

    python examples/streaming_online.py [--seconds 12] [--staleness 1.5]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=12.0,
                    help="how long the live stream runs")
    ap.add_argument("--staleness", type=float, default=1.5,
                    help="freshness budget (s): publish deadline")
    ap.add_argument("--rate", type=float, default=400.0,
                    help="records/s the writer appends")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend instead of the "
                         "accelerator JAX finds")
    args = ap.parse_args()

    from paddlebox_tpu.utils.backend import setup_backend

    setup_backend(cpu=args.cpu)

    import numpy as np

    from paddlebox_tpu.config import SparseTableConfig, TrainerConfig
    from paddlebox_tpu.data.slot_parser import SlotParser
    from paddlebox_tpu.data.synth import make_synth_config, stream_line
    from paddlebox_tpu.inference import ScoringServer
    from paddlebox_tpu.models import CtrDnn
    from paddlebox_tpu.serving_sync import Publisher, Syncer
    from paddlebox_tpu.sparse.table import SparseTable
    from paddlebox_tpu.streaming import (
        DeadlinePublishPolicy,
        MiniPassScheduler,
        StreamingTrainer,
        TailingFileSource,
    )
    from paddlebox_tpu.train.trainer import Trainer

    S, DENSE, B = 2, 2, 16
    conf = make_synth_config(n_sparse_slots=S, dense_dim=DENSE,
                             batch_size=B, max_feasigns_per_ins=8)
    tconf = SparseTableConfig(embedding_dim=4, learning_rate=0.3,
                              store_buckets=8, plan_scratch_rows=64)
    model = CtrDnn(S, tconf.row_width, dense_dim=DENSE, hidden=(8,))
    table = SparseTable(tconf, seed=0)
    trainer = Trainer(model, tconf, TrainerConfig(auc_buckets=1 << 12),
                      seed=0)

    work = tempfile.mkdtemp(prefix="pbox_streaming_")
    root = os.path.join(work, "publish")
    stream = os.path.join(work, "stream")
    os.makedirs(stream)
    rng = np.random.default_rng(0)

    def line(label: int) -> str:
        """One record: the hot key pair (5, 1005) plus one noise key each."""
        return stream_line(rng, label, n_sparse_slots=S, dense_dim=DENSE,
                           hot_keys=(5, 1005))

    # -- warm start: one tiny batch pass anchors the delta chain ------------ #
    parser = SlotParser(conf)
    warm = [line(1) for _ in range(4 * B)]
    block = parser.parse_lines(warm)

    from paddlebox_tpu.streaming.minipass import MiniPassWindow, WindowDataset
    from paddlebox_tpu.data.feed import BatchBuilder

    w0 = MiniPassWindow(0, block, np.unique(block.keys), len(warm),
                        time.time(), time.time(), "warm", time.time())
    table.begin_pass(w0.census)
    trainer.train_from_dataset(WindowDataset(w0, BatchBuilder(conf)), table)
    table.end_pass()

    pub = Publisher(root, staging_dir=os.path.join(work, "staging"))
    kcap = B * conf.max_feasigns_per_ins
    pub.publish_base("base", model, trainer.params, table,
                     lineage="warmup", batch_size=B, key_capacity=kcap,
                     dense_dim=DENSE, feed_conf=conf)

    # -- serving side -------------------------------------------------------- #
    server = ScoringServer()
    syncer = Syncer(root, server, "live",
                    cache_dir=os.path.join(work, "cache"),
                    poll_interval_s=0.1)
    syncer.poll_once()
    syncer.start()
    port = server.start(port=0)
    probe = b"1 0 2 5 30 2 1005 1030 2 0.0 0.0\n"

    def score() -> float:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/score/live", data=probe, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())["scores"][0]

    # -- streaming plane ------------------------------------------------------ #
    source = TailingFileSource(stream, poll_interval_s=0.02)
    sched = MiniPassScheduler(source, conf, window_records=4 * B,
                              window_seconds=0.5)
    policy = DeadlinePublishPolicy(pub, args.staleness, scheduler=sched)
    runner = StreamingTrainer(
        trainer, table, sched, policy=policy, model=model,
        served_seq_fn=lambda: (server.model_version("live") or {}).get("seq"),
    )
    source.start()
    sched.start()

    flip_at = args.seconds / 2
    flipped = threading.Event()

    def writer():
        t0 = time.monotonic()
        path = os.path.join(stream, "part-000")
        with open(path, "w", buffering=1) as fh:
            while time.monotonic() - t0 < args.seconds:
                late = time.monotonic() - t0 >= flip_at
                if late and not flipped.is_set():
                    flipped.set()
                    print(f"[writer] t+{time.monotonic() - t0:.1f}s: "
                          "LABEL FLIP 1 -> 0 for the hot keys")
                fh.write(line(0 if late else 1))
                time.sleep(1.0 / args.rate)
        runner.stop()  # drain-and-checkpoint shutdown

    def reporter():
        while not runner._stop_evt.is_set():
            try:
                s = score()
            except Exception:
                s = float("nan")
            info = server.model_version("live") or {}
            print(f"[serve] score={s:.4f} seq={info.get('seq')} "
                  f"freshness={policy.last_freshness_s and round(policy.last_freshness_s, 2)}s "
                  f"windows={runner.windows_trained}")
            time.sleep(1.0)

    threading.Thread(target=writer, daemon=True).start()
    threading.Thread(target=reporter, daemon=True).start()
    summary = runner.run()

    final = score()
    syncer.stop()
    server.stop()
    print("\nstream summary:", json.dumps(summary, indent=2))
    print(f"final served score: {final:.4f}")
    print("workdir:", work)


if __name__ == "__main__":
    main()
