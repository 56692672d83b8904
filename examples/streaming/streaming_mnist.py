"""Continuous (streaming) MNIST training — the reference's Spark
Streaming mode at example level.

Reference capability (SURVEY.md §2 Cluster API row, §3.5):
``TFCluster.train`` accepts a DStream and feeds each micro-batch through
the same queue plane; ``shutdown(ssc)`` stops the stream before ending
the feed. Here the driver tails a spool directory with
``StreamingContext.textFileStream`` — drop new CSV part-files in and
the cluster trains on them as they arrive (the classic streaming-ingest
deployment: an upstream ETL lands files, trainers never restart).

Self-contained demo run (CPU):

    JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/streaming/streaming_mnist.py --cluster_size 2 \
        --intervals 3 --interval_examples 256

(--intervals N synthesizes N micro-batch files into the spool dir on a
timer, then shuts down cleanly; point --spool_dir at a real landing
zone and omit --intervals for an open-ended run.)
"""

import argparse
import logging
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

from examples.mnist import mnist_dist  # noqa: E402
from tensorflowonspark_tpu import cluster  # noqa: E402
from tensorflowonspark_tpu.engine import Context  # noqa: E402
from tensorflowonspark_tpu.engine.streaming import StreamingContext  # noqa: E402,E501


def spool_feeder(spool_dir, intervals, per_interval, interval_s):
    """Synthesize micro-batch CSV files the way an upstream ETL would."""
    from examples.mnist import mnist_data_setup

    x, y, _, _ = mnist_data_setup.load_mnist_like(
        num_train=per_interval * intervals, num_test=1)
    # run-unique names: the stream snapshots pre-existing files at start,
    # so a re-run reusing yesterday's names would be invisible to it
    run_id = "%d-%d" % (os.getpid(), int(time.time()))
    for i in range(intervals):
        rows = []
        for j in range(i * per_interval, (i + 1) * per_interval):
            px = x[j].reshape(-1)
            rows.append(",".join([str(int(y[j]))] +
                                 [str(int(v)) for v in px]))
        # dot-prefixed write then rename: hidden files are invisible to
        # the stream (engine semantics, same as Spark), so a poll can
        # never read a half-written file
        tmp = os.path.join(spool_dir, ".part-%s-%05d.tmp" % (run_id, i))
        with open(tmp, "w") as f:
            f.write("\n".join(rows) + "\n")
        os.rename(tmp, os.path.join(spool_dir,
                                    "part-%s-%05d.csv" % (run_id, i)))
        time.sleep(interval_s)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_size", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--spool_dir", default=".scratch/stream_spool")
    ap.add_argument("--model_dir", default=".scratch/streaming_model")
    ap.add_argument("--intervals", type=int, default=3,
                    help="self-feed N synthesized micro-batches then stop "
                         "(0 = open-ended; feed --spool_dir externally)")
    ap.add_argument("--interval_examples", type=int, default=256)
    ap.add_argument("--interval_secs", type=float, default=2.0)
    args = ap.parse_args(argv)
    logging.basicConfig(level="INFO")
    os.makedirs(args.spool_dir, exist_ok=True)

    tf_args = {"batch_size": args.batch_size, "lr": args.lr,
               "model_dir": args.model_dir, "images": args.spool_dir,
               "epochs": 1, "input_mode": "spark", "log_every": 10}

    sc = Context(num_executors=args.cluster_size)
    try:
        ssc = StreamingContext(sc, batch_interval=args.interval_secs / 2)
        tfc = cluster.run(sc, mnist_dist.map_fun, tf_args,
                          num_executors=args.cluster_size,
                          input_mode=cluster.InputMode.SPARK)
        stream = ssc.textFileStream(args.spool_dir,
                                    num_slices=args.cluster_size)
        tfc.train(stream)  # continuous: every micro-batch feeds the queues
        ssc.start()

        try:
            if args.intervals:
                feeder = threading.Thread(
                    target=spool_feeder,
                    args=(args.spool_dir, args.intervals,
                          args.interval_examples, args.interval_secs),
                    daemon=True)
                feeder.start()
                feeder.join()
                # one more interval so the final file's batch dispatches
                time.sleep(args.interval_secs)
            else:
                ssc.awaitTermination()
        except KeyboardInterrupt:
            # Ctrl-C is the documented way OUT of the open-ended mode —
            # teardown below must still run so trainers get EndFeed and
            # the chief writes its stats
            print("interrupted: shutting the stream and cluster down")

        tfc.shutdown(ssc)  # stops the stream FIRST, then ends the feed
    finally:
        sc.stop()
    print("streaming training complete; stats in",
          os.path.join(args.model_dir, "train_stats.json"))


if __name__ == "__main__":
    main()
