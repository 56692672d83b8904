"""Inception-v3 batch inference — BASELINE config #5
("TFoS inference mode, Spark RDD images -> TPU").

Uses the cluster *inference* path (SURVEY.md §3.3): images stream through
the queue plane, every node runs the jitted forward over its feed, and
predictions come back as an RDD with per-partition count/order preserved.
Random-init weights by default (zero-egress env) — the plumbing and
throughput are what this example demonstrates; point --export_dir at a
trained export to serve real weights via the same flow.

CPU dev run::

    JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/inception/inception_inference.py --cluster_size 2 \
        --num_images 32 --image_size 75
"""

import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

from tensorflowonspark_tpu import cluster  # noqa: E402
from tensorflowonspark_tpu.engine import Context  # noqa: E402


def map_fun(args, ctx):
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.models.inception import InceptionV3

    ctx.initialize_jax()
    model = InceptionV3(num_classes=args["num_classes"])
    size = args["image_size"]

    if args["export_dir"]:
        from tensorflowonspark_tpu import export

        _, variables, _ = export.load_model(args["export_dir"])
    else:
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, size, size, 3)))

    # variables as a jit ARGUMENT, not a closure: closed-over weights bake
    # into the executable as constants (~95MB duplicated, huge compiles)
    @jax.jit
    def _forward(variables, x):
        logits = model.apply(variables, x)
        return jnp.argmax(logits, axis=-1), jnp.max(
            jax.nn.log_softmax(logits), axis=-1)

    def forward(x):
        return _forward(variables, x)

    feed = ctx.get_data_feed(train_mode=False)
    B = args["batch_size"]
    while not feed.should_stop():
        batch = feed.next_batch(B)
        if not batch:
            continue
        x = np.stack([np.frombuffer(b, np.uint8).reshape(size, size, 3)
                      for b in batch]).astype(np.float32) / 255.0
        n = len(batch)
        if n < B:  # pad to the compiled shape; emit only n results
            x = np.concatenate([x, np.zeros((B - n,) + x.shape[1:],
                                            x.dtype)])
        labels, scores = forward(x)
        feed.batch_results(
            ["%d\t%.4f" % (int(l), float(s))
             for l, s in zip(labels[:n], scores[:n])])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_size", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=8)
    ap.add_argument("--num_images", type=int, default=64)
    ap.add_argument("--image_size", type=int, default=299,
                    help="75 for quick CPU runs; 299 = real Inception-v3")
    ap.add_argument("--num_classes", type=int, default=1000)
    ap.add_argument("--export_dir", default=None)
    ap.add_argument("--output", default=".scratch/inception_predictions")
    args = ap.parse_args(argv)
    logging.basicConfig(level="INFO")
    if args.export_dir:
        # trainers run from their executor workdirs; pin the path here
        args.export_dir = os.path.abspath(args.export_dir)

    rng = np.random.RandomState(0)
    images = [rng.randint(0, 256, (args.image_size, args.image_size, 3),
                          np.uint8).tobytes() for _ in range(args.num_images)]

    sc = Context(num_executors=args.cluster_size)
    try:
        tfc = cluster.run(sc, map_fun, vars(args),
                          num_executors=args.cluster_size,
                          input_mode=cluster.InputMode.SPARK)
        rdd = sc.parallelize(images, args.cluster_size * 2)
        preds = tfc.inference(rdd)
        import shutil

        if os.path.exists(args.output):
            shutil.rmtree(args.output)
        preds.saveAsTextFile(args.output)
        tfc.shutdown()
    finally:
        sc.stop()
    total = sum(len(open(os.path.join(args.output, f)).read().splitlines())
                for f in os.listdir(args.output))
    print("wrote {} predictions under {}".format(total, args.output))
    assert total == args.num_images, "prediction count mismatch!"


if __name__ == "__main__":
    main()
