"""MNIST via the Spark ML Pipeline API — fit → export → transform.

Reference: the ``examples/mnist/keras`` + ``examples/mnist/estimator``
drivers (SURVEY.md §2.1 v2.x era) exercise the high-level API family the
same way ``pipeline.TFEstimator``/``TFModel`` do here: the estimator
spins up the cluster and trains from a DataFrame, the fitted model runs
single-node parallel inference with a per-process cached export
(reference ``pipeline._run_model``, SURVEY.md §3.4). Run::

    JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/mnist/mnist_pipeline.py --cluster_size 2 \
        --images .scratch/data/mnist --epochs 2

(``--images`` must hold ``mnist_data_setup.py`` CSV output; it is
written on demand when absent.)
"""

import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

from tensorflowonspark_tpu.engine import Context  # noqa: E402


def train_fn(args, ctx):
    """Cluster-side: LeNet over the DataFeed, chief exports the model."""
    import jax
    import optax

    from tensorflowonspark_tpu import export, infeed, training
    from tensorflowonspark_tpu.models.lenet import LeNet

    ctx.initialize_jax()
    mesh = ctx.mesh()
    model = LeNet()
    trainer = training.Trainer(model, optax.adam(args.lr), mesh)
    state = trainer.init(jax.random.PRNGKey(0),
                         np.zeros((8, 28, 28, 1), np.float32))

    feed = ctx.get_data_feed(train_mode=True)

    def batches():
        for rows in feed.numpy_batches(args.batch_size,
                                       pad_to_batch=True):
            # input_mapping order: (image, label)
            x = np.asarray([r[0] for r in rows], np.float32)
            yield {"x": (x / 255.0).reshape(-1, 28, 28, 1),
                   "y": np.asarray([r[1] for r in rows], np.int64)}

    state, steps, rate = trainer.train_loop(
        state, infeed.sharded_batches(batches(), mesh), log_every=20)

    if ctx.job_name == "chief":
        variables = {"params": jax.device_get(state["params"]),
                     **jax.device_get(state["extra"])}

        def apply_fn(variables, batch, _model=model):
            x = np.asarray(batch["image"], np.float32) / 255.0
            logits = _model.apply(variables, x.reshape(-1, 28, 28, 1))
            return {"prediction": np.argmax(logits, axis=-1)}

        export.save_model(args.export_dir, apply_fn, variables,
                          signature={"inputs": ["image"],
                                     "outputs": ["prediction"]})


def load_csv_rows(csv_dir):
    rows = []
    for part in sorted(os.listdir(csv_dir)):
        for line in open(os.path.join(csv_dir, part)):
            vals = np.fromstring(line, np.float32, sep=",")
            rows.append({"image": vals[1:].tolist(), "label": int(vals[0])})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_size", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--images", default=".scratch/data/mnist")
    ap.add_argument("--num_train", type=int, default=1024,
                    help="examples to materialize when --images is absent")
    ap.add_argument("--export_dir", default=".scratch/mnist_pipeline_export")
    args = ap.parse_args(argv)
    logging.basicConfig(level="INFO")
    # the chief exports from its own working dir; pin the path driver-side
    args.export_dir = os.path.abspath(args.export_dir)

    if not os.path.isdir(os.path.join(args.images, "train")):
        from examples.mnist import mnist_data_setup
        mnist_data_setup.main(["--output", args.images, "--format", "csv",
                               "--num-train", str(args.num_train),
                               "--num-test", "256"])

    from tensorflowonspark_tpu import pipeline

    sc = Context(num_executors=args.cluster_size)
    try:
        train_df = sc.createDataFrame(
            load_csv_rows(os.path.join(args.images, "train")),
            num_slices=args.cluster_size * 2)
        est = (pipeline.TFEstimator(train_fn,
                                    {"lr": args.lr})
               .setClusterSize(args.cluster_size)
               .setBatchSize(args.batch_size)
               .setEpochs(args.epochs)
               .setExportDir(args.export_dir)
               .setInputMapping({"image": "image", "label": "label"}))
        model = est.fit(train_df)

        test_rows = load_csv_rows(os.path.join(args.images, "test"))
        test_df = sc.createDataFrame(test_rows,
                                     num_slices=args.cluster_size)
        model.setInputMapping({"image": "image"}) \
             .setOutputMapping({"prediction": "prediction"}) \
             .setBatchSize(args.batch_size)
        preds = model.transform(test_df.select("image")).collect()
        correct = sum(int(p["prediction"]) == r["label"]
                      for p, r in zip(preds, test_rows))
        acc = correct / max(len(test_rows), 1)
        print("pipeline fit+transform complete: test accuracy {:.3f} "
              "({} examples)".format(acc, len(test_rows)))
    finally:
        sc.stop()


if __name__ == "__main__":
    main()
