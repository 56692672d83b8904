"""Distributed Inception-v3 train → eval → export — the reference's
``examples/imagenet/inception`` training side (SURVEY.md §2.1: the
distributed Inception train/eval/export port; the sibling
``inception_inference.py`` is BASELINE config #5's inference mode).

Cluster-fed (SPARK input mode) training of the first-party flax
Inception-v3, a held-out eval pass on the chief, and a model export the
inference driver (or ``tfos-serve``) can load via ``--export_dir``.
Synthetic separable data by default (zero-egress environment): class k
images carry a class-dependent mean shift, so a learning run must beat
chance by a wide margin.

CPU dev run::

    JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/inception/inception_train.py --cluster_size 2 \
        --num_examples 256 --image_size 75 --num_classes 4
"""

import argparse
import json
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

from tensorflowonspark_tpu import cluster  # noqa: E402
from tensorflowonspark_tpu.engine import Context  # noqa: E402


def make_example(rng, size, classes):
    """Synthetic separable image: class-dependent channel mean + noise."""
    y = int(rng.randint(classes))
    # float math: integer division would floor the per-class shift to 0
    # at large --num_classes and silently train on unseparable noise
    shift = (np.arange(3) + 1.0) * (y + 1) * (160.0 / (classes + 1))
    img = np.clip(rng.normal(shift, 40.0, (size, size, 3)), 0, 255)
    return {"x": img.astype(np.uint8), "y": y}


def map_fun(args, ctx):
    import jax
    import optax

    from tensorflowonspark_tpu import infeed, training
    from tensorflowonspark_tpu.models.inception import InceptionV3

    ctx.initialize_jax()
    mesh = ctx.mesh()
    size, classes = args["image_size"], args["num_classes"]
    model = InceptionV3(num_classes=classes)
    trainer = training.Trainer(model, optax.adam(args["lr"]), mesh,
                               dropout_rng=True)
    state = trainer.init(jax.random.PRNGKey(0),
                         np.zeros((8, size, size, 3), np.float32))

    feed = ctx.get_data_feed(train_mode=True)

    def batches():
        for records in feed.numpy_batches(args["batch_size"],
                                          pad_to_batch=True):
            yield {"x": np.stack([r["x"] for r in records])
                   .astype(np.float32) / 255.0,
                   "y": np.asarray([r["y"] for r in records], np.int64)}

    state, steps, rate = trainer.train_loop(
        state, infeed.sharded_batches(batches(), mesh),
        log_every=args.get("log_every", 10))

    if ctx.job_name == "chief":
        from tensorflowonspark_tpu import export

        variables = {"params": jax.device_get(state["params"]),
                     **jax.device_get(state["extra"])}
        # eval pass: held-out synthetic batch, same generator as training
        rng = np.random.RandomState(99_991)
        val = [make_example(rng, size, classes)
               for _ in range(args["batch_size"])]
        vx = np.stack([v["x"] for v in val]).astype(np.float32) / 255.0
        vy = np.asarray([v["y"] for v in val])
        logits = model.apply(variables, vx)
        acc = float((np.argmax(logits, -1) == vy).mean())

        out = ctx.absolute_path(args["model_dir"])
        os.makedirs(out, exist_ok=True)
        if args.get("export_dir"):

            def apply_fn(variables, batch, _m=model):
                import numpy as _np
                x = _np.asarray(batch["image"], _np.float32) / 255.0
                logits = _m.apply(variables, x)
                return {"label": _np.argmax(logits, -1)}

            export.save_model(args["export_dir"], apply_fn, variables,
                              signature={"inputs": ["image"],
                                         "outputs": ["label"]})
        with open(os.path.join(out, "train_stats.json"), "w") as f:
            json.dump({"steps": steps, "images_per_sec": rate,
                       "val_accuracy": acc}, f)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_size", type=int, default=2)
    ap.add_argument("--num_examples", type=int, default=512)
    ap.add_argument("--batch_size", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--image_size", type=int, default=299,
                    help="75 for quick CPU runs; 299 = real Inception-v3")
    ap.add_argument("--num_classes", type=int, default=1000)
    ap.add_argument("--log_every", type=int, default=10)
    ap.add_argument("--model_dir", default=".scratch/inception_model")
    ap.add_argument("--export_dir", default=None,
                    help="chief exports here; feed to "
                         "inception_inference.py --export_dir")
    args = ap.parse_args(argv)
    logging.basicConfig(level="INFO")
    if args.export_dir:
        args.export_dir = os.path.abspath(args.export_dir)
        # clear a stale export NOW: discovering it exists only at the
        # chief's end-of-training save would waste the whole run
        # (criteo_spark.py convention)
        if os.path.isdir(args.export_dir):
            import shutil
            shutil.rmtree(args.export_dir)

    rng = np.random.RandomState(0)
    records = [make_example(rng, args.image_size, args.num_classes)
               for _ in range(args.num_examples)]

    sc = Context(num_executors=args.cluster_size)
    try:
        tfc = cluster.run(sc, map_fun, vars(args),
                          num_executors=args.cluster_size,
                          input_mode=cluster.InputMode.SPARK)
        rdd = sc.parallelize(records, args.cluster_size * 2)
        tfc.train(rdd, num_epochs=args.epochs)
        tfc.shutdown()
    finally:
        sc.stop()
    print("inception training complete; stats in",
          os.path.join(args.model_dir, "train_stats.json"))


if __name__ == "__main__":
    main()
