"""Wide & Deep on Criteo-shaped data — BASELINE config #4
("Spark ETL -> TPU embedding tables").

The ETL stage runs in the DataFrame world: raw rows (13 numeric + 26
categorical string slots, tab-separated like the Criteo dump) are parsed,
log-normalized, and the categoricals hashed into embedding buckets
host-side; the queue plane then feeds integer/float tensors only, so the
device graph is gather+matmul (models/widedeep.py).

CPU dev run::

    JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/criteo/criteo_spark.py --cluster_size 2
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

BUCKETS = 1000


def synthetic_criteo_lines(n, seed=0):
    """Tab-separated: label, 13 ints (some blank), 26 hex categoricals.
    The label correlates with dense[0] and cat[0] so training can learn."""
    rng = np.random.RandomState(seed)
    lines = []
    for _ in range(n):
        d0 = rng.randint(0, 100)
        c0 = rng.randint(0, 8)
        label = 1 if (d0 > 50) ^ (c0 < 2) else 0
        dense = [str(d0)] + [str(rng.randint(0, 1000)) if rng.rand() > 0.1
                             else "" for _ in range(12)]
        cats = ["%08x" % c0] + ["%08x" % rng.randint(0, 500)
                                for _ in range(25)]
        lines.append("\t".join([str(label)] + dense + cats))
    return lines


def etl(line, buckets=BUCKETS):
    """One raw line -> (dense[13] float32, cat[26] int64, label) tuple."""
    from tensorflowonspark_tpu.models.widedeep import hash_categorical

    parts = line.rstrip("\n").split("\t")
    label = int(parts[0])
    dense = np.array([np.log1p(float(v)) if v else 0.0
                      for v in parts[1:14]], np.float32)
    cat = hash_categorical(parts[14:40], buckets)
    return dense, cat, label


def save_tfrecords(lines, out_dir, shards=4, buckets=BUCKETS):
    """ETL once, materialize dense tensors as TFRecord shards — the
    reference workflow of persisting the ETL output for repeated
    training runs (dfutil.saveAsTFRecords analog, dense schema)."""
    from tensorflowonspark_tpu import tfrecord

    os.makedirs(out_dir, exist_ok=True)
    per = -(-len(lines) // shards)
    for s in range(shards):
        rows = lines[s * per:(s + 1) * per]
        tfrecord.write_tfrecords(
            os.path.join(out_dir, "part-%05d" % s),
            ({"dense": dense, "cat": cat, "label": [label]}
             for dense, cat, label in (etl(r, buckets) for r in rows)))


def _make_model(args, quantized=False):
    """The ONE WideDeep constructor both training and export use — a
    config drift between them would surface as a flax shape mismatch at
    serve time, the worst place to find it."""
    from tensorflowonspark_tpu.models import widedeep

    return widedeep.WideDeep(
        hash_buckets=args.get("hash_buckets", BUCKETS),
        embed_dim=args.get("embed_dim", 16),
        mlp_sizes=(64, 32), quantized=quantized)


def _build_trainer(args, ctx):
    import optax

    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.models import widedeep

    devices = ctx.initialize_jax()
    tp = int(args.get("tp", 1))
    if tp > 1:
        # DP x TP mesh: the fused embedding tables (the dominant params
        # at recommender scale — hash_buckets x 26 rows) row-shard over
        # the model axis per WIDEDEEP_TP_RULES, so each chip holds
        # rows/tp and XLA emits the sharded-gather + psum pattern
        mesh = ctx.mesh({"data": len(devices) // tp, "model": tp})
    else:
        mesh = ctx.mesh()
    return mesh, training.Trainer(_make_model(args), optax.adam(args["lr"]),
                                  mesh,
                                  loss_fn=widedeep.ctr_loss,
                                  input_keys=("dense", "cat"),
                                  constrain_state=(tp <= 1))


def _shard_params(state, mesh, args):
    """Row-shard the embedding tables over the model axis (tp > 1).

    The optimizer moments mirror the params tree and dominate memory at
    recommender scale (adam: 2x the table again), so they re-lay with
    the SAME rule tree — sharding only params would leave 2/3 of the
    table bytes replicated and defeat TP's memory point. (init() itself
    still materializes one replicated copy transiently; a real-chip 10M
    run at the memory edge should init under jit with these shardings
    as out_shardings.)"""
    if int(args.get("tp", 1)) <= 1:
        return state
    import jax

    from tensorflowonspark_tpu.parallel.sharding import (
        WIDEDEEP_TP_RULES, tree_shardings)

    shardings = tree_shardings(state["params"], mesh, WIDEDEEP_TP_RULES)
    pdef = jax.tree.structure(state["params"])

    def params_like(node):
        try:
            return jax.tree.structure(node) == pdef
        except TypeError:
            return False

    state["params"] = jax.device_put(state["params"], shardings)
    state["opt_state"] = jax.tree.map(
        lambda sub: jax.device_put(sub, shardings)
        if params_like(sub) else sub,
        state["opt_state"], is_leaf=params_like)
    return state


def _quantize_export(args, ctx, state, mesh):
    """Chief-only: post-training int8 table quantization + model export.

    The recommender serving journey (SURVEY §2.2 quantized lookups):
    trained f32 params -> quantize_embeddings -> export; serve with
    `tfos-serve --model-dir DIR` and the logits track f32 within
    quantization error (tests/test_serving.py proves the parity).
    Rerunnable: an existing export dir is replaced, like --model_dir.
    """
    out_dir = args.get("quantize_export")
    if not out_dir or ctx.job_name != "chief":
        return
    import shutil

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from tensorflowonspark_tpu import export
    from tensorflowonspark_tpu.models import widedeep

    # TP-sharded params span processes in a real distributed run; a
    # bare device_get on non-addressable shards raises. Replicate
    # through a jitted identity first (XLA emits the all-gather), then
    # fetch the now-addressable copies.
    replicated = NamedSharding(mesh, PartitionSpec())
    params = jax.device_get(jax.jit(
        lambda p: p, out_shardings=replicated)(state["params"]))
    slim, quant = widedeep.quantize_embeddings(params)
    cfg = {k: args.get(k) for k in
           ("hash_buckets", "embed_dim") if args.get(k) is not None}

    def apply_fn(variables, batch, _cfg=cfg):
        import numpy as np

        qmodel = _make_model(dict(_cfg), quantized=True)
        return {"ctr_logit": qmodel.apply(
            variables, np.asarray(batch["dense"], np.float32),
            np.asarray(batch["cat"], np.int32))}

    out = ctx.absolute_path(out_dir)
    if os.path.isdir(out):
        shutil.rmtree(out)
    export.save_model(out, apply_fn,
                      {"params": slim, "quant": quant},
                      signature={"inputs": ["dense", "cat"],
                                 "outputs": ["ctr_logit"]})


def _write_stats(args, ctx, payload):
    if ctx.job_name == "chief":
        import json

        out = ctx.absolute_path(args["model_dir"])
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "train_stats.json"), "w") as f:
            json.dump(payload, f)


def map_fun_tfrecord(args, ctx):
    """InputMode.TENSORFLOW trainer: each worker reads its own dense
    TFRecord shards with the native batched decoder (tfrecord.read_batch
    — the 100x dense path), no queue plane in the loop."""
    import time

    import jax

    from tensorflowonspark_tpu import infeed, tfrecord

    mesh, trainer = _build_trainer(args, ctx)
    files = tfrecord.list_tfrecord_files(
        ctx.absolute_path(args["tfrecord_dir"]))
    # task_sorted_index: global ordinal across chief+workers (task_index
    # restarts per job family, so chief and worker-0 would collide)
    mine = files[ctx.task_sorted_index()::max(ctx.num_workers, 1)]
    if not mine:
        raise ValueError("fewer TFRecord shards than workers")
    schema = {"dense": ("float32", 13), "cat": ("int64", 26),
              "label": ("int64", 1)}
    t0 = time.monotonic()
    cols = [tfrecord.read_batch(f, schema) for f in mine]
    dense = np.concatenate([c["dense"] for c in cols])
    cat = np.concatenate([c["cat"] for c in cols])
    label = np.concatenate([c["label"] for c in cols])[:, 0].astype(np.int32)
    read_rate = len(dense) / (time.monotonic() - t0)

    # SPMD discipline: every worker must run the SAME number of steps or
    # the gradient all-reduce deadlocks on uneven shards. All workers
    # count every shard (metadata-rate native index) and agree on
    # min-worker batches; local data wraps circularly (resnet example
    # pattern).
    W = max(ctx.num_workers, 1)
    # verify_crc=False: this is a COUNT of all shards by all workers —
    # checksumming W x full-dataset here would multiply startup I/O by
    # the cluster size; the shards a worker trains on were already
    # CRC-validated by its read_batch above
    shard_counts = [tfrecord.count_records(f, verify_crc=False)
                    for f in files]
    worker_counts = [sum(shard_counts[w::W]) for w in range(W)]
    B = args["batch_size"]
    steps = max(1, args["epochs"] * (min(worker_counts) // B))

    def batches():
        i = 0
        n = len(dense)
        for _ in range(steps):
            idx = np.arange(i, i + B) % n
            i = (i + B) % n
            yield {"dense": dense[idx], "cat": cat[idx],
                   "label": label[idx]}

    sample = {"dense": np.zeros((8, 13), np.float32),
              "cat": np.zeros((8, 26), np.int64)}
    state = _shard_params(trainer.init(jax.random.PRNGKey(0), sample),
                          mesh, args)
    state, steps, rate = trainer.train_loop(
        state, infeed.sharded_batches(batches(), mesh), log_every=20)
    _write_stats(args, ctx, {"steps": steps, "examples_per_sec": rate,
                             "reader_records_per_sec": read_rate,
                             "table_rows": 26 * args.get("hash_buckets",
                                                         BUCKETS),
                             "input": "tfrecord"})
    _quantize_export(args, ctx, state, mesh)


def map_fun(args, ctx):
    import jax

    from tensorflowonspark_tpu import infeed

    mesh, trainer = _build_trainer(args, ctx)

    feed = ctx.get_data_feed(train_mode=True)

    def batches():
        B = args["batch_size"]
        for records in feed.numpy_batches(B, pad_to_batch=True):
            yield {"dense": np.stack([r[0] for r in records]),
                   "cat": np.stack([r[1] for r in records]),
                   "label": np.array([r[2] for r in records], np.int32)}

    sample = {"dense": np.zeros((8, 13), np.float32),
              "cat": np.zeros((8, 26), np.int64)}
    state = _shard_params(trainer.init(jax.random.PRNGKey(0), sample),
                          mesh, args)
    state, steps, rate = trainer.train_loop(
        state, infeed.sharded_batches(batches(), mesh), log_every=20)
    _write_stats(args, ctx, {"steps": steps, "examples_per_sec": rate,
                             "feed_stats": feed.stats(),
                             "table_rows": 26 * args.get("hash_buckets",
                                                         BUCKETS),
                             "input": "spark-etl"})
    _quantize_export(args, ctx, state, mesh)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_size", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--num_examples", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--hash_buckets", type=int, default=BUCKETS,
                    help="buckets per categorical slot; the fused table "
                         "holds 26x this many rows (385000 ~= a 10M-row "
                         "table)")
    ap.add_argument("--embed_dim", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1,
                    help="model-axis size; >1 row-shards the embedding "
                         "tables over the mesh (WIDEDEEP_TP_RULES)")
    ap.add_argument("--quantize_export", default=None, metavar="DIR",
                    help="after training, quantize the deep embedding "
                         "table to int8 and export a servable model to "
                         "DIR (chief only; serve with tfos-serve)")
    ap.add_argument("--data", default=None,
                    help="path to a Criteo-format text file (default: "
                         "synthetic)")
    ap.add_argument("--save_tfrecords", default=None, metavar="DIR",
                    help="run the ETL once and materialize dense TFRecord "
                         "shards to DIR, then exit (no training)")
    ap.add_argument("--tfrecord_dir", default=None, metavar="DIR",
                    help="train from dense TFRecord shards written by "
                         "--save_tfrecords (InputMode.TENSORFLOW; each "
                         "worker reads its own shards via the native "
                         "batched decoder)")
    ap.add_argument("--model_dir", default=".scratch/widedeep_model")
    args = ap.parse_args(argv)
    logging.basicConfig(level="INFO")

    def load_lines():  # only the ETL-consuming paths pay for this
        if args.data:
            return open(args.data).read().splitlines()
        return synthetic_criteo_lines(args.num_examples)

    if args.save_tfrecords:
        save_tfrecords(load_lines(), args.save_tfrecords,
                       shards=max(4, args.cluster_size),
                       buckets=args.hash_buckets)
        print("wrote dense TFRecord shards to", args.save_tfrecords)
        return

    sc = Context(num_executors=args.cluster_size)
    try:
        if args.tfrecord_dir:
            tfc = cluster.run(sc, map_fun_tfrecord, vars(args),
                              num_executors=args.cluster_size,
                              input_mode=cluster.InputMode.TENSORFLOW)
            tfc.shutdown()
            print("widedeep tfrecord training complete; stats in",
                  os.path.join(args.model_dir, "train_stats.json"))
            return  # finally: sc.stop()
        tfc = cluster.run(sc, map_fun, vars(args),
                          num_executors=args.cluster_size,
                          input_mode=cluster.InputMode.SPARK)
        # Spark-ETL stage: raw lines -> hashed tensors, on the executors
        buckets = args.hash_buckets
        rdd = sc.parallelize(load_lines(), args.cluster_size * 2).map(
            lambda line, _b=buckets: etl(line, _b))
        tfc.train(rdd, num_epochs=args.epochs)
        tfc.shutdown()
    finally:
        sc.stop()
    print("wide&deep training complete; stats in",
          os.path.join(args.model_dir, "train_stats.json"))


if __name__ == "__main__":
    main()
