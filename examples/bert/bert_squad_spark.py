"""BERT SQuAD-style fine-tune from a DataFrame text feed — BASELINE
config #3 ("Spark DataFrame text feed -> TPU infeed").

The driver tokenizes host-side (ETL in the DataFrame world), feeds
(input_ids, attention_mask, start, end) rows through the queue plane, and
every node fine-tunes the QA span head data-parallel. Synthetic QA pairs
by default (zero-egress env): the answer span is a repeated marker token
the model must learn to locate — convergence is observable in minutes.

CPU dev run::

    JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/bert/bert_squad_spark.py --cluster_size 2 --epochs 2
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

VOCAB = 1024
SEQ = 64
MARKER = 7  # the "answer" token the span head must locate


def tokenize(text, vocab=VOCAB):
    """Whitespace + stable-hash tokenizer (the ETL step; a real run swaps
    in WordPiece here — the feed contract doesn't change)."""
    ids = []
    for w in text.split():
        h = 0
        for ch in w.encode("utf-8"):
            h = (h * 131 + ch) % (vocab - 16)
        ids.append(h + 16)
    return ids


def rows_from_text(path, seed=0):
    """Real-text path: tokenize each line (the DataFrame ETL step) and
    plant a marker answer span the head must learn to locate."""
    rng = np.random.RandomState(seed)
    rows = []
    for line in open(path).read().splitlines():
        ids = tokenize(line)[:SEQ]
        if len(ids) < 8:
            continue
        start = rng.randint(0, len(ids) - 3)
        span = rng.randint(2, 4)
        for j in range(start, min(start + span, len(ids))):
            ids[j] = MARKER
        rows.append({"input_ids": ids, "start": int(start),
                     "end": int(min(start + span, len(ids)) - 1)})
    if not rows:
        raise ValueError("no usable lines in " + path)
    return rows


def synthetic_rows(n, seed=0):
    rng = np.random.RandomState(seed)
    rows = []
    for _ in range(n):
        length = rng.randint(SEQ // 2, SEQ)
        ids = rng.randint(16, VOCAB, size=length)
        start = rng.randint(0, length - 3)
        span = rng.randint(2, 4)
        ids[start:start + span] = MARKER
        rows.append({"input_ids": ids.tolist(),
                     "start": int(start), "end": int(start + span - 1)})
    return rows


def map_fun(args, ctx):
    import jax
    import optax

    from tensorflowonspark_tpu import infeed, training
    from tensorflowonspark_tpu.models import bert

    ctx.initialize_jax()
    mesh = ctx.mesh()
    cfg = bert.bert_base() if args["full_size"] else bert.bert_tiny(VOCAB)
    model = bert.BertForQuestionAnswering(cfg)
    trainer = training.Trainer(
        model, optax.adamw(args["lr"]), mesh, loss_fn=bert.qa_span_loss,
        input_keys=("input_ids", "attention_mask"), dropout_rng=True)

    feed = ctx.get_data_feed(train_mode=True)

    def batches():
        B = args["batch_size"]
        for records in feed.numpy_batches(B, pad_to_batch=True):
            ids = np.zeros((B, SEQ), np.int32)
            mask = np.zeros((B, SEQ), bool)
            start = np.zeros((B,), np.int32)
            end = np.zeros((B,), np.int32)
            for i, (row_ids, s, e) in enumerate(records):
                row_ids = row_ids[:SEQ]
                ids[i, :len(row_ids)] = row_ids
                mask[i, :len(row_ids)] = True
                start[i], end[i] = s, e
            yield {"input_ids": ids, "attention_mask": mask,
                   "start_positions": start, "end_positions": end}

    sample = {"input_ids": np.zeros((8, SEQ), np.int32),
              "attention_mask": np.ones((8, SEQ), bool)}
    state = trainer.init(jax.random.PRNGKey(0), sample)
    state, steps, rate = trainer.train_loop(
        state, infeed.sharded_batches(batches(), mesh), log_every=10)
    if ctx.job_name == "chief":
        import json

        out = ctx.absolute_path(args["model_dir"])
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "train_stats.json"), "w") as f:
            json.dump({"steps": steps, "examples_per_sec": rate}, f)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster_size", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--num_examples", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full_size", action="store_true",
                    help="BERT-base (default: tiny config, same code path)")
    ap.add_argument("--text_file", default=None,
                    help="tokenize real text lines instead of synthetic "
                         "pre-tokenized rows")
    ap.add_argument("--model_dir", default=".scratch/bert_model")
    args = ap.parse_args(argv)
    logging.basicConfig(level="INFO")

    sc = Context(num_executors=args.cluster_size)
    try:
        tfc = cluster.run(sc, map_fun, vars(args),
                          num_executors=args.cluster_size,
                          input_mode=cluster.InputMode.SPARK)
        # DataFrame ETL: tokenized rows -> (ids, start, end) feed tuples
        rows = (rows_from_text(args.text_file) if args.text_file
                else synthetic_rows(args.num_examples))
        df = sc.createDataFrame(rows, num_slices=args.cluster_size * 2)
        rdd = df.rdd.map(lambda r: (r["input_ids"], r["start"], r["end"]))
        tfc.train(rdd, num_epochs=args.epochs)
        tfc.shutdown()
    finally:
        sc.stop()
    print("bert fine-tune complete; stats in",
          os.path.join(args.model_dir, "train_stats.json"))


if __name__ == "__main__":
    main()
