"""Example-surface smoke: every shipped example runs end to end, tiny.

Reference test strategy (SURVEY.md §4): the reference's examples ARE its
integration surface — users start from them, so a broken example is a
broken product even when the library suite is green. Each test runs the
real driver script in a subprocess exactly as the README documents, on
the virtual CPU mesh, with the smallest shapes that still train/infer.

Marked ``slow``: `make test` runs them, `make test-fast` skips.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               TFOS_TPU_DISTRIBUTED="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)] + list(args),
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_ROOT)
    assert out.returncode == 0, \
        "{} failed:\n{}".format(script, out.stdout[-2000:] +
                                out.stderr[-2000:])
    return out


def _stats(model_dir):
    with open(os.path.join(model_dir, "train_stats.json")) as f:
        return json.load(f)


def test_mnist_spark(tmp_path):
    data = str(tmp_path / "mnist")
    _run("examples/mnist/mnist_data_setup.py", "--output", data,
         "--num-train", "512", "--num-test", "64", "--format", "csv")
    model = str(tmp_path / "model")
    _run("examples/mnist/mnist_spark.py", "--cluster_size", "2",
         "--images", os.path.join(data, "train"), "--model_dir", model,
         "--batch_size", "32", "--log_every", "5")
    assert _stats(model)["steps"] > 0


def test_bert_squad(tmp_path):
    model = str(tmp_path / "bert")
    _run("examples/bert/bert_squad_spark.py", "--cluster_size", "2",
         "--num_examples", "64", "--batch_size", "8", "--model_dir", model)
    assert _stats(model)["steps"] > 0


def test_inception_inference(tmp_path):
    out = str(tmp_path / "preds")
    _run("examples/inception/inception_inference.py", "--cluster_size", "2",
         "--num_images", "16", "--batch_size", "4", "--image_size", "64",
         "--num_classes", "10", "--output", out)
    files = os.listdir(out)
    assert files, "no prediction output written"


def test_criteo_tfrecord_roundtrip(tmp_path):
    """ETL -> materialized dense shards -> InputMode.TENSORFLOW training
    via the native batched decoder (the --save_tfrecords/--tfrecord_dir
    pair added for the W&D config)."""
    shards = str(tmp_path / "shards")
    model = str(tmp_path / "wd")
    _run("examples/criteo/criteo_spark.py", "--num_examples", "512",
         "--save_tfrecords", shards)
    _run("examples/criteo/criteo_spark.py", "--cluster_size", "2",
         "--epochs", "1", "--tfrecord_dir", shards,
         "--batch_size", "32", "--model_dir", model)
    stats = _stats(model)
    assert stats["input"] == "tfrecord"
    assert stats["steps"] > 0
    assert stats["reader_records_per_sec"] > 0


def test_criteo_sharded_embedding_table(tmp_path):
    """--tp row-shards the fused embedding tables over the model axis
    (VERDICT r4 task 5). Modest 1.3M-row table in CI; the 10M-row run is
    a ledger result (BASELINE.md) — same code path, bigger knob."""
    model = str(tmp_path / "wd_tp")
    qdir = str(tmp_path / "wd_q")
    _run("examples/criteo/criteo_spark.py", "--cluster_size", "1",
         "--tp", "2", "--hash_buckets", "50000", "--num_examples", "512",
         "--batch_size", "64", "--epochs", "1", "--model_dir", model,
         "--quantize_export", qdir)
    stats = _stats(model)
    assert stats["table_rows"] == 26 * 50000
    assert stats["steps"] > 0 and stats["examples_per_sec"] > 0
    assert stats["feed_stats"]["records"] == 512

    # the exported int8 model serves: one REST predict round trip
    import urllib.request

    from tensorflowonspark_tpu import serving
    with serving.ModelServer(qdir, name="wd", port=0) as srv:
        req = urllib.request.Request(
            "http://%s:%d/v1/models/wd:predict" % (srv._host, srv._port),
            data=json.dumps({"inputs": {
                "dense": [[0.0] * 13], "cat": [[1] * 26]}}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
    assert len(out["outputs"]) == 1
    assert isinstance(out["outputs"][0], float)


def test_lm_generate(tmp_path):
    """Decoder LM trains on a periodic pattern and the KV-cache decode
    continues it exactly (the observable proof the cache works)."""
    out = str(tmp_path / "gen.json")
    _run("examples/generate/lm_generate.py", "--steps", "150",
         "--serve", "4", "--out", out)
    result = json.load(open(out))
    assert result["loss"] < 0.1, result
    # the continuous-batching serving leg ran and agreed with solo decode
    assert result["serve"]["requests"] == 4, result
    assert result["serve"]["solo_mismatches"] == 0, result
    period = 4
    start = (result["prompt"][-1] + 1) % period
    want = [(start + i) % period for i in range(len(result["generated"]))]
    assert result["generated"] == want, result


def test_longcontext(tmp_path):
    _run("examples/longcontext/train_long.py", "--seq_len", "256",
         "--steps", "4", "--batch", "1", "--hidden", "32", "--layers", "1")


def test_segmentation_spark(tmp_path):
    """U-Net dense prediction through the SPARK feed (the reference's
    examples/segmentation family)."""
    model = str(tmp_path / "seg")
    _run("examples/segmentation/segmentation_spark.py", "--cluster_size", "2",
         "--num_examples", "192", "--batch_size", "16", "--image_size", "32",
         "--model_dir", model)
    stats = _stats(model)
    assert stats["steps"] > 0
    # 3-class problem: random guessing sits near ~0.2 macro IoU; even a
    # dozen smoke steps separates shapes from background
    assert stats["val_mean_iou"] > 0.3


def test_mnist_pipeline(tmp_path):
    """ML Pipeline API at example level: TFEstimator.fit spins the
    cluster from a DataFrame, TFModel.transform serves the export
    (reference examples/mnist/{keras,estimator} family)."""
    out = _run("examples/mnist/mnist_pipeline.py", "--cluster_size", "2",
               "--images", str(tmp_path / "mnist"),
               "--num_train", "768", "--epochs", "2",
               "--export_dir", str(tmp_path / "export"))
    line = [ln for ln in out.stdout.splitlines()
            if "test accuracy" in ln][-1]
    acc = float(line.split("test accuracy")[1].split()[0])
    # load_digits upscaled; LeNet reaches ~0.85 in two smoke epochs.
    # Anything below coin-flip-on-10-classes x5 means the pipeline fed
    # garbage (mapping/order bugs), which is what this guards.
    assert acc > 0.5, line


def test_cifar10_spark(tmp_path):
    """Cluster-fed image classification at CIFAR shape through the SPARK
    feed (the reference's examples/cifar10 family; examples/resnet covers
    the same model in InputMode.TENSORFLOW)."""
    model = str(tmp_path / "cifar")
    _run("examples/cifar10/cifar10_spark.py", "--cluster_size", "2",
         "--num_examples", "192", "--batch_size", "32", "--model_dir", model)
    assert _stats(model)["steps"] > 0


def test_resnet_resume(tmp_path):
    """Submit the resnet job twice with --ckpt_dir: the second run must
    resume from the first's final step, not restart (the recovery story
    at example level)."""
    model = str(tmp_path / "model")
    args = ["examples/resnet/resnet_spark.py", "--cluster_size", "2",
            "--steps", "4", "--batch_size", "16", "--model_dir", model,
            "--ckpt_dir", str(tmp_path / "ckpt"), "--ckpt_every", "2"]
    _run(*args)
    first = _stats(model)
    assert first["start_step"] == 0 and first["end_step"] > 0
    _run(*args)
    second = _stats(model)
    assert second["start_step"] == first["end_step"]
    assert second["end_step"] > second["start_step"]


def test_resnet_profile(tmp_path):
    """--profile: device-trace capture + TensorBoard summaries at example
    level (SURVEY §5 tracing row's user-facing surface)."""
    import glob

    model = str(tmp_path / "model")
    _run("examples/resnet/resnet_spark.py", "--cluster_size", "2",
         "--steps", "4", "--batch_size", "16", "--model_dir", model,
         "--profile", "--log_every", "2")
    assert glob.glob(os.path.join(model, "tb", "trace", "plugins",
                                  "profile", "*", "*.xplane.pb")), \
        "no profiler trace captured"
    assert glob.glob(os.path.join(model, "tb", "events.out.tfevents.*")), \
        "no TensorBoard summaries written"


def test_streaming_mnist(tmp_path):
    """Continuous training from a spooled directory stream (the
    reference's Spark Streaming mode at example level): micro-batches
    land as files, trainers consume across intervals, shutdown stops
    the stream before ending the feed."""
    model = str(tmp_path / "model")
    _run("examples/streaming/streaming_mnist.py", "--cluster_size", "2",
         "--intervals", "2", "--interval_examples", "128",
         "--interval_secs", "1.5",
         "--spool_dir", str(tmp_path / "spool"), "--model_dir", model)
    assert _stats(model)["steps"] > 0


def test_inception_train_export_infer_roundtrip(tmp_path):
    """Distributed Inception train -> eval -> export -> cluster inference
    from the export (the reference's imagenet/inception training side)."""
    model = str(tmp_path / "model")
    export_dir = str(tmp_path / "export")
    out = _run("examples/inception/inception_train.py", "--cluster_size", "2",
               "--num_examples", "96", "--batch_size", "16",
               "--image_size", "75", "--num_classes", "4",
               "--model_dir", model, "--export_dir", export_dir)
    stats = _stats(model)
    assert stats["steps"] > 0
    # a dozen smoke steps of from-scratch Inception is too noisy for a
    # learning bar (observed 0.25-0.56 across seeds); the smoke asserts
    # the eval pass ran and reported a sane value — learning-at-smoke is
    # proven by the mnist/segmentation/pipeline examples
    assert 0.0 <= stats["val_accuracy"] <= 1.0, stats
    preds = str(tmp_path / "preds")
    _run("examples/inception/inception_inference.py", "--cluster_size", "2",
         "--num_images", "8", "--batch_size", "4", "--image_size", "75",
         "--num_classes", "4", "--export_dir", export_dir,
         "--output", preds)
    assert os.listdir(preds)
