"""Tests for the reference-surface parity modules: TFParallel analog,
streaming DStreams, device_info, compat, tfnode."""

import os
import queue

import pytest

from tensorflowonspark_tpu.engine import Context
from tensorflowonspark_tpu.engine.streaming import StreamingContext


@pytest.fixture()
def sc(tmp_path):
    ctx = Context(num_executors=2, work_root=str(tmp_path / "engine"))
    yield ctx
    ctx.stop()


def test_parallel_runner(sc):
    from tensorflowonspark_tpu import parallel_runner

    def map_fn(args, index):
        import jax
        import jax.numpy as jnp

        return {"index": index,
                "n_devices": len(jax.devices()),
                "value": float(jnp.square(jnp.asarray(args["base"] + index)))}

    results = parallel_runner.run(sc, map_fn, {"base": 3}, num_executors=2)
    results = sorted(results, key=lambda r: r["index"])
    assert [r["value"] for r in results] == [9.0, 16.0]
    assert all(r["n_devices"] == 8 for r in results)


def test_parallel_runner_error(sc):
    from tensorflowonspark_tpu import parallel_runner

    def boom(args, index):
        raise ValueError("worker boom %d" % index)

    with pytest.raises(Exception, match="boom"):
        parallel_runner.run(sc, boom, {}, num_executors=2)


def test_streaming_queue_stream(sc):
    seen = []
    ssc = StreamingContext(sc, batch_interval=0.05)
    q = queue.Queue()
    stream = ssc.queueStream(q)
    stream.foreachRDD(lambda rdd: seen.append(sorted(rdd.collect())))
    ssc.start()
    q.put(sc.parallelize([1, 2, 3], 2))
    q.put(sc.parallelize([4, 5], 1))
    import time
    deadline = time.monotonic() + 10
    while len(seen) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    ssc.stop()
    assert seen[:2] == [[1, 2, 3], [4, 5]]


def test_streaming_text_file_stream(sc, tmp_path):
    d = tmp_path / "incoming"
    d.mkdir()
    seen = []
    ssc = StreamingContext(sc, batch_interval=0.05)
    ssc.textFileStream(str(d), num_slices=1).foreachRDD(
        lambda rdd: seen.extend(rdd.collect()))
    ssc.start()
    # hidden files are invisible (Spark semantics): a writer's dotfile
    # tmp must never be read, even once renamed content appears later
    (d / ".b.txt.tmp").write_text("half-writ")
    (d / "a.txt").write_text("one\ntwo\n")
    import time
    deadline = time.monotonic() + 10
    while len(seen) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    import os as _os
    _os.rename(str(d / ".b.txt.tmp"), str(d / "b.txt"))
    deadline = time.monotonic() + 10
    while len(seen) < 3 and time.monotonic() < deadline:
        time.sleep(0.05)
    ssc.stop()
    assert seen == ["one", "two", "half-writ"]


def test_streaming_cluster_train(sc):
    """The reference DStream path: continuous queue-fed training."""
    import json

    from tensorflowonspark_tpu import cluster

    out = {}

    def map_fun(args, ctx):
        feed = ctx.get_data_feed(train_mode=True)
        total = 0
        while not feed.should_stop():
            total += sum(feed.next_batch(16))
        with open(os.path.join(args["dir"], "sum-%d" % ctx.executor_id),
                  "w") as f:
            f.write(json.dumps(total))

    workdir = sc.work_root
    tfc = cluster.run(sc, map_fun, {"dir": workdir}, num_executors=2,
                      input_mode=cluster.InputMode.SPARK)
    ssc = StreamingContext(sc, batch_interval=0.05)
    q = queue.Queue()
    tfc.train(ssc.queueStream(q))
    ssc.start()
    q.put(sc.parallelize(range(10), 2))
    q.put(sc.parallelize(range(10, 20), 2))
    import time
    time.sleep(1.0)
    tfc.shutdown(ssc)
    sums = []
    for name in os.listdir(workdir):
        if name.startswith("sum-"):
            sums.append(json.loads(open(os.path.join(workdir, name)).read()))
    assert sum(sums) == sum(range(20))


def test_device_info_and_compat():
    from tensorflowonspark_tpu import compat, device_info

    # Whatever this host exposes, these must not crash and must agree.
    avail = device_info.is_tpu_available()
    assert isinstance(avail, bool)
    assert compat.is_tpu_available() == avail
    if avail:
        assert device_info.get_devices()
    assert isinstance(device_info.topology_env(), dict)
    assert compat.disable_auto_shard(options={"x": 1}) == {"x": 1}


def _node(eid, host, chips):
    return {"executor_id": eid, "host": host, "chips": chips}


@pytest.mark.parametrize("nodes", [
    [_node(0, "a", "all"), _node(1, "b", "all")],      # one owner per host
    [_node(0, "a", None), _node(1, "a", None)],        # CPU trainers
    [_node(0, "a", "0,1"), _node(1, "a", "2,3")],      # operator-bound
    [_node(0, "a", "all"), _node(1, "a", None)],       # a ps beside a worker
    [{"executor_id": 0, "host": "a"}] * 2,             # pre-claim metadata
])
def test_chip_claims_that_may_form_a_cluster(nodes):
    from tensorflowonspark_tpu import device_info

    device_info.check_one_owner_per_chip(nodes)


@pytest.mark.parametrize("nodes", [
    [_node(0, "a", "all"), _node(1, "a", "all")],
    [_node(0, "a", "0,1"), _node(1, "a", "all")],
    [_node(0, "a", "0,1"), _node(1, "b", "0"), _node(2, "a", "1,2")],
])
def test_two_claimants_for_one_chip_refuse_to_form(nodes):
    from tensorflowonspark_tpu import device_info

    with pytest.raises(RuntimeError, match="a chip belongs to one process"):
        device_info.check_one_owner_per_chip(nodes)


def test_chip_claim_follows_the_environment(monkeypatch):
    from tensorflowonspark_tpu import device_info

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device_info.chip_claim() is None  # what every tier-1 trainer is
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert device_info.chip_claim() == "all"
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert device_info.chip_claim() == "2,3"


def test_tfnode_module(tmp_path):
    import numpy as np

    from tensorflowonspark_tpu import tfnode

    class FakeCtx(object):
        def absolute_path(self, p):
            return "/abs/" + p

    assert tfnode.hdfs_path(FakeCtx(), "model") == "/abs/model"
    assert tfnode.DataFeed is not None

    d = str(tmp_path / "exp")
    tfnode.export_saved_model(
        d, lambda v, b: {"y": b["x"] + v["c"]}, {"c": np.asarray(1.0)},
        signature={"inputs": ["x"], "outputs": ["y"]})
    from tensorflowonspark_tpu import export
    fn, variables, sig = export.load_model(d)
    assert float(fn(variables, {"x": np.asarray([2.0])})["y"][0]) == 3.0


def test_tune_malloc_idempotent_and_gated(monkeypatch):
    """Feed-plane allocator tuning: applies once on glibc, honors the
    TFOS_MALLOC_TUNE=0 gate (fresh module state via reload)."""
    import importlib

    from tensorflowonspark_tpu import util as util_mod

    assert util_mod.tune_malloc() in (True, False)
    first = util_mod._MALLOC_TUNED
    assert util_mod.tune_malloc() == first  # idempotent

    mod = importlib.reload(util_mod)
    try:
        monkeypatch.setenv("TFOS_MALLOC_TUNE", "0")
        assert mod.tune_malloc() is False
    finally:
        importlib.reload(util_mod)
