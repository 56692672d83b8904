"""End-to-end cluster micro-jobs.

Reference test strategy (SURVEY.md §4 ``tests/test_TFCluster.py``): run the
full bootstrap on a real multi-process engine on one host, with trivial
map_funs — a sum-the-fed-numbers trainer, a SPARK-mode train + inference
round-trip, an inline TENSORFLOW-mode run, and shutdown error propagation.
"""

import json
import os

import pytest

from tensorflowonspark_tpu import cluster
from tensorflowonspark_tpu.engine import Context


@pytest.fixture()
def sc(tmp_path):
    ctx = Context(num_executors=2, work_root=str(tmp_path / "engine"))
    yield ctx
    ctx.stop()


def test_spark_mode_train_roundtrip(sc, tmp_path):
    """Queue-fed training: each node sums what it is fed; totals add up."""
    out_dir = str(tmp_path / "sums")
    os.makedirs(out_dir)

    def map_fun(args, ctx):
        feed = ctx.get_data_feed(train_mode=True)
        total = 0
        count = 0
        while not feed.should_stop():
            batch = feed.next_batch(8)
            total += sum(batch)
            count += len(batch)
        with open(os.path.join(args["out_dir"],
                               "node-{}.json".format(ctx.executor_id)), "w") as f:
            json.dump({"total": total, "count": count,
                       "job_name": ctx.job_name,
                       "task_index": ctx.task_index,
                       "num_workers": ctx.num_workers}, f)

    tfc = cluster.run(sc, map_fun, {"out_dir": out_dir}, num_executors=2,
                      input_mode=cluster.InputMode.SPARK)
    assert len(tfc.cluster_info) == 2
    data = sc.parallelize(range(100), 4)
    tfc.train(data, num_epochs=2)
    tfc.shutdown()

    files = sorted(os.listdir(out_dir))
    assert len(files) == 2
    stats = [json.load(open(os.path.join(out_dir, f))) for f in files]
    assert sum(s["total"] for s in stats) == sum(range(100)) * 2
    assert sum(s["count"] for s in stats) == 200
    assert sorted(s["job_name"] for s in stats) == ["chief", "worker"]
    assert all(s["num_workers"] == 2 for s in stats)


def test_spark_mode_inference_roundtrip(sc):
    """Inference: every record comes back transformed, count preserved."""

    def map_fun(args, ctx):
        feed = ctx.get_data_feed(train_mode=False)
        while not feed.should_stop():
            batch = feed.next_batch(8)
            if batch:
                feed.batch_results([x * 10 for x in batch])

    tfc = cluster.run(sc, map_fun, {}, num_executors=2,
                      input_mode=cluster.InputMode.SPARK)
    data = sc.parallelize(range(20), 4)
    results = tfc.inference(data).collect()
    # EXACT order, not a multiset: the reference guarantees per-partition
    # count/order (q_in.join() + counted q_out reads, SURVEY.md §7.3
    # names it a hard part), and collect() reassembles partitions in
    # order — so the round trip must be order-preserving end to end.
    assert results == [x * 10 for x in range(20)]
    tfc.shutdown()


def test_inference_deep_partition_no_wedge(sc):
    """Results drain concurrently with feeding (ADVICE r3): a partition
    deep enough to fill BOTH bounded queues (input 16 chunks x 256
    records, output 256 result items) must stream through instead of
    deadlocking trainer batch_results against feeder backpressure."""

    def map_fun(args, ctx):
        feed = ctx.get_data_feed(train_mode=False)
        while not feed.should_stop():
            batch = feed.next_batch(8)
            if batch:
                feed.batch_results([x + 1 for x in batch])

    prev = os.environ.get("TFOS_FEED_TRANSPORT")
    os.environ["TFOS_FEED_TRANSPORT"] = "queue"
    try:
        tfc = cluster.run(sc, map_fun, {}, num_executors=2,
                          input_mode=cluster.InputMode.SPARK)
        n = 8000  # > 16*256 buffered input + > 256 buffered result lists
        data = sc.parallelize(range(n), 2)
        results = tfc.inference(data, feed_timeout=60).collect()
        assert len(results) == n
        # exact order even with both queues cycling through backpressure
        assert results == [x + 1 for x in range(n)]
        tfc.shutdown()
    finally:
        if prev is None:
            os.environ.pop("TFOS_FEED_TRANSPORT", None)
        else:
            os.environ["TFOS_FEED_TRANSPORT"] = prev


def test_tensorflow_mode_inline(sc, tmp_path):
    """InputMode.TENSORFLOW: fn runs inline; run() returns after barrier."""
    out_dir = str(tmp_path / "marks")
    os.makedirs(out_dir)

    def map_fun(args, ctx):
        with open(os.path.join(args["out_dir"],
                               "node-{}".format(ctx.executor_id)), "w") as f:
            f.write("{}:{}".format(ctx.job_name, ctx.task_index))

    tfc = cluster.run(sc, map_fun, {"out_dir": out_dir}, num_executors=2,
                      input_mode=cluster.InputMode.TENSORFLOW)
    tfc.shutdown()
    assert sorted(os.listdir(out_dir)) == ["node-0", "node-1"]


def test_spark_mode_error_propagates(sc):
    """A trainer exception must surface as a driver-side raise at shutdown."""

    def map_fun(args, ctx):
        feed = ctx.get_data_feed(train_mode=True)
        feed.next_batch(1)
        raise ValueError("boom on node {}".format(ctx.executor_id))

    tfc = cluster.run(sc, map_fun, {}, num_executors=2,
                      input_mode=cluster.InputMode.SPARK)
    data = sc.parallelize(range(10), 2)
    tfc.train(data)
    with pytest.raises(RuntimeError) as err:
        tfc.shutdown(grace_secs=1)
    assert "boom" in str(err.value.__cause__ or err.value)


def test_tensorflow_mode_error_propagates(sc):
    """Inline map_fun exception fails the bootstrap job -> shutdown raises."""

    def map_fun(args, ctx):
        if ctx.job_name == "worker":
            raise ValueError("inline boom")

    tfc = cluster.run(sc, map_fun, {}, num_executors=2,
                      input_mode=cluster.InputMode.TENSORFLOW)
    with pytest.raises(RuntimeError):
        tfc.shutdown()


def test_cluster_spec_shape(sc):
    """cluster_spec has the TF_CONFIG shape; tensorboard_url None if off."""
    seen = {}

    def map_fun(args, ctx):
        pass

    tfc = cluster.run(sc, map_fun, {}, num_executors=2,
                      input_mode=cluster.InputMode.TENSORFLOW)
    assert tfc.tensorboard_url() is None
    info = tfc.cluster_info
    assert [n["executor_id"] for n in info] == [0, 1]
    assert info[0]["job_name"] == "chief"
    assert info[1]["job_name"] == "worker"
    tfc.shutdown()


def test_ps_and_evaluator_roles(tmp_path):
    """Role-template parity: num_ps and eval_node create ps/evaluator
    nodes whose fns run with those job names, parked OUTSIDE the device
    collective (they are not participants)."""
    out = str(tmp_path / "roles")
    os.makedirs(out)

    def map_fun(args, ctx):
        participants = [n["job_name"] for n in ctx.participants()]
        with open(os.path.join(args["out"],
                               "role-%d" % ctx.executor_id), "w") as f:
            f.write("{}|{}".format(ctx.job_name, ",".join(participants)))

    sc = Context(num_executors=3, work_root=str(tmp_path / "engine"))
    try:
        tfc = cluster.run(sc, map_fun, {"out": out}, num_executors=3,
                          num_ps=1, eval_node=True,
                          input_mode=cluster.InputMode.TENSORFLOW)
        tfc.shutdown()
    finally:
        sc.stop()

    roles = {}
    for name in os.listdir(out):
        job, parts = open(os.path.join(out, name)).read().split("|")
        roles[job] = parts.split(",")
    assert set(roles) == {"ps", "chief", "evaluator"}
    # every node agrees: only the chief joins the device collective
    for parts in roles.values():
        assert parts == ["chief"], roles


def test_shutdown_grace_rearms_on_feed_progress(tmp_path):
    """A trainer slowly stepping through its buffered backlog outlives a
    grace window shorter than the drain, because the DataFeed heartbeat
    re-arms the no-progress deadline (the old hard join cap once killed
    a live trainer whose steps ran ~4s each over a slow link).
    Chunks land in DataFeed._pending long before the last batch is
    served, so this exercises the no-queue-traffic drain phase."""
    out = str(tmp_path / "done.json")

    def map_fun(args, ctx):
        import time as _t
        feed = ctx.get_data_feed(train_mode=True)
        total = 0
        while not feed.should_stop():
            batch = feed.next_batch(4)
            total += sum(batch)
            _t.sleep(0.8)  # slow "step": full drain ~8s >> 4s grace
        # the file is the proof the trainer was NOT killed mid-drain
        with open(args["out"], "w") as f:
            json.dump({"total": total}, f)

    sc = Context(num_executors=1, work_root=str(tmp_path / "engine"))
    try:
        tfc = cluster.run(sc, map_fun, {"out": out}, num_executors=1,
                          input_mode=cluster.InputMode.SPARK)
        tfc.train(sc.parallelize(range(40), 1))
        tfc.shutdown(grace_secs=4)
    finally:
        sc.stop()
    assert json.load(open(out))["total"] == sum(range(40))


def test_shutdown_still_kills_wedged_trainer(tmp_path):
    """The progress-aware grace is still a liveness bound: a trainer that
    stops serving batches (wedged in user code) is terminated once the
    heartbeat goes stale, and shutdown returns promptly."""
    import time as _time
    out = str(tmp_path / "never.json")

    def map_fun(args, ctx):
        import time as _t
        feed = ctx.get_data_feed(train_mode=True)
        while not feed.should_stop():
            feed.next_batch(4)  # prompt consumption: the feed join returns
        _t.sleep(120)  # wedge AFTER the feed: heartbeat goes stale
        with open(args["out"], "w") as f:
            f.write("{}")

    sc = Context(num_executors=1, work_root=str(tmp_path / "engine"))
    try:
        tfc = cluster.run(sc, map_fun, {"out": out}, num_executors=1,
                          input_mode=cluster.InputMode.SPARK)
        tfc.train(sc.parallelize(range(40), 1))
        t0 = _time.monotonic()
        tfc.shutdown(grace_secs=3)
        elapsed = _time.monotonic() - t0
    finally:
        sc.stop()
    assert elapsed < 30, "wedged trainer not reaped within grace bounds"
    assert not os.path.exists(out)
