"""``resnet50-fed``: the system's reason to exist, through its public
entry points - ``engine.Context`` -> ``cluster.run(InputMode.SPARK)`` ->
``train(rdd)`` -> ``ctx.get_data_feed`` -> ``infeed.sharded_batches`` ->
``Trainer.step`` - with the default (``auto``) feed transport.

The trainer process the executor forks holds the chip: it times the
window, traces, runs the comparison and writes its result to a file.
This parent stays off JAX. The record stream ends when the trainer
drops a stop file (a stream that ends, then ``feed.terminate()``).
"""

import functools
import json
import os
import sys
import time
import traceback

from benchmarks.runners import train_common

#: ``shutdown`` ends a trainer whose feed shows no progress for its grace,
#: 60 s by default. The trainer runs the reference once the feed has
#: ended, and in a checkout's first run the reference compiles for longer
#: than that (104 s, PERF.md PR 26): the executor ended the trainer before
#: it had written its result. The grace covers the reference, compiled.
SHUTDOWN_GRACE_S = 900


def _partition(traffic, seed, stop_path, index, _):
    import importlib

    gen = importlib.import_module("benchmarks.generators."
                                  + traffic["generator"])
    return gen.stream(traffic, seed, index, stop_path)


def _map_fun(args, ctx):
    """Runs in the trainer process."""
    from tensorflowonspark_tpu import infeed

    def touch_stop():
        with open(args["stop_path"], "w"):
            pass

    try:
        feed = ctx.get_data_feed(input_mapping={"x": "x", "y": "y"})

        def get_batches(mesh):
            return infeed.sharded_batches(
                feed.numpy_batches(args["batch"]), mesh, timers=feed.timers)

        def snapshot():
            return {"seconds": feed.timers.snapshot(),
                    "samples": feed.timers.counts()}

        def end_feed():
            touch_stop()
            feed.terminate()

        result = train_common.run(args, get_batches, snapshot, end_feed)
        result["counters"]["transport"] = feed.mgr.get("feed_transport")
        result["counters"]["transport_probe"] = feed.mgr.get(
            "feed_transport_probe")
        train_common.dump(result, args["result_path"])
    except BaseException:
        train_common.dump({"error": traceback.format_exc()},
                          args["result_path"])
        raise
    finally:
        touch_stop()


def run(ctx):
    from tensorflowonspark_tpu import cluster
    from tensorflowonspark_tpu.engine import Context

    work = ctx["work_dir"]
    args = train_common.args_for(ctx)
    args["result_path"] = os.path.join(work, "trainer.json")
    args["stop_path"] = os.path.join(work, "stop")
    traffic, parts = ctx["traffic"], ctx["traffic"]["parts"]
    sc = Context(num_executors=1, work_root=work)
    try:
        t_run = time.time()
        tfc = cluster.run(sc, _map_fun, args, num_executors=1,
                          input_mode=cluster.InputMode.SPARK)
        rdd = sc.parallelize(range(parts), parts).mapPartitionsWithIndex(
            functools.partial(_partition, traffic, ctx["seed"],
                              args["stop_path"]))
        tfc.train(rdd, num_epochs=1)
        tfc.shutdown(grace_secs=SHUTDOWN_GRACE_S)
        if not os.path.exists(args["result_path"]):
            raise RuntimeError("the trainer ended without a result: ended "
                               "by the executor's shutdown before its "
                               "comparison was done, or killed")
    except BaseException:
        log = os.path.join(work, "executor-0", "executor.log")
        if os.path.exists(log):
            with open(log, errors="replace") as f:
                print("".join(f.readlines()[-60:]), file=sys.stderr)
        raise
    finally:
        sc.stop()  # the executor and the trainer it forked are reaped here
    with open(args["result_path"]) as f:
        result = json.load(f)
    if "error" in result:
        raise RuntimeError("the trainer failed:\n" + result["error"])
    c = result["counters"]
    c["bootstrap_s"] = c["first_record_epoch"] - t_run
    return result
