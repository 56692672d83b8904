"""The trainer's side of both ResNet cells, run by the process that
holds the chip: build ONE compiled step with its state, drive it from
the seed through its first three steps (which the reference follows),
warm up, hand the same object to the timed window, then free it and
run the plain reference.

The system under test is ``training.Trainer.step`` on the program's
``ResNet`` (and, for the fed cell, everything that delivers the
batches). The weights are the benchmark's own, from the seed, placed in
the layout the program reads.
"""

import importlib
import json
import os
import time

from benchmarks import common

FOLLOWED_STEPS = 3


def _trace_leaves(opt_state, n_params):
    """The momentum buffer inside an optax state: after one step of
    SGD with momentum it IS the first gradient as the optimizer got
    it."""
    import jax

    leaves = jax.tree.leaves(opt_state)
    if len(leaves) != n_params:
        raise RuntimeError("optimizer state has {} leaves, parameters {}: "
                           "cannot find the momentum buffer".format(
                               len(leaves), n_params))
    return leaves


def gaps(program, reference):
    """The numbers compared, from the two sides' readings (see
    ``reference/resnet50.follow``): each step's relative loss gap, and
    by the worst leaf the gap between the program's norm and the
    reference's (NOT the norm of a difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger. Leaves whose reference gradient is under a thousandth of
    the median leaf's are left out of the change (they move by
    round-off alone)."""
    out = {}
    for k, (a, b) in enumerate(zip(program["losses"],
                                   reference["losses"])):
        out["loss_gap.%d" % (k + 1)] = abs(a - b) / max(abs(b), 1e-12)
    g_ref = reference["grad_norms"]
    g_med = common.median(g_ref)
    g_gaps = [abs(a - b) / max(b, g_med)
              for a, b in zip(program["grad_norms"], g_ref)]
    c_ref = reference["change_norms"]
    moved = [i for i, g in enumerate(g_ref) if g >= 1e-3 * g_med]
    c_med = common.median([c_ref[i] for i in moved])
    c_gaps = [abs(program["change_norms"][i] - c_ref[i])
              / max(c_ref[i], c_med) for i in moved]
    # the worst leaf swings from seed to seed by its nature; the median
    # leaf is steady, and is what catches a fault that moves every leaf
    # a little (half of the batch left out)
    out["grad_gap"], out["grad_gap_median"] = max(g_gaps), \
        common.median(g_gaps)
    out["change_gap"], out["change_gap_median"] = max(c_gaps), \
        common.median(c_gaps)
    return out


def judge(numbers, limits, not_compared):
    """``(Checks, {name: value} printed and not judged)``. A number is
    held to its limit unless the cell's file NAMES it as not compared
    (no reading separates it, PERF.md); one with neither a limit nor
    that mention is an error, so a deleted key cannot weaken
    ``correct`` unseen."""
    checks, left_out = common.Checks(), {}
    for name, value in numbers.items():
        if name in not_compared:
            left_out[name] = value
        elif name in limits:
            checks.add(name, value, limits[name])
        else:
            raise KeyError("the cell's file gives {!r} neither a limit "
                           "nor a place in not_compared".format(name))
    return checks, left_out


def run(args, get_batches, snapshot=None, on_window_end=None, broken=None,
        also=None):
    """Set-up, window and comparison. ``args`` is plain data (it crosses
    a process boundary in the fed cell):

      platform, chips, seed, seconds, trace, trace_dir, trace_seconds,
      model, optimizer, batch, image, warm_steps, limits, not_compared,
      reference, generator, traffic, t0_epoch

    ``get_batches(mesh)`` gives the iterator of device batches
    (``{"x", "y"}``) that the followed steps, the warm-up and the window
    all draw from; ``snapshot()`` the feed's stage timers as
    ``{"seconds", "samples"}`` (None where nothing feeds);
    ``on_window_end()`` ends the feed. ``broken`` is for the tests only:
    a function wrapping ``trainer.step`` (a fault planted under the
    timed path). Returns the result as plain data."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.models.resnet import ResNet
    from tensorflowonspark_tpu.parallel import build_mesh

    marks = [("start", args["t0_epoch"]), ("imports", time.time())]
    device = common.device_record(args["platform"], args["chips"])
    marks.append(("jax_ready", time.time()))
    stats = common.CompileStats()
    ref = importlib.import_module(
        "benchmarks.reference." + args["reference"])
    gen = importlib.import_module(
        "benchmarks.generators." + args["generator"])
    model, opt = args["model"], args["optimizer"]
    batch, image = args["batch"], args["image"]
    devices = jax.devices()[:args["chips"]]
    mesh = build_mesh({"data": len(devices)}, devices=devices)
    trainer = training.Trainer(
        ResNet(stage_sizes=model["stage_sizes"],
               num_classes=model["num_classes"], width=model["width"]),
        optax.sgd(opt["learning_rate"], momentum=opt["momentum"]), mesh)
    step = trainer.step if broken is None else broken(trainer.step)
    key = jax.random.PRNGKey(args["seed"] % (2 ** 32))
    state = trainer.init(key, np.zeros((batch, image, image, 3),
                                       np.float32))
    make_params = jax.jit(lambda k: ref.init_params(k, model),
                          out_shardings=trainer.replicated)
    params = make_params(key)
    if jax.tree.map(lambda a: (a.shape, a.dtype), params) != jax.tree.map(
            lambda a: (a.shape, a.dtype), state["params"]):
        raise RuntimeError("the benchmark's weights do not have the "
                           "layout the program's ResNet reads")
    state = dict(state, params=params)
    n_leaves = len(jax.tree.leaves(params))
    p0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)
    del params
    norms = jax.jit(ref.leaf_norms)
    change = jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree.map(lambda x, y: x - y, a, b)))
    tags_of = jax.jit(lambda x: x[:, 0, 0, :])

    marks.append(("state", time.time()))
    batches = iter(get_batches(mesh))
    t_first = None
    program = {"losses": []}
    followed = []  # [(record ids, labels as they sat on the device)]
    for k in range(FOLLOWED_STEPS):
        b = next(batches)
        if t_first is None:
            t_first = time.time()
        followed.append((gen.untag(np.asarray(tags_of(b["x"]))),
                         np.asarray(b["y"])))
        state, m = step(state, b)
        program["losses"].append(float(m["loss"]))
        if k == 0:
            program["grad_norms"] = [float(v) for v in norms(
                _trace_leaves(state["opt_state"], n_leaves))]
    program["change_norms"] = [float(v) for v in
                               change(state["params"], p0)]
    del p0
    marks += [("first_batch", t_first), ("followed_steps", time.time())]
    for _ in range(args["warm_steps"]):
        b = next(batches)
        state, m = step(state, b)
        float(m["loss"])

    # ---- the window: the same object, the same call, the same feed ----
    marks.append(("warm_steps", time.time()))
    # With --trace 1 the first ``trace_seconds`` run under the profiler;
    # the window that the per-layer counters cover starts once the trace
    # has been written out (the same object, call and feed all through).
    trace = None
    if args["trace"]:
        trace = common.TraceWindow(args["trace_dir"], args["trace_seconds"])
        trace.start()
        while not trace.done.is_set():
            with jax.profiler.TraceAnnotation("bench:next_batch"):
                b = next(batches)
            with jax.profiler.TraceAnnotation("bench:step"):
                state, m = step(state, b)
                float(m["loss"])
        trace.join()
    steps_before_window = int(state["step"])
    snap0 = snapshot() if snapshot else None
    compiled_before = stats.programs
    step_ms, wait_ms, losses, tag_arrays, kept = [], [], [], [], {}
    keep_at = args["seed"] % 16  # one early batch and the last are kept
    load0 = common.host_load()
    setup_s = time.time() - args["t0_epoch"]
    t0 = time.monotonic()
    while True:
        ta = time.monotonic()
        b = next(batches)
        tb = time.monotonic()
        state, m = step(state, b)
        losses.append(float(m["loss"]))  # waits for the step
        tc = time.monotonic()
        tag_arrays.append(tags_of(b["x"]))
        if len(step_ms) == keep_at:
            kept["early"] = b
        wait_ms.append((tb - ta) * 1e3)
        step_ms.append((tc - tb) * 1e3)
        if tc - t0 >= args["seconds"]:
            break
    window_s = tc - t0
    load = common.host_load_between(load0, common.host_load())
    snap1 = snapshot() if snapshot else None
    kept["last"] = b
    compiled_in_window = stats.programs - compiled_before
    consumed = np.concatenate([np.asarray(t) for t in tag_arrays])
    del tag_arrays
    if on_window_end is not None:
        on_window_end()
    peak = common.memory_peak_bytes(len(devices))
    mem_stats = common.memory_stats(devices[0])
    final_step = int(state["step"])
    kept = {k: (np.asarray(v["x"]), np.asarray(v["y"]))
            for k, v in kept.items()}
    del state, b, m, batches
    steps = len(step_ms)

    # ---- the comparison, once the window has closed -------------------
    t_ref = time.monotonic()
    ref_batches = []
    for gids, labels in followed:
        xs, ys = gen.rebuild(args["traffic"], args["seed"], gids)
        ref_batches.append((xs, ys))
    labels_match = all(np.array_equal(ys, labels) for (_, ys), (_, labels)
                       in zip(ref_batches, followed))
    reference = ref.follow(make_params(key), ref_batches, model, opt)
    calibration = also(ref, program, reference, ref_batches,
                       lambda: make_params(key)) if also else None
    checks, not_compared = judge(gaps(program, reference), args["limits"],
                                 args["not_compared"])
    # what reached the device is what was fed: whole batches of the
    # window against the generator's own, and every record's identity
    differ = 0
    for xs, ys in kept.values():
        want_x, want_y = gen.rebuild(args["traffic"], args["seed"],
                                     gen.untag(xs[:, 0, 0, :]))
        differ += int(not (np.array_equal(xs, want_x)
                           and np.array_equal(ys, want_y)))
    checks.add("window_batches_differ", differ, 0)
    checks.add("followed_labels_differ", 0 if labels_match else 1, 0)
    if args["traffic"].get("records_distinct"):
        all_ids = np.concatenate([g for g, _ in followed]
                                 + [gen.untag(consumed)])
        checks.add("records_repeated",
                   int(len(all_ids) - len(np.unique(all_ids))), 0)
    checks.add("steps_missing", steps_before_window + steps - final_step, 0)
    checks.add("loss_not_finite", sum(1 for x in losses if not x == x
                                      or abs(x) == float("inf")), 0)
    checks.add("compiled_in_window", compiled_in_window, 0)
    flops = importlib.import_module("benchmarks.flops." + args["flops"])
    return {
        "device": dict(device, memory_peak_bytes=peak),
        "correct": checks.ok, "attempted": steps, "failed": 0,
        "checks": checks.as_dict(),
        "end_to_end": {
            "train_images_per_s": steps * batch / window_s,
            "setup_s": setup_s},
        "counters": {
            "window": {"seconds": window_s, "steps": steps,
                       "next_batch_seconds": sum(wait_ms) / 1e3,
                       "images": steps * batch,
                       "model_flops": steps * batch
                       * flops.train_flops_per_image(model, image),
                       "traced_seconds": args["trace_seconds"]
                       if args["trace"] else None},
            "step": {"median_ms": common.median(step_ms),
                     "p95_ms": common.percentile(step_ms, 95),
                     "next_batch_median_ms": common.median(wait_ms),
                     "slowest": sorted(((ms, i) for i, ms in
                                        enumerate(step_ms)),
                                       reverse=True)[:5]},
            "host_load": load,
            "first_record_epoch": t_first,
            "compile": stats.snapshot(),
            "setup_breakdown_s": {b[0]: b[1] - a[1]
                                  for a, b in zip(marks, marks[1:])},
            "memory_stats": mem_stats,
            "feed": _delta(snap0, snap1),
            "reference_seconds": time.monotonic() - t_ref,
            "calibration": calibration,
            "not_compared": not_compared,
            "losses": {"first": program["losses"], "last": losses[-1]}},
        "trace_dir": args["trace_dir"] if args["trace"] else None}


def _delta(a, b):
    """Per-stage seconds and samples between two timer snapshots."""
    if a is None or b is None:
        return None
    return {"stage_seconds": {k: v - a["seconds"].get(k, 0.0)
                              for k, v in b["seconds"].items()},
            "stage_samples": {k: v - a["samples"].get(k, 0)
                              for k, v in b["samples"].items()}}


def args_for(ctx):
    """The plain-data arguments of :func:`run` from run.py's context."""
    cfg, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    return {
        "platform": ctx["platform"], "chips": cell["chips"],
        "seed": ctx["seed"], "seconds": ctx["seconds"],
        "trace": ctx["trace"], "trace_dir": ctx["trace_dir"],
        "trace_seconds": cell.get("trace_seconds", 3.0),
        "model": cfg["model"], "optimizer": cfg["optimizer"],
        "batch": traffic["batch"], "image": traffic["image"],
        "warm_steps": traffic["warm_steps"], "limits": cell["limits"],
        "not_compared": cell.get("not_compared", []),
        "reference": cfg["reference"], "flops": cfg["flops"],
        "generator": traffic["generator"], "traffic": traffic,
        "t0_epoch": ctx["t0_epoch"]}


def dump(result, path):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, path)
