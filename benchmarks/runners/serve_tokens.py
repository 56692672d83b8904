"""``mellum2-12b-mixedlen`` and every other open-loop cell of a model
that yields one token a row and step and is NOT ``DecoderLM``: what
``serve_openloop`` measures, gap for gap (requests arrive on a schedule
made from the seed, each goes into ``DecodeEngine.submit()`` when it is
due, one consumer per request iterates ``GenerationHandle.stream()`` and
stamps every token as it is delivered), with the model's class, its
dtype and the engine's pool sizes taken from the configuration's file
(``model_class``: ``<module under tensorflowonspark_tpu.models>:<class>``
built from the ``model`` group; ``serving``: slots and every ``kv_*``
option of the engine), where ``serve_openloop`` builds ``DecoderLM`` by
name. Where the model has sparse experts, the router's counters between
the two ends of the traced stretch go into ``traced_work`` for the
experts' roofline, as ``serve_blockdiff`` records them.

This process holds the chip. The weights are the benchmark's own, made
on the device from the seed by the configuration's reference.
"""

import importlib
import threading
import time

from benchmarks import common

from benchmarks.runners.serve_openloop import (  # noqa: F401
    COMPARE_REQUESTS, FAILED_MS, STREAM_TIMEOUT_S, _timer_snapshot, _warm)


def run(ctx, tamper=None, control=None):
    """``tamper(engine)`` is for the tests only (a fault planted under
    the timed path); ``control`` (a precision of the reference) for the
    calibration only: also read the lower-precision control's gaps."""
    import jax
    import numpy as np

    import jax.numpy as jnp

    from tensorflowonspark_tpu import serving

    cfg, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
    # first of all: a program that lacks the model's family fails here,
    # cleanly and at once
    module, _, name = cfg["model_class"].partition(":")
    model_class = getattr(importlib.import_module(
        "tensorflowonspark_tpu.models." + module), name)
    marks = [("start", ctx["t0_epoch"]), ("imports", time.time())]
    device = common.device_record(ctx["platform"], cell["chips"])
    marks.append(("jax_ready", time.time()))
    stats = common.CompileStats()
    model, serve = cfg["model"], cfg["serving"]
    ref = importlib.import_module("benchmarks.reference." + cfg["reference"])
    flops = importlib.import_module("benchmarks.flops." + cfg["flops"])
    gen = importlib.import_module("benchmarks.generators."
                                  + traffic["generator"])
    seed = ctx["seed"]
    params = jax.block_until_ready(
        ref.init_params(jax.random.PRNGKey(seed % (2 ** 32)), model))
    marks.append(("weights", time.time()))
    # a flax module is hashed by its fields: lists become tuples
    dec = model_class(decode=True, dtype=jnp.dtype(cfg["dtype"]), **{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in model.items()})
    total_len = int(traffic["total_len"])
    engine = serving.DecodeEngine(
        dec, params, slots=serve["slots"], total_len=total_len,
        buckets=traffic["buckets"], temperature=0.0,
        **{k: v for k, v in serve.items() if k.startswith("kv_")})
    marks.append(("engine", time.time()))
    if tamper is not None:
        tamper(engine)
    try:
        # every bucket's prefill and the step compile side by side (a
        # first run at a checkout: the compiler's minutes, halved) or
        # load side by side; the warm-up then runs each once
        engine.precompile()
        marks.append(("programs", time.time()))
        _warm(engine, traffic["buckets"], total_len, model["vocab"], seed)
        marks.append(("warm", time.time()))
        reqs = gen.schedule(traffic, seed, ctx["seconds"], model["vocab"])
        records = [{"due": r["due_s"], "prompt": r["prompt"],
                    "max_new": r["max_new"], "t": [], "tokens": [],
                    "error": None, "late": None} for r in reqs]

        def consume(rec, handle):
            try:
                for tok in handle.stream(timeout=STREAM_TIMEOUT_S):
                    rec["t"].append(time.monotonic())
                    rec["tokens"].append(tok)
            except Exception as e:  # noqa: BLE001 - counted as failed
                rec["error"] = repr(e)

        snap0 = _timer_snapshot(engine)
        compiled_before = stats.programs
        load0 = common.host_load()
        setup_s = time.time() - ctx["t0_epoch"]
        t0 = time.monotonic()
        close = t0 + ctx["seconds"]
        # With --trace 1 the first ``trace_seconds`` of the window run
        # under the profiler; the counters the per-layer metrics read
        # are taken from the moment the trace has been written out.
        # What the traced stretch itself did: a snapshot of the
        # engine's counts at each of its ends (the second by a watcher,
        # within a millisecond).
        trace, counted_from, traced = None, t0, {}
        if ctx["trace"]:
            trace = common.TraceWindow(ctx["trace_dir"],
                                       cell.get("trace_seconds", 5.0))
            trace.start()
            traced["begin"] = engine.counters.snapshot()["counts"]

            def watch():
                while trace.t1 is None and not trace.done.is_set():
                    time.sleep(0.001)
                traced["end"] = engine.counters.snapshot()["counts"]

            threading.Thread(target=watch, daemon=True,
                             name="bench-trace-end").start()
        for rec in records:
            while True:
                wait = t0 + rec["due"] - time.monotonic()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
                if trace is not None and trace.done.is_set() \
                        and counted_from == t0:
                    snap0, counted_from = (_timer_snapshot(engine),
                                           time.monotonic())
            rec["late"] = time.monotonic() - (t0 + rec["due"])
            try:
                handle = engine.submit(rec["prompt"], rec["max_new"])
            except Exception as e:  # noqa: BLE001 - refused: counts as worst
                rec["error"] = repr(e)
                continue
            th = threading.Thread(target=consume, args=(rec, handle),
                                  daemon=True)
            th.start()
            rec["thread"] = th
        time.sleep(max(0.0, close - time.monotonic()))
        load = common.host_load_between(load0, common.host_load())
        if trace is not None:
            trace.join()
        # an answer that comes late is late, not wrong: wait for each
        for rec in records:
            th = rec.pop("thread", None)
            if th is not None:
                th.join(timeout=max(0.0, close + 60.0 - time.monotonic()))
                if th.is_alive() and rec["error"] is None:
                    rec["error"] = "not finished a minute past the close"
        drained_s = time.monotonic() - close
        snap1 = _timer_snapshot(engine)
        compiled_in_window = stats.programs - compiled_before
        peak = common.memory_peak_bytes(cell["chips"])
        mem_stats = common.memory_stats()
    finally:
        engine.stop()
    del engine

    seconds = ctx["seconds"]
    ttft, gaps_ms, failed = [], [], 0
    done_tokens = window_flops = 0
    work = {"prefills": [], "decode_positions": []}
    for rec in records:
        bad = rec["error"] is not None or len(rec["tokens"]) != rec["max_new"]
        failed += int(bad)
        due = t0 + rec["due"]
        ttft.append((rec["t"][0] - due) * 1e3 if rec["t"] and not bad
                    else FAILED_MS)
        ts, p = rec["t"], len(rec["prompt"])
        gaps_ms += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])]
        if not bad and ts[-1] <= close:
            done_tokens += len(ts)
        for i, t in enumerate(ts):
            if t > close:
                break
            if t >= counted_from:
                window_flops += (
                    flops.sequence_flops(model, p, 1) if i == 0
                    else flops.step_token_flops(model, p + i - 1))
            if trace is not None and trace.t0 <= t <= trace.t1:
                if i == 0:
                    work["prefills"].append(p)
                else:
                    work["decode_positions"].append(p + i - 1)
    if traced.get("end"):
        for k in ("expert_calls", "expert_rows", "experts_touched"):
            work[k] = traced["end"].get(k, 0) - traced["begin"].get(k, 0)

    # ---- the comparison: a seeded sample of finished requests, the
    # longest among them, each re-run whole by the reference -----------
    t_ref = time.monotonic()
    finished = [r for r in records
                if r["error"] is None and len(r["tokens"]) == r["max_new"]]
    checks = common.Checks()
    worst = worst_control = mean = mean_control = None
    gaps, control_gaps = [], []  # of every compared token
    replayed = []                # [[positions, seconds] a request]
    if finished:
        rng = np.random.RandomState((seed + 2) % (2 ** 32))
        longest = max(finished,
                      key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        k = min(COMPARE_REQUESTS, len(finished)) - 1
        sample = [longest] + [finished[i] for i in rng.choice(
            len(finished), size=k, replace=False)
            if finished[i] is not longest][:k]
        for rec in sample:
            t_req = time.monotonic()
            g = ref.served_gaps(params, rec["prompt"], rec["tokens"], model,
                                total_len, control)
            gaps.append(g["served"])
            if control is not None:
                control_gaps.append(g["control"])
            replayed.append([len(rec["prompt"]) + len(rec["tokens"]),
                             time.monotonic() - t_req])
        gaps = np.concatenate(gaps)
        worst, mean = float(gaps.max()), float(gaps.mean())
        if control is not None:
            control_gaps = np.concatenate(control_gaps)
            worst_control = float(control_gaps.max())
            mean_control = float(control_gaps.mean())
    # the widest gap sees a wrong precision; the MEAN over every compared
    # token sees what moves many logits a little (a window a block out
    # can read under the widest gap's limit, and sixteen times and more
    # a sound run's mean: PERF.md has the readings)
    checks.add("served_gap_max", worst, cell["limits"]["served_gap_max"])
    checks.add("served_gap_mean", mean, cell["limits"]["served_gap_mean"])
    checks.add("requests_failed", failed, 0)
    checks.add("compiled_in_window", compiled_in_window, 0)
    d = {k: snap1["seconds"].get(k, 0.0) - snap0["seconds"].get(k, 0.0)
         for k in snap1["seconds"]}
    n = {k: snap1["samples"].get(k, 0) - snap0["samples"].get(k, 0)
         for k in snap1["samples"]}
    late = [r["late"] * 1e3 for r in records if r["late"] is not None]
    return {
        "device": dict(device, memory_peak_bytes=peak),
        "correct": checks.ok, "attempted": len(records), "failed": failed,
        "checks": checks.as_dict(),
        "end_to_end": {
            "gap_p50_ms": common.median(gaps_ms) if gaps_ms else FAILED_MS,
            "setup_s": setup_s},
        "counters": {
            "window": {"seconds": close - counted_from,
                       "requests": len(records),
                       "model_flops": window_flops,
                       "drained_s": drained_s,
                       "traced_seconds": None if trace is None
                       else trace.t1 - trace.t0},
            "memory_stats": mem_stats,
            "host_load": load,
            "latency": {"ttft_mean_ms": sum(ttft) / len(ttft),
                        "ttft_p50_ms": common.median(ttft),
                        "ttft_p95_ms": common.percentile(ttft, 95),
                        "gap_p95_ms": common.percentile(gaps_ms, 95)
                        if gaps_ms else None,
                        "out_tokens_per_s": done_tokens / seconds,
                        "late_p95_ms": common.percentile(late, 95),
                        "late_max_ms": max(late)},
            "engine": {"stage_seconds": d, "stage_samples": n,
                       "counts": {k: v - snap0["counts"].get(k, 0)
                                  for k, v in snap1["counts"].items()}},
            "traced_work": work,
            "setup_breakdown_s": {b[0]: b[1] - a[1]
                                  for a, b in zip(marks, marks[1:])},
            "compile": stats.snapshot(),
            "compared_tokens": len(gaps),
            # the share of compared tokens that are not the reference's
            # best (a sound run: 3%, by hundredths)
            "served_nonzero_share": None if worst is None
            else float(np.mean(gaps > 0)),
            "control_gap_max": worst_control,
            "control_gap_mean": mean_control,
            "reference_seconds": time.monotonic() - t_ref,
            "reference_requests": replayed},
        "trace_dir": ctx["trace_dir"] if ctx["trace"] else None}
