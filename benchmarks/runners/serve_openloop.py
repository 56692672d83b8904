"""``gpt2-large-chat`` and every other open-loop serving cell:
requests arrive on a schedule made from the seed, each goes into
``DecodeEngine.submit()`` when it is due, and one consumer per request
iterates ``GenerationHandle.stream()`` and stamps every token as it is
delivered. In process: ``POST :generate`` answers with the whole
completion, so time to first token cannot be seen over HTTP.

This process holds the chip. The weights are the benchmark's own, made
on the device in one jitted call from the seed.
"""

import importlib
import threading
import time

from benchmarks import common

FAILED_MS = 120000.0  # what a failed or refused request counts as
STREAM_TIMEOUT_S = 120.0
COMPARE_REQUESTS = 8  # finished requests the reference re-runs per run


def _warm(engine, buckets, total_len, vocab, seed):
    """One throw-away request per prefill bucket, two tokens each: every
    prefill program and the decode step compile (or load) here."""
    import numpy as np

    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    for bucket in buckets:
        n = min(int(bucket), total_len - 2)
        engine.submit(rng.randint(0, vocab, size=n).tolist(), 2) \
            .result(timeout=1800)


def _timer_snapshot(engine):
    return {"seconds": engine.timers.snapshot(),
            "samples": engine.timers.counts(),
            "counts": engine.counters.snapshot()["counts"]}


def run(ctx, tamper=None, control=None):
    """``tamper(engine)`` is for the tests only (a fault planted under
    the timed path); ``control`` (a precision of the reference) for the
    calibration only: also read the lower-precision control's gaps."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    cfg, traffic, cell = ctx["config"], ctx["traffic"], ctx["cell"]
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
    key = jax.random.PRNGKey(seed % (2 ** 32))
    params = jax.block_until_ready(
        jax.jit(lambda k: ref.init_params(k, model))(key))
    marks.append(("weights", time.time()))
    dec = DecoderLM(decode=True, vocab=model["vocab"],
                    hidden=model["hidden"], num_heads=model["num_heads"],
                    num_layers=model["num_layers"],
                    max_len=model["max_len"])
    total_len = int(traffic["total_len"])
    engine = serving.DecodeEngine(
        dec, params, slots=serve["slots"], total_len=total_len,
        buckets=traffic["buckets"], temperature=0.0,
        kv_block_size=serve["kv_block_size"], kv_blocks=serve["kv_blocks"])
    marks.append(("engine", time.time()))
    if tamper is not None:
        tamper(engine)
    try:
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
        trace, counted_from = None, t0
        if ctx["trace"]:
            trace = common.TraceWindow(ctx["trace_dir"],
                                       cell.get("trace_seconds", 5.0))
            trace.start()
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

    # ---- the comparison: a seeded sample of finished requests, the
    # longest among them, each re-run whole by the reference -----------
    t_ref = time.monotonic()
    finished = [r for r in records
                if r["error"] is None and len(r["tokens"]) == r["max_new"]]
    checks = common.Checks()
    worst = worst_control = None
    compared = 0
    if finished:
        rng = np.random.RandomState((seed + 2) % (2 ** 32))
        longest = max(finished,
                      key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        k = min(COMPARE_REQUESTS, len(finished)) - 1
        sample = [longest] + [finished[i] for i in rng.choice(
            len(finished), size=k, replace=False)
            if finished[i] is not longest][:k]
        worst = 0.0
        for rec in sample:
            g = ref.served_gaps(params, rec["prompt"], rec["tokens"], model,
                                total_len, control)
            worst = max(worst, float(g["served"].max()))
            compared += len(rec["tokens"])
            if control is not None:
                worst_control = max(worst_control or 0.0,
                                    float(g["control"].max()))
    checks.add("served_gap_max", worst, cell["limits"]["served_gap_max"])
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
            "compared_tokens": compared,
            "control_gap_max": worst_control,
            "reference_seconds": time.monotonic() - t_ref},
        "trace_dir": ctx["trace_dir"] if ctx["trace"] else None}
