"""``sdar-30b-a3b-blockgen`` and every other open-loop cell of a model
that generates by diffusion over blocks: requests arrive on a schedule
made from the seed, each goes into ``DecodeEngine.submit()`` when it is
due, and one consumer per request iterates ``GenerationHandle.stream()``
and stamps every token as it is delivered - the same entry points and
scheduler as ``serve_openloop``; the engine learns from the model that
a step is a pass over a block.

A delivery hands over a whole block. A request's first delivery is its
time to first token and counts no gap; every later delivery of ``n``
tokens at ``t``, the one before it at ``t'``, counts as ``n`` gaps of
``(t - t') / n``: for ``n`` = 1 that is the gap as ``serve_openloop``
takes it, and taken token by token three gaps in four would be zero.

This process holds the chip. The weights are the benchmark's own, made
on the device from the seed, a jitted call a layer.
"""

import importlib
import threading
import time

from benchmarks import common

FAILED_MS = 120000.0  # what a failed or refused request counts as
STREAM_TIMEOUT_S = 120.0
# what the reference replays per run: 12 x 8 blocks x 4 passes, a
# position a pass at the defaults, so some 380 served positions
COMPARE_REQUESTS = 12  # finished requests, the longest among them
COMPARE_BLOCKS = 8     # in each: the first block, the last, six between


def _warm(engine, buckets, total_len, block_len, vocab, seed):
    """One throw-away request per prefill bucket, two blocks each: every
    prefill program and the step compile (or load) here."""
    import numpy as np

    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    for bucket in buckets:
        n = min(int(bucket), total_len - 2 * block_len)
        engine.submit(rng.randint(0, vocab, size=n).tolist(),
                      2 * block_len).result(timeout=1800)


def _timer_snapshot(engine):
    return {"seconds": engine.timers.snapshot(),
            "samples": engine.timers.counts(),
            "counts": engine.counters.snapshot()["counts"]}


def _deliveries(rec):
    """[(time, first token's index, tokens)] of one request: the
    handle's deliveries, each stamped when its first token came."""
    out, i = [], 0
    for n in rec["deliveries"]:
        if i + n > len(rec["t"]):
            break
        out.append((rec["t"][i], i, n))
        i += n
    return out


def _mean(values):
    return sum(values) / len(values) if values else None


def _gap_hist(gaps_ms, lo=16, hi=56):
    """How many gaps fall in each millisecond from ``lo`` to ``hi`` (the
    first and last bin take what lies outside). For the info line: it
    shows how thick the distribution is where the median lies, which
    is what a median's steadiness hangs on (PERF.md, PR 31)."""
    bins = [0] * (hi - lo)
    for g in gaps_ms:
        bins[min(max(int(g) - lo, 0), hi - lo - 1)] += 1
    return {"from_ms": lo, "counts": bins}


def _blocks_to_compare(rec, block_len, rng):
    """The first block a request generated in, the last, and
    ``COMPARE_BLOCKS - 2`` drawn between them."""
    first = len(rec["prompt"]) // block_len
    last = (len(rec["prompt"]) + len(rec["tokens"]) - 1) // block_len
    between = list(range(first + 1, last))
    drawn = rng.choice(between, size=min(COMPARE_BLOCKS - 2, len(between)),
                       replace=False).tolist() if between else []
    return sorted({first, last, *drawn})


def run(ctx, tamper=None, control=None):
    """``tamper(engine)`` is for the tests only (a fault planted under
    the timed path); ``control`` (a precision of the reference) for the
    calibration only: also read the lower-precision control's gaps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models.sdar_moe import SdarMoeLM

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
    params = jax.block_until_ready(
        ref.init_params(jax.random.PRNGKey(seed % (2 ** 32)), model))
    marks.append(("weights", time.time()))
    dec = SdarMoeLM(decode=True, dtype=jnp.dtype(cfg["dtype"]), **model)
    total_len, b = int(traffic["total_len"]), model["block_len"]
    engine = serving.DecodeEngine(
        dec, params, slots=serve["slots"], total_len=total_len,
        buckets=traffic["buckets"], temperature=0.0,
        kv_block_size=serve["kv_block_size"], kv_blocks=serve["kv_blocks"])
    marks.append(("engine", time.time()))
    if tamper is not None:
        tamper(engine)
    # prompts draw their ids below the MASK id
    vocab = model["mask_token_id"]
    try:
        _warm(engine, traffic["buckets"], total_len, b, vocab, seed)
        marks.append(("warm", time.time()))
        reqs = gen.schedule(traffic, seed, ctx["seconds"], vocab)
        records = [{"due": r["due_s"], "prompt": r["prompt"],
                    "max_new": r["max_new"], "t": [], "tokens": [],
                    "deliveries": [], "passes": [], "error": None,
                    "late": None} for r in reqs]

        def consume(rec, handle):
            try:
                for tok in handle.stream(timeout=STREAM_TIMEOUT_S):
                    rec["t"].append(time.monotonic())
                    rec["tokens"].append(tok)
            except Exception as e:  # noqa: BLE001 - counted as failed
                rec["error"] = repr(e)
            rec["deliveries"] = handle.deliveries
            rec["passes"] = handle.unmask_passes

        snap0 = _timer_snapshot(engine)
        compiled_before = stats.programs
        load0 = common.host_load()
        setup_s = time.time() - ctx["t0_epoch"]
        t0 = time.monotonic()
        close = t0 + ctx["seconds"]
        # With --trace 1 the first ``trace_seconds`` of the window run
        # under the profiler; the counters the per-layer metrics read
        # are taken from the moment the trace has been written out, and
        # what the traced stretch itself did from a snapshot at each of
        # its ends (the second by a watcher, within a millisecond).
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
    work = {"prefills": [], "pass_starts": []}
    for rec in records:
        bad = rec["error"] is not None or len(rec["tokens"]) != rec["max_new"]
        failed += int(bad)
        due = t0 + rec["due"]
        ttft.append((rec["t"][0] - due) * 1e3 if rec["t"] and not bad
                    else FAILED_MS)
        p = len(rec["prompt"])
        given = _deliveries(rec)
        for (t_prev, _, _), (t, _, n) in zip(given, given[1:]):
            gaps_ms += [(t - t_prev) * 1e3 / n] * n
        if not bad and rec["t"][-1] <= close:
            done_tokens += len(rec["t"])
        for j, (t, i, n) in enumerate(given):
            if t > close:
                break
            start = (p + i) // b * b
            # the block's denoising passes and its commit
            passes = max(rec["passes"][i:i + n]) + 2
            if t >= counted_from:
                window_flops += passes * flops.block_pass_flops(model, start)
                if j == 0:
                    window_flops += flops.prefill_flops(model, p // b * b)
            if trace is not None and trace.t0 <= t <= trace.t1:
                work["pass_starts"] += [start] * passes
                if j == 0 and p >= b:
                    work["prefills"].append(p // b * b)
    if traced.get("end"):
        for k in ("expert_calls", "expert_rows", "experts_touched"):
            work[k] = traced["end"].get(k, 0) - traced["begin"].get(k, 0)

    # ---- the comparison: a seeded sample of finished requests, the
    # longest among them; in each a few blocks, every denoising pass of
    # which the reference replays on the sequence up to the block's end
    t_ref = time.monotonic()
    finished = [r for r in records
                if r["error"] is None and len(r["tokens"]) == r["max_new"]
                and len(r["passes"]) == r["max_new"]]
    checks = common.Checks()
    gaps = {"served": [], "order": [], "control_served": [],
            "control_order": []}
    if finished:
        rng = np.random.RandomState((seed + 2) % (2 ** 32))
        longest = max(finished,
                      key=lambda r: len(r["prompt"]) + len(r["tokens"]))
        k = min(COMPARE_REQUESTS, len(finished)) - 1
        sample = [longest] + [finished[i] for i in rng.choice(
            len(finished), size=k, replace=False)
            if finished[i] is not longest][:k]
        for rec in sample:
            g = ref.served_gaps(
                params, rec["prompt"], rec["tokens"], rec["passes"], model,
                _blocks_to_compare(rec, b, rng), control,
                pad_to=total_len // 2)
            for name, values in g.items():
                gaps[name] += values.tolist()
    # the widest gap of a served token; the order's gap as a MEAN over
    # the positions compared (on random weights the positions of a
    # block are nearly equally confident, so the order's widest gap
    # reads alike for sound runs and faults: PERF.md, PR 31)
    numbers = {
        "served_gap_max": max(gaps["served"]) if gaps["served"] else None,
        "order_gap_mean": _mean(gaps["order"]),
        "order_gap_max": max(gaps["order"]) if gaps["order"] else None}
    not_compared = {}
    for name, value in numbers.items():
        if name in cell.get("not_compared", ()):
            not_compared[name] = value
        elif name in cell["limits"]:
            checks.add(name, value, cell["limits"][name])
        else:
            raise KeyError("the cell's file gives {!r} neither a limit nor "
                           "a place in not_compared".format(name))
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
                        "gap_mean_ms": _mean(gaps_ms),
                        "gap_hist": _gap_hist(gaps_ms),
                        "out_tokens_per_s": done_tokens / seconds,
                        "late_p95_ms": common.percentile(late, 95),
                        "late_max_ms": max(late)},
            "engine": {"stage_seconds": d, "stage_samples": n,
                       "counts": {k: v - snap0["counts"].get(k, 0)
                                  for k, v in snap1["counts"].items()}},
            "traced_work": work,
            "setup_breakdown_s": {b_[0]: b_[1] - a_[1]
                                  for a_, b_ in zip(marks, marks[1:])},
            "compile": stats.snapshot(),
            # the two keys ``calibrate.py`` reads of a serving runner,
            # named as ``serve_openloop`` names them; a position
            # compared is a served token
            "compared_tokens": len(gaps["served"]),
            "control_gap_max": max(gaps["control_served"], default=None),
            "control_order_gap_mean": _mean(gaps["control_order"]),
            "control_order_gap_max": max(gaps["control_order"],
                                         default=None),
            "not_compared": not_compared,
            "reference_seconds": time.monotonic() - t_ref},
        "trace_dir": ctx["trace_dir"] if ctx["trace"] else None}
