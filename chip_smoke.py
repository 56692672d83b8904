"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [--seed N]         # one chip, phases 1-3
    python chip_smoke.py --multichip        # four chips, phase 4 only

Drives the two paths users depend on through their normal entry points
at full width, checks what comes out by the repo's own means, and
exits non-zero unless every phase passed ON A TPU. There is no CPU
mode: with ``JAX_PLATFORMS=cpu`` (or no chip) the script fails and
prints no result line. Every phase prints one JSON line naming the
device it ran on; the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Phases (functions below, each taking its sizes and the platform it
must find; tests/test_chip_smoke.py calls them at tiny sizes on the
CPU):

1. ``fed_phase`` — ``engine.Context`` -> ``cluster.run(InputMode.SPARK)``
   -> ``train(rdd)`` -> ``shutdown()``: ResNet-50, batch 256, 224 px
   uint8 records made from the seed, default (``auto``) feed transport.
2. ``serving_phase`` — ``ModelServer`` + paged ``DecodeEngine`` over a
   GPT-2-small-width ``DecoderLM``; real HTTP ``:generate`` requests
   checked against ``generate_jit``, and the paged attention op at the
   engine's shapes against its ``gather`` formulation.
3. ``kernel_phase`` — both Pallas kernels alone against their XLA
   oracles, with ``tpu_custom_call`` asserted from the lowered text.
4. ``multichip_phase`` (``--multichip`` only) — the fed job on a
   four-device data mesh against the same job on one device.

One process owns the chip at a time. The parent of this script stays
off JAX while a child needs the chip: phase 1's (and phase 4's)
trainer is a grandchild forked by the executor, and phases 2-3 run in
the parent STRICTLY AFTER that cluster has been shut down and its
executor — with the trainer it forked — stopped and reaped
(``Context.stop()``). Phase 4 never touches JAX in the parent at all.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: ResNet-50 (models.resnet.ResNet50's arguments) and the serving
#: block at GPT-2-small widths — the two full-width configurations
RESNET50 = {"stage_sizes": [3, 4, 6, 3], "num_classes": 1000, "width": 64}
GPT2_SMALL = {"vocab": 50257, "hidden": 768, "num_heads": 12,
              "num_layers": 12, "max_len": 1024}

#: labels are drawn from this many classes and each class has its own
#: mean colour, so a few SGD steps visibly lower the loss
_CLASSES = 10


def emit(record):
    print(json.dumps(record), flush=True)
    return record


def require(ok, *detail):
    """A check that fails the phase (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(*detail)


class CompileStats(object):
    """Programs this process compiled (or fetched from the persistent
    cache) and the seconds that took, read off JAX's own monitoring
    events."""

    def __init__(self):
        import jax

        self.programs = self.cache_hits = self.cache_misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return {"programs": self.programs,
                "compile_seconds": round(self.seconds, 3),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def device_record(platform):
    """The device as JAX reports it; raises unless it is ``platform``
    (checked before a phase does any work: a quiet fall-back to the CPU
    must not run a full-width model there)."""
    import jax

    dev = jax.devices()
    if dev[0].platform != platform:
        raise RuntimeError("chip_smoke: wanted a {!r} device, JAX found "
                           "{}".format(platform, dev))
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


# -- phase 1 / phase 4: the fed trainer ------------------------------------


def _records(n, image, seed, part):
    """``n`` (uint8 image, label) records of partition ``part``: each
    class has a mean colour (from ``seed``) under per-pixel noise, and
    the first pixel of every image is the record's identity
    (:func:`_tag`), which the trainer reads back FROM THE DEVICE."""
    import numpy as np

    colours = np.random.RandomState(seed).randint(
        48, 208, size=(_CLASSES, 1, 1, 3)).astype(np.int16)
    rng = np.random.RandomState([seed, part])
    ys = (np.arange(n) % _CLASSES).astype(np.int64)
    noise = rng.randint(-40, 40, size=(n, image, image, 3), dtype=np.int16)
    xs = np.clip(colours[ys] + noise, 0, 255).astype(np.uint8)
    xs[:, 0, 0, :] = [_tag(i, part) for i in range(n)]
    return [(xs[i], ys[i]) for i in range(n)]


def _tag(i, part):
    return [i % 256, i // 256, part]


def _train_map_fun(args, ctx):
    """The canonical consumption loop, run by the trainer process:
    ``ctx.get_data_feed`` -> ``infeed.sharded_batches`` ->
    ``training.Trainer.step``. Writes what it saw to
    ``args["result_path"]``."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import infeed, training
    from tensorflowonspark_tpu.models.resnet import ResNet
    from tensorflowonspark_tpu.parallel import build_mesh

    stats = CompileStats()
    device = device_record(args["platform"])
    devices = jax.devices()
    if args["n_devices"]:
        devices = devices[:args["n_devices"]]
    mesh = build_mesh({"data": len(devices)}, devices=devices)
    trainer = training.Trainer(ResNet(**args["model"]),
                               optax.sgd(0.1, momentum=0.9), mesh)
    batch, image = args["batch"], args["image"]
    state = trainer.init(jax.random.PRNGKey(args["seed"]),
                         np.zeros((batch, image, image, 3), np.float32))
    feed = ctx.get_data_feed(input_mapping={"x": "x", "y": "y"})
    batches = infeed.sharded_batches(feed.numpy_batches(batch), mesh,
                                     timers=feed.timers)
    losses, step_seconds, step_ends, tags, layout = [], [], [], [], None
    for b in batches:
        if layout is None:
            leaf = jax.tree.leaves(state["params"])[0]
            layout = {
                "batch_sharding": str(b["x"].sharding),
                "batch_shards": [[str(s.device), list(s.data.shape)]
                                 for s in b["x"].addressable_shards],
                "param_replicated": bool(leaf.sharding.is_fully_replicated),
                "param_devices": len(leaf.addressable_shards)}
        t0 = time.monotonic()
        state, metrics = trainer.step(state, b)
        losses.append(float(metrics["loss"]))  # waits for the step
        step_ends.append(time.monotonic())
        step_seconds.append(round(step_ends[-1] - t0, 4))
        tags += np.asarray(b["x"][:, 0, 0, :]).tolist()
    result = dict(
        device, mesh_devices=len(devices), losses=losses,
        step_seconds=step_seconds, tags=tags, layout=layout,
        # smoke observations, not metrics: images/s over the steps after
        # the compile step — the steps alone, and end to end with the
        # time the loop waited on the feed between them
        step_images_per_sec=round(
            batch * (len(losses) - 1) / sum(step_seconds[1:]), 1),
        fed_images_per_sec=round(
            batch * (len(losses) - 1) / (step_ends[-1] - step_ends[0]), 1),
        compile=stats.snapshot(), feed_stages_ms=feed.timers.per_ms(),
        transport=feed.mgr.get("feed_transport"),
        transport_probe=feed.mgr.get("feed_transport_probe"))
    if args["want_hlo"]:
        text = trainer._jit_step.lower(state, b).compile().as_text()
        result["all_reduce_in_step"] = "all-reduce" in text
    with open(args["result_path"], "w") as f:
        json.dump(result, f)


def _tail(path, n=60):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return "<no {}: {}>".format(path, e)


def fed_job(seed, platform, model, batch, image, steps, n_devices=None,
            want_hlo=False):
    """One fed training job through the public cluster API; returns the
    trainer's report plus what the driver fed. On failure prints the
    tail of the executor log (the trainer's output lands there)."""
    from tensorflowonspark_tpu import cluster, shm
    from tensorflowonspark_tpu.engine import Context

    if not shm.available():
        raise RuntimeError(
            "the native shm ring did not build (g++ / native/shm_ring.cpp)"
            ": the fed phase would run on the queue transport only")
    work = tempfile.mkdtemp(prefix="tfos-chip-smoke-")
    result_path = os.path.join(work, "trainer.json")
    parts = 4
    per_part = -(-(steps + 1) // parts) * batch  # whole batches each
    t0 = time.monotonic()
    try:
        sc = Context(num_executors=1, work_root=work)
        try:
            tfc = cluster.run(
                sc, _train_map_fun,
                {"seed": seed, "platform": platform, "model": model,
                 "batch": batch, "image": image, "n_devices": n_devices,
                 "want_hlo": want_hlo, "result_path": result_path},
                num_executors=1, input_mode=cluster.InputMode.SPARK)
            rdd = sc.parallelize(range(parts), parts) \
                .mapPartitionsWithIndex(
                    lambda i, _: iter(_records(per_part, image, seed, i)))
            tfc.train(rdd, num_epochs=1)
            tfc.shutdown()
        finally:
            sc.stop()  # executor and the trainer it forked are reaped here
        # this cluster's rings (node.py names them after the cluster id)
        leftovers = sorted(glob.glob("/dev/shm/tfos-{}-*".format(
            tfc.cluster_meta["id"][-10:])))
        with open(result_path) as f:
            result = json.load(f)
    except BaseException:
        print("fed job failed; tail of executor-0/executor.log:\n"
              + _tail(os.path.join(work, "executor-0", "executor.log")),
              file=sys.stderr, flush=True)
        raise
    finally:
        shutil.rmtree(work, ignore_errors=True)
    fed = sorted(_tag(i, p) for p in range(parts) for i in range(per_part))
    result["records_fed"] = len(fed)
    result["records"] = len(result["tags"])
    # every record fed arrived on the device exactly once, none other
    result["records_match"] = sorted(result.pop("tags")) == fed
    result["wall_seconds"] = round(time.monotonic() - t0, 2)
    result["shm_leftovers"] = leftovers
    return result


def _check_fed(r, steps):
    losses = r["losses"]
    require(len(losses) >= steps + 1, len(losses), steps)
    require(all(x == x and abs(x) != float("inf") for x in losses), losses)
    require(losses[-1] < losses[0], "loss did not fall", losses)
    require(r["records"] == r["records_fed"] and r["records_match"],
            r["records"], r["records_fed"], r["records_match"])
    require(r["transport"] in ("shm", "queue"), r["transport"])
    require(not r["shm_leftovers"], r["shm_leftovers"])


def fed_phase(seed, platform="tpu", model=RESNET50, batch=256, image=224,
              steps=11):
    """Phase 1. ``steps`` training steps after the compile step."""
    r = fed_job(seed, platform, model, batch, image, steps)
    _check_fed(r, steps)
    return emit({
        "phase": "fed_training", "ok": True,
        "device": {k: r[k] for k in ("platform", "kind", "count")},
        "model": model, "batch": batch, "image": image,
        "steps_after_compile": len(r["losses"]) - 1, "losses": r["losses"],
        "records_fed": r["records_fed"], "records_consumed": r["records"],
        "transport": r["transport"], "transport_probe": r["transport_probe"],
        "feed_stages_ms": r["feed_stages_ms"],
        "wall_seconds": r["wall_seconds"], "compile": r["compile"],
        "first_step_seconds": r["step_seconds"][0],
        "smoke_observations": {
            k: r[k] for k in ("step_images_per_sec", "fed_images_per_sec")}})


#: per-step loss agreement between the four-device and the one-device
#: job: same seed, data and global batch, so they differ only by the
#: order bf16 convolutions and the batch reductions accumulate in —
#: which the SGD steps then amplify
MULTICHIP_LOSS_RTOL = 0.05


def multichip_phase(seed, platform="tpu", model=RESNET50, batch=256,
                    image=224, steps=11, n_devices=4):
    """Phase 4: the fed job with one executor whose trainer owns
    ``n_devices`` chips on a ``{"data": n_devices}`` mesh, then the
    same job on a one-device mesh in a trainer process of its own."""
    many = fed_job(seed, platform, model, batch, image, steps,
                   n_devices=n_devices, want_hlo=True)
    one = fed_job(seed, platform, model, batch, image, steps, n_devices=1)
    for r in (many, one):
        _check_fed(r, steps)
    layout = many["layout"]
    require(many["mesh_devices"] == n_devices, many["mesh_devices"])
    require(layout["param_replicated"]
            and layout["param_devices"] == n_devices, layout)
    shards = layout["batch_shards"]
    require(len(shards) == n_devices
            and len({d for d, _ in shards}) == n_devices
            and all(s[0] == batch // n_devices for _, s in shards), shards)
    require(many["all_reduce_in_step"], "no all-reduce in the compiled step")
    worst = max(abs(a - b) / max(abs(b), 1e-6)
                for a, b in zip(many["losses"], one["losses"]))
    require(worst <= MULTICHIP_LOSS_RTOL, worst, many["losses"],
            one["losses"])
    return emit({
        "phase": "multichip_fed_training", "ok": True,
        "device": {k: many[k] for k in ("platform", "kind", "count")},
        "mesh_devices": n_devices, "batch": batch, "image": image,
        "layout": layout, "all_reduce_in_step": True,
        "losses": many["losses"], "losses_one_device": one["losses"],
        "loss_rel_diff_max": round(worst, 5),
        "loss_rtol": MULTICHIP_LOSS_RTOL,
        "wall_seconds": [many["wall_seconds"], one["wall_seconds"]],
        "compile": [many["compile"], one["compile"]],
        "smoke_observations": [
            {k: r[k] for k in ("step_images_per_sec", "fed_images_per_sec")}
            for r in (many, one)]})


# -- phase 2: the decode server --------------------------------------------

#: where engine and ``generate_jit`` tokens part (seeded random weights
#: leave thin argmax margins, and the chip's XLA dots round f32 inputs
#: to bf16 while the kernel does not), every token the engine emitted
#: must be within this of the top logit of a plain full forward over
#: the engine's own sequence
LOGIT_MARGIN_TOL = 5e-2


def _post(port, path, body, timeout=600):
    import urllib.request

    req = urllib.request.Request(
        "http://127.0.0.1:{}{}".format(port, path),
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(port, path, timeout=60):
    import urllib.request

    with urllib.request.urlopen(
            "http://127.0.0.1:{}{}".format(port, path),
            timeout=timeout) as resp:
        return resp.status, resp.read().decode()


def serving_phase(seed, platform="tpu", model=GPT2_SMALL,
                  prompt_lens=(17, 128, 500), new_tokens=32):
    """Phase 2. Requests: one per prompt length, the middle one sent
    twice CONCURRENTLY with the last, then the first repeated (a prefix
    cache hit)."""
    import functools
    import importlib
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorflowonspark_tpu import generation, serving
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    t_phase = time.monotonic()
    device = device_record(platform)
    stats = CompileStats()
    full = DecoderLM(decode=False, **model)
    dec = DecoderLM(decode=True, **model)
    max_len = model["max_len"]
    params = jax.jit(lambda key: full.init(
        key, jnp.zeros((1, max_len), jnp.int32))["params"])(
            jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, model["vocab"], size=n).tolist()
               for n in prompt_lens]
    # every prompt_len gets a request; two run concurrently; one repeats
    plan = [[0], [1, 2], [1], [0]]

    def solo(prompt):
        out = generation.generate_jit(
            dec, params, jnp.asarray([prompt], jnp.int32), new_tokens)
        return np.asarray(out)[0].tolist()[-new_tokens:]

    full_logits = jax.jit(lambda p, toks: full.apply({"params": p}, toks))

    def margin(prompt, tokens):
        """Largest shortfall of an emitted token's logit below the top
        logit of a plain forward over the engine's own sequence."""
        seq = (prompt + tokens)[:max_len]
        padded = jnp.asarray([seq + [0] * (max_len - len(seq))], jnp.int32)
        logits = np.asarray(full_logits(params, padded))[0]
        worst = 0.0
        for i, tok in enumerate(tokens):
            row = logits[len(prompt) + i - 1]
            worst = max(worst, float(row.max() - row[tok]))
        return worst

    engine = serving.DecodeEngine(dec, params)
    server = serving.ModelServer(None, engine=engine, name="lm", port=0)
    got = {}
    try:
        _, port = server.start()
        route = "/v1/models/lm:generate"

        def ask(tag, i):  # the body answers prompt + generated
            got[tag] = _post(port, route, {
                "prompt": prompts[i],
                "max_new_tokens": new_tokens})["tokens"][len(prompts[i]):]

        t_req = time.monotonic()
        for r, group in enumerate(plan):
            threads = [threading.Thread(target=ask, args=((r, i), i))
                       for i in group]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        request_seconds = time.monotonic() - t_req
        require(len(got) == sum(len(g) for g in plan), sorted(got))
        # a smoke observation, not a metric: one more request whose
        # programs all exist by now (a fresh prompt of the middle
        # length: same prefill bucket, no prefix hit), timed alone
        programs = stats.programs
        t_warm = time.monotonic()
        _post(port, route, {
            "prompt": rng.randint(0, model["vocab"],
                                  size=prompt_lens[1]).tolist(),
            "max_new_tokens": new_tokens})
        warm_seconds = time.monotonic() - t_warm
        require(stats.programs == programs, "the warm request compiled")
        code, body = _get(port, "/healthz")
        health = json.loads(body)
        require(code == 200 and health["status"] == "ok", health)
        require(health["kv_blocks_free"] == health["kv_blocks_total"], health)
        require(health["prefix_hit_rate"] > 0, health)
        code, metrics = _get(port, "/metrics")
        require(code == 200 and "tfos_serving_tokens_total" in metrics,
                "no token counter on /metrics")
    finally:
        server.stop()
    emit({"phase": "serving_compile", "device": device, **stats.snapshot()})
    want = [solo(p) for p in prompts]
    equal, worst_margin = 0, 0.0
    for (_, i), tokens in sorted(got.items()):
        require(len(tokens) == new_tokens, i, tokens)
        if tokens == want[i]:
            equal += 1
        else:
            m = margin(prompts[i], tokens)
            worst_margin = max(worst_margin, m)
            require(m <= LOGIT_MARGIN_TOL, i, m, tokens, want[i])
    parity = "tokens equal" if equal == len(got) else (
        "{} of {} requests token-equal to generate_jit; the others within "
        "{} of the top logit of a plain forward at every step (worst "
        "{:.4f})".format(equal, len(got), LOGIT_MARGIN_TOL, worst_margin))
    # the kernel against an implementation that shares none of its code:
    # one decode-shaped call of the op each way, at the engine's own
    # pool, table and query shapes
    pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")
    heads = model["num_heads"]
    head_dim = model["hidden"] // heads
    width = engine.total_len // engine.kv_block_size
    pool_rows = engine.kv_blocks + 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (engine.slots, 1, heads, head_dim))
    kp, vp = (jax.random.normal(
        key, (pool_rows, engine.kv_block_size, heads * head_dim))
        for key in keys[1:])
    table = jnp.asarray(
        1 + rng.permutation(pool_rows - 1)[:engine.slots * width]
        .reshape(engine.slots, width), jnp.int32)
    pos = jnp.asarray(
        rng.randint(0, engine.total_len, size=(engine.slots, 1)), jnp.int32)

    @functools.partial(jax.jit, static_argnames="impl")
    def paged(q, kp, vp, table, pos, impl=None):
        return pa.paged_attention(q, kp, vp, table, pos, impl=impl)

    args = (q, kp, vp, table, pos)
    has_kernel = "tpu_custom_call" in paged.lower(*args).as_text()
    require(has_kernel or platform != "tpu",
            "no tpu_custom_call in the engine-shaped paged attention")
    gather_err = _rel_err(paged(*args), paged(*args, impl="gather"))
    require(gather_err <= KERNEL_FWD_RTOL, gather_err, KERNEL_FWD_RTOL)
    return emit({
        "phase": "serving", "ok": True, "device": device,
        "model": model, "prompt_lens": list(prompt_lens),
        "requests": len(got), "new_tokens": new_tokens,
        "parity": parity,
        "default_vs_gather": {
            "what": "ops.paged_attention, impl=None against "
                    "impl='gather', one decode call at the engine's shapes",
            "q": list(q.shape), "pool": list(kp.shape),
            "table": list(table.shape), "rel_err": round(gather_err, 6),
            "tol": KERNEL_FWD_RTOL, "tpu_custom_call": has_kernel},
        "prefix_hit_rate": health["prefix_hit_rate"],
        "kv_blocks_total": health["kv_blocks_total"],
        "wall_seconds": round(time.monotonic() - t_phase, 2),
        "compile": stats.snapshot(),
        "request_seconds_including_compiles": round(request_seconds, 2),
        "smoke_observations": {
            "warm_request_seconds": round(warm_seconds, 4),
            "warm_request_shape": [prompt_lens[1], new_tokens],
            "warm_tokens_per_sec": round(new_tokens / warm_seconds, 1)}})


# -- phase 3: the kernels alone ----------------------------------------------

#: max |kernel - oracle| allowed, as a share of max |oracle|. The
#: oracle is the XLA reference at the chip's DEFAULT matmul precision,
#: which rounds f32 operands to bf16: so bf16-grade agreement is what
#: either dtype can show (forward); gradients of a sum-of-squares loss
#: accumulate that rounding over the sequence
KERNEL_FWD_RTOL = 2e-2
KERNEL_BWD_RTOL = 4e-2


def _rel_err(got, want):
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))


def kernel_phase(seed, platform="tpu", batch=8, seq=1024, heads=12,
                 head_dim=64, block_size=16, prefill=128):
    """Phase 3: flash forward and backward (causal, and key-masked) and
    paged attention (float and int8 pools; decode and a prefill bucket)
    through the default ``impl`` against the XLA reference / ``gather``.
    On a TPU every case's lowered text must hold a ``tpu_custom_call``,
    so a quiet fall-through to the reference cannot pass."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
    pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")
    t_phase = time.monotonic()
    device = device_record(platform)
    stats = CompileStats()
    cases = []

    def lowered_has_kernel(fn, *args):
        return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()

    def case(name, err, tol, has_kernel):
        require(err <= tol, name, err, tol)
        require(has_kernel or platform != "tpu",
                "no tpu_custom_call in the lowered text of " + name)
        cases.append({"case": name, "rel_err": round(err, 6), "tol": tol,
                      "tpu_custom_call": has_kernel})

    scale = head_dim ** -0.5
    for dtype in (jnp.bfloat16, jnp.float32):
        dname = jnp.dtype(dtype).name
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        q, k, v = (jax.random.normal(kk, (batch, seq, heads, head_dim),
                                     dtype) for kk in keys)
        lens = jnp.asarray([seq // 2 if i % 2 == 0 else seq
                            for i in range(batch)])
        key_mask = jnp.arange(seq)[None, :] < lens[:, None]
        for mode, causal, mask in (("causal", True, None),
                                   ("key_mask", False, key_mask)):
            def flash(q, k, v):
                return fa.flash_attention(q, k, v, causal=causal,
                                          key_mask=mask)

            def reference(q, k, v):
                return fa._reference(q, k, v, causal, scale,
                                     fa._mask_to_bias(mask))

            def grads(fn):
                return jax.jit(jax.grad(
                    lambda q, k, v: jnp.sum(
                        fn(q, k, v).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2)))

            case("flash_fwd_{}_{}".format(mode, dname),
                 _rel_err(jax.jit(flash)(q, k, v),
                          jax.jit(reference)(q, k, v)),
                 KERNEL_FWD_RTOL, lowered_has_kernel(flash, q, k, v))
            case("flash_bwd_{}_{}".format(mode, dname),
                 max(_rel_err(a, b) for a, b in zip(
                     grads(flash)(q, k, v), grads(reference)(q, k, v))),
                 KERNEL_BWD_RTOL,
                 lowered_has_kernel(grads(flash), q, k, v))

    table_width = seq // block_size
    pool_rows = batch * table_width + 1
    rng = np.random.RandomState(seed)
    pools = [jax.random.normal(jax.random.PRNGKey(seed + i),
                               (pool_rows, block_size, heads, head_dim),
                               jnp.float32) for i in (1, 2)]

    def flat(pool):  # the op's pools: heads and head_dim in one axis
        return pool.reshape(pool_rows, block_size, heads * head_dim)

    for dtype in (jnp.bfloat16, jnp.float32):
        for pool in ("float", "int8"):
            if pool == "int8":
                (kp, ks), (vp, vs) = (pa.quantize_kv(p) for p in pools)
                kp, vp = flat(kp), flat(vp)
            else:
                kp, vp = (flat(p.astype(dtype)) for p in pools)
                ks = vs = None
            for name, rows, s_q in (("decode", batch, 1),
                                    ("prefill", 1, prefill)):
                q = jax.random.normal(jax.random.PRNGKey(seed + 3),
                                      (rows, s_q, heads, head_dim), dtype)
                table = jnp.asarray(
                    1 + rng.permutation(pool_rows - 1)[:rows * table_width]
                    .reshape(rows, table_width), jnp.int32)
                if s_q == 1:
                    pos = jnp.asarray(
                        rng.randint(0, seq, size=(rows, 1)), jnp.int32)
                else:  # a warm prefill: the tail starts mid-sequence
                    pos = (3 * block_size
                           + jnp.arange(s_q, dtype=jnp.int32))[None, :]

                def paged(q, kp, vp, table, pos, ks, vs, impl=None):
                    return pa.paged_attention(q, kp, vp, table, pos,
                                              impl=impl, k_scale=ks,
                                              v_scale=vs)

                args = (q, kp, vp, table, pos, ks, vs)
                case("paged_{}_{}_pool_{}".format(
                        name, pool, jnp.dtype(dtype).name),
                     _rel_err(jax.jit(paged)(*args),
                              jax.jit(paged, static_argnames="impl")(
                                  *args, impl="gather")),
                     KERNEL_FWD_RTOL, lowered_has_kernel(paged, *args))
    return emit({
        "phase": "kernels", "ok": True, "device": device,
        "shape": {"batch": batch, "seq": seq, "heads": heads,
                  "head_dim": head_dim, "kv_block": block_size,
                  "prefill": prefill},
        "cases": cases,
        "wall_seconds": round(time.monotonic() - t_phase, 2),
        "compile": stats.snapshot()})


# -- entry -------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the four-device fed job and "
                         "its one-device comparison")
    args = ap.parse_args(argv)
    from tensorflowonspark_tpu import device_info, util

    # refuse before any work, without touching JAX in this process
    if device_info.chip_claim() is None:
        raise SystemExit(
            "chip_smoke: a trainer started here would take no TPU chip "
            "(JAX_PLATFORMS={!r}, TPU on this host: {}); this script has "
            "no CPU mode".format(os.environ.get("JAX_PLATFORMS"),
                                 device_info.is_tpu_available()))
    if args.multichip:
        device = multichip_phase(args.seed)["device"]
        if device["count"] != 4:
            raise SystemExit("chip_smoke: --multichip needs four chips, "
                             "found {}".format(device))
    else:
        device = fed_phase(args.seed)["device"]
        # the cluster is down and its trainer reaped: from here on THIS
        # process owns the chip
        util.enable_compile_cache()
        serving_phase(args.seed)
        kernel_phase(args.seed)
    # the phases checked their own rings; run as a script, nothing
    # else on the machine makes any
    if glob.glob("/dev/shm/tfos-*"):
        raise SystemExit("chip_smoke: left behind {}".format(
            glob.glob("/dev/shm/tfos-*")))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
