"""Benchmark: ResNet-50 training throughput (images/sec/chip), FED path.

The primary metric from BASELINE.json ("ResNet-50 images/sec/chip"). The
reference publishes no reproducible numbers (BASELINE.md), so
``vs_baseline`` is measured against BASELINE_IMAGES_PER_SEC below — the
device-resident bar recorded when this benchmark first ran on the v5e
chip.

Since round 3 the HEADLINE number is the *cluster-fed* path — the
framework's reason to exist (SURVEY.md §7.3 "Feed throughput",
BASELINE.md north star): records stream executor→ring/queue→DataFeed→
infeed→jit step through the production cluster machinery
(``cluster.run`` + ``cluster.train`` + ``node._feed_partition``), not a
bench-private feeder. ``device_only`` (batch staged in HBM once) is
reported alongside as the ceiling.

Needs the chip: a run that finds none exits non-zero and prints no
result — unless the caller pinned ``JAX_PLATFORMS=cpu`` itself (``make
smoke``), which runs the tiny CPU smoke under the unit ``images/sec``,
never ``/chip``. A leg that raises is reported in its slot and under
``failed_legs``, and makes the exit code non-zero. Chip ownership: the
fed legs' trainers are child processes and run first; the device-only
spin and the serving legs run in this process after those are reaped.

Prints ONE JSON line. Fields:

- ``device``           — platform, device kind and count as JAX reports
                         them for the run.
- ``value``/``vs_baseline`` — best cluster-fed images/sec/chip vs the
  device-resident bar (a fed/device ratio of 1.0 means the feed plane
  keeps the chip fully busy).
- ``device_only``      — step time with the batch staged in HBM once.
- ``cluster_fed_shm``  — fed via the native /dev/shm ring (forced).
- ``cluster_fed_queue``— fed via the manager-proxy queue transport (forced).
- ``cluster_fed_auto`` — fed via the production DEFAULT: the bootstrap
                         micro-probe picks the measured-faster transport.
- ``transport_probe``  — that probe's evidence: per-transport MB/s rates
                         plus ``choice`` (the transport auto selected).
- ``fed_frac_of_device`` — best fed / device_only.
- ``feed_stages``      — per-transport, per-stage feed breakdown (mean
                         ms per sample: ring/queue wait, decode, gather,
                         device_put) so the fed/device gap is attributed
                         to a stage instead of unexplained.
- ``mfu``              — model FLOP utilization from XLA's compiled cost
                         analysis vs the chip's bf16 peak.
- ``serving_fleet``    — the fleet plane (PR 6): a mixed-length
                         workload (prompt 8-128, max_new 8-128) pushed over HTTP through the
                         least-loaded ``fleet.FleetRouter`` at 1 vs 2
                         vs 4 DecodeEngine replicas — aggregate
                         tokens/sec, router-observed p50/p99, failover
                         count (0 on a clean run), and the routing
                         overhead (request wall minus upstream wall,
                         from the router's own histograms).
                         ``scaling_2x``/``scaling_4x`` are the
                         aggregate-throughput ratios vs 1 replica; on
                         the 1-core CPU box the replicas share one
                         core, so scaling there measures the router's
                         overhead floor, not capacity (chip runs are
                         the capacity claim). The ``affinity`` subleg
                         (PR 16) pins prefix/session-aware routing:
                         warm turn-2 TTFT p50 at 4 replicas >= 3x
                         better than the load-only baseline published
                         beside it, and hot-session-skew p99 within
                         1.5x of pure load balancing (the load
                         guard). The ``qos`` subleg (PR 18) publishes
                         the antagonist isolation factor (quiet-tenant
                         p99 flooded / solo), HIGH-class preemption
                         TTFT p50/p99 into a LOW-saturated engine, and
                         the 3:1 weighted fair-share convergence time.
- ``recovery``         — the supervision plane (PR 3): MTTR of an
                         injected mid-job trainer SIGKILL under
                         ``cluster.run(..., supervise=...)``, with the
                         per-stage breakdown (detect / reform / restore
                         / first post-restore step) and the
                         ``exactly_once`` verdict (final step count and
                         consumed-data sum match an uninterrupted run).
                         CPU-pinned trainers: the number tracks the
                         supervision plane itself, not device bring-up.

Fed batches carry uint8 images (the realistic decoded-image payload; a
production input pipeline ships uint8 and normalizes on-device) with the
cast happening in the model's first op, so the host pipe moves 1 byte per
channel exactly as a tuned pipeline would.
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: images/sec/chip bar for vs_baseline: the first real-chip *device-only*
#: measurement (2026-07-29, v5e-1, bf16, batch 256: a pre-PR-1 figure
#: whose record was removed with PR 21). The fed path is judged against
#: it directly.
BASELINE_IMAGES_PER_SEC = float(os.environ.get("TFOS_BENCH_BASELINE", 0)) \
    or 1986.42

#: round-2 fed bar (bench-private feeder, pickled 32-record chunks):
#: best of queue_fed=156.49 / shm_fed=79.55 — kept for the ledger.
ROUND2_FED_IMAGES_PER_SEC = 156.49

#: dense bf16 peak FLOP/s by device kind (public TPU specs)
_PEAK_BF16 = (
    ("v6 lite", 918e12), ("v6e", 918e12),
    ("v5 lite", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v5", 459e12),
    ("v4", 275e12),
)


def _bench_map_fun(args, ctx):
    """Trainer fn for the cluster-fed benchmark: the canonical consumption
    loop (DataFeed → infeed.sharded_batches → jit step), timed from the
    second batch (first batch pays the uint8-signature compile)."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import infeed, training
    from tensorflowonspark_tpu.parallel import build_mesh

    model = _bench_model(args["on_tpu"])
    batch = args["batch"]
    image = args["image"]
    mesh = build_mesh({"data": len(jax.devices())})
    trainer = training.Trainer(model, optax.sgd(0.1, momentum=0.9), mesh,
                               remat=_bench_remat())
    state = trainer.init(
        jax.random.PRNGKey(0),
        np.zeros((batch, image, image, 3), np.float32))

    feed = ctx.get_data_feed(input_mapping={"x": "x", "y": "y"})
    # one StageTimers instance spans DataFeed (ring wait / decode /
    # gather) and the prefetcher (device_put): the whole host-side feed
    # cost of the run lands in feed.stats()["stages"]
    batches = infeed.sharded_batches(feed.numpy_batches(batch), trainer.mesh,
                                     timers=feed.timers)
    it = iter(batches)
    state, metrics = trainer.step(state, next(it))  # uint8-sig compile
    float(jax.device_get(metrics["loss"]))
    images = 0
    t0 = time.monotonic()
    for b in it:
        state, metrics = trainer.step(state, b)
        images += batch
    # device->host value read: a sync that provably drains the
    # dispatch queue, whatever the runtime
    float(jax.device_get(metrics["loss"]))
    dt = time.monotonic() - t0
    n_dev = len(jax.devices())
    stats = feed.stats()
    result = {"images_per_sec": images / dt / n_dev if images else 0.0,
              "images": images, "n_devices": n_dev,
              "feed_stats": stats,
              # per-stage feed breakdown (seconds totals + mean ms per
              # sample): where the host-side feed time actually went
              "feed_stages": stats.get("stages"),
              "feed_stages_ms": feed.timers.per_ms()}
    try:
        # measured-at-bootstrap transport selection evidence — rates from
        # the auto-probe kv plus the decision itself ("feed_transport" is
        # the effective choice; rates alone mislead in the near-tie
        # regime where the probe's 1.1x shm bias decides) — so every
        # bench artifact carries its own transport story
        probe = feed.mgr.get("feed_transport_probe")
        if probe is not None:
            probe = dict(probe)
            probe["choice"] = feed.mgr.get("feed_transport")
        result["transport_probe"] = probe
    except Exception:  # noqa: BLE001 - kv absent under forced transport
        result["transport_probe"] = None
    with open(args["result_path"], "w") as f:
        json.dump(result, f)


def _synth_partition(n_records, image, seed):
    """Executor-side record generator: one buffer, per-record views."""
    import numpy as np
    rng = np.random.RandomState(seed)
    xs = rng.randint(0, 255, size=(n_records, image, image, 3),
                     dtype=np.uint8)
    ys = (np.arange(n_records) % 1000).astype(np.int64)
    return [(xs[i], ys[i]) for i in range(n_records)]


#: transport-selection evidence from the latest auto-mode fed run (the
#: node bootstrap's measured probe, via the trainer's broker kv read)
_LAST_TRANSPORT_PROBE = {}

#: per-transport feed-stage breakdown from the latest fed run of each
#: transport (ring/queue wait, decode, gather, device_put — mean ms per
#: sample), so the artifact attributes the fed/device gap to a stage
#: instead of leaving it unexplained (VERDICT r5 #5)
_LAST_FEED_STAGES = {}


def _cluster_fed_images_per_sec(transport, batch, image, steps, on_tpu):
    """images/sec of the production fed path for one transport.

    Drives cluster.run + train + shutdown over the engine with ONE
    executor (this host's chip count) so the number covers node.py /
    manager.py / frames.py / shm.py / datafeed.py end to end.
    """
    import tempfile

    from tensorflowonspark_tpu import cluster
    from tensorflowonspark_tpu.engine import Context

    prev = os.environ.get("TFOS_FEED_TRANSPORT")
    os.environ["TFOS_FEED_TRANSPORT"] = transport
    fd, result_path = tempfile.mkstemp(prefix="tfos-bench-", suffix=".json")
    os.close(fd)
    try:
        sc = Context(num_executors=1)
        try:
            tfc = cluster.run(
                sc, _bench_map_fun,
                {"batch": batch, "image": image, "on_tpu": on_tpu,
                 "result_path": result_path},
                num_executors=1, input_mode=cluster.InputMode.SPARK)
            # +1 batch: the first batch is compile warmup, untimed
            n_records = batch * (steps + 1)
            # 4 partitions, each a multiple of the device batch so no
            # short batches (and no recompiles) at partition boundaries
            per_part = -(-n_records // 4 // batch) * batch
            rdd = sc.parallelize(range(4), 4).mapPartitionsWithIndex(
                lambda i, _: iter(_synth_partition(per_part, image, seed=i)))
            tfc.train(rdd, num_epochs=1)
            tfc.shutdown()
        finally:
            sc.stop()
        with open(result_path) as f:
            result = json.load(f)
        if result.get("transport_probe"):
            _LAST_TRANSPORT_PROBE.clear()
            _LAST_TRANSPORT_PROBE.update(result["transport_probe"])
        if result.get("feed_stages_ms"):
            _LAST_FEED_STAGES[transport] = result["feed_stages_ms"]
        if os.environ.get("TFOS_BENCH_VERBOSE"):
            print("cluster_fed[{}]: {}".format(transport, result),
                  file=sys.stderr)
        return result["images_per_sec"]
    except Exception as e:  # noqa: BLE001 - reported, then fails the run
        print("cluster_fed[{}] failed: {}".format(transport, e),
              file=sys.stderr)
        _FAILED_LEGS.append("cluster_fed[{}]".format(transport))
        return None
    finally:
        if prev is None:
            os.environ.pop("TFOS_FEED_TRANSPORT", None)
        else:
            os.environ["TFOS_FEED_TRANSPORT"] = prev
        try:
            os.unlink(result_path)
        except OSError:
            pass


def _mfu(trainer, state, batch_data, images_per_sec_per_chip, batch,
         n_devices):
    """images/sec x FLOPs/image (XLA cost analysis) vs the bf16 peak."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    peak = next((p for key, p in _PEAK_BF16 if key in kind), None)
    if peak is None:
        return None
    try:
        cost = trainer._jit_step.lower(state, batch_data).compile() \
            .cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops_per_step = float(cost["flops"])
    except Exception:  # noqa: BLE001 - cost analysis is best-effort
        return None
    flops_per_img = flops_per_step / batch / n_devices
    return images_per_sec_per_chip * flops_per_img / peak


def _bench_remat():
    """TFOS_BENCH_REMAT=1: rematerialized backward (jax.checkpoint) —
    the knob for pushing batch into the HBM ceiling on the sweep."""
    return os.environ.get("TFOS_BENCH_REMAT") == "1"


def _bench_model(on_tpu):
    """ResNet-50 (tiny variant on CPU smoke), with perf-experiment knobs:
    TFOS_BENCH_BN_DTYPE=bfloat16 runs BatchNorm in bf16 (halves the HBM
    traffic of every norm; stats/params stay fp32)."""
    import jax.numpy as jnp

    bn_dtype = jnp.bfloat16 \
        if os.environ.get("TFOS_BENCH_BN_DTYPE") == "bfloat16" \
        else jnp.float32
    if on_tpu:
        from tensorflowonspark_tpu.models.resnet import ResNet50
        return ResNet50(bn_dtype=bn_dtype)
    from tensorflowonspark_tpu.models.resnet import ResNet
    return ResNet(stage_sizes=[1, 1], num_classes=10, width=8,
                  bn_dtype=bn_dtype)


def _median(values):
    from tensorflowonspark_tpu import metrics_report
    return metrics_report.median(values)


def _device_only(on_tpu, batch, image, steps, warmup):
    """Step time with the batch staged in HBM once (the ceiling)."""
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.parallel import build_mesh

    model = _bench_model(on_tpu)

    mesh = build_mesh({"data": len(jax.devices())})
    trainer = training.Trainer(model, optax.sgd(0.1, momentum=0.9), mesh,
                               remat=_bench_remat())

    rng = np.random.RandomState(0)
    x = rng.rand(batch, image, image, 3).astype(np.float32)
    y = (np.arange(batch) % 10).astype(np.int64)
    batch_data = jax.device_put({"x": x, "y": y}, trainer.batch_sharding)

    state = trainer.init(jax.random.PRNGKey(0), x)
    for _ in range(warmup):
        state, metrics = trainer.step(state, batch_data)
    float(jax.device_get(metrics["loss"]))

    # CPU smoke: median of 3 timed spins — single-spin device numbers
    # jitter with box load and make fed_frac_of_device read as noise
    # (evidence discipline, VERDICT r4 weak #6 spirit). Chip runs are
    # stable and expensive: one spin.
    rates = []
    for _ in range(1 if on_tpu else 3):
        t0 = time.monotonic()
        for _ in range(steps):
            state, metrics = trainer.step(state, batch_data)
        float(jax.device_get(metrics["loss"]))
        rates.append(batch * steps / (time.monotonic() - t0))

    n_dev = len(jax.devices())
    rate = _median(rates) / n_dev
    mfu = _mfu(trainer, state, batch_data, rate, batch, n_dev)
    return rate, mfu


def _serving_workload(n_requests, total_len, vocab, seed=0):
    """Mixed-length generation traffic: (prompt, max_new) pairs with
    prompt 8-128 and max_new 8-128 (multiples of 8, so the baseline's
    per-signature compile count stays bounded enough to measure), every
    request fitting ``prompt + max_new <= total_len``. Prompts cap at
    ``total_len // 2`` so small-cache configs (scripts/profile_fleet
    shares this generator) still leave decode room; at the bench's own
    total_len=256 that cap is 128 — no change to the published
    workload. Needs ``total_len >= 16``."""
    import numpy as np
    rng = np.random.RandomState(seed)
    reqs = []
    for _ in range(n_requests):
        p_len = int(rng.choice(range(8, min(129, total_len // 2 + 1), 8)))
        max_new = int(rng.choice(range(8, 129, 8)))
        max_new = min(max_new, total_len - p_len)
        prompt = rng.randint(0, vocab, size=p_len).tolist()
        reqs.append((prompt, max_new))
    return reqs


def _serving_model(on_tpu):
    """Decoder LM for the serving bench (shape-matched to the box)."""
    from tensorflowonspark_tpu.models.decoder import DecoderLM
    kw = dict(vocab=256, hidden=256 if on_tpu else 64,
              num_heads=8 if on_tpu else 4,
              num_layers=4 if on_tpu else 2, max_len=256)
    return (DecoderLM(decode=False, **kw), DecoderLM(decode=True, **kw))


def _fleet_leg(dec, params, reqs, n_replicas, slots=8, concurrency=None):
    """Push ``reqs`` over HTTP through a FleetRouter fronting
    ``n_replicas`` in-process DecodeEngines; returns (aggregate
    tokens/sec, router-observed latency quantiles, stats). THE
    fleet-measurement harness — scripts/profile_fleet.py imports it so
    bench numbers and routing-overhead attributions describe the same
    run shape. All percentiles and the overhead split are read from
    the router's OWN MetricsRegistry histograms (the objects its
    ``GET /metrics`` renders)."""
    import concurrent.futures
    import json as json_mod
    import urllib.request

    from tensorflowonspark_tpu import fleet, metrics_report

    with fleet.ServingFleet(dec, params, replicas=n_replicas,
                            engine_kw={"slots": slots}) as f:
        url = f.url("/v1/models/model:generate")

        def one(req):
            prompt, max_new = req
            body = json_mod.dumps({"prompt": prompt,
                                   "max_new_tokens": max_new}).encode()
            http_req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(http_req, timeout=1800) as r:
                out = json_mod.loads(r.read())
            return len(out["tokens"]) - len(prompt)

        workers = concurrency or min(16, 4 * n_replicas)
        t0 = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            tokens = sum(pool.map(one, reqs))
        wall = time.monotonic() - t0
        counts = f.router.counters.snapshot()["counts"]
        registry = f.router.metrics
        quantiles = metrics_report.quantiles_ms(
            registry.get_histogram("tfos_fleet_request_seconds"))
        stats = {
            "replicas": n_replicas, "slots_per_replica": slots,
            "concurrency": workers,
            "tokens": int(tokens), "wall_s": round(wall, 3),
            "failovers": counts.get("failovers", 0),
            "no_replica": counts.get("no_replica", 0),
            "upstream": metrics_report.quantiles_ms(
                registry.get_histogram("tfos_fleet_upstream_seconds")),
            "route_overhead": metrics_report.quantiles_ms(
                registry.get_histogram(
                    "tfos_fleet_route_overhead_seconds")),
            "stage_ms": metrics_report.stage_ms(f.router.timers),
        }
        return tokens / wall, quantiles, stats


def _autoscale_leg(dec, params, slots=4):
    """serving_fleet.autoscale (PR 13): offered load ramps up then
    down against a min=1/max=2 SLO-autoscaled fleet. Published claims:
    the replica count TRACKS the load (>=1 scale-up during the high
    plateau, >=1 scale-down back at low load — the scale-down lands
    UNDER live traffic, so it also pins zero-loss retirement), p99 at
    every plateau, and zero client-visible failures / zero duplicate
    completions across every transition. Closed-loop offered load
    (N workers, each holding one request open) so 'offered load' has
    one number per plateau."""
    import concurrent.futures
    import json as json_mod
    import math
    import threading
    import urllib.request

    from tensorflowonspark_tpu import fleet as fleet_mod
    from tensorflowonspark_tpu.autoscale import AutoscalePolicy

    policy = AutoscalePolicy(
        min_replicas=1, max_replicas=2, queue_wait_slo_s=0.25,
        up_cooldown_s=0.5, down_cooldown_s=2.5, occupancy_low=0.35,
        dead_after_s=10.0)
    with fleet_mod.ServingFleet(dec, params, replicas=1,
                                engine_kw={"slots": slots}) as f:
        ctl = f.autoscale(policy=policy, interval=0.1)
        url = f.url("/v1/models/model:generate")
        responses_by_request = {}
        resp_lock = threading.Lock()

        def one(req_key, prompt, max_new):
            body = json_mod.dumps({"prompt": prompt,
                                   "max_new_tokens": max_new}).encode()
            http_req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.monotonic()
            with urllib.request.urlopen(http_req, timeout=600) as r:
                r.read()
                status = r.status
            with resp_lock:
                responses_by_request[req_key] = \
                    responses_by_request.get(req_key, 0) + 1
            return status, time.monotonic() - t0

        trajectory = []
        stop = threading.Event()
        t_start = time.monotonic()

        def sampler():
            while not stop.is_set():
                trajectory.append(
                    (round(time.monotonic() - t_start, 2),
                     len(f.reservation.serving_snapshot())))
                time.sleep(0.25)

        threading.Thread(target=sampler, daemon=True).start()

        def plateau(name, workers, n_requests):
            walls, failures = [], 0
            reqs = [("{}:{}".format(name, i),
                     [(i % 5) + 1, 2, 3, (i % 3) + 1], 16)
                    for i in range(n_requests)]
            lo = len(f.reservation.serving_snapshot())
            hi = lo
            with concurrent.futures.ThreadPoolExecutor(workers) as pool:
                futures = [pool.submit(one, *req) for req in reqs]
                for fut in futures:
                    try:
                        status, wall = fut.result()
                        if status == 200:
                            walls.append(wall)
                        else:
                            failures += 1
                    except Exception:  # noqa: BLE001 - counted
                        failures += 1
                    n = len(f.reservation.serving_snapshot())
                    lo, hi = min(lo, n), max(hi, n)
            p99 = None
            if walls:
                # ceil-rank (the worst request is IN the p99 at n<=100)
                p99 = sorted(walls)[min(len(walls) - 1,
                                        int(math.ceil(
                                            0.99 * len(walls))) - 1)]
            return {"plateau": name, "workers": workers,
                    "requests": n_requests, "failures": failures,
                    "p99_ms": round(p99 * 1e3, 1)
                    if p99 is not None else None,
                    "replicas_range": [lo, hi],
                    "replicas_end":
                        len(f.reservation.serving_snapshot())}

        phases = [plateau("low_1", 2, 10),
                  plateau("high", 12, 36),
                  plateau("low_2", 2, 14)]
        # trail low-rate traffic until the scale-down lands (bounded):
        # the retirement must happen UNDER load to pin zero loss
        deadline = time.monotonic() + 25.0
        tail_reqs = 0
        while time.monotonic() < deadline and ctl.counters.snapshot()[
                "counts"].get("scale_downs", 0) < 1:
            one("tail:{}".format(tail_reqs), [1, 2, 3], 8)
            tail_reqs += 1
            time.sleep(0.2)
        stop.set()
        counts = ctl.counters.snapshot()["counts"]
        down_events = ctl.events.events("autoscale_scaled_down")
        duplicates = sum(n - 1 for n in responses_by_request.values()
                         if n > 1)
        # compact the trajectory: keep points where the count changes
        # (plus endpoints) so the artifact stays readable
        compact = [pt for i, pt in enumerate(trajectory)
                   if i in (0, len(trajectory) - 1)
                   or trajectory[i - 1][1] != pt[1]]
        return {
            "policy": {"min": 1, "max": 2,
                       "queue_wait_slo_s": policy.queue_wait_slo_s,
                       "down_cooldown_s": policy.down_cooldown_s},
            "phases": phases,
            "tail_requests": tail_reqs,
            "scale_ups": counts.get("scale_ups", 0),
            "scale_downs": counts.get("scale_downs", 0),
            "scale_down_drained_clean":
                bool(down_events and down_events[-1]["drained_clean"]),
            "failures": sum(p["failures"] for p in phases),
            "duplicate_completions": duplicates,
            "replica_trajectory": compact,
        }


def _affinity_leg(slots=4, n_replicas=4, sessions=16,
                  prefix_len=192, turn1_new=24, turn2_new=2):
    """serving_fleet.affinity (PR 16): prefix-aware routing vs the
    load-only baseline on the SAME multi-turn workload. Two claims:

    ``multi_turn`` — ``sessions`` conversations each run turn-1 then a
    turn-2 continuation (turn-1 output + fresh tokens) against a
    ``n_replicas`` fleet, once with affinity routing and once with the
    router's ``affinity_enabled=False`` baseline (fresh engines each
    run, so caches start equally empty). Turn-2 client wall at
    max_new=``turn2_new`` is the fleet-wide warm-TTFT proxy; the
    published pin is affinity p50 >= 3x better than the baseline p50
    (the baseline lands warm only when least-loaded happens to pick
    the caching replica — the ~1/N the motivation cites).

    ``hot_skew`` — one session receives a concurrent burst (every
    request naming the SAME warm replica) alongside background
    singles; the pin is affinity-routed overall p99 within 1.5x of
    pure load balancing, because the load guard diverts the burst's
    overflow instead of letting the warm replica become a hotspot
    (`affinity_breaks{load_guard}` counts the diversions).

    Both runs prewarm through one throwaway engine touching every
    prefill bucket the workload hits (including the warm TAIL bucket —
    the warm path's own compile), so compile time cancels out. The leg
    builds the larger serving model at every box size: warm-vs-cold is
    a PREFILL ratio, and the smoke model's prefill is so cheap the
    fixed per-request floor (HTTP, admission, decode steps) would
    drown the signal being measured."""
    import concurrent.futures
    import json as json_mod
    import math
    import urllib.request

    import jax
    import numpy as np

    from tensorflowonspark_tpu import fleet as fleet_mod
    from tensorflowonspark_tpu import serving

    train, dec = _serving_model(True)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, dec.max_len), np.int32))["params"]
    rs = np.random.RandomState(3)
    turn1 = [[int(t) for t in rs.randint(1, dec.vocab, prefix_len)]
             for _ in range(sessions)]
    # turn-1 outputs are deterministic (greedy decode), so one
    # throwaway engine both precomputes every turn-2 prompt and
    # prewarms every prefill bucket either fleet will hit
    with serving.DecodeEngine(dec, params, slots=slots) as warm_eng:
        outs = [warm_eng.submit(p, turn1_new).result(600)
                for p in turn1]
        turn2 = [out + [int(t) for t in rs.randint(1, dec.vocab, 2)]
                 for out in outs]
        for p2 in turn2:
            warm_eng.submit(p2, 1).result(600)

    def pctl(walls, q):
        if not walls:
            return None
        walls = sorted(walls)
        return walls[min(len(walls) - 1,
                         int(math.ceil(q * len(walls))) - 1)]

    def run(affinity):
        with fleet_mod.ServingFleet(
                dec, params, replicas=n_replicas,
                engine_kw={"slots": slots},
                router_kw={"affinity_enabled": affinity}) as f:
            url = f.url("/v1/models/model:generate")

            def turn(session, prompt, max_new):
                body = json_mod.dumps(
                    {"prompt": prompt, "max_new_tokens": max_new,
                     "session": session}).encode()
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                t0 = time.monotonic()
                with urllib.request.urlopen(req, timeout=600) as r:
                    r.read()
                return time.monotonic() - t0

            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                # turn-1: establish per-session caches (and, under
                # affinity, the session -> replica map entries).
                # CONCURRENT so backlog spreads the sessions across
                # the fleet — the scatter that makes turn-2 routing
                # matter at all
                list(pool.map(
                    lambda i: turn("s{}".format(i), turn1[i],
                                   turn1_new), range(sessions)))
            # turn-2: SEQUENTIAL, one in flight — each wall is a clean
            # TTFT proxy (prefill + fixed floor), not a measurement of
            # the box's CPU contention under 8 concurrent prefills
            t2_walls = [turn("s{}".format(i), turn2[i], turn2_new)
                        for i in range(sessions)]
            # hot-session skew: seed one hot conversation warm, then
            # burst it concurrently alongside unique-session singles
            turn("hot", turn1[0], turn1_new)
            burst = [("hot", turn2[0]) for _ in range(3 * n_replicas)] \
                + [("bg{}".format(i), turn1[i])
                   for i in range(1, n_replicas + 1)]
            with concurrent.futures.ThreadPoolExecutor(
                    len(burst)) as pool:
                skew_walls = list(pool.map(
                    lambda sp: turn(sp[0], sp[1], turn2_new), burst))
            counts = f.router.counters.snapshot()["counts"]
            breaks = dict(f.router._affinity_breaks)
            return {
                "turn2_ttft_p50_ms":
                    round(pctl(t2_walls, 0.5) * 1e3, 1),
                "skew_p99_ms": round(pctl(skew_walls, 0.99) * 1e3, 1),
                "affinity_hits": counts.get("affinity_hits", 0),
                "affinity_breaks": breaks,
                "map_entries": len(f.router.affinity),
            }

    warm = run(True)
    cold = run(False)
    out = {
        "replicas": n_replicas, "slots_per_replica": slots,
        "sessions": sessions,
        "workload": {"prefix_len": prefix_len, "turn1_new": turn1_new,
                     "turn2_new": turn2_new},
        "affinity": warm,
        "load_only_baseline": cold,
    }
    if cold["turn2_ttft_p50_ms"] and warm["turn2_ttft_p50_ms"]:
        out["warm_ttft_speedup"] = round(
            cold["turn2_ttft_p50_ms"] / warm["turn2_ttft_p50_ms"], 2)
    if cold["skew_p99_ms"] and warm["skew_p99_ms"]:
        out["skew_p99_vs_balance"] = round(
            warm["skew_p99_ms"] / cold["skew_p99_ms"], 2)
    return out


def _disagg_leg(slots=4, n_prefill=1, n_decode=2, bombers=6,
                chat_sessions=8, chat_turns=4, chat_new=16,
                long_len=224, chat_len=12, block_size=16,
                kv_blocks=256):
    """serving_fleet.disagg (PR 17): prefill/decode disaggregation
    under prompt bombardment, against co-located serving of the SAME
    total width on the SAME workload.

    The workload is the disaggregation motivation in miniature: a
    steady chat plane (short prompts, ``chat_new`` decode steps each —
    the latency-sensitive stream) while ``bombers`` threads hammer the
    fleet with FRESH long prompts (never repeated, so every one is a
    cold prefill somewhere). Co-located, each long prefill runs on the
    scheduler thread of whatever mixed replica catches it, stalling
    every in-flight chat stream there for the whole prefill; split,
    the prefill tier absorbs the long prompts and ships the filled
    int8 KV blocks to the decode tier, whose own prefill collapses to
    a block-table splice hit — chat decode never waits behind a
    stranger's prompt.

    Published pins: chat per-token p99 (request wall / tokens
    generated — the decode-interactivity proxy; wall includes the
    chat's own short prefill in BOTH configs) disaggregated vs
    co-located, the same comparison at a doubled prefill tier (TTFT
    scaling with prefill width, read off the long-prompt walls), and
    the shipped-bytes accounting: physical int8 wire bytes (codes +
    per-head scales, via the very pack path the ship moves) against
    the same blocks packed from an fp pool — the PR 15 economics,
    measured end to end rather than asserted."""
    import concurrent.futures
    import json as json_mod
    import math
    import threading
    import urllib.request

    import jax
    import numpy as np

    from tensorflowonspark_tpu import fleet as fleet_mod
    from tensorflowonspark_tpu import frames, serving

    train, dec = _serving_model(True)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, dec.max_len), np.int32))["params"]
    engine_kw = {"slots": slots, "kv_block_size": block_size,
                 "kv_blocks": kv_blocks, "kv_dtype": "int8"}
    rs = np.random.RandomState(17)
    chats = [[int(t) for t in rs.randint(1, dec.vocab, chat_len)]
             for _ in range(chat_sessions)]
    warm_longs = [[int(t) for t in rs.randint(1, dec.vocab, long_len)]
                  for _ in range(2)]
    # prewarm through one throwaway engine with the SAME pool config:
    # every prefill bucket both fleets will hit (chat + long), so
    # compile time cancels out of the comparison
    with serving.DecodeEngine(dec, params, **engine_kw) as warm_eng:
        warm_eng.submit(chats[0], chat_new).result(600)
        warm_eng.submit(warm_longs[0], 4).result(600)

    def pctl(walls, q):
        if not walls:
            return None
        walls = sorted(walls)
        return walls[min(len(walls) - 1,
                         int(math.ceil(q * len(walls))) - 1)]

    def run(tiers):
        fleet_kw = dict(engine_kw=dict(engine_kw), name="model")
        if tiers:
            fleet_kw["tiers"] = dict(tiers)
        else:
            fleet_kw["replicas"] = n_prefill + n_decode
        with fleet_mod.ServingFleet(dec, params, **fleet_kw) as f:
            url = f.url("/v1/models/model:generate")

            def call(prompt, max_new, session=None):
                payload = {"prompt": prompt, "max_new_tokens": max_new}
                if session is not None:
                    payload["session"] = session
                req = urllib.request.Request(
                    url, data=json_mod.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"})
                t0 = time.monotonic()
                with urllib.request.urlopen(req, timeout=600) as r:
                    r.read()
                return time.monotonic() - t0

            stop = threading.Event()
            long_walls = []
            walls_lock = threading.Lock()

            def bombard(i):
                # FRESH prompts per iteration: every long prefill is
                # cold somewhere, the sustained pressure the split is
                # for (a repeating prompt set would warm every cache
                # and measure nothing after the first lap)
                brs = np.random.RandomState(100 + i)
                while not stop.is_set():
                    prompt = [int(t) for t in
                              brs.randint(1, dec.vocab, long_len)]
                    try:
                        w = call(prompt, 4)
                    except Exception:  # noqa: BLE001 - teardown race
                        break
                    with walls_lock:
                        long_walls.append(w)

            threads = [threading.Thread(target=bombard, args=(i,),
                                        daemon=True)
                       for i in range(bombers)]
            for t in threads:
                t.start()
            time.sleep(0.5)  # bombardment reaches steady state

            def chat_plane(i):
                walls = []
                for _ in range(chat_turns):
                    walls.append(call(chats[i], chat_new,
                                      session="chat{}".format(i)))
                return walls

            with concurrent.futures.ThreadPoolExecutor(
                    chat_sessions) as pool:
                per_turn = list(pool.map(chat_plane,
                                         range(chat_sessions)))
            stop.set()
            for t in threads:
                t.join(timeout=600)
            chat_walls = [w for walls in per_turn for w in walls]
            per_token = [w / chat_new for w in chat_walls]
            counts = f.router.counters.snapshot()["counts"]
            shipped_bytes = shipped_blocks = spliced_blocks = 0
            for r in f.replicas:
                kv = r.server.engine.kv_counters.snapshot()["counts"]
                shipped_bytes += kv.get("ship_bytes", 0)
                shipped_blocks += kv.get("ship_blocks", 0)
                spliced_blocks += kv.get("spliced_blocks", 0)
            return {
                "chat_per_token_p50_ms":
                    round(pctl(per_token, 0.5) * 1e3, 2),
                "chat_per_token_p99_ms":
                    round(pctl(per_token, 0.99) * 1e3, 2),
                "long_prompt_p50_ms":
                    round(pctl(long_walls, 0.5) * 1e3, 1)
                    if long_walls else None,
                "long_prompts_served": len(long_walls),
                "prefill_dispatches":
                    counts.get("prefill_dispatches", 0),
                "prefill_ships": counts.get("prefill_ships", 0),
                "shipped_bytes": shipped_bytes,
                "shipped_blocks": shipped_blocks,
                "spliced_blocks": spliced_blocks,
            }

    colocated = run(None)
    disagg = run({"prefill": n_prefill, "decode": n_decode})
    wide = run({"prefill": 2 * n_prefill, "decode": n_decode})

    # shipped-bytes accounting, through the very pack path the ship
    # moves: the same prompt's resident blocks from an int8 pool vs an
    # fp pool of identical geometry. Physical wire bytes (codes +
    # per-head scales + frame header) — never the logical dequantized
    # size (that's the satellite-1 accounting bug this PR fixes).
    probe = warm_longs[1]
    wire = {}
    for dtype in ("int8", None):
        kw = dict(engine_kw, kv_dtype=dtype, slots=2, kv_blocks=64)
        with serving.DecodeEngine(dec, params, **kw) as eng:
            eng.submit(probe, 1).result(600)
            exported = eng.export_prefix(probe)
            assert exported is not None
            buffers, meta = exported
            wire[dtype or "fp"] = {
                "bytes": frames.frame_bytes(buffers),
                "blocks": len(meta["origins"]),
            }
    per_block_int8 = wire["int8"]["bytes"] / wire["int8"]["blocks"]
    per_block_fp = wire["fp"]["bytes"] / wire["fp"]["blocks"]
    out = {
        "replicas_total": n_prefill + n_decode,
        "tiers": {"prefill": n_prefill, "decode": n_decode},
        "workload": {"bombers": bombers, "long_len": long_len,
                     "chat_sessions": chat_sessions,
                     "chat_turns": chat_turns, "chat_len": chat_len,
                     "chat_new": chat_new},
        "colocated": colocated,
        "disaggregated": disagg,
        "prefill_x2": wide,
        "ship_wire": {
            "int8_bytes_per_block": round(per_block_int8, 1),
            "fp_bytes_per_block": round(per_block_fp, 1),
            "int8_vs_fp_pool": round(per_block_int8 / per_block_fp, 4),
        },
    }
    if colocated["chat_per_token_p99_ms"] \
            and disagg["chat_per_token_p99_ms"]:
        out["chat_p99_speedup"] = round(
            colocated["chat_per_token_p99_ms"]
            / disagg["chat_per_token_p99_ms"], 2)
    if disagg["long_prompt_p50_ms"] and wide["long_prompt_p50_ms"]:
        out["long_p50_prefill_x2_speedup"] = round(
            disagg["long_prompt_p50_ms"]
            / wide["long_prompt_p50_ms"], 2)
    return out


def _qos_leg(slots=4, block_size=16, kv_blocks=192, quiet_reqs=10,
             antagonists=3, high_probes=8):
    """serving_fleet.qos (PR 18): the three numbers the QoS plane is
    for, measured on the live engine rather than asserted.

    ``isolation`` — a quiet HIGH-class tenant's request p99 while an
    antagonist floods the same engine at LOW class (the interactive
    tier vs batch tier split docs/qos.md recommends), over its SOLO
    p99 on the idle warmed engine (the chaos test pins the
    bounded-factor contract; the bench publishes the measured
    factor). Class preemption is what keeps this near 1: the plan
    names a LOW victim the moment the HIGH request is blocked, so
    the quiet tenant never waits out the antagonist's whole queue.

    ``preemption`` — HIGH-class time-to-first-token while every slot
    is held by LOW-class long sequences: the submit->first-token wall
    IS the preemption latency (plan names a victim at the next step
    boundary, the freed slot prefills the HIGH request). p50/p99 over
    ``high_probes`` sequential probes.

    ``fair_share`` — two flooding tenants at weights 3:1; convergence
    time is the first moment the cumulative admitted ratio (read from
    ``engine.qos_tallies()`` — the same tallies the /metrics scrape
    renders as ``tfos_qos_admitted_total``) lands within 25% of the
    configured ratio and the deficit scheduler keeps it there."""
    import math
    import threading

    import jax
    import numpy as np

    from tensorflowonspark_tpu import metrics_report, serving

    train, dec = _serving_model(False)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, dec.max_len), np.int32))["params"]
    engine_kw = {"slots": slots, "kv_block_size": block_size,
                 "kv_blocks": kv_blocks}
    rs = np.random.RandomState(23)

    def pctl(walls, q):
        if not walls:
            return None
        walls = sorted(walls)
        return walls[min(len(walls) - 1,
                         int(math.ceil(q * len(walls))) - 1)]

    quiet_prompts = [[int(t) for t in rs.randint(1, dec.vocab, 8)]
                     for _ in range(quiet_reqs)]

    def quiet_pass(eng):
        walls = []
        for p in quiet_prompts:
            t0 = time.monotonic()
            eng.submit(p, 16, tenant="quiet",
                       priority="high").result(600)
            walls.append(time.monotonic() - t0)
        return walls

    # --- isolation: solo baseline, then the same pass under flood ---
    with serving.DecodeEngine(dec, params, **engine_kw) as eng:
        quiet_pass(eng)  # warm every program/bucket off the clock
        solo = quiet_pass(eng)
        stop = threading.Event()

        def flood(i):
            brs = np.random.RandomState(200 + i)
            while not stop.is_set():
                prompt = [int(t) for t in brs.randint(1, dec.vocab, 16)]
                try:
                    eng.submit(prompt, 32, tenant="antagonist",
                               priority="low").result(600)
                except serving.QueueFull:
                    stop.wait(0.01)
                except Exception:  # noqa: BLE001 - teardown race
                    break

        threads = [threading.Thread(target=flood, args=(i,), daemon=True)
                   for i in range(antagonists)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # flood reaches steady state
        # first flooded pass absorbs the one-time prefill-bucket
        # compiles the flood regime introduces (preemption
        # continuations are novel prompt lengths); steady state is
        # the second pass — the chaos test drops warm-up the same way
        quiet_pass(eng)
        flooded = quiet_pass(eng)
        stop.set()
        for t in threads:
            t.join(timeout=600)
        qos_plan_ms = metrics_report.stage_ms(eng.timers).get("qos_plan")
    isolation = {
        "quiet_solo_p99_ms": round(pctl(solo, 0.99) * 1e3, 1),
        "quiet_flooded_p99_ms": round(pctl(flooded, 0.99) * 1e3, 1),
        "antagonists": antagonists,
    }
    isolation["factor"] = round(isolation["quiet_flooded_p99_ms"]
                                / isolation["quiet_solo_p99_ms"], 2)

    # --- preemption latency: HIGH TTFT into a LOW-saturated engine ---
    ttfts = []
    with serving.DecodeEngine(dec, params, **engine_kw) as eng:
        eng.submit(quiet_prompts[0], 2, tenant="warm").result(600)
        # 3x slots of LOW work so the queue refills every slot a LOW
        # sequence (or a preemption victim) vacates — each probe meets
        # a genuinely saturated engine, not the tail of a drained one
        low = [eng.submit([int(t) for t in rs.randint(1, dec.vocab, 8)],
                          128, tenant="bg", priority="low")
               for _ in range(slots * 3)]
        deadline = time.monotonic() + 30
        while (eng.load_stats()["slot_occupancy"] < slots
               and time.monotonic() < deadline):
            time.sleep(0.01)
        # probe 0 is discarded: the first preemption's continuation
        # re-prefill (prompt + emitted tokens, a novel length) pays a
        # one-time bucket compile that is not preemption latency
        for probe in range(high_probes + 1):
            t0 = time.monotonic()
            h = eng.submit([int(t) for t in rs.randint(1, dec.vocab, 8)],
                           4, tenant="urgent", priority="high")
            first = None
            # no break: abandoning a stream cancels the request
            for _tok in h.stream(600):
                if first is None:
                    first = time.monotonic() - t0
            if probe > 0:
                ttfts.append(first)
            h.result(600)
        preempted = eng.qos_tallies()["preemptions"]
        for h in low:
            h.result(600)
    preemption = {
        "ttft_p50_ms": round(pctl(ttfts, 0.5) * 1e3, 1),
        "ttft_p99_ms": round(pctl(ttfts, 0.99) * 1e3, 1),
        "probes": high_probes,
        "victims": sum(preempted.values()),
    }

    # --- fair-share convergence at weights 3:1 ---
    policy = {"weights": {"heavy": 3.0, "light": 1.0}}
    with serving.DecodeEngine(dec, params, qos_policy=policy,
                              **engine_kw) as eng:
        eng.submit(quiet_prompts[0], 2, tenant="warmup").result(600)
        handles = []
        for _ in range(40):
            for tenant in ("heavy", "light"):
                handles.append(eng.submit(
                    [int(t) for t in rs.randint(1, dec.vocab, 8)],
                    4, tenant=tenant))
        # the contested window is while BOTH tenants still have queued
        # work — once either side fully admits, the other rightly gets
        # every slot and the cumulative ratio of a finite workload
        # drifts to 1.0, which says nothing about fairness
        t0 = time.monotonic()
        converged_s = None
        heavy = light = 0
        while heavy < 40 and light < 40:
            adm = eng.qos_tallies()["admitted"]
            heavy = sum(n for (t, _), n in adm.items() if t == "heavy")
            light = sum(n for (t, _), n in adm.items() if t == "light")
            if light >= 4 and abs(heavy / light - 3.0) <= 0.75:
                if converged_s is None:
                    converged_s = time.monotonic() - t0
            else:
                converged_s = None  # drifted back out: not converged
            time.sleep(0.01)
        for h in handles:
            h.result(600)
    fair_share = {
        "weights": {"heavy": 3.0, "light": 1.0},
        "admitted_at_window_end": {"heavy": heavy, "light": light},
        "contested_ratio": round(heavy / max(light, 1), 2),
        "convergence_s": (round(converged_s, 3)
                          if converged_s is not None else None),
    }
    return {
        "isolation": isolation,
        "preemption": preemption,
        "fair_share": fair_share,
        "qos_plan_ms_mean": qos_plan_ms,
    }


def _slo_leg(slots=4, n_requests=12, gray_delay_s=0.5):
    """serving_fleet.slo (PR 20): the SLO plane's three verdicts,
    measured live rather than asserted.

    ``burn`` — a 1-replica fleet with a tiny-window router-observed
    latency SLO (threshold well under the injected delay): error-budget
    remaining and firing state healthy vs under a gray link
    (``net_delay`` on the router->replica hop) vs after the heal — the
    raise/clear cycle the chaos e2e pins, with the measured fast-window
    burn published.  The windows are driven with an injected clock
    (``SloMonitor.sample(now=)``), so the leg takes seconds, not the
    window lengths.

    ``canary`` — a real tenant's request p99 with the canary loop OFF
    vs ON at a 4 Hz cadence (~20x a production probe rate) against a
    2-replica fleet, plus the canary's own probe/failure/drift
    counters: the zero-displacement claim as a measured ratio (the
    acceptance pin is <= 1.05x on a quiet box; CI noise is published,
    not hidden).

    ``attribution`` — mean cost of the pure critical-path sweep over
    the fleet's real stitched traces vs the mean request wall; the
    acceptance pin is < 1% of request wall."""
    import json as json_mod
    import threading
    import urllib.request

    import jax
    import numpy as np

    from tensorflowonspark_tpu import chaos
    from tensorflowonspark_tpu import fleet as fleet_mod
    from tensorflowonspark_tpu import slo as slo_mod

    train, dec = _serving_model(False)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, dec.max_len), np.int32))["params"]
    rs = np.random.RandomState(11)
    prompts = [[int(t) for t in rs.randint(1, dec.vocab, 8)]
               for _ in range(n_requests)]

    def pctl(walls, q):
        walls = sorted(walls)
        return walls[min(len(walls) - 1,
                         int(math.ceil(q * len(walls))) - 1)]

    def post(url, prompt, max_new, tenant=None):
        payload = {"prompt": prompt, "max_new_tokens": max_new}
        if tenant is not None:
            payload["tenant"] = tenant
        req = urllib.request.Request(
            url, data=json_mod.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=600) as r:
            r.read()
        return time.monotonic() - t0

    spec = ("name=wall,kind=latency,family=tfos_fleet_request_seconds,"
            "threshold=0.25,objective=0.9,fast=2/8/2,slow=4/16/1.5")
    out = {}
    with fleet_mod.ServingFleet(dec, params, replicas=1, name="model",
                                engine_kw={"slots": slots},
                                router_kw={"slo": spec}) as f:
        url = f.url("/v1/models/model:generate")
        monitor = f.router.slo
        for p in prompts:  # warm + healthy traffic under the bound
            post(url, p, 4)
        monitor.sample(now=0.0)
        healthy = monitor.sample(now=1.0)[0]
        try:
            chaos.arm("net_delay={},only=router:replica-0".format(
                gray_delay_s))
            gray_walls = [post(url, p, 4) for p in prompts[:6]]
        finally:
            chaos.disarm()
        gray = monitor.sample(now=3.0)[0]
        monitor.sample(now=18.0)
        healed_walls = [post(url, p, 4) for p in prompts[:4]]
        healed = monitor.sample(now=19.5)[0]
        out["burn"] = {
            "gray_delay_s": gray_delay_s,
            "healthy": {
                "firing": healthy["firing"],
                "budget_remaining": healthy["error_budget_remaining"],
            },
            "gray": {
                "firing": gray["firing"],
                "budget_remaining": gray["error_budget_remaining"],
                "fast_short_burn": gray["windows"][0]["short_burn"],
                "request_p99_ms": round(pctl(gray_walls, 0.99) * 1e3, 1),
            },
            "healed": {
                "firing": healed["firing"],
                "request_p99_ms": round(
                    pctl(healed_walls, 0.99) * 1e3, 1),
            },
            "alerts_total": monitor.engine.alerts_total(),
            "incidents": [i["kind"] for i in monitor.incidents()],
        }
        # the full /slo-shaped document, for slo_report.py --from-bench
        out["verdict"] = monitor.verdict(now=20.0)
        # attribution overhead over the SAME fleet's real traces
        with urllib.request.urlopen(f.url("/debug/trace"),
                                    timeout=60) as r:
            doc = json_mod.loads(r.read())
        ids = sorted({int(e["tid"]) for e in doc["traceEvents"]
                      if e.get("ph") == "X"
                      and int(e.get("tid", 0)) > 0})
        t0 = time.monotonic()
        reports = [slo_mod.attribute_trace(doc, trace) for trace in ids]
        sweep_s = time.monotonic() - t0
        walls = [rep["wall_s"] for rep in reports if rep["wall_s"]]
        mean_wall = sum(walls) / max(len(walls), 1)
        per_request = sweep_s / max(len(ids), 1)
        out["attribution"] = {
            "requests_attributed": len(ids),
            "mean_request_wall_ms": round(mean_wall * 1e3, 2),
            "sweep_us_per_request": round(per_request * 1e6, 1),
            "overhead_pct_of_wall": round(
                100.0 * per_request / mean_wall, 4) if mean_wall else None,
        }
    # canary displacement: a fresh 2-replica fleet, default specs
    with fleet_mod.ServingFleet(dec, params, replicas=2, name="model",
                                engine_kw={"slots": slots}) as f:
        url = f.url("/v1/models/model:generate")
        for p in prompts[:4]:  # warm both replicas
            post(url, p, 4, tenant="prod")
        # warm the CONCURRENT decode paths too (batch>1 step shapes):
        # a canary overlapping a real request must not be the first
        # batch-2 step a replica ever compiles, or the one-time compile
        # stall would be billed to the canary as displacement
        for _ in range(6):
            threads = [threading.Thread(
                target=post, args=(url, p, 4),
                kwargs={"tenant": "prod"}) for p in prompts[:3]]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        n_measure = 160
        off = [post(url, prompts[i % n_requests], 4, tenant="prod")
               for i in range(n_measure)]
        # canary prompt reuses the real traffic's shapes so the prober
        # never triggers a fresh compile mid-measurement; 4 Hz is ~20x
        # a production cadence yet still a tiny occupancy fraction
        prober = f.router.slo.attach_canary(slo_mod.CanaryProber(
            url, prompts[0], max_new_tokens=4, interval=0.25))
        prober.start()
        time.sleep(0.3)  # first probe lands before the measured window
        try:
            on = [post(url, prompts[i % n_requests], 4, tenant="prod")
                  for i in range(n_measure)]
        finally:
            prober.stop()
        counters = prober.counters()
        out["verdict"]["canary"] = {
            "counters": counters,
            "expected_pinned": prober.expected is not None,
            "history": prober.history()[-8:],
        }
        p99_off, p99_on = pctl(off, 0.99), pctl(on, 0.99)
        p50_off, p50_on = pctl(off, 0.50), pctl(on, 0.50)
        out["canary"] = {
            "real_p99_ms_off": round(p99_off * 1e3, 1),
            "real_p99_ms_on": round(p99_on * 1e3, 1),
            "p99_ratio_on_over_off": round(p99_on / p99_off, 3),
            "p50_ratio_on_over_off": round(p50_on / p50_off, 3),
            "probes": counters["probes"],
            "failures": counters["failures"],
            "drift": counters["drift"],
        }
    return out


def _serving_fleet_bench(on_tpu, replica_counts=(1, 2, 4)):
    """Aggregate serving throughput at 1 vs 2 vs 4 router-fronted
    replicas on the shared mixed-length workload. Returns the
    ``serving_fleet`` JSON block.

    Every leg runs WARM: the engine's step programs are shared per (model,
    sampling-config) across all engines, so without a prewarm the
    1-replica leg would pay every compile and the scaling ratios would
    flatter the bigger fleets with someone else's compile time. This
    block's claim is CAPACITY scaling."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu import serving

    train, dec = _serving_model(on_tpu)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, dec.max_len), np.int32))["params"]
    reqs = _serving_workload(24, dec.max_len, dec.vocab, seed=1)
    # prewarm: one throwaway engine touches the decode program and every
    # prefill bucket the workload will hit (max_new=1 requests)
    with serving.DecodeEngine(dec, params, slots=8) as warm_eng:
        warm_lens = sorted({len(p) for p, _ in reqs})
        for handle in [warm_eng.submit(list(range(1, n + 1)), 1)
                       for n in warm_lens]:
            handle.result(600)
    legs = []
    for n in replica_counts:
        tps, quantiles, stats = _fleet_leg(dec, params, reqs, n)
        legs.append(dict(tokens_per_sec=round(tps, 1), **quantiles,
                         **stats))
    by_replicas = {leg["replicas"]: leg["tokens_per_sec"]
                   for leg in legs}
    base = by_replicas.get(1)
    block = {
        "workload": {"requests": len(reqs),
                     "total_tokens": sum(mn for _, mn in reqs)},
        "legs": legs,
    }
    for n in replica_counts:
        if n > 1 and base and by_replicas.get(n):
            block["scaling_{}x".format(n)] = round(
                by_replicas[n] / base, 2)
    # autoscale load-ramp leg (PR 13): replica count tracks offered
    # load between min=1/max=2 with zero failures at every transition.
    # TFOS_BENCH_AUTOSCALE=0 skips just this leg.
    if os.environ.get("TFOS_BENCH_AUTOSCALE", "1") == "1":
        try:
            block["autoscale"] = _autoscale_leg(dec, params)
        except Exception as e:  # noqa: BLE001 - report, not die
            print("serving_fleet.autoscale failed: {}".format(e),
                  file=sys.stderr)
            block["autoscale"] = {"error": str(e)}
    # prefix/session-affinity leg (PR 16): warm turn-2 TTFT vs the
    # load-only baseline + hot-skew load-guard check.
    # TFOS_BENCH_AFFINITY=0 skips just this leg.
    if os.environ.get("TFOS_BENCH_AFFINITY", "1") == "1":
        try:
            block["affinity"] = _affinity_leg()
        except Exception as e:  # noqa: BLE001 - report, not die
            print("serving_fleet.affinity failed: {}".format(e),
                  file=sys.stderr)
            block["affinity"] = {"error": str(e)}
    # prefill/decode disaggregation leg (PR 17): chat per-token p99
    # under prompt bombardment vs co-located, TTFT scaling with
    # prefill-tier width, and the int8 ship-wire byte accounting.
    # TFOS_BENCH_DISAGG=0 skips just this leg.
    if os.environ.get("TFOS_BENCH_DISAGG", "1") == "1":
        try:
            block["disagg"] = _disagg_leg()
        except Exception as e:  # noqa: BLE001 - report, not die
            print("serving_fleet.disagg failed: {}".format(e),
                  file=sys.stderr)
            block["disagg"] = {"error": str(e)}
    # multi-tenant QoS leg (PR 18): antagonist isolation factor,
    # HIGH-class preemption TTFT, fair-share convergence time.
    # TFOS_BENCH_QOS=0 skips just this leg.
    if os.environ.get("TFOS_BENCH_QOS", "1") == "1":
        try:
            block["qos"] = _qos_leg()
        except Exception as e:  # noqa: BLE001 - report, not die
            print("serving_fleet.qos failed: {}".format(e),
                  file=sys.stderr)
            block["qos"] = {"error": str(e)}
    # serving SLO plane leg (PR 20): error-budget burn gray vs healthy,
    # canary displacement ratio, attribution sweep overhead.
    # TFOS_BENCH_SLO=0 skips just this leg.
    if os.environ.get("TFOS_BENCH_SLO", "1") == "1":
        try:
            block["slo"] = _slo_leg()
        except Exception as e:  # noqa: BLE001 - report, not die
            print("serving_fleet.slo failed: {}".format(e),
                  file=sys.stderr)
            block["slo"] = {"error": str(e)}
    return block


def _fault_plane_bench(on_tpu, flap_cycles=3, hedge_requests=24,
                       gray_delay_s=0.6):
    """Network fault plane (PR 12): two legs, both over the netchaos
    injections with FIXED seeds/windows so repeated runs see the same
    fault schedule.

    ``partition_flap`` — one router-fronted replica, ``flap_cycles``
    ``net_partition`` heal cycles where the OPENING exchange executes
    but loses its response (the ambiguous timeout): the verdict is
    zero client-visible failures AND zero duplicate completions, with
    the replica's dedup-hit counter as the proof the retries were
    absorbed rather than re-executed.

    ``hedging`` — a 2-replica fleet with one GRAY replica
    (``net_delay`` on the router->replica-0 link): request-latency p99
    with hedging OFF vs ON (quantile-derived hedge delay, first
    response wins). Clients here read whole short responses, so
    request wall clock IS their time-to-first-token.

    ``control_mttr`` — the control-plane survivability leg (PR 19):
    under live session traffic, crash the reservation server and
    restart it from its journal (detect / reconnect /
    snapshot-rebuild breakdown), then crash the router and let a warm
    standby take over. Verdicts: zero client-visible errors across
    both deaths, and the affinity warm-hit rate before vs after the
    takeover (the promoted router starts COLD by design).
    """
    import jax
    import numpy as np

    from tensorflowonspark_tpu import chaos, fleet

    train, dec = _serving_model(on_tpu)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, dec.max_len), np.int32))["params"]

    def post(url, prompt, max_new, session=None):
        import json as json_mod
        import urllib.request
        payload = {"prompt": prompt, "max_new_tokens": max_new}
        if session is not None:
            payload["session"] = session
        body = json_mod.dumps(payload).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=600) as r:
            out = json_mod.loads(r.read())
        return time.monotonic() - t0, out

    block = {}
    # -- leg 1: partition flap, retries absorbed by the dedup window --
    with fleet.ServingFleet(dec, params, replicas=1,
                            engine_kw={"slots": 4}) as f:
        url = f.url("/v1/models/model:generate")
        post(url, [1, 2, 3], 2)  # warm (compiles outside the verdict)
        eng = f.replicas[0].engine
        base = eng.counters.snapshot()["counts"]
        failures = 0
        walls = []
        for cycle in range(flap_cycles):
            chaos.arm("net_partition=router:replica-0,for=0.25")
            try:
                wall, _ = post(url, [2 + cycle, 3 + cycle, 4 + cycle], 8)
                walls.append(wall)
            except Exception:  # noqa: BLE001 - counted, not raised
                failures += 1
            chaos.disarm()
        counts = eng.counters.snapshot()["counts"]
        completions = counts.get("prefills", 0) - base.get("prefills", 0)
        dedup_hits = counts.get("dedup_hits", 0) \
            - base.get("dedup_hits", 0)
        block["partition_flap"] = {
            "cycles": flap_cycles,
            "client_failures": failures,
            "duplicate_completions": completions - (flap_cycles
                                                    - failures),
            "dedup_hits": dedup_hits,
            "p50_ms": round(float(_median(walls)) * 1e3, 1)
            if walls else None,
            "zero_loss": failures == 0
            and completions == flap_cycles - failures
            and dedup_hits >= flap_cycles,
        }

    # -- leg 2: hedged requests vs one gray replica --
    def hedge_leg(hedge_quantile):
        router_kw = {} if hedge_quantile is None else {
            "hedge_quantile": hedge_quantile, "hedge_min_samples": 8,
            "hedge_min_delay": 0.05}
        with fleet.ServingFleet(dec, params, replicas=2,
                                engine_kw={"slots": 4},
                                router_kw=router_kw) as f:
            url = f.url("/v1/models/model:generate")
            rng = np.random.RandomState(3)
            for i in range(10):  # warm + build the hedge-delay evidence
                post(url, [1 + (i % 5), 2], 2)
            chaos.arm("net_delay={},only=router:replica-0".format(
                gray_delay_s))
            walls = []
            for i in range(hedge_requests):
                prompt = [int(t) for t in
                          rng.randint(1, dec.vocab, size=4)]
                wall, _ = post(url, prompt, 8)
                walls.append(wall)
            chaos.disarm()
            counts = f.router.counters.snapshot()["counts"]
            walls.sort()
            # nearest-rank p99: ceil(0.99*n) — at n=24 that is the MAX,
            # so the one worst request cannot hide outside the tail
            p99_idx = min(len(walls) - 1,
                          max(0, math.ceil(len(walls) * 0.99) - 1))
            return {
                "requests": hedge_requests,
                "p50_ms": round(walls[len(walls) // 2] * 1e3, 1),
                "p99_ms": round(walls[p99_idx] * 1e3, 1),
                "hedges": counts.get("hedges", 0),
                "hedge_wins": counts.get("hedge_wins", 0),
            }

    baseline = hedge_leg(None)
    hedged = hedge_leg(0.9)
    block["hedging"] = {
        "gray_delay_ms": gray_delay_s * 1e3,
        "baseline": baseline,
        "hedged": hedged,
        "p99_improvement": round(
            baseline["p99_ms"] / hedged["p99_ms"], 2)
        if hedged["p99_ms"] else None,
    }

    # -- leg 3: control-plane MTTR (PR 19) --
    # Kill the CONTROL plane twice under live session traffic — the
    # reservation server (journal-seeded restart: detect / reconnect /
    # snapshot-rebuild breakdown) and then the router (warm-standby
    # takeover) — and report the repair timeline plus the two verdicts
    # that make the timeline honest: client-visible errors (target 0;
    # the data plane never stopped) and the affinity warm-hit rate
    # before vs after the takeover rebuild (the promoted router starts
    # COLD by design and re-learns pins from live traffic).
    import tempfile as tempfile_mod
    import threading as threading_mod

    from tensorflowonspark_tpu import chaos as chaos_mod

    journal = os.path.join(
        tempfile_mod.mkdtemp(prefix="tfos-bench-control"),
        "control.journal")
    with fleet.ServingFleet(dec, params, replicas=2,
                            engine_kw={"slots": 4}, beat_interval=0.1,
                            journal=journal) as f:
        def spost(session, prompt, max_new=4):
            # f.url() re-reads f.router: follows the takeover
            return post(f.url("/v1/models/model:generate"),
                        prompt, max_new, session=session)

        spost("warm", [1, 2, 3], 2)  # compiles outside the verdict

        def hit_rate(rounds=8):
            base = f.router.counters.snapshot()["counts"]
            for i in range(rounds):
                spost("sess-%d" % (i % 4), [1 + i % 5, 2, 3])
            counts = f.router.counters.snapshot()["counts"]
            req = counts.get("requests", 0) - base.get("requests", 0)
            hits = counts.get("affinity_hits", 0) \
                - base.get("affinity_hits", 0)
            return hits / req if req else 0.0

        hit_rate()  # learn the session pins
        warm_hit_rate = hit_rate()

        errors = [0]
        stop = threading_mod.Event()

        def client_loop():
            # a router DEATH severs in-flight TCP connections — no
            # server-side retry can hide that, so the realistic client
            # (and the one the e2e pins) retries against the promoted
            # router. An error here = a request that failed even after
            # bounded retries: actual lost work, not a dropped socket.
            i = 0
            while not stop.is_set():
                for _ in range(8):
                    try:
                        spost("sess-%d" % (i % 4), [1 + i % 5, 2, 3])
                        break
                    except Exception:  # noqa: BLE001 - retried
                        time.sleep(0.25)
                else:
                    errors[0] += 1
                i += 1
                time.sleep(0.05)

        client = threading_mod.Thread(
            target=client_loop, daemon=True,
            name="tfos-bench-control-client")
        client.start()
        time.sleep(0.3)

        # reservation-server death -> journal-seeded restart
        t_crash = time.monotonic()
        f.reservation.crash()
        chaos_mod.poll_until(
            lambda: all(r._backoff for r in f.replicas), timeout=30)
        detect_s = time.monotonic() - t_crash  # beat loops noticed
        f.restart_reservation()
        t_restart = time.monotonic()
        chaos_mod.poll_until(
            lambda: all(r.beat_reconnects >= 1 for r in f.replicas),
            timeout=30)
        reconnect_s = time.monotonic() - t_restart
        chaos_mod.poll_until(
            lambda: len(f.reservation.serving_snapshot()) == 2
            and not f.reservation.recovering(), timeout=30)
        rebuild_s = time.monotonic() - t_restart
        reservation_mttr_s = time.monotonic() - t_crash

        # router death -> warm-standby takeover
        sb = fleet.RouterStandby(f, probe_interval=0.1, confirm=3)
        sb.start()
        time.sleep(0.5)  # standby shadows at least one quota snapshot
        t_kill = time.monotonic()
        f.router.crash()
        took_over = sb.took_over.wait(timeout=30)
        takeover_s = time.monotonic() - t_kill
        serve_s = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                spost("probe", [1, 2, 3], 2)
                serve_s = time.monotonic() - t_kill
                break
            except Exception:  # noqa: BLE001 - until deadline
                time.sleep(0.05)
        cold_hit_rate = hit_rate()      # promoted router starts cold
        rebuilt_hit_rate = hit_rate()   # pins re-learned from traffic
        sb.stop()

        stop.set()
        client.join(timeout=30)
        block["control_mttr"] = {
            "reservation": {
                "detect_ms": round(detect_s * 1e3, 1),
                "reconnect_ms": round(reconnect_s * 1e3, 1),
                "snapshot_rebuild_ms": round(rebuild_s * 1e3, 1),
                "mttr_ms": round(reservation_mttr_s * 1e3, 1),
            },
            "router_takeover": {
                "took_over": bool(took_over),
                "takeover_ms": round(takeover_s * 1e3, 1),
                "first_served_ms": round(serve_s * 1e3, 1)
                if serve_s is not None else None,
                "control_epoch": f.control_epoch,
            },
            "client_errors": errors[0],
            "affinity_hit_rate": {
                "warm_before": round(warm_hit_rate, 3),
                "cold_after_takeover": round(cold_hit_rate, 3),
                "rebuilt": round(rebuilt_hit_rate, 3),
            },
            "zero_loss": errors[0] == 0 and bool(took_over),
        }
    return block


def _recovery_map_fun(args, ctx):
    """Supervision-aware trainer for the recovery AND goodput legs:
    restore -> attach -> one checkpointed step per batch -> publish.
    The chaos kill-at-step site fires inside ``sup.step`` — AFTER that
    step's checkpoint committed and its feed partition was recorded
    consumed, so a killed step N is restorable at N with nothing
    double-fed. ONE copy of that exactly-once protocol serves both
    benches; ``args["step_s"]`` (goodput leg) adds a synthetic device
    step of that wall time inside ``ledger.step_span()`` — so the
    published ratio has a real numerator — and attaches the feed so
    the step boundary flushes accounting before the kill site."""
    import json as _json
    import os as _os
    import time as _time

    import numpy as _np

    from tensorflowonspark_tpu import chaos as _chaos
    from tensorflowonspark_tpu import checkpoint as _checkpoint
    from tensorflowonspark_tpu import goodput as _goodput
    from tensorflowonspark_tpu import reservation as _reservation
    from tensorflowonspark_tpu import supervisor as _supervisor

    step_s = args.get("step_s")
    ledger = _goodput.ledger() if step_s else None
    ckpt = _checkpoint.Checkpointer(args["dir"], chief=True)
    like = {"step": _np.array(0, _np.int32),
            "seen": _np.array(0.0, _np.float64)}
    restored = ckpt.restore(like, fallback=True)
    state = restored if restored is not None else like
    step = int(state["step"])
    start = step
    feed = ctx.get_data_feed(train_mode=True)
    sup = _supervisor.attach(
        ctx, restored_step=step if restored is not None else None,
        feed=feed if step_s else None)

    def _acked_up_to(n):
        # n counts THIS attempt's steps (a reformed cluster's server
        # starts with an empty ack set; already-acked partitions are
        # drained driver-side and never re-fed)
        client = _reservation.Client(ctx.cluster_meta["server_addr"])
        try:
            return _chaos.poll_until(lambda: len(client.acked()) >= n,
                                     timeout=60)
        finally:
            client.close()

    def _advance(batch):
        return {"step": _np.array(step, _np.int32),
                "seen": _np.array(float(state["seen"]) + sum(batch),
                                  _np.float64)}

    while not feed.should_stop():
        batch = feed.next_batch(args["batch"])
        if not batch:
            continue
        step += 1
        if ledger is not None:
            with ledger.step_span():
                _time.sleep(step_s)  # the synthetic device step
                state = _advance(batch)
        else:
            state = _advance(batch)
        ckpt.save(step, state, force=True)
        ckpt.wait()
        _acked_up_to(step - start)  # one partition == one step
        sup.step(step)  # (flushes accounting, then) chaos kill site
    ckpt.close()
    with open(_os.path.join(args["dir"], "final.json"), "w") as f:
        _json.dump({"step": step, "seen": float(state["seen"])}, f)


def _recovery_bench(batch=4, parts=8, kill_step=3, max_restarts=2,
                    heartbeat_interval=0.25, poll_interval=0.1):
    """MTTR of the supervision plane: one supervised job, one injected
    trainer SIGKILL right after ``kill_step``'s checkpoint committed,
    measured detect -> reform -> restore -> first-post-restore-step.

    One feed partition == one device batch == one checkpointed step
    (the exactly-once alignment docs/fault_tolerance.md documents), so
    ``exactly_once`` asserts the recovered run's final step count AND
    consumed-data sum match an uninterrupted run's.

    Trainers are pinned to CPU (``JAX_PLATFORMS=cpu``): the number
    published is the supervision plane's own latency — detection,
    teardown, reformation, checkpoint restore — not device bring-up,
    so it regression-tracks across boxes. scripts/profile_recovery.py
    shares this harness.
    """
    import shutil
    import tempfile

    from tensorflowonspark_tpu import chaos, cluster, supervisor
    from tensorflowonspark_tpu.engine import Context

    work = tempfile.mkdtemp(prefix="tfos-recovery-")
    ckpt_dir = os.path.join(work, "ckpt")
    os.makedirs(ckpt_dir)
    fuse = os.path.join(work, "fuse")
    records = list(range(batch * parts))
    try:
        sc = Context(
            num_executors=1, work_root=os.path.join(work, "engine"),
            executor_env={
                chaos.ENV_VAR: "kill_trainer_at_step={},fuse={}".format(
                    kill_step, fuse),
                "TFOS_FEED_TRANSPORT": "queue",
                "JAX_PLATFORMS": "cpu"})
        cfg = supervisor.SupervisorConfig(
            policy=supervisor.RestartFromCheckpoint(
                max_restarts=max_restarts, backoff=0.1),
            heartbeat_interval=heartbeat_interval,
            heartbeat_timeout=20.0, poll_interval=poll_interval,
            classify_grace=10.0)
        t0 = time.monotonic()
        try:
            tfc = cluster.run(sc, _recovery_map_fun,
                              {"dir": ckpt_dir, "batch": batch},
                              num_executors=1,
                              input_mode=cluster.InputMode.SPARK,
                              supervise=cfg)
            tfc.train(sc.parallelize(records, parts), feed_timeout=120)
        finally:
            sc.stop()
        wall = time.monotonic() - t0
        # the fuse file's content is the kill's wall-clock fire time —
        # the out-of-process evidence the detect span is anchored to
        kill_wall = float(open(fuse).read()) if os.path.exists(fuse) \
            else None
        stages = supervisor.recovery_stages(tfc.events, kill_wall=kill_wall)
        rep = tfc.report()
        with open(os.path.join(ckpt_dir, "final.json")) as f:
            final = json.load(f)
        return {
            "workload": {"partitions": parts, "batch": batch,
                         "kill_at_step": kill_step,
                         "policy": "RestartFromCheckpoint(max_restarts="
                                   "{})".format(max_restarts)},
            "injection_fired": kill_wall is not None,
            "mttr_s": stages.get("mttr_s") if stages else None,
            "stages": None if stages is None else {
                k: stages[k] for k in ("detect_s", "reform_s",
                                       "restore_s", "first_step_s")},
            "formations": rep["formations"],
            "failure_kinds": [f["kind"] for f in rep["failures"]],
            "acked_partitions": rep["acked_partitions"],
            "final_step": final["step"],
            "expected_step": parts,
            "exactly_once": final["step"] == parts and
            final["seen"] == float(sum(records)),
            "wall_s": round(wall, 3),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _resize_map_fun(args, ctx):
    """Elastic-resize trainer: per-executor checkpoint root, one
    checkpointed step per batch, same ack-before-step discipline as
    ``_recovery_map_fun``. Steps once at start so the scoped
    ``drop_executor_then_return_after`` site fires in the targeted
    executor BEFORE it consumes anything (whole-executor loss with a
    clean ledger)."""
    import json as _json
    import os as _os

    import numpy as _np

    from tensorflowonspark_tpu import chaos as _chaos
    from tensorflowonspark_tpu import checkpoint as _checkpoint
    from tensorflowonspark_tpu import reservation as _reservation
    from tensorflowonspark_tpu import supervisor as _supervisor

    eid = ctx.executor_id
    ckpt = _checkpoint.Checkpointer(
        _os.path.join(args["dir"], "exec-{}".format(eid)), chief=True)
    like = {"step": _np.array(0, _np.int32),
            "seen": _np.array(0.0, _np.float64)}
    restored = ckpt.restore(like, fallback=True)
    state = restored if restored is not None else like
    step = int(state["step"])
    start = step
    sup = _supervisor.attach(
        ctx, restored_step=step if restored is not None else None)
    sup.step(step)  # drop_executor chaos site (scoped by only=EID)
    feed = ctx.get_data_feed(train_mode=True)

    def _acked_up_to(n):
        # n counts THIS executor's steps this attempt; the global ack
        # count is >= it whenever this trainer's own partitions landed
        # (exact in the single-consumer shrink window, conservative
        # when siblings consume too)
        client = _reservation.Client(ctx.cluster_meta["server_addr"])
        try:
            return _chaos.poll_until(lambda: len(client.acked()) >= n,
                                     timeout=60)
        finally:
            client.close()

    while not feed.should_stop():
        batch = feed.next_batch(args["batch"])
        if not batch:
            continue
        step += 1
        state = {"step": _np.array(step, _np.int32),
                 "seen": _np.array(float(state["seen"]) + sum(batch),
                                   _np.float64)}
        # ack-confirm BEFORE checkpoint: a teardown abort racing the
        # feeder's join can leave a CONSUMED partition unacked — if
        # that partition were already in a committed step, replay
        # would double-feed it. Ordering ack -> save means an unacked
        # partition is never in saved state: the failure mode is a
        # clean replay, never a double count. A timed-out ack wait is
        # the same story (the attempt is being torn down, or the
        # server is gone): abort THIS step uncommitted.
        if not _acked_up_to(step - start):
            raise RuntimeError(
                "feed ack for step {} never observed; aborting the "
                "step uncommitted so replay covers it".format(step))
        ckpt.save(step, state, force=True)
        ckpt.wait()
        sup.step(step)  # boundary: chaos kill site AND ResizeDrain site
    ckpt.close()
    with open(_os.path.join(args["dir"],
                            "final-{}.json".format(eid)), "w") as f:
        # absolute step: this executor's TOTAL consumed partitions
        # across all of its incarnations (state accumulates through
        # its own checkpoint chain)
        _json.dump({"step": step, "seen": float(state["seen"])}, f)


def _elastic_finals(ckpt_dir, records, parts):
    """Sum the per-executor final ledgers of an elastic run; the
    exactly-once verdict is TOTAL step count == partitions and TOTAL
    consumed-data sum == the dataset's (nothing lost, nothing
    double-fed, across every mesh width the job passed through)."""
    import glob
    total_steps, total_seen = 0, 0.0
    for path in glob.glob(os.path.join(ckpt_dir, "final-*.json")):
        with open(path) as f:
            final = json.load(f)
        total_steps += final["step"]
        total_seen += final["seen"]
    return {
        "final_step_total": total_steps,
        "expected_step": parts,
        "exactly_once": total_steps == parts and
        total_seen == float(sum(records)),
    }


def _shrink_recovery_bench(batch=4, parts=8, return_after=3600.0,
                           heartbeat_interval=0.25, poll_interval=0.1,
                           regrow_probe_s=3600.0, max_restarts=2):
    """MTTR of an elastic shrink-by-one: a 2-executor supervised job
    loses ONE WHOLE EXECUTOR (chaos drops it at the scoped trainer's
    first step site) and the ElasticResize policy reforms immediately
    at width 1 — no blacklist permanence, no waiting for a replacement
    — restoring the survivor's checkpoint and rebalancing the un-ACKed
    partitions onto the surviving width.

    The published comparison (docs/fault_tolerance.md "Elastic
    resize"): under RestartFromCheckpoint an executor loss cannot
    recover at all until capacity returns (reform at fixed width needs
    the dead executor back), so the honest baseline for MTTR is the
    full-restart number ``_recovery_bench`` publishes — shrink-by-one
    must land materially below it, and the detect stage in particular
    collapses because the engine's liveness view classifies the loss
    instead of waiting out heartbeat_timeout.

    Defaults measure the SHRINK only (capacity never returns inside
    the run: ``return_after``/``regrow_probe_s`` are parked at 3600s);
    tests/test_resize.py's e2e drives the full shrink→regrow cycle.
    """
    import shutil
    import tempfile

    from tensorflowonspark_tpu import chaos, cluster, supervisor
    from tensorflowonspark_tpu.engine import Context

    work = tempfile.mkdtemp(prefix="tfos-shrink-")
    ckpt_dir = os.path.join(work, "ckpt")
    os.makedirs(ckpt_dir)
    fuse = os.path.join(work, "fuse")
    records = list(range(batch * parts))
    try:
        sc = Context(
            num_executors=2, work_root=os.path.join(work, "engine"),
            executor_env={
                chaos.ENV_VAR:
                    "drop_executor_then_return_after={},only=1,fuse={}"
                    .format(return_after, fuse),
                "TFOS_FEED_TRANSPORT": "queue",
                "JAX_PLATFORMS": "cpu"})
        cfg = supervisor.SupervisorConfig(
            policy=supervisor.ElasticResize(
                min_width=1, max_restarts=max_restarts, backoff=0.1,
                regrow_probe_s=regrow_probe_s),
            heartbeat_interval=heartbeat_interval,
            heartbeat_timeout=20.0, poll_interval=poll_interval,
            classify_grace=10.0)
        t0 = time.monotonic()
        try:
            tfc = cluster.run(sc, _resize_map_fun,
                              {"dir": ckpt_dir, "batch": batch},
                              num_executors=2,
                              input_mode=cluster.InputMode.SPARK,
                              supervise=cfg)
            tfc.train(sc.parallelize(records, parts), feed_timeout=120)
        finally:
            sc.stop()
        wall = time.monotonic() - t0
        kill_wall = float(open(fuse).read()) if os.path.exists(fuse) \
            else None
        stages = supervisor.recovery_stages(tfc.events, kill_wall=kill_wall)
        rep = tfc.report()
        widths = [e["width"] for e in rep["events"]
                  if e["name"] == "cluster_formed"]
        block = {
            "workload": {"partitions": parts, "batch": batch,
                         "drop_executor": 1,
                         "policy": "ElasticResize(min_width=1, "
                                   "max_restarts={})".format(max_restarts)},
            "injection_fired": kill_wall is not None,
            "mttr_s": stages.get("mttr_s") if stages else None,
            "stages": None if stages is None else {
                k: stages[k] for k in ("detect_s", "reform_s",
                                       "restore_s", "first_step_s")},
            "formations": rep["formations"],
            "widths": widths,
            "width_changes": rep["width_changes"],
            "failure_kinds": [f["kind"] for f in rep["failures"]],
            "acked_partitions": rep["acked_partitions"],
            "wall_s": round(wall, 3),
        }
        block.update(_elastic_finals(ckpt_dir, records, parts))
        return block
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _ledger_overhead(step_s):
    """Per-operation cost of the accounting itself, measured: one
    track() enter/exit cycle and one note_step, amortized over 20k
    reps, against the leg's step time — the <1%-of-step acceptance
    bound."""
    from tensorflowonspark_tpu import goodput as goodput_mod

    ledger = goodput_mod.GoodputLedger(flight=False)
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with ledger.track("feed_wait"):
            pass
    track_s = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        ledger.note_step(1e-7)
    note_s = (time.perf_counter() - t0) / reps
    # per step the framework pays ~1 step_span + ~2 track cycles
    # (feed wait + checkpoint)
    per_step = note_s + 2 * track_s
    return {"track_cycle_us": round(track_s * 1e6, 3),
            "note_step_us": round(note_s * 1e6, 3),
            "frac_of_step": round(per_step / step_s, 6) if step_s
            else None}


def _goodput_bench(batch=4, parts=8, kill_step=3, stall_s=2.0,
                   step_s=0.2, max_restarts=2):
    """Goodput accounting under chaos: one supervised job with an
    injected consumer stall (batch 1) AND a trainer SIGKILL (after
    ``kill_step``'s checkpoint) — recovery included — publishing the
    job goodput ratio, per-category badput, the sum-to-wall invariant
    residual, and the measured ledger overhead. The same harness the
    chaos e2e in tests/test_goodput.py pins."""
    import shutil
    import tempfile

    from tensorflowonspark_tpu import cluster, goodput, supervisor
    from tensorflowonspark_tpu import chaos as chaos_mod  # noqa: F401
    from tensorflowonspark_tpu.engine import Context

    work = tempfile.mkdtemp(prefix="tfos-goodput-")
    ckpt_dir = os.path.join(work, "ckpt")
    os.makedirs(ckpt_dir)
    kill_fuse = os.path.join(work, "kill_fuse")
    stall_fuse = os.path.join(work, "stall_fuse")
    records = list(range(batch * parts))
    try:
        spec = ("kill_trainer_at_step={},fuse={};"
                "stall_consumer_for={},fuse={}").format(
                    kill_step, kill_fuse, stall_s, stall_fuse)
        sc = Context(
            num_executors=1, work_root=os.path.join(work, "engine"),
            executor_env={
                "TFOS_CHAOS": spec,
                "TFOS_FEED_TRANSPORT": "queue",
                "JAX_PLATFORMS": "cpu"})
        cfg = supervisor.SupervisorConfig(
            policy=supervisor.RestartFromCheckpoint(
                max_restarts=max_restarts, backoff=0.1),
            heartbeat_interval=0.25, heartbeat_timeout=20.0,
            poll_interval=0.1, classify_grace=10.0)
        t0 = time.monotonic()
        try:
            tfc = cluster.run(sc, _recovery_map_fun,
                              {"dir": ckpt_dir, "batch": batch,
                               "step_s": step_s},
                              num_executors=1,
                              input_mode=cluster.InputMode.SPARK,
                              supervise=cfg)
            tfc.train(sc.parallelize(records, parts), feed_timeout=120)
        finally:
            sc.stop()
        wall = time.monotonic() - t0
        report = tfc.goodput_report()
        rep = tfc.report()
        with open(os.path.join(ckpt_dir, "final.json")) as f:
            final = json.load(f)
        # snapshot-internal invariant: categories vs the wall gauge
        # each executor published ATOMICALLY with them
        rollup = tfc.metrics() or {}
        merged = rollup.get("cluster", {}).get("merged")
        cats = goodput.merged_categories(merged)
        wall_gauge = (((merged or {}).get("counters") or {})
                      .get("tfos_goodput") or {}).get("gauges", {}) \
            .get("wall_seconds", 0.0)
        accounted = sum(cats.values())
        return {
            "workload": {"partitions": parts, "batch": batch,
                         "kill_at_step": kill_step,
                         "stall_s": stall_s, "step_s": step_s},
            "injection_fired": {
                "kill": os.path.exists(kill_fuse),
                "stall": os.path.exists(stall_fuse)},
            "report": report,
            # per-executor skew rows (goodput.skew_rows shape) so
            # `goodput_report.py --from-bench` renders a real
            # straggler table instead of "no step-time skew data"
            "stragglers": goodput.skew_rows(rollup.get("executors")),
            "goodput_ratio": report["goodput_ratio"],
            "badput": report["badput"],
            "unaccounted_frac_of_wall": round(
                report["unaccounted_s"] / report["wall_s"], 4)
            if report["wall_s"] else None,
            "snapshot_residual_frac": round(
                abs(accounted - wall_gauge) / wall_gauge, 4)
            if wall_gauge else None,
            "ledger_overhead": _ledger_overhead(step_s),
            "formations": rep["formations"],
            "failure_kinds": [f["kind"] for f in rep["failures"]],
            "exactly_once": final["step"] == parts and
            final["seen"] == float(sum(records)),
            "wall_s": round(wall, 3),
        }
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _probe_device():
    """``(platform, device_kind, count)`` as JAX reports them, WITHOUT
    initializing jax in this process.

    A chip belongs to one process at a time: the bench driver must not
    hold it while the cluster-fed trainers (separate processes) need
    it, so the probe runs in a throwaway subprocess — which has exited,
    and released the chip, before the first trainer starts — and the
    driver itself only touches jax after the fed runs are done and
    their trainers reaped.
    """
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); "
         "print(d[0].platform + '|' + d[0].device_kind + '|' + str(len(d)))"],
        capture_output=True, text=True)
    lines = out.stdout.strip().splitlines()
    if out.returncode != 0 or not lines:
        raise SystemExit("bench: device probe rc={}: {}".format(
            out.returncode, (out.stderr or "")[-500:].strip()))
    platform, kind, count = lines[-1].split("|")
    return platform, kind, int(count)


#: legs that raised: each is reported in its slot of the JSON line AND
#: fails the run — a leg's failure is never laundered into exit 0
_FAILED_LEGS = []


def _leg(name, fn, *args, **kw):
    try:
        return fn(*args, **kw)
    except Exception as e:  # noqa: BLE001 - reported, then fails the run
        import traceback
        traceback.print_exc()
        _FAILED_LEGS.append(name)
        return {"error": str(e)}


def main():
    platform, device_kind, device_count = _probe_device()
    on_tpu = platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        # a measurement path that finds no chip fails; the CPU smoke is
        # something the caller asks for (`make smoke`), never a fallback
        raise SystemExit(
            "bench: no TPU (jax found {!r}). For the CPU smoke of the "
            "cluster-fed path set JAX_PLATFORMS=cpu yourself "
            "(make smoke).".format(platform))
    device = {"platform": platform, "kind": device_kind,
              "count": device_count}
    # a CPU smoke's rate is not a per-chip number and is never named one
    unit = "images/sec/chip" if on_tpu else "images/sec"
    if on_tpu:
        batch, image, steps, warmup, fed_steps = 256, 224, 30, 5, 12
    else:  # CPU smoke mode so the bench is runnable anywhere
        batch, image, steps, warmup, fed_steps = 16, 32, 5, 2, 4
    def _env_int(name, default):
        """int env knob; unset/malformed/0 -> default."""
        raw = os.environ.get(name)
        if not raw:
            return default
        try:
            v = int(raw)
        except ValueError:
            print("ignoring malformed {}={!r}".format(name, raw),
                  file=sys.stderr)
            return default
        return v or default

    batch = _env_int("TFOS_BENCH_BATCH", batch)
    fed_steps = _env_int("TFOS_BENCH_FED_STEPS", fed_steps)
    image = _env_int("TFOS_BENCH_IMAGE", image)

    # Fed runs first: the driver has not initialized jax yet, so the
    # trainer subprocesses are the chip's only owners.
    fed_enabled = os.environ.get("TFOS_BENCH_FED", "1") == "1"
    # CPU smoke is noise-dominated on the 1-core box (docs/feedpath.md):
    # take the median of 3 cluster spins per transport there. Chip runs
    # are stable and expensive — one spin.
    fed_reps = _env_int("TFOS_BENCH_FED_REPS", 1 if on_tpu else 3)

    def _fed_median(transport, reps=None):
        rates = [r for r in (_cluster_fed_images_per_sec(
            transport, batch, image, fed_steps, on_tpu)
            for _ in range(reps or fed_reps)) if r is not None]
        if not rates:
            return None
        return _median(rates)

    fed_shm = fed_queue = fed_auto = None
    auto_full_reps = True
    if fed_enabled:
        fed_shm = _fed_median("shm")
        fed_queue = _fed_median("queue")
        # the production DEFAULT config: auto-probed transport; also the
        # leg that captures the probe's measured rates for the artifact.
        # One spin on CPU (unless TFOS_BENCH_FED_REPS was set
        # explicitly): the forced legs above carry the median-based
        # comparison; this leg's job is the default path + probe
        # evidence, and 3 more smoke spins would push the fallback past
        # a driver's bench budget for no added signal. A single-spin
        # auto is excluded from the headline max below — one lucky
        # un-medianed spin must not become the published value.
        auto_full_reps = bool(on_tpu or
                              os.environ.get("TFOS_BENCH_FED_REPS"))
        fed_auto = _fed_median("auto",
                               reps=None if auto_full_reps else 1)

    # Supervision plane: MTTR of an injected mid-job trainer SIGKILL
    # (detect -> reform -> restore -> first post-restore step), published
    # so recovery latency is regression-tracked alongside throughput.
    # Runs in the fed regime (driver has not initialized jax; trainers
    # are separate CPU-pinned processes). Rides the fed gate: the
    # device-only subprocess child must not spin recovery clusters.
    # TFOS_BENCH_RECOVERY=0 skips it.
    recovery = None
    if fed_enabled and os.environ.get("TFOS_BENCH_RECOVERY", "1") == "1":
        recovery = _leg("recovery", _recovery_bench)
        # elastic shrink-by-one leg (PR 7): executor loss recovered by
        # reforming at width-1 instead of waiting for capacity, MTTR
        # published against the full-restart number above.
        # TFOS_BENCH_SHRINK=0 skips just this leg.
        if os.environ.get("TFOS_BENCH_SHRINK", "1") == "1":
            recovery["shrink"] = _leg("recovery.shrink",
                                      _shrink_recovery_bench)
            full = recovery.get("mttr_s")
            part = recovery["shrink"].get("mttr_s")
            recovery["shrink_vs_full_restart_mttr"] = \
                round(part / full, 3) if full and part else None

    # Goodput plane (PR 10): badput-attributed wall time of a short
    # supervised job under one injected consumer stall + one trainer
    # kill — publishes the goodput ratio, per-category badput, the
    # sum-to-wall residual, and the ledger's own measured overhead.
    # Shares the fed gate; TFOS_BENCH_GOODPUT=0 skips it.
    goodput_leg = None
    if fed_enabled and os.environ.get("TFOS_BENCH_GOODPUT", "1") == "1":
        goodput_leg = _leg("goodput", _goodput_bench)

    # Every cluster above is down and its trainer reaped: from here on
    # THIS process owns the chip — the device-only spin and the serving
    # legs run in it, one after the other.
    from tensorflowonspark_tpu import util
    util.enable_compile_cache()
    device_only = mfu = None
    device_error = None
    try:
        device_only, mfu = _device_only(on_tpu, batch, image, steps, warmup)
    except Exception as e:  # noqa: BLE001 - reported, then fails the run
        device_error = str(e)
        _FAILED_LEGS.append("device_only")
        print("device_only failed: {}".format(device_error), file=sys.stderr)

    # Fleet plane (PR 6): mixed-length traffic through the least-loaded
    # router at 1 vs 2 vs 4 replicas — aggregate tokens/sec scaling +
    # routing overhead. TFOS_BENCH_SERVING=0 or TFOS_BENCH_FLEET=0
    # skips it.
    serving_fleet = None
    if os.environ.get("TFOS_BENCH_SERVING", "1") == "1" \
            and os.environ.get("TFOS_BENCH_FLEET", "1") == "1":
        serving_fleet = _leg("serving_fleet", _serving_fleet_bench, on_tpu)

    # Network fault plane (PR 12): partition-flap exactly-once verdict
    # + hedging-vs-gray-replica p99. Shares the serving gate;
    # TFOS_BENCH_FAULT_PLANE=0 skips just this block.
    fault_plane = None
    if os.environ.get("TFOS_BENCH_SERVING", "1") == "1" \
            and os.environ.get("TFOS_BENCH_FAULT_PLANE", "1") == "1":
        fault_plane = _leg("fault_plane", _fault_plane_bench, on_tpu)

    metric_name = ("resnet50_cluster_fed_images_per_sec_per_chip"
                   if fed_enabled else
                   "resnet50_device_only_images_per_sec_per_chip") if on_tpu \
        else "tiny_resnet_cpu_smoke_images_per_sec"
    headline_legs = (fed_shm, fed_queue,
                     fed_auto if auto_full_reps else None)
    best_fed = max((f for f in headline_legs if f is not None),
                   default=0.0)
    if fed_enabled and not best_fed:
        # Both transports broken must NOT masquerade as a healthy fed run.
        print(json.dumps({
            "metric": metric_name, "device": device,
            "value": 0.0, "unit": unit, "vs_baseline": 0.0,
            "device_only": round(device_only, 2)
            if device_only is not None else None,
            "device_error": device_error,
            "recovery": recovery,
            "error": "both cluster-fed transports failed",
        }))
        sys.exit(1)
    value = best_fed if fed_enabled else device_only
    if value is None:  # device-only mode with a dead device stage
        print(json.dumps({
            "metric": metric_name, "device": device,
            "value": 0.0, "unit": unit, "vs_baseline": 0.0,
            "error": device_error or "device-only stage failed",
        }))
        sys.exit(1)
    # the bar is a chip measurement: a CPU smoke is not compared with it
    vs = (value / BASELINE_IMAGES_PER_SEC) \
        if BASELINE_IMAGES_PER_SEC and on_tpu else None
    print(json.dumps({
        "metric": metric_name,
        "device": device,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(vs, 3) if vs is not None else None,
        "device_only": round(device_only, 2)
        if device_only is not None else None,
        "device_error": device_error,
        "cluster_fed_shm": round(fed_shm, 2) if fed_shm else None,
        "cluster_fed_queue": round(fed_queue, 2) if fed_queue else None,
        "cluster_fed_auto": round(fed_auto, 2) if fed_auto else None,
        "transport_probe": _LAST_TRANSPORT_PROBE or None,
        # mean ms per sample, per stage, per transport (ring/queue wait /
        # decode / gather / device_put) — attributes whatever gap
        # fed_frac_of_device shows to a concrete stage
        "feed_stages": _LAST_FEED_STAGES or None,
        "fed_frac_of_device": round(best_fed / device_only, 3)
        if device_only and best_fed else None,
        # like-regimes only (VERDICT r4 weak #6): the round-2 fed bar is
        # a real-chip number, so the ratio is meaningless from CPU smoke
        "fed_vs_round2": round(best_fed / ROUND2_FED_IMAGES_PER_SEC, 2)
        if best_fed and on_tpu else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        # fleet plane (PR 6): aggregate tokens/sec + p99 through the
        # least-loaded router at 1 vs 2 vs 4 replicas
        "serving_fleet": serving_fleet,
        # network fault plane (PR 12): partition-flap exactly-once
        # verdict (zero failures, zero duplicate completions, dedup
        # hits) + hedged-request p99 vs one injected gray replica
        "fault_plane": fault_plane,
        # supervision plane MTTR: injected trainer SIGKILL -> detect ->
        # reform -> restore -> first step (PR 3; docs/fault_tolerance.md)
        "recovery": recovery,
        # goodput plane (PR 10): badput-attributed wall time + ledger
        # overhead under an injected stall + kill + recovery
        "goodput": goodput_leg,
        "failed_legs": _FAILED_LEGS or None,
    }))
    if _FAILED_LEGS:
        sys.exit("bench: legs failed: {}".format(_FAILED_LEGS))


if __name__ == "__main__":
    main()
