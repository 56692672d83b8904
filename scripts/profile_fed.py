"""Per-stage profile of the composed fed path (docs/feedpath.md harness).

Reproduces the transport / composed-loop numbers in docs/feedpath.md: a
real feeder process pushes ColumnarChunk frames through the chosen
transport (shm ring or manager queue) into a DataFeed + trainer loop in
this process, timing every stage separately.

Usage (CPU, hermetic — same platform pinning as tests/conftest.py):

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python scripts/profile_fed.py <transport> <mode> [options]

    transport: queue | shm
    mode:      sync      one thread, explicit device sync per step
               async     one thread, async dispatch (bench.py-like)
               prefetch  staging thread + main loop (bench.py's shape)
    --batch N --image N --chunk N --steps N   shape knobs:
        defaults (256/224/256/10) are the production 224px regime
        (~38MB frames); --batch 16 --image 32 --chunk 16 is the
        smoke regime (~49KB frames) from `make smoke`.
    --transport-only   skip the model; time the raw transport round trip
                       (feeder encode+write -> consumer read+materialize).

Stage legend: read = next_batch (transport read + decode + combine),
put = jax.device_put, dispatch = trainer.step call returning,
sync = device_get of the loss. Every mode additionally prints the
DataFeed-internal per-stage breakdown (``feed stages``, mean ms per
sample: ring_wait/queue_wait, decode, gather — plus device_put in
prefetch mode, where the staging thread's puts share the feed's
StageTimers) — the same attribution bench.py publishes as
``feed_stages``.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def feeder_main(mgr_addr, authkey_hex, transport, ring_name, n_images,
                chunk_records, image):
    import multiprocessing as mp

    from tensorflowonspark_tpu import frames
    from tensorflowonspark_tpu import manager as manager_lib
    from tensorflowonspark_tpu.marker import EndFeed

    authkey = bytes.fromhex(authkey_hex)
    mp.current_process().authkey = authkey
    from tensorflowonspark_tpu import util
    util.tune_malloc()  # match the production node bootstrap
    mgr = manager_lib.connect(tuple(mgr_addr), authkey)
    rng = np.random.RandomState(0)
    xs = rng.randint(0, 255, size=(chunk_records, image, image, 3),
                     dtype=np.uint8)
    # label range must match run_mode's model choice (10-class smoke
    # stand-in below 128px, 1000-class ResNet50 at production size)
    ys = (np.arange(chunk_records) % (1000 if image >= 128 else 10)) \
        .astype(np.int64)
    chunk = frames.ColumnarChunk([xs, ys])
    bufs = frames.encode(chunk)

    ring = None
    if transport == "shm":
        from tensorflowonspark_tpu import shm
        ring = shm.ShmRing.open(ring_name)
    q = None if ring is not None else mgr.get_queue("input")

    t0 = time.monotonic()
    sent = 0
    while sent < n_images:
        if ring is not None:
            ring.write_buffers(bufs, timeout=120.0)
        else:
            q.put(chunk, block=True, timeout=120.0)
        sent += chunk_records
    dt = time.monotonic() - t0
    print("[feeder] %s: %.0f img/s send side" % (transport, sent / dt),
          flush=True)
    if ring is not None:
        ring.write_obj(EndFeed(), timeout=120.0)
        ring.close()
    else:
        q.put(EndFeed(), block=True, timeout=120.0)


def _start_feeder(transport, n_images, chunk, image, ring_capacity):
    import multiprocessing as mp

    from tensorflowonspark_tpu import manager as manager_lib

    authkey = os.urandom(16)
    mgr = manager_lib.start(authkey, ["input"], maxsize=16)
    ring_name = None
    ring = None
    if transport == "shm":
        from tensorflowonspark_tpu import shm
        ring_name = "/tfos-prof-feed"
        shm._load().shmring_unlink(ring_name.encode())
        ring = shm.ShmRing.create(ring_name, capacity=ring_capacity)
        mgr.set("shm_name", ring_name)
    proc = mp.get_context("spawn").Process(
        target=feeder_main,
        args=(list(mgr.address), authkey.hex(), transport, ring_name,
              n_images, chunk, image))
    proc.start()
    return mgr, ring, proc


def run_transport_only(transport, args):
    """Raw transport round trip: no model, no jax — consumer materializes
    each batch and drops it."""
    from tensorflowonspark_tpu.datafeed import DataFeed

    n_images = args.batch * args.steps
    mgr, ring, proc = _start_feeder(transport, n_images, args.chunk,
                                    args.image, args.ring_capacity)
    feed = DataFeed(mgr, train_mode=True, input_mapping={"x": "x", "y": "y"})
    images = 0
    t0 = time.monotonic()
    for batch in feed.numpy_batches(args.batch):
        images += len(batch["x"])
    dt = time.monotonic() - t0
    proc.join(timeout=60)
    if proc.is_alive():
        proc.terminate()
    if ring is not None:
        ring.unlink()
        ring.close()
    from tensorflowonspark_tpu import metrics_report
    print("[%s/transport-only] %.0f img/s consumer side (%.2fs, "
          "feedwait=%.3fs)  feed stages/sample(ms): %s"
          % (transport, images / dt, dt, feed.stats()["wait_s"],
             metrics_report.format_stage_ms(feed.timers)), flush=True)
    return images / dt


def run_mode(transport, mode, args):
    import jax
    import optax

    from tensorflowonspark_tpu import infeed, training
    from tensorflowonspark_tpu.datafeed import DataFeed
    from tensorflowonspark_tpu.parallel import build_mesh

    if args.image >= 128:
        from tensorflowonspark_tpu.models.resnet import ResNet50
        model = ResNet50()
    else:  # smoke-regime stand-in, same as bench.py's CPU model
        from tensorflowonspark_tpu.models.resnet import ResNet
        model = ResNet(stage_sizes=[1, 1], num_classes=10, width=8)

    mesh = build_mesh({"data": len(jax.devices())})
    trainer = training.Trainer(model, optax.sgd(0.1, momentum=0.9), mesh)

    rng = np.random.RandomState(0)
    x = rng.rand(args.batch, args.image, args.image, 3).astype(np.float32)
    state = trainer.init(jax.random.PRNGKey(0), x)

    # warm the uint8 signature
    xu = rng.randint(0, 255, size=(args.batch, args.image, args.image, 3),
                     dtype=np.uint8)
    y = (np.arange(args.batch) % (1000 if args.image >= 128 else 10)) \
        .astype(np.int64)
    warm = jax.device_put({"x": xu, "y": y}, trainer.batch_sharding)
    state, metrics = trainer.step(state, warm)
    float(jax.device_get(metrics["loss"]))

    n_images = args.batch * (args.steps + 1)
    mgr, ring, proc = _start_feeder(transport, n_images, args.chunk,
                                    args.image, args.ring_capacity)

    feed = DataFeed(mgr, train_mode=True, input_mapping={"x": "x", "y": "y"})
    T = {"read": 0.0, "put": 0.0, "dispatch": 0.0, "sync": 0.0}

    try:
        if mode in ("sync", "async"):
            t_start = None
            images = 0
            for step_i in range(args.steps + 1):
                t0 = time.monotonic()
                batch = feed.next_batch(args.batch)  # read+decode+combine
                t1 = time.monotonic()
                b = jax.device_put(batch, trainer.batch_sharding)
                t2 = time.monotonic()
                state, metrics = trainer.step(state, b)
                t3 = time.monotonic()
                if mode == "sync":
                    float(jax.device_get(metrics["loss"]))
                t4 = time.monotonic()
                if step_i == 0:
                    t_start = time.monotonic()
                    continue
                images += args.batch
                T["read"] += t1 - t0
                T["put"] += t2 - t1
                T["dispatch"] += t3 - t2
                T["sync"] += t4 - t3
            float(jax.device_get(metrics["loss"]))
            dt = time.monotonic() - t_start
        else:  # prefetch — bench.py's actual shape
            batches = infeed.sharded_batches(feed.numpy_batches(args.batch),
                                             trainer.mesh,
                                             timers=feed.timers)
            it = iter(batches)
            state, metrics = trainer.step(state, next(it))
            float(jax.device_get(metrics["loss"]))
            images = 0
            t_start = time.monotonic()
            for b in it:
                t0 = time.monotonic()
                state, metrics = trainer.step(state, b)
                T["dispatch"] += time.monotonic() - t0
                images += args.batch
            float(jax.device_get(metrics["loss"]))
            dt = time.monotonic() - t_start
    finally:
        proc.join(timeout=60)
        if proc.is_alive():
            proc.terminate()
        if ring is not None:
            ring.unlink()
            ring.close()

    from tensorflowonspark_tpu import metrics_report
    rate = images / dt if images else 0.0
    print("[%s/%s] %.0f img/s  (%.2fs total)  stages/step(ms): %s  "
          "feedwait=%.3fs  feed stages/sample(ms): %s"
          % (transport, mode, rate, dt,
             {k: round(v / max(args.steps, 1) * 1000, 1)
              for k, v in T.items()},
             feed.stats()["wait_s"],
             metrics_report.format_stage_ms(feed.timers)), flush=True)
    return rate


def main():
    from tensorflowonspark_tpu import util
    # Same allocator tuning the production node bootstrap applies (the
    # docs/feedpath.md "tuned" rows). Reproduce the untuned baseline
    # rows with TFOS_MALLOC_TUNE=0.
    util.tune_malloc()

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("transport", choices=["queue", "shm"])
    p.add_argument("mode", nargs="?", default="sync",
                   choices=["sync", "async", "prefetch"])
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--chunk", type=int, default=256)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--ring-capacity", type=int, default=1 << 28)
    p.add_argument("--transport-only", action="store_true")
    args = p.parse_args()
    if args.transport_only:
        run_transport_only(args.transport, args)
    else:
        run_mode(args.transport, args.mode, args)


if __name__ == "__main__":
    main()
