"""Trainer/infeed/mesh tests on the virtual 8-device CPU platform."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def jax():
    import jax
    return jax


def test_build_mesh_shapes(jax):
    from tensorflowonspark_tpu.parallel import build_mesh

    mesh = build_mesh()
    assert mesh.shape == {"data": 8}
    mesh = build_mesh({"data": -1, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        build_mesh({"data": 3})
    with pytest.raises(ValueError):
        build_mesh({"data": -1, "model": -1})


def test_prefetch_order_and_error(jax):
    from tensorflowonspark_tpu import infeed

    batches = [np.full((2,), i) for i in range(5)]
    out = list(infeed.prefetch(iter(batches), size=2))
    assert [int(b[0]) for b in out] == [0, 1, 2, 3, 4]

    def boom():
        yield np.zeros((2,))
        raise ValueError("stage boom")

    it = infeed.prefetch(boom(), size=2)
    next(it)
    with pytest.raises(ValueError, match="stage boom"):
        next(it)


def test_prefetch_early_close_joins_staging_thread(jax):
    """Abandoning the generator (inference terminate(), a consumer error)
    must cancel the staging thread, not strand it on a full buffer."""
    import threading
    import time

    from tensorflowonspark_tpu import infeed

    produced = [0]

    def endless():
        while True:
            produced[0] += 1
            yield np.zeros((2,))

    it = infeed.prefetch(endless(), size=2)
    next(it)  # staging thread is now live and its buffer fills up
    it.close()  # early exit: generator finalizer must join the thread

    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if not any(t.name == "infeed-prefetch" and t.is_alive()
                   for t in threading.enumerate()):
            break
        time.sleep(0.05)
    leaked = [t.name for t in threading.enumerate()
              if t.name == "infeed-prefetch" and t.is_alive()]
    assert not leaked, leaked
    n = produced[0]
    time.sleep(0.2)
    assert produced[0] == n  # production stopped, not just unobserved


def test_sharded_batches_layout(jax):
    from tensorflowonspark_tpu import infeed
    from tensorflowonspark_tpu.parallel import build_mesh

    mesh = build_mesh()
    batches = [{"x": np.ones((16, 4), np.float32)} for _ in range(3)]
    out = list(infeed.sharded_batches(iter(batches), mesh))
    assert len(out) == 3
    x = out[0]["x"]
    assert x.shape == (16, 4)
    assert len(x.sharding.device_set) == 8
    # each device holds 1/8 of the batch dim
    assert x.addressable_shards[0].data.shape == (2, 4)


@pytest.mark.parametrize("n_devices,shape", [(1, (4, 8)), (4, (4, 8)),
                                             (4, (64, 8192))])
def test_sharded_batches_copies_reused_buffers(jax, n_devices, shape):
    """CPU jax.device_put can zero-copy ALIAS a numpy source — the
    whole array on a 1-device mesh, and each contiguous shard slice of
    a split one (the large, page-aligned case) — so sharded_batches
    must copy first, or DataFeed's reused staging buffers would
    overwrite prefetched-but-unconsumed batches (silent corruption)."""
    from jax.sharding import Mesh

    from tensorflowonspark_tpu import infeed

    buf = np.zeros(shape, np.float32)

    def reusing_gen():
        for i in range(6):
            buf[:] = i  # ONE buffer reused, like the feed's staging
            yield {"x": buf}

    mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
    out = list(infeed.sharded_batches(reusing_gen(), mesh))
    for i, b in enumerate(out):
        np.testing.assert_array_equal(
            np.asarray(b["x"]), np.full(shape, i, np.float32))


def test_lenet_dp_training_converges(jax):
    import optax

    from tensorflowonspark_tpu import infeed, training
    from tensorflowonspark_tpu.models.lenet import LeNet
    from tensorflowonspark_tpu.parallel import build_mesh

    rng = np.random.RandomState(0)
    # Synthetic, linearly-separable-ish images: class k lights up block k.
    def make_batch(n):
        y = rng.randint(0, 10, size=n)
        x = rng.rand(n, 28, 28, 1).astype(np.float32) * 0.1
        for i, k in enumerate(y):
            x[i, (k * 2):(k * 2 + 3), :, 0] += 1.0
        return {"x": x, "y": y}

    mesh = build_mesh()
    trainer = training.Trainer(LeNet(), optax.adam(1e-3), mesh)
    state = trainer.init(jax.random.PRNGKey(0), make_batch(16)["x"])

    losses = []

    def record(step, state, metrics):
        losses.append(metrics["loss"])

    batches = (make_batch(64) for _ in range(30))
    state, steps, rate = trainer.train_loop(
        state, infeed.sharded_batches(batches, mesh), log_every=0,
        hooks=[record])
    assert steps == 30
    first, last = float(losses[0]), float(losses[-1])
    assert last < first * 0.5, (first, last)
    assert rate > 0


def test_remat_step_matches_plain(jax):
    """remat=True (jax.checkpoint backward) is numerically identical to
    the plain step — it changes WHEN activations exist, not the math."""
    import numpy as np
    import optax

    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.models.resnet import ResNet
    from tensorflowonspark_tpu.parallel import build_mesh

    mesh = build_mesh({"data": len(jax.devices())})
    model = ResNet(stage_sizes=[1], num_classes=4, width=8)
    rng = np.random.RandomState(0)
    x = rng.rand(16, 16, 16, 3).astype(np.float32)
    y = (np.arange(16) % 4).astype(np.int64)

    states = []
    for remat in (False, True):
        trainer = training.Trainer(model, optax.sgd(0.1), mesh,
                                   remat=remat, donate_state=False)
        batch = jax.device_put({"x": x, "y": y}, trainer.batch_sharding)
        state = trainer.init(jax.random.PRNGKey(0), x)
        for _ in range(3):
            state, metrics = trainer.step(state, batch)
        states.append((jax.device_get(state["params"]),
                       float(metrics["loss"])))
    (p0, l0), (p1, l1) = states
    assert abs(l0 - l1) < 1e-5, (l0, l1)
    flat0 = jax.tree_util.tree_leaves(p0)
    flat1 = jax.tree_util.tree_leaves(p1)
    for a, b in zip(flat0, flat1):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
