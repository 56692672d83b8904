"""Host→device infeed: overlap transfer with the device step.

The TPU-native replacement for the reference's feed consumption idiom
(``tf.data.Dataset.from_generator(DataFeed...)`` — SURVEY.md §2.1 v2.x
examples). The reference moves records per-item through queues and hands
them to the TF runtime; here the host side assembles full device batches
and stages them into HBM *ahead* of the step so the device loop never
blocks on the host (SURVEY.md §7.3 "Feed throughput": async dispatch gives
the overlap almost free — keep the device loop un-blocked).

Two layers:

- :func:`prefetch` — wrap any batch iterator with an N-deep background
  staging pipeline (``jax.device_put`` on a worker thread; JAX transfers
  are async, so the thread mostly just *initiates* DMA early).
- :func:`sharded_batches` — also lay each batch out with a
  ``NamedSharding`` over a mesh (batch dim split over the data axis), so
  the arrays arrive ready for a pjit-ed step function.
"""

import queue as _queue
import threading

from tensorflowonspark_tpu import tracing

_END = object()


def prefetch(batch_iter, size=2, device_put=None, timers=None):
    """Iterate ``batch_iter`` with ``size`` batches staged ahead.

    ``device_put``: callable applied to each batch on the staging thread
    (default ``jax.device_put`` — leaves layout to JAX). The generator
    yields staged batches in order. Exceptions on the staging thread
    re-raise at the consuming ``next()``.

    ``timers``: optional :class:`tracing.StageTimers`; each batch's
    host→device transfer dispatch lands in its ``device_put`` stage.
    Pass the consuming DataFeed's ``.timers`` so the whole feed-plane
    breakdown (ring wait / decode / gather / device_put) shares one
    snapshot — ``feed.stats()["stages"]`` then attributes every host-
    side millisecond of the fed path.

    Staging-buffer caveat: DataFeed's mapped columnar batches are
    REUSED buffers (valid until its next ``next_batch``). The default
    ``jax.device_put`` can ZERO-COPY alias an aligned numpy array on
    the CPU backend, so feeding DataFeed batches through this plain
    prefetch on CPU can alias staged arrays to memory the feed will
    overwrite. Use :func:`sharded_batches` (it copies before the put —
    the canonical consumption everywhere in this framework), pass a
    copying ``device_put``, or set ``TFOS_FEED_STAGING=0`` on the feed.

    Closing the generator early (break, ``inference terminate()``, an
    error in the consumer) cancels and joins the staging thread — a bare
    ``buf.put`` there would strand the thread forever on a full queue,
    holding staged device arrays, once per abandoned feed.
    """
    import jax

    put = device_put or jax.device_put
    if timers is None:
        timers = tracing.StageTimers()  # read by nobody
    buf = _queue.Queue(maxsize=size)
    stop = threading.Event()

    def _put(item):
        """Bounded put that observes cancellation; False when cancelled."""
        while not stop.is_set():
            try:
                buf.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def _stage():
        try:
            for batch in batch_iter:
                # one span per batch, on the staging thread
                with timers.timed("device_put"):
                    staged = jax.tree.map(put, batch)
                if stop.is_set() or not _put(staged):
                    return
            _put(_END)
        except BaseException as e:  # noqa: BLE001 - re-raised at next()
            _put(e)

    t = threading.Thread(target=_stage, name="infeed-prefetch", daemon=True)
    t.start()

    try:
        while True:
            item = buf.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        try:  # unblock a put-in-flight so the join below is prompt
            while True:
                buf.get_nowait()
        except _queue.Empty:
            pass
        t.join(timeout=5.0)


def sharded_batches(batch_iter, mesh, axis="data", size=2, timers=None):
    """Prefetch + shard: yield batches laid out over ``mesh``'s data axis.

    Each array's leading dim is split across ``axis`` (must divide it);
    everything arrives as committed global arrays, so a pjit-ed step with
    matching in_shardings runs without any implicit resharding. Every
    numpy batch is COPIED before the put, which is what makes
    DataFeed's reusable staging buffers safe to hand straight in here:
    ``jax.device_put`` gives a numpy source to the runtime without a
    copy of its own (jax 0.9 ignores ``may_alias`` for numpy inputs) —
    the CPU backend then ALIASES each contiguous shard slice for the
    array's lifetime, whole array or split alike (measured: a 4-way
    split on CPU served batches 3,4,5,6,7,7,7,7 of 0..7), and an
    accelerator may still be reading the host buffer after the call
    returns — so prefetched-but-unconsumed batches would be silently
    overwritten by the feed's next gather. ``timers`` forwards to
    :func:`prefetch`.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec(axis))

    def put(x):
        if isinstance(x, np.ndarray):
            x = np.array(x, copy=True)
        return jax.device_put(x, sharding)

    return prefetch(batch_iter, size=size, device_put=put, timers=timers)
