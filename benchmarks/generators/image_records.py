"""Image records from a seed: the one generator behind every training
traffic mix.

Each class has a mean colour (from the seed) under per-pixel noise, so
no two records of a pool are alike, and the first pixel of every image
is overwritten with the record's identity (a 24-bit id) which the
benchmark reads back FROM THE DEVICE. The same (seed, part) always
gives the same pool, so the reference rebuilds any batch from the ids
it finds in it.

Imports numpy only: it runs inside executors, which must stay off JAX.
"""

import os

import numpy as np


def pool(seed, part, n, image, classes, class_run=1):
    """``n`` distinct (uint8 [image, image, 3], int64 label) records of
    partition ``part``, as two arrays. Labels come in runs of
    ``class_run`` records: with a run shorter than a batch, a contiguous
    part of a batch holds other classes than the whole, so a step that
    leaves part of its batch out computes a visibly different
    gradient."""
    seed = int(seed) % (2 ** 32)
    colours = np.random.RandomState(seed).randint(
        48, 208, size=(classes, 1, 1, 3)).astype(np.int16)
    rng = np.random.RandomState([seed, int(part)])
    ys = ((np.arange(n) // int(class_run)) % classes).astype(np.int64)
    noise = rng.randint(-40, 40, size=(n, image, image, 3), dtype=np.int16)
    xs = np.clip(colours[ys] + noise, 0, 255).astype(np.uint8)
    return xs, ys


def _pool_of(params, seed, part):
    return pool(seed, part, int(params["pool_records"]),
                int(params["image"]), int(params["classes"]),
                int(params.get("class_run", 1)))


def tag(gid):
    """The three identity bytes of record ``gid`` (< 2**24)."""
    return [gid & 255, (gid >> 8) & 255, (gid >> 16) & 255]


def untag(pixels):
    """Record ids from ``[n, 3]`` identity pixels."""
    p = np.asarray(pixels).astype(np.int64)
    return p[:, 0] | (p[:, 1] << 8) | (p[:, 2] << 16)


def stream(params, seed, part, stop_path=None):
    """Records of partition ``part`` until ``records_per_part`` have been
    yielded or ``stop_path`` exists (checked every 64 records): record i
    is pool record ``i % pool_records`` with identity
    ``part * records_per_part + i``. The pool is built once, before the
    first record."""
    per_part = int(params["records_per_part"])
    if stop_path and os.path.exists(stop_path):
        return  # a partition that starts after the stop builds no pool
    xs, ys = _pool_of(params, seed, part)
    n = len(xs)
    for i in range(per_part):
        if stop_path and i % 64 == 0 and os.path.exists(stop_path):
            return
        x = xs[i % n].copy()
        x[0, 0, :] = tag(part * per_part + i)
        yield x, ys[i % n]


def rebuild(params, seed, gids):
    """The records with identities ``gids``, as the executor made them:
    (uint8 images, int64 labels)."""
    per_part = int(params["records_per_part"])
    gids = np.asarray(gids, np.int64)
    xs_out = np.empty((len(gids), int(params["image"]),
                       int(params["image"]), 3), np.uint8)
    ys_out = np.empty((len(gids),), np.int64)
    for part in np.unique(gids // per_part):
        xs, ys = _pool_of(params, seed, int(part))
        sel = np.nonzero(gids // per_part == part)[0]
        idx = (gids[sel] % per_part) % len(xs)
        xs_out[sel] = xs[idx]
        ys_out[sel] = ys[idx]
    xs_out[:, 0, 0, :] = np.stack(
        [gids & 255, (gids >> 8) & 255, (gids >> 16) & 255], axis=1)
    return xs_out, ys_out


def resident_batches(params, seed):
    """``resident_batches`` whole batches for the device-resident mix:
    batch b holds records b*batch .. (b+1)*batch-1 of partition 0's
    stream."""
    batch, k = int(params["batch"]), int(params["resident_batches"])
    gids = np.arange(batch * k)
    xs, ys = rebuild(params, seed, gids)
    return [(xs[b * batch:(b + 1) * batch], ys[b * batch:(b + 1) * batch])
            for b in range(k)]
