"""``resnet50-resident``: ``Trainer.step`` on batches that already sit
on the device - the feed bypassed. This process holds the chip."""

import itertools

from benchmarks.runners import train_common


def run(ctx, broken=None, also=None):
    import importlib

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    args = train_common.args_for(ctx)
    gen = importlib.import_module("benchmarks.generators."
                                  + args["generator"])

    def get_batches(mesh):
        sharding = NamedSharding(mesh, PartitionSpec("data"))
        resident = [
            {"x": jax.device_put(xs, sharding),
             "y": jax.device_put(ys, sharding)}
            for xs, ys in gen.resident_batches(args["traffic"],
                                               args["seed"])]
        return itertools.cycle(resident)

    return train_common.run(args, get_batches, broken=broken, also=also)
