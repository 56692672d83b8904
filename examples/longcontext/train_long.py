"""Driver for the long-context ring+flash LM example. Run::

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/longcontext/train_long.py --seq_len 2048

On a TPU pod slice, drop the env prefix — the ``seq`` mesh axis spans
the slice's chips and the KV rotation rides ICI.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq_len", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--period", type=int, default=37)
    ap.add_argument("--layout", choices=["contiguous", "zigzag"],
                    default="contiguous",
                    help="zigzag: load-balanced causal ring (~2x less "
                         "causal wall time on a real ring)")
    args = ap.parse_args(argv)

    from examples.longcontext import long_dist

    first, last = long_dist.train(
        seq_len=args.seq_len, batch=args.batch, steps=args.steps,
        hidden=args.hidden, layers=args.layers, period=args.period,
        layout=args.layout)
    print("first loss %.4f -> last loss %.4f" % (first, last))
    if last >= first:
        raise SystemExit("loss did not improve")


if __name__ == "__main__":
    main()
