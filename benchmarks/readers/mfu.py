"""The whole step's share of the chip's peak, in %: the model FLOPs the
runner counted (from the benchmark's own FLOP functions) over the
seconds they took, over chips times peak FLOP/s."""

from benchmarks.readers.counter import lookup


def read(spec, run):
    flops = lookup(run["counters"], spec["flops"])
    seconds = lookup(run["counters"], spec["seconds"])
    if not flops or not seconds:
        return None
    peak = run["peaks"]["flops_per_s"] * run["cell"]["chips"]
    return 100.0 * flops / seconds / peak
