"""Sum of the counters at ``num`` (paths; absent ones count 0) over the
counter at ``den``, times ``scale``. None where no numerator is present
or the denominator is absent or 0."""

from benchmarks.readers.counter import lookup


def read(spec, run):
    nums = [lookup(run["counters"], p) for p in spec["num"]]
    den = lookup(run["counters"], spec["den"])
    if all(v is None for v in nums) or not den:
        return None
    return sum(v for v in nums if v is not None) / den \
        * spec.get("scale", 1.0)
