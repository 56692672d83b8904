"""The counter at ``num`` less the sum of the counters at ``minus``
(paths; absent ones count 0), over the counter at ``den``, times
``scale``: what a stage's seconds hold beyond the stages opened
directly inside it, such as a scheduler pass's own time outside its
named stages. List direct children only, never a grandchild (its
seconds are inside its parent's already) nor a stage that overlaps
every other (a garbage collection) or a sample added from elsewhere.
None where ``num`` is absent or the denominator is absent or 0."""

from benchmarks.readers.counter import lookup


def read(spec, run):
    total = lookup(run["counters"], spec["num"])
    den = lookup(run["counters"], spec["den"])
    if total is None or not den:
        return None
    inside = sum(lookup(run["counters"], p) or 0.0 for p in spec["minus"])
    return (total - inside) / den * spec.get("scale", 1.0)
