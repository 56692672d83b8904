"""A kernel's share of its roofline, in %: the least time the chip's
peaks allow for the work its calls needed in the traced window
(``work_fn`` of ``benchmarks/flops/<module>.py`` applied to the
runner's record of that work) over the summed device time of the
events whose HLO text holds all of ``events``: substrings, ``{key}``
filled from the configuration. A Pallas call carries no name of its own
in the trace, so the kernel is known by the operands it is given. None
where no such event ran or no work was recorded: never 0."""

import importlib

from benchmarks.readers.counter import lookup


def read(spec, run):
    trace = run["trace"]
    hit = trace and trace["matched"].get(spec["name"])
    work = lookup(run["counters"], spec["work"])
    if not hit or not hit["seconds"] or not work:
        return None
    module, _, fn = spec["work_fn"].partition(":")
    least, _bound = getattr(
        importlib.import_module("benchmarks.flops." + module), fn)(
            run["config"]["model"], work, run["peaks"])
    if not least:
        return None
    return 100.0 * least / hit["seconds"]
