"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads ``cells/<cell>.json``, the configuration and the traffic mix it
names, imports the runner the cell names, and prints one JSON object as
the last line of standard output. With ``--trace 0`` its metrics are the
cell's end-to-end metrics, with ``--trace 1`` the cell's per-layer
metrics, each taken by the reader its file under ``layer_metrics/``
names. There is no CPU mode: without the chips the cell asks for it
exits non-zero and prints no result. Nothing in this file knows a cell,
a configuration, a traffic mix or a metric by name.
"""

import time

T0_EPOCH = time.time()  # as near to the start of the process as it gets

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402


def load_cell(name):
    cell = common.load_json("cells", name + ".json")
    config = common.load_json("configs", cell["config"] + ".json")
    traffic = common.load_json("traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def layer_specs(cell):
    """{metric name: its file under layer_metrics/} for the cell."""
    return {name: common.load_json("layer_metrics", name + ".json")
            for name in cell["per_layer"]}


def event_patterns(specs, config):
    """{metric name: substrings that pick its device events}: each
    metric's ``events``, with ``{key}`` filled from the configuration's
    ``model`` and ``serving`` groups (a kernel is known by the shapes it
    is given, and those are the configuration's)."""
    fields = dict(config.get("model", {}), **config.get("serving", {}))
    return {n: [e.format(**fields) for e in s["events"]]
            for n, s in specs.items() if "events" in s}


def per_layer_metrics(specs, cell, config, result, reduced, peaks):
    """{name: {"value", "unit"}} for the cell's per-layer metrics; a
    reader that finds nothing to read returns None and the metric is
    left out."""
    run = {"counters": result["counters"], "trace": reduced,
           "peaks": peaks, "config": config, "cell": cell}
    out = {}
    for name, spec in specs.items():
        reader = importlib.import_module("benchmarks.readers."
                                         + spec["reader"])
        value = reader.read(spec, run)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    common.fix_compile_cache()
    cell, config, traffic = load_cell(args.workload)
    runner = importlib.import_module("benchmarks.runners." + cell["runner"])
    ctx = common.make_ctx(args.workload, cell, config, traffic, args.seed,
                          args.seconds, args.trace, t0_epoch=T0_EPOCH)
    try:
        result = runner.run(ctx)
        device = result["device"]
        peaks = common.peaks_for(device["kind"])
        line = {"correct": result["correct"],
                "attempted": result["attempted"],
                "failed": result["failed"]}
        if args.trace:
            from benchmarks import trace_reduce

            specs = layer_specs(cell)
            xplane = trace_reduce.find_xplane(result["trace_dir"])
            reduced = xplane and trace_reduce.reduce(
                xplane, event_patterns(specs, config))
            if not reduced:
                print("run.py: the traced window holds no device "
                      "operation", file=sys.stderr)
                return 4
            line["metrics"] = per_layer_metrics(specs, cell, config, result,
                                                reduced, peaks)
            device = dict(device, busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            line["device"] = device
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
        else:
            units = {n: common.load_json("end_to_end", n + ".json")["unit"]
                     for n in cell["end_to_end"]}
            line["metrics"] = {
                n: {"value": result["end_to_end"][n], "unit": units[n]}
                for n in cell["end_to_end"]}
            line["device"] = device
    except common.NoChip as e:
        print("run.py: {}".format(e), file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    print(json.dumps({"info": result["counters"]}), flush=True)
    line["checks"] = result["checks"]
    sys.stderr.flush()
    for name, (value, limit) in result["checks"].items():
        print("check {}: {!r} limit {!r}".format(name, value, limit),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
