"""Readings that the limits of ``correct`` are set from, taken on the
chip at a cell's own size, many seeds in one process:

    python benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 3] [--seconds 4]

For each seed it drives the cell's runner over a short window and reads
the numbers compared (the program against the reference: the LOWER
readings). On the first ``--control-seeds`` seeds it also reads the
control (the reference computed one precision below what the
configuration states, put in the program's place) and, for training,
the planted faults (half of the batch left out): the UPPER readings.
The benchmark's own runs never run this. Results go to standard output
and ``chiprun_out/calibrate-<cell>.json``.
"""

import argparse
import gc
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402


def _training_also(ctx, control):
    """More readings against the same reference, same batches."""
    from benchmarks.runners import train_common

    cfg = ctx["config"]

    def also(ref, program, reference, ref_batches, make_params):
        out = {"program_readings": program,
               "reference_readings": reference}
        if not control:
            return out
        low = ref.follow(make_params(), ref_batches, cfg["model"],
                         cfg["optimizer"],
                         precision=cfg["control_precision"])
        out["control"] = train_common.gaps(low, reference)
        half = list(range(len(ref_batches[0][1]) // 2))
        fault = ref.follow(make_params(), ref_batches, cfg["model"],
                           cfg["optimizer"], batch_rows=half)
        out["half_batch"] = train_common.gaps(fault, reference)
        return out

    return also


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    common.fix_compile_cache()
    run_py = importlib.import_module("benchmarks.run")
    cell, config, traffic = run_py.load_cell(args.workload)
    runner = importlib.import_module("benchmarks.runners." + cell["runner"])
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = common.make_ctx("calibrate-" + args.workload, cell, config,
                              traffic, seed, args.seconds)
        control = i < args.control_seeds
        if "optimizer" in config:
            # the step is the cells' own; the resident path feeds it
            from benchmarks.runners import train_resident

            ctx["traffic"] = dict(traffic, resident_batches=3)
            result = train_resident.run(
                ctx, also=_training_also(ctx, control))
            row = {"seed": seed, "program": {
                k: v[0] for k, v in result["checks"].items()},
                "more": result["counters"]["calibration"],
                "reference_seconds":
                    result["counters"]["reference_seconds"]}
        else:
            result = runner.run(
                ctx, control=config["control_precision"] if control
                else None)
            row = {"seed": seed, "program": {
                k: v[0] for k, v in result["checks"].items()},
                "control_gap_max": result["counters"]["control_gap_max"],
                "compared_tokens": result["counters"]["compared_tokens"],
                "end_to_end": result["end_to_end"],
                "latency": result["counters"]["latency"],
                "reference_seconds":
                    result["counters"]["reference_seconds"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        shutil.rmtree(ctx["work_dir"], ignore_errors=True)
        del result
        gc.collect()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "calibrate-{}.json".format(args.workload)),
              "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
