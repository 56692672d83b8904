"""The rate sweep of a serving cell, once, on the chip: the same cell at
several offered rates in one process (one set-up of the compile cache),
to find the highest rate at which the backlog does not grow.

    python benchmarks/sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20 --seed 5

``--seed`` may be a list: the i-th rate then runs on the i-th seed (one
rate given several times reads its spread over seeds in one set-up).

For each rate it prints what came out: requests, the tails, the tokens
per second completed, and ``drained_s`` - how long after the window's
close the last request finished, which grows with the backlog. The
cell's ``rate_rps`` is then written by hand, as a number, into its
traffic file at four fifths of the knee.
"""

import argparse
import copy
import gc
import importlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", default="5")
    args = ap.parse_args(argv)
    common.fix_compile_cache()
    run_py = importlib.import_module("benchmarks.run")
    cell, config, traffic = run_py.load_cell(args.workload)
    runner = importlib.import_module("benchmarks.runners." + cell["runner"])
    rows = []
    seeds = [int(x) for x in args.seed.split(",")]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = seeds[i % len(seeds)]
        t = copy.deepcopy(traffic)
        t["arrivals"]["rate_rps"] = rate
        ctx = common.make_ctx("sweep-" + args.workload, cell, config, t,
                              seed, args.seconds)
        result = runner.run(ctx)
        c = result["counters"]
        row = {"rate_rps": rate, "seed": seed,
               "requests": result["attempted"],
               "failed": result["failed"], "correct": result["correct"],
               "end_to_end": result["end_to_end"],
               "latency": c["latency"], "drained_s": c["window"]["drained_s"],
               "engine_ms": {k: 1e3 * v / max(c["engine"]["stage_samples"]
                                              .get(k, 1), 1)
                             for k, v in c["engine"]["stage_seconds"].items()},
               "checks": result["checks"],
               "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        shutil.rmtree(ctx["work_dir"], ignore_errors=True)
        del result
        gc.collect()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sweep-{}.json".format(args.workload)),
              "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
