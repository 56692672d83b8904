"""Write ``BENCHMARK.json`` from the files under ``benchmarks/``: one
entry per ``cells/*.json``, the configurations they name, every
``end_to_end/*.json`` and ``layer_metrics/*.json``, all sorted by name,
each metric's ``workloads`` being the cells whose file lists it. A later PR adds its
files and runs this (or adds the same entries by hand); nothing that is
there is edited.

    python benchmarks/make_manifest.py [--check]
"""

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN_SECONDS = 51


def _load(path):
    with open(path) as f:
        return json.load(f)


def _names(sub):
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(HERE, sub, "*.json")))


def build():
    cells = {n: _load(os.path.join(HERE, "cells", n + ".json"))
             for n in _names("cells")}
    cell_names = sorted(cells)
    configs, seen = [], set()
    for n in cell_names:
        c = cells[n]["config"]
        if c not in seen:
            seen.add(c)
            cfg = _load(os.path.join(HERE, "configs", c + ".json"))
            configs.append({"name": c, "source": cfg["source"],
                            "file": "benchmarks/configs/{}.json".format(c),
                            "reduced": cfg["reduced"], "why": cfg["why"]})
    workloads = [{"name": n, "config": cells[n]["config"],
                  "traffic": cells[n]["traffic"],
                  "chips": cells[n]["chips"], "why": cells[n]["why"]}
                 for n in cell_names]

    def metric(sub, name, keys, listed_in):
        spec = _load(os.path.join(HERE, sub, name + ".json"))
        out = {k: spec[k] for k in keys}
        out["workloads"] = [n for n in cell_names
                            if name in cells[n][listed_in]]
        return out

    end_to_end = [metric("end_to_end", n,
                         ("name", "unit", "better", "bound", "source"),
                         "end_to_end") for n in _names("end_to_end")]
    for m in end_to_end:
        if len(m["workloads"]) == len(cell_names):
            del m["workloads"]  # reported by every cell
    per_layer = [metric("layer_metrics", n,
                        ("name", "unit", "better", "source", "layer",
                         "moves"), "per_layer")
                 for n in _names("layer_metrics")]
    return {"command": ["python3", "benchmarks/run.py"],
            "paths": ["benchmarks"], "run_seconds": RUN_SECONDS,
            "configs": configs, "workloads": workloads,
            "end_to_end": end_to_end,
            "per_layer": [m for m in per_layer if m["workloads"]]}


def main(argv):
    text = json.dumps(build(), indent=1) + "\n"
    path = os.path.join(ROOT, "BENCHMARK.json")
    if "--check" in argv:
        with open(path) as f:
            same = f.read() == text
        print("BENCHMARK.json is {}".format(
            "what the files give" if same else "NOT what the files give"))
        return 0 if same else 1
    with open(path, "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
