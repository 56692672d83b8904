"""Write ``BENCHMARK.json`` as ``make_manifest.build()`` gives it from
the files, in the order the file on disk already has: an entry that is
there stays where it is, an entry the files add goes to the END of its
list (a cell added to a metric's ``workloads`` likewise).

For a PR that may only ADD to the benchmark: the driver reads an entry
put first or in the middle of a list as a change to what was there,
and ``make_manifest.py`` sorts every list by name, so a new metric
named ``commit_pass_share`` would land second. Every entry's content is
``build()``'s; only the order is kept. A ``benchmark`` PR, which may
edit ``make_manifest.py``, should give it this mode and delete this
file (PERF.md, Open questions).

    python benchmarks/append_manifest.py [--check]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmarks import make_manifest  # noqa: E402

LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def _held_first(built, held):
    """``built`` with what ``held`` also has first, in ``held``'s
    order, and the rest after it in ``built``'s."""
    rest = [x for x in built if x not in held]
    return [x for x in held if x in built] + rest


def in_held_order(built, held):
    """``built`` (a manifest as ``make_manifest.build()`` gives it) with
    every list in the order the manifest ``held`` has."""
    out = dict(built)
    for key in LISTS:
        entries = {e["name"]: e for e in built[key]}
        was = {e["name"]: e for e in held.get(key, ())}
        out[key] = []
        for name in _held_first(list(entries), list(was)):
            entry = dict(entries[name])
            if "workloads" in entry:
                entry["workloads"] = _held_first(
                    entry["workloads"], was.get(name, {}).get("workloads", []))
            out[key].append(entry)
    return out


def main(argv):
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        held_text = f.read()
    text = json.dumps(in_held_order(make_manifest.build(),
                                    json.loads(held_text)), indent=1) + "\n"
    if "--check" in argv:
        same = held_text == text
        print("BENCHMARK.json is {}".format(
            "what the files give, in the order it had" if same
            else "NOT what the files give"))
        return 0 if same else 1
    with open(path, "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
