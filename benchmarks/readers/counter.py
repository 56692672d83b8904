"""A number the runner counted or timed, at ``path`` of its counter
dump (``a.b.c``), times ``scale``. None where the path is absent."""


def lookup(tree, path):
    for part in path.split("."):
        if not isinstance(tree, dict) or part not in tree:
            return None
        tree = tree[part]
    return tree


def read(spec, run):
    value = lookup(run["counters"], spec["path"])
    if value is None:
        return None
    return value * spec.get("scale", 1.0)
