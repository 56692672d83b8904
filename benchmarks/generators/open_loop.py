"""Open-loop request traffic from a seed: the one generator behind
every serving traffic mix. A mix is a data file of parameters:

    arrivals:   {"process": "poisson", "rate_rps": r}
    prompt_len: {"dist": "loguniform", "lo": a, "hi": b}
    output_len: {"dist": "loguniform", "lo": a, "hi": b}

Every seed gets the SAME set of inter-arrival gaps and the SAME set of
lengths, in another order: the gaps are n draws of the arrival process
from a fixed stream, the lengths an even grid over the distribution's
quantiles, and the seed only permutes them and draws the token ids. So
two seeds offer the same work and differ in what meets what.

Imports numpy only.
"""

import numpy as np

_SHAPE_STREAM = 20260930  # the fixed stream the gap set is drawn from


def _gaps(arrivals, n):
    if arrivals["process"] != "poisson":
        raise ValueError("unknown arrival process {!r}".format(
            arrivals["process"]))
    g = np.random.RandomState(_SHAPE_STREAM).exponential(1.0, size=n)
    return g / g.mean()  # mean 1; scaled by the rate below


def _lengths(spec, n):
    if spec["dist"] != "loguniform":
        raise ValueError("unknown length distribution {!r}".format(
            spec["dist"]))
    u = (np.arange(n) + 0.5) / n
    lo, hi = np.log(spec["lo"]), np.log(spec["hi"])
    return np.clip(np.rint(np.exp(lo + u * (hi - lo))).astype(np.int64),
                   spec["lo"], spec["hi"])


def schedule(params, seed, seconds, vocab):
    """[{due_s, prompt, max_new}] sorted by ``due_s``, all due inside
    ``[0, seconds)``: round(rate * seconds) requests."""
    seed = int(seed) % (2 ** 32)
    rate = float(params["arrivals"]["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.RandomState(seed)
    gaps = rng.permutation(_gaps(params["arrivals"], n)) * (seconds / n)
    due = np.cumsum(gaps) - gaps[0]
    plen = rng.permutation(_lengths(params["prompt_len"], n))
    olen = rng.permutation(_lengths(params["output_len"], n))
    return [{"due_s": float(due[i]),
             "prompt": rng.randint(0, vocab, size=int(plen[i])).tolist(),
             "max_new": int(olen[i])} for i in range(n)]
