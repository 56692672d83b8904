"""Open-loop request traffic in one fixed order: ``open_loop``'s
parameters, its set of inter-arrival gaps and its set of lengths, put
in the SAME order for every seed. The order is drawn from
``open_loop``'s fixed stream; the seed draws only the token ids.

Where a request holds its slot for many seconds and a step's time grows
with the rows it carries, what meets what sets a window's occupancy:
under ``open_loop`` the seed's order alone moves a cell's median gap by
a tenth. Under this generator two seeds offer the same arrivals and the
same lengths at the same moments, and differ in the prompts' tokens
(and the weights the runner makes from the seed).

Imports numpy only.
"""

import numpy as np

from benchmarks.generators import open_loop


def schedule(params, seed, seconds, vocab):
    """[{due_s, prompt, max_new}] sorted by ``due_s``, all due inside
    ``[0, seconds)``: round(rate * seconds) requests, in an order that
    depends on the rate and the window only."""
    rate = float(params["arrivals"]["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.RandomState(open_loop._SHAPE_STREAM)
    gaps = order.permutation(
        open_loop._gaps(params["arrivals"], n)) * (seconds / n)
    due = np.cumsum(gaps) - gaps[0]
    plen = order.permutation(open_loop._lengths(params["prompt_len"], n))
    olen = order.permutation(open_loop._lengths(params["output_len"], n))
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    return [{"due_s": float(due[i]),
             "prompt": rng.randint(0, vocab, size=int(plen[i])).tolist(),
             "max_new": int(olen[i])} for i in range(n)]
