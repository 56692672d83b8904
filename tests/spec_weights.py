"""Weights that FORCE a speculation outcome, for tests (and the bench
leg that publishes speculation's ceiling)."""

import jax
import numpy as np


def zero_residual_tail(params, keep_layers, num_layers):
    """Params whose blocks past ``keep_layers`` contribute NOTHING to
    the residual stream (attn out + mlp_out projections zeroed — each
    block becomes an exact identity). The weight-tied draft (the first
    ``keep_layers`` blocks + the shared head) then agrees with the
    target at EVERY position: acceptance is exactly 1.0, so every
    round emits its whole window and the window arithmetic (including
    the clamp at a request's length cap) is deterministic. Correctness
    at arbitrary acceptance is pinned with natural random weights."""
    def zeroed(tree):
        return jax.tree.map(lambda a: np.zeros_like(a), tree)

    params = dict(params)
    for i in range(int(keep_layers), int(num_layers)):
        blk = dict(params["block_%d" % i])
        attn = dict(blk["attn"])
        attn["out"] = zeroed(attn["out"])
        blk["attn"] = attn
        blk["mlp_out"] = zeroed(blk["mlp_out"])
        params["block_%d" % i] = blk
    return params
