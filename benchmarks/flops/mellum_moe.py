"""Operations and bytes a Mellum-MoE decoder needs, from shapes alone.

A multiply-accumulate is 2 FLOPs. ``model`` is the configuration's
``model`` group. Only the ``experts_per_tok`` experts a position is
routed to count (active parameters); the output head counts once per
token that is sampled (the last position of a prefill, every decode
step); embeddings are free. Attention is counted by (query, visible
key) pairs and by LAYER KIND: a full layer's query at position ``p``
sees ``p + 1`` keys, a sliding layer's ``min(p + 1, sliding_window)``.
The least bytes of attention are counted the same way: the K and V of
the keys a query sees, once, whatever an implementation walks beyond
them (a kernel that reads dead blocks behind a window reads low
against these, as it should).
"""


def layer_kinds(model):
    """``(full layers, sliding layers)`` of the configuration."""
    kinds = model["layer_types"]
    each = [kinds[i % len(kinds)] for i in range(model["num_layers"])]
    return each.count("full"), each.count("sliding")


def _attention_params(model):
    """Wq and Wo, Wk and Wv of one layer."""
    h, d = model["hidden"], model["head_dim"]
    return 2 * h * d * (model["num_heads"] + model["num_kv_heads"])


def layer_matmul_params(model):
    """Weights of one layer that one position multiplies: the attention
    projections, the router, and its ``experts_per_tok`` experts."""
    h = model["hidden"]
    experts = model["experts_per_tok"] * 3 * h * model["moe_hidden"]
    return _attention_params(model) + h * model["num_experts"] + experts


def parameter_count(model):
    """Every parameter held: all experts of each layer, the norms (two
    a layer, QK-norm's two, the final one), the embedding and the
    untied head."""
    h, d = model["hidden"], model["head_dim"]
    layer = _attention_params(model) + 2 * h + 2 * d \
        + h * model["num_experts"] \
        + model["num_experts"] * 3 * h * model["moe_hidden"]
    return model["num_layers"] * layer + h + 2 * h * model["vocab"]


def visible_keys(model, position):
    """``(a full layer's, a sliding layer's)`` keys a query at
    ``position`` sees, itself among them."""
    return position + 1, min(position + 1, model["sliding_window"])


def attention_pairs(model, q_len, start=0):
    """(query, visible key) pairs of ``q_len`` queries at positions
    ``start .. start + q_len - 1``, summed over ALL layers, each kind
    by its own mask."""
    full, sliding = layer_kinds(model)
    w = model["sliding_window"]
    lo, hi = start, start + q_len          # positions lo .. hi - 1
    full_pairs = (hi * (hi + 1) - lo * (lo + 1)) // 2
    # a sliding layer: p + 1 keys while p + 1 <= w, then w
    ramp_hi, ramp_lo = min(hi, w), min(lo, w)
    win_pairs = (ramp_hi * (ramp_hi + 1) - ramp_lo * (ramp_lo + 1)) // 2 \
        + w * (max(hi, w) - max(lo, w))
    return full * full_pairs + sliding * win_pairs


def pair_flops(model):
    """QK^T and PV of one (query, visible key) pair in one layer."""
    return 4 * model["num_heads"] * model["head_dim"]


def position_flops(model, head=True):
    """Matrix-product FLOPs of one position, attention's score and
    value products apart."""
    flops = 2 * model["num_layers"] * layer_matmul_params(model)
    return flops + (2 * model["hidden"] * model["vocab"] if head else 0)


def sequence_flops(model, prompt_len, new_tokens):
    """Model FLOPs to prefill ``prompt_len`` tokens and decode until
    ``new_tokens`` have been emitted (the first comes from the prefill,
    so ``new_tokens - 1`` decode steps)."""
    steps = max(new_tokens - 1, 0)
    processed = prompt_len + steps
    heads = 2 * model["hidden"] * model["vocab"] * (1 + steps) \
        if new_tokens else 0
    return processed * position_flops(model, head=False) + heads \
        + pair_flops(model) * attention_pairs(model, processed)


def step_token_flops(model, position):
    """Model FLOPs of one decode step for one sequence whose new token
    sits at ``position``."""
    return position_flops(model) \
        + pair_flops(model) * attention_pairs(model, 1, position)


def kv_bytes_per_token(model, bytes_per_value=2):
    """K and V of one cached position in ONE layer."""
    return 2 * model["num_kv_heads"] * model["head_dim"] * bytes_per_value


def attention_least_bytes(model, position, bytes_per_value=2):
    """The least K/V bytes the attention of one decode step at
    ``position`` must read, all layers: a full layer ``position + 1``
    tokens, a sliding layer ``min(position + 1, sliding_window)``."""
    full, sliding = layer_kinds(model)
    f_keys, w_keys = visible_keys(model, position)
    return (full * f_keys + sliding * w_keys) \
        * kv_bytes_per_token(model, bytes_per_value)


def paged_attention_least_seconds(model, work, peaks, bytes_per_value=2):
    """The least time the chip's peaks allow for the paged kernel's
    calls of ``work`` = ``{"decode_positions": [position, ...]}`` (one a
    slot and step), both layer kinds together: the larger of the pairs'
    FLOPs over peak FLOP/s and :func:`attention_least_bytes` over peak
    bytes/s. A prefill of this family attends its own K and V and never
    calls the kernel, so ``work["prefills"]`` is not counted here. Also
    returns which of the two bounds it."""
    flops = sum(pair_flops(model) * attention_pairs(model, 1, p)
                for p in work["decode_positions"])
    nbytes = sum(attention_least_bytes(model, p, bytes_per_value)
                 for p in work["decode_positions"])
    f, b = flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"]
    return max(f, b), ("flops" if f > b else "bytes")
