"""Operations and bytes a Laguna decoder needs, from shapes alone.

A multiply-accumulate is 2 FLOPs. ``model`` is the configuration's
``model`` group. A layer kind has its own query heads (``num_heads`` on
a full layer, ``sliding_num_heads`` on a sliding one) over the one
``num_kv_heads``; the layers in ``mlp_only_layers`` are dense. Of a
sparse layer a position multiplies the router, the shared expert and
the HELD share of its routed experts: ``experts_per_tok`` assignments
of which ``expert_count / num_experts`` land on this chip in
expectation (5 of 10 at 128 of 256); the rest is another chip's work.
The output head (over the vocabulary held, ``vocab``) counts once per
token that is sampled (the last position of a prefill, every decode
step); embeddings are free. Attention is counted by (query, visible
key) pairs and by layer kind, as ``flops/mellum_moe.py`` counts them.
"""

from benchmarks.flops.mellum_moe import (
    kv_bytes_per_token, layer_kinds, visible_keys)


def _kinds(model):
    """``(full, sliding)`` of each layer, in order."""
    kinds = model["layer_types"]
    return [kinds[i % len(kinds)] for i in range(model["num_layers"])]


def heads(model, kind):
    return model["sliding_num_heads"] if kind == "sliding" \
        else model["num_heads"]


def attention_params(model, kind):
    """Wq and Wo, Wk and Wv and the head gate of one layer of ``kind``."""
    h, d, n = model["hidden"], model["head_dim"], heads(model, kind)
    return 2 * h * d * (n + model["num_kv_heads"]) + h * n


def held_share(model):
    """The share of the routed experts this chip holds."""
    held = model.get("expert_count") or model["num_experts"]
    return held / float(model["num_experts"])


def feed_forward_params(model, layer, routed=True):
    """Weights of one layer's feed-forward that one position multiplies:
    the dense layer's; or the router, the shared expert and (``routed``)
    the held share of its ``experts_per_tok`` experts."""
    h = model["hidden"]
    if layer in model["mlp_only_layers"]:
        return 3 * h * model["dense_hidden"]
    out = h * model["num_experts"] + 3 * h * model["shared_hidden"]
    if routed:
        out += model["experts_per_tok"] * held_share(model) \
            * 3 * h * model["moe_hidden"]
    return out


def parameter_count(model):
    """Every parameter held: each layer's attention (its kind's heads),
    QK-norm and two norms; the dense layer's MLP or a sparse layer's
    router, shared expert and ``expert_count`` held experts; the final
    norm, the embedding and the untied head over the held vocabulary."""
    h, d = model["hidden"], model["head_dim"]
    held = model.get("expert_count") or model["num_experts"]
    total = h + 2 * h * model["vocab"]
    for i, kind in enumerate(_kinds(model)):
        total += attention_params(model, kind) + 2 * d + 2 * h \
            + feed_forward_params(model, i, routed=False)
        if i not in model["mlp_only_layers"]:
            total += held * 3 * h * model["moe_hidden"]
    return total


def position_flops(model, head=True):
    """Matrix-product FLOPs of one position, attention's score and
    value products apart."""
    flops = sum(2 * (attention_params(model, kind)
                     + feed_forward_params(model, i))
                for i, kind in enumerate(_kinds(model)))
    return flops + (2 * model["hidden"] * model["vocab"] if head else 0)


def pair_flops(model, kind):
    """QK^T and PV of one (query, visible key) pair in one layer."""
    return 4 * heads(model, kind) * model["head_dim"]


def attention_flops(model, q_len, start=0):
    """Score and value FLOPs of ``q_len`` queries at positions ``start
    ..``, all layers, each kind by its own mask and heads."""
    full, sliding = layer_kinds(model)
    w = model["sliding_window"]
    lo, hi = start, start + q_len
    full_pairs = (hi * (hi + 1) - lo * (lo + 1)) // 2
    ramp_hi, ramp_lo = min(hi, w), min(lo, w)
    win_pairs = (ramp_hi * (ramp_hi + 1) - ramp_lo * (ramp_lo + 1)) // 2 \
        + w * (max(hi, w) - max(lo, w))
    return full * full_pairs * pair_flops(model, "full") \
        + sliding * win_pairs * pair_flops(model, "sliding")


def sequence_flops(model, prompt_len, new_tokens):
    """Model FLOPs to prefill ``prompt_len`` tokens and decode until
    ``new_tokens`` have been emitted (the first comes from the prefill,
    so ``new_tokens - 1`` decode steps)."""
    steps = max(new_tokens - 1, 0)
    processed = prompt_len + steps
    head = 2 * model["hidden"] * model["vocab"] * (1 + steps) \
        if new_tokens else 0
    return processed * position_flops(model, head=False) + head \
        + attention_flops(model, processed)


def step_token_flops(model, position):
    """Model FLOPs of one decode step for one sequence whose new token
    sits at ``position``."""
    return position_flops(model) + attention_flops(model, 1, position)


def attention_least_bytes(model, position, bytes_per_value=2):
    """The least K/V bytes the attention of one decode step at
    ``position`` must read, all layers: a full layer ``position + 1``
    tokens, a sliding layer ``min(position + 1, sliding_window)``, each
    at the one pool width of ``num_kv_heads`` heads."""
    full, sliding = layer_kinds(model)
    f_keys, w_keys = visible_keys(model, position)
    return (full * f_keys + sliding * w_keys) \
        * kv_bytes_per_token(model, bytes_per_value)


def paged_attention_least_seconds(model, work, peaks, bytes_per_value=2):
    """The least time the chip's peaks allow for the paged kernel's
    calls of ``work`` = ``{"decode_positions": [position, ...]}`` (one a
    slot and step), both layer kinds together: the larger of the pairs'
    FLOPs (each kind's query heads) over peak FLOP/s and
    :func:`attention_least_bytes` over peak bytes/s. A prefill attends
    its own K and V and never calls the kernel. Also returns which of
    the two bounds it."""
    flops = sum(attention_flops(model, 1, p)
                for p in work["decode_positions"])
    nbytes = sum(attention_least_bytes(model, p, bytes_per_value)
                 for p in work["decode_positions"])
    f, b = flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"]
    return max(f, b), ("flops" if f > b else "bytes")
