"""Operations and bytes a GPT-2 style decoder needs, from shapes alone.

A multiply-accumulate is 2 FLOPs. ``model`` is the configuration's
``model`` group: ``vocab``, ``hidden``, ``num_heads``, ``num_layers``.
Attention is counted causally (a query at position p multiplies p + 1
keys), the output head once per token that is sampled (the last
position of a prefill, every decode step), embeddings as free.
"""


def matmul_params(model):
    """(per layer, head) weights that take part in a matrix product."""
    h = model["hidden"]
    return 12 * h * h, h * model["vocab"]


def parameter_count(model, untied_head=True):
    h, v = model["hidden"], model["vocab"]
    per_layer = 12 * h * h + 13 * h  # kernels + biases + two LayerNorms
    n = model["num_layers"] * per_layer + v * h + model["max_len"] * h + 2 * h
    return n + (h * v + v if untied_head else 0)


def attention_flops(model, q_len, start=0):
    """QK^T and PV for ``q_len`` queries at positions start..start+q_len-1,
    all layers: 4 * hidden FLOPs per (query, visible key) pair."""
    pairs = q_len * start + q_len * (q_len + 1) // 2
    return 4 * model["hidden"] * pairs * model["num_layers"]


def attention_kv_bytes(model, q_len, start=0, bytes_per_value=4):
    """The live K and V one call per layer must read, all layers: every
    key/value position up to the last query's, once - whatever the
    implementation reads beyond that is its own."""
    live = start + q_len
    return 2 * live * model["hidden"] * bytes_per_value * model["num_layers"]


def sequence_flops(model, prompt_len, new_tokens):
    """Model FLOPs to prefill ``prompt_len`` tokens and decode until
    ``new_tokens`` have been emitted (the first comes from the prefill,
    so ``new_tokens - 1`` decode steps)."""
    per_layer, head = matmul_params(model)
    steps = max(new_tokens - 1, 0)
    processed = prompt_len + steps
    dense = 2 * model["num_layers"] * per_layer * processed
    heads = 2 * head * (1 + steps) if new_tokens else 0
    return dense + heads + attention_flops(model, processed)


def step_token_flops(model, position):
    """Model FLOPs of one decode step for one sequence whose new token
    sits at ``position`` (it sees position + 1 keys)."""
    per_layer, head = matmul_params(model)
    return (2 * model["num_layers"] * per_layer + 2 * head
            + attention_flops(model, 1, position))


def paged_attention_least_seconds(model, work, peaks, bytes_per_value=4):
    """The least time the chip's peaks allow for the attention calls of
    ``work`` = ``{"prefills": [prompt_len, ...], "decode_positions":
    [position, ...]}``: for each prefill, and for all decode tokens
    together, the larger of FLOPs over peak FLOP/s and live KV bytes
    over peak bytes/s. Also returns which of the two bounds most of
    it."""
    f_peak, b_peak = peaks["flops_per_s"], peaks["bytes_per_s"]
    total = by_flops = 0.0
    for n in work["prefills"]:
        f = attention_flops(model, n) / f_peak
        b = attention_kv_bytes(model, n, 0, bytes_per_value) / b_peak
        total += max(f, b)
        by_flops += f if f > b else 0.0
    f = sum(attention_flops(model, 1, p)
            for p in work["decode_positions"]) / f_peak
    b = sum(attention_kv_bytes(model, 1, p, bytes_per_value)
            for p in work["decode_positions"]) / b_peak
    total += max(f, b)
    by_flops += f if f > b else 0.0
    return total, ("flops" if by_flops > total / 2 else "bytes")
