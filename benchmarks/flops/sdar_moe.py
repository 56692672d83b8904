"""Operations and bytes an SDAR-MoE decoder needs, from shapes alone.

A multiply-accumulate is 2 FLOPs. ``model`` is the configuration's
``model`` group. Only the ``experts_per_tok`` experts a position is
routed to count (active parameters), the output head counts for every
position of a denoising or commit pass (each yields logits) and for
none of a prefill (it samples nothing), embeddings are free. Attention
is counted by (query, visible key) pairs: a query sees every position
up to the end of its own block.
"""


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
    """Every parameter held: all ``expert_count`` experts of each
    layer, the norms, the embedding and the untied head."""
    h, d = model["hidden"], model["head_dim"]
    held = model.get("expert_count") or model["num_experts"]
    layer = _attention_params(model) + 2 * h + 2 * d \
        + h * model["num_experts"] \
        + held * 3 * h * model["moe_hidden"]
    return model["num_layers"] * layer + h + 2 * h * model["vocab"]


def position_flops(model, head=True):
    """Matrix-product FLOPs of one position in one pass, attention's
    score and value products apart."""
    flops = 2 * model["num_layers"] * layer_matmul_params(model)
    return flops + (2 * model["hidden"] * model["vocab"] if head else 0)


def attention_pair_flops(model):
    """QK^T and PV of one (query, visible key) pair, all layers."""
    return 4 * model["num_heads"] * model["head_dim"] * model["num_layers"]


def block_pass_flops(model, start):
    """One pass (denoising or commit) over the block at ``start``:
    ``block_len`` positions, each seeing ``start + block_len`` keys."""
    b = model["block_len"]
    return b * position_flops(model) \
        + attention_pair_flops(model) * b * (start + b)


def prefill_flops(model, n):
    """Prefill of ``n`` prompt tokens (whole blocks), no head."""
    b = model["block_len"]
    blocks = n // b
    pairs = b * b * blocks * (blocks + 1) // 2
    return n * position_flops(model, head=False) \
        + attention_pair_flops(model) * pairs


def kv_bytes(model, keys, bytes_per_value=2):
    """K and V of ``keys`` positions, all layers."""
    return 2 * keys * model["num_kv_heads"] * model["head_dim"] \
        * bytes_per_value * model["num_layers"]


def _least(flops, nbytes, peaks):
    f, b = flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"]
    return max(f, b), f > b


def paged_attention_least_seconds(model, work, peaks, bytes_per_value=2):
    """The least time the chip's peaks allow for the attention calls of
    ``work`` = ``{"prefills": [tokens, ...], "pass_starts": [start,
    ...]}`` (one start per slot and pass): for each prefill, and for all
    passes together, the larger of FLOPs over peak FLOP/s and the live
    K/V bytes (4 K/V heads of 128 at 2 bytes, read once) over peak
    bytes/s. Also returns which of the two bounds most of it."""
    b = model["block_len"]
    pair = attention_pair_flops(model)
    total = by_flops = 0.0
    for n in work["prefills"]:
        blocks = n // b
        t, f = _least(pair * b * b * blocks * (blocks + 1) // 2,
                      kv_bytes(model, n, bytes_per_value), peaks)
        total += t
        by_flops += t if f else 0.0
    t, f = _least(sum(pair * b * (s + b) for s in work["pass_starts"]),
                  sum(kv_bytes(model, s + b, bytes_per_value)
                      for s in work["pass_starts"]), peaks)
    total += t
    by_flops += t if f else 0.0
    return total, ("flops" if by_flops > total / 2 else "bytes")


def expert_gmm_least_seconds(model, work, peaks, bytes_per_value=2):
    """The least time the chip's peaks allow for the grouped products
    of ``work`` = ``{"experts_touched": sum over calls of the experts
    with at least one row, "expert_rows": sum over calls of the rows
    (positions x experts_per_tok) routed to held experts}``, both over
    the positions that belong to a request (the rows of idle slots and
    of padding are multiplied too and are no necessary work): the bytes
    of the three matrices of every expert touched, plus each row's
    activations in and out of the three products, against their FLOPs.
    Also returns which bounds it."""
    h, f = model["hidden"], model["moe_hidden"]
    rows = work.get("expert_rows", 0)
    weights = work.get("experts_touched", 0) * 3 * h * f
    # gate and up read H and write F each; down reads F and writes H
    activations = rows * (3 * h + 3 * f)
    t, by_flops = _least(rows * 3 * 2 * h * f,
                         (weights + activations) * bytes_per_value, peaks)
    return t, ("flops" if by_flops else "bytes")
