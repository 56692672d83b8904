"""Operations and bytes a Falcon-H1 decoder needs, from shapes alone.

A multiply-accumulate is 2 FLOPs. ``model`` is the configuration's
``model`` group. Per position and layer the matrix products multiply
every weight of the attention projections, the mixer's ``in_proj`` and
``out_proj`` and the MLP once; attention adds its (query, visible key)
pairs (every layer is causal over the whole sequence); the mixer's
recurrence adds, per element of the state ``[heads, head_dim,
d_state]``, the update's multiply-add and the output's multiply-add
(4 FLOPs), and its convolution ``d_conv`` multiply-adds a channel.
The head counts once per token that is sampled (the last position of
a prefill, every decode step); embeddings are free.

The least bytes of the decode step's state kernel (``ops/ssm_step.py``)
are, per decoded position and layer, the SSM state read and written,
the convolution's inputs read and written, and the row's x, B, C, dt
and z read and its output written: what a step must move whatever the
kernel does beyond it. The state rows and the activations are the
model's bfloat16, dt and the kernel's output float32.
"""

#: bytes of the state's and the activations' bfloat16, and of dt's and
#: the output's float32
STATE_BYTES, ACT_BYTES, F32_BYTES = 2, 2, 4


def _widths(model):
    """``(d_ssm, groups x d_state, convolution channels)``."""
    d_ssm = model["ssm_heads"] * model["ssm_head_dim"]
    gn = model["ssm_groups"] * model["ssm_state"]
    return d_ssm, gn, d_ssm + 2 * gn


def layer_matmul_params(model):
    """Weights of one layer that one position multiplies."""
    h, d = model["hidden"], model["head_dim"]
    d_ssm, gn, _ = _widths(model)
    attention = 2 * h * d * (model["num_heads"] + model["num_kv_heads"])
    mixer = h * (2 * d_ssm + 2 * gn + model["ssm_heads"]) + d_ssm * h
    return attention + mixer + 3 * h * model["mlp_hidden"]


def state_elements(model):
    """Elements of one layer's SSM state a row."""
    return model["ssm_heads"] * model["ssm_head_dim"] * model["ssm_state"]


def parameter_count(model):
    """Every parameter held: each layer's matrices, the convolution's
    weights and bias, ``dt_bias``, ``A_log`` and ``D``, the gated norm
    and the two RMSNorms; the final norm, the embedding and the untied
    head."""
    h = model["hidden"]
    d_ssm, _, conv = _widths(model)
    layer = layer_matmul_params(model) + conv * (model["conv_kernel"] + 1) \
        + 3 * model["ssm_heads"] + d_ssm + 2 * h
    return model["num_layers"] * layer + h + 2 * h * model["vocab"]


def position_flops(model, head=True):
    """FLOPs of one position, attention's score and value products
    apart."""
    _, _, conv = _widths(model)
    layer = 2 * layer_matmul_params(model) + 4 * state_elements(model) \
        + 2 * model["conv_kernel"] * conv
    return model["num_layers"] * layer \
        + (2 * model["hidden"] * model["vocab"] if head else 0)


def pair_flops(model):
    """QK^T and PV of one (query, visible key) pair, all layers."""
    return 4 * model["num_heads"] * model["head_dim"] * model["num_layers"]


def sequence_flops(model, prompt_len, new_tokens):
    """Model FLOPs to prefill ``prompt_len`` tokens and decode until
    ``new_tokens`` have been emitted (the first comes from the prefill,
    so ``new_tokens - 1`` decode steps)."""
    steps = max(new_tokens - 1, 0)
    processed = prompt_len + steps
    heads = 2 * model["hidden"] * model["vocab"] * (1 + steps) \
        if new_tokens else 0
    pairs = processed * (processed + 1) // 2
    return processed * position_flops(model, head=False) + heads \
        + pair_flops(model) * pairs


def step_token_flops(model, position):
    """Model FLOPs of one decode step for one sequence whose new token
    sits at ``position`` (it sees ``position + 1`` keys)."""
    return position_flops(model) + pair_flops(model) * (position + 1)


def ssm_step_bytes(model):
    """The least bytes the state kernel moves for one decoded position,
    all layers (module docstring)."""
    d_ssm, gn, conv = _widths(model)
    state = 2 * state_elements(model) * STATE_BYTES
    window = 2 * (model["conv_kernel"] - 1) * conv * ACT_BYTES
    rows = (2 * d_ssm + 2 * gn) * ACT_BYTES \
        + model["ssm_heads"] * F32_BYTES + d_ssm * F32_BYTES
    return model["num_layers"] * (state + window + rows)


def ssm_step_least_seconds(model, work, peaks):
    """The least time the chip's peaks allow for the state kernel's
    calls of ``work`` = ``{"decode_positions": [position, ...]}`` (one a
    live row and step): the larger of the recurrence's FLOPs over peak
    FLOP/s and :func:`ssm_step_bytes` over peak bytes/s. A prefill runs
    the chunked scan and never calls the kernel. Also returns which of
    the two bounds it."""
    rows = len(work["decode_positions"])
    flops = rows * model["num_layers"] * 4 * state_elements(model)
    nbytes = rows * ssm_step_bytes(model)
    f, b = flops / peaks["flops_per_s"], nbytes / peaks["bytes_per_s"]
    return max(f, b), ("flops" if f > b else "bytes")
