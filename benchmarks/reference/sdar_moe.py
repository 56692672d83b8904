"""Plain reference for SDAR-MoE decoders (``sdar-30b-a3b``): the
forward pass of ``JetLM/SDAR-30B-A3B-Chat`` (``model_type: sdar_moe``)
in straightforward ``jax.numpy``, float32 on the bfloat16-rounded
weights, ``highest`` matmul precision, no cache, no kernels, no
batching, a loop over the experts with masks. It imports nothing of the
program; the weights are the benchmark's own (:func:`init_params`, from
the seed: bfloat16, normal(0.02), norms 1).

One layer over ``x [T, hidden]``, no bias anywhere::

    h = rmsnorm(x; w_in)
    q, k, v = h Wq, h Wk, h Wv            heads of head_dim, fewer K/V heads
    q, k = rmsnorm over head_dim (QK-norm), then rotate-half RoPE at p
    query at p sees key at p' iff p' <= B*floor(p/B) + B - 1
    x = x + concat(softmax(q k^T / sqrt(head_dim)) v) Wo
    h = rmsnorm(x; w_post)
    r = softmax(h Wr);  I = top-k of r;  g_i = r_i / sum_{j in I} r_j
    x = x + sum_{i in I} g_i (silu(h Wg_i) * (h Wu_i)) Wd_i

then ``rmsnorm`` and the untied head. The layers run one small jitted
program at a time and the experts inside it one at a time
(``lax.scan``), so the most it holds in float32 is one expert.

The chip's share: ``model["first_expert"]``/``model["expert_count"]``
say which experts are held; routing is over all of them, and only the
held ones add to the result (what the absent ones would have added is
left out, as in the program).

Generation (:func:`unmask_rule`, :func:`block_states`) is diffusion
over blocks of ``B`` positions at temperature 0, as the family's
generation code has it (``low_confidence_dynamic``). Departures from
the published description, the program's and followed here:

- positions of the last block that lie past ``prompt + max_new`` stay
  ``MASK`` in every pass and are never unmasked (the published code
  denoises the whole last block and cuts the output afterwards); so
  every intermediate state can be rebuilt from what was served;
- a tie between confidences goes to the earlier position.

``precision`` says what every matrix product multiplies in:
``"float32"`` (the reference), ``"bfloat16"`` (what the configuration
states) or ``"float8"`` (the control, one step below: operands rounded
to ``float8_e4m3fn``). Everything between the products stays float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
PAD_TO = 256  # a replayed sequence is padded to a multiple of this


def _dims(model):
    return (model["vocab"], model["hidden"], model["num_heads"],
            model["num_kv_heads"], model["head_dim"], model["num_experts"],
            model["moe_hidden"])


@functools.partial(jax.jit, static_argnames=("dims",))
def _init_layer(key, dims):
    _v, h, n, kv, d, e, f = dims
    ks = jax.random.split(key, 8)

    def normal(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)) \
            .astype(jnp.bfloat16)

    def ones(shape):
        return jnp.ones(shape, jnp.bfloat16)

    return {
        "ln_in": {"scale": ones((h,))}, "ln_post": {"scale": ones((h,))},
        "attn": {"wq": normal(ks[0], (h, n * d)),
                 "wk": normal(ks[1], (h, kv * d)),
                 "wv": normal(ks[2], (h, kv * d)),
                 "wo": normal(ks[3], (n * d, h)),
                 "q_norm": ones((d,)), "k_norm": ones((d,))},
        "moe": {"router": normal(ks[4], (h, e)),
                "gate": normal(ks[5], (e, h, f)),
                "up": normal(ks[6], (e, h, f)),
                "down": normal(ks[7], (e, f, h))}}


@functools.partial(jax.jit, static_argnames=("shape",))
def _init_matrix(key, shape):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)) \
        .astype(jnp.bfloat16)


def init_params(key, model):
    """The benchmark's weights from the seed, bfloat16, in the layout
    the program's ``SdarMoeLM`` reads; one jitted call per layer, so
    that the float32 draws of one layer are the most it holds beside
    the weights."""
    dims = _dims(model)
    v, h = dims[0], dims[1]
    ks = jax.random.split(key, model["num_layers"] + 2)
    params = {
        "embedding": _init_matrix(ks[0], (v, h)),
        "ln_f": {"scale": jnp.ones((h,), jnp.bfloat16)},
        "head": _init_matrix(ks[1], (h, v))}
    for i in range(model["num_layers"]):
        params["layer_%d" % i] = _init_layer(ks[i + 2], dims)
    return params


def _rounder(precision):
    """What an operand of a matrix product is rounded to."""
    if precision == "float32":
        return lambda x: x
    dt = {"bfloat16": jnp.bfloat16, "float8": jnp.float8_e4m3fn}[precision]
    return lambda x: x.astype(dt).astype(jnp.float32)


def _mm(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a.astype(jnp.float32)),
                      rnd(b.astype(jnp.float32)), precision=_HI)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half RoPE over the last axis of ``x [T, heads, D]`` at
    positions 0..T-1."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "top_k", "block_len", "theta", "eps", "first",
    "count", "precision"))
def _layer(p, x, heads, kv_heads, top_k, block_len, theta, eps, first,
           count, precision):
    """One layer over one sequence ``x [T, H]`` (float32)."""
    rnd = _rounder(precision)
    t = x.shape[0]
    a = p["attn"]
    d = a["wq"].shape[1] // heads
    h = _rms(x, p["ln_in"]["scale"], eps)
    q = _mm("th,hf->tf", h, a["wq"], rnd).reshape(t, heads, d)
    k = _mm("th,hf->tf", h, a["wk"], rnd).reshape(t, kv_heads, d)
    v = _mm("th,hf->tf", h, a["wv"], rnd).reshape(t, kv_heads, d)
    q = _rope(_rms(q, a["q_norm"], eps), theta)
    k = _rope(_rms(k, a["k_norm"], eps), theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)  # query head n reads K/V head n // group
    v = jnp.repeat(v, group, axis=1)
    scores = _mm("qnd,knd->nqk", q, k, rnd) * (d ** -0.5)
    pos = jnp.arange(t)
    last_seen = (pos // block_len) * block_len + block_len - 1
    scores = jnp.where((pos[None, :] <= last_seen[:, None])[None], scores,
                       -jnp.inf)
    ctx = _mm("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v, rnd)
    x = x + _mm("tf,fh->th", ctx.reshape(t, heads * d), a["wo"], rnd)

    m = p["moe"]
    h = _rms(x, p["ln_post"]["scale"], eps)
    r = jax.nn.softmax(_mm("th,he->te", h, m["router"], rnd), axis=-1)
    top, idx = lax.top_k(r, top_k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    # weight[e, t]: the gate of expert e for token t, 0 where e is not
    # among the token's top-k
    weight = jnp.zeros((r.shape[1], t), jnp.float32).at[
        idx.T, jnp.arange(t)[None, :]].set(gates.T)

    def one_expert(y, w):
        gate, up, down, wt = w
        act = jax.nn.silu(_mm("th,hf->tf", h, gate, rnd)) \
            * _mm("th,hf->tf", h, up, rnd)
        return y + wt[:, None] * _mm("tf,fh->th", act, down, rnd), None

    held = slice(first, first + count)
    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (m["gate"][held], m["up"][held], m["down"][held],
                     weight[held]))
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(ln_f, head, x, eps, precision):
    return _mm("sh,hv->sv", _rms(x, ln_f["scale"], eps), head,
               _rounder(precision))


def logits(params, tokens, model, rows=None, precision="float32"):
    """float32 ``[len(rows), vocab]`` logits of one sequence (every
    row when ``rows`` is None)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embedding"][tokens].astype(jnp.float32)
    for i in range(model["num_layers"]):
        x = layer_output(params, x, model, i, precision)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return _head(params["ln_f"], params["head"], x,
                 float(model["rms_eps"]), precision)


def layer_output(params, x, model, layer=0, precision="float32"):
    """One layer's output for hidden states ``x [T, H]``: a step of
    :func:`logits`, and the tests' handle on a single layer and on the
    experts' shares."""
    return _layer(params["layer_%d" % layer], jnp.asarray(x, jnp.float32),
                  model["num_heads"], model["num_kv_heads"],
                  model["experts_per_tok"], model["block_len"],
                  float(model["rope_theta"]), float(model["rms_eps"]),
                  model.get("first_expert", 0),
                  model.get("expert_count", model["num_experts"]), precision)


# -- generation, as a plain function of logits --------------------------


def confidences(block_logits):
    """(argmax token, its log-probability) per row of ``[B, vocab]``."""
    lg = np.asarray(block_logits, np.float64)
    best = lg.max(axis=-1)
    lse = best + np.log(np.exp(lg - best[:, None]).sum(axis=-1))
    return lg.argmax(axis=-1), best - lse


def unmask_rule(log_conf, masked, quota, tau):
    """Which of the ``masked`` positions one denoising pass unmasks:
    every one whose confidence exceeds ``tau``, or, if those are fewer
    than ``quota``, the ``quota`` most confident (``low_confidence_
    dynamic``; ``tau >= 1`` is the static schedule). Boolean ``[B]``."""
    masked = np.asarray(masked, bool)
    conf = np.where(masked, np.exp(np.asarray(log_conf, np.float64)),
                    -np.inf)
    high = masked & (conf > tau)
    if high.sum() >= quota:
        return high
    order = np.argsort(-conf, kind="stable")[:min(quota, masked.sum())]
    chosen = np.zeros_like(masked)
    chosen[order] = True
    return chosen & masked


def quota(model):
    return max(1, model["block_len"] // model["denoise_steps"])


def block_states(prompt, served, passes, model, block):
    """The inputs of every denoising pass of ``block`` (an index into
    the sequence's blocks of ``B``), rebuilt from what was served:
    ``[(tokens up to the block's end, masked [B], unmasked_now [B])]``,
    one per pass. A served token is known from the pass after the one
    that unmasked it; positions past the end of the request stay
    ``MASK`` and count as neither."""
    b, mask_id = model["block_len"], model["mask_token_id"]
    p, seq = len(prompt), list(prompt) + list(served)
    lo = block * b
    own = range(lo, lo + b)
    when = [(-1 if i < p else passes[i - p]) if i < len(seq) else None
            for i in own]
    out = []
    for k in range(max(w for w in when if w is not None) + 1):
        state = [seq[i] if w is not None and w < k else mask_id
                 for i, w in zip(own, when)]
        out.append((seq[:lo] + state,
                    np.array([w is not None and w >= k for w in when]),
                    np.array([w == k for w in when])))
    return out


def served_gaps(params, prompt, served, passes, model, blocks,
                control=None, pad_to=PAD_TO):
    """For one finished request and the given blocks: every denoising
    pass replayed on the sequence up to the block's end. Per position
    that the pass unmasked: ``served``, how far the served token's
    logit lies below the reference's best there, and ``order``, how far
    the position's log-confidence lies below what the rule asked of it
    (the ``quota``-th best among the positions then masked, or
    ``tau``). With ``control`` (a precision), ``control_served`` and
    ``control_order`` are the same two numbers for what the
    lower-precision forward would have served at the same states. A
    sequence is padded to a multiple of ``pad_to`` (what comes after a
    block is invisible to it), so that few programs serve every
    length."""
    b, q = model["block_len"], quota(model)
    log_tau = math.log(model["confidence_threshold"]) \
        if model["confidence_threshold"] > 0 else -np.inf
    out = {"served": [], "order": []}
    if control is not None:
        out.update(control_served=[], control_order=[])
    p = len(prompt)
    for block in blocks:
        lo = block * b
        rows = list(range(lo, lo + b))
        for tokens, masked, now in block_states(prompt, served, passes,
                                                model, block):
            padded = tokens + [0] * (-len(tokens) % pad_to)
            ref = np.asarray(logits(params, padded, model, rows))
            _, lc = confidences(ref)
            asked = _asked(lc, masked, q, log_tau)
            for j in np.flatnonzero(now):
                tok = served[lo + j - p]
                out["served"].append(float(ref[j].max() - ref[j, tok]))
                out["order"].append(float(max(0.0, asked - lc[j])))
            if control is None:
                continue
            low = np.asarray(logits(params, padded, model, rows, control))
            pick, low_lc = confidences(low)
            for j in np.flatnonzero(unmask_rule(
                    low_lc, masked, q, model["confidence_threshold"])):
                out["control_served"].append(
                    float(ref[j].max() - ref[j, pick[j]]))
                out["control_order"].append(float(max(0.0, asked - lc[j])))
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def _asked(log_conf, masked, q, log_tau):
    """The log-confidence a masked position needs to be unmasked now:
    the ``q``-th best among the masked, or ``tau`` if that is lower."""
    live = np.sort(log_conf[masked])[::-1]
    kth = live[min(q, len(live)) - 1]
    return min(kth, log_tau)
