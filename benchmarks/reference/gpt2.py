"""Plain reference for GPT-2 style decoders (``gpt2-large``): the
forward pass of Radford et al. 2019 in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no cache, no kernels, no
batching. It imports nothing of the program; the weights are the
benchmark's own (:func:`init_params`, from the seed).

Pre-LN blocks, learned positions, GELU (tanh form), causal softmax
attention, heads of ``hidden / num_heads``. Departures from the
published model, which are the program's and are followed here: the
output head is a separate matrix with a bias (GPT-2 ties it to the
token embedding), LayerNorm eps is 1e-6 (GPT-2: 1e-5).

The forward runs layer by layer (one small jitted program called once
per layer) so that it fits beside nothing but the weights.

``precision`` says what every matrix product multiplies in:
``"float32"`` (the reference), ``"bfloat16"`` (what the configuration
states: float32 storage, operands rounded to bfloat16 by the chip's
default matmul precision, float32 accumulation) or ``"float8"`` (the
control, one step below: operands rounded to ``float8_e4m3fn``).
Everything between the products stays float32, as in the program.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-6
_HI = lax.Precision.HIGHEST


def init_params(key, model):
    """The benchmark's weights from the seed, in one traceable call:
    normal(0.02) kernels and token embeddings, normal(0.01) positions,
    output projections scaled by 1/sqrt(2 * layers) (GPT-2's scheme),
    LayerNorm scale 1, biases 0 - float32, in the layout the program's
    ``DecoderLM`` reads."""
    v, h, n, nl = (model["vocab"], model["hidden"], model["num_heads"],
                   model["num_layers"])
    d = h // n
    ks = jax.random.split(key, 9)
    out_std = 0.02 / (2.0 * nl) ** 0.5

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    qkv = [normal(ks[i], (nl, h, n, d), 0.02) for i in range(3)]
    out = normal(ks[3], (nl, n, d, h), out_std)
    mlp_in = normal(ks[4], (nl, h, 4 * h), 0.02)
    mlp_out = normal(ks[5], (nl, 4 * h, h), out_std)

    def ln():
        return {"scale": jnp.ones((h,), jnp.float32),
                "bias": jnp.zeros((h,), jnp.float32)}

    params = {
        "tok_embed": {"embedding": normal(ks[6], (v, h), 0.02)},
        "pos_embed": normal(ks[7], (model["max_len"], h), 0.01),
        "ln_f": ln(),
        "head": {"kernel": normal(ks[8], (h, v), 0.02),
                 "bias": jnp.zeros((v,), jnp.float32)}}
    for i in range(nl):
        params["block_%d" % i] = {
            "ln1": ln(), "ln2": ln(),
            "attn": {
                "query": {"kernel": qkv[0][i],
                          "bias": jnp.zeros((n, d), jnp.float32)},
                "key": {"kernel": qkv[1][i],
                        "bias": jnp.zeros((n, d), jnp.float32)},
                "value": {"kernel": qkv[2][i],
                          "bias": jnp.zeros((n, d), jnp.float32)},
                "out": {"kernel": out[i],
                        "bias": jnp.zeros((h,), jnp.float32)}},
            "mlp_in": {"kernel": mlp_in[i],
                       "bias": jnp.zeros((4 * h,), jnp.float32)},
            "mlp_out": {"kernel": mlp_out[i],
                        "bias": jnp.zeros((h,), jnp.float32)}}
    return params


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _rounder(precision):
    """What an operand of a matrix product is rounded to."""
    if precision == "float32":
        return lambda x: x
    dt = {"bfloat16": jnp.bfloat16, "float8": jnp.float8_e4m3fn}[precision]
    return lambda x: x.astype(dt).astype(jnp.float32)


def _mm(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b), precision=_HI)


@jax.jit
def _embed(tok_embed, pos_embed, tokens):
    return tok_embed["embedding"][tokens] + pos_embed[:tokens.shape[0]]


@functools.partial(jax.jit, static_argnames=("precision",))
def _layer(p, x, precision):
    """One pre-LN block over one sequence ``x [S, H]``."""
    rnd = _rounder(precision)
    s = x.shape[0]
    y = _ln(x, p["ln1"])
    a = p["attn"]
    q = _mm("sh,hnd->snd", y, a["query"]["kernel"], rnd) + a["query"]["bias"]
    k = _mm("sh,hnd->snd", y, a["key"]["kernel"], rnd) + a["key"]["bias"]
    v = _mm("sh,hnd->snd", y, a["value"]["kernel"], rnd) + a["value"]["bias"]
    scores = _mm("qnd,knd->nqk", q, k, rnd) * (q.shape[-1] ** -0.5)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm("nqk,knd->qnd", probs, v, rnd)
    x = x + _mm("qnd,ndh->qh", ctx, a["out"]["kernel"], rnd) \
        + a["out"]["bias"]
    y = _ln(x, p["ln2"])
    y = _gelu(_mm("sh,hf->sf", y, p["mlp_in"]["kernel"], rnd)
              + p["mlp_in"]["bias"])
    return x + _mm("sf,fh->sh", y, p["mlp_out"]["kernel"], rnd) \
        + p["mlp_out"]["bias"]


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(ln_f, head, x, precision):
    x = _ln(x, ln_f)
    return _mm("sh,hv->sv", x, head["kernel"], _rounder(precision)) \
        + head["bias"]


def logits(params, tokens, model, precision="float32"):
    """float32 ``[S, vocab]`` next-token logits for one sequence."""
    x = _embed(params["tok_embed"], params["pos_embed"],
               jnp.asarray(tokens, jnp.int32))
    for i in range(model["num_layers"]):
        x = _layer(params["block_%d" % i], x, precision)
    return _head(params["ln_f"], params["head"], x, precision)


@jax.jit
def _gaps(ref_logits, chosen):
    """How far each chosen token's logit lies below the row's best."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=1)[:, 0]
    return best - got


def served_gaps(params, prompt, served, model, pad_to, control=None):
    """For one finished request: the gap of every served token under
    the reference (float32 array, one per served token), and - with
    ``control`` (a precision) - the gap of the token the lower-precision
    forward puts first at the same positions. The sequence is padded to
    ``pad_to`` (causal attention: padding after the end changes
    nothing before it) so that one program serves every request."""
    seq = list(prompt) + list(served)
    n, first = len(seq), len(prompt) - 1
    padded = jnp.asarray(seq + [0] * (pad_to - n), jnp.int32)
    ref = logits(params, padded, model)
    rows = ref[first:first + len(served)]
    out = {"served": _gaps(rows, jnp.asarray(served, jnp.int32))}
    if control is not None:
        low = logits(params, padded, model, control)
        pick = jnp.argmax(low[first:first + len(served)], axis=-1)
        out["control"] = _gaps(rows, pick.astype(jnp.int32))
    return out
