"""Plain reference for Falcon-H1 decoders (``falcon-h1-34b``): the forward
pass of ``tiiuae/Falcon-H1-34B-Instruct`` (``model_type: falcon_h1``) in
straightforward ``jax.numpy``, float32 on the bfloat16-rounded weights,
``highest`` matmul precision, no cache, no kernels, no batching. It
imports nothing of the program; the weights are the benchmark's own
(:func:`init_params`, from the seed).

One layer over ``x [T, hidden]``, attention and the Mamba-2 mixer in
parallel on the same normed input, no bias but the convolution's::

    h   = rmsnorm(x; w_in)                                  (eps 1e-5)
    q   = (h am) Wq; k = (h am) Wk * key_multiplier; v = (h am) Wv
    q, k = rotate-half RoPE at p (theta rope_theta)
    a   = softmax(q k^T / sqrt(head_dim), causal) v Wo * attention_out
    zxbcdt = ((h * ssm_in) W_in) * [z x B C dt multipliers]
    xBC = silu(b + sum_k w_k xBC_{t - 3 + k})    a direct causal sum
    dt  = softplus(dt + dt_bias); A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t + D x_t
          ONE POSITION AT A TIME (lax.scan over t), S_{-1} = 0
    y   = rmsnorm over groups(y * silu(z)) * w_gn
    m   = y W_out * ssm_out
    x   = x + a + m
    x   = x + ((h' Wup) * silu((h' Wgate) g)) Wdown * d,  h' = rmsnorm(x; w_ff)

then ``rmsnorm``, the untied head and ``lm_head_multiplier``; ``x_0`` is
the embedding times ``embedding_multiplier``. Nothing here shares a form
with the program's chunked scan (models/falcon_h1.ssd_scan) or its
step kernel (ops/ssm_step.py): the recurrence is the definition,
position by position, and the convolution a sum of shifted inputs.

Computed in blocks so that it fits beside the bfloat16 weights: a layer
is ONE jitted program whose weights are upcast inside it (a layer at a
time), the queries ``Q_BLOCK`` at a time against all keys, and the
head over the vocabulary in column chunks (:func:`_head_scan`: the best
logit and the logits of the tokens asked about, never the whole row).

Departures from the published description, each the program's and
followed here (the configuration's ``assumed``): the gated norm is
transformers' ``FalconH1RMSNormGated`` with the norm after the gate;
``key_multiplier`` multiplies k; the five ``ssm_multipliers`` go in the
order z, x, B, C, dt.

``precision`` says what every matrix product multiplies in:
``"float32"`` (the reference), ``"bfloat16"`` or ``"float8"`` (the
control, one step below the bfloat16 the configuration states: operands
rounded to ``float8_e4m3fn``). Everything between the products, the
recurrence among it, stays float32.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference.sdar_moe import _mm, _rms, _rounder

Q_BLOCK = 512     # query rows attended at once
PAD_SHORT = 512   # a replayed sequence is padded to this, or to the longest
ROWS_STEP = 512   # the positions the head runs on, to a multiple of this
HEAD_COLUMNS = 16384  # the most vocabulary columns of the head at once


def _widths(model):
    nh, p = model["ssm_heads"], model["ssm_head_dim"]
    gn = model["ssm_groups"] * model["ssm_state"]
    return nh * p, gn, nh * p + 2 * gn


@functools.partial(jax.jit, static_argnames=("dims",))
def _init_layer(key, dims):
    h, n, kv, d, f, nh, d_ssm, gn, conv, k = dims
    ks = jax.random.split(key, 13)

    def normal(i, shape):
        return (0.02 * jax.random.normal(ks[i], shape, jnp.float32)) \
            .astype(jnp.bfloat16)

    def ones(shape):
        return jnp.ones(shape, jnp.bfloat16)

    def conv_default(i, shape):
        # torch's Conv1d default, which Mamba-2 leaves the depthwise
        # convolution at: uniform in +-1 / sqrt(fan_in), fan_in = d_conv
        bound = 1.0 / math.sqrt(k)
        return jax.random.uniform(ks[i], shape, jnp.float32, -bound,
                                  bound).astype(jnp.bfloat16)

    # A in [1, 16]; dt log-uniform in [0.001, 0.1], stored through
    # dt_bias as its inverse softplus (Mamba-2's initialisation)
    a = jax.random.uniform(ks[8], (nh,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[9], (nh,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return {
        "ln_in": {"scale": ones((h,))}, "ln_ff": {"scale": ones((h,))},
        "attn": {"wq": normal(0, (h, n * d)), "wk": normal(1, (h, kv * d)),
                 "wv": normal(2, (h, kv * d)), "wo": normal(3, (n * d, h))},
        "mixer": {"in_proj": normal(4, (h, 2 * d_ssm + 2 * gn + nh)),
                  "conv_w": conv_default(5, (k, conv)),
                  "conv_b": conv_default(6, (conv,)),
                  "dt_bias": (dt + jnp.log(-jnp.expm1(-dt)))
                  .astype(jnp.bfloat16),
                  "A_log": jnp.log(a).astype(jnp.bfloat16),
                  "D": ones((nh,)), "norm": ones((d_ssm,)),
                  "out_proj": normal(7, (d_ssm, h))},
        "gate": normal(10, (h, f)), "up": normal(11, (h, f)),
        "down": normal(12, (f, h))}


@functools.partial(jax.jit, static_argnames=("shape",))
def _init_matrix(key, shape):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)) \
        .astype(jnp.bfloat16)


def init_params(key, model):
    """The benchmark's weights from the seed, bfloat16, in the layout the
    program's ``FalconH1LM`` reads; one jitted call per layer, so that
    the float32 draws of one layer are the most it holds beside the
    weights. Normal(0.02) but for the norms (1) and the mixer's own,
    which are Mamba-2's: ``D`` 1, ``A_log`` (``A`` uniform in [1, 16]),
    ``dt_bias`` (``dt`` log-uniform in [0.001, 0.1] through the inverse
    softplus) and the convolution's weights and bias at torch's Conv1d
    default, uniform in +-1/sqrt(d_conv). (At normal(0.02) the
    convolution's outputs would be its bias, a hundredth, and the
    state's part of the mixer's output a few thousandths of the skip
    ``D x``: no fault of the state would reach a served token.)"""
    d_ssm, gn, conv = _widths(model)
    h, v = model["hidden"], model["vocab"]
    dims = (h, model["num_heads"], model["num_kv_heads"], model["head_dim"],
            model["mlp_hidden"], model["ssm_heads"], d_ssm, gn, conv,
            model["conv_kernel"])
    ks = jax.random.split(key, model["num_layers"] + 2)
    params = {"embedding": _init_matrix(ks[0], (v, h)),
              "ln_f": {"scale": jnp.ones((h,), jnp.bfloat16)},
              "head": _init_matrix(ks[1], (h, v))}
    for i in range(model["num_layers"]):
        params["layer_%d" % i] = _init_layer(ks[i + 2], dims)
    return params


def _rope_tables(model, t):
    d = model["head_dim"]
    inv = float(model["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def _turn(x, cos, sin):
    d = x.shape[-1]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None, :] + half * sin[:, None, :]


def _consts(model):
    """The scalars a layer program is compiled with (hashable)."""
    return tuple(float(model[k]) for k in (
        "attention_in_multiplier", "key_multiplier",
        "attention_out_multiplier", "ssm_in_multiplier",
        "ssm_out_multiplier")) \
        + tuple(float(m) for m in model["ssm_multipliers"]) \
        + tuple(float(m) for m in model["mlp_multipliers"])


def _mixer(p, h, shape, consts, eps, rnd):
    """The mixer of one layer on its normed input ``h [T, H]``: ``(m [T,
    H] before ssm_out_multiplier, S [heads, P, N] after the last
    position, the convolution's last d_conv - 1 inputs)``."""
    nh, pd, groups, n, k = shape
    _, _, _, ssm_in, _ = consts[:5]
    mult = consts[5:10]
    t = h.shape[0]
    d_ssm, gn = nh * pd, groups * n
    seg = (d_ssm, d_ssm, gn, gn, nh)
    mup = jnp.concatenate([jnp.full((w,), m, jnp.float32)
                           for w, m in zip(seg, mult)])
    zxbcdt = _mm("th,hf->tf", h * ssm_in, p["in_proj"], rnd) * mup
    z, xbc, dt = jnp.split(zxbcdt, [d_ssm, 2 * d_ssm + 2 * gn], axis=-1)
    w = p["conv_w"].astype(jnp.float32)
    # out_t = b + sum_k w_k in_{t - (k_size - 1) + k}, zero before 0
    before = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = p["conv_b"].astype(jnp.float32) + sum(
        w[j] * before[j:j + t] for j in range(k))
    act = jax.nn.silu(conv)
    x, b, c = jnp.split(act, [d_ssm, d_ssm + gn], axis=-1)
    x = x.reshape(t, nh, pd)
    rep = nh // groups
    b = jnp.repeat(b.reshape(t, groups, n), rep, axis=1)
    c = jnp.repeat(c.reshape(t, groups, n), rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    skip = p["D"].astype(jnp.float32)

    def one_position(state, inputs):
        xt, dtt, bt, ct = inputs
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return state, jnp.sum(state * ct[:, None, :], axis=-1) \
            + skip[:, None] * xt

    state, y = lax.scan(one_position, jnp.zeros((nh, pd, n), jnp.float32),
                        (x, dt, b, c))
    y = y.reshape(t, d_ssm) * jax.nn.silu(z)
    g = y.reshape(t, groups, d_ssm // groups)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    y = g.reshape(t, d_ssm) * p["norm"].astype(jnp.float32)
    return _mm("tf,fh->th", y, p["out_proj"], rnd), state, before[t:]


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "shape", "consts", "eps", "precision"))
def _layer(p, x, cos, sin, heads, kv_heads, shape, consts, eps, precision):
    """One layer over one sequence ``x [T, H]`` (float32): ``(x', m, S,
    conv)`` with the mixer's parts as :func:`_mixer` gives them."""
    rnd = _rounder(precision)
    t = x.shape[0]
    am, km, ao, _, so = consts[:5]
    gate_m, down_m = consts[10:12]
    a = p["attn"]
    d = a["wq"].shape[1] // heads
    h = _rms(x, p["ln_in"]["scale"], eps)
    ha = h * am
    q = _mm("th,hf->tf", ha, a["wq"], rnd).reshape(t, heads, d)
    k = (_mm("th,hf->tf", ha, a["wk"], rnd) * km).reshape(t, kv_heads, d)
    v = _mm("th,hf->tf", ha, a["wv"], rnd).reshape(t, kv_heads, d)
    q, k = _turn(q, cos, sin), _turn(k, cos, sin)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)  # query head n reads K/V head n // group
    v = jnp.repeat(v, group, axis=1)
    qb = min(Q_BLOCK, t)
    qb = qb if t % qb == 0 else t   # else all queries at once
    key_at = jnp.arange(t)

    def attend(i):
        rows = i * qb + jnp.arange(qb)
        scores = _mm("qnd,knd->nqk", lax.dynamic_slice_in_dim(q, i * qb, qb),
                     k, rnd) * (d ** -0.5)
        scores = jnp.where((key_at[None, :] <= rows[:, None])[None], scores,
                           -jnp.inf)
        return _mm("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v, rnd)

    ctx = lax.map(attend, jnp.arange(t // qb)).reshape(t, heads * d)
    attn = _mm("tf,fh->th", ctx, a["wo"], rnd) * ao
    m, state, conv = _mixer(p["mixer"], h, shape, consts, eps, rnd)
    x = x + attn + m * so
    h = _rms(x, p["ln_ff"]["scale"], eps)
    act = _mm("th,hf->tf", h, p["up"], rnd) \
        * jax.nn.silu(_mm("th,hf->tf", h, p["gate"], rnd) * gate_m)
    return x + _mm("tf,fh->th", act, p["down"], rnd) * down_m, m, state, conv


def _shape(model):
    return (model["ssm_heads"], model["ssm_head_dim"], model["ssm_groups"],
            model["ssm_state"], model["conv_kernel"])


def layer_output(params, x, model, layer=0, precision="float32",
                 tables=None):
    """One layer for hidden states ``x [T, H]``: ``(x', m, S, conv)``,
    ``m`` the mixer's output before ``ssm_out_multiplier``, ``S`` and
    ``conv`` its state after the last position (the tests' handle on
    one layer). A ``T`` that is no multiple of ``Q_BLOCK`` attends all
    its queries at once."""
    t = x.shape[0]
    if tables is None or tables[0].shape[0] != t:
        tables = _rope_tables(model, t)
    return _layer(params["layer_%d" % layer], jnp.asarray(x, jnp.float32),
                  tables[0], tables[1], model["num_heads"],
                  model["num_kv_heads"], _shape(model), _consts(model),
                  float(model["rms_eps"]), precision)


def hidden(params, tokens, model, precision="float32"):
    """The final residual stream ``[T, H]`` of one sequence, and per
    layer the mixer's ``(m, S, conv)``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embedding"][tokens].astype(jnp.float32) \
        * float(model["embedding_multiplier"])
    tables = _rope_tables(model, x.shape[0])
    mixers = []
    for i in range(model["num_layers"]):
        x, m, state, conv = layer_output(params, x, model, i, precision,
                                         tables)
        mixers.append((m, state, conv))
    return x, mixers


def _columns(vocab):
    """Vocabulary columns of the head at once: the fewest equal chunks
    of at most ``HEAD_COLUMNS``."""
    chunks = next(c for c in range(1, vocab + 1)
                  if vocab % c == 0 and vocab // c <= HEAD_COLUMNS)
    return vocab // chunks


@functools.partial(jax.jit, static_argnames=("eps", "scale", "precision",
                                             "cols"))
def _head_scan(ln_f, head, x, asked, eps, scale, precision, cols):
    """Over the vocabulary ``cols`` columns at a time, of the rows ``x
    [R, H]``: the best logit and its token ``[R]``, and the logits of
    the tokens ``asked [A, R]`` (``[A, R]``). float32; the head's
    columns are upcast a chunk at a time."""
    rnd = _rounder(precision)
    hx = _rms(x, ln_f["scale"], eps)
    rows = x.shape[0]

    def chunk(carry, i):
        best, arg, got = carry
        w = lax.dynamic_slice_in_dim(head, i * cols, cols, axis=1)
        z = _mm("rh,hv->rv", hx, w, rnd) * scale
        top = jnp.max(z, axis=-1)
        at = jnp.argmax(z, axis=-1).astype(jnp.int32) + i * cols
        arg = jnp.where(top > best, at, arg)
        best = jnp.maximum(best, top)
        local = asked - i * cols
        inside = (local >= 0) & (local < cols)
        here = jnp.take_along_axis(
            z[None], jnp.clip(local, 0, cols - 1)[..., None], axis=-1)[..., 0]
        return (best, arg, jnp.where(inside, here, got)), None

    init = (jnp.full((rows,), -jnp.inf), jnp.zeros((rows,), jnp.int32),
            jnp.zeros(asked.shape, jnp.float32))
    (best, arg, got), _ = lax.scan(chunk, init,
                                   jnp.arange(head.shape[1] // cols))
    return best, arg, got


def head_stats(params, x, model, asked, precision="float32"):
    """:func:`_head_scan` with the model's numbers."""
    return _head_scan(params["ln_f"], params["head"],
                      jnp.asarray(x, jnp.float32),
                      jnp.asarray(asked, jnp.int32), float(model["rms_eps"]),
                      float(model["lm_head_multiplier"]), precision,
                      _columns(model["vocab"]))


def logits(params, tokens, model, rows=None, precision="float32"):
    """float32 ``[len(rows), vocab]`` logits of one sequence (every row
    when ``rows`` is None), the head whole: the tests' (small) sizes."""
    x, _ = hidden(params, tokens, model, precision)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    hx = _rms(x, params["ln_f"]["scale"], float(model["rms_eps"]))
    return _mm("sh,hv->sv", hx, params["head"], _rounder(precision)) \
        * float(model["lm_head_multiplier"])


def padded_len(n, pad_to):
    """What a sequence of ``n`` is padded to: ``PAD_SHORT`` where it
    fits, else ``pad_to`` rounded up to ``Q_BLOCK``: two lengths, two
    compiled layer programs for every sequence."""
    longest = -(-pad_to // Q_BLOCK) * Q_BLOCK
    return min(PAD_SHORT, longest) if n <= PAD_SHORT else longest


def served_gaps(params, prompt, served, model, pad_to, control=None):
    """For one finished request: the gap of every served token under the
    reference (how far its logit lies below the row's best; float32
    numpy, one per served token), and - with ``control`` (a precision) -
    the gap of the token the lower-precision forward puts first at the
    same positions. The sequence is padded (:func:`padded_len`; what
    comes after a position is invisible to it) and the head runs on the
    served positions only, their number padded to a multiple of
    ``ROWS_STEP`` by repeating the last."""
    seq = list(prompt) + list(served)
    n, first = len(seq), len(prompt) - 1
    padded = seq + [0] * (padded_len(n, pad_to) - n)
    rows = list(range(first, first + len(served)))
    rows += rows[-1:] * (-len(rows) % ROWS_STEP)
    chosen = list(served) + list(served[-1:]) * (len(rows) - len(served))
    x, _ = hidden(params, padded, model)
    x = x[jnp.asarray(rows, jnp.int32)]
    if control is None:
        best, _, got = head_stats(params, x, model, [chosen])
        gaps = np.asarray(best - got[0])
        return {"served": gaps[:len(served)]}
    low, _ = hidden(params, padded, model, control)
    _, picked, _ = head_stats(params, low[jnp.asarray(rows, jnp.int32)],
                              model, [chosen], control)
    best, _, got = head_stats(params, x, model, [chosen, picked])
    gaps = np.asarray(best[None] - got)
    return {"served": gaps[0][:len(served)],
            "control": gaps[1][:len(served)]}
