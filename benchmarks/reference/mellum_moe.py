"""Plain reference for Mellum-MoE decoders (``mellum2-12b-a2.5b``): the
forward pass of ``JetBrains/Mellum2-12B-A2.5B-Instruct`` (``model_type:
mellum``) in straightforward ``jax.numpy``, float32 on the
bfloat16-rounded weights, ``highest`` matmul precision, no cache, no
kernels, no batching, a loop over the experts with masks. It imports
nothing of the program; the weights are the benchmark's own
(``init_params``, from the seed: bfloat16, normal(0.02), norms 1, in
the layout and by the code of ``reference/sdar_moe.py``).

One layer over ``x [T, hidden]``, pre-norm, no bias anywhere::

    h = rmsnorm(x; w_in)
    q, k, v = h Wq, h Wk, h Wv            heads of head_dim, fewer K/V heads
    q, k = rmsnorm over head_dim (QK-norm), then rotate-half RoPE at p
           with the layer kind's frequencies
    query at i sees key at j iff j <= i            (a "full" layer)
                             iff 0 <= i - j < W    (a "sliding" layer)
    x = x + concat(softmax(q k^T / sqrt(head_dim)) v) Wo
    h = rmsnorm(x; w_post)
    r = softmax(h Wr);  I = top-k of r;  g_i = r_i / sum_{j in I} r_j
    x = x + sum_{i in I} g_i (silu(h Wg_i) * (h Wu_i)) Wd_i

then ``rmsnorm`` and the untied head. A sliding layer turns its heads
by ``theta ** (-2i / head_dim)``; a full layer by YaRN as the published
``rope_scaling`` gives it (:func:`yarn_inv_freq`), its cos and sin
multiplied by ``attention_factor``.

The layers run one small jitted program at a time (the same one for
both kinds: the window is an argument of the call), the experts inside
it one at a time (``lax.scan``: every expert over every position, its
gate 0 where it was not chosen) and the queries ``Q_BLOCK`` at a time
against all keys, so 16,896 positions fit beside the weights: the most
it holds is ``[heads, Q_BLOCK, T]`` of scores and one expert in float32.

Departures from the published description, each the program's and
followed here:

- QK-norm: the published ``config.json`` carries Qwen3-MoE's keys and
  that family normalises each query and key head; the key itself is not
  in the file (the configuration's ``assumed``);
- a window's edge: ``0 <= i - j < sliding_window``, the query's own
  position counted;
- YaRN's ``truncate`` is left at its default (the correction dimensions
  are rounded outwards);
- no multi-token prediction head (the configuration's ``not_modelled``).

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

# the benchmark's weights from the seed (bfloat16, normal(0.02), norms
# 1, a jitted call a layer: the same layout) and a product's rounding
# are the other sparse-expert reference's, shared between the two
from benchmarks.reference.sdar_moe import (  # noqa: F401
    _mm, _rms, _rounder, init_params)

Q_BLOCK = 512   # query rows attended at once
PAD_SHORT = 4096  # a replayed sequence is padded to this, or to the longest
ROWS_STEP = 512  # the positions the head runs on, to a multiple of this


def layer_kind(model, layer):
    kinds = model["layer_types"]
    return kinds[layer % len(kinds)]


def yarn_inv_freq(model):
    """The full layers' inverse frequencies ``[head_dim / 2]`` (float64)
    as ``rope_scaling`` of the published config gives them: ``theta **
    (-2i / d)`` for the dimensions that make more than ``beta_fast``
    rotations over the original 8,192 positions, that over ``factor``
    for those that make fewer than ``beta_slow``, and a linear ramp
    between the two correction dimensions, the lower rounded down and
    the upper up (``truncate``)."""
    d, theta = model["head_dim"], float(model["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / d)

    def correction(rotations):
        return d * math.log(model["yarn_original_max_len"]
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(model["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(model["yarn_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / model["yarn_factor"] * ramp + plain * (1.0 - ramp)


def _rope_tables(model, kind, t):
    """cos and sin ``[T, head_dim]`` of positions 0..T-1 for a layer
    kind (numpy float64, then float32)."""
    d = model["head_dim"]
    if kind == "full":
        inv, factor = yarn_inv_freq(model), model["yarn_attention_factor"]
    else:
        inv = float(model["rope_theta"]) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)
        factor = 1.0
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1) * factor
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1) * factor
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def _turn(x, cos, sin):
    """Rotate-half RoPE of ``x [T, heads, D]``."""
    d = x.shape[-1]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None, :] + half * sin[:, None, :]


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "top_k", "eps", "precision"))
def _layer(p, x, cos, sin, window, heads, kv_heads, top_k, eps, precision):
    """One layer over one sequence ``x [T, H]`` (float32). ``window`` is
    the positions a query sees, its own counted: a number of the call
    and not of the program, so that the two kinds of layer (a full
    layer: ``T``, which hides nothing) are ONE compiled program a
    length. ``T`` is a multiple of ``Q_BLOCK`` or under it."""
    rnd = _rounder(precision)
    t = x.shape[0]
    a = p["attn"]
    d = a["wq"].shape[1] // heads
    h = _rms(x, p["ln_in"]["scale"], eps)
    q = _mm("th,hf->tf", h, a["wq"], rnd).reshape(t, heads, d)
    k = _mm("th,hf->tf", h, a["wk"], rnd).reshape(t, kv_heads, d)
    v = _mm("th,hf->tf", h, a["wv"], rnd).reshape(t, kv_heads, d)
    q = _turn(_rms(q, a["q_norm"], eps), cos, sin)
    k = _turn(_rms(k, a["k_norm"], eps), cos, sin)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)  # query head n reads K/V head n // group
    v = jnp.repeat(v, group, axis=1)
    qb = min(Q_BLOCK, t)
    key_at = jnp.arange(t)

    def attend(i):
        rows = i * qb + jnp.arange(qb)
        scores = _mm("qnd,knd->nqk", lax.dynamic_slice_in_dim(q, i * qb, qb),
                     k, rnd) * (d ** -0.5)
        seen = (key_at[None, :] <= rows[:, None]) \
            & (rows[:, None] - key_at[None, :] < window)
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return _mm("nqk,knd->qnd", jax.nn.softmax(scores, axis=-1), v, rnd)

    ctx = lax.map(attend, jnp.arange(t // qb)).reshape(t, heads * d)
    x = x + _mm("tf,fh->th", ctx, a["wo"], rnd)

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

    y, _ = lax.scan(one_expert, jnp.zeros_like(x),
                    (m["gate"], m["up"], m["down"], weight))
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(ln_f, head, x, eps, precision):
    return _mm("sh,hv->sv", _rms(x, ln_f["scale"], eps), head,
               _rounder(precision))


def layer_output(params, x, model, layer=0, precision="float32",
                 tables=None):
    """One layer's output for hidden states ``x [T, H]``: a step of
    :func:`logits`, and the tests' handle on a single layer. ``tables``
    keeps each kind's cos and sin from one layer to the next."""
    kind = layer_kind(model, layer)
    tables = {} if tables is None else tables
    if kind not in tables:
        tables[kind] = _rope_tables(model, kind, x.shape[0])
    cos, sin = tables[kind]
    window = int(model["sliding_window"]) if kind == "sliding" \
        else x.shape[0]
    return _layer(params["layer_%d" % layer], jnp.asarray(x, jnp.float32),
                  cos, sin, np.int32(window), model["num_heads"],
                  model["num_kv_heads"], model["experts_per_tok"],
                  float(model["rms_eps"]), precision)


def logits(params, tokens, model, rows=None, precision="float32"):
    """float32 ``[len(rows), vocab]`` logits of one sequence (every
    row when ``rows`` is None). A sequence longer than ``Q_BLOCK`` has
    to be a multiple of it (:func:`served_gaps` pads)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = params["embedding"][tokens].astype(jnp.float32)
    tables = {}
    for i in range(model["num_layers"]):
        x = layer_output(params, x, model, i, precision, tables)
    if rows is not None:
        x = x[jnp.asarray(rows, jnp.int32)]
    return _head(params["ln_f"], params["head"], x,
                 float(model["rms_eps"]), precision)


@jax.jit
def _gaps(ref_logits, chosen):
    """How far each chosen token's logit lies below the row's best."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=1)[:, 0]
    return best - got


def padded_len(n, pad_to):
    """What a sequence of ``n`` is padded to: ``PAD_SHORT`` where it fits,
    else ``pad_to``, the longest there is (rounded up to ``Q_BLOCK``).
    Two lengths, so two compiled programs serve every sequence: a first
    run at a checkout compiles each for some seven seconds, and a third
    length would cost more than the padding it saves."""
    longest = -(-pad_to // Q_BLOCK) * Q_BLOCK
    return min(PAD_SHORT, longest) if n <= PAD_SHORT else longest


def served_gaps(params, prompt, served, model, pad_to, control=None):
    """For one finished request: the gap of every served token under
    the reference (float32 numpy array, one per served token), and - with
    ``control`` (a precision) - the gap of the token the lower-precision
    forward puts first at the same positions. The sequence is padded
    (:func:`padded_len`; what comes after a position is invisible to
    it) and the head runs on the served positions only, their number
    padded to a multiple of ``ROWS_STEP`` by repeating the last (a
    program a distinct count would compile in every run)."""
    seq = list(prompt) + list(served)
    n, first = len(seq), len(prompt) - 1
    padded = seq + [0] * (padded_len(n, pad_to) - n)
    rows = list(range(first, first + len(served)))
    rows += rows[-1:] * (-len(rows) % ROWS_STEP)
    chosen = jnp.asarray(list(served) + list(served[-1:])
                         * (len(rows) - len(served)), jnp.int32)
    ref = logits(params, padded, model, rows)
    # cut to the served tokens on the host: a slice on the device is a
    # program a distinct count, compiled in every run
    out = {"served": np.asarray(_gaps(ref, chosen))[:len(served)]}
    if control is not None:
        low = logits(params, padded, model, rows, control)
        out["control"] = np.asarray(_gaps(
            ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[:len(served)]
    return out
