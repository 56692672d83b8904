"""Plain reference for Laguna decoders (``laguna-s-2.1``): the forward
pass of ``poolside/Laguna-S-2.1`` (``model_type: laguna``) in
straightforward ``jax.numpy``, float32 on the bfloat16-rounded weights,
``highest`` matmul precision, no cache, no kernels, no batching, a loop
over the experts with masks. It imports nothing of the program; the
weights are the benchmark's own (:func:`init_params`, from the seed:
bfloat16, normal(0.02), norms 1).

One layer over ``x [T, hidden]``, pre-norm, no bias anywhere::

    h = rmsnorm(x; w_in)
    q = h Wq [T, n, D];  k, v = h Wk, h Wv [T, kv, D]
                 n = num_heads on a full layer, sliding_num_heads on a
                 sliding one; query head i reads K/V head i // (n / kv)
    q, k = rmsnorm over D (QK-norm), then RoPE at p:
           full:    the first rotary_dim = partial_rotary_factor * D dims
                    by YaRN over rotary_dim (rope_theta), cos and sin
                    times attention_factor; the rest passed through
           sliding: all D dims, plain, at sliding_rope_theta
    query at i sees key at j iff j <= i            (a "full" layer)
                             iff 0 <= i - j < W    (a "sliding" layer)
    o_i = softmax(q_i k^T / sqrt(D)) v * sigmoid(h W_gate)[:, i]
    x = x + concat(o) Wo
    h = rmsnorm(x; w_post)
    a dense layer (mlp_only_layers):
        x = x + (silu(h Wg) * (h Wu)) Wd                    width dense_hidden
    every other:
        r = softmax(h Wr) over num_experts;  I = top-k of r
        g_e = routed_scale * r_e / sum_{j in I} r_j
        x = x + sum_{e in I held} g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
              + (silu(h Sg) * (h Su)) Sd                    the shared expert

then ``rmsnorm`` and the untied head.

The chip's share: ``model["first_expert"]``/``model["expert_count"]``
say which routed experts are held; routing is over all of them, and
only the held ones add to the result. The vocabulary is the model's
``vocab`` (a slice of the published one, as the configuration says).

The layers run one small jitted program at a time, the experts inside
it one at a time (``lax.scan``: every held expert over every position,
its weight 0 where it was not chosen) and the queries ``Q_BLOCK`` at a
time against all keys, so 10,240 positions fit beside the weights: the
most it holds is ``[heads, Q_BLOCK, T]`` of scores and one expert in
float32.

Departures from the published description, each the program's and
followed here (the configuration's ``assumed``):

- QK-norm: RMSNorm over each query and key head before RoPE (not a key
  of ``config.json``);
- the per-head gate (``"gating": "per-head"``): ``sigmoid(h W_gate)``,
  ``W_gate [hidden, heads]``, on each head's attention output before
  ``Wo``;
- the shared expert is added ungated;
- a window's edge: ``0 <= i - j < sliding_window``.

``precision`` says what every matrix product multiplies in:
``"float32"`` (the reference), ``"bfloat16"`` (what the configuration
states) or ``"float8"`` (the control, one step below: operands rounded
to ``float8_e4m3fn``). Everything between the products stays float32.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference import mellum_moe
from benchmarks.reference.sdar_moe import _mm, _rms, _rounder

Q_BLOCK = 256   # query rows attended at once
PAD_SHORT = 4096  # a replayed sequence is padded to this, or to the longest
ROWS_STEP = 512  # the positions the head runs on, to a multiple of this


def layer_kind(model, layer):
    kinds = model["layer_types"]
    return kinds[layer % len(kinds)]


def is_dense(model, layer):
    return layer in model["mlp_only_layers"]


def heads_of(model, layer):
    """Query heads of a layer: by its kind."""
    return model["sliding_num_heads"] \
        if layer_kind(model, layer) == "sliding" else model["num_heads"]


def held_experts(model):
    """``(first, count)`` of the routed experts this share holds."""
    return (int(model.get("first_expert", 0)),
            int(model.get("expert_count") or model["num_experts"]))


def rotary_dim(model):
    return int(model["head_dim"] * model["partial_rotary_factor"])


def _normal(key, shape):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)) \
        .astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("dims",))
def _init_layer(key, dims):
    h, n, kv, d, e, held, f, shared, dense = dims
    ks = jax.random.split(key, 12)

    def ones(shape):
        return jnp.ones(shape, jnp.bfloat16)

    def mlp(k, width, lead=()):
        k = jax.random.split(k, 3)
        return {"gate": _normal(k[0], lead + (h, width)),
                "up": _normal(k[1], lead + (h, width)),
                "down": _normal(k[2], lead + (width, h))}

    layer = {
        "ln_in": {"scale": ones((h,))}, "ln_post": {"scale": ones((h,))},
        "attn": {"wq": _normal(ks[0], (h, n * d)),
                 "wk": _normal(ks[1], (h, kv * d)),
                 "wv": _normal(ks[2], (h, kv * d)),
                 "wo": _normal(ks[3], (n * d, h)),
                 "w_gate": _normal(ks[4], (h, n)),
                 "q_norm": ones((d,)), "k_norm": ones((d,))}}
    if dense:
        layer["mlp"] = mlp(ks[5], dense)
    else:
        layer["moe"] = dict(mlp(ks[6], f, (held,)),
                            router=_normal(ks[7], (h, e)),
                            shared=mlp(ks[8], shared))
    return layer


def init_params(key, model):
    """The benchmark's weights from the seed, bfloat16, in the layout
    the program's ``LagunaLM`` reads: attention's projections and gate,
    a dense layer's ``mlp``, a sparse layer's router over all experts,
    its HELD experts and its shared expert. One jitted call per layer,
    so that the float32 draws of one layer are the most it holds beside
    the weights."""
    v, h = model["vocab"], model["hidden"]
    _first, held = held_experts(model)
    ks = jax.random.split(key, model["num_layers"] + 2)
    params = {"embedding": _normal(ks[0], (v, h)),
              "ln_f": {"scale": jnp.ones((h,), jnp.bfloat16)},
              "head": _normal(ks[1], (h, v))}
    for i in range(model["num_layers"]):
        dims = (h, heads_of(model, i), model["num_kv_heads"],
                model["head_dim"], model["num_experts"], held,
                model["moe_hidden"], model["shared_hidden"],
                model["dense_hidden"] if is_dense(model, i) else 0)
        params["layer_%d" % i] = _init_layer(ks[i + 2], dims)
    return params


def _rope_tables(model, kind, t):
    """cos and sin ``[T, turned dims]`` of positions 0..T-1 for a layer
    kind (numpy float64, then float32)."""
    d = model["head_dim"]
    if kind == "full":
        r = rotary_dim(model)
        inv = mellum_moe.yarn_inv_freq(dict(model, head_dim=r))
        factor = model["yarn_attention_factor"]
    else:
        inv = float(model["sliding_rope_theta"]) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)
        factor = 1.0
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1) * factor
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1) * factor
    return jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)


def _turn(x, cos, sin):
    """Rotate-half RoPE of the first ``cos.shape[-1]`` dims of ``x [T,
    heads, D]``; the rest pass through."""
    r = cos.shape[-1]
    rot, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], -1)
    return jnp.concatenate(
        [rot * cos[:, None, :] + half * sin[:, None, :], rest], -1)


def _swiglu(h, w, rnd):
    act = jax.nn.silu(_mm("th,hf->tf", h, w["gate"], rnd)) \
        * _mm("th,hf->tf", h, w["up"], rnd)
    return _mm("tf,fh->th", act, w["down"], rnd)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "top_k", "scale", "first", "eps", "precision"))
def _layer(p, x, cos, sin, window, heads, kv_heads, top_k, scale, first,
           eps, precision):
    """One layer over one sequence ``x [T, H]`` (float32). ``window`` is
    the positions a query sees, its own counted: a number of the call
    and not of the program (a full layer: ``T``, which hides nothing).
    ``T`` is a multiple of ``Q_BLOCK`` or under it."""
    rnd = _rounder(precision)
    t = x.shape[0]
    a = p["attn"]
    d = a["wq"].shape[1] // heads
    group = heads // kv_heads
    h = _rms(x, p["ln_in"]["scale"], eps)
    q = _mm("th,hf->tf", h, a["wq"], rnd).reshape(t, heads, d)
    k = _mm("th,hf->tf", h, a["wk"], rnd).reshape(t, kv_heads, d)
    v = _mm("th,hf->tf", h, a["wv"], rnd).reshape(t, kv_heads, d)
    # query head i is (K/V head i // group, i % group)
    q = _turn(_rms(q, a["q_norm"], eps), cos, sin) \
        .reshape(t, kv_heads, group, d)
    k = _turn(_rms(k, a["k_norm"], eps), cos, sin)
    qb = min(Q_BLOCK, t)
    key_at = jnp.arange(t)

    def attend(i):
        rows = i * qb + jnp.arange(qb)
        scores = _mm("qkgd,tkd->kgqt",
                     lax.dynamic_slice_in_dim(q, i * qb, qb), k, rnd) \
            * (d ** -0.5)
        seen = (key_at[None, :] <= rows[:, None]) \
            & (rows[:, None] - key_at[None, :] < window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return _mm("kgqt,tkd->qkgd", jax.nn.softmax(scores, axis=-1), v,
                   rnd)

    ctx = lax.map(attend, jnp.arange(t // qb)).reshape(t, heads, d)
    gate = jax.nn.sigmoid(_mm("th,hn->tn", h, a["w_gate"], rnd))
    ctx = (ctx * gate[..., None]).reshape(t, heads * d)
    x = x + _mm("tf,fh->th", ctx, a["wo"], rnd)

    h = _rms(x, p["ln_post"]["scale"], eps)
    if "mlp" in p:
        return x + _swiglu(h, p["mlp"], rnd)
    m = p["moe"]
    r = jax.nn.softmax(_mm("th,he->te", h, m["router"], rnd), axis=-1)
    top, idx = lax.top_k(r, top_k)
    gates = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    # weight[e, t]: the gate of expert e for token t, 0 where e is not
    # among the token's top-k; then the held experts' rows alone
    weight = jnp.zeros((r.shape[1], t), jnp.float32).at[
        idx.T, jnp.arange(t)[None, :]].set(gates.T)
    weight = lax.dynamic_slice_in_dim(weight, first, m["gate"].shape[0])

    def one_expert(y, w):
        gate_w, up, down, wt = w
        act = jax.nn.silu(_mm("th,hf->tf", h, gate_w, rnd)) \
            * _mm("th,hf->tf", h, up, rnd)
        return y + wt[:, None] * _mm("tf,fh->th", act, down, rnd), None

    y, _ = lax.scan(one_expert, _swiglu(h, m["shared"], rnd),
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
                  cos, sin, np.int32(window), heads_of(model, layer),
                  model["num_kv_heads"], model["experts_per_tok"],
                  float(model["routed_scale"]), held_experts(model)[0],
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
    else ``pad_to``, the longest there is (rounded up to ``Q_BLOCK``):
    two compiled programs serve every sequence."""
    longest = -(-pad_to // Q_BLOCK) * Q_BLOCK
    return min(PAD_SHORT, longest) if n <= PAD_SHORT else longest


def served_gaps(params, prompt, served, model, pad_to, control=None):
    """For one finished request: the gap of every served token under
    the reference (float32 numpy array, one per served token), and - with
    ``control`` (a precision) - the gap of the token the lower-precision
    forward puts first at the same positions. The sequence is padded
    (:func:`padded_len`; what comes after a position is invisible to
    it) and the head runs on the served positions only, their number
    padded to a multiple of ``ROWS_STEP`` by repeating the last."""
    seq = list(prompt) + list(served)
    n, first = len(seq), len(prompt) - 1
    padded = seq + [0] * (padded_len(n, pad_to) - n)
    rows = list(range(first, first + len(served)))
    rows += rows[-1:] * (-len(rows) % ROWS_STEP)
    chosen = jnp.asarray(list(served) + list(served[-1:])
                         * (len(rows) - len(served)), jnp.int32)
    ref = logits(params, padded, model, rows)
    out = {"served": np.asarray(_gaps(ref, chosen))[:len(served)]}
    if control is not None:
        low = logits(params, padded, model, rows, control)
        out["control"] = np.asarray(_gaps(
            ref, jnp.argmax(low, axis=-1).astype(jnp.int32)))[:len(served)]
    return out
