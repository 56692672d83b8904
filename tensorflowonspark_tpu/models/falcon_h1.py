"""Falcon-H1: a "parallel hybrid" decoder, attention heads and a Mamba-2
mixer side by side in every layer (``tiiuae/Falcon-H1-34B-Instruct``,
``model_type: falcon_h1``).

Both read the same normed input and both add into the residual. The
multipliers of the published muP scheme sit on every path. With
``h = rmsnorm(x; w_in)`` (eps ``rms_eps``) over the residual stream
``x [T, hidden]``::

    x_0    = embed(tokens) * embedding_multiplier
    # every layer alike (attn_layer_indices null: attention in all)
    h      = rmsnorm(x; w_in)
    # attention: grouped K/V heads, no bias, rotate-half RoPE at rope_theta
    q      = (h * attention_in_multiplier) Wq
    k      = (h * attention_in_multiplier) Wk * key_multiplier
    v      = (h * attention_in_multiplier) Wv
    a      = softmax(q k^T / sqrt(head_dim), causal) v Wo
             * attention_out_multiplier
    # Mamba-2 mixer: ssm_heads heads of ssm_head_dim, ssm_groups groups
    zxbcdt = ((h * ssm_in_multiplier) W_in) * mup
             mup = ssm_multipliers over [z | x | B | C | dt]
    xBC    = silu(causal_depthwise_conv1d(xBC; w_conv [d_conv, ...],
                                          b_conv))      -> x | B | C
    dt     = softplus(dt + dt_bias)     A = -exp(A_log)      (per head)
    for head e (group g = e // (ssm_heads / ssm_groups)), position t:
        S_t   = exp(dt_t,e A_e) S_{t-1} + dt_t,e x_t,e (outer) B_t,g
        y_t,e = S_t C_t,g + D_e x_t,e                  S_{-1} = 0
    y      = y * silu(z); y = rmsnorm over ssm_groups groups (w_gn)
    m      = y W_out * ssm_out_multiplier
    x      = x + a + m
    # MLP, no bias
    h      = rmsnorm(x; w_ff)
    x      = x + ((h Wup) * silu((h Wgate) * mlp_multipliers[0])) Wdown
                 * mlp_multipliers[1]
    logits = rmsnorm(x; w_f) Whead * lm_head_multiplier

Parameters, activations and the K/V pool in ``dtype`` (bfloat16); every
product accumulates in float32; the norms' statistics, softplus and
``exp(dt A)``, the SSM state within a call and the logits are float32.
The state rows kept between calls are at ``dtype``, as transformers'
cache for this family keeps them.
``benchmarks/reference/falcon_h1.py`` is the same in plain float32
``jax.numpy``, its recurrence token by token.

Readings of the published code that ``config.json`` does not spell out
(each also under the configuration's ``assumed``):

- the gated norm is transformers' ``FalconH1RMSNormGated`` with
  ``mamba_norm_before_gate`` false: ``y * silu(z)`` first, then an
  RMSNorm over each of the ``ssm_groups`` groups of ``d_ssm /
  ssm_groups`` channels, then the weight;
- ``key_multiplier`` multiplies k (the projection's output, before
  RoPE, which is linear);
- the five ``ssm_multipliers`` go in the order of ``in_proj``'s
  segments: z, x, B, C, dt.

Two ways in. ``decode=False`` runs a whole sequence with no cache.
``decode=True`` is the serving path (``serving.DecodeEngine``), paged
and token by token, with two kinds of cache a slot: the attention's
K/V in the block pool (``models/decoder.PagedKV``) and, per layer, the
mixer's recurrent STATE: ``ssm_state [state_slots, heads, head_dim,
d_state]`` and ``conv_state
[state_slots, d_conv - 1, conv width]``, a row a slot, addressed by
the ``state_slot`` leaf (the host feeds it: generation.py). A call of
ONE position a row is a decode step: the mixer's step for the live
rows (cursor above 0) is ``ops/ssm_step.py``'s kernel and idle rows'
state is not touched. A call of several is a PREFILL FROM POSITION 0
into one slot: the chunked scan (:func:`ssd_scan`) from a zero state,
positions past ``last`` masked out of it, and the slot's whole row
written. Hence no prefix sharing and no speculation for this family
yet: the engine refuses both by name.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models.decoder import PagedKV
from tensorflowonspark_tpu.models.mellum_moe import band_attention
from tensorflowonspark_tpu.models.sdar_moe import (
    Norm, _matrix, _scale, rmsnorm, rope)

_HI = jax.lax.Precision.HIGHEST


def ssd_scan(x, dt, a, b, c, chunk):
    """The Mamba-2 recurrence over a whole sequence from a zero state, in
    chunks (state-space duality): ``x [T, heads, P]``, ``dt [T, heads]``
    (float32, after softplus; 0 where a position must not touch the
    state), ``a [heads]`` (negative), ``b``/``c`` ``[T, groups, N]``.
    Returns ``(y [T, heads, P], S [heads, P, N])``, both float32, ``y``
    without the skip ``D x`` and ``S`` the state after the last position.

    Inside a chunk of ``chunk`` positions the output is the quadratic
    form ``y_i = sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j``
    (``cum`` the running sum of ``dt A`` in the chunk) plus the state
    the chunk came in with, decayed: ``exp(cum_i) S C_i``; the state
    passes from chunk to chunk by a ``lax.scan``. A position with
    ``dt = 0`` decays nothing and adds nothing."""
    t, nh, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    q = min(int(chunk), t)
    pad = -t % q
    f32 = jnp.float32
    x, dt, b, c = (jnp.pad(v.astype(f32), ((0, pad),) + ((0, 0),)
                           * (v.ndim - 1)) for v in (x, dt, b, c))
    nc = (t + pad) // q
    rep = nh // groups
    xs = x.reshape(nc, q, nh, p)
    dts = dt.reshape(nc, q, nh)
    bs = jnp.repeat(b.reshape(nc, q, groups, n), rep, axis=2)  # by head
    cs = jnp.repeat(c.reshape(nc, q, groups, n), rep, axis=2)
    below = jnp.tril(jnp.ones((q, q), bool))

    def one_chunk(state, inputs):
        xc, dtc, bc, cc = inputs
        cum = jnp.cumsum(dtc * a[None, :], axis=0)               # [Q, nh]
        seg = jnp.where(below[:, :, None],
                        cum[:, None, :] - cum[None, :, :], -jnp.inf)
        cb = jnp.einsum("ihn,jhn->hij", cc, bc, precision=_HI)
        w = jnp.exp(seg).transpose(2, 0, 1) * cb * dtc.T[:, None, :]
        y = jnp.einsum("hij,jhp->ihp", w, xc, precision=_HI)
        y = y + jnp.einsum("ihn,hpn->ihp", cc, state, precision=_HI) \
            * jnp.exp(cum)[:, :, None]
        to_end = jnp.exp(cum[-1][None, :] - cum) * dtc            # [Q, nh]
        state = jnp.exp(cum[-1])[:, None, None] * state + jnp.einsum(
            "jh,jhp,jhn->hpn", to_end, xc, bc, precision=_HI)
        return state, y

    state, y = jax.lax.scan(one_chunk, jnp.zeros((nh, p, n), f32),
                            (xs, dts, bs, cs))
    return y.reshape(nc * q, nh, p)[:t], state


def gated_group_norm(y, z, scale, groups, eps):
    """transformers' ``FalconH1RMSNormGated`` (norm after the gate):
    ``y * silu(z)``, an RMSNorm over each of ``groups`` equal groups of
    the last axis, times ``scale``; float32 in, float32 out."""
    y = y * jax.nn.silu(z)
    shape = y.shape
    g = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + eps)
    return g.reshape(shape) * scale.astype(jnp.float32)


class Mixer(nn.Module):
    """The Mamba-2 mixer of one layer, on ``h`` (the layer's normed
    input). Returns its output times ``ssm_out_multiplier``."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int
    chunk: int
    in_multiplier: float
    multipliers: tuple
    out_multiplier: float
    rms_eps: float
    dtype: jnp.dtype
    state_dtype: jnp.dtype
    decode: bool = False
    state_slots: int = 0

    @nn.compact
    def __call__(self, h, last):
        b, s, width = h.shape
        nh, p, g, n = self.heads, self.head_dim, self.groups, self.state
        d_ssm, gn = nh * p, g * n
        conv_width = d_ssm + 2 * gn
        f32 = jnp.float32
        w_in = _matrix(self, "in_proj", (width, 2 * d_ssm + 2 * gn + nh),
                       self.dtype)
        w_conv = _matrix(self, "conv_w", (self.conv, conv_width), self.dtype)
        b_conv = _matrix(self, "conv_b", (conv_width,), self.dtype)
        dt_bias = _matrix(self, "dt_bias", (nh,), self.dtype)
        a_log = _matrix(self, "A_log", (nh,), self.dtype)
        skip = _scale(self, "D", nh, self.dtype)
        w_gn = _scale(self, "norm", d_ssm, self.dtype)
        w_out = _matrix(self, "out_proj", (d_ssm, width), self.dtype)

        seg = (d_ssm, d_ssm, gn, gn, nh)          # z | x | B | C | dt
        mup = jnp.concatenate([jnp.full((k,), m, f32)
                               for k, m in zip(seg, self.multipliers)])
        zxbcdt = (jnp.dot(h * self.in_multiplier, w_in,
                          preferred_element_type=f32) * mup) \
            .astype(self.dtype)
        z, xbc, dt = jnp.split(zxbcdt, [d_ssm, d_ssm + conv_width], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        a = -jnp.exp(a_log.astype(f32))
        wc = w_conv.astype(f32)

        def activate(conv_out):        # [..., conv_width] f32 -> x, B, C
            out = jax.nn.silu(conv_out + b_conv.astype(f32)) \
                .astype(self.dtype)
            xo, bo, co = jnp.split(out, [d_ssm, d_ssm + gn], axis=-1)
            return (xo.reshape(xo.shape[:-1] + (nh, p)),
                    bo.reshape(bo.shape[:-1] + (g, n)),
                    co.reshape(co.shape[:-1] + (g, n)))

        if self.decode:
            if self.state_slots < 1:
                raise ValueError(
                    "FalconH1LM decodes with a state row a slot "
                    "(state_slots >= 1; serving.DecodeEngine sets it)")
            initialized = self.has_variable("cache", "ssm_state")
            ssm = self.variable(
                "cache", "ssm_state", jnp.zeros,
                (self.state_slots, nh, p, n), self.state_dtype)
            conv = self.variable(
                "cache", "conv_state", jnp.zeros,
                (self.state_slots, self.conv - 1, conv_width), self.dtype)
            rows = self.variable("cache", "state_slot",
                                 lambda: jnp.zeros((b,), jnp.int32))
            cursor = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((b,), jnp.int32))
            if not initialized:
                return jnp.zeros_like(h)   # creation pass: shapes only
            if s == 1:
                # a decode step: the live rows (cursor above 0) through
                # the kernel; an idle row's state is not touched
                import importlib

                ops = importlib.import_module(
                    "tensorflowonspark_tpu.ops.ssm_step")
                new = xbc[:, 0]
                window = jnp.concatenate(
                    [conv.value[rows.value].astype(f32),
                     new[:, None].astype(f32)], axis=1)
                xo, bo, co = activate(jnp.einsum("skc,kc->sc", window, wc))
                live = cursor.value > 0
                y, ssm.value, conv.value = ops.ssm_step(
                    ssm.value, conv.value, xo, dt[:, 0], a, bo, co,
                    skip.astype(f32), new, rows.value, live)
                return self._out(y[:, None], z, w_gn, w_out)
        # a whole sequence, or a prefill from position 0: positions past
        # ``last`` (a bucket's pads) carry no input and no dt
        if last is not None:
            real = jnp.arange(s)[None, :] <= last[:, None]
            xbc = jnp.where(real[..., None], xbc, 0)
            dt = jnp.where(real[..., None], dt, 0.0)
        padded = jnp.pad(xbc, ((0, 0), (self.conv - 1, 0), (0, 0)))
        conv_out = sum(padded[:, k:k + s].astype(f32) * wc[k]
                       for k in range(self.conv))
        xo, bo, co = activate(conv_out)
        if last is not None:
            xo = jnp.where(real[..., None, None], xo, 0)
        y, final = jax.vmap(ssd_scan, in_axes=(0, 0, None, 0, 0, None))(
            xo, dt, a, bo, co, self.chunk)
        y = y + skip.astype(f32)[:, None] * xo.astype(f32)
        if self.decode:
            # the slot's whole row: the state after the last real
            # position, the convolution's last d_conv - 1 real inputs
            end = s - 1 if last is None else last
            at = rows.value
            ssm.value = ssm.value.at[at].set(final.astype(ssm.value.dtype))
            tails = jax.vmap(
                lambda xp, e: jax.lax.dynamic_slice_in_dim(
                    xp, e + 1, self.conv - 1, axis=0))(
                padded, jnp.broadcast_to(end, (b,)))
            conv.value = conv.value.at[at].set(tails.astype(conv.value.dtype))
        return self._out(y, z, w_gn, w_out)

    def _out(self, y, z, w_gn, w_out):
        b, s = y.shape[:2]
        y = gated_group_norm(y.reshape(b, s, -1), z.astype(jnp.float32),
                             w_gn, self.groups, self.rms_eps)
        return (jnp.dot(y.astype(self.dtype), w_out,
                        preferred_element_type=jnp.float32)
                * self.out_multiplier).astype(self.dtype)


class Attention(nn.Module):
    """Grouped-query attention with the muP multipliers: ``x`` is the
    layer's normed input times ``attention_in_multiplier``."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    key_multiplier: float
    out_multiplier: float
    dtype: jnp.dtype
    prefill_chunk: int = 512
    decode: bool = False
    kv_block_size: int = 0
    kv_blocks: int = 0

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim

        def project(name, heads, times=1.0):
            w = _matrix(self, name, (h, heads * d), self.dtype)
            return (jnp.dot(x, w, preferred_element_type=jnp.float32)
                    * times).astype(self.dtype).reshape(b, s, heads, d)

        q, v = project("wq", n), project("wv", kv)
        k = project("wk", kv, self.key_multiplier)
        wo = _matrix(self, "wo", (n * d, h), self.dtype)
        if self.decode:
            pool = PagedKV(self, b, s, kv, d, self.dtype,
                           self.kv_block_size, self.kv_blocks)
            if not pool.initialized:
                ctx = q  # cache creation pass (full-length dummy): shapes
            else:
                pos = pool.positions(s)
                ctx = pool.attend(rope(q, pos, self.rope_theta),
                                  rope(k, pos, self.rope_theta), v, pos)
        else:
            pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            ctx = band_attention(rope(q, pos, self.rope_theta),
                                 rope(k, pos, self.rope_theta), v, None,
                                 self.prefill_chunk)
        return (jnp.dot(ctx.reshape(b, s, n * d), wo,
                        preferred_element_type=jnp.float32)
                * self.out_multiplier).astype(self.dtype)


class FalconH1Layer(nn.Module):
    attn: dict
    mixer: dict
    mlp_hidden: int
    attention_in_multiplier: float
    mlp_multipliers: tuple
    rms_eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, last):
        h = Norm(self.rms_eps, self.dtype, name="ln_in")(x)
        a = Attention(name="attn", **self.attn)(
            h * jnp.asarray(self.attention_in_multiplier, self.dtype))
        m = Mixer(name="mixer", **self.mixer)(h, last)
        x = x + a + m
        h = Norm(self.rms_eps, self.dtype, name="ln_ff")(x)
        width = x.shape[-1]
        f32 = jnp.float32
        gate = jnp.dot(h, _matrix(self, "gate", (width, self.mlp_hidden),
                                  self.dtype), preferred_element_type=f32)
        up = jnp.dot(h, _matrix(self, "up", (width, self.mlp_hidden),
                                self.dtype), preferred_element_type=f32)
        act = (up * jax.nn.silu(gate * self.mlp_multipliers[0])) \
            .astype(self.dtype)
        down = jnp.dot(act, _matrix(self, "down", (self.mlp_hidden, width),
                                    self.dtype), preferred_element_type=f32)
        return x + (down * self.mlp_multipliers[1]).astype(self.dtype)


class FalconH1LM(nn.Module):
    """The whole decoder. ``decode=True`` instances carry the paged K/V
    and the state rows (init them with ``generation.init_cache``).
    ``last [B]`` gives, per row, the ONE position whose logits are
    wanted (a prefill samples from its last real token, and the mixer
    leaves what comes after it out of the state); None gives every
    position's."""

    vocab: int
    hidden: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 2
    mlp_hidden: int = 128
    ssm_heads: int = 4
    ssm_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    chunk: int = 128
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    max_len: int = 512
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 0.011048543456039804
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    #: over in_proj's segments z, x, B, C, dt
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    #: the gate's and the down projection's
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    dtype: jnp.dtype = jnp.bfloat16
    #: query positions a step of band_attention (decode=False)
    prefill_chunk: int = 512
    decode: bool = False
    #: paged KV and the state rows (decode=True only):
    #: serving.DecodeEngine clones the model with these set
    kv_block_size: int = 0
    kv_blocks: int = 0
    state_slots: int = 0
    #: a multi-position call is a prefill whose head runs on ``last``
    takes_last = True

    @property
    def cache_kinds(self):
        """What serving.DecodeEngine reads to size its caches: beside the
        paged K/V, a state row a slot in every layer (paging.StateKind)."""
        return {"state": self.num_layers}

    @nn.compact
    def __call__(self, tokens, last=None):
        if self.decode and not self.kv_block_size:
            raise ValueError(
                "FalconH1LM decodes through the paged cache only "
                "(kv_block_size > 0); decode=False runs a whole sequence")
        embed = _matrix(self, "embedding", (self.vocab, self.hidden),
                        self.dtype)
        x = (embed[tokens].astype(jnp.float32)
             * self.embedding_multiplier).astype(self.dtype)
        attn = dict(num_heads=self.num_heads,
                    num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, rope_theta=self.rope_theta,
                    key_multiplier=self.key_multiplier,
                    out_multiplier=self.attention_out_multiplier,
                    dtype=self.dtype, prefill_chunk=self.prefill_chunk,
                    decode=self.decode, kv_block_size=self.kv_block_size,
                    kv_blocks=self.kv_blocks)
        mixer = dict(heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                     groups=self.ssm_groups, state=self.ssm_state,
                     conv=self.conv_kernel, chunk=self.chunk,
                     in_multiplier=self.ssm_in_multiplier,
                     multipliers=tuple(self.ssm_multipliers),
                     out_multiplier=self.ssm_out_multiplier,
                     rms_eps=self.rms_eps, dtype=self.dtype,
                     state_dtype=self.dtype, decode=self.decode,
                     state_slots=self.state_slots)
        for i in range(self.num_layers):
            x = FalconH1Layer(attn, mixer, self.mlp_hidden,
                              self.attention_in_multiplier,
                              tuple(self.mlp_multipliers), self.rms_eps,
                              self.dtype, name="layer_%d" % i)(x, last)
        x = Norm(self.rms_eps, self.dtype, name="ln_f")(x)
        if last is not None:
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        w = _matrix(self, "head", (self.hidden, self.vocab), self.dtype)
        return jnp.dot(x, w, preferred_element_type=jnp.float32) \
            * self.lm_head_multiplier
