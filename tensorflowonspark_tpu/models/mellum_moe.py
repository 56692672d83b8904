"""Mellum-MoE: a sparse-expert decoder whose attention layers are of two
kinds (``JetBrains/Mellum2-12B-A2.5B-Instruct``, ``model_type: mellum``).

``layer_types`` says, layer by layer, ``"sliding"`` or ``"full"``. A
full layer is causal. A sliding layer's query at ``i`` sees key ``j``
iff ``0 <= i - j < sliding_window``. The two kinds also turn their heads
differently: a sliding layer by plain rotate-half RoPE at ``rope_theta``,
a full layer by YaRN (:func:`yarn_inv_freq`; cos and sin times
``yarn_attention_factor``). Everything else is the family of
``models/sdar_moe.py`` and shares its pieces (``rmsnorm``, ``rope``,
``Norm``, ``SparseExperts`` over ``ops/expert_gmm.py``): pre-norm,
grouped K/V heads, QK-norm, no bias, a sparse gated feed-forward in
every layer, final RMSNorm and an untied head; parameters, activations
and the K/V pools in ``dtype`` (bfloat16), every product accumulating
in float32, the router's softmax, the norms' statistics and the logits
in float32. ``benchmarks/reference/mellum_moe.py`` is the same in plain
float32 ``jax.numpy``.

Two ways in. ``decode=False`` runs a whole sequence with no cache.
``decode=True`` is the serving path, PAGED only and token by token
(``serving.DecodeEngine`` with one step in flight). The two kinds of
layer are two kinds of CACHE (``models/decoder.PagedKV``): a full layer
keeps every position, in a pool of ``kv_blocks`` rows behind
``block_table``; a sliding layer keeps the window, in a pool of
``kv_window_blocks`` rows behind ``window_table``, whose entries behind
the window the host gives back. A call of ONE position a row is a
decode step and attends through the tables. A call of several is a
PREFILL FROM POSITION 0: a prompt longer than the window cannot go
through a sliding layer's table (its pool holds a window a slot), so
every layer of a prefill attends the call's own K and V
(:func:`band_attention`) and only WRITES the cache, where whatever the
table does not map lands in scratch. Hence no prefix sharing and no
speculation for this family yet: the engine refuses both by name.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu.models.decoder import PagedKV
from tensorflowonspark_tpu.models.sdar_moe import (
    Norm, SparseExperts, _matrix, _scale, rmsnorm, rope)


def yarn_inv_freq(head_dim, theta, factor, original_max_len, beta_fast,
                  beta_slow, truncate=True):
    """YaRN's inverse frequencies ``[head_dim / 2]`` (float64 numpy):
    dimension ``i`` turns at ``theta ** (-2i / head_dim)`` where it
    makes more than ``beta_fast`` rotations over ``original_max_len``
    positions (left as it is: extrapolated), at ``1 / factor`` of that
    where it makes fewer than ``beta_slow`` (interpolated), and at a
    linear blend between the two correction dimensions (rounded
    outwards when ``truncate``)."""
    half = head_dim // 2
    extrapolated = theta ** (-np.arange(half, dtype=np.float64) * 2
                             / head_dim)

    def correction_dim(rotations):
        return head_dim * math.log(
            original_max_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def band_attention(q, k, v, window=None, chunk=512):
    """Attention of a whole call over its OWN keys and values, from
    position 0: ``q [B, S, heads, D]``, ``k``/``v`` ``[B, S, kv_heads,
    D]`` -> ``[B, S, heads, D]``. Query ``i`` sees key ``j`` iff ``j <=
    i`` and, with a ``window``, ``i - j < window``. Plain ``lax``: the
    queries a ``chunk`` at a time, each folding the key chunks its band
    reaches (all up to the diagonal without a window, the last
    ``ceil((window - 1) / chunk) + 1`` with one) into an online softmax,
    so the scores held at once are ``[heads, chunk, chunk]`` whatever
    ``S``, and nothing outside the band is multiplied."""
    b, s, n, d = q.shape
    kv = k.shape[2]
    g = n // kv
    c = min(int(chunk), s)
    pad = -s % c
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    nq = (s + pad) // c
    back = nq - 1 if window is None else min(nq - 1, -(-(window - 1) // c))
    qc = q.reshape(b, nq, c, kv, g, d)
    scale = d ** -0.5
    at = jnp.arange(c)

    def one_chunk(i):
        qi = jax.lax.dynamic_index_in_dim(qc, i, axis=1, keepdims=False)
        qpos = i * c + at

        def fold(j, carry):
            m, l, acc = carry
            kj = jax.lax.dynamic_slice_in_dim(k, j * c, c, axis=1)
            vj = jax.lax.dynamic_slice_in_dim(v, j * c, c, axis=1)
            sc = jnp.einsum("bqkgd,bckd->bkgqc", qi, kj,
                            preferred_element_type=jnp.float32) * scale
            kpos = j * c + at
            seen = kpos[None, :] <= qpos[:, None]
            if window is not None:
                seen &= kpos[None, :] > qpos[:, None] - window
            sc = jnp.where(seen, sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.where(jnp.isneginf(sc), 0.0,
                          jnp.exp(sc - safe_m[..., None]))
            corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
            return (m_new, l * corr + jnp.sum(p, axis=-1),
                    acc * corr[..., None] + jnp.einsum(
                        "bkgqc,bckd->bkgqd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32))

        m, l, acc = jax.lax.fori_loop(
            jnp.maximum(i - back, 0), i + 1, fold,
            (jnp.full((b, kv, g, c), -jnp.inf, jnp.float32),
             jnp.zeros((b, kv, g, c), jnp.float32),
             jnp.zeros((b, kv, g, c, d), jnp.float32)))
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return out.astype(q.dtype).transpose(0, 3, 1, 2, 4) \
            .reshape(b, c, n, d)

    out = jax.lax.map(one_chunk, jnp.arange(nq))       # [nq, B, c, N, D]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, nq * c, n, d)[:, :s]


class KindAttention(nn.Module):
    """Grouped-query attention of one layer kind: ``window`` 0 is a full
    layer, else the positions a query sees; ``inv_freq``/``rope_factor``
    are the kind's RoPE (None: plain, at ``rope_theta``)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int
    rope_theta: float
    inv_freq: tuple
    rope_factor: float
    rms_eps: float
    dtype: jnp.dtype
    prefill_chunk: int = 512
    decode: bool = False
    kv_block_size: int = 0
    kv_blocks: int = 0

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim
        window = self.window or None

        def project(name, heads):
            w = _matrix(self, name, (h, heads * d), self.dtype)
            return jnp.dot(x, w, preferred_element_type=jnp.float32) \
                .astype(self.dtype).reshape(b, s, heads, d)

        def turn(t, pos):
            return rope(t, pos, self.rope_theta, inv_freq=self.inv_freq,
                        factor=self.rope_factor)

        q, k, v = project("wq", n), project("wk", kv), project("wv", kv)
        q = rmsnorm(q, _scale(self, "q_norm", d, self.dtype), self.rms_eps)
        k = rmsnorm(k, _scale(self, "k_norm", d, self.dtype), self.rms_eps)
        wo = _matrix(self, "wo", (n * d, h), self.dtype)
        if not self.decode:
            pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            ctx = band_attention(turn(q, pos), turn(k, pos), v, window,
                                 self.prefill_chunk)
        else:
            pool = PagedKV(self, b, s, kv, d, self.dtype,
                           self.kv_block_size, self.kv_blocks, window=window)
            if not pool.initialized:
                ctx = q  # cache creation pass (full-length dummy): shapes
            elif s == 1:
                pos = pool.positions(s)
                ctx = pool.attend(turn(q, pos), turn(k, pos), v, pos)
            else:
                # a prefill from position 0 (module docstring): the
                # cache is written, the call's own K and V attended
                pos = pool.positions(s)
                k = turn(k, pos)
                pool.write(k, v, pos)
                ctx = band_attention(turn(q, pos), k, v, window,
                                     self.prefill_chunk)
        return jnp.dot(ctx.reshape(b, s, n * d), wo,
                       preferred_element_type=jnp.float32).astype(self.dtype)


class MellumMoeLayer(nn.Module):
    attn: dict
    moe: dict
    rms_eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = Norm(self.rms_eps, self.dtype, name="ln_in")(x)
        x = x + KindAttention(name="attn", **self.attn)(h)
        h = Norm(self.rms_eps, self.dtype, name="ln_post")(x)
        return x + SparseExperts(name="moe", **self.moe)(h)


class MellumMoeLM(nn.Module):
    """The whole decoder. ``decode=True`` instances carry the two paged
    caches (init them with ``generation.init_cache``). ``last [B]``
    gives, per row, the ONE position whose logits are wanted (a prefill
    samples from its last real token: the head then runs on that row
    and not on the bucket); None gives every position's."""

    vocab: int
    hidden: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 4
    num_experts: int = 8
    experts_per_tok: int = 2
    moe_hidden: int = 32
    rope_theta: float = 5e5
    rms_eps: float = 1e-6
    max_len: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    #: the kind of every layer, ``"sliding"`` or ``"full"``; cycled if
    #: shorter than ``num_layers``
    layer_types: tuple = ("sliding", "sliding", "sliding", "full")
    sliding_window: int = 16
    #: the full layers' YaRN (``rope_scaling`` of the published config)
    yarn_factor: float = 16.0
    yarn_original_max_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    #: query positions a step of :func:`band_attention`
    prefill_chunk: int = 512
    decode: bool = False
    #: paged KV (decode=True only): serving.DecodeEngine clones the
    #: model with these set; the sliding layers' pool has its own size
    kv_block_size: int = 0
    kv_blocks: int = 0
    kv_window_blocks: int = 0
    #: a multi-position call is a prefill whose head runs on ``last``
    takes_last = True

    def kind(self, layer):
        return self.layer_types[layer % len(self.layer_types)]

    @property
    def cache_kinds(self):
        """What serving.DecodeEngine reads to size its caches: per kind
        of layer that has a cache of its own, beside the full one, the
        window it keeps (paging.CacheKinds)."""
        kinds = {self.kind(i) for i in range(self.num_layers)}
        return {"window": int(self.sliding_window)} \
            if "sliding" in kinds else {}

    @property
    def routed_layers(self):
        """Layers whose router's choices a step answers behind its
        tokens (generation.with_routed)."""
        return self.num_layers

    @nn.compact
    def __call__(self, tokens, last=None):
        if self.decode and not self.kv_block_size:
            raise ValueError(
                "MellumMoeLM decodes through the paged caches only "
                "(kv_block_size > 0); decode=False runs a whole sequence")
        embed = _matrix(self, "embedding", (self.vocab, self.hidden),
                        self.dtype)
        x = embed[tokens]
        yarn = tuple(yarn_inv_freq(
            self.head_dim, self.rope_theta, self.yarn_factor,
            self.yarn_original_max_len, self.yarn_beta_fast,
            self.yarn_beta_slow).tolist())
        moe = dict(
            num_experts=self.num_experts,
            experts_per_tok=self.experts_per_tok,
            moe_hidden=self.moe_hidden, dtype=self.dtype)
        for i in range(self.num_layers):
            sliding = self.kind(i) == "sliding"
            attn = dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta,
                window=int(self.sliding_window) if sliding else 0,
                inv_freq=None if sliding else yarn,
                rope_factor=None if sliding else self.yarn_attention_factor,
                rms_eps=self.rms_eps, dtype=self.dtype,
                prefill_chunk=self.prefill_chunk, decode=self.decode,
                kv_block_size=self.kv_block_size,
                kv_blocks=self.kv_window_blocks if sliding
                else self.kv_blocks)
            x = MellumMoeLayer(attn, moe, self.rms_eps, self.dtype,
                               name="layer_%d" % i)(x)
        x = Norm(self.rms_eps, self.dtype, name="ln_f")(x)
        if last is not None:
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        w = _matrix(self, "head", (self.hidden, self.vocab), self.dtype)
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
