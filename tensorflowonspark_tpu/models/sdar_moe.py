"""SDAR-MoE: a sparse-expert decoder that generates by diffusion over
blocks (``JetLM/SDAR-30B-A3B-Chat``, ``model_type: sdar_moe``).

The second decoder family beside ``models/decoder.py``, written apart
from the cache plumbing: RMSNorm, rotary positions, QK-norm, grouped
K/V heads, a sparse gated feed-forward, no bias anywhere, parameters,
activations and the K/V pool in ``dtype`` (bfloat16), every matrix
product accumulating in float32 and the router's softmax, the norms'
statistics and the logits in float32. One layer over ``x [T, H]``::

    h = rmsnorm(x; w_in)
    q, k, v = h Wq, h Wk, h Wv          [T, heads | kv_heads, head_dim]
    q, k = rmsnorm over head_dim, then rotate-half RoPE at position p
    query at p sees key at p' iff p' <= B*floor(p/B) + B - 1
    x = x + concat(softmax(q k^T / sqrt(head_dim)) v) Wo
    h = rmsnorm(x; w_post)
    x = x + experts(h)                  ops/expert_gmm.expert_layer

then ``rmsnorm`` and the untied head (benchmarks/reference/sdar_moe.py
is the same in plain float32 ``jax.numpy``). The mask is causal BETWEEN
blocks of ``block_len`` positions and full INSIDE one: that is what
lets a block be denoised as a whole.

Two ways in. ``decode=False`` runs a whole sequence with no cache
(tests, and :func:`generate`, the family's cacheless generation).
``decode=True`` is the serving path and is PAGED only: K/V lives in the
block pool of ``models/decoder.PagedKV`` (the one helper every decoder
family's paged branch goes through), a call runs ``s`` positions per
row from the row's cursor, writes their K/V through the block table
and attends through it with the block's last position as what each
query may see. The cursor is the host's (generation._set_paged_leaves
sets it before every call), so a denoising pass is "the same call
again at the same cursor" and a commit is "the same call, then the
host moves the cursor on by ``block_len``": serving.DecodeEngine reads
``block_len``, ``denoise_steps``, ``confidence_threshold`` and
``mask_token_id`` off this module and steps by blocks.

The chip's share of a deployment: ``first_expert``/``expert_count`` say
which experts this instance holds (all of them when ``expert_count`` is
0); routing is over all ``num_experts`` either way.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu.models.decoder import PagedKV
from tensorflowonspark_tpu.ops import expert_gmm


def rmsnorm(x, scale, eps):
    """``x / rms(x) * scale`` over the last axis, statistics in
    float32, result in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta, inv_freq=None, factor=None):
    """Rotate-half RoPE: ``x [B, S, heads, D]`` at ``pos [B, S]``,
    angles in float32. ``inv_freq [D/2]`` replaces the plain
    ``theta ** (-2i / D)`` and ``factor`` multiplies cos and sin (a
    scaled RoPE: models/mellum_moe.py's YaRN layers)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[..., None] * inv          # [B, S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + half * sin).astype(x.dtype)


def block_end(pos, block_len):
    """The last position of the block that holds ``pos``: what a query
    at ``pos`` may see."""
    return (pos // block_len) * block_len + block_len - 1


def _matrix(mod, name, shape, dtype):
    return mod.param(name, nn.initializers.normal(0.02), shape, dtype)


def _scale(mod, name, width, dtype):
    return mod.param(name, nn.initializers.ones, (width,), dtype)


class Norm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        return rmsnorm(x, _scale(self, "scale", x.shape[-1], self.dtype),
                       self.eps)


class BlockAttention(nn.Module):
    """Grouped-query attention, causal between blocks and full inside."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    block_len: int
    rope_theta: float
    rms_eps: float
    dtype: jnp.dtype
    decode: bool = False
    kv_block_size: int = 0
    kv_blocks: int = 0

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        n, kv, d = self.num_heads, self.num_kv_heads, self.head_dim

        def project(name, heads):
            w = _matrix(self, name, (h, heads * d), self.dtype)
            return jnp.dot(x, w, preferred_element_type=jnp.float32) \
                .astype(self.dtype).reshape(b, s, heads, d)

        q, k, v = project("wq", n), project("wk", kv), project("wv", kv)
        q = rmsnorm(q, _scale(self, "q_norm", d, self.dtype), self.rms_eps)
        k = rmsnorm(k, _scale(self, "k_norm", d, self.dtype), self.rms_eps)
        wo = _matrix(self, "wo", (n * d, h), self.dtype)
        if self.decode:
            pool = PagedKV(self, b, s, kv, d, self.dtype,
                           self.kv_block_size, self.kv_blocks)
            if not pool.initialized:
                ctx = q  # cache creation pass (full-length dummy): shapes
            else:
                pos = pool.positions(s)
                ctx = pool.attend(
                    rope(q, pos, self.rope_theta),
                    rope(k, pos, self.rope_theta), v, pos,
                    visible=block_end(pos, self.block_len))
        else:
            pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
            q = rope(q, pos, self.rope_theta)
            k = jnp.repeat(rope(k, pos, self.rope_theta), n // kv, axis=2)
            scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                                preferred_element_type=jnp.float32) \
                * d ** -0.5
            last_seen = block_end(pos, self.block_len)
            seen = pos[:, None, :] <= last_seen[:, :, None]
            scores = jnp.where(seen[:, None], scores,
                               jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
            ctx = jnp.einsum("bnqk,bknd->bqnd", probs,
                             jnp.repeat(v, n // kv, axis=2))
        return jnp.dot(ctx.reshape(b, s, n * d), wo,
                       preferred_element_type=jnp.float32).astype(self.dtype)


class SparseExperts(nn.Module):
    """The held experts' part of the sparse feed-forward; sows the
    experts the router sent each position to (``intermediates``,
    ``expert_ids`` ``[positions, experts_per_tok]``) for whoever counts
    the load: only the caller knows which positions belong to a
    request."""

    num_experts: int
    experts_per_tok: int
    moe_hidden: int
    dtype: jnp.dtype
    first_expert: int = 0
    expert_count: int = 0

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        held = self.expert_count or self.num_experts
        f = self.moe_hidden
        router = _matrix(self, "router", (h, self.num_experts), self.dtype)
        gate = _matrix(self, "gate", (held, h, f), self.dtype)
        up = _matrix(self, "up", (held, h, f), self.dtype)
        down = _matrix(self, "down", (held, f, h), self.dtype)
        y, experts = expert_gmm.expert_layer(
            x.reshape(b * s, h), router, gate, up, down,
            self.experts_per_tok, first=self.first_expert)
        self.sow("intermediates", "expert_ids", experts)
        return y.reshape(b, s, h)


class SdarMoeLayer(nn.Module):
    attn: dict
    moe: dict
    rms_eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = Norm(self.rms_eps, self.dtype, name="ln_in")(x)
        x = x + BlockAttention(name="attn", **self.attn)(h)
        h = Norm(self.rms_eps, self.dtype, name="ln_post")(x)
        return x + SparseExperts(name="moe", **self.moe)(h)


class SdarMoeLM(nn.Module):
    """The whole decoder. ``decode=True`` instances carry the paged
    K/V cache (init it with ``generation.init_cache``); ``head=False``
    returns the final hidden states instead of logits (a prefill
    samples nothing, so it skips ``[bucket, vocab]`` of logits)."""

    vocab: int
    hidden: int = 64
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 2
    num_experts: int = 8
    experts_per_tok: int = 2
    moe_hidden: int = 32
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    max_len: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    #: generation by diffusion over blocks: serving.DecodeEngine and
    #: :func:`generate` read these four
    block_len: int = 4
    denoise_steps: int = 4
    confidence_threshold: float = 0.9
    mask_token_id: int = 0
    #: the chip's share of the experts (0 = all of them)
    first_expert: int = 0
    expert_count: int = 0
    decode: bool = False
    #: paged KV (decode=True only): serving.DecodeEngine clones the
    #: model with these set, as for DecoderLM
    kv_block_size: int = 0
    kv_blocks: int = 0

    @nn.compact
    def __call__(self, tokens, head=True):
        if self.decode and not self.kv_block_size:
            raise ValueError(
                "SdarMoeLM decodes through the paged cache only "
                "(kv_block_size > 0); decode=False runs a whole sequence")
        embed = _matrix(self, "embedding", (self.vocab, self.hidden),
                        self.dtype)
        x = embed[tokens]
        attn = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, block_len=self.block_len,
            rope_theta=self.rope_theta, rms_eps=self.rms_eps,
            dtype=self.dtype, decode=self.decode,
            kv_block_size=self.kv_block_size, kv_blocks=self.kv_blocks)
        moe = dict(
            num_experts=self.num_experts,
            experts_per_tok=self.experts_per_tok,
            moe_hidden=self.moe_hidden, dtype=self.dtype,
            first_expert=self.first_expert, expert_count=self.expert_count)
        for i in range(self.num_layers):
            x = SdarMoeLayer(attn, moe, self.rms_eps, self.dtype,
                             name="layer_%d" % i)(x)
        x = Norm(self.rms_eps, self.dtype, name="ln_f")(x)
        if not head:
            return x
        w = _matrix(self, "head", (self.hidden, self.vocab), self.dtype)
        return jnp.dot(x, w, preferred_element_type=jnp.float32)


def generate(model, params, prompt, max_new_tokens):
    """The family's generation at temperature 0, with no cache: every
    pass runs the whole sequence up to the current block's end. Returns
    ``(prompt + generated, passes)`` where ``passes[i]`` is the pass of
    its block (0 = the first) at which generated token ``i`` was
    unmasked. The prompt's whole blocks are context; the block that
    holds its last ``len(prompt) % block_len`` tokens starts with those
    known and the rest ``MASK``; positions of the last block that lie
    past ``max_new_tokens`` stay ``MASK`` and are never unmasked.
    serving.DecodeEngine serves the same through the paged cache."""
    from tensorflowonspark_tpu import generation

    b, mask_id = model.block_len, model.mask_token_id
    quota = max(1, b // model.denoise_steps)
    seq, end = [int(t) for t in prompt], len(prompt) + int(max_new_tokens)
    padded_len = -(-end // b) * b
    whole = model.clone(decode=False, kv_block_size=0, kv_blocks=0)
    forward = jax.jit(lambda p, t: whole.apply({"params": p}, t))
    passes = []
    while len(seq) < end:
        start = len(seq) - len(seq) % b
        given, live = len(seq) - start, min(b, end - start)
        tok = np.array(seq[start:] + [mask_id] * (b - given), np.int32)
        masked = np.arange(b) >= given
        when = np.full(b, -1, np.int32)
        k = 0
        while masked[:live].any():
            state = np.where(masked, mask_id, tok)
            tokens = np.zeros((1, padded_len), np.int32)
            tokens[0, :start] = seq[:start]
            tokens[0, start:start + b] = state
            logits = np.asarray(forward(params, jnp.asarray(tokens))
                                [0, start:start + live], np.float64)
            top = logits.max(-1)
            conf = 1.0 / np.exp(logits - top[:, None]).sum(-1)
            chosen = generation.unmask(conf, masked[:live], quota,
                                       model.confidence_threshold)
            tok[:live][chosen] = logits.argmax(-1)[chosen]
            when[:live][chosen] = k
            masked[:live][chosen] = False
            k += 1
        seq += tok[given:live].tolist()
        passes += when[given:live].tolist()
    return seq, passes
