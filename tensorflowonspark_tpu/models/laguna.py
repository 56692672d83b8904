"""Laguna: a sparse-expert decoder with a leading dense layer, a shared
expert, per-head attention gates and query head counts that differ by
layer kind (``poolside/Laguna-S-2.1``, ``model_type: laguna``).

The window and full layers of ``models/mellum_moe.py`` (``layer_types``
full, sliding, sliding, sliding, repeated), with what this family adds
to them, each a field of the shared pieces:

- a full layer has ``num_heads`` query heads and a sliding one
  ``sliding_num_heads``, over the same ``num_kv_heads`` K/V heads (so
  the groups differ: 6 and 9 at the published widths);
- a full layer turns the first ``partial_rotary_factor * head_dim``
  dims of each head by YaRN over those dims, cos and sin times
  ``yarn_attention_factor``, and passes the rest through; a sliding
  layer turns the whole head by plain RoPE at ``sliding_rope_theta``
  (``KindAttention.rotary_dim``);
- each head's output is multiplied by ``sigmoid(h W_gate)`` of its own
  before ``Wo`` (``KindAttention.gate_heads``);
- the layers in ``mlp_only_layers`` have a dense gated feed-forward of
  ``dense_hidden`` (``GatedMlp``); every other layer routes over
  ``num_experts`` by a softmax router, normalises its top
  ``experts_per_tok`` weights, scales them by ``routed_scale``, and
  adds a shared expert of ``shared_hidden`` that every position visits
  (``SparseExperts``).

One layer over ``x [T, H]``::

    h = rmsnorm(x; w_in)
    q = h Wq [T, n, D];  k, v = h Wk, h Wv [T, kv, D]   (n by layer kind)
    q, k = rmsnorm over D (QK-norm), then the kind's RoPE
    o_i = softmax(q_i k_g(i)^T / sqrt(D)) v_g(i) * sigmoid(h W_gate)_i
    x = x + concat(o) Wo
    h = rmsnorm(x; w_post)
    x = x + dense(h)                                     (mlp_only_layers)
    x = x + sum_top-k routed_scale * w_e E_e(h) + S(h)   (every other)

then ``rmsnorm`` and the untied head. ``benchmarks/reference/laguna.py``
is the same in plain float32 ``jax.numpy``. Parameters, activations and
the pools in ``dtype`` (bfloat16), every product accumulating in
float32; the router's softmax, the gates, the norms' statistics and the
logits in float32.

The chip's share: ``first_expert``/``expert_count`` say which routed
experts this instance holds (all when ``expert_count`` is 0), as for
``models/sdar_moe.py``; the router keeps its width, the shared expert
and attention are whole. Serving is Mellum's: ``decode=True`` is paged
only, two kinds of cache a slot, a prefill attends its own K and V from
position 0, and the engine refuses prefix sharing, speculation, an int8
pool and kvship tiers by name.
"""

import flax.linen as nn
import jax.numpy as jnp

from tensorflowonspark_tpu.models.mellum_moe import (
    KindAttention, yarn_inv_freq)
from tensorflowonspark_tpu.models.sdar_moe import (
    GatedMlp, Norm, SparseExperts, _matrix)


class LagunaLayer(nn.Module):
    attn: dict
    #: the sparse feed-forward's fields, or None for a dense layer
    moe: dict
    dense_hidden: int
    rms_eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = Norm(self.rms_eps, self.dtype, name="ln_in")(x)
        x = x + KindAttention(name="attn", **self.attn)(h)
        h = Norm(self.rms_eps, self.dtype, name="ln_post")(x)
        if self.moe is None:
            return x + GatedMlp(self.dense_hidden, self.dtype,
                                name="mlp")(h)
        return x + SparseExperts(name="moe", **self.moe)(h)


class LagunaLM(nn.Module):
    """The whole decoder, with ``MellumMoeLM``'s interface: ``decode=True``
    instances carry the two paged caches (init them with
    ``generation.init_cache``); ``last [B]`` gives, per row, the one
    position whose logits are wanted, None every position's."""

    vocab: int
    hidden: int = 64
    #: query heads of a full layer and of a sliding one
    num_heads: int = 4
    sliding_num_heads: int = 6
    num_kv_heads: int = 2
    head_dim: int = 16
    num_layers: int = 5
    num_experts: int = 8
    experts_per_tok: int = 2
    moe_hidden: int = 32
    shared_hidden: int = 32
    routed_scale: float = 2.5
    dense_hidden: int = 64
    #: layers whose feed-forward is dense (``mlp_only_layers``)
    mlp_only_layers: tuple = (0,)
    rms_eps: float = 1e-6
    max_len: int = 512
    dtype: jnp.dtype = jnp.bfloat16
    #: the kind of every layer, ``"sliding"`` or ``"full"``; cycled if
    #: shorter than ``num_layers``
    layer_types: tuple = ("full", "sliding", "sliding", "sliding")
    sliding_window: int = 16
    sliding_rope_theta: float = 1e4
    #: the full layers' RoPE: YaRN over the first
    #: ``partial_rotary_factor * head_dim`` dims of a head
    rope_theta: float = 5e5
    partial_rotary_factor: float = 0.5
    yarn_factor: float = 128.0
    yarn_original_max_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    #: the chip's share of the routed experts (0 = all of them)
    first_expert: int = 0
    expert_count: int = 0
    #: query positions a step of ``band_attention``
    prefill_chunk: int = 512
    decode: bool = False
    #: paged KV (decode=True only), as for ``MellumMoeLM``
    kv_block_size: int = 0
    kv_blocks: int = 0
    kv_window_blocks: int = 0
    #: a multi-position call is a prefill whose head runs on ``last``
    takes_last = True

    def kind(self, layer):
        return self.layer_types[layer % len(self.layer_types)]

    @property
    def rotary_dim(self):
        """Dims of a full layer's head that YaRN turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def cache_kinds(self):
        """Per kind of layer with a cache of its own beside the full one,
        the window it keeps (paging.CacheKinds)."""
        kinds = {self.kind(i) for i in range(self.num_layers)}
        return {"window": int(self.sliding_window)} \
            if "sliding" in kinds else {}

    @property
    def routed_layers(self):
        """Layers whose router's choices a step answers behind its
        tokens (generation.with_routed): the sparse ones."""
        return sum(1 for i in range(self.num_layers)
                   if i not in self.mlp_only_layers)

    @nn.compact
    def __call__(self, tokens, last=None):
        if self.decode and not self.kv_block_size:
            raise ValueError(
                "LagunaLM decodes through the paged caches only "
                "(kv_block_size > 0); decode=False runs a whole sequence")
        embed = _matrix(self, "embedding", (self.vocab, self.hidden),
                        self.dtype)
        x = embed[tokens]
        yarn = tuple(yarn_inv_freq(
            self.rotary_dim, self.rope_theta, self.yarn_factor,
            self.yarn_original_max_len, self.yarn_beta_fast,
            self.yarn_beta_slow).tolist())
        moe = dict(
            num_experts=self.num_experts,
            experts_per_tok=self.experts_per_tok,
            moe_hidden=self.moe_hidden, dtype=self.dtype,
            first_expert=self.first_expert, expert_count=self.expert_count,
            routed_scale=self.routed_scale,
            shared_hidden=self.shared_hidden)
        for i in range(self.num_layers):
            sliding = self.kind(i) == "sliding"
            attn = dict(
                num_heads=self.sliding_num_heads if sliding
                else self.num_heads,
                num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                window=int(self.sliding_window) if sliding else 0,
                rope_theta=self.sliding_rope_theta if sliding
                else self.rope_theta,
                inv_freq=None if sliding else yarn,
                rope_factor=None if sliding else self.yarn_attention_factor,
                rotary_dim=0 if sliding else self.rotary_dim,
                gate_heads=True, rms_eps=self.rms_eps, dtype=self.dtype,
                prefill_chunk=self.prefill_chunk, decode=self.decode,
                kv_block_size=self.kv_block_size,
                kv_blocks=self.kv_window_blocks if sliding
                else self.kv_blocks)
            x = LagunaLayer(attn, None if i in self.mlp_only_layers else moe,
                            self.dense_hidden, self.rms_eps, self.dtype,
                            name="layer_%d" % i)(x)
        x = Norm(self.rms_eps, self.dtype, name="ln_f")(x)
        if last is not None:
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        w = _matrix(self, "head", (self.hidden, self.vocab), self.dtype)
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
